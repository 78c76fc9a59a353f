"""Per-shard collective backends for the persistent alltoallv engine.

Each function runs *inside* ``jax.shard_map`` over the communication axis and
implements one synchronization design from the paper, adapted to TPU:

  fence            one fused ``lax.all_to_all`` over the capacity-bucketed
                   layout — a single collective epoch, the analogue of the
                   ``MPI_Win_fence`` pair bracketing all puts.
  lock             (P-1) pairwise ``lax.ppermute`` rounds (ring or XOR
                   pairwise schedule) — per-target epochs; each round's shape
                   is gated by the hottest pair, reproducing the lock-queue
                   serialization the paper measures under skew.
  fence_hierarchy  leader-combined three-hop exchange (Träff-style message
                   combining): an intra-group gather stages every rank's
                   cross-group rows at distributed group leaders, leaders
                   exchange ONE combined ragged slab per (source group,
                   target group) pair — O((P/g)^2) inter-group messages
                   instead of O(P * P/g) — and an intra-group scatter
                   delivers rows to their final ranks.  Purely-local rows
                   bypass the inter-group epoch entirely and enter at the
                   scatter stage, so their staging overlaps the remote puts
                   (the paper's remote-first ordering).  Driven by the
                   INIT-baked ``metadata.HierSchedule`` tables
                   (``hierarchy_exchange_combined``); a table-free
                   uniform-capacity rendition (``hierarchy_exchange``)
                   serves consumers with static bucket layouts (MoE
                   dispatch, Ulysses).
  ragged           ``lax.ragged_all_to_all`` — true variable-size exchange.
                   XLA:TPU only (XLA:CPU has no ragged-all-to-all emitter);
                   kept behind a flag for real-pod deployment and covered by
                   lowering tests.

All backends exchange a *bucketed* send layout ``[P * C, F]`` (or the ragged
layout for ``ragged``) produced by ``pack``; ``unpack`` restores the ragged
recv buffer.  Pack/unpack are the local data-movement hot spots and have
Pallas kernel implementations (``repro.kernels``) selected via ``impl=``.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import metadata as md


# ---------------------------------------------------------------------------
# Local pack / unpack (jnp reference path; Pallas path lives in repro.kernels)
# ---------------------------------------------------------------------------


def pack_rows(x: jax.Array, src_idx: jax.Array, valid: jax.Array) -> jax.Array:
    """Gather ragged send rows into the bucketed layout.

    x:       [S, F...]   ragged send buffer (padded to the SPMD max)
    src_idx: [P * C]     gather map (constant under a persistent plan)
    valid:   [P * C]     padding mask
    """
    out = jnp.take(x, src_idx, axis=0)
    mask = valid.reshape(valid.shape + (1,) * (out.ndim - 1))
    return jnp.where(mask, out, jnp.zeros((), out.dtype))


def unpack_rows(buckets: jax.Array, src_idx: jax.Array, valid: jax.Array) -> jax.Array:
    """Gather bucketed recv layout back into the contiguous ragged buffer."""
    out = jnp.take(buckets, src_idx, axis=0)
    mask = valid.reshape(valid.shape + (1,) * (out.ndim - 1))
    return jnp.where(mask, out, jnp.zeros((), out.dtype))


# ---------------------------------------------------------------------------
# Fence: one fused collective epoch
# ---------------------------------------------------------------------------


def fence_exchange(packed: jax.Array, axis: str) -> jax.Array:
    """[P * C, F] -> [P * C, F]; output bucket j holds rank j's data for us."""
    return jax.lax.all_to_all(packed, axis, split_axis=0, concat_axis=0, tiled=True)


# ---------------------------------------------------------------------------
# Lock: per-target pairwise epochs
# ---------------------------------------------------------------------------


def lock_exchange(
    packed: jax.Array,
    axis: str,
    p: int,
    capacity: int,
    round_capacities: Sequence[int],
    schedule: str = "ring",
) -> jax.Array:
    """(P-1) serialized pairwise rounds; round r moves bucket (i -> i+r).

    ``round_capacities[r]`` lets a *persistent* plan shrink each round to the
    largest message actually exchanged in it — metadata a non-persistent call
    cannot exploit (it must assume the global capacity every round).  A round
    capacity of 0 means the round carries no data on any rank, and the
    persistent schedule *elides it entirely*: no ``ppermute``, no
    ``dynamic_update_slice`` — under sparse patterns the epoch shrinks to the
    active rounds only.  The Python loop is intentional: each round is its
    own collective with its own static permutation, mirroring per-target
    lock epochs.
    """
    i = jax.lax.axis_index(axis)

    # Local bucket: rank i's data for itself never leaves the chip.
    local_blk = jax.lax.dynamic_slice_in_dim(packed, i * capacity, capacity, axis=0)
    result = jnp.zeros_like(packed)
    result = jax.lax.dynamic_update_slice_in_dim(result, local_blk, i * capacity, axis=0)
    for r in range(1, p):
        cap_r = int(round_capacities[r]) if round_capacities is not None else capacity
        cap_r = min(cap_r, capacity)
        if cap_r == 0:
            continue  # sparsity-aware elision: empty round, skip the collective
        if schedule == "ring":
            perm = [(s, (s + r) % p) for s in range(p)]
            tgt_of_src = (i + r) % p          # whom I send to this round
            src_of_tgt = (i - r) % p          # who sends to me this round
        elif schedule == "pairwise":
            if p & (p - 1):
                raise ValueError("pairwise schedule requires power-of-two P")
            perm = [(s, s ^ r) for s in range(p)]
            tgt_of_src = i ^ r
            src_of_tgt = i ^ r
        else:
            raise ValueError(f"unknown lock schedule {schedule!r}")
        # Slice my bucket for this round's target down to the round capacity.
        send = jax.lax.dynamic_slice_in_dim(packed, tgt_of_src * capacity, capacity, 0)
        send = jax.lax.slice_in_dim(send, 0, cap_r, axis=0)
        recv = jax.lax.ppermute(send, axis, perm=perm)
        pad = capacity - cap_r
        if pad:
            recv = jnp.pad(recv, [(0, pad)] + [(0, 0)] * (recv.ndim - 1))
        result = jax.lax.dynamic_update_slice_in_dim(
            result, recv, src_of_tgt * capacity, axis=0
        )
    return result


# ---------------------------------------------------------------------------
# Fence-hierarchy: leader-combined three-hop exchange (message combining)
# ---------------------------------------------------------------------------


def stage2_leader_ppermute(
    s1_recv: jax.Array,
    s2_src: jax.Array,
    s2_valid: jax.Array,
    schedule,                    # metadata.HierSchedule (static)
    axes: tuple[str, str],
) -> jax.Array:
    """Inter-group leader epoch, one ``ppermute`` per active macro-round.

    Each active round moves one combined slab per (source group, target
    group) pair whose cross-traffic is non-empty — the permutation was
    slab-filtered at INIT (``HierSchedule.round_perms``), so the posted
    message count is exactly ``schedule.cross_group_puts`` per epoch.
    Rounds whose capacity is 0 were elided from the schedule entirely.
    """
    s2_send = pack_rows(s1_recv, s2_src, s2_valid)
    s2_recv = jnp.zeros_like(s2_send)
    for m, perm in enumerate(schedule.round_perms):
        cap, off = schedule.s2_caps[m], schedule.s2_offs[m]
        if cap == 0 or not perm:
            continue
        blk = jax.lax.slice_in_dim(s2_send, off, off + cap, axis=0)
        got = jax.lax.ppermute(blk, axes, perm=list(perm))
        s2_recv = jax.lax.dynamic_update_slice_in_dim(s2_recv, got, off, axis=0)
    return s2_recv


def hierarchy_exchange_combined(
    x: jax.Array,                # [send_rows, F...] this shard's ragged buffer
    tables: Sequence[jax.Array],  # this rank's rows: s1_src/valid, s2_src/valid, s3_src/valid
    schedule,                    # metadata.HierSchedule (static host metadata)
    outer_axis: str,
    inner_axis: str,
    stage2_impl=None,            # override for the fused Pallas leader epoch
) -> jax.Array:
    """Leader-combined hierarchical alltoallv body (call inside shard_map).

    Three hops, all driven by INIT-baked index tables:

      1. intra-group gather  (``all_to_all`` over ``inner_axis``): my
         cross-group rows ship to the distributed leaders of their target
         groups.
      2. inter-group leader exchange: one ragged combined slab per group
         pair (``stage2_leader_ppermute``, or the fused gather+put Pallas
         kernel via ``stage2_impl``).
      3. intra-group scatter (``all_to_all`` over ``inner_axis``): received
         slab rows — plus my own group-local rows, which skipped hops 1-2
         and therefore overlap them — are delivered to final ranks.

    Returns the stage-3 recv layout ``[p_inner * s3_cap, F...]``; the
    caller unpacks it with ``schedule``'s unpack tables.  An all-local
    pattern (``schedule.remote_needed == False``) elides hops 1-2 at trace
    time — the epoch is a single intra-group collective.
    """
    s1_src, s1_valid, s2_src, s2_valid, s3_src, s3_valid = tables
    if schedule.remote_needed:
        s1_send = pack_rows(x, s1_src, s1_valid)
        s1_recv = jax.lax.all_to_all(
            s1_send, inner_axis, split_axis=0, concat_axis=0, tiled=True)
        if stage2_impl is not None:
            s2_recv = stage2_impl(s1_recv, s2_src, s2_valid)
        else:
            s2_recv = stage2_leader_ppermute(
                s1_recv, s2_src, s2_valid, schedule, (outer_axis, inner_axis))
        cat = jnp.concatenate([s2_recv, x], axis=0)
    else:
        # No row crosses a group boundary: hops 1-2 vanish (total_s2 == 0,
        # the s3 tables index straight into the send buffer).
        cat = x
    s3_send = pack_rows(cat, s3_src, s3_valid)
    return jax.lax.all_to_all(
        s3_send, inner_axis, split_axis=0, concat_axis=0, tiled=True)


def hierarchy_exchange(
    packed: jax.Array,
    outer_axis: str,
    inner_axis: str,
    p_outer: int,
    p_inner: int,
    capacity: int,
    remote_needed: bool = True,
) -> jax.Array:
    """Leader-combined exchange for *uniform* bucket layouts (no tables).

    The table-free twin of ``hierarchy_exchange_combined`` for consumers
    whose per-peer buckets all share one static capacity (MoE dispatch,
    Ulysses head exchange): every index map reduces to host-static
    reshapes/gathers, so no INIT-baked tables are needed.  Semantically
    identical to a flat ``all_to_all`` over the linearized (outer, inner)
    axis pair on the bucketed layout ``[P * C, F...]``.

    Global rank g = o * P_inner + q (outer-major).  In macro-round ``m``
    inner rank ``q`` is the leader for the group at ring offset
    ``d = m * P_inner + q + 1``: the intra-group gather hands it the whole
    group's buckets for that target group, it exchanges one combined slab
    of ``P_inner^2 * C`` rows — P_outer * (P_outer - 1) inter-group
    messages total instead of P * (P_outer - 1) — and the intra-group
    scatter delivers.  Group-local buckets bypass the inter-group epoch
    (``remote_needed=False`` skips it wholesale, the INIT-time
    ``metadata.hierarchy_is_all_local`` detection).
    """
    f = packed.shape[1:]
    c = capacity
    blocks = packed.reshape(p_outer, p_inner, c, *f)   # [to, ti, C, F]
    o = jax.lax.axis_index(outer_axis)
    n_macro = -(-(p_outer - 1) // p_inner) if p_outer > 1 else 0
    slots = n_macro * p_inner + 1                      # per-ti stage-3 slots

    if remote_needed and n_macro > 0:
        # --- hop 1: intra-group gather (split over the leader dim) -------
        # send[q', m, ti, C] = my bucket for (group (o + d(m, q')) % P_outer,
        # inner ti); slots whose offset exceeds the ring are zero padding.
        d_tbl = np.arange(p_inner)[:, None] * 0 + (
            np.arange(n_macro)[None, :] * p_inner
            + np.arange(p_inner)[:, None] + 1)         # [q', m]
        d_ok = d_tbl < p_outer
        to = (o + jnp.asarray(d_tbl)) % p_outer        # traced [q', m]
        send1 = jnp.take(blocks, to.reshape(-1), axis=0).reshape(
            p_inner, n_macro, p_inner, c, *f)
        send1 = jnp.where(
            jnp.asarray(d_ok).reshape(p_inner, n_macro, *([1] * (send1.ndim - 2))),
            send1, jnp.zeros((), send1.dtype))
        recv1 = jax.lax.all_to_all(
            send1, inner_axis, split_axis=0, concat_axis=0, tiled=True)
        # recv1[sq, m, ti, C] = local rank sq's bucket for my owned groups.

        # --- hop 2: one combined slab per (source group, target group) ---
        q = jax.lax.axis_index(inner_axis)
        lin = o * p_inner + q
        slabs = []
        for m in range(n_macro):
            perm = []
            for oo in range(p_outer):
                for qq in range(p_inner):
                    d = m * p_inner + qq + 1
                    if d < p_outer:
                        perm.append((oo * p_inner + qq,
                                     ((oo + d) % p_outer) * p_inner + qq))
            slab = recv1[:, m]                          # [sq, ti, C, F]
            slabs.append(jax.lax.ppermute(
                slab, (outer_axis, inner_axis), perm=perm))
        recv2 = jnp.stack(slabs, axis=0)                # [m, sq, ti, C, F]

        # --- hop 3: intra-group scatter + local bypass -------------------
        local = jnp.take(blocks, o[None], axis=0)[0]    # [ti, C, F]
        remote_part = recv2.transpose(2, 0, 1, *range(3, recv2.ndim))
        send3 = jnp.concatenate(
            [remote_part.reshape(p_inner, n_macro * p_inner, c, *f),
             local[:, None]], axis=1)                   # [ti, slots, C, F]
    else:
        local = jnp.take(blocks, o[None], axis=0)[0]
        send3 = local[:, None]                          # [ti, 1, C, F]
        slots = 1
    recv3 = jax.lax.all_to_all(
        send3, inner_axis, split_axis=0, concat_axis=0, tiled=True)
    # recv3[q, slot, C, F]: slot m*P_inner+sq = rows from (so(m, q), sq);
    # the last slot = local rank q's own bucket for me.

    # Reorder by source rank.  ds = (o - so) % P_outer selects (leader q,
    # slot); ds == 0 is the local bypass slot.
    flat = recv3.reshape(p_inner * slots, c, *f)
    lin_idx = np.zeros((p_outer, p_inner), np.int64)    # [ds, sq]
    for ds in range(p_outer):
        for sq in range(p_inner):
            if ds == 0:
                lin_idx[ds, sq] = sq * slots + (slots - 1)
            else:
                qq, mm = (ds - 1) % p_inner, (ds - 1) // p_inner
                lin_idx[ds, sq] = qq * slots + mm * p_inner + sq
    by_ds = jnp.take(flat, jnp.asarray(lin_idx.reshape(-1)), axis=0).reshape(
        p_outer, p_inner, c, *f)
    if not (remote_needed and n_macro > 0):
        # Only ds == 0 carries data; every remote slot must read as zeros.
        mask = (jnp.arange(p_outer) == 0).reshape(p_outer, *([1] * (by_ds.ndim - 1)))
        by_ds = jnp.where(mask, by_ds, jnp.zeros((), by_ds.dtype))
    ds_of_so = (o - jnp.arange(p_outer)) % p_outer      # traced [so]
    out = jnp.take(by_ds, ds_of_so, axis=0)             # [so, sq, C, F]
    return out.reshape(p_outer * p_inner * c, *f)


def uniform_bucketed_exchange(
    packed: jax.Array,
    variant: str,
    axis: str | tuple[str, str],
    capacity: int,
    axis_sizes: Sequence[int],
    lock_schedule: str = "ring",
) -> jax.Array:
    """Table-free variant dispatch for *uniform* bucketed layouts.

    One switch shared by every consumer whose per-peer buckets all have one
    static capacity (MoE expert dispatch's table-free fallback, the Ulysses
    head exchange): ``packed`` is ``[P * capacity, F...]``, ``axis`` names
    the exchange axis (or the (outer, inner) pair for a grouped mesh —
    fence/lock then run over the linearized pair), and ``axis_sizes`` are
    the corresponding mesh extents.  The plan-backed path
    (``AlltoallvPlan.embed``) supersedes this where a real plan exists; this
    helper survives for ad-hoc exchanges with no INIT stage to amortize.
    """
    p = int(np.prod(list(axis_sizes)))
    a2a_axis = axis if isinstance(axis, str) else tuple(axis)
    if variant == "lock":
        return lock_exchange(packed, a2a_axis, p, capacity, None, lock_schedule)
    if variant == "fence_hierarchy":
        if isinstance(axis, str) or len(axis) != 2:
            raise ValueError("fence_hierarchy needs axis=(outer, inner)")
        return hierarchy_exchange(packed, axis[0], axis[1],
                                  int(axis_sizes[0]), int(axis_sizes[1]),
                                  capacity)
    if variant != "fence":
        raise ValueError(f"unknown uniform exchange variant {variant!r}")
    return fence_exchange(packed, a2a_axis)


# ---------------------------------------------------------------------------
# Ragged: true variable-size exchange (TPU execution only)
# ---------------------------------------------------------------------------


def ragged_exchange(
    x: jax.Array,
    window: jax.Array,
    input_offsets: jax.Array,
    send_sizes: jax.Array,
    output_offsets: jax.Array,
    recv_sizes: jax.Array,
    axis: str,
) -> jax.Array:
    """Direct ``ragged_all_to_all`` into the persistent window buffer.

    ``output_offsets`` are the paper's ``put_displs``: where my block lands in
    each target's window.  The window operand is donated by the plan, so the
    same device buffer is reused epoch over epoch (window reuse).
    """
    return jax.lax.ragged_all_to_all(
        x, window, input_offsets, send_sizes, output_offsets, recv_sizes, axis_name=axis
    )


# ---------------------------------------------------------------------------
# In-graph metadata exchange (the *non-persistent* path pays this per call).
# Persistent plans no longer call these twins: their index maps are baked on
# host at INIT (metadata.baked_index_tables) and embedded as constants, so
# these exist solely so baseline.py honestly models the per-call cost.
# ---------------------------------------------------------------------------


def exchange_counts_in_graph(counts_row: jax.Array, axis: str) -> jax.Array:
    """One int32 all_to_all: my send-count row -> my recv-count row.

    The INIT-time ``MPI_Alltoall(sendcounts)``.  Persistent plans run this
    once on host; the baseline re-runs it (plus all derived offset math) every
    iteration.
    """
    return jax.lax.all_to_all(counts_row, axis, split_axis=0, concat_axis=0, tiled=True)


def pack_index_map_in_graph(
    counts_row: jax.Array, displs_row: jax.Array, p: int, capacity: int
) -> tuple[jax.Array, jax.Array]:
    """Traced twin of ``metadata.pack_index_map`` (per-call metadata work)."""
    t = jnp.arange(p * capacity, dtype=jnp.int32)
    peer = t // capacity
    k = t % capacity
    cnt = counts_row[peer]
    valid = k < cnt
    src = displs_row[peer] + jnp.minimum(k, jnp.maximum(cnt - 1, 0))
    return jnp.where(valid, src, 0).astype(jnp.int32), valid


def unpack_index_map_in_graph(
    recv_counts_row: jax.Array, rdispls_row: jax.Array, p: int, capacity: int, out_rows: int
) -> tuple[jax.Array, jax.Array]:
    """Traced twin of ``metadata.unpack_index_map``."""
    m = jnp.arange(out_rows, dtype=jnp.int32)
    edges = jnp.concatenate(
        [rdispls_row, (rdispls_row[-1] + recv_counts_row[-1])[None]]
    )
    peer = jnp.clip(jnp.searchsorted(edges, m, side="right") - 1, 0, p - 1)
    within = m - rdispls_row[peer]
    valid = within < recv_counts_row[peer]
    src = peer * capacity + jnp.minimum(within, capacity - 1)
    return jnp.where(valid, src, 0).astype(jnp.int32), valid


def displacements_in_graph(counts_row: jax.Array) -> jax.Array:
    z = jnp.zeros((1,), counts_row.dtype)
    return jnp.concatenate([z, jnp.cumsum(counts_row)[:-1]])
