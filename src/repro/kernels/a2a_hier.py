"""Pallas TPU kernel: fused inter-group leader epoch for the hierarchy.

Stage 2 of the leader-combined hierarchical alltoallv
(``core.variants.hierarchy_exchange_combined``): every leader exchanges one
combined ragged slab per (source group, target group) pair it owns.  The
unfused path materializes the packed slab buffer in HBM (gather) and then
``ppermute``s it round by round; this kernel fuses the two:

  * epoch OPEN — a barrier on the collective barrier semaphore with exactly
    the leaders I exchange with this epoch (my put target and my put source
    for every active macro-round), guaranteeing their slab windows are
    re-exposed before any put lands — the ``MPI_Win_fence`` hazard, scoped
    to the leader group instead of all P ranks.
  * per macro-round, the slab's rows are gathered from the stage-1 recv
    buffer (HBM) straight into a VMEM staging tile via the INIT-baked,
    scalar-prefetched index map (padding rows are zeroed in VMEM),
    and put remotely from VMEM.  Two staging tiles alternate so the *local
    gather* of round m overlaps the *inter-leader put* of round m-1 — the
    local work of group pair g hides behind the wire time of group pair g-1.
  * epoch CLOSE — drain my sends, then wait for the slabs my inbound
    leaders put into my window (send/recv DMA semaphores).

Ring addressing: in macro-round ``m`` inner rank ``q`` serves group offset
``d = m * P_inner + q + 1``; ranks whose offset exceeds the ring
(``d >= P_outer``) sit the round out (predicated puts/waits — the predicate
is symmetric between a round's sender and receiver, so no one waits on a
message that was never posted).  Rounds with INIT capacity 0 are elided at
trace time.  Unlike the jnp fallback, the kernel does not drop individual
empty slabs inside an active round (that filtering is rank-asymmetric, and
a one-sided wait would deadlock); their rows are dead weight masked off by
the stage-3 tables.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .a2a_fence import device_id
from .gather_rows import from_words, gather_into, masked_index, to_words


def _hier_leader_kernel(idx_ref, s1_ref, out_ref, scratch, row_sems,
                        send_sem, recv_sem,
                        *, p_outer, p_inner, round_caps, round_offs,
                        outer_axis, inner_axis, mesh_axes):
    o = jax.lax.axis_index(outer_axis)
    q = jax.lax.axis_index(inner_axis)
    active = [m for m, cap in enumerate(round_caps) if cap > 0]

    def ring(m):
        """(valid, dst_outer, src_outer) for macro-round m (traced)."""
        d = m * p_inner + q + 1
        valid = d <= p_outer - 1
        dst = jax.lax.rem(o + d, p_outer)
        dd = jax.lax.rem(d, p_outer)            # keep the subtraction positive
        src = jax.lax.rem(o - dd + p_outer, p_outer)
        return valid, dst, src

    # ---- epoch OPEN: barrier with this epoch's exchange partners ----
    barrier_sem = pltpu.get_barrier_semaphore()
    n_valid = jnp.zeros((), jnp.int32)
    for m in active:
        valid, dst, src = ring(m)

        @pl.when(valid)
        def _():
            pltpu.semaphore_signal(barrier_sem, 1,
                                   device_id=device_id(mesh_axes, outer_axis, dst),
                                   device_id_type=pltpu.DeviceIdType.MESH)
            pltpu.semaphore_signal(barrier_sem, 1,
                                   device_id=device_id(mesh_axes, outer_axis, src),
                                   device_id_type=pltpu.DeviceIdType.MESH)
        n_valid = n_valid + valid.astype(jnp.int32)
    pltpu.semaphore_wait(barrier_sem, 2 * n_valid)

    def gather_slab(m, slot):
        """Slab m's rows: stage-1 recv buffer (HBM) -> scratch[slot]."""
        gather_into(idx_ref, round_offs[m], round_caps[m], s1_ref,
                    scratch.at[slot], row_sems)

    def remote_put(i):
        """Descriptor for active round i's put (recreated for the waits)."""
        m = active[i]
        cap, off = round_caps[m], round_offs[m]
        _, dst, _ = ring(m)
        return pltpu.make_async_remote_copy(
            src_ref=scratch.at[i % 2, pl.ds(0, cap)],
            dst_ref=out_ref.at[pl.ds(off, cap)],
            send_sem=send_sem.at[i % 2], recv_sem=recv_sem,
            device_id=device_id(mesh_axes, outer_axis, dst),
            device_id_type=pltpu.DeviceIdType.MESH)

    # ---- pipelined gather+put rounds: gather m overlaps put m-1 ----
    for i, m in enumerate(active):
        valid, _, _ = ring(m)
        if i >= 2:
            prev_valid, _, _ = ring(active[i - 2])

            @pl.when(prev_valid)
            def _():
                remote_put(i - 2).wait_send()   # same slot: drain before reuse

        @pl.when(valid)
        def _():
            gather_slab(m, i % 2)
            remote_put(i).start()

    # ---- epoch CLOSE: my sends drained, my expected slabs arrived ----
    for i in range(max(0, len(active) - 2), len(active)):
        valid, _, _ = ring(active[i])

        @pl.when(valid)
        def _():
            remote_put(i).wait_send()
    for i in range(len(active)):
        valid, _, _ = ring(active[i])

        @pl.when(valid)
        def _():
            remote_put(i).wait_recv()


def rma_hier_leader_exchange(
    s1_recv: jax.Array,     # per-shard [S1, F] stage-1 recv buffer
    s2_idx: jax.Array,      # [total_s2] host-baked slab gather map
    s2_valid: jax.Array,    # [total_s2] slab padding mask
    *,
    p_outer: int,
    p_inner: int,
    round_caps: tuple[int, ...],
    round_offs: tuple[int, ...],
    total_s2: int,
    outer_axis: str,
    inner_axis: str,
    mesh_axes: tuple[str, ...],
    interpret: bool | object = False,
) -> jax.Array:
    """Fused slab-gather + inter-leader puts; returns the stage-2 recv
    layout ``[total_s2, F]`` (call inside shard_map over ``mesh_axes``)."""
    max_cap = max(cap for cap in round_caps if cap > 0)
    words = to_words(s1_recv)
    w = words.shape[2]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(1,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],     # s1 recv in HBM
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((2, max_cap, 1, w), jnp.uint32),   # staging slabs
            pltpu.SemaphoreType.DMA((max_cap,)),          # per-row gathers
            pltpu.SemaphoreType.DMA((2,)),                # send, per slot
            pltpu.SemaphoreType.DMA,                      # recv
        ],
    )
    out = pl.pallas_call(
        functools.partial(_hier_leader_kernel, p_outer=p_outer,
                          p_inner=p_inner, round_caps=tuple(round_caps),
                          round_offs=tuple(round_offs),
                          outer_axis=outer_axis, inner_axis=inner_axis,
                          mesh_axes=mesh_axes),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((total_s2, 1, w), jnp.uint32),
        compiler_params=pltpu.CompilerParams(collective_id=11),
        interpret=interpret,
    )(masked_index(s2_idx, s2_valid), words)
    return from_words(out.reshape(total_s2, w), s1_recv.dtype)
