"""Mixture-of-Experts layer with persistent-alltoallv expert dispatch.

Expert-parallel dispatch/combine IS an alltoallv: every step, each data
shard owes each expert shard a different number of tokens.  This layer is
the paper's technique embedded as a first-class framework feature — the
dispatch path is selectable:

  persistent_a2a     (paper) explicit shard_map alltoallv over the expert
                     axis through a *plan-backed persistent dispatch*: at
                     layer build (INIT) a real table-backed
                     ``core.AlltoallvPlan`` is constructed for the frozen
                     capacity-bucketed pattern — via the PlanCache and the
                     on-disk plan store, so a second process warm-starts
                     with zero table bakes and zero autotune bursts — and
                     its *embedded* form (``plan.embed()``) runs the
                     exchange inside the jitted step.  The capacity
                     schedule is static per plan; only the routing overflow
                     mask stays in-graph.  a2a variant: fence / lock /
                     fence_hierarchy / auto (measured at INIT, break-even
                     fit recorded with the decision).
  nonpersistent_a2a  same data path, but re-derives the metadata every call:
                     an extra int32 counts all_to_all plus in-graph
                     displacement/index-map computation (what a generic
                     MPI_Alltoallv-style library call pays per invocation).
  gspmd              scatter into an expert-sharded bucket tensor and let
                     GSPMD insert the collectives (the vendor-collective
                     baseline).

``moe.overlap_chunks > 1`` splits the capacity axis into chunks and
software-pipelines dispatch -> expert FFN -> combine (the in-graph
rendition of ``AlltoallvPlan.start_pipelined``): chunk m's exchange is
issued before chunk m-1's expert compute, so the collectives overlap the
FFN on hardware with async collectives.  Any depth is bit-identical to
depth 1 — the FFN is row-independent and chunks partition the capacity
axis.

Embedded-plan lifecycle: one backing ``AlltoallvPlan`` per (layer
geometry, mesh, chunk geometry), built once at model INIT, shared by every
MoE layer and every step through the process-global PlanCache, and
published to / warm-started from the plan store (``--plan-store`` /
``REPRO_PLANSTORE_DIR``).  The dispatch and combine hops reuse the same
plan (the uniform pattern is symmetric).

Routing is Switch/GShard-style top-k with capacity factor, aux load-balance
loss and router z-loss.  The aux vector also carries the number of experts
with at least one kept assignment (per routing group), which the serving
engine sums into its experts-touched counter.

Each stage runs under a ``jax.named_scope`` (``moe/router``,
``moe/dispatch``, ``moe/expert_ffn``, ``moe/combine``), so a profiler trace
names the device ops of each stage in their ``tf_op`` path.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from jax import shard_map
from repro.configs.base import MoEConfig
from repro.core import variants as core_variants
from repro.kernels import ops as kops
from repro.parallel import wirecodec
from repro.parallel.sharding import (ScopedFactory, active_rules, batch_ways,
                                     cs, current_mesh, normal_init, resolve)

# apply_moe's aux vector: (load-balance loss, router z-loss, experts touched)
N_AUX = 3


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------


def init_moe(f: ScopedFactory, d_model: int, moe: MoEConfig) -> None:
    std = d_model ** -0.5
    f.param("router", (d_model, moe.n_experts), ("embed", None), normal_init(std))
    f.param("w_gate", (moe.n_experts, d_model, moe.d_expert),
            ("experts", "embed", "expert_ff"), normal_init(std))
    f.param("w_up", (moe.n_experts, d_model, moe.d_expert),
            ("experts", "embed", "expert_ff"), normal_init(std))
    f.param("w_down", (moe.n_experts, moe.d_expert, d_model),
            ("experts", "expert_ff", "embed"), normal_init(moe.d_expert ** -0.5))
    if moe.n_shared_experts:
        d_sh = moe.d_expert * moe.n_shared_experts
        f.param("sh_gate", (d_model, d_sh), ("embed", "ff"), normal_init(std))
        f.param("sh_up", (d_model, d_sh), ("embed", "ff"), normal_init(std))
        f.param("sh_down", (d_sh, d_model), ("ff", "embed"), normal_init(d_sh ** -0.5))


# ---------------------------------------------------------------------------
# Persistent dispatch plan (the MPIX_Request analogue for the MoE layer)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MoEDispatchPlan:
    """Frozen INIT-time metadata for one MoE layer's alltoallv.

    Built once at model construction; every train/serve step reuses it.
    With ``a2a`` set (the plan-backed form) the exchange runs through the
    embedded shard-fn of a real table-backed ``core.AlltoallvPlan`` —
    INIT-baked capacity tables, store warm-start, autotuned variant; the
    ``a2a is None`` form keeps the table-free uniform exchange (used by the
    A/B benchmark axis and when no layer geometry is known).  A
    non-persistent call re-derives the dynamic parts in-graph instead.
    """

    n_experts: int
    top_k: int
    ep_size: int            # shards along the expert axis (or axis pair)
    e_local: int            # experts per shard
    tokens_per_shard: int   # padded token chunk per EP shard (T_loc)
    capacity: int           # per-(chunk, expert) slot capacity C
    variant: str            # fence | lock | fence_hierarchy | gspmd-only
    # EP mesh axis: a single name, a linearized (outer, inner) pair (the
    # hierarchical EP factorization), or None (no EP axis in mesh).
    axis: str | tuple[str, str] | None
    hier_axes: tuple[str, str] | None = None
    # dispatch->FFN->combine pipeline depth (chunks of the capacity axis);
    # clamped at build to what the tile-aligned capacity supports.
    overlap_chunks: int = 1
    # Wire codec for the dispatch/combine exchanges (parallel.wirecodec).
    # The MoE path runs the codec FUSED: token rows are encoded before the
    # capacity scatter (so the scatter, the exchange, and the FFN gather
    # all move wire-width rows, with per-row scales inlined as extra
    # lanes), and decode folds into the fused unpack-gather-matmul — the
    # backing plan is built at wire width as a plain byte mover.
    wire_codec: str = "identity"
    # Backing persistent plan (core.AlltoallvPlan) for the chunk-geometry
    # pattern; excluded from identity/hash (it is derived state).
    a2a: Any = dataclasses.field(default=None, compare=False, repr=False)

    @property
    def peer_rows(self) -> int:
        return self.e_local * self.capacity

    @property
    def chunk_capacity(self) -> int:
        return self.capacity // self.overlap_chunks

    @property
    def chunk_peer_rows(self) -> int:
        return self.e_local * self.chunk_capacity

    @property
    def plan_backed(self) -> bool:
        return self.a2a is not None

    @property
    def codec(self) -> str:
        """Wire codec of the dispatch/combine exchanges (fused form: the
        MoE body encodes/decodes; the backing plan just moves the bytes)."""
        return self.wire_codec

    @staticmethod
    def _ep_axes(mesh) -> tuple[str, ...]:
        """EP mesh axes under the active sharding rules: whatever the
        ``experts`` rule maps to (size-1 axes dropped).  Under
        ``DEFAULT_RULES`` that is ``("model",)``; under ``HIER_EP_RULES``
        the ``("pod", "model")`` pair — which is how the hierarchical EP
        launch profile reaches this plan without a test-local mesh."""
        if mesh is None:
            return ()
        rule = active_rules().get("experts") or ()
        rule = (rule,) if isinstance(rule, str) else tuple(rule)
        return tuple(a for a in rule
                     if a in mesh.axis_names and int(mesh.shape[a]) > 1)

    @staticmethod
    def build(moe: MoEConfig, n_tokens: int, mesh, tile: int = 8,
              hier_axes: tuple[str, str] | None = None, *,
              d_model: int | None = None, dtype=None,
              plan_backed: bool = True, store=None, cache=None,
              pack_impl: str = "jnp", autotune_iters: int = 8,
              overlap_chunks: int | None = None,
              hier_leader_perm=None) -> "MoEDispatchPlan":
        """Build the INIT-time dispatch plan for one layer geometry.

        The EP axis (or (outer, inner) pair) is derived from the active
        ``experts`` sharding rule; ``hier_axes=(outer, inner)`` overrides
        it explicitly.  Over a pair, the alltoallv runs linearized and
        ``a2a_variant="fence_hierarchy"`` dispatches through the
        leader-combined exchange — O((EP/g)^2) cross-pod messages per MoE
        layer instead of O(EP^2/g).

        Passing ``d_model`` (the row feature width) makes the dispatch
        *plan-backed*: a real ``AlltoallvPlan`` for the uniform
        chunk-geometry pattern is fetched or built through the PlanCache
        and the plan ``store`` (None = the process default, i.e. the
        launchers' ``--plan-store``), so EP INIT warm-starts across
        processes and ``a2a_variant="auto"`` resolves through the
        measured + stored decision.  ``plan_backed=False`` keeps the
        table-free exchange (the benchmark's A/B axis).
        """
        if hier_axes is not None and mesh is not None \
                and all(a in mesh.axis_names for a in hier_axes):
            axis: str | tuple[str, str] | None = tuple(hier_axes)
            ep = int(np.prod([mesh.shape[a] for a in hier_axes]))
        else:
            hier_axes = None
            ep_axes = MoEDispatchPlan._ep_axes(mesh)
            if len(ep_axes) >= 2:
                hier_axes = tuple(ep_axes[:2])
                axis = hier_axes
                ep = int(np.prod([mesh.shape[a] for a in hier_axes]))
            elif len(ep_axes) == 1:
                axis = ep_axes[0]
                ep = int(mesh.shape[axis])
            else:
                axis = None
                ep = 1
        if moe.n_experts % ep:
            raise ValueError(f"{moe.n_experts} experts not divisible by EP={ep}")
        t_loc = max(-(-n_tokens // ep), tile)
        t_loc = -(-t_loc // tile) * tile
        cap = max(int(math.ceil(t_loc * moe.top_k * moe.capacity_factor
                                / moe.n_experts)), tile)
        cap = -(-cap // tile) * tile

        # Pipeline depth: largest k <= requested that partitions the
        # capacity evenly AND keeps each chunk's per-peer bucket
        # (e_local * cap/k rows) tile-aligned — chunking never changes the
        # capacity schedule, so any depth is bit-identical to depth 1.
        k_req = max(int(overlap_chunks if overlap_chunks is not None
                        else moe.overlap_chunks), 1)
        e_loc = moe.n_experts // ep
        k = max(kk for kk in range(1, min(k_req, cap) + 1)
                if cap % kk == 0 and (e_loc * (cap // kk)) % tile == 0)

        variant = moe.a2a_variant
        if variant == "fence_hierarchy" and hier_axes is None:
            variant = "fence"          # no (outer, inner) pair to group over
        if hier_axes is None:
            hier_leader_perm = None    # leadership needs the grouped exchange
        # Lossy codecs are opt-in via an explicit tolerance, enforced here
        # for every dispatch impl (the fused path bypasses the generic
        # plan-level gate by handing the plan pre-encoded wire rows).
        codec = wirecodec.require(moe.wire_codec, moe.codec_tol)
        a2a = None
        if (plan_backed and d_model is not None and axis is not None
                and ep > 1 and moe.dispatch == "persistent_a2a"):
            from repro.core import api as core_api
            chunk_rows = (moe.n_experts // ep) * (cap // k)
            counts = np.full((ep, ep), chunk_rows, np.int64)
            # Fused wire path: the MoE body encodes token rows before the
            # capacity scatter and decodes inside the FFN gather, so the
            # backing plan is a byte mover at wire width — feature
            # d_model (+ inlined scale lanes), wire dtype, codec=identity.
            wire_d = int(d_model) + codec.scale_lanes
            wire_dt = (codec.wire_dtype if codec.wire_dtype is not None
                       else (dtype if dtype is not None else jnp.float32))
            a2a = core_api.alltoallv_init(
                counts, (wire_d,), wire_dt,
                mesh, axis=axis, variant=variant, tile_rows=tile,
                pack_impl=pack_impl, cache=cache, store=store,
                autotune_iters=autotune_iters, embeddable=True,
                hier_leader_perm=hier_leader_perm)
            variant = a2a.spec.variant   # "auto" resolved to the winner
        elif variant == "auto":
            if (moe.dispatch == "persistent_a2a" and axis is not None
                    and ep > 1):
                raise ValueError(
                    "a2a_variant='auto' needs the plan-backed dispatch "
                    "(build with d_model=... so the autotuner has a "
                    "pattern to measure)")
            # No EP exchange to tune (ep == 1 / gspmd / nonpersistent):
            # resolve to the dense-uniform default instead of failing.
            variant = "fence"
        return MoEDispatchPlan(
            n_experts=moe.n_experts, top_k=moe.top_k, ep_size=ep,
            e_local=moe.n_experts // ep, tokens_per_shard=t_loc,
            capacity=cap, variant=variant, axis=axis,
            hier_axes=hier_axes, overlap_chunks=k,
            wire_codec=moe.wire_codec, a2a=a2a)


# ---------------------------------------------------------------------------
# Routing (top-k with capacity) — shared by all dispatch impls
# ---------------------------------------------------------------------------


def _route(chunk, router_w, valid, k, n_experts, capacity):
    """Returns (slot [T*k], keep [T*k], weight [T*k], counts [E],
    aux (lb, z)).  ``counts`` are the valid assignments per expert before
    the capacity cut; an expert's first assignment always fits (capacity
    >= 1), so ``counts > 0`` marks the experts with a kept assignment."""
    t = chunk.shape[0]
    logits = (chunk @ router_w).astype(jnp.float32)          # [T, E]
    logits = jnp.where(valid[:, None], logits, -1e9)
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, k)                          # [T, k]
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    w = w * valid[:, None]

    flat_e = idx.reshape(-1)                                  # [T*k]
    flat_valid = jnp.repeat(valid, k)
    # rank within expert via stable sort; padding entries sort after every
    # expert (key n_experts) so they never shift a real entry's position
    key = jnp.where(flat_valid, flat_e, n_experts)
    sort_ix = jnp.argsort(key, stable=True)
    sorted_e = key[sort_ix]
    counts = jax.ops.segment_sum(flat_valid.astype(jnp.int32), flat_e,
                                 num_segments=n_experts)
    starts = jnp.concatenate([jnp.zeros(1, jnp.int32),
                              jnp.cumsum(counts).astype(jnp.int32)])
    pos_sorted = jnp.arange(t * k, dtype=jnp.int32) - starts[sorted_e]
    pos = jnp.zeros(t * k, jnp.int32).at[sort_ix].set(pos_sorted)
    keep = (pos < capacity) & flat_valid
    slot = jnp.where(keep, flat_e * capacity + pos, n_experts * capacity)

    # aux losses (Switch): E * sum_e f_e * p_e ; router z-loss
    nvalid = jnp.maximum(valid.sum(), 1.0)
    top1 = idx[:, 0]
    f_e = jax.ops.segment_sum(valid.astype(jnp.float32), top1,
                              num_segments=n_experts) / nvalid
    p_e = (probs * valid[:, None]).sum(0) / nvalid
    lb = n_experts * jnp.sum(f_e * p_e)
    lse = jnp.where(valid, jax.nn.logsumexp(logits, axis=-1), 0.0)
    z = jnp.sum(jnp.square(lse)) / nvalid
    return slot, keep, w.reshape(-1), counts, (lb, z)


def _experts_touched(counts):
    """Experts with at least one kept assignment, as float32 (an aux entry)."""
    return (counts > 0).sum().astype(jnp.float32)


def _scatter_buckets(chunk, slot, keep, k, n_rows, d):
    """Pack dispatch entries into bucket rows (overflow row sliced off)."""
    src = jnp.repeat(chunk, k, axis=0)                        # [T*k, D]
    src = src * keep[:, None].astype(chunk.dtype)
    buckets = jnp.zeros((n_rows + 8, d), chunk.dtype).at[slot].add(src)
    return buckets[:n_rows]


def _expert_ffn(h, w_gate, w_up, w_down):
    """h: [E_loc, C*, D]; weights: [E_loc, D, F], [E_loc, F, D]."""
    g = jnp.einsum("ecd,edf->ecf", h, w_gate.astype(h.dtype))
    u = jnp.einsum("ecd,edf->ecf", h, w_up.astype(h.dtype))
    a = jax.nn.silu(g) * u
    return jnp.einsum("ecf,efd->ecd", a, w_down.astype(h.dtype))


# ---------------------------------------------------------------------------
# Dispatch implementations
# ---------------------------------------------------------------------------


def _shard_exchange_fn(plan: MoEDispatchPlan):
    """The per-chunk exchange callable for the shard body.

    Plan-backed dispatch embeds the backing ``AlltoallvPlan``'s shard fn
    (INIT-baked tables, identity fast path); otherwise the table-free
    uniform exchange runs with the plan's static chunk capacity.  Either
    way the callable maps the bucketed ``[EP * chunk_peer_rows, D]`` layout
    to itself.  Returns None when there is no EP axis (local FFN only).
    """
    if plan.axis is None or plan.ep_size == 1:
        return None
    if plan.a2a is not None:
        return plan.a2a.embed()
    # build() guarantees variant == "fence_hierarchy" implies hier_axes;
    # a hand-built inconsistent plan fails loudly inside the exchange.
    variant = plan.variant
    if isinstance(plan.axis, tuple):
        mesh = current_mesh()
        sizes = tuple(int(mesh.shape[a]) for a in plan.axis)
    else:
        sizes = (plan.ep_size,)
    return lambda b: core_variants.uniform_bucketed_exchange(
        b, variant, plan.axis, plan.chunk_peer_rows, sizes)


def _a2a_shard_body(tokens, router_w, w_gate, w_up, w_down,
                    *, plan: MoEDispatchPlan, persistent: bool,
                    mesh_axes: tuple[str, ...]):
    """Per-shard body under shard_map: route -> pack -> a2a -> ffn -> a2a -> combine.

    tokens: [T_shard, D] this (pod, data) shard's tokens, replicated over the
    model axis; the body first chunks them across the EP axis.

    With ``plan.overlap_chunks > 1`` the capacity axis is split into chunks
    and the three hops are software-pipelined (the in-graph analogue of
    ``AlltoallvPlan.start_pipelined``): chunk m+1's dispatch exchange is
    issued *before* chunk m's expert FFN, so async collectives overlap the
    compute.  The chunks partition the capacity axis and the FFN is
    row-independent, so any depth is bit-identical to depth 1.
    """
    d = tokens.shape[1]
    ep, e_loc, cap = plan.ep_size, plan.e_local, plan.capacity
    t_loc = plan.tokens_per_shard
    axis = plan.axis
    m = jax.lax.axis_index(axis) if axis else 0

    # chunk tokens across the EP axis (pad handled by plan geometry)
    t_have = tokens.shape[0]
    pad = ep * t_loc - t_have
    if pad > 0:
        tokens = jnp.pad(tokens, ((0, pad), (0, 0)))
    chunk = jax.lax.dynamic_slice_in_dim(tokens, m * t_loc, t_loc, axis=0)
    valid = (m * t_loc + jnp.arange(t_loc)) < t_have

    with jax.named_scope("moe/router"):
        slot, keep, w, counts, aux = _route(chunk, router_w, valid,
                                            plan.top_k, plan.n_experts, cap)

    # Fused wire codec: token rows are encoded ONCE, before the capacity
    # scatter, so the scatter, both exchanges, and the FFN gather all move
    # wire-width rows; per-row fp32 scales ride inlined as extra wire
    # lanes (row-preserving hops keep scale r with row r).  Decode folds
    # into the consuming gathers — the decoded fp32 buffer between
    # exchange and FFN never materializes.
    codec = (wirecodec.get(plan.codec) if plan.codec != "identity" else None)
    lanes = codec.scale_lanes if codec is not None else 0
    ctype = chunk.dtype

    def to_wire(rows):
        if codec is None:
            return rows
        wire, sc = codec.encode(rows)
        return wirecodec.inline_rows(wire, sc, lanes) if lanes else wire

    with jax.named_scope("moe/dispatch"):
        wrows = to_wire(chunk)
        dw = wrows.shape[1]
        packed = _scatter_buckets(wrows, slot, keep, plan.top_k,
                                  plan.n_experts * cap, dw)

        if not persistent and axis:
            # Non-persistent: re-exchange metadata every call (per-target
            # counts + in-graph displacement math) — the overhead
            # persistence removes.
            per_peer = counts.reshape(ep, e_loc).sum(-1).astype(jnp.int32)
            rcounts = core_variants.exchange_counts_in_graph(per_peer, axis)
            rdispls = core_variants.displacements_in_graph(rcounts)
            # Fold the (otherwise unused) metadata into the data path so
            # XLA cannot DCE it: scale-by-one keyed on the recomputed
            # displacements.
            one = (rdispls[-1] >= 0).astype(packed.dtype)
            packed = packed * one

    # alltoallv over the EP axis.  Each per-peer chunk bucket is e_local
    # slots of chunk_capacity rows = plan.chunk_peer_rows rows — the uniform
    # capacity the exchange (and the backing plan's pattern) is built on.
    exchange = _shard_exchange_fn(plan)
    n_chunks = plan.overlap_chunks if exchange is not None else 1
    ck = cap // n_chunks
    packed4 = packed.reshape(ep, e_loc, cap, dw)

    def dispatch_chunk(c):
        with jax.named_scope("moe/dispatch"):
            blk = jax.lax.slice_in_dim(packed4, c * ck, (c + 1) * ck, axis=2)
            blk = blk.reshape(ep * e_loc * ck, dw)
            return exchange(blk) if exchange is not None else blk

    # Receive-side regroup table: expert e's FFN rows, in [peer-major,
    # slot-minor] order, addressed directly in the exchanged chunk buffer
    # ([ep, e_loc, ck, D] row-major).  Static per chunk geometry, so the
    # fused unpack-gather-matmul consumes it as a baked constant — the
    # regrouped [e_loc, ep*ck, D] intermediate never materializes.
    regroup_idx = ((np.arange(ep)[:, None] * (e_loc * ck)
                    + np.arange(ck)[None, :])[None]
                   + (np.arange(e_loc) * ck)[:, None, None]
                   ).reshape(e_loc, ep * ck).astype(np.int32)

    def ffn_combine_chunk(xch):
        # Expert FFN straight off the receive buffer: the gate/up matmuls
        # gather expert e's rows via the static regroup table (fused
        # unpack-gather-matmul; Pallas on TPU, jnp gather+einsum off-TPU),
        # then the reverse exchange (all_to_all is an involution on the
        # bucket layout).  Under a codec the receive buffer holds wire
        # rows: the scale lanes split off and dequant rides the gather.
        with jax.named_scope("moe/expert_ffn"):
            if lanes:
                xq, xsc = wirecodec.split_rows(xch, lanes)
            else:
                xq, xsc = xch, None
            g = kops.fused_unpack_matmul(xq, regroup_idx,
                                         w_gate.astype(ctype), scales=xsc)
            u = kops.fused_unpack_matmul(xq, regroup_idx,
                                         w_up.astype(ctype), scales=xsc)
            a = jax.nn.silu(g) * u
            h = jnp.einsum("ecf,efd->ecd", a, w_down.astype(ctype))
        with jax.named_scope("moe/combine"):
            back = h.reshape(e_loc, ep, ck, d).transpose(1, 0, 2, 3)
            back = to_wire(back.reshape(ep * e_loc * ck, d).astype(ctype))
            out = exchange(back) if exchange is not None else back
            return out.reshape(ep, e_loc, ck, dw)

    # Software pipeline: issue chunk c+1's dispatch before chunk c's FFN.
    dispatched = [None] * n_chunks
    dispatched[0] = dispatch_chunk(0)
    outs = []
    for c in range(n_chunks):
        if c + 1 < n_chunks:
            dispatched[c + 1] = dispatch_chunk(c + 1)
        outs.append(ffn_combine_chunk(dispatched[c]))
    returned = (outs[0] if n_chunks == 1
                else jnp.concatenate(outs, axis=2)).reshape(ep * e_loc * cap, dw)

    # combine: gather my entries back out of the returned buckets; under a
    # codec the gather reads narrow wire rows and dequant follows it (on
    # [T*k, D] gathered entries, never on the full bucket buffer).
    with jax.named_scope("moe/combine"):
        padded = jnp.concatenate(
            [returned, jnp.zeros((8, dw), returned.dtype)], axis=0)
        ent = padded[slot]
        comb = keep.astype(ctype) * w.astype(ctype)
        if codec is not None:
            if lanes:
                # Fold the per-row dequant scale into the combine weight:
                # one [T*k] product instead of a second full-width [T*k, D]
                # pass.
                eq, esc = wirecodec.split_rows(ent, lanes)
                ent = eq.astype(ctype)
                comb = comb * esc.reshape(-1).astype(ctype)
            else:
                ent = codec.decode(ent, None, ctype)
        out_entries = ent * comb[:, None]
        y_chunk = out_entries.reshape(t_loc, plan.top_k, d).sum(axis=1)

        if axis:
            # Gather-then-slice is the minimal form here, not an oversight:
            # the slice bound t_have IS host-static (token shapes are
            # trace-time constants), but XLA collectives move uniform
            # per-rank shapes, so any "gather only t_have rows" schedule
            # still ships a full t_loc-row bucket from every rank — an
            # allgatherv plan with ragged tail counts would set capacity =
            # max(counts) = t_loc and re-materialize the same [EP * t_loc]
            # wire buffer inside unpack.  The spill is < EP rows of routing
            # padding, truncated before any consumer sees it.  Semantics
            # pinned by the moe_ragged_tail_combine dist case.
            y = jax.lax.all_gather(y_chunk, axis, axis=0, tiled=True)[:t_have]
        else:
            y = y_chunk[:t_have]
    # Experts touched by this shard's routing group; the pmean below makes
    # it the mean over groups.
    aux_arr = jnp.stack(aux + (_experts_touched(counts),))
    if mesh_axes:
        aux_arr = jax.lax.pmean(aux_arr, axis_name=mesh_axes)
    return y, aux_arr


def _gspmd_dispatch(x2d, nvalid, params, moe: MoEConfig, plan: MoEDispatchPlan):
    """Scatter into an expert-sharded bucket tensor; GSPMD inserts comms."""
    t, d = x2d.shape
    e, cap_total = moe.n_experts, plan.capacity * plan.ep_size
    valid = jnp.arange(t) < nvalid
    with jax.named_scope("moe/router"):
        slot, keep, w, counts, aux = _route(
            x2d, params["router"].astype(x2d.dtype), valid, moe.top_k, e,
            cap_total)
    with jax.named_scope("moe/dispatch"):
        buckets = _scatter_buckets(x2d, slot, keep, moe.top_k, e * cap_total, d)
        buckets = cs(buckets.reshape(e, cap_total, d), "experts", None, "embed")
    with jax.named_scope("moe/expert_ffn"):
        h = _expert_ffn(buckets, params["w_gate"], params["w_up"],
                        params["w_down"])
    with jax.named_scope("moe/combine"):
        # Combine gathers back out of h with *token*-sharded indices.
        h = h.reshape(e * cap_total, d)
        padded = jnp.concatenate([h, jnp.zeros((8, d), h.dtype)], axis=0)
        out = padded[slot] * (keep.astype(h.dtype) * w.astype(h.dtype))[:, None]
        y = out.reshape(t, moe.top_k, d).sum(axis=1)
    return y, jnp.stack(aux + (_experts_touched(counts),))


# ---------------------------------------------------------------------------
# Public layer
# ---------------------------------------------------------------------------


def apply_moe(params: dict, x: jax.Array, moe: MoEConfig,
              plan: Optional[MoEDispatchPlan]) -> tuple[jax.Array, jax.Array]:
    """x: [B, S, D] -> (y [B, S, D], aux [lb_loss, z_loss, experts_touched])."""
    b, s, d = x.shape
    x2d = x.reshape(b * s, d)
    mesh = current_mesh()

    if plan is None:
        # tokens per batch shard under the active batch rules
        dp = batch_ways(b * s, mesh)
        plan = MoEDispatchPlan.build(moe, max((b * s) // dp, 1), mesh,
                                     d_model=d, dtype=x2d.dtype)

    if moe.dispatch == "gspmd" or plan.axis is None or mesh is None:
        y, aux = _gspmd_dispatch(x2d, b * s, params, moe, plan)
    else:
        persistent = moe.dispatch == "persistent_a2a"
        body = partial(_a2a_shard_body, plan=plan, persistent=persistent,
                       mesh_axes=tuple(mesh.axis_names))
        tok_spec = resolve(("batch", None), x2d.shape)  # tokens sharded like batch
        rep = P()
        wspec = resolve(("experts", None, None))
        y, aux = shard_map(
            body, mesh=mesh,
            in_specs=(tok_spec, rep, wspec, wspec, wspec),
            out_specs=(tok_spec, rep),
            check_vma=False,
        )(x2d, params["router"].astype(x2d.dtype),
          params["w_gate"], params["w_up"], params["w_down"])

    y = y.reshape(b, s, d)
    if moe.n_shared_experts:
        g = jax.nn.silu(x @ params["sh_gate"].astype(x.dtype))
        u = x @ params["sh_up"].astype(x.dtype)
        y = y + (g * u) @ params["sh_down"].astype(x.dtype)
    return cs(y, "batch", "seq", "embed"), aux
