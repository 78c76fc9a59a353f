"""Public persistent-alltoallv API: INIT / START / WAIT / FREE.

    plan = alltoallv_init(send_counts, feature_shape, dtype, mesh,
                          axis="x", variant="fence")
    recv = plan.start(sendbuf)     # async launch (epoch open + puts)
    recv = plan.wait(recv)         # epoch close
    ...
    plan.free()

Variant decision tree
---------------------

``variant`` selects the synchronization design for the frozen pattern:

  auto             measure every applicable variant at INIT (interleaved
                   min-of-bursts, ``core.autotune``) and keep the fastest;
                   the decision is cached per ``PatternSignature``.  Use it
                   whenever the pattern is long-lived and you don't already
                   know the answer — the sweep is one-time INIT cost.
  fence            one fused collective epoch.  Best default for dense,
                   roughly uniform patterns; the ``pack_impl="fused"``
                   Pallas kernel removes the packed-intermediate HBM round
                   trip on top.
  lock             (P-1) pairwise rounds with per-round capacities; empty
                   rounds are elided at INIT.  Wins sparse/banded
                   (neighborhood) patterns; loses under receiver skew
                   (the hottest pair gates every round).
  fence_hierarchy  leader-combined three-hop exchange over a grouped
                   ``axis=(outer, inner)`` mesh: cross-group rows stage at
                   distributed leaders, leaders exchange one combined ragged
                   slab per group pair — O((P/g)^2) inter-group messages vs
                   the flat epoch's O(P^2) — and purely-local rows bypass
                   the inter-group hop.  Wins when inter-group links are the
                   bottleneck, rows are large, or flat-fence padding blows
                   up under skew; see ``benchmarks/hierarchy_sweep.py``.
  ragged           ``lax.ragged_all_to_all`` (real-TPU only): no capacity
                   padding at all; XLA:CPU cannot execute it.

For embedding inside a larger shard_map program (MoE dispatch), use
``plan.embed()`` — the traced epoch body driven by the same INIT-baked
tables (compiled into the *host's* executable as constants), with an
identity fast path for uniform bucketed patterns.  ``repro.models.moe``
is the flagship consumer: every ``dispatch="persistent_a2a"`` MoE layer
builds its backing plan through this API at model INIT, so EP dispatch
warm-starts from the plan store like every other pattern.
"""

from __future__ import annotations

from typing import Sequence

import jax
import numpy as np

from ._init_stats import INIT_STATS, capturing_inits, record_init_request
from .plan import AlltoallvPlan, AlltoallvSpec, ExchangePlan, ExchangeSpec, PlanCache
from .window import WindowCache

_GLOBAL_CACHE = PlanCache()


def _resolve_store(store):
    """None -> the process default (``repro.planstore.configure`` /
    ``REPRO_PLANSTORE_DIR``), False -> explicitly disabled, anything else is
    used as-is (duck-typed PlanStore)."""
    if store is False:
        return None
    if store is not None:
        return store
    from repro import planstore

    return planstore.default_store()


def exchange_init(
    collective: str,
    send_counts: np.ndarray,
    feature_shape: Sequence[int],
    dtype,
    mesh: jax.sharding.Mesh,
    axis: str | Sequence[str] = "x",
    variant: str = "fence",
    lock_schedule: str = "ring",
    tile_rows: int | None = None,
    pack_impl: str = "jnp",
    baked_metadata: bool = True,
    cache: PlanCache | None = None,
    autotune_iters: int = 12,
    store=None,
    embeddable: bool = False,
    codec: str = "identity",
    error_tol: float | None = None,
    hier_leader_perm: Sequence[Sequence[int]] | None = None,
) -> ExchangePlan:
    """Collective-agnostic INIT: build (or fetch) a persistent plan.

    ``collective`` names the exchange family (``core.patterns``);
    ``send_counts`` is the family's natural counts form — the ``[P, P]``
    matrix for alltoallv, a ``[P]`` vector (or its expanded matrix) for
    allgatherv / reduce_scatter.  Everything else matches
    ``alltoallv_init``, which (with ``allgatherv_init`` and
    ``reduce_scatter_init``) is a thin wrapper over this function.

    ``variant="auto"`` measures all applicable variants once at INIT and
    returns the fastest plan (see the decision tree above); the chosen
    variant and per-candidate timings land on ``plan.auto_choice``.
    ``baked_metadata=False`` reverts to in-graph index-map recomputation
    (the seed behavior) — kept for A/B benchmarking only.

    ``codec`` selects the wire encoding (``parallel.wirecodec``): the
    exchange then moves quantized rows plus a per-row fp32 scale side
    channel, decode fused into unpack.  Lossy codecs are strictly opt-in:
    a non-identity ``codec`` requires a caller-declared ``error_tol``
    covering the codec's declared relative error bound.  With
    ``variant="auto"`` and an ``error_tol``, the INIT sweep also measures
    the codec arms eligible under the tolerance and persists the winning
    (variant, codec) pair like any auto decision — warm INITs replay it
    with zero re-measurement.

    ``store`` selects the persistent plan store (``repro.planstore``): None
    uses the process default (opt-in via ``planstore.configure`` or
    ``REPRO_PLANSTORE_DIR``), False disables it, or pass a ``PlanStore``.
    With a populated store, INIT warm-starts: baked index tables, hierarchy
    schedules, and ``variant="auto"`` decisions load from disk instead of
    being re-baked/re-measured — observable via ``init_stats()``.

    ``embeddable=True`` declares the plan will be consumed through
    ``plan.embed()``: ``variant="auto"`` then excludes candidates the
    embedded form cannot run (``ragged``, which puts into the plan-owned
    window).
    """
    from . import metadata as md
    from . import patterns
    from ..parallel import wirecodec

    axis_t = (axis,) if isinstance(axis, str) else tuple(axis)
    if codec != "identity":
        wirecodec.require(codec, error_tol)   # unknown names / lossy opt-in
    if variant == "auto":
        # auto resolves to a measured concrete variant below; the spec needs
        # a valid placeholder to pass construction.  fused+2-axis (and a
        # non-identity leader perm) are only valid for the hierarchy, so
        # those combinations placehold there.
        placeholder = ("fence_hierarchy"
                       if len(axis_t) == 2 and (pack_impl == "fused"
                                                or hier_leader_perm)
                       else "fence")
    else:
        placeholder = variant
    spec = ExchangeSpec(
        send_counts=patterns.as_matrix(collective, send_counts),
        feature_shape=tuple(int(s) for s in feature_shape),
        dtype=dtype,
        axis=axis_t,
        variant=placeholder,
        lock_schedule=lock_schedule,
        tile_rows=tile_rows if tile_rows is not None else md.TILE_ROWS,
        pack_impl=pack_impl,
        baked_metadata=baked_metadata,
        codec=codec,
        hier_leader_perm=hier_leader_perm,
        collective=collective,
    )
    if capturing_inits():
        # Everything a prewarm host needs to replay this INIT verbatim
        # (``planstore.prewarm``): the exchange mesh is reconstructible from
        # axis names + sizes alone — the signature never covers other axes.
        record_init_request({
            "collective": collective,
            "send_counts": spec.send_counts.tolist(),
            "feature_shape": list(spec.feature_shape),
            "dtype": str(jax.numpy.dtype(dtype)),
            "axis": list(axis_t),
            "axis_sizes": [int(mesh.shape[a]) for a in axis_t],
            "variant": variant,
            "lock_schedule": spec.lock_schedule,
            "tile_rows": spec.tile_rows,
            "pack_impl": spec.pack_impl,
            "baked_metadata": spec.baked_metadata,
            "embeddable": bool(embeddable),
            "autotune_iters": int(autotune_iters),
            "codec": spec.codec,
            "error_tol": (float(error_tol) if error_tol is not None
                          else None),
            "hier_leader_perm": ([list(r) for r in spec.hier_leader_perm]
                                 if spec.hier_leader_perm else None),
        })
    resolved_store = _resolve_store(store)
    if variant == "auto":
        from .autotune import autotune_variant
        return autotune_variant(spec, mesh, cache or _GLOBAL_CACHE,
                                iters=autotune_iters, store=resolved_store,
                                embeddable=embeddable, error_tol=error_tol)
    return (cache or _GLOBAL_CACHE).get(spec, mesh, store=resolved_store)


def alltoallv_init(
    send_counts: np.ndarray,
    feature_shape: Sequence[int],
    dtype,
    mesh: jax.sharding.Mesh,
    axis: str | Sequence[str] = "x",
    variant: str = "fence",
    lock_schedule: str = "ring",
    tile_rows: int | None = None,
    pack_impl: str = "jnp",
    baked_metadata: bool = True,
    cache: PlanCache | None = None,
    autotune_iters: int = 12,
    store=None,
    embeddable: bool = False,
    codec: str = "identity",
    error_tol: float | None = None,
    hier_leader_perm: Sequence[Sequence[int]] | None = None,
) -> AlltoallvPlan:
    """Persistent alltoallv INIT (see ``exchange_init`` for the contract)."""
    return exchange_init(
        "alltoallv", send_counts, feature_shape, dtype, mesh, axis=axis,
        variant=variant, lock_schedule=lock_schedule, tile_rows=tile_rows,
        pack_impl=pack_impl, baked_metadata=baked_metadata, cache=cache,
        autotune_iters=autotune_iters, store=store, embeddable=embeddable,
        codec=codec, error_tol=error_tol, hier_leader_perm=hier_leader_perm)


def allgatherv_init(
    counts: np.ndarray,
    feature_shape: Sequence[int],
    dtype,
    mesh: jax.sharding.Mesh,
    axis: str | Sequence[str] = "x",
    variant: str = "fence",
    lock_schedule: str = "ring",
    tile_rows: int | None = None,
    cache: PlanCache | None = None,
    autotune_iters: int = 12,
    store=None,
    embeddable: bool = False,
) -> ExchangePlan:
    """Persistent allgatherv INIT: ``counts[i]`` = rows rank i contributes.

    Every rank's epoch input is its own ``[send_rows, F...]`` contribution;
    the output is the ragged concatenation of all contributions (identical
    on every rank).  Variants: fence (one ``all_gather``), lock (ring
    broadcast), fence_hierarchy (nested inner/outer gathers on a grouped
    mesh), or auto.  Uniform tile-aligned counts hit the identity fast path
    — the embedded epoch is the bare ``all_gather``.
    """
    return exchange_init(
        "allgatherv", counts, feature_shape, dtype, mesh, axis=axis,
        variant=variant, lock_schedule=lock_schedule, tile_rows=tile_rows,
        cache=cache, autotune_iters=autotune_iters, store=store,
        embeddable=embeddable)


def reduce_scatter_init(
    counts: np.ndarray,
    feature_shape: Sequence[int],
    dtype,
    mesh: jax.sharding.Mesh,
    axis: str | Sequence[str] = "x",
    variant: str = "fence",
    lock_schedule: str = "ring",
    tile_rows: int | None = None,
    cache: PlanCache | None = None,
    autotune_iters: int = 12,
    store=None,
    embeddable: bool = False,
) -> ExchangePlan:
    """Persistent reduce-scatter INIT: ``counts[j]`` = rows rank j receives.

    Every rank's epoch input is the full per-destination concatenation
    (``sum(counts)`` rows); rank j's output is the element-wise SUM
    (``op="sum"``) of the P blocks destined for it, the reduction fused
    into unpack.  Variants: fence (``all_to_all`` + fused sum), lock
    (ring-accumulate), or auto — the leader-combined hierarchy and wire
    codecs are structurally forbidden (see ``core.patterns``).
    """
    return exchange_init(
        "reduce_scatter", counts, feature_shape, dtype, mesh, axis=axis,
        variant=variant, lock_schedule=lock_schedule, tile_rows=tile_rows,
        cache=cache, autotune_iters=autotune_iters, store=store,
        embeddable=embeddable)


def global_plan_cache() -> PlanCache:
    return _GLOBAL_CACHE


def reset_global_plan_cache() -> None:
    global _GLOBAL_CACHE
    _GLOBAL_CACHE = PlanCache()


def init_stats() -> dict:
    """Snapshot of the process-wide INIT counters (see ``core._init_stats``):
    cold vs warm INITs, table bakes, autotune measurement bursts, and plan-
    store hit/miss/invalid/put counts."""
    return INIT_STATS.as_dict()


def reset_init_stats() -> None:
    INIT_STATS.reset()
