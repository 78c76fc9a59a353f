"""Collective helpers: hierarchical (pod-aware) gradient reduction.

On a multi-pod mesh the flat all-reduce over (pod, data) pays the slow
inter-pod links for the full payload.  The hierarchical schedule —
reduce-scatter within the pod, all-reduce the 1/P_data shard across pods,
all-gather within the pod — moves only payload/P_data bytes over the
inter-pod links, the same locality idea as the paper's fence-hierarchy
variant (remote stage carries aggregated blocks).

With a ``mesh`` the RS+AG pair rides persistent plans from the exchange
engine (``core.patterns``): one uniform counts vector is the single source
of the shard geometry for both sides, the plans warm-start from the plan
store, and the pair handles row counts the raw ``psum_scatter`` path could
not (non-divisible rows pad to the tile capacity; zero rows are sum-inert).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np



def plan_rs_ag_pair(rows: int, feature_shape, dtype, inner_axis: str, mesh):
    """The promoted ``psum_scatter``+``all_gather`` pair as persistent plans.

    Returns ``(rs_plan, ag_plan, capacity)``: a reduce-scatter plan and its
    matching allgatherv plan over ``inner_axis``, both built from ONE
    uniform counts vector (``capacity`` rows per rank, ``rows`` padded up
    to the tile grid) — the shard geometry the two raw collectives used to
    derive independently.  Both plans are embeddable and signature-keyed
    through the global ``PlanCache``, so they warm-start from the plan
    store like every other consumer of the engine.
    """
    from repro.core import allgatherv_init, metadata as md, reduce_scatter_init

    inner = int(mesh.shape[inner_axis])
    cap = max(md.round_up(-(-rows // inner), md.TILE_ROWS), md.TILE_ROWS)
    counts = np.full(inner, cap, np.int64)
    rs = reduce_scatter_init(counts, tuple(feature_shape), dtype, mesh,
                             axis=inner_axis, embeddable=True)
    ag = allgatherv_init(counts, tuple(feature_shape), dtype, mesh,
                         axis=inner_axis, embeddable=True)
    return rs, ag, cap


def hierarchical_psum_mean(x: jax.Array, inner_axis: str, outer_axis: str,
                           scatter_dim: int = 0, mesh=None) -> jax.Array:
    """Mean-reduce over (inner, outer) with pod-aware scheduling.

    Call inside shard_map.  With ``mesh`` the inner RS/AG pair rides the
    persistent plans of ``plan_rs_ag_pair`` (any row count; padding is
    sum-inert).  Without it the raw ``psum_scatter`` path requires
    ``x.shape[scatter_dim]`` divisible by the inner axis size and falls
    back to a flat psum otherwise.
    """
    inner = jax.lax.axis_size(inner_axis)
    outer = jax.lax.axis_size(outer_axis)
    n = inner * outer
    if mesh is not None and inner > 1:
        xt = jnp.moveaxis(x, scatter_dim, 0)
        rows = xt.shape[0]
        rs, ag, cap = plan_rs_ag_pair(rows, xt.shape[1:], x.dtype,
                                      inner_axis, mesh)
        pad = inner * cap - rows
        if pad:
            xt = jnp.concatenate(
                [xt, jnp.zeros((pad,) + xt.shape[1:], xt.dtype)])
        # 1. persistent reduce-scatter within the pod
        shard = rs.embed()(xt)
        # 2. all-reduce the shard across pods (1/inner of the bytes)
        shard = jax.lax.psum(shard, outer_axis)
        # 3. persistent all-gather within the pod
        full = ag.embed()(shard)[:rows]
        return jnp.moveaxis(full, 0, scatter_dim) / n
    if x.shape[scatter_dim] % inner:
        return jax.lax.psum(x, (inner_axis, outer_axis)) / n
    # 1. reduce-scatter within the pod
    shard = jax.lax.psum_scatter(x, inner_axis, scatter_dimension=scatter_dim,
                                 tiled=True)
    # 2. all-reduce the shard across pods (1/inner of the bytes)
    shard = jax.lax.psum(shard, outer_axis)
    # 3. all-gather within the pod
    full = jax.lax.all_gather(shard, inner_axis, axis=scatter_dim, tiled=True)
    return full / n


def flat_psum_mean(x: jax.Array, axes) -> jax.Array:
    n = 1
    for a in (axes if isinstance(axes, (tuple, list)) else (axes,)):
        n *= jax.lax.axis_size(a)
    return jax.lax.psum(x, axes) / n
