"""Ulysses-style sequence-parallel attention via the alltoallv engine.

DeepSpeed-Ulysses (arXiv:2309.14509) computes attention with
sequence-sharded activations by exchanging shards twice per layer:

    [B, S/P, H, d]  --all-to-all-->  [B, S, H/P, d]     (heads out, seq in)
    ... attention over the full sequence on local heads ...
    [B, S, H/P, d]  --all-to-all-->  [B, S/P, H, d]

Both exchanges are *uniform* alltoallvs — the degenerate case of the
paper's engine (every pair moves the same S/P x H/P block), so they route
through ``core.variants.fence_exchange`` with a persistent head-exchange
plan: the bucket geometry is frozen at layer build, per-step work is pure
data movement.  This is the second production consumer of the technique
(DESIGN.md §3); MoE dispatch is the first.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from jax import shard_map
from repro.core import allgatherv_init
from repro.core import variants as core_variants
from repro.parallel.sharding import current_mesh, resolve


@dataclasses.dataclass(frozen=True)
class UlyssesPlan:
    """Persistent head-exchange geometry (INIT-time metadata)."""

    # mesh axis carrying the sequence shards: one name, or a linearized
    # (outer, inner) pair when the sequence spans a grouped (pod, chip) mesh
    axis: str | tuple[str, str]
    p: int             # shards
    n_heads: int
    head_dim: int
    # route the head exchange through the leader-combined hierarchical
    # schedule (uniform-capacity rendition): O((P/g)^2) cross-pod messages
    # per exchange instead of O(P * P/g).  Requires a 2-axis ``axis``.
    hier: bool = False

    @staticmethod
    def build(n_heads: int, head_dim: int, mesh=None, axis="model",
              hier: bool = False):
        mesh = mesh if mesh is not None else current_mesh()
        axes = (axis,) if isinstance(axis, str) else tuple(axis)
        if mesh is not None and all(a in mesh.axis_names for a in axes):
            p = int(np.prod([mesh.shape[a] for a in axes]))
        else:
            p = 1
        if hier and len(axes) != 2:
            raise ValueError("hier head exchange needs axis=(outer, inner)")
        if n_heads % max(p, 1):
            raise ValueError(f"{n_heads} heads not divisible by {p} shards")
        return UlyssesPlan(axis=axis if isinstance(axis, str) else axes,
                           p=p, n_heads=n_heads, head_dim=head_dim, hier=hier)


def _head_exchange(packed: jax.Array, plan: UlyssesPlan) -> jax.Array:
    """Bucketed [P*B, ...] exchange: flat fence epoch, or the
    leader-combined hierarchical schedule on a grouped (outer, inner) mesh
    (bit-identical output; the cross-group message count drops from
    O(P * P_outer) to O(P_outer^2)).  Routed through the shared
    uniform-bucket exchange switch (``core.variants``) — the same table-free
    path MoE dispatch falls back to when it has no backing plan; the
    feature shape here varies per call site (seq x head slices), so there
    is no frozen pattern for a table-backed plan to key on."""
    variant = "fence_hierarchy" if plan.hier else "fence"
    if plan.hier:
        mesh = current_mesh()
        sizes = tuple(int(mesh.shape[a]) for a in plan.axis)
    else:
        sizes = (plan.p,)
    return core_variants.uniform_bucketed_exchange(
        packed, variant, plan.axis, packed.shape[0] // plan.p, sizes)


def _seq_to_heads(x: jax.Array, plan: UlyssesPlan) -> jax.Array:
    """[B, S_loc, H, d] -> [B, S_loc*P, H/P, d] (inside shard_map)."""
    b, s_loc, h, d = x.shape
    p = plan.p
    # bucket j = my sequence shard's slice of head-group j
    packed = x.reshape(b, s_loc, p, h // p, d).transpose(2, 0, 1, 3, 4)
    packed = packed.reshape(p * b, s_loc, h // p, d)
    out = _head_exchange(packed, plan)
    out = out.reshape(p, b, s_loc, h // p, d).transpose(1, 0, 2, 3, 4)
    return out.reshape(b, p * s_loc, h // p, d)


def _heads_to_seq(x: jax.Array, plan: UlyssesPlan) -> jax.Array:
    """[B, S, H/P, d] -> [B, S/P, H, d] (inverse exchange)."""
    b, s, hp, d = x.shape
    p = plan.p
    packed = x.reshape(b, p, s // p, hp, d).transpose(1, 0, 2, 3, 4)
    packed = packed.reshape(p * b, s // p, hp, d)
    out = _head_exchange(packed, plan)
    # recv bucket i = my position block computed with head-group i:
    # [p, b, s_loc, hp, d] -> [b, s_loc, (p, hp)=H, d]
    out = out.reshape(p, b, s // p, hp, d).transpose(1, 2, 0, 3, 4)
    return out.reshape(b, s // p, p * hp, d)


def ulysses_attention(
    q: jax.Array, k: jax.Array, v: jax.Array,   # [B, S, H, d] seq-sharded via mesh
    positions: jax.Array,                        # [B, S]
    plan: UlyssesPlan,
    causal: bool = True,
) -> jax.Array:
    """Attention over sequence-sharded q/k/v (MHA: n_kv == n_heads).

    Outside shard_map: q/k/v arrive sharded on dim 1 over ``plan.axis``;
    inside, each shard holds S/P positions of all H heads, exchanges into
    all S positions of H/P heads, attends, and exchanges back.
    """
    mesh = current_mesh()
    if plan.p == 1 or mesh is None:
        return _attend(q, k, v, positions, causal)

    seq_spec = P(None, plan.axis, None, None)
    pos_spec = P(None, plan.axis)

    # The positions gather rides a persistent allgatherv plan: the pattern
    # (p uniform shards of S/P rows) is frozen by the layer geometry, so the
    # plan warm-starts from the store on every process after the first and
    # the embedded epoch collapses to the bare all_gather when S/P is
    # tile-aligned (the identity fast path).  Signature-keyed through the
    # global PlanCache, so re-traces reuse the same plan.
    b, s = positions.shape
    s_loc = s // plan.p
    gplan = allgatherv_init(
        np.full(plan.p, s_loc, np.int64), (b,), positions.dtype, mesh,
        axis=plan.axis,
        variant="fence_hierarchy" if plan.hier else "fence",
        embeddable=True)
    gather_pos = gplan.embed()

    def body(q_l, k_l, v_l, pos_l):
        qh = _seq_to_heads(q_l, plan)
        kh = _seq_to_heads(k_l, plan)
        vh = _seq_to_heads(v_l, plan)
        own = pos_l.T                                   # [s_loc, B] rows
        if gplan.send_rows != s_loc:
            own = jnp.pad(own, ((0, gplan.send_rows - s_loc), (0, 0)))
        pos_full = gather_pos(own)[:s].T                # [B, S]
        o = _attend(qh, kh, vh, pos_full, causal)
        return _heads_to_seq(o, plan)

    return shard_map(
        body, mesh=mesh,
        in_specs=(seq_spec, seq_spec, seq_spec, pos_spec),
        out_specs=seq_spec, check_vma=False,
    )(q, k, v, positions)


def _attend(q, k, v, positions, causal):
    """Plain softmax attention [B, S, H, d] (fp32 softmax)."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        mask = positions[:, None, :, None] >= positions[:, None, None, :]
        s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)
