"""Gradient utilities: global-norm clipping, microbatch accumulation, and
the compressed data-parallel gradient sync (int8 + error feedback)."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def global_norm(tree) -> jax.Array:
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree.leaves(tree)))


def clip_by_global_norm(grads, max_norm: float):
    gn = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(gn, 1e-12))
    return jax.tree.map(lambda g: (g.astype(jnp.float32) * scale).astype(g.dtype),
                        grads), gn


def compressed_sync(mesh, specs, dp_axes):
    """Build the int8+error-feedback data-parallel gradient sync.

    Returns ``sync(grads, err) -> (grads, new_err)``: a shard_map over the
    full mesh at ``specs`` (the TP-only PartitionSpecs — every leaf is
    replicated across the data axes there) running
    ``compression.compressed_psum_tree`` over the DP axes.  Each DP replica
    quantizes its (identical) gradient shard to the int8 grid with the
    carried error-feedback residual folded in, the quantized payload is
    mean-reduced over ``dp_axes``, and the fresh residual comes back for
    the optimizer state to carry to the next step.  Replicas quantize
    identical inputs, so the residual stays DP-replicated by construction
    and the sync is exactly quantize-with-EF in value — what changes is
    what crosses the DP wire.

    ``dp_axes`` not present in the mesh (or size 1) drop out; with no DP
    axis left the psum degenerates to the identity and the sync is a pure
    local quantize+EF pass, so the state threading is identical either way.
    """
    from jax import shard_map
    from repro.parallel import compression

    dp = tuple(a for a in dp_axes
               if a in mesh.axis_names and int(mesh.shape[a]) > 1)

    def body(g, e):
        return compression.compressed_psum_tree(g, dp, e)

    return shard_map(body, mesh=mesh, in_specs=(specs, specs),
                     out_specs=(specs, specs), check_vma=False)


def persistent_rs_sync(mesh, specs, dp_axes, error_feedback: bool = False):
    """Plan-backed DP gradient sync (``grad_sync="persistent_rs"``).

    Same contract and sharding story as ``compressed_sync`` — a shard_map
    over the full mesh at the TP-only ``specs`` (every leaf DP-replicated)
    — but the DP wire is the persistent-plan engine instead of a bare
    psum: the leaf shards flatten into one fp32 row buffer, a persistent
    reduce-scatter plan sums it across the DP replicas (counts frozen by
    the parameter geometry, so INIT warm-starts from the plan store and a
    second process pays zero bakes), the matching allgatherv plan — the
    identity fast path, counts are uniform tile-aligned — gathers the
    1/P shard back, and the mean follows.  That is the Rabenseifner
    RS+AG decomposition of the all-reduce, riding the same baked plans
    MoE dispatch and Ulysses use.  Replicas hold identical grads
    (autodiff already mean-reduced the loss), so the sync is
    value-preserving — what changes is what crosses the wire.

    ``error_feedback=True`` composes with the int8 path: each leaf is
    quantized with the carried residual folded in (``compression``'s EF
    arithmetic) and the *dequantized* payload rides the plan wire.
    Returns ``sync(grads, err) -> (grads, new_err)`` with error feedback,
    ``sync(grads) -> grads`` without.

    ``dp_axes`` absent from the mesh (or size 1) drop out; with none left
    the exchange is skipped and the sync degenerates to the same local
    quantize+EF pass (or the identity) as ``compressed_sync``.
    """
    import numpy as np

    from jax import shard_map
    from repro.core import allgatherv_init, metadata as md, reduce_scatter_init
    from repro.parallel import compression

    dp = tuple(a for a in dp_axes
               if a in mesh.axis_names and int(mesh.shape[a]) > 1)
    n_dp = 1
    for a in dp:
        n_dp *= int(mesh.shape[a])
    axis = dp[0] if len(dp) == 1 else dp

    def _wire(leaves):
        """flatten -> plan-RS -> plan-AG -> mean -> unflatten (fp32)."""
        flat = (jnp.concatenate([l.reshape(-1) for l in leaves])
                if len(leaves) > 1 else leaves[0].reshape(-1))
        n = flat.shape[0]
        if n_dp > 1 and n:
            cap = md.round_up(-(-n // n_dp), md.TILE_ROWS)
            counts = np.full(n_dp, cap, np.int64)
            rs = reduce_scatter_init(counts, (), jnp.float32, mesh,
                                     axis=axis, embeddable=True)
            ag = allgatherv_init(counts, (), jnp.float32, mesh,
                                 axis=axis, embeddable=True)
            padded = jnp.zeros((n_dp * cap,), jnp.float32).at[:n].set(flat)
            shard = rs.embed()(padded)
            flat = ag.embed()(shard)[:n] / n_dp
        out, off = [], 0
        for l in leaves:
            out.append(jax.lax.dynamic_slice_in_dim(
                flat, off, l.size).reshape(l.shape))
            off += l.size
        return out

    if error_feedback:
        def body(g, e):
            leaves, treedef = jax.tree.flatten(g)
            wire, new_err = [], []
            for x, err in zip(leaves, jax.tree.leaves(e)):
                carry = x.astype(jnp.float32) + err.astype(jnp.float32)
                q, scale = compression.quantize_int8(carry)
                deq = compression.dequantize_int8(q, scale)
                wire.append(deq)
                new_err.append((carry - deq).astype(err.dtype))
            synced = _wire(wire)
            out = [s.astype(x.dtype) for s, x in zip(synced, leaves)]
            return (jax.tree.unflatten(treedef, out),
                    jax.tree.unflatten(treedef, new_err))

        return shard_map(body, mesh=mesh, in_specs=(specs, specs),
                         out_specs=(specs, specs), check_vma=False)

    def body(g):
        leaves, treedef = jax.tree.flatten(g)
        synced = _wire([l.astype(jnp.float32) for l in leaves])
        out = [s.astype(l.dtype) for s, l in zip(synced, leaves)]
        return jax.tree.unflatten(treedef, out)

    return shard_map(body, mesh=mesh, in_specs=(specs,), out_specs=specs,
                     check_vma=False)


def accumulate_grads(loss_fn, params, batch, n_micro: int, constrain=None):
    """Split the batch into n_micro slices along dim 0 and scan-accumulate.

    loss_fn(params, microbatch) -> (loss, metrics).  Returns mean-reduced
    (loss, metrics, grads).  ``constrain`` (tree -> tree) applies sharding
    constraints to each microbatch's grads — passing the ZeRO shardings here
    makes GSPMD reduce-scatter every micro-step instead of holding
    model-sharded fp32 grads (ZeRO-2).
    """
    constrain = constrain or (lambda g: g)
    if n_micro <= 1:
        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch)
        return loss, metrics, constrain(grads)

    def reshape(x):
        b = x.shape[0]
        assert b % n_micro == 0, (b, n_micro)
        return x.reshape(n_micro, b // n_micro, *x.shape[1:])

    micro = jax.tree.map(reshape, batch)

    def body(carry, mb):
        acc_loss, acc_metrics, acc_grads = carry
        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, mb)
        acc = constrain(jax.tree.map(jnp.add, acc_grads, constrain(grads)))
        return (acc_loss + loss,
                jax.tree.map(jnp.add, acc_metrics, metrics), acc), None

    (loss0, metrics0), grads0 = jax.value_and_grad(loss_fn, has_aux=True)(
        params, jax.tree.map(lambda x: x[0], micro))
    carry0 = (loss0, metrics0,
              constrain(jax.tree.map(lambda g: g.astype(jnp.float32), grads0)))
    rest = jax.tree.map(lambda x: x[1:], micro)
    (loss, metrics, grads), _ = jax.lax.scan(body, carry0, rest)
    inv = 1.0 / n_micro
    return (loss * inv,
            jax.tree.map(lambda x: x * inv, metrics),
            jax.tree.map(lambda g: g * inv, grads))
