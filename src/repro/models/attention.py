"""GQA attention with RoPE, KV cache, cross-attention, and a flash-style
chunked path for long sequences.

TP sharding: heads over the model axis.  Decode with a sequence-sharded KV
cache (long-context SP) needs no manual ring: scores over the sharded key
axis get their softmax reductions from GSPMD.

The flash path is a pure-JAX online-softmax over key chunks inside a scan
over query chunks — O(q_chunk * k_chunk) live scores instead of O(S^2) —
selected automatically above ``FLASH_THRESHOLD`` keys.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.parallel.sharding import (ScopedFactory, cs, normal_init,
                                     pin_default_layout)

FLASH_THRESHOLD = 4096
Q_CHUNK = 512
K_CHUNK = 1024
NEG_INF = -1e30


def init_attention(f: ScopedFactory, d_model: int, n_heads: int, n_kv: int,
                   head_dim: int, qk_norm: bool = False) -> None:
    std = d_model ** -0.5
    f.param("wq", (d_model, n_heads, head_dim), ("embed", "heads", "head_dim"),
            normal_init(std))
    f.param("wk", (d_model, n_kv, head_dim), ("embed", "kv_heads", "head_dim"),
            normal_init(std))
    f.param("wv", (d_model, n_kv, head_dim), ("embed", "kv_heads", "head_dim"),
            normal_init(std))
    f.param("wo", (n_heads, head_dim, d_model), ("heads", "head_dim", "embed"),
            normal_init((n_heads * head_dim) ** -0.5))
    if qk_norm:
        from repro.parallel.sharding import ones_init
        f.param("q_norm", (head_dim,), ("head_dim",), ones_init())
        f.param("k_norm", (head_dim,), ("head_dim",), ones_init())


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [B, S, N, dh]; positions: [S] or [B, S]."""
    dh = x.shape[-1]
    half = dh // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    if positions.ndim == 1:
        positions = positions[None, :]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, S, half]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def _rms(x, scale, eps=1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x.astype(jnp.float32) * jax.lax.rsqrt(var + eps) * scale).astype(x.dtype)


def _direct_attention(q, k, v, mask, scale):
    """q: [B,Sq,N,G,dh]  k,v: [B,Sk,N,dh]  mask: [B,Sq,Sk] or None."""
    s = jnp.einsum("bqngd,bknd->bngqk", q, k).astype(jnp.float32) * scale
    if mask is not None:
        s = jnp.where(mask[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bngqk,bknd->bqngd", p.astype(v.dtype), v)
    return o


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _flash_attention(q, k, v, q_pos, k_pos, causal, scale):
    """FlashAttention-2-style chunked attention with a tile-recompute VJP.

    q: [B,Sq,N,G,dh]; k,v: [B,Sk,N,dh]; *_pos: [B, S*] absolute positions.
    Residuals are only (q, k, v, o, L): the backward recomputes each tile's
    probabilities instead of saving the O(Sq*Sk) matrices a scan-autodiff
    would stash.
    """
    o, _ = _flash_fwd_impl(q, k, v, q_pos, k_pos, causal, scale)
    return o


def _flash_fwd_impl(q, k, v, q_pos, k_pos, causal, scale):
    b, sq, n, g, dh = q.shape
    sk = k.shape[1]
    qc = min(Q_CHUNK, sq)
    kc = min(K_CHUNK, sk)
    nq, nk = sq // qc, sk // kc
    qr = q.reshape(b, nq, qc, n, g, dh).transpose(1, 0, 2, 3, 4, 5)
    qpr = q_pos.reshape(b, nq, qc).transpose(1, 0, 2)
    kr = k.reshape(b, nk, kc, n, dh).transpose(1, 0, 2, 3, 4)
    vr = v.reshape(b, nk, kc, n, dh).transpose(1, 0, 2, 3, 4)
    kpr = k_pos.reshape(b, nk, kc).transpose(1, 0, 2)

    def q_step(_, qi):
        qb, qp = qi  # [B,qc,N,G,dh], [B,qc]

        def k_step(carry, ki):
            m, l, acc = carry
            kb, vb, kp = ki
            s = jnp.einsum("bqngd,bknd->bngqk", qb, kb).astype(jnp.float32) * scale
            if causal:
                msk = qp[:, None, None, :, None] >= kp[:, None, None, None, :]
                s = jnp.where(msk, s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = l * alpha + p.sum(axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bngqk,bknd->bngqd", p, vb.astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, n, g, qc), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, n, g, qc), jnp.float32)
        a0 = jnp.zeros((b, n, g, qc, dh), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(k_step, (m0, l0, a0), (kr, vr, kpr))
        l_safe = jnp.maximum(l, 1e-30)
        o = acc / l_safe[..., None]
        lse = m + jnp.log(l_safe)                    # [B,N,G,qc]
        return None, (o.transpose(0, 3, 1, 2, 4), lse)

    _, (o, lse) = jax.lax.scan(q_step, None, (qr, qpr))
    o = o.transpose(1, 0, 2, 3, 4, 5).reshape(b, sq, n, g, dh)
    lse = lse.transpose(1, 2, 3, 0, 4).reshape(b, n, g, sq)
    return o.astype(q.dtype), lse


def _flash_fwd(q, k, v, q_pos, k_pos, causal, scale):
    o, lse = _flash_fwd_impl(q, k, v, q_pos, k_pos, causal, scale)
    return o, (q, k, v, q_pos, k_pos, o, lse)


def _flash_bwd(causal, scale, res, do):
    q, k, v, q_pos, k_pos, o, lse = res
    b, sq, n, g, dh = q.shape
    sk = k.shape[1]
    qc = min(Q_CHUNK, sq)
    kc = min(K_CHUNK, sk)
    nq, nk = sq // qc, sk // kc

    # D = rowsum(do * o)  [B,N,G,Sq]
    dsum = jnp.einsum("bqngd,bqngd->bngq", do.astype(jnp.float32),
                      o.astype(jnp.float32))

    qr = q.reshape(b, nq, qc, n, g, dh).transpose(1, 0, 2, 3, 4, 5)
    dor = do.reshape(b, nq, qc, n, g, dh).transpose(1, 0, 2, 3, 4, 5)
    qpr = q_pos.reshape(b, nq, qc).transpose(1, 0, 2)
    lser = lse.reshape(b, n, g, nq, qc).transpose(3, 0, 1, 2, 4)
    dsr = dsum.reshape(b, n, g, nq, qc).transpose(3, 0, 1, 2, 4)
    kr = k.reshape(b, nk, kc, n, dh).transpose(1, 0, 2, 3, 4)
    vr = v.reshape(b, nk, kc, n, dh).transpose(1, 0, 2, 3, 4)
    kpr = k_pos.reshape(b, nk, kc).transpose(1, 0, 2)

    def q_step(carry, qi):
        dk_acc, dv_acc = carry                        # [B,Sk,N,dh] fp32
        qb, dob, qp, lseb, dsb = qi

        def k_step(cum, ki):
            dq_acc = cum                              # [B,qc,N,G,dh]
            kb, vb, kp = ki
            s = jnp.einsum("bqngd,bknd->bngqk", qb, kb).astype(jnp.float32) * scale
            if causal:
                msk = qp[:, None, None, :, None] >= kp[:, None, None, None, :]
                s = jnp.where(msk, s, NEG_INF)
            p = jnp.exp(s - lseb[..., None])          # [B,N,G,qc,kc]
            do32 = dob.astype(jnp.float32)
            dv_t = jnp.einsum("bngqk,bqngd->bknd", p, do32)
            dp = jnp.einsum("bqngd,bknd->bngqk", do32, vb.astype(jnp.float32))
            ds = p * (dp - dsb[..., None]) * scale
            dq_t = jnp.einsum("bngqk,bknd->bqngd", ds, kb.astype(jnp.float32))
            dk_t = jnp.einsum("bngqk,bqngd->bknd", ds, qb.astype(jnp.float32))
            return dq_acc + dq_t, (dk_t, dv_t)

        dq0 = jnp.zeros((b, qc, n, g, dh), jnp.float32)
        dq_b, (dk_ts, dv_ts) = jax.lax.scan(k_step, dq0, (kr, vr, kpr))
        # scatter per-k-chunk contributions back into [B,Sk,N,dh]
        dk_full = dk_ts.transpose(1, 0, 2, 3, 4).reshape(b, sk, n, dh)
        dv_full = dv_ts.transpose(1, 0, 2, 3, 4).reshape(b, sk, n, dh)
        return (dk_acc + dk_full, dv_acc + dv_full), dq_b

    dk0 = jnp.zeros((b, sk, n, dh), jnp.float32)
    dv0 = jnp.zeros((b, sk, n, dh), jnp.float32)
    (dk, dv), dqs = jax.lax.scan(q_step, (dk0, dv0), (qr, dor, qpr, lser, dsr))
    dq = dqs.transpose(1, 0, 2, 3, 4, 5).reshape(b, sq, n, g, dh)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            None, None)


_flash_attention.defvjp(_flash_fwd, _flash_bwd)


def apply_attention(
    params: dict,
    x: jax.Array,                      # [B, S, D]
    *,
    n_heads: int,
    n_kv: int,
    head_dim: int,
    positions: jax.Array,              # [B, S] absolute positions of x
    causal: bool = True,
    rope_theta: Optional[float] = 10000.0,
    qk_norm: bool = False,
    x_kv: Optional[jax.Array] = None,  # cross-attention source [B, T, D]
    kv_positions: Optional[jax.Array] = None,
    kv_cache: Optional[dict] = None,   # {"k","v": [B, S_max, N_kv, dh]}
    cache_index: Optional[jax.Array] = None,  # scalar write offset
    cache_layer: Optional[jax.Array] = None,  # layer of a stacked kv_cache
) -> tuple[jax.Array, Optional[dict]]:
    """Returns (y, new_cache).

    With ``cache_layer``, ``kv_cache`` holds every layer's K/V stacked
    ``[n_rep, B, S_max, N_kv, dh]``: only this layer's new rows are written
    into the stack (in place when the stack is a scan carry or a donated
    buffer), and the scores read this layer's K/V back from it."""
    b, s, _ = x.shape
    g = n_heads // n_kv
    scale = head_dim ** -0.5

    q = jnp.einsum("bsd,dnh->bsnh", x, params["wq"].astype(x.dtype))
    src = x if x_kv is None else x_kv
    k = jnp.einsum("btd,dnh->btnh", src, params["wk"].astype(x.dtype))
    v = jnp.einsum("btd,dnh->btnh", src, params["wv"].astype(x.dtype))

    if qk_norm:
        q = _rms(q, params["q_norm"])
        k = _rms(k, params["k_norm"])

    if rope_theta is not None and x_kv is None:
        q = apply_rope(q, positions, rope_theta)
        k = apply_rope(k, positions, rope_theta)

    q = cs(q, "batch", "seq", "heads", "head_dim").reshape(b, s, n_kv, g, head_dim)

    new_cache = None
    if kv_cache is not None and cache_index is None:
        # Static cache (e.g. cross-attention K/V computed once at prefill).
        k, v = kv_cache["k"], kv_cache["v"]
        k_pos = jnp.broadcast_to(jnp.arange(k.shape[1])[None], (b, k.shape[1]))
        new_cache = kv_cache
    elif kv_cache is not None and cache_layer is not None:
        with jax.named_scope("kv_cache"):
            at = (cache_layer, 0, cache_index, 0, 0)
            sk = jax.lax.dynamic_update_slice(kv_cache["k"], k[None], at)
            sv = jax.lax.dynamic_update_slice(kv_cache["v"], v[None], at)
            # Keep the stack in its layout as the step's argument: left
            # free, the compiler gives the layer loop the layout the scores
            # prefer and relayouts the whole cache into and out of it.
            axes = ("stack", "batch", "seq", "kv_heads", "head_dim")
            sk = pin_default_layout(cs(sk, *axes), *axes)
            sv = pin_default_layout(cs(sv, *axes), *axes)
            k = jax.lax.dynamic_index_in_dim(sk, cache_layer, keepdims=False)
            v = jax.lax.dynamic_index_in_dim(sv, cache_layer, keepdims=False)
            k = cs(k, "batch", "seq", "kv_heads", "head_dim")
            v = cs(v, "batch", "seq", "kv_heads", "head_dim")
        new_cache = {"k": sk, "v": sv}
        k_pos = jnp.broadcast_to(jnp.arange(k.shape[1])[None], (b, k.shape[1]))
    elif kv_cache is not None:
        with jax.named_scope("kv_cache"):
            ck = jax.lax.dynamic_update_slice_in_dim(kv_cache["k"], k,
                                                     cache_index, axis=1)
            cv = jax.lax.dynamic_update_slice_in_dim(kv_cache["v"], v,
                                                     cache_index, axis=1)
            ck = cs(ck, "batch", "seq", "kv_heads", "head_dim")
            cv = cs(cv, "batch", "seq", "kv_heads", "head_dim")
        new_cache = {"k": ck, "v": cv}
        k, v = ck, cv
        k_pos = jnp.broadcast_to(jnp.arange(k.shape[1])[None], (b, k.shape[1]))
    else:
        k = cs(k, "batch", "seq", "kv_heads", "head_dim")
        v = cs(v, "batch", "seq", "kv_heads", "head_dim")
        if kv_positions is not None:
            k_pos = kv_positions
        elif x_kv is not None:
            # cross-attention: key positions index the encoder sequence
            k_pos = jnp.broadcast_to(jnp.arange(k.shape[1])[None], (b, k.shape[1]))
        else:
            k_pos = positions if positions.ndim == 2 else \
                jnp.broadcast_to(positions[None], (b, k.shape[1]))

    q_pos = positions if positions.ndim == 2 else \
        jnp.broadcast_to(positions[None], (b, s))

    sk = k.shape[1]
    use_flash = (s > 1 and sk >= FLASH_THRESHOLD and sk % min(K_CHUNK, sk) == 0
                 and s % min(Q_CHUNK, s) == 0)
    if use_flash:
        o = _flash_attention(q, k, v, q_pos, k_pos, causal, scale)
    else:
        mask = None
        if causal:
            mask = q_pos[:, :, None] >= k_pos[:, None, :]
        o = _direct_attention(q, k, v, mask, scale)

    o = o.reshape(b, s, n_heads, head_dim).astype(x.dtype)
    o = cs(o, "batch", "seq", "heads", "head_dim")
    y = jnp.einsum("bsnh,nhd->bsd", o, params["wo"].astype(x.dtype))
    # Megatron-SP: constrain the (model-partial) projection output to the
    # sequence-sharded layout -> GSPMD emits a bf16 reduce-scatter instead
    # of a full fp32 all-reduce.
    return cs(y, "batch", "seq_sp", "embed"), new_cache
