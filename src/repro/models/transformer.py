"""Decoder-only LM assembly: periodic layer stacks under lax.scan + remat.

Heterogeneous architectures (jamba's 7:1 mamba:attn interleave, xlstm's
mlstm/slstm alternation, MoE cadence) are expressed as a repeating *period*
of layer slots; the scan runs over ``n_layers / period`` repetitions with all
slot parameters stacked on a leading "stack" axis.  This keeps the HLO size
O(period) regardless of depth (95-layer deepseek compiles as one scan) and
gives remat a natural per-period boundary.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.parallel.sharding import ParamFactory, ScopedFactory, cs
from . import attention, embedding, mamba, mlp, moe, norms, xlstm


# ---------------------------------------------------------------------------
# Periodic layer structure
# ---------------------------------------------------------------------------


def layer_period(cfg: ModelConfig) -> int:
    """Smallest repeating pattern of layer kinds (and MoE cadence)."""
    p = 1
    if cfg.family == "hybrid":
        p = cfg.attn_every
    elif cfg.family == "ssm" and cfg.xlstm is not None:
        p = cfg.xlstm.slstm_every
    if cfg.moe is not None:
        p = math.lcm(p, cfg.moe.every_k_layers)
    assert cfg.n_layers % p == 0, (cfg.name, cfg.n_layers, p)
    return p


def _stacked(init_fn, n_rep: int):
    def f(key, shape, dtype):
        keys = jax.random.split(key, n_rep)
        return jax.vmap(lambda kk: init_fn(kk, shape[1:], dtype))(keys)
    return f


class _StackFactory:
    """ScopedFactory adapter that prepends the scan ("stack") axis."""

    def __init__(self, base: ScopedFactory, n_rep: int):
        self._base = base
        self._n = n_rep

    @property
    def dtype(self):
        return self._base.dtype

    def param(self, path, shape, axes, init):
        return self._base.param(path, (self._n,) + tuple(shape),
                                ("stack",) + tuple(axes), _stacked(init, self._n))

    def scope(self, prefix):
        return _StackFactory(self._base.scope(prefix), self._n)


# ---------------------------------------------------------------------------
# One block (slot): sequence mixer + (optional) FFN/MoE, pre-norm residual
# ---------------------------------------------------------------------------


def init_block(f, cfg: ModelConfig, slot: int) -> None:
    kind = cfg.layer_kind(slot)
    norms.init_norm(f.scope("ln1"), cfg.norm, cfg.d_model)
    if kind == "attn":
        attention.init_attention(f.scope("attn"), cfg.d_model, cfg.n_heads,
                                 cfg.n_kv_heads, cfg.head_dim, cfg.qk_norm)
    elif kind == "mamba":
        mc = cfg.mamba
        mamba.init_mamba(f.scope("mamba"), cfg.d_model, mc.d_state, mc.d_conv,
                         mc.expand, mc.dt_rank)
    elif kind == "mlstm":
        xc = cfg.xlstm
        xlstm.init_mlstm(f.scope("mlstm"), cfg.d_model, cfg.n_heads,
                         xc.proj_factor, xc.qk_dim_factor)
    elif kind == "slstm":
        xlstm.init_slstm(f.scope("slstm"), cfg.d_model, cfg.n_heads)
    else:
        raise ValueError(kind)

    if cfg.d_ff > 0 or cfg.is_moe_layer(slot):
        norms.init_norm(f.scope("ln2"), cfg.norm, cfg.d_model)
        if cfg.is_moe_layer(slot):
            moe.init_moe(f.scope("moe"), cfg.d_model, cfg.moe)
        else:
            mlp.init_mlp(f.scope("mlp"), cfg.activation, cfg.d_model, cfg.d_ff)


def apply_block(params: dict, cfg: ModelConfig, slot: int, x: jax.Array, *,
                positions: jax.Array,
                moe_plan: Optional[moe.MoEDispatchPlan],
                cache: Optional[dict] = None,
                cache_index: Optional[jax.Array] = None,
                cache_layer: Optional[jax.Array] = None,
                causal: bool = True):
    """Returns (x, aux [moe.N_AUX], new_cache).

    ``cache_layer``: an attention slot's ``cache`` is the whole stack of its
    layers' K/V, and this block is layer ``cache_layer`` of it.

    Device ops run under ``jax.named_scope``: ``attention`` (with the cache
    write as ``attention/kv_cache``) and the MoE layer's ``moe/*`` stages."""
    kind = cfg.layer_kind(slot)
    rs = cfg.residual_scale
    h = norms.apply_norm(params.get("ln1"), cfg.norm, x)
    new_cache = dict(cache) if cache is not None else None

    if kind == "attn":
        with jax.named_scope("attention"):
            y, kvc = attention.apply_attention(
                params["attn"], h, n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads,
                head_dim=cfg.head_dim, positions=positions, causal=causal,
                rope_theta=cfg.rope_theta, qk_norm=cfg.qk_norm,
                kv_cache=(None if cache is None
                          else {"k": cache["k"], "v": cache["v"]}),
                cache_index=cache_index, cache_layer=cache_layer)
        if kvc is not None:
            new_cache.update(kvc)
    elif kind == "mamba":
        mc = cfg.mamba
        if cache is None:
            y = mamba.apply_mamba(params["mamba"], h, d_state=mc.d_state,
                                  d_conv=mc.d_conv)
        elif h.shape[1] > 1:   # serve prefill: run full scan, prime the state
            y, new_cache = mamba.apply_mamba(params["mamba"], h,
                                             d_state=mc.d_state, d_conv=mc.d_conv,
                                             return_cache=True)
        else:
            y, new_cache = mamba.mamba_decode_step(params["mamba"], cache, h,
                                                   d_state=mc.d_state, d_conv=mc.d_conv)
    elif kind == "mlstm":
        if cache is None:
            y = xlstm.apply_mlstm(params["mlstm"], h, n_heads=cfg.n_heads)
        elif h.shape[1] > 1:
            y, new_cache = xlstm.apply_mlstm(params["mlstm"], h, n_heads=cfg.n_heads,
                                             return_cache=True)
        else:
            y, new_cache = xlstm.mlstm_decode_step(params["mlstm"], cache, h,
                                                   n_heads=cfg.n_heads)
    elif kind == "slstm":
        if cache is None:
            y = xlstm.apply_slstm(params["slstm"], h, n_heads=cfg.n_heads)
        elif h.shape[1] > 1:
            y, new_cache = xlstm.apply_slstm(params["slstm"], h, n_heads=cfg.n_heads,
                                             return_cache=True)
        else:
            y, new_cache = xlstm.slstm_decode_step(params["slstm"], cache, h,
                                                   n_heads=cfg.n_heads)
    else:
        raise ValueError(kind)
    x = x + y * rs if rs != 1.0 else x + y

    aux = jnp.zeros((moe.N_AUX,), jnp.float32)
    if cfg.is_moe_layer(slot):
        h = norms.apply_norm(params.get("ln2"), cfg.norm, x)
        y, aux = moe.apply_moe(params["moe"], h, cfg.moe, moe_plan)
        x = x + y * rs if rs != 1.0 else x + y
    elif cfg.d_ff > 0:
        h = norms.apply_norm(params.get("ln2"), cfg.norm, x)
        y = mlp.apply_mlp(params["mlp"], cfg.activation, h)
        x = x + y * rs if rs != 1.0 else x + y
    return cs(x, "batch", "seq_sp", "embed"), aux, new_cache


# ---------------------------------------------------------------------------
# Full decoder stack
# ---------------------------------------------------------------------------


def init_lm(key: Optional[jax.Array], cfg: ModelConfig, abstract: bool = False):
    """Returns (params, logical_specs).  abstract=True: ShapeDtypeStructs."""
    f = ParamFactory(key, jnp.dtype(cfg.param_dtype), abstract=abstract)
    embedding.init_embedding(f.scope("embed"), cfg.padded_vocab, cfg.d_model)
    period = layer_period(cfg)
    n_rep = cfg.n_layers // period
    for slot in range(period):
        init_block(_StackFactory(f.scope(f"slot{slot}"), n_rep), cfg, slot)
    norms.init_norm(f.scope("ln_f"), cfg.norm, cfg.d_model)
    embedding.init_lm_head(f.scope("head"), cfg.padded_vocab, cfg.d_model,
                           cfg.tie_embeddings)
    if cfg.frontend == "vision_patches":
        from . import vlm
        vlm.init_projector(f.scope("projector"), cfg.frontend_dim, cfg.d_model)
    return f.params, f.logical_specs


def _stack_params(params: dict, cfg: ModelConfig) -> list[dict]:
    return [params[f"slot{s}"] for s in range(layer_period(cfg))]


def scan_blocks(body, carry, xs, n_rep: int, remat: bool = True):
    """lax.scan over the stacked blocks; unrolls when n_rep <= 2.

    The unrolled path matters for the dry-run's cost accounting:
    cost_analysis counts a while-loop body ONCE regardless of trip count, so
    the roofline correction lowers 1- and 2-period unrolled variants and
    diffs them to recover per-period cost (see launch/dryrun.py)."""
    if remat:
        body = jax.checkpoint(body)
    if n_rep <= 2:
        ys = []
        for i in range(n_rep):
            carry, y = body(carry, jax.tree.map(lambda a: a[i], xs))
            ys.append(y)
        stacked = jax.tree.map(lambda *z: jnp.stack(z), *ys)
        return carry, stacked
    return jax.lax.scan(body, carry, xs)


def apply_stack(params: dict, cfg: ModelConfig, x: jax.Array, *,
                positions: jax.Array,
                moe_plan: Optional[moe.MoEDispatchPlan] = None,
                caches: Optional[list] = None,
                cache_index: Optional[jax.Array] = None,
                causal: bool = True,
                remat: bool = True):
    """Scan the periodic stack. caches: per-slot stacked pytrees or None.

    With ``cache_index`` (prefill and decode), each attention slot's stacked
    K/V rides in the scan carry and every layer writes only its new rows
    into it, so a donated cache is updated in place.  Recurrent states
    (mamba, mLSTM, sLSTM) are replaced whole each step: they stay scan
    inputs and outputs."""
    period = layer_period(cfg)
    slots = _stack_params(params, cfg)
    n_rep = cfg.n_layers // period
    carried = [caches is not None and cache_index is not None
               and cfg.layer_kind(s) == "attn" for s in range(period)]

    def body(carry, xs):
        h, kv = carry
        slot_params, layer, slot_caches = xs
        auxs = jnp.zeros((moe.N_AUX,), jnp.float32)
        kv = list(kv)
        new_caches = []
        for s in range(period):
            def block_fn(p, hh, cc, _s=s):
                return apply_block(p, cfg, _s, hh, positions=positions,
                                   moe_plan=moe_plan, cache=cc,
                                   cache_index=cache_index,
                                   cache_layer=layer if carried[_s] else None,
                                   causal=causal)
            if remat and period > 1:
                # nested remat: a multi-layer period (jamba's 8) must not
                # keep all its layers' backward transients live at once
                block_fn = jax.checkpoint(block_fn)
            if carried[s]:
                h, aux, kv[s] = block_fn(slot_params[s], h, kv[s])
                new_caches.append(None)
            else:
                h, aux, nc = block_fn(
                    slot_params[s], h,
                    None if slot_caches is None else slot_caches[s])
                new_caches.append(nc)
            auxs = auxs + aux
        return (h, kv), (auxs, new_caches if caches is not None else 0)

    kv = [c if carried[s] else None for s, c in enumerate(caches or [])]
    scanned = (None if caches is None else
               [None if carried[s] else c for s, c in enumerate(caches)])
    xs = (slots, jnp.arange(n_rep), scanned)
    (x, kv), (auxs, new_caches) = scan_blocks(body, (x, kv), xs, n_rep,
                                              remat=remat)
    if caches is None:
        return x, auxs.sum(axis=0), None
    return x, auxs.sum(axis=0), [kv[s] if carried[s] else c
                                 for s, c in enumerate(new_caches)]


def forward(params: dict, cfg: ModelConfig, tokens: jax.Array, *,
            moe_plan=None, caches=None, cache_index=None,
            extra_embeds: Optional[jax.Array] = None,
            remat: bool = True, return_hidden: bool = False):
    """tokens: [B, S] -> logits [B, S, V_padded] (+ aux, new caches).

    aux: [load-balance loss, router z-loss, experts touched], each summed
    over the MoE layers (zeros without MoE).

    extra_embeds (VLM): [B, N, D_frontend-projected] prepended embeddings.
    decode: pass caches + cache_index (tokens is [B, 1]).
    return_hidden: skip the logits matmul (the loss computes it chunked).
    """
    x = embedding.embed_tokens(params["embed"], tokens, cfg.embed_scale)
    if extra_embeds is not None:
        x = jnp.concatenate([extra_embeds.astype(x.dtype), x], axis=1)
    b, s = x.shape[:2]
    if cache_index is not None:
        # decode (s==1): position = cache_index; prefill: cache_index + arange
        base = cache_index if jnp.ndim(cache_index) == 0 else cache_index.reshape(())
        positions = jnp.broadcast_to((base + jnp.arange(s))[None], (b, s))
    else:
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    x = cs(x, "batch", "seq_sp", "embed")
    x, aux, new_caches = apply_stack(
        params, cfg, x, positions=positions, moe_plan=moe_plan,
        caches=caches, cache_index=cache_index, remat=remat)
    x = norms.apply_norm(params.get("ln_f"), cfg.norm, x)
    if return_hidden:
        return x, aux, new_caches
    with jax.named_scope("lm_head"):
        logits = embedding.lm_logits(params.get("head"), params["embed"], x,
                                     cfg.tie_embeddings, cfg.logit_scale,
                                     valid_vocab=cfg.vocab_size)
    return logits, aux, new_caches


def cross_entropy(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Per-token NLL that stays vocab-sharded.

    take_along_axis over a model-sharded vocab dim makes GSPMD all-gather
    the full [B,S,V] fp32 logits (13 GB/chip at 50k vocab); the where-iota
    contraction keeps everything sharded — local partial sums + one psum.
    """
    l32 = logits.astype(jnp.float32)
    m = jax.lax.stop_gradient(jnp.max(l32, axis=-1, keepdims=True))
    lse = jnp.log(jnp.sum(jnp.exp(l32 - m), axis=-1)) + m[..., 0]
    iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    tgt = jnp.sum(jnp.where(iota == targets[..., None], l32, 0.0), axis=-1)
    return lse - tgt


def chunked_nll(params: dict, cfg: ModelConfig, hidden: jax.Array,
                tokens: jax.Array, mask: Optional[jax.Array] = None,
                n_chunks: int = 4, offset: int = 0):
    """Next-token NLL computed in sequence chunks so only one chunk's
    [tokens, V/TP] fp32 logits block is ever live (the head matmul is
    recomputed per chunk in the backward via jax.checkpoint).

    hidden: [B, S, D] final hidden states; tokens: [B, S_tok] with
    hidden position offset+i predicting tokens[:, i+1].
    Returns (sum_nll, n_valid)."""
    b, s, _ = hidden.shape
    s_tok = tokens.shape[1]
    assert s == s_tok + offset, (s, s_tok, offset)
    # hidden position p predicts tokens[:, p - offset + 1]
    pos = jnp.arange(s)
    valid = (pos >= offset) & (pos < s - 1)
    m = jnp.broadcast_to(valid[None], (b, s)).astype(jnp.float32)
    tgt_full = jnp.zeros((b, s), tokens.dtype)
    tgt_full = tgt_full.at[:, offset:s - 1].set(tokens[:, 1:])
    if mask is not None:
        m = m.at[:, offset:s - 1].mul(mask.astype(jnp.float32)[:, 1:])

    chunk = s // n_chunks if (s % n_chunks == 0 and s >= 2 * n_chunks) else s

    def chunk_fn(h_c, t_c, m_c):
        logits = embedding.lm_logits(params.get("head"), params["embed"], h_c,
                                     cfg.tie_embeddings, cfg.logit_scale,
                                     valid_vocab=cfg.vocab_size)
        return (cross_entropy(logits, t_c) * m_c).sum()

    chunk_fn = jax.checkpoint(chunk_fn)
    total = jnp.float32(0)
    for a in range(0, s, chunk):
        total = total + chunk_fn(hidden[:, a:a + chunk],
                                 tgt_full[:, a:a + chunk], m[:, a:a + chunk])
    return total, jnp.maximum(m.sum(), 1.0)


def lm_loss(params: dict, cfg: ModelConfig, batch: dict, *,
            moe_plan=None, remat: bool = True):
    """batch: {"tokens": [B, S] int32, "loss_mask": optional [B, S]}."""
    tokens = batch["tokens"]
    # forward on the FULL sequence (power-of-two seq keeps the seq_sp
    # sharding and flash-chunk divisibility); shift inside chunked_nll.
    hidden, aux, _ = forward(params, cfg, tokens, moe_plan=moe_plan,
                             remat=remat, return_hidden=True)
    total, denom = chunked_nll(params, cfg, hidden, tokens,
                               batch.get("loss_mask"))
    loss = total / denom
    total = loss
    metrics = {"nll": loss}
    if cfg.moe is not None:
        lb, z = aux[0], aux[1]
        total = total + cfg.moe.aux_loss * lb + cfg.moe.router_z_loss * z
        metrics.update({"moe_lb": lb, "moe_z": z})
    metrics["loss"] = total
    return total, metrics


# ---------------------------------------------------------------------------
# Decode caches
# ---------------------------------------------------------------------------


def cache_logical_specs(cfg: ModelConfig) -> list:
    """Logical sharding axes mirroring init_caches' structure (leading
    "stack" axis from the scan layout)."""
    specs = []
    for slot in range(layer_period(cfg)):
        kind = cfg.layer_kind(slot)
        if kind == "attn":
            kv = ("stack", "batch", "seq", "kv_heads", "head_dim")
            c = {"k": kv, "v": kv}
        elif kind == "mamba":
            c = {"conv": ("stack", "batch", "conv", "d_inner"),
                 "ssm": ("stack", "batch", "d_inner", "state")}
        elif kind == "mlstm":
            c = {"c": ("stack", "batch", "heads", "head_dim", None),
                 "n": ("stack", "batch", "heads", "head_dim"),
                 "m": ("stack", "batch", "heads")}
        elif kind == "slstm":
            ax = ("stack", "batch", "heads", "head_dim")
            c = {"c": ax, "n": ax, "h": ax, "m": ax}
        else:
            raise ValueError(kind)
        specs.append(c)
    return specs


def cache_shape_specs(cfg: ModelConfig, batch: int, max_seq: int,
                      dtype=jnp.bfloat16):
    """ShapeDtypeStruct tree for caches (dry-run, no allocation)."""
    return jax.eval_shape(lambda: init_caches(cfg, batch, max_seq, dtype))


def init_caches(cfg: ModelConfig, batch: int, max_seq: int, dtype=jnp.bfloat16):
    """Per-slot stacked cache pytrees matching apply_stack's scan layout."""
    period = layer_period(cfg)
    n_rep = cfg.n_layers // period

    def stacked(tree):
        return jax.tree.map(lambda a: jnp.broadcast_to(a, (n_rep,) + a.shape), tree)

    caches = []
    for slot in range(period):
        kind = cfg.layer_kind(slot)
        if kind == "attn":
            c = {"k": jnp.zeros((batch, max_seq, cfg.n_kv_heads, cfg.head_dim), dtype),
                 "v": jnp.zeros((batch, max_seq, cfg.n_kv_heads, cfg.head_dim), dtype)}
        elif kind == "mamba":
            mc = cfg.mamba
            di = mamba.d_inner(cfg.d_model, mc.expand)
            c = mamba.init_mamba_cache(batch, di, mc.d_state, mc.d_conv, dtype)
        elif kind == "mlstm":
            xc = cfg.xlstm
            c = xlstm.init_mlstm_cache(batch, cfg.d_model, cfg.n_heads,
                                       xc.proj_factor, xc.qk_dim_factor, dtype)
        elif kind == "slstm":
            c = xlstm.init_slstm_cache(batch, cfg.d_model, cfg.n_heads)
        else:
            raise ValueError(kind)
        caches.append(stacked(c))
    return caches
