"""Plain float32 forward of a decoder LM whose every layer is a routed MoE.

Written from the configuration file alone (olmoe-1b-7b and models like it):
RMSNorm, multi-head attention with per-head RMS QK-norm and rotary
positions (rotate-half), SwiGLU experts under top-k routing with a token
capacity, final RMSNorm and an untied LM head.  It imports nothing of the
program and takes none of its arrays: the weights are recomputed from the
seed by `chipbench.weights`, layer by layer, so the reference fits on one
chip after the program's state is freed.  Every matmul runs at
`Precision.HIGHEST`.

Routing is the configuration's: softmax over all experts, the top `k`,
weights renormalised over those `k` (`norm_topk_prob`), and per step a
capacity of `ceil(tokens * k * capacity_factor / experts)` rounded up to
`capacity_tile` (at least one tile).  Within an expert, earlier tokens
(batch-major, then position) win the slots; assignments past capacity are
dropped.  A serving engine routes each prefill as one group and each decode
step as another, so the reference routes the same groups.

`quant="fp8"` computes the same forward in float8_e4m3: both operands of
every matmul rounded to it (one scale per tensor), accumulation in float32.
That is the control that a lower precision than the configuration states
has to fail.
"""

from __future__ import annotations

import math
from functools import partial

import numpy as np

from chipbench import weights as W

HIGHEST = None  # set on first use (jax is imported lazily)


def _jax():
    import jax
    import jax.numpy as jnp
    global HIGHEST
    HIGHEST = jax.lax.Precision.HIGHEST
    return jax, jnp


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------

LAYER_LEAVES = ("ln1/scale", "attn/wq", "attn/wk", "attn/wv", "attn/wo",
                "attn/q_norm", "attn/k_norm", "ln2/scale", "moe/router",
                "moe/w_gate", "moe/w_up", "moe/w_down")


def padded_vocab(m: dict) -> int:
    t = m["vocab_pad_multiple"]
    return -(-m["vocab_size"] // t) * t


def leaf_shape(m: dict, name: str) -> tuple[int, ...]:
    d, h, kv, dh = (m["hidden_size"], m["num_attention_heads"],
                    m["num_key_value_heads"], m["head_dim"])
    e, f = m["num_experts"], m["intermediate_size"]
    return {
        "embed/table": (padded_vocab(m), d),
        "ln_f/scale": (d,),
        "head/w_out": (d, padded_vocab(m)),
        "ln1/scale": (d,), "ln2/scale": (d,),
        "attn/wq": (d, h, dh), "attn/wk": (d, kv, dh), "attn/wv": (d, kv, dh),
        "attn/wo": (h, dh, d),
        "attn/q_norm": (dh,), "attn/k_norm": (dh,),
        "moe/router": (d, e),
        "moe/w_gate": (e, d, f), "moe/w_up": (e, d, f), "moe/w_down": (e, f, d),
    }[name]


def leaf_f32(m: dict, seed: int, name: str, layer: int = 0):
    """One layer's slice of a leaf, as served (bf16 values), in float32."""
    jax, jnp = _jax()
    std, mean = m["weights"][name]
    x = W.uniform_jnp(seed, W.stream_id(name, layer), leaf_shape(m, name),
                      std, mean)
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def program_params(m: dict, seed: int, abstract_tree, out_shardings=None):
    """The weights in the program's own tree, made on the device in one
    jitted call.  `abstract_tree` is the program's parameter shape tree
    (paths like `slot0/attn/wq` with a leading layer axis, or `embed/table`);
    a leaf this configuration has no rule for is an error."""
    jax, jnp = _jax()

    def path_name(path) -> tuple[str, bool]:
        keys = [getattr(k, "key", str(k)) for k in path]
        stacked = keys[0].startswith("slot")
        return "/".join(keys[1:] if stacked else keys), stacked

    def make(leaf_path, a):
        name, stacked = path_name(leaf_path)
        if name not in m["weights"]:
            raise KeyError(f"no weight rule for program leaf {name!r}")
        std, mean = m["weights"][name]
        if stacked:
            streams = jnp.asarray([W.stream_id(name, l) for l in range(a.shape[0])],
                                  jnp.uint32)
            x = jax.vmap(lambda s: W.uniform_jnp(seed, s, a.shape[1:], std,
                                                 mean))(streams)
        else:
            x = W.uniform_jnp(seed, W.stream_id(name, 0), a.shape, std, mean)
        if x.shape != tuple(a.shape):
            raise ValueError(f"{name}: rule shape {x.shape} != program {a.shape}")
        return x.astype(a.dtype)

    fn = lambda: jax.tree_util.tree_map_with_path(make, abstract_tree)  # noqa: E731
    return jax.jit(fn, out_shardings=out_shardings)()


def _fp8(w):
    jax, jnp = _jax()
    scale = jnp.maximum(jnp.max(jnp.abs(w)), 1e-30) / 448.0
    return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def capacity(m: dict, tokens: int) -> int:
    t = m["capacity_tile"]
    t_loc = -(-max(tokens, t) // t) * t
    cap = max(math.ceil(t_loc * m["num_experts_per_tok"] * m["capacity_factor"]
                        / m["num_experts"]), t)
    return -(-cap // t) * t


def _rms(x, scale, eps):
    _, jnp = _jax()
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * (1.0 / jnp.sqrt(var + eps)) * scale


def _rope(x, positions, theta):
    _, jnp = _jax()
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions[:, None].astype(jnp.float32) * freqs        # [T, half]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _einsum(quant):
    """einsum at HIGHEST precision, with its operands in float8 under fp8."""
    _, jnp = _jax()
    if quant is None:
        return partial(jnp.einsum, precision=HIGHEST)
    if quant != "fp8":
        raise ValueError(f"unknown quant {quant!r}")
    return lambda spec, a, b: jnp.einsum(spec, _fp8(a), _fp8(b),
                                         precision=HIGHEST)


def _attention(m, w, h, quant=None):
    jax, jnp = _jax()
    ein = _einsum(quant)
    b, t, _ = h.shape
    q = ein("btd,dnh->btnh", h, w["attn/wq"])
    k = ein("btd,dnh->btnh", h, w["attn/wk"])
    v = ein("btd,dnh->btnh", h, w["attn/wv"])
    q = _rms(q, w["attn/q_norm"], m["qk_norm_eps"])
    k = _rms(k, w["attn/k_norm"], m["qk_norm_eps"])
    pos = jnp.arange(t)
    q = _rope(q, pos, m["rope_theta"])
    k = _rope(k, pos, m["rope_theta"])
    g = m["num_attention_heads"] // m["num_key_value_heads"]
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)
    s = ein("bqnh,bknh->bnqk", q, k) * (m["head_dim"] ** -0.5)
    causal = pos[:, None] >= pos[None, :]
    s = jnp.where(causal[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = ein("bnqk,bknh->bqnh", p, v)
    return ein("bqnh,nhd->bqd", o, w["attn/wo"])


def _moe_group(m, w, hg, cap, quant=None):
    """hg [T, D]: one routing group -> ([T, D], dropped assignments)."""
    jax, jnp = _jax()
    ein = _einsum(quant)
    t, d = hg.shape
    e, k = m["num_experts"], m["num_experts_per_tok"]
    logits = ein("td,de->te", hg, w["moe/router"])
    probs = jax.nn.softmax(logits, axis=-1)
    wt, idx = jax.lax.top_k(probs, k)
    if m["norm_topk_prob"]:
        wt = wt / jnp.sum(wt, axis=-1, keepdims=True)
    flat_e = idx.reshape(-1)                                   # token-major
    onehot = (flat_e[:, None] == jnp.arange(e)[None]).astype(jnp.int32)
    pos = jnp.take_along_axis(jnp.cumsum(onehot, axis=0) - 1,
                              flat_e[:, None], axis=1)[:, 0]
    keep = pos < cap
    slot = jnp.where(keep, flat_e * cap + pos, e * cap)
    tok = jnp.arange(t * k) // k
    bucket_tok = jnp.full((e * cap + 1,), t, jnp.int32).at[slot].set(tok)[:-1]
    rows = jnp.concatenate([hg, jnp.zeros((1, d), hg.dtype)])[bucket_tok]
    xb = rows.reshape(e, cap, d)
    gate = ein("ecd,edf->ecf", xb, w["moe/w_gate"])
    up = ein("ecd,edf->ecf", xb, w["moe/w_up"])
    hb = ein("ecf,efd->ecd", jax.nn.silu(gate) * up, w["moe/w_down"])
    out = jnp.concatenate([hb.reshape(e * cap, d),
                           jnp.zeros((1, d), hb.dtype)])[slot]
    y = (out * (wt.reshape(-1) * keep)[:, None]).reshape(t, k, d).sum(axis=1)
    return y, jnp.sum(~keep)


def layer(m, w, x, prompt_len: int, quant=None):
    """One block over [B, T, D] tokens: the prompt is one routing group,
    each later position (one decode step) is another."""
    _, jnp = _jax()
    x = x + _attention(m, w, _rms(x, w["ln1/scale"], m["rms_norm_eps"]), quant)
    h = _rms(x, w["ln2/scale"], m["rms_norm_eps"])
    b, t, d = h.shape
    s = prompt_len
    y_pre, drop = _moe_group(m, w, h[:, :s].reshape(b * s, d),
                             capacity(m, b * s), quant)
    y = y_pre.reshape(b, s, d)
    if t > s:
        import jax
        h_dec = jnp.swapaxes(h[:, s:], 0, 1)                    # [T-S, B, D]
        y_dec, drop_dec = jax.vmap(
            lambda hg: _moe_group(m, w, hg, capacity(m, b), quant))(h_dec)
        y = jnp.concatenate([y, jnp.swapaxes(y_dec, 0, 1)], axis=1)
        drop = drop + jnp.sum(drop_dec)
    return x + y, drop


def served_logits(m: dict, seed: int, requests: list[np.ndarray],
                  prompt_len: int, quant: str | None = None):
    """Teacher-forced logits at the positions that picked served tokens.

    `requests`: list of [B, prompt_len + n] arrays, prompt then the n
    served tokens.  Returns a list of [B, n, vocab] float32 arrays on the
    host and the count of dropped expert assignments."""
    jax, jnp = _jax()
    req = [np.asarray(r, np.int32) for r in requests]
    n = req[0].shape[1] - prompt_len
    get = partial(leaf_f32, m, seed)

    table = get("embed/table")
    if quant == "fp8":
        table = _fp8(table)
    xs = [jnp.take(table, jnp.asarray(r[:, :-1]), axis=0) for r in req]
    del table
    step = jax.jit(partial(layer, m, prompt_len=prompt_len, quant=quant))
    drops = 0
    for li in range(m["num_hidden_layers"]):
        w = {name: get(name, li) for name in LAYER_LEAVES}
        for i, x in enumerate(xs):
            xs[i], d = step(w, x)
            drops += int(d)
        del w
    ln_f, w_out = get("ln_f/scale"), get("head/w_out")

    @jax.jit
    def head(x, ln_f, w_out):
        x = _rms(x[:, prompt_len - 1:], ln_f, m["rms_norm_eps"])
        logits = _einsum(quant)("btd,dv->btv", x, w_out)
        return logits[..., :m["vocab_size"]]

    out = [np.asarray(head(x, ln_f, w_out)) for x in xs]
    assert all(o.shape[1] == n for o in out)
    return out, drops


def token_gaps(logits: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """How far each token's logit lies below the best logit, [B, n]."""
    best = logits.max(axis=-1)
    got = np.take_along_axis(logits, tokens[..., None].astype(np.int64),
                             axis=-1)[..., 0]
    return best - got
