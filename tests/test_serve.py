"""Serving-path tests: KV-cache decode must match full-forward greedy."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.launch.mesh import make_mesh
from repro.models import api as model_api, attention, embedding, norms, transformer
from repro.parallel.sharding import DEFAULT_RULES, axis_rules
from repro.serve import ServeEngine


def _no_drop(cfg):
    """Capacity drops make cached vs uncached runs diverge (expected for
    capacity MoE); equivalence tests use a no-drop capacity factor."""
    if cfg.moe is None:
        return cfg
    return dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))


@pytest.mark.parametrize("arch", ["olmo-1b", "minicpm-2b", "xlstm-125m",
                                  "jamba-v0.1-52b", "olmoe-1b-7b"])
def test_decode_matches_forward(arch):
    cfg = _no_drop(get_reduced(arch))
    mesh = make_mesh((1, 1), ("data", "model"))
    eng = ServeEngine(cfg, mesh, batch=2, prompt_len=16, max_seq=48, seed=0)
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_size, (2, 16)).astype(np.int32)
    toks, stats = eng.generate(prompts, n_tokens=6)

    with axis_rules(DEFAULT_RULES, mesh):
        params, _ = model_api.init_model(jax.random.key(0), cfg)
        seq = jnp.asarray(prompts)
        for _ in range(6):
            logits, _, _ = transformer.forward(params, cfg, seq, remat=False)
            nxt = jnp.argmax(logits[:, -1].astype(jnp.float32), -1)
            seq = jnp.concatenate([seq, nxt.astype(jnp.int32)[:, None]], 1)
    oracle = np.asarray(seq[:, 16:])
    np.testing.assert_array_equal(toks, oracle)
    assert stats.tokens_generated == 12


def _uncached_kv(params, cfg, tokens):
    """Each layer's K/V [L, B, S, N_kv, dh] in an uncached forward over
    ``tokens``, taken layer by layer (one attention slot per period)."""
    x = embedding.embed_tokens(params["embed"], tokens, cfg.embed_scale)
    pos = jnp.broadcast_to(jnp.arange(tokens.shape[1])[None], tokens.shape)
    ks, vs = [], []
    for layer in range(cfg.n_layers):
        p = jax.tree.map(lambda a: a[layer], params["slot0"])
        h = norms.apply_norm(p.get("ln1"), cfg.norm, x)
        k = jnp.einsum("btd,dnh->btnh", h, p["attn"]["wk"])
        v = jnp.einsum("btd,dnh->btnh", h, p["attn"]["wv"])
        if cfg.qk_norm:
            k = attention._rms(k, p["attn"]["k_norm"])
        ks.append(attention.apply_rope(k, pos, cfg.rope_theta))
        vs.append(v)
        x, _, _ = transformer.apply_block(p, cfg, 0, x, positions=pos,
                                          moe_plan=None)
    return np.stack(ks), np.stack(vs)


@pytest.mark.parametrize("arch", ["olmo-1b", "olmoe-1b-7b"])
def test_decode_cache_holds_forward_kv(arch):
    """The caches the last decode step returns hold, in rows [0, prompt +
    n - 1), each layer's K/V of an uncached forward over the prompt and the
    tokens fed back; the rows past them are still zero."""
    cfg = _no_drop(get_reduced(arch))
    assert transformer.layer_period(cfg) == 1
    mesh = make_mesh((1, 1), ("data", "model"))
    eng = ServeEngine(cfg, mesh, batch=2, prompt_len=16, max_seq=48, seed=0)
    step, last = eng.decode_bundle.jitted, {}

    def recording(*args):
        out = step(*args)
        last["caches"] = out[1]
        return out

    eng.decode_bundle.jitted = recording
    rng = np.random.default_rng(1)
    prompts = rng.integers(0, cfg.vocab_size, (2, 16)).astype(np.int32)
    n = 6
    toks, _ = eng.generate(prompts, n_tokens=n)
    filled = 16 + n - 1
    (cache,) = last["caches"]

    with axis_rules(DEFAULT_RULES, mesh):
        params, _ = model_api.init_model(jax.random.key(0), cfg)
        seq = jnp.asarray(np.concatenate([prompts, toks[:, :n - 1]], 1))
        ref = dict(zip("kv", _uncached_kv(params, cfg, seq)))
    for name in "kv":
        got = np.asarray(cache[name])
        assert got.shape == (cfg.n_layers, 2, 48, cfg.n_kv_heads, cfg.head_dim)
        np.testing.assert_allclose(got[:, :, :filled], ref[name],
                                   rtol=1e-5, atol=1e-5)
        assert not got[:, :, filled:].any()


def test_whisper_generate_smoke():
    cfg = get_reduced("whisper-base")
    mesh = make_mesh((1, 1), ("data", "model"))
    eng = ServeEngine(cfg, mesh, batch=2, prompt_len=16, max_seq=40, seed=0)
    rng = np.random.default_rng(0)
    frames = rng.standard_normal((2, 16, cfg.d_model)).astype(np.float32) * 0.02
    prompts = rng.integers(0, cfg.vocab_size, (2, 8)).astype(np.int32)
    toks, _ = eng.generate(prompts, n_tokens=5, frames=frames)
    assert toks.shape == (2, 5)
    assert (toks >= 0).all() and (toks < cfg.vocab_size).all()


def test_prompt_exceeding_max_seq_rejected_up_front():
    """Regression: prompt_len > max_seq used to surface as a negative-pad
    crash deep inside jnp.pad when growing prefill caches; now both engine
    construction and generate() validate the window with clear errors."""
    cfg = get_reduced("olmo-1b")
    mesh = make_mesh((1, 1), ("data", "model"))
    with pytest.raises(ValueError, match=r"prompt_len 64 exceeds max_seq 32"):
        ServeEngine(cfg, mesh, batch=2, prompt_len=64, max_seq=32, seed=0)

    eng = ServeEngine(cfg, mesh, batch=2, prompt_len=8, max_seq=16, seed=0)
    prompts = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, 8)).astype(np.int32)
    with pytest.raises(ValueError, match=r"exceeds max_seq 16"):
        eng.generate(prompts, n_tokens=9)
    toks, _ = eng.generate(prompts, n_tokens=8)   # exactly fills the window
    assert toks.shape == (2, 8)


def test_sampler():
    from repro.serve import sampler

    logits = jnp.asarray([[0.0, 5.0, 1.0], [9.0, 0.0, 0.0]])
    np.testing.assert_array_equal(np.asarray(sampler.greedy(logits)), [1, 0])
    # temperature 0 == greedy
    s = sampler.sample(logits, jax.random.key(0), temperature=0.0)
    np.testing.assert_array_equal(np.asarray(s), [1, 0])
    # top-k=1 == greedy regardless of temperature
    s = sampler.sample(logits, jax.random.key(0), temperature=5.0, top_k=1)
    np.testing.assert_array_equal(np.asarray(s), [1, 0])


def test_padded_vocab_never_sampled():
    """Pad logits are masked to -inf: argmax can't land past vocab_size."""
    cfg = get_reduced("olmo-1b", vocab_size=500)   # padded to 512
    assert cfg.padded_vocab == 512
    params, _ = model_api.init_model(jax.random.key(0), cfg)
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 500, (2, 8)),
                         jnp.int32)
    logits, _, _ = transformer.forward(params, cfg, tokens, remat=False)
    assert logits.shape[-1] == 512
    assert int(jnp.max(jnp.argmax(logits, -1))) < 500
