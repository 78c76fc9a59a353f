#!/usr/bin/env python3
"""Smoke run of the persistent-alltoallv engine and olmoe-1b-7b serving on TPU.

    python3 chip_smoke.py              # one chip: engine, kernels, serving
    python3 chip_smoke.py --chips 4    # four chips: cross-chip exchange and
                                       # expert-parallel serving only

Every phase checks what it computes against the repo's own references and
raises on a mismatch, so any failing phase exits non-zero.  The last line
printed is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU (or without the ``src/repro`` package beside this file) the
script exits non-zero and prints no result.  All phases run in this one
process; it starts no other.

The compile cache is ``JAX_COMPILATION_CACHE_DIR`` when set, else
``<checkout>/.jax_cache``; the closing counters show its hits and misses.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# olmoe-1b-7b serving shape: batch, prompt length, new tokens per prompt.
BATCH, PROMPT, NEW_TOKENS = 4, 128, 16
# Engine phase: row width and an irregular per-pair row count.
FEATURE = 256
MAX_SEQ = PROMPT + NEW_TOKENS + 8
# Pallas vs jnp gather-matmul at olmoe widths, bf16 (one bf16 ulp of the
# outputs' magnitude, which reach ~4).
KERNEL_TOL = 1 / 16
# persistent_a2a vs gspmd MoE layer outputs in bf16, per token: relative L2
# difference within four bf16 ulps (2**-8 each).  The two dispatchers route
# identically and differ only in accumulation order; a token sent to the
# wrong expert or slot differs by order 1.
LAYER_TOKEN_TOL = 4 * 2.0 ** -8


class CompileCounters:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events."""

    def __init__(self, jax):
        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def peak_bytes(device):
    """``peak_bytes_in_use`` where the backend reports it (TPU does)."""
    return (device.memory_stats() or {}).get("peak_bytes_in_use")


# ---------------------------------------------------------------------------
# exchange checks
# ---------------------------------------------------------------------------


def banded_counts(p: int, rng) -> "np.ndarray":
    """Each rank sends only to itself and its ring neighbours."""
    import numpy as np
    counts = np.zeros((p, p), np.int64)
    for i in range(p):
        for j in (i - 1, i, i + 1):
            counts[i, j % p] = rng.integers(5, 40)
    return counts


def exchange_case(counts, mesh, axis):
    """Send buffers on the mesh and the numpy oracle for ``counts``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import metadata as md, reference

    p = counts.shape[0]
    send_rows = max(md.round_up(md.max_total_send(counts), 8), 8)
    recv_rows = max(md.round_up(md.max_total_recv(counts), 8), 8)
    bufs = reference.make_testbufs(counts, (FEATURE,), np.float32, send_rows)
    expect = reference.alltoallv_global(bufs, counts, recv_rows)
    x = jax.device_put(jnp.asarray(bufs.reshape(p * send_rows, FEATURE)),
                       NamedSharding(mesh, P(axis)))
    return x, expect, md.recv_counts(counts).sum(1), send_rows, recv_rows


def check_rows(label, got, expect, n_valid):
    """Valid rows of every rank must match the oracle bit for bit."""
    import numpy as np
    for r, n in enumerate(n_valid):
        if not np.array_equal(got[r, :n].view(np.uint32),
                              expect[r, :n].view(np.uint32)):
            bad = int((got[r, :n] != expect[r, :n]).any(-1).sum())
            raise AssertionError(f"{label}: rank {r}: {bad} of {n} rows "
                                 f"differ from the oracle")


def run_plan(label, counts, mesh, axis, variant, pack_impl, expect_case):
    import numpy as np

    from repro.core import alltoallv_init

    x, expect, n_valid, _, recv_rows = expect_case
    p = counts.shape[0]
    plan = alltoallv_init(counts, (FEATURE,), np.float32, mesh, axis=axis,
                          variant=variant, pack_impl=pack_impl)
    got = np.asarray(plan.wait(plan.start(x))).reshape(p, recv_rows, FEATURE)
    check_rows(label, got, expect, n_valid)
    extra = ""
    if variant == "auto":
        choice = plan.auto_choice
        if "degraded" in choice:
            raise AssertionError(f"{label}: autotune faulted: "
                                 f"{choice['degraded']}")
        times = {k: f"{v * 1e6:.1f}us" for k, v in choice["times"].items()}
        extra = f", chose {choice['variant']} from {times}"
    print(f"  {label}: {int(sum(n_valid))} rows x {FEATURE} f32 bit-exact "
          f"vs oracle{extra}")
    return got


# ---------------------------------------------------------------------------
# one-chip phases
# ---------------------------------------------------------------------------


def engine_phase(args):
    """alltoallv_init at P=1 for every variant/pack_impl the spec allows."""
    import numpy as np

    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1,), ("x",))
    counts = np.array([[37]])        # not a multiple of the 8-row tile
    case = exchange_case(counts, mesh, "x")
    for variant, pack_impl in [("fence", "jnp"), ("fence", "pallas"),
                               ("fence", "fused"), ("lock", "jnp"),
                               ("lock", "pallas")]:
        run_plan(f"P=1 {variant}/{pack_impl}", counts, mesh, "x", variant,
                 pack_impl, case)


def kernel_phase(args):
    """Pallas kernels, compiled for the chip, against their jnp forms at
    olmoe widths (bf16, D=2048, F=1024, 64 local experts)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import variants
    from repro.kernels import ops as kops

    rng = np.random.default_rng(args.seed)
    e, d, f, n, rows = 64, 2048, 1024, 80, 4096
    x = jnp.asarray(rng.standard_normal((rows, d)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((e, d, f)) / np.sqrt(d), jnp.bfloat16)
    idx = jnp.asarray(rng.integers(0, rows, (e, n)), jnp.int32)
    valid = jnp.asarray(rng.random((e, n)) < 0.9, jnp.int32)

    kern = jax.jit(kops.fused_unpack_matmul)
    assert "tpu_custom_call" in kern.lower(x, idx, w, valid).compile(
    ).as_text(), "fused_unpack_matmul did not compile to a Mosaic kernel"
    got = np.asarray(kern(x, idx, w, valid), np.float32)
    want = np.asarray(jax.jit(kops.unpack_matmul_ref)(x, idx, w, valid),
                      np.float32)
    err = float(np.abs(got - want).max())
    print(f"  fused_unpack_matmul [{e}x{n}] x [{d}x{f}] bf16: max abs err "
          f"{err} vs jnp form (tol {KERNEL_TOL}, max |ref| "
          f"{float(np.abs(want).max())})")
    if not err <= KERNEL_TOL:
        raise AssertionError(f"fused_unpack_matmul error {err} > {KERNEL_TOL}")

    n_rows = 5003                    # not a multiple of the 8-row tile
    src = jnp.asarray(rng.integers(0, rows, n_rows), jnp.int32)
    ok = jnp.asarray(rng.random(n_rows) < 0.8, jnp.int32)
    pack = jax.jit(kops.pack)
    assert "tpu_custom_call" in pack.lower(x, src, ok).compile().as_text(), \
        "pack did not compile to a Mosaic kernel"
    got = np.asarray(pack(x, src, ok)).view(np.uint16)
    want = np.asarray(variants.pack_rows(x, src, ok)).view(np.uint16)
    if not np.array_equal(got, want):
        raise AssertionError("Pallas pack differs from variants.pack_rows")
    print(f"  pack {n_rows} rows x {d} bf16: bit-exact vs variants.pack_rows")


def serve_run(eng, prompts, vocab):
    """Generate twice (the first call compiles); returns tokens, stats and
    the first call's wall time."""
    import jax.numpy as jnp
    import numpy as np

    t0 = time.perf_counter()
    first, _ = eng.generate(prompts, NEW_TOKENS)
    cold_s = time.perf_counter() - t0
    toks, stats = eng.generate(prompts, NEW_TOKENS)
    if toks.shape != (prompts.shape[0], NEW_TOKENS):
        raise AssertionError(f"generated {toks.shape}")
    if not ((toks >= 0) & (toks < vocab)).all():
        raise AssertionError("generated a token outside the vocabulary")
    if not np.array_equal(first, toks):
        raise AssertionError("greedy decoding is not repeatable")
    with eng.prefill_bundle.trace_context():
        logits, _ = eng.prefill_bundle.jitted(eng.params, jnp.asarray(prompts))
    logits = np.asarray(logits, np.float32)
    if not np.isfinite(logits).all():
        raise AssertionError("prefill logits are not finite")
    if not np.array_equal(logits.argmax(-1), toks[:, 0]):
        raise AssertionError("first token is not the argmax of the logits")
    return toks, stats, cold_s, logits


def serve_bytes(cfg, mesh) -> int:
    """Bytes one chip needs to serve ``cfg``: the larger of the compiled
    prefill and decode steps' arguments, outputs and temporaries
    (``memory_analysis()``), donated buffers counted once."""
    from repro.configs.base import ShapeConfig
    from repro.launch import steps as steps_mod

    need = 0
    for make, shape in (
            (steps_mod.make_prefill_bundle,
             ShapeConfig("serve_prefill", "prefill", PROMPT, BATCH)),
            (steps_mod.make_decode_bundle,
             ShapeConfig("serve_decode", "decode", MAX_SEQ, BATCH))):
        ma = make(cfg, shape, mesh).compile().memory_analysis()
        need = max(need, ma.argument_size_in_bytes + ma.output_size_in_bytes
                   - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    return need


def fit_depth(cfg, mesh, limit: int):
    """``cfg`` with as many of its layers as fit in ``limit`` bytes (widths
    are never cut); prints each memory_analysis() reading."""
    n = cfg.n_layers
    while True:
        trial = dataclasses.replace(cfg, n_layers=n)
        need = serve_bytes(trial, mesh)
        print(f"  memory_analysis at {n} layers: {need} bytes of {limit}")
        if need <= limit:
            return trial
        if n == 1:
            raise AssertionError("one layer does not fit on the chip")
        n = max(1, min(n - 1, n * limit // need))


def dispatch_taken(eng) -> str:
    """The MoE path the engine's layers run (apply_moe's own test)."""
    plan = eng.moe_plan
    if eng.cfg.moe.dispatch == "gspmd":
        return "gspmd einsum, experts sharded by the partitioner"
    if plan is None or plan.axis is None:
        return "gspmd einsum, no exchange (EP=1)"
    return f"{eng.cfg.moe.dispatch} exchange over {plan.axis}"


def serve_phase(args, counters):
    """ServeEngine (the launch/serve.py path) for olmoe-1b-7b at its
    published widths, random weights from --seed; layers are cut only if
    the compiled steps do not fit the chip."""
    import jax
    import numpy as np

    from repro.configs import get
    from repro.launch.mesh import make_mesh
    from repro.serve import ServeEngine

    full = get("olmoe-1b-7b")
    assert full.moe.dispatch == "persistent_a2a", full.moe.dispatch
    mesh = make_mesh((1, 1), ("data", "model"))
    c0 = counters.compile_s
    cfg = fit_depth(full, mesh, jax.devices()[0].memory_stats()["bytes_limit"])
    cut = ("no depth cut" if cfg.n_layers == full.n_layers else
           f"depth cut from {full.n_layers}: the full depth does not fit")
    print(f"  olmoe-1b-7b: {cfg.n_layers} of {full.n_layers} layers ({cut}), "
          f"d_model {cfg.d_model}, {cfg.n_heads} heads, "
          f"{cfg.moe.n_experts} experts top-{cfg.moe.top_k}, d_expert "
          f"{cfg.moe.d_expert}, vocab {cfg.vocab_size}, bf16, dispatch "
          f"{cfg.moe.dispatch}")
    t0 = time.perf_counter()
    eng = ServeEngine(cfg, mesh, batch=BATCH, prompt_len=PROMPT,
                      max_seq=MAX_SEQ, seed=args.seed)
    print(f"  MoE layers take: {dispatch_taken(eng)}")
    jax.block_until_ready(eng.params)
    init_s = time.perf_counter() - t0
    prompts = np.random.default_rng(args.seed).integers(
        0, cfg.vocab_size, (BATCH, PROMPT)).astype(np.int32)
    toks, stats, cold_s, _ = serve_run(eng, prompts, cfg.vocab_size)
    peak = peak_bytes(jax.devices()[0])
    print(f"  init {init_s:.2f} s; first generate {cold_s:.2f} s; backend "
          f"compile (memory check, init, steps) "
          f"{counters.compile_s - c0:.2f} s")
    print(f"  batch {BATCH} x prompt {PROMPT}, {NEW_TOKENS} tokens each: "
          f"prefill {stats.prefill_seconds * 1e3:.3f} ms, decode "
          f"{stats.decode_seconds_per_token * 1e3:.3f} ms/token")
    print(f"  peak_bytes_in_use {peak}")
    print(f"  tokens in vocab, logits finite; prompt 0 -> {toks[0].tolist()}")


# ---------------------------------------------------------------------------
# four-chip phases
# ---------------------------------------------------------------------------


def cross_chip_exchange_phase(args):
    """P=4 alltoallv arms against the numpy oracle and the non-persistent
    baseline, on a dense and a banded count matrix."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import alltoallv_init
    from repro.core.baseline import make_nonpersistent
    from repro.launch.mesh import make_mesh

    p = 4
    rng = np.random.default_rng(args.seed)
    flat = make_mesh((p,), ("x",))
    grid = make_mesh((2, 2), ("o", "i"))
    for name, counts in [("dense", rng.integers(1, 40, (p, p))),
                         ("banded", banded_counts(p, rng))]:
        print(f"  {name} counts {counts.tolist()}")
        case = exchange_case(counts, flat, "x")
        x, expect, n_valid, send_rows, recv_rows = case
        cap = alltoallv_init(counts, (FEATURE,), np.float32, flat,
                             axis="x").capacity
        exe = make_nonpersistent(flat, axis="x", p=p, capacity=cap,
                                 send_rows=send_rows, recv_rows=recv_rows,
                                 feature_shape=(FEATURE,), dtype=jnp.float32)
        cnts = jax.device_put(jnp.asarray(counts.reshape(-1), jnp.int32),
                              NamedSharding(flat, P("x")))
        base = np.asarray(exe(x, cnts)).reshape(p, recv_rows, FEATURE)
        check_rows(f"{name} baseline", base, expect, n_valid)
        print(f"  {name} baseline (non-persistent): bit-exact vs oracle")
        arms = [("fence", "jnp"), ("fence", "fused"), ("lock", "jnp"),
                ("ragged", "jnp"), ("auto", "jnp")]
        for variant, pack_impl in arms:
            got = run_plan(f"{name} P=4 {variant}/{pack_impl}", counts, flat,
                           "x", variant, pack_impl, case)
            check_rows(f"{name} {variant}/{pack_impl} vs baseline", got, base,
                       n_valid)
        gcase = exchange_case(counts, grid, ("o", "i"))
        for pack_impl in ("jnp", "fused"):
            got = run_plan(f"{name} (2,2) fence_hierarchy/{pack_impl}", counts,
                           grid, ("o", "i"), "fence_hierarchy", pack_impl,
                           gcase)
            check_rows(f"{name} fence_hierarchy/{pack_impl} vs baseline",
                       got, base, n_valid)


def rma_kernel_phase(args):
    """The standalone fence and lock remote-DMA kernels at P=4."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.kernels import ops, ref
    from repro.launch.mesh import make_mesh

    p, cap = 4, 24
    mesh = make_mesh((p,), ("x",))
    rng = np.random.default_rng(args.seed)
    for dtype in (jnp.float32, jnp.bfloat16):
        packed = np.asarray(jnp.asarray(
            rng.standard_normal((p, p * cap, FEATURE)), dtype))
        want = ref.a2a_bucketed_ref(packed, p, cap)
        xg = jax.device_put(jnp.asarray(packed.reshape(p * p * cap, FEATURE)),
                            NamedSharding(mesh, P("x")))
        for variant in ("fence", "lock"):
            f = shard_map(
                lambda t, v=variant: ops.rma_alltoallv(
                    t, variant=v, p=p, capacity=cap, axis="x",
                    mesh_axes=("x",)),
                mesh=mesh, in_specs=P("x"), out_specs=P("x"), check_vma=False)
            got = np.asarray(jax.jit(f)(xg)).reshape(want.shape)
            if not np.array_equal(got, want):
                raise AssertionError(f"rma {variant} {dtype.__name__} differs "
                                     f"from the bucket-transpose oracle")
            print(f"  rma_alltoallv {variant} {jnp.dtype(dtype).name}: "
                  f"bit-exact vs oracle")


def ep_layer_phase(args):
    """One olmoe-1b-7b MoE layer with experts over four chips, bf16, at
    dropless capacity: persistent_a2a (exchange + Pallas gather-matmul)
    against gspmd token by token (within LAYER_TOKEN_TOL), and each against
    an f32 reference at highest matmul precision (the persistent path no
    less accurate than gspmd, within 2x)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get
    from repro.launch.mesh import make_mesh
    from repro.models import moe as moe_mod
    from repro.parallel.sharding import DEFAULT_RULES, ParamFactory, axis_rules

    cfg = get("olmoe-1b-7b")
    d, tokens = cfg.d_model, BATCH * PROMPT
    base = dataclasses.replace(
        cfg.moe, capacity_factor=cfg.moe.n_experts / cfg.moe.top_k)
    mesh = make_mesh((1, 4), ("data", "model"))
    x32 = jnp.asarray(np.random.default_rng(args.seed).standard_normal(
        (1, tokens, d)), jnp.float32)

    def layer(params, x, dispatch):
        m = dataclasses.replace(base, dispatch=dispatch)
        plan = moe_mod.MoEDispatchPlan.build(m, tokens, mesh, d_model=d,
                                             dtype=x.dtype)
        # Weights are arguments: closed over, they would be baked into the
        # executable as gigabytes of constants.
        y, _ = jax.jit(lambda p, xx: moe_mod.apply_moe(p, xx, m, plan))(
            params, x)
        return np.asarray(y, np.float32)

    with axis_rules(DEFAULT_RULES, mesh):
        f = ParamFactory(jax.random.key(args.seed), jnp.float32)
        moe_mod.init_moe(f.scope("moe"), d, base)
        p32 = f.params["moe"]
        p16 = jax.tree.map(lambda a: a.astype(jnp.bfloat16), p32)
        with jax.default_matmul_precision("highest"):
            ref = layer(p32, x32, "gspmd")
        y = {dispatch: layer(p16, x32.astype(jnp.bfloat16), dispatch)
             for dispatch in ("persistent_a2a", "gspmd")}
    err = {k: float(np.linalg.norm(v - ref) / np.linalg.norm(ref))
           for k, v in y.items()}
    a, b = (y[k].reshape(tokens, d) for k in ("persistent_a2a", "gspmd"))
    per_token = np.linalg.norm(a - b, axis=1) / np.linalg.norm(b, axis=1)
    n_over = int((per_token > LAYER_TOKEN_TOL).sum())
    print(f"  MoE layer, {tokens} tokens, capacity factor "
          f"{base.capacity_factor}: relative RMS error vs f32 reference "
          f"{err}")
    print(f"  persistent_a2a vs gspmd, bf16, per token relative L2 diff: "
          f"max {float(per_token.max())}, median "
          f"{float(np.median(per_token))}, {n_over} of {tokens} tokens over "
          f"{LAYER_TOKEN_TOL}; elementwise max abs diff "
          f"{float(np.abs(a - b).max())}")
    if n_over:
        raise AssertionError(f"{n_over} tokens differ between persistent_a2a "
                             f"and gspmd by more than {LAYER_TOKEN_TOL}")
    if not err["persistent_a2a"] <= 2 * err["gspmd"]:
        raise AssertionError(f"persistent_a2a layer error "
                             f"{err['persistent_a2a']} exceeds twice gspmd's")


def ep_serve_phase(args, counters):
    """olmoe-1b-7b with experts over four chips (--mesh 1,4): persistent_a2a
    against gspmd on the same prompts and weights.

    The two dispatchers drop overflow tokens differently at the published
    capacity factor (persistent_a2a caps each source rank's bucket per
    expert, gspmd caps each expert over all tokens), so their tokens are
    compared there and only reported.  At capacity factor n_experts / top_k
    neither drops a token — the source model's dropless routing — and they
    must then generate the same greedy tokens."""
    import jax
    import numpy as np

    from repro.configs import get
    from repro.launch.mesh import make_mesh
    from repro.serve import ServeEngine

    cfg = get("olmoe-1b-7b")
    mesh = make_mesh((1, 4), ("data", "model"))
    prompts = np.random.default_rng(args.seed).integers(
        0, cfg.vocab_size, (BATCH, PROMPT)).astype(np.int32)
    params = None
    for label, cf in (("published", cfg.moe.capacity_factor),
                      ("dropless", cfg.moe.n_experts / cfg.moe.top_k)):
        out = {}
        for dispatch in ("persistent_a2a", "gspmd"):
            arm = dataclasses.replace(cfg, moe=dataclasses.replace(
                cfg.moe, dispatch=dispatch, capacity_factor=cf))
            c0 = counters.compile_s
            eng = ServeEngine(arm, mesh, batch=BATCH, prompt_len=PROMPT,
                              max_seq=MAX_SEQ, seed=args.seed, params=params)
            params = eng.params
            toks, stats, cold_s, logits = serve_run(eng, prompts,
                                                    cfg.vocab_size)
            out[dispatch] = (toks, logits)
            print(f"  capacity factor {cf} ({label}), {dispatch} "
                  f"({dispatch_taken(eng)}): first "
                  f"generate {cold_s:.2f} s (backend compile "
                  f"{counters.compile_s - c0:.2f} s); prefill "
                  f"{stats.prefill_seconds * 1e3:.3f} ms, decode "
                  f"{stats.decode_seconds_per_token * 1e3:.3f} ms/token")
        (ta, la), (tb, lb) = out["persistent_a2a"], out["gspmd"]
        print(f"  capacity factor {cf} ({label}): prefill logits max abs diff "
              f"{float(np.abs(la - lb).max())}; greedy tokens agree on "
              f"{float((ta == tb).mean())} of {ta.size}")
        if label == "dropless" and not np.array_equal(ta, tb):
            raise AssertionError("dropless persistent_a2a and gspmd generate "
                                 "different greedy tokens")
    print(f"  peak_bytes_in_use per chip "
          f"{[peak_bytes(d) for d in jax.devices()]}")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the cross-chip path and its references")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    src = ROOT / "src"
    if not (src / "repro").is_dir():
        print(f"chip_smoke: no src/repro package beside {Path(__file__).name}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))

    import jax

    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {d0.platform} "
              f"({d0.device_kind})", file=sys.stderr)
        return 3
    print(f"device: platform {d0.platform}, kind {d0.device_kind}, "
          f"count {len(devices)}")
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices",
              file=sys.stderr)
        return 3

    from repro.launch.compile_cache import use_compile_cache

    print(f"compile cache: {use_compile_cache()}")
    counters = CompileCounters(jax)
    if args.chips == 1:
        phases = [("engine", lambda: engine_phase(args)),
                  ("kernels", lambda: kernel_phase(args)),
                  ("serve", lambda: serve_phase(args, counters))]
    else:
        phases = [("exchange", lambda: cross_chip_exchange_phase(args)),
                  ("rma kernels", lambda: rma_kernel_phase(args)),
                  ("ep moe layer", lambda: ep_layer_phase(args)),
                  ("ep serve", lambda: ep_serve_phase(args, counters))]
    for name, run in phases:
        print(f"phase {name}:", flush=True)
        t0 = time.perf_counter()
        run()
        print(f"phase {name}: ok in {time.perf_counter() - t0:.2f} s",
              flush=True)
    print(f"backend compile {counters.compile_s:.2f} s; persistent cache "
          f"{counters.hits} hits, {counters.misses} misses")
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
