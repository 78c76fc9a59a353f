"""Closed loop of `ServeEngine.generate` calls, one call in flight.

Set-up: the program's model configuration built from the configuration
file, its parameter tree filled on the device in one jitted call from the
seed (`refs.moe_lm.program_params`), a `ServeEngine` on a (1, 1) mesh as
`launch/serve.py` builds it, and one untimed call that warms every shape
the window uses.  The window: `generate(prompts, new_tokens)` again and
again on fresh seeded prompts (greedy), until `--seconds` have passed; the
call in flight then finishes and all of its time counts.

Once the window has closed and the program's weights are freed, a seeded
sample of the finished requests is run through the plain float32 reference,
teacher-forced on the served tokens, and each served token is judged by how
far its reference logit lies below the reference's best at that position
(its gap).  Two numbers are compared: the widest gap, which a single wrong
token raises, and the mean gap over the served tokens, which a lower
precision raises (the widest gap is set by rare routing near-ties that any
precision flips, so it cannot tell bf16 from fp8; see PERF.md).
"""

from __future__ import annotations

import dataclasses
import gc
import sys
import time

import numpy as np

from chipbench import harness, work


def program_config(m: dict):
    """The program's ModelConfig for the configuration file's sizes."""
    from repro.configs import get
    base = get(m["program_arch"])
    moe = dataclasses.replace(
        base.moe, n_experts=m["num_experts"], top_k=m["num_experts_per_tok"],
        d_expert=m["intermediate_size"], capacity_factor=m["capacity_factor"])
    return dataclasses.replace(
        base, n_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        n_heads=m["num_attention_heads"], n_kv_heads=m["num_key_value_heads"],
        d_head=m["head_dim"], d_ff=m["intermediate_size"],
        vocab_size=m["vocab_size"], rope_theta=m["rope_theta"],
        qk_norm=True, tie_embeddings=m["tie_word_embeddings"],
        norm="rmsnorm", activation="swiglu", param_dtype=m["dtype"], moe=moe)


def run(spec: harness.Spec) -> harness.Outcome:
    import jax

    from repro.launch.mesh import make_mesh
    from repro.models import api as model_api
    from repro.serve import ServeEngine

    import importlib
    ref = importlib.import_module(f"chipbench.refs.{spec.config['reference']}")
    m, tr = spec.config["model"], spec.traffic
    b, s, n = int(tr["batch"]), int(tr["prompt_len"]), int(tr["new_tokens"])
    cfg = program_config(m)
    mesh = make_mesh((1, 1), ("data", "model"))
    params_abs, _ = model_api.init_model(None, cfg, abstract=True)
    params = ref.program_params(m, spec.seed, params_abs)
    eng = ServeEngine(cfg, mesh, batch=b, prompt_len=s, max_seq=s + n + 8,
                      params=params)
    del params
    rng = np.random.default_rng(spec.seed)
    prompts = rng.integers(0, m["vocab_size"], (int(tr["distinct_prompts"]), b, s),
                           dtype=np.int32)
    eng.generate(prompts[-1], n)
    setup_s = time.perf_counter() - spec.t_start

    annotate = spec.trace_dir is not None
    served, call_s = [], []
    with spec.window():
        tw0 = time.perf_counter()
        while True:
            i = len(served)
            t0 = time.perf_counter()
            if annotate:
                with jax.profiler.TraceAnnotation("generate"):
                    toks, _ = eng.generate(prompts[i % len(prompts)], n)
            else:
                toks, _ = eng.generate(prompts[i % len(prompts)], n)
            served.append(toks)
            call_s.append(time.perf_counter() - t0)
            if time.perf_counter() - tw0 >= spec.seconds:
                break
        window_s = time.perf_counter() - tw0
    calls = len(served)
    peak = harness.memory_peak(mesh.devices.flat)
    eng.params = None
    del eng
    gc.collect()

    pick = np.random.default_rng([spec.seed, 3]).choice(
        calls, size=min(int(tr["check_requests"]), calls), replace=False)
    requests = [np.concatenate([prompts[i % len(prompts)], served[i]], axis=1)
                for i in sorted(pick)]
    t_ref = time.perf_counter()
    logits, drops = ref.served_logits(m, spec.seed, requests, s)
    t_ref = time.perf_counter() - t_ref
    gaps = np.concatenate([ref.token_gaps(lg, req[:, s:]).ravel()
                           for lg, req in zip(logits, requests)])
    checks = []
    for name, value in (("max_logit_gap", float(gaps.max())),
                        ("mean_logit_gap", float(gaps.mean()))):
        limit = float(tr["limits"][name])
        checks.append(harness.Check(name, value, limit, value <= limit))
    failed = int(not all(c.ok for c in checks))
    print("generate seconds: " + " ".join(f"{x:.4f}" for x in call_s),
          file=sys.stderr)

    metrics = {"request_ms": window_s / calls * 1e3, "setup_s": setup_s}
    layer = {"calls": calls,
             "prefill_flops": work.prefill_flops(m, b, s),
             "decode_flops_per_step": work.decode_flops(m, b, s, n) / max(n - 1, 1),
             "tokens_checked": int(gaps.size),
             "reference_dropped": drops, "reference_s": t_ref,
             "requests": requests, "reference_logits": logits}
    return harness.Outcome(metrics, calls, failed, checks, layer, peak)
