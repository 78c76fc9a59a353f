"""Runs of the benchmark's cells on the CPU at a small size, with the timed
path intact, with a control in the program's place, or broken underneath.

    python chipbench/tests/_cpu_cases.py exchange DIR   # 4 virtual devices
    python chipbench/tests/_cpu_cases.py serve

Each run skips the harness's look for a chip and drives the rest of it
(set-up, window, comparison); the last line printed is a JSON object
`{case: {"correct": bool, "checks": {...}}}`.  Used by the tests beside it.
"""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

EXCHANGE_CELL = "a2a.hugetrace.1mib"     # in chipbench/pending/, not yet measured
SERVE_CELL = "olmoe.decode.b8"
# A tenth of a second of epochs; 16 KiB per pair; a sample every 3 epochs.
EXCHANGE_SMALL = {"traffic": {"mean_pair_bytes": 16 * 1024, "sample_gap": 3}}
SEED = 2 ** 33 + 17


def small_model(config: dict) -> dict:
    """The configuration's model at test widths: same structure, 6 layers."""
    m = dict(config["model"])
    d, h, dh, f = 128, 4, 32, 64
    m.update(num_hidden_layers=6, hidden_size=d, num_attention_heads=h,
             num_key_value_heads=h, head_dim=dh, intermediate_size=f,
             num_experts=8, num_experts_per_tok=2, vocab_size=512)
    w = {k: list(v) for k, v in m["weights"].items()}
    for k, (std, mean) in w.items():
        if mean == 0.0 and k != "embed/table":
            w[k] = [d ** -0.5, 0.0]
    w["moe/w_down"] = [f ** -0.5, 0.0]
    w["attn/wo"] = [(h * dh) ** -0.5, 0.0]
    m["weights"] = w
    return m


def serve_small(config: dict) -> dict:
    return {"config": {"model": small_model(config)},
            "traffic": {"batch": 8, "prompt_len": 16, "new_tokens": 16,
                        "check_requests": 1}}


def pending_root(dst: str) -> str:
    """A checkout at `dst` whose BENCHMARK.json also holds the cells of
    `chipbench/pending/` (the code is the same: `chipbench` is a link)."""
    import glob
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for path in sorted(glob.glob(os.path.join(ROOT, "chipbench", "pending", "*.json"))):
        with open(path) as f:
            extra = json.load(f)
        for key in ("configs", "workloads", "end_to_end", "per_layer"):
            bench[key] = bench[key] + extra[key]
    os.makedirs(dst, exist_ok=True)
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    link = os.path.join(dst, "chipbench")
    if not os.path.exists(link):
        os.symlink(os.path.join(ROOT, "chipbench"), link)
    return dst


def _run(harness, cell, overrides, seconds=0.1, seed=SEED, root=None):
    kw = {"root": harness.Path(root)} if root else {}
    res = harness.run(cell, seed, seconds, False, accelerator=False,
                      overrides=overrides, **kw)
    return {"correct": res["correct"], "checks": res["checks"]}


def exchange_cases(root: str) -> dict:
    import repro.core as core
    from repro.core import plan as plan_mod
    from repro.core import variants
    from chipbench import harness

    out = {"clean": _run(harness, EXCHANGE_CELL, EXCHANGE_SMALL, root=root)}
    cls = plan_mod.ExchangePlan
    start, fence, init = cls.start, variants.fence_exchange, core.alltoallv_init

    def stale(self, x):                 # a step that returns its state unchanged
        if getattr(self, "_kept", None) is None:
            self._kept = start(self, x)
        return self._kept

    def half(self, x):                  # half of the rows left out
        y = start(self, x)
        return y.at[y.shape[0] // 2:].set(0)

    def altered(self, x):               # one answer altered where produced
        return start(self, x).at[0, 0].add(1.0)

    def bf16(*a, **kw):                 # control: the program's bf16 wire codec
        return init(*a, codec="bf16", error_tol=2.0 ** -8, **kw)

    def fresh(*a, **kw):                # a plan traced anew, not the cached one
        return init(*a, cache=plan_mod.PlanCache(), **kw)

    for name, patch in [("fault_stale", ("start", stale)),
                        ("fault_half", ("start", half)),
                        ("fault_altered", ("start", altered)),
                        ("fault_no_exchange", ("fence", lambda p, axis: p)),
                        ("control_bf16", ("init", bf16))]:
        kind, fn = patch
        if kind == "start":
            cls.start = fn
        elif kind == "fence":
            variants.fence_exchange = fn
            core.alltoallv_init = fresh
        else:
            core.alltoallv_init = fn
        try:
            out[name] = _run(harness, EXCHANGE_CELL, EXCHANGE_SMALL, root=root)
        finally:
            cls.start, variants.fence_exchange, core.alltoallv_init = \
                start, fence, init
    return out


def serve_cases() -> dict:
    import numpy as np

    from repro.models import attention
    from repro.serve import engine as engine_mod
    from chipbench import harness

    _, _, config, _ = harness.load_cell(SERVE_CELL)
    small = serve_small(config)
    vocab = small["config"]["model"]["vocab_size"]
    out = {"clean": _run(harness, SERVE_CELL, small)}
    gen, attn = engine_mod.ServeEngine.generate, attention.apply_attention

    def stale_cache(params, x, **kw):   # decode leaves the KV cache unchanged
        y, cache = attn(params, x, **kw)
        if kw.get("kv_cache") is not None and x.shape[1] == 1:
            cache = kw["kv_cache"]
        return y, cache

    def half(self, prompts, n, **kw):   # half of the batch left out
        toks, stats = gen(self, prompts, n, **kw)
        toks = np.array(toks)
        toks[toks.shape[0] // 2:] = 0
        return toks, stats

    def altered(self, prompts, n, **kw):  # one token altered where produced
        toks, stats = gen(self, prompts, n, **kw)
        toks = np.array(toks)
        toks[0, n // 2] = (toks[0, n // 2] + 1) % vocab
        return toks, stats

    for name, kind, fn in [("fault_stale", "attn", stale_cache),
                           ("fault_half", "gen", half),
                           ("fault_altered", "gen", altered)]:
        if kind == "attn":
            attention.apply_attention = fn
        else:
            engine_mod.ServeEngine.generate = fn
        try:
            out[name] = _run(harness, SERVE_CELL, small)
        finally:
            attention.apply_attention = attn
            engine_mod.ServeEngine.generate = gen
    from chipbench import calibrate
    limits = harness.load_cell(SERVE_CELL)[3]["limits"]
    for i in (1, 2, 3):                 # control: the reference in float8
        res, run = harness.execute(SERVE_CELL, SEED + i, 0.1, False,
                                   accelerator=False, overrides=small)
        read = calibrate.serve_control(run, small["config"], SEED + i)
        out[f"control_fp8_{i}"] = {
            "correct": all(read[k] <= limits[k] for k in read),
            "checks": {k: {"value": v, "limit": limits[k]}
                       for k, v in read.items()}}
    return out


def main(which: str):
    if which == "exchange":
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + " --xla_force_host_platform_device_count=4")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    cases = (exchange_cases(pending_root(sys.argv[2])) if which == "exchange"
             else serve_cases())
    print(json.dumps(cases))


if __name__ == "__main__":
    main(sys.argv[1])
