"""Readings that the limits of `correct` are set from, on the chip.

    python3 chipbench/calibrate.py --workload <cell> --seeds 1,2,3 \
        --control-seeds 1,2,3 --seconds 3

For each seed, one run of the cell as the benchmark makes it (a short
window), and its compared numbers: the program's lower readings.  For each
control seed, the control's readings on the same kind of run: for a served
model, the float32 reference computed in float8_e4m3, read as the gap of
the token it puts first at each served position, and the faults a served
cell can have planted in its tokens; for the exchange, the program's own
bf16 wire codec switched on.  All runs share one process, so the compiled
programs load once.  Prints one JSON line per
reading and writes them to `chiprun_out/calibrate-<cell>.jsonl`.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from chipbench import harness  # noqa: E402


def serve_control(out, config, seed):
    """The control's readings on the run's requests: the float8 reference's
    first choice at each served position, judged against the reference."""
    import numpy as np
    from chipbench.refs import moe_lm as ref
    m = config["model"]
    reqs = out.layer["requests"]
    s = reqs[0].shape[1] - out.layer["reference_logits"][0].shape[1]
    low, _ = ref.served_logits(m, seed, reqs, s, quant="fp8")
    gaps = np.concatenate([ref.token_gaps(r, np.argmax(q, axis=-1)).ravel()
                           for r, q in zip(out.layer["reference_logits"], low)])
    return {"max_logit_gap": float(gaps.max()),
            "mean_logit_gap": float(gaps.mean())}


def serve_faults(out, config, seed):
    """Readings of the faults a served cell can have, planted in the served
    tokens and judged against the same reference logits: one token altered
    where it is produced, and half of the batch left out (its tokens 0)."""
    import numpy as np
    from chipbench.refs import moe_lm as ref
    vocab = config["model"]["vocab_size"]
    rng = np.random.default_rng([seed, 5])
    logits, reqs = out.layer["reference_logits"], out.layer["requests"]
    n = logits[0].shape[1]
    served = [r[:, -n:].copy() for r in reqs]
    read = {}
    alt = [t.copy() for t in served]
    r, b, i = (int(rng.integers(len(alt))), int(rng.integers(alt[0].shape[0])),
               int(rng.integers(n)))
    alt[r][b, i] = (alt[r][b, i] + 1) % vocab
    half = [t.copy() for t in served]
    for t in half:
        t[t.shape[0] // 2:] = 0
    for name, toks in (("fault_altered", alt), ("fault_half", half)):
        gaps = np.concatenate([ref.token_gaps(lg, t).ravel()
                               for lg, t in zip(logits, toks)])
        read[name] = {"max_logit_gap": float(gaps.max()),
                      "mean_logit_gap": float(gaps.mean())}
    return read


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", default="")
    p.add_argument("--control-seeds", default="")
    p.add_argument("--seconds", type=float, default=3.0)
    args = p.parse_args()
    seeds = [int(x) for x in args.seeds.split(",") if x]
    cseeds = [int(x) for x in args.control_seeds.split(",") if x]
    out_dir = os.path.join(_ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    log = open(os.path.join(out_dir, f"calibrate-{args.workload}.jsonl"), "a")

    def emit(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        log.write(line + "\n")
        log.flush()

    _, _, config, _ = harness.load_cell(args.workload)
    t_start = T_START
    for seed in sorted(set(seeds) | set(cseeds)):
        t0 = time.perf_counter()
        res, out = harness.execute(args.workload, seed, args.seconds, False,
                                   t_start=t_start)
        t_start = time.perf_counter()
        rec = {"seed": seed, "kind": "program", "checks": res["checks"],
               "metrics": res["metrics"], "attempted": res["attempted"],
               "seconds": time.perf_counter() - t0}
        rec.update({k: v for k, v in out.layer.items()
                    if k in ("tokens_checked", "reference_dropped", "reference_s",
                             "answers_checked", "variant")})
        emit(rec)
        if seed in cseeds and config["driver"] == "serve":
            t0 = time.perf_counter()
            emit({"seed": seed, "kind": "control_fp8",
                  **serve_control(out, config, seed),
                  "seconds": time.perf_counter() - t0})
            for kind, read in serve_faults(out, config, seed).items():
                emit({"seed": seed, "kind": kind, **read})
    if cseeds and config["driver"] == "exchange":
        import repro.core as core
        plain = core.alltoallv_init

        def bf16_codec(*a, **kw):
            return plain(*a, codec="bf16", error_tol=2.0 ** -8, **kw)

        core.alltoallv_init = bf16_codec
        try:
            for seed in cseeds:
                res, out = harness.execute(args.workload, seed, args.seconds,
                                           False, t_start=time.perf_counter())
                emit({"seed": seed, "kind": "control_bf16_codec",
                      "checks": res["checks"], "correct": res["correct"]})
        finally:
            core.alltoallv_init = plain


if __name__ == "__main__":
    main()
