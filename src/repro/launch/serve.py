"""Serving launcher: batched generation with KV caches.

    PYTHONPATH=src python -m repro.launch.serve --arch olmo-1b --reduced \
        --batch 4 --prompt-len 32 --tokens 16
"""

from __future__ import annotations

import argparse


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", required=True)
    p.add_argument("--reduced", action="store_true")
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--prompt-len", type=int, default=32)
    p.add_argument("--tokens", type=int, default=16)
    p.add_argument("--max-seq", type=int, default=None)
    p.add_argument("--mesh", default="1,1")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--wire-codec", default=None,
                   choices=["identity", "bf16", "int8", "fp8"],
                   help="wire codec for the MoE EP exchange; lossy codecs "
                        "require --codec-tol")
    p.add_argument("--codec-tol", type=float, default=None,
                   help="declared relative error tolerance for lossy wire "
                        "compression of routed activations")
    p.add_argument("--plan-store", default=None, metavar="DIR_OR_URL",
                   help="persistent plan store, set as the process default "
                        "(repro.planstore.configure): a directory, "
                        "fsremote://PATH, or tiered:local=DIR,remote=URL — "
                        "a fresh replica pointed at a prewarmed fleet store "
                        "warm-starts its very first INIT; any alltoallv_init "
                        "in this process — including the built-in "
                        "plan-backed MoE EP dispatch — reuses artifacts of "
                        "previous serving processes")
    p.add_argument("--trace", default=None, metavar="PATH",
                   help="enable span tracing (repro.obs) and export a "
                        "Chrome-trace JSON to PATH at exit — INIT spans plus "
                        "the serve.* EXECUTE spans of each generate call")
    p.add_argument("--metrics-port", type=int, default=None,
                   help="serve Prometheus metrics on 127.0.0.1:PORT for the "
                        "lifetime of the process (repro.obs.MetricsServer); "
                        "0 picks a free port")
    p.add_argument("--metrics-file", default=None, metavar="PATH",
                   help="write a Prometheus text-format metrics snapshot "
                        "to PATH at exit")
    args = p.parse_args(argv)
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()

    import dataclasses

    import numpy as np

    if args.trace:
        from repro.obs import TRACER
        TRACER.enable()
    metrics_server = None
    if args.metrics_port is not None:
        from repro.obs import MetricsServer
        metrics_server = MetricsServer(args.metrics_port).start()
        print(f"metrics: http://127.0.0.1:{metrics_server.port}/metrics")

    from repro.configs import get, get_reduced
    from repro.launch.mesh import make_mesh
    from repro.serve import ServeEngine

    cfg = get_reduced(args.arch) if args.reduced else get(args.arch)
    if args.wire_codec or args.codec_tol is not None:
        assert cfg.moe is not None, f"{cfg.name} has no MoE layers"
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe,
            wire_codec=args.wire_codec or cfg.moe.wire_codec,
            codec_tol=(args.codec_tol if args.codec_tol is not None
                       else cfg.moe.codec_tol)))
    dims = tuple(int(d) for d in args.mesh.split(","))
    axes = ("pod", "data", "model")[-len(dims):]
    mesh = make_mesh(dims, axes)
    max_seq = args.max_seq or (args.prompt_len + args.tokens + 8)

    eng = ServeEngine(cfg, mesh, batch=args.batch, prompt_len=args.prompt_len,
                      max_seq=max_seq, seed=args.seed,
                      plan_store=args.plan_store)
    rng = np.random.default_rng(args.seed)
    prompts = rng.integers(0, cfg.vocab_size,
                           (args.batch, args.prompt_len)).astype(np.int32)
    frames = None
    if cfg.family == "audio":
        frames = rng.standard_normal(
            (args.batch, args.prompt_len, cfg.d_model)).astype(np.float32) * 0.02
        prompts = prompts[:, :8]
    toks, stats = eng.generate(prompts, args.tokens, frames=frames)
    print(f"generated {toks.shape}: prefill {stats.prefill_seconds*1e3:.1f} ms, "
          f"decode {stats.decode_seconds_per_token*1e3:.2f} ms/token")
    print(toks[:2])
    if args.plan_store:
        from repro.core import init_stats
        print("plan-store init stats:", init_stats())
    if args.trace:
        from repro.obs import write_trace
        trace = write_trace(args.trace)
        print(f"trace: {len(trace['traceEvents'])} events -> {args.trace}")
    if args.metrics_file:
        from repro.obs import write_metrics
        text = write_metrics(args.metrics_file)
        print(f"metrics: {len(text.splitlines())} lines -> {args.metrics_file}")
    if metrics_server is not None:
        metrics_server.stop()
    return stats


if __name__ == "__main__":
    main()
