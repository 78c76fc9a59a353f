"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch olmoe-1b-7b \
        --reduced --steps 20 --mesh 1,1 --ckpt-dir /tmp/ckpt

Full-size configs on the production mesh are exercised through the dry-run;
``--reduced`` runs the same code path end-to-end with the smoke-scale
config.
"""

from __future__ import annotations

import argparse
import logging

import jax


def main(argv=None) -> dict:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", required=True)
    p.add_argument("--shape", default="train_4k")
    p.add_argument("--reduced", action="store_true",
                   help="smoke-scale config (CPU-runnable)")
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--global-batch", type=int, default=None)
    p.add_argument("--seq-len", type=int, default=None)
    p.add_argument("--mesh", default="1,1",
                   help="data,model (2 dims) or pod,data,model (3)")
    p.add_argument("--dispatch", default=None,
                   choices=["persistent_a2a", "nonpersistent_a2a", "gspmd"])
    p.add_argument("--a2a-variant", default=None,
                   choices=["fence", "lock", "fence_hierarchy", "auto"])
    p.add_argument("--overlap-chunks", type=int, default=None,
                   help="chunked dispatch->FFN->combine pipeline depth for "
                        "MoE EP dispatch (1 = no overlap; clamped to the "
                        "capacity geometry)")
    p.add_argument("--wire-codec", default=None,
                   choices=["identity", "bf16", "int8", "fp8"],
                   help="wire codec for the MoE EP exchange "
                        "(parallel.wirecodec); lossy codecs additionally "
                        "require --codec-tol covering the codec's declared "
                        "relative error bound")
    p.add_argument("--codec-tol", type=float, default=None,
                   help="declared relative error tolerance for lossy wire "
                        "compression of routed activations; with "
                        "--a2a-variant auto it widens the INIT sweep to "
                        "(variant, codec) arms")
    p.add_argument("--grad-compression", action="store_true",
                   help="int8 + error-feedback data-parallel gradient sync "
                        "(parallel.compression); the EF residual rides in "
                        "the optimizer state and checkpoints with it")
    p.add_argument("--grad-sync", default="default",
                   choices=["default", "persistent_rs"],
                   help="data-parallel gradient sync wire: 'persistent_rs' "
                        "rides a persistent reduce-scatter + allgatherv "
                        "plan pair (train.grad.persistent_rs_sync) that "
                        "warm-starts from --plan-store; composes with "
                        "--grad-compression (the int8+EF payload rides the "
                        "plan wire)")
    p.add_argument("--rules", default="default",
                   choices=["default", "long_context", "decode", "pure_dp",
                            "hier_ep"],
                   help="sharding-rule launch profile (parallel.sharding."
                        "RULE_PROFILES); 'hier_ep' widens the experts rule "
                        "to the (pod, model) axis pair for hierarchical "
                        "expert parallelism")
    p.add_argument("--schedule", default=None,
                   choices=["cosine", "linear", "wsd", "constant"])
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--ckpt-dir", default=None)
    p.add_argument("--ckpt-every", type=int, default=50)
    p.add_argument("--log-every", type=int, default=10)
    p.add_argument("--no-zero1", action="store_true")
    p.add_argument("--micro", type=int, default=1)
    p.add_argument("--plan-store", default=None, metavar="DIR_OR_URL",
                   help="persistent plan store, set as the process default "
                        "(repro.planstore.configure): a directory, "
                        "fsremote://PATH (remote object-store semantics), or "
                        "tiered:local=DIR,remote=URL (local cache in front "
                        "of a fleet-shared remote).  Any alltoallv_init in "
                        "this process — including the built-in plan-backed "
                        "MoE EP dispatch — warm-starts from artifacts of "
                        "previous runs or a deploy-time prewarm (zero table "
                        "bakes, zero autotune bursts on a warm hit)")
    p.add_argument("--assert-warm-init", action="store_true",
                   help="exit non-zero unless every INIT in this run was "
                        "warm: zero autotune measurement bursts, zero table "
                        "bakes, at least one store hit (the CI warm-EP "
                        "contract for a second --plan-store run)")
    p.add_argument("--elastic", action="store_true",
                   help="elastic-mesh resume: capture this run's INIT "
                        "requests into <ckpt-dir>/init_requests.json; when "
                        "a prior capture exists and its mesh differs from "
                        "--mesh, reshard+prewarm those plans for the new "
                        "geometry (runtime.replan.reshard_plans) before the "
                        "bundle is built, so the resumed run rebuilds warm")
    p.add_argument("--replan-at", type=int, default=None, metavar="STEP",
                   help="force one online re-plan of the EP dispatch "
                        "decision after STEP completes (re-measure in a "
                        "sandbox, hot-swap on a changed verdict)")
    p.add_argument("--replan", action="store_true",
                   help="arm the skew monitor: sustained per-step skew "
                        "attributable to the EP dispatch plan triggers an "
                        "online re-plan")
    p.add_argument("--chaos", default=None, metavar="SPEC",
                   help="deterministic fault injection for the run "
                        "(runtime.chaos.ChaosInjector.parse), e.g. "
                        "'seed=7,fail_step=5,stall_steps=3-4,"
                        "stall_seconds=0.1'")
    p.add_argument("--assert-recovery", action="store_true",
                   help="exit non-zero unless the run completed all steps "
                        "cleanly AND every injected --chaos fault was "
                        "recovered (plus, with --replan-at, the forced "
                        "re-plan ran)")
    p.add_argument("--trace", default=None, metavar="PATH",
                   help="enable span tracing (repro.obs) and export a "
                        "Chrome-trace JSON to PATH at exit — INIT bakes/"
                        "bursts/store ops, per-epoch EXECUTE, replan/swap "
                        "events; open in Perfetto or chrome://tracing")
    p.add_argument("--trace-jsonl", default=None, metavar="PATH",
                   help="also append the raw span records as JSONL to PATH "
                        "(implies tracing)")
    p.add_argument("--metrics-file", default=None, metavar="PATH",
                   help="write a Prometheus text-format metrics snapshot "
                        "(repro.obs.metrics) to PATH at exit")
    args = p.parse_args(argv)
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()

    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")

    if args.trace or args.trace_jsonl:
        from repro.obs import TRACER
        TRACER.enable()

    if args.plan_store:
        from repro import planstore
        planstore.configure(args.plan_store)

    import dataclasses

    from repro.configs import SHAPES, ShapeConfig, get, get_reduced
    from repro.launch import steps as steps_mod
    from repro.launch.mesh import make_mesh
    from repro.train import ScheduleConfig, Trainer, TrainerConfig

    cfg = get_reduced(args.arch) if args.reduced else get(args.arch)
    if (args.dispatch or args.a2a_variant or args.overlap_chunks
            or args.wire_codec or args.codec_tol is not None):
        assert cfg.moe is not None, f"{cfg.name} has no MoE layers"
        moe = dataclasses.replace(
            cfg.moe,
            dispatch=args.dispatch or cfg.moe.dispatch,
            a2a_variant=args.a2a_variant or cfg.moe.a2a_variant,
            overlap_chunks=args.overlap_chunks or cfg.moe.overlap_chunks,
            wire_codec=args.wire_codec or cfg.moe.wire_codec,
            codec_tol=(args.codec_tol if args.codec_tol is not None
                       else cfg.moe.codec_tol))
        cfg = dataclasses.replace(cfg, moe=moe)

    base_shape = SHAPES[args.shape]
    seq = args.seq_len or (256 if args.reduced else base_shape.seq_len)
    gb = args.global_batch or (8 if args.reduced else base_shape.global_batch)
    shape = ShapeConfig(args.shape, base_shape.kind, seq, gb)

    dims = tuple(int(d) for d in args.mesh.split(","))
    axes = ("pod", "data", "model")[-len(dims):]
    mesh = make_mesh(dims, axes)

    sched_kind = args.schedule or ("wsd" if cfg.name.startswith("minicpm") else "cosine")
    sched = ScheduleConfig(kind=sched_kind, peak_lr=args.lr,
                           warmup_steps=max(args.steps // 10, 1),
                           total_steps=args.steps,
                           decay_steps=max(args.steps // 5, 1))
    from repro.parallel.sharding import RULE_PROFILES

    # Elastic resume: before building anything, check whether a prior run
    # of this checkpoint dir captured INIT requests on a DIFFERENT mesh —
    # if so, project those plans onto today's geometry and prewarm the
    # store, then reset INIT stats so --assert-warm-init judges only the
    # bundle build that follows (the reshard replay is one-time INIT work
    # by design, exactly like a deploy-time prewarm).
    import json
    import os
    req_path = (os.path.join(args.ckpt_dir, "init_requests.json")
                if args.elastic and args.ckpt_dir else None)
    if args.elastic and req_path is None:
        raise SystemExit("--elastic requires --ckpt-dir")
    if req_path and os.path.exists(req_path):
        from repro.ckpt.reshard import mesh_axis_sizes
        from repro.runtime import replan as replan_mod
        with open(req_path) as fh:
            prior = json.load(fh)
        if prior.get("mesh") != mesh_axis_sizes(mesh) and prior.get("requests"):
            from repro import planstore
            from repro.core import reset_init_stats
            report = replan_mod.reshard_plans(
                prior["requests"], mesh, store=planstore.default_store())
            print(f"elastic resume: mesh {prior['mesh']} -> "
                  f"{mesh_axis_sizes(mesh)}; resharded "
                  f"{len(report['resharded'])} plan(s), skipped "
                  f"{len(report['skipped'])}:", report)
            reset_init_stats()

    chaos = None
    if args.chaos:
        from repro.runtime.chaos import ChaosInjector
        chaos = ChaosInjector.parse(args.chaos)

    def build_bundle():
        return steps_mod.make_train_bundle(
            cfg, shape, mesh, sched=sched, zero1=not args.no_zero1,
            n_micro=args.micro, rules=RULE_PROFILES[args.rules],
            grad_compression=args.grad_compression,
            grad_sync=args.grad_sync)

    if args.elastic:
        from repro.ckpt.reshard import mesh_axis_sizes
        from repro.core import capture_init_requests
        with capture_init_requests() as reqs:
            bundle = build_bundle()
        os.makedirs(args.ckpt_dir, exist_ok=True)
        with open(req_path, "w") as fh:
            json.dump({"mesh": mesh_axis_sizes(mesh),
                       "requests": list(reqs)}, fh)
        print(f"elastic: captured {len(reqs)} INIT request(s) -> {req_path}")
    else:
        bundle = build_bundle()
    trainer = Trainer(bundle, TrainerConfig(
        n_steps=args.steps, ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every, log_every=args.log_every,
        replan=args.replan, replan_at=args.replan_at), chaos=chaos)
    result = trainer.run()
    print("train finished:", result)
    # Export observability artifacts BEFORE the assert gates below — a
    # failed assertion is exactly when the trace is most wanted.
    if args.trace:
        from repro.obs import write_trace
        trace = write_trace(args.trace)
        print(f"trace: {len(trace['traceEvents'])} events -> {args.trace}")
    if args.trace_jsonl:
        from repro.obs import write_jsonl
        n = write_jsonl(args.trace_jsonl)
        print(f"trace-jsonl: {n} events -> {args.trace_jsonl}")
    if args.metrics_file:
        from repro.obs import write_metrics
        text = write_metrics(args.metrics_file)
        print(f"metrics: {len(text.splitlines())} lines -> {args.metrics_file}")
    if args.assert_recovery:
        injected = sum((result.get("chaos") or {}).values())
        problems = []
        if result["final_step"] != args.steps:
            problems.append(f"run stopped at step {result['final_step']}"
                            f"/{args.steps}")
        if injected == 0:
            problems.append("no chaos faults were injected (nothing to "
                            "recover from — the assertion would be vacuous)")
        faults = sum((result.get("chaos") or {}).get(k, 0)
                     for k in ("step", "device", "window"))
        if faults and len(result["recoveries"]) < faults:
            problems.append(f"{faults} injected failure(s) but only "
                            f"{len(result['recoveries'])} recoveries")
        if args.replan_at is not None and not result["replans"]:
            problems.append("forced re-plan never ran")
        if problems:
            print("ASSERT-RECOVERY FAILED:", "; ".join(problems))
            raise SystemExit(4)
        print(f"ASSERT-RECOVERY OK: {injected} fault(s) injected, "
              f"{len(result['recoveries'])} recovered, "
              f"{len(result['replans'])} re-plan(s)")
    if args.plan_store or args.assert_warm_init:
        from repro.core import init_stats
        stats = init_stats()
        print("plan-store init stats:", stats)
        if args.assert_warm_init:
            cold = {k: stats[k] for k in ("autotune_bursts", "table_bakes")
                    if stats[k] != 0}
            if cold or stats["store_hits"] == 0:
                print("ASSERT-WARM-INIT FAILED:", stats)
                raise SystemExit(3)
            print("ASSERT-WARM-INIT OK: zero bursts, zero bakes, "
                  f"{stats['store_hits']} store hits")
    return result


if __name__ == "__main__":
    main()
