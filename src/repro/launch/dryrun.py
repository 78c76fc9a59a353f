"""Multi-pod dry-run: lower + compile every (arch x shape) on the production
meshes, prove memory/sharding coherence, and capture roofline inputs.

The ``os.environ`` statement right below the imports runs before ANY jax
import — jax locks the device count at first init.  512 fake host devices
(override: ``REPRO_DRYRUN_DEVICES``) back both the (16,16) single-pod mesh
(first 256) and the (2,16,16) multi-pod mesh (all 512).

    PYTHONPATH=src python -m repro.launch.dryrun --mesh both --out experiments/dryrun
    PYTHONPATH=src python -m repro.launch.dryrun --cell olmo-1b:train_4k
    REPRO_DRYRUN_DEVICES=8 PYTHONPATH=src python -m repro.launch.dryrun \\
        --cell olmoe-1b-7b:train_4k --reduced --mesh-shape 2,4 \\
        --seq-len 64 --global-batch 8   # CI prewarm capture

Per cell, writes <out>/<arch>__<shape>__<mesh>.json with:
  memory_analysis (bytes per device), cost_analysis (FLOPs / bytes),
  per-collective counts + wire bytes, the derived roofline terms, and
  plan_inits — every ``alltoallv_init`` request the cell's bundle issued
  (``core.capture_init_requests``), the input ``repro.planstore.prewarm``
  replays at deploy time to prewarm a fleet store.
Failures (sharding mismatch, compile OOM, unsupported collective) are
bugs — the run exits nonzero listing them.
"""

import os

# Before ANY jax import (the module docstring above is the only earlier
# statement, and it touches nothing): jax locks the device count at first
# init, so the fake-device override must already be in the environment.
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count="
                           + os.environ.get("REPRO_DRYRUN_DEVICES", "512"))

import argparse
import json
import sys
import time
import traceback

# The production meshes below are TPU v5e pods; roofline terms use its peaks.
TARGET_DEVICE_KIND = "TPU v5 lite"


def _mem_analysis_dict(compiled):
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return None
    if ma is None:
        return None
    keys = ("generated_code_size_in_bytes", "argument_size_in_bytes",
            "output_size_in_bytes", "alias_size_in_bytes",
            "temp_size_in_bytes")
    out = {}
    for k in keys:
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = int(v)
    if not out:
        out = {"repr": str(ma)}
    return out


def dataclasses_replace_wire(colls, wire_corrected: float):
    import dataclasses as _dc
    return _dc.replace(colls, total_wire_bytes=int(wire_corrected))


def _shallow_cfg(cfg, k: int):
    """Config cut to k periods of depth (scan bodies unroll at <= 2)."""
    import dataclasses

    from repro.models.transformer import layer_period
    repl = {"n_layers": layer_period(cfg) * k}
    if cfg.encdec:
        repl["n_enc_layers"] = k
    return dataclasses.replace(cfg, **repl)


def _costs_of(cfg, shape, mesh, bundle_kw=None):
    from repro.launch import steps as steps_mod
    from repro.roofline.hlo import parse_collectives

    kw = dict(bundle_kw or {})
    kw.pop("n_micro", None)   # shallow cost variants are exact at n_micro=1
    compiled = steps_mod.make_bundle(cfg, shape, mesh, **kw).compile()
    cost = {k: float(v) for k, v in (compiled.cost_analysis() or {}).items()
            if isinstance(v, (int, float))}
    colls = parse_collectives(compiled.as_text())
    return (cost.get("flops", 0.0), cost.get("bytes accessed", 0.0),
            float(colls.total_wire_bytes))


def scan_corrected_costs(cfg, shape, mesh, raw_cost, raw_wire,
                         bundle_kw=None):
    """XLA's cost_analysis counts a while-loop body ONCE regardless of trip
    count.  Recover the true totals by lowering 1- and 2-period *unrolled*
    variants: body = U2 - U1, base = U1 - body, total = base + n_rep*body."""
    from repro.models.transformer import layer_period

    period = layer_period(cfg)
    n_rep = cfg.n_layers // period
    if n_rep <= 2:   # already unrolled — raw numbers are exact
        return (raw_cost.get("flops", 0.0),
                raw_cost.get("bytes accessed", 0.0), raw_wire, None)
    u1 = _costs_of(_shallow_cfg(cfg, 1), shape, mesh, bundle_kw)
    u2 = _costs_of(_shallow_cfg(cfg, 2), shape, mesh, bundle_kw)
    out = []
    for a, b in zip(u1, u2):
        body = max(b - a, 0.0)
        base = max(a - body, 0.0)
        out.append(base + n_rep * body)
    return out[0], out[1], out[2], {"u1": u1, "u2": u2, "n_rep": n_rep}


HBM_BUDGET = 15.5 * 2**30   # leave headroom under the 16 GiB v5e HBM


def run_cell(cfg, shape, mesh, mesh_name, out_dir, perf_variant=None,
             bundle_kw=None):
    from repro.core import start_init_capture, stop_init_capture
    from repro.launch import steps as steps_mod
    from repro.planstore.prewarm import dedupe_requests
    from repro.roofline import analyze as roofline_mod
    from repro.roofline.hlo import parse_collectives

    bundle_kw = dict(bundle_kw or {})
    micro_ladder = [bundle_kw.pop("n_micro", 1), 4, 8] if shape.kind == "train" \
        else [None]

    # Record every alltoallv_init the cell's bundles issue (including the
    # shallow scan-correction variants — dedup collapses repeats): the
    # prewarm pipeline replays these at deploy time.
    start_init_capture()

    t_lower = t_compile = 0.0
    compiled = None
    n_micro_used = None
    for n_micro in micro_ladder:
        kw = dict(bundle_kw)
        if n_micro is not None:
            kw["n_micro"] = n_micro
        t0 = time.time()
        bundle = steps_mod.make_bundle(cfg, shape, mesh, **kw)
        lowered = bundle.lower()
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
        n_micro_used = n_micro
        ma = _mem_analysis_dict(compiled) or {}
        used = ma.get("temp_size_in_bytes", 0) + ma.get("argument_size_in_bytes", 0)
        if used <= HBM_BUDGET or n_micro == micro_ladder[-1]:
            break
        print(f"    [mem {used/2**30:.1f} GiB > budget; retry n_micro={n_micro}->next]",
              flush=True)
    if n_micro_used not in (None, 1):
        bundle_kw["n_micro"] = n_micro_used

    mem = _mem_analysis_dict(compiled)
    cost = {k: float(v) for k, v in (compiled.cost_analysis() or {}).items()
            if isinstance(v, (int, float))}
    colls = parse_collectives(compiled.as_text())
    chips = 1
    for n in mesh.shape.values():
        chips *= int(n)

    flops_c, bytes_c, wire_c, corr = scan_corrected_costs(
        cfg, shape, mesh, cost, float(colls.total_wire_bytes), bundle_kw)
    plan_inits = dedupe_requests(stop_init_capture())
    cost_corrected = dict(cost)
    cost_corrected["flops"] = flops_c
    cost_corrected["bytes accessed"] = bytes_c
    colls_corrected = dataclasses_replace_wire(colls, wire_c)
    roof = roofline_mod.analyze(cfg, shape, mesh_name, chips, cost_corrected,
                                colls_corrected,
                                device_kind=TARGET_DEVICE_KIND,
                                peak_memory=(mem or {}).get("temp_size_in_bytes"))

    record = {
        "arch": cfg.name, "shape": shape.name, "mesh": mesh_name,
        "chips": chips, "kind": shape.kind,
        "n_micro": n_micro_used,
        "seconds_lower": round(t_lower, 2),
        "seconds_compile": round(t_compile, 2),
        "memory_analysis": mem,
        "cost_analysis_raw": {k: cost[k] for k in sorted(cost)
                              if k in ("flops", "bytes accessed",
                                       "transcendentals")},
        "scan_correction": corr,
        "cost_analysis": {"flops": flops_c, "bytes accessed": bytes_c},
        "collectives": colls.to_json(),
        "collective_wire_bytes_corrected": wire_c,
        "roofline": roof.to_json(),
        "plan_inits": plan_inits,
    }
    if perf_variant:
        record["perf_variant"] = perf_variant
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        tag = f"{cfg.name}__{shape.name}__{mesh_name}"
        if perf_variant:
            tag += f"__{perf_variant}"
        with open(os.path.join(out_dir, tag + ".json"), "w") as f:
            json.dump(record, f, indent=1)
    return record


def main(argv=None):
    p = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    p.add_argument("--mesh-shape", default=None, metavar="D,D[,D]",
                   help="explicit mesh dims instead of the production "
                        "meshes — axes named like launch/train.py "
                        "((pod,)data,model), so a reduced cell's captured "
                        "plan_inits match a --mesh D,D train run exactly")
    p.add_argument("--cell", default="all",
                   help="all | comma list of arch:shape")
    p.add_argument("--reduced", action="store_true",
                   help="smoke-scale configs (CPU-runnable; pairs with "
                        "REPRO_DRYRUN_DEVICES for small fake-device counts)")
    p.add_argument("--seq-len", type=int, default=None)
    p.add_argument("--global-batch", type=int, default=None)
    p.add_argument("--out", default="experiments/dryrun")
    p.add_argument("--list", action="store_true")
    args = p.parse_args(argv)

    from repro.configs import SHAPES, ShapeConfig, cells, get, get_reduced
    from repro.launch.mesh import make_mesh, make_production_mesh

    arch_of = get_reduced if args.reduced else get
    if args.cell == "all":
        todo = [(c, s) for c, s, skip in cells(include_skipped=False)]
        skipped = [(c, s, skip) for c, s, skip in cells(include_skipped=True)
                   if skip]
        if args.reduced:
            todo = [(get_reduced(c.name), s) for c, s in todo]
    else:
        todo, skipped = [], []
        for spec in args.cell.split(","):
            a, s = spec.split(":")
            todo.append((arch_of(a), SHAPES[s]))
    if args.seq_len or args.global_batch or args.reduced:
        todo = [(c, ShapeConfig(s.name, s.kind,
                                args.seq_len or (256 if args.reduced else s.seq_len),
                                args.global_batch or (8 if args.reduced
                                                      else s.global_batch)))
                for c, s in todo]

    if args.list:
        for c, s in todo:
            print(f"{c.name}:{s.name}")
        return 0

    meshes = []
    if args.mesh_shape:
        dims = tuple(int(d) for d in args.mesh_shape.split(","))
        axes = ("pod", "data", "model")[-len(dims):]
        meshes.append((f"mesh{'x'.join(str(d) for d in dims)}",
                       make_mesh(dims, axes)))
    else:
        if args.mesh in ("single", "both"):
            meshes.append(("pod256", make_production_mesh(multi_pod=False)))
        if args.mesh in ("multi", "both"):
            meshes.append(("pods2x256", make_production_mesh(multi_pod=True)))

    failures = []
    n_total = len(todo) * len(meshes)
    i = 0
    for mesh_name, mesh in meshes:
        for cfg, shape in todo:
            i += 1
            tag = f"{cfg.name}:{shape.name}:{mesh_name}"
            print(f"[{i}/{n_total}] {tag} ...", flush=True)
            try:
                rec = run_cell(cfg, shape, mesh, mesh_name, args.out)
                r = rec["roofline"]
                print(f"    ok  lower={rec['seconds_lower']}s "
                      f"compile={rec['seconds_compile']}s "
                      f"flops/chip={r['flops_per_chip']:.3e} "
                      f"dominant={r['dominant']}", flush=True)
            except Exception as e:  # noqa: BLE001 — collect all failures
                failures.append((tag, repr(e)))
                traceback.print_exc()

    for cfg, shape, reason in skipped:
        print(f"SKIP {cfg.name}:{shape.name} — {reason}")
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for tag, err in failures:
            print(" ", tag, err[:200])
        return 1
    print(f"\nall {n_total} cells passed on {[m for m, _ in meshes]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
