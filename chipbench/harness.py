"""Run one cell of `BENCHMARK.json` once and print its result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file found by its name:

- `chipbench/configs/<config>.json`: the configuration as it is run; its
  `driver` names the timed loop in `chipbench/drivers/` and its
  `reference` the plain reference in `chipbench/refs/`;
- `chipbench/traffic/<traffic>.json`: the mix's parameters and the limits
  of the comparison that decides `correct`;
- `chipbench/metrics/<metric>.py`: a `read(ctx)` that takes one per-layer
  metric from the reduced trace and returns None when it finds nothing.

A driver's `run(spec)` sets up, measures for `spec.seconds` inside
`spec.window()`, checks what the window produced, and returns an `Outcome`.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib
import importlib.util
import json
import math
import os
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


class Refused(Exception):
    """The run cannot be made: missing files, no accelerator, bad arguments."""


@dataclasses.dataclass
class Check:
    name: str
    value: float
    limit: float
    ok: bool


@dataclasses.dataclass
class Outcome:
    metrics: dict             # end-to-end metric name -> value
    attempted: int
    failed: int
    checks: list              # [Check]
    layer: dict               # what the per-layer readers need besides the trace
    memory_peak_bytes: int | None


@dataclasses.dataclass
class Spec:
    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace_dir: str | None
    t_start: float            # perf_counter at process start
    compiles: "CompileCounter"

    @contextlib.contextmanager
    def window(self):
        """The measured window: traced when `--trace 1`, and checked for
        compiles, which must not happen inside it."""
        import jax
        before = self.compiles.count
        if self.trace_dir:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0      # host spans: ours and the runtime's
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation("chipbench.window"):
                yield
        finally:
            if self.trace_dir:
                jax.profiler.stop_trace()
            self.window_compiles = self.compiles.count - before

    window_compiles: int = 0


class CompileCounter:
    """Backend compiles, from JAX's own monitoring events."""

    def __init__(self):
        import jax
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


def _shown(path: Path, root: Path) -> str:
    try:
        return str(path.relative_to(root))
    except ValueError:
        return str(path)


def load_json(path: Path, what: str, root: Path = ROOT) -> dict:
    if not path.is_file():
        raise Refused(f"{what}: no file {_shown(path, root)}")
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: Path = ROOT):
    """(benchmark, cell, config, traffic) for the workload `name`."""
    bench = load_json(root / "BENCHMARK.json", "benchmark", root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise Refused(f"workload {name!r} is not in BENCHMARK.json "
                      f"(known: {sorted(cells)})")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if cell["config"] not in configs:
        raise Refused(f"workload {name!r}: no configuration {cell['config']!r}")
    config = load_json(root / configs[cell["config"]]["file"],
                       f"configuration {cell['config']!r}", root)
    traffic = load_json(root / "chipbench" / "traffic" / f"{cell['traffic']}.json",
                        f"traffic {cell['traffic']!r}", root)
    return bench, cell, config, traffic


def load_module(kind: str, name: str, root: Path = ROOT):
    """`chipbench/<kind>/<name>.py` as a module (names may hold dots)."""
    path = root / "chipbench" / kind / f"{name}.py"
    if not path.is_file():
        raise Refused(f"{kind}: no file {_shown(path, root)}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench.{kind}.{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(bench: dict, section: str, cell: str) -> list[dict]:
    return [m for m in bench[section]
            if "workloads" not in m or cell in m["workloads"]]


def use_compile_cache():
    """JAX's persistent compilation cache at a fixed path in the checkout
    (or where JAX_COMPILATION_CACHE_DIR says), holding every program."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def require_accelerator(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(f"needs a TPU; JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise Refused(f"the cell asks for {chips} chips; JAX found {len(devs)}")
    return devs


def device_info(devices, peak) -> dict:
    d0 = devices[0]
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def memory_peak(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run(workload: str, seed: int, seconds: float, trace: bool, **kw) -> dict:
    """One run of one cell; returns the result line as a dict."""
    return execute(workload, seed, seconds, trace, **kw)[0]


def execute(workload: str, seed: int, seconds: float, trace: bool, *,
            accelerator: bool = True, root: Path = ROOT, overrides=None,
            t_start: float | None = None):
    """One run of one cell: (result dict, the driver's Outcome).

    `accelerator=False` skips the look for a chip (the tests drive a run on
    the CPU at a small size); `overrides` replaces keys of the configuration
    and traffic files: `{"config": {...}, "traffic": {...}}`."""
    t_start = time.perf_counter() if t_start is None else t_start
    bench, cell, config, traffic = load_cell(workload, root)
    if overrides:
        config = {**config, **overrides.get("config", {})}
        traffic = {**traffic, **overrides.get("traffic", {})}
    driver = load_module("drivers", config["driver"], root)
    readers = {}
    if trace:
        for m in cell_metrics(bench, "per_layer", workload):
            readers[m["name"]] = load_module("metrics", m["name"], root)
    import jax
    if accelerator:
        devices = require_accelerator(int(cell["chips"]))
        use_compile_cache()
    else:
        devices = jax.devices()
    spec = Spec(cell, config, traffic, int(seed), float(seconds), None,
                t_start, CompileCounter())
    with contextlib.ExitStack() as stack:
        if trace:
            spec.trace_dir = stack.enter_context(tempfile.TemporaryDirectory(
                prefix="chipbench-trace-"))
        out = driver.run(spec)
        result_metrics = {}
        device = device_info(devices, out.memory_peak_bytes)
        breakdown = None
        if trace:
            from chipbench import trace as tr
            from chipbench.peaks import peaks
            summ = tr.summarize(tr.find_xplane(spec.trace_dir))
            ctx = {"trace": summ, "layer": out.layer, "config": config,
                   "traffic": traffic,
                   "peaks": peaks(devices[0].device_kind) if accelerator else None}
            for m in cell_metrics(bench, "per_layer", workload):
                v = readers[m["name"]].read(ctx)
                if v is not None:
                    result_metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
            device["busy_s"] = summ.busy_s_mean()
            device["window_s"] = summ.window_s
            breakdown = {"device_ops": summ.top_ops(10),
                         "idle_gaps": summ.idle_gaps(10)}
        else:
            for m in cell_metrics(bench, "end_to_end", workload):
                if m["name"] in out.metrics:
                    result_metrics[m["name"]] = {"value": float(out.metrics[m["name"]]),
                                                 "unit": m["unit"]}
    checks = list(out.checks) + [Check("window_compiles", spec.window_compiles,
                                       0, spec.window_compiles == 0)]
    result = {
        "correct": all(c.ok for c in checks) and bool(out.checks),
        "attempted": int(out.attempted),
        "failed": int(out.failed),
        "metrics": result_metrics,
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    return result, out


def _finite(x):
    """JSON has no NaN or infinity: such a number prints as null."""
    if isinstance(x, dict):
        return {k: _finite(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_finite(v) for v in x]
    if isinstance(x, float) and not math.isfinite(x):
        return None
    return x


def main(argv=None, t_start: float | None = None) -> int:
    import argparse
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    try:
        res = run(args.workload, args.seed, args.seconds, bool(args.trace),
                  t_start=t_start)
    except Refused as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    for name, c in res["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(_finite(res)))
    return 0
