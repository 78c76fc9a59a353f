"""Shared benchmark plumbing: timing, CSV emission, device-count setup.

Each benchmark module sets its host-device count BEFORE importing jax (so
run.py executes them as subprocesses) and prints ``name,us_per_call,derived``
CSV rows, mirroring the paper's measurement discipline: warmup iterations,
then mean over N timed iterations of start+wait, worst-case (max) across
ranks implicit in single-process host timing.
"""

from __future__ import annotations

import csv
import json
import os
import sys
import time
from typing import Callable


def set_host_devices(n: int) -> None:
    assert "jax" not in sys.modules, "set_host_devices must run before jax import"
    os.environ["XLA_FLAGS"] = (os.environ.get("_REPRO_BASE_XLA", "")
                               + f" --xla_force_host_platform_device_count={n}")
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()


def time_call(fn: Callable[[], object], iters: int = 30, warmup: int = 5) -> float:
    """Mean seconds per call (block_until_ready barriers included)."""
    import jax
    for _ in range(warmup):
        jax.block_until_ready(fn())
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn())
    return (time.perf_counter() - t0) / iters


def provenance() -> dict:
    """Measurement provenance stamped onto every BENCH_*.json row: without
    the jax version / XLA backend / device count / run timestamp, two
    baseline files cannot be compared meaningfully (check_regress windows
    assume same-backend rows).  The timestamp comes from the runner
    (``benchmarks/run.py`` exports REPRO_BENCH_TIMESTAMP so every benchmark
    of one sweep shares it); standalone invocations stamp their own."""
    import jax
    return {
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "devices": jax.device_count(),
        "timestamp": (os.environ.get("REPRO_BENCH_TIMESTAMP")
                      or time.strftime("%Y-%m-%dT%H:%M:%S")),
    }


class Csv:
    def __init__(self, path: str | None = None):
        self.rows: list[tuple] = []
        self.path = path

    def row(self, name: str, us_per_call: float, derived: str = "") -> None:
        self.rows.append((name, f"{us_per_call:.1f}", derived))
        print(f"{name},{us_per_call:.1f},{derived}", flush=True)

    def save(self) -> None:
        if not self.path:
            return
        os.makedirs(os.path.dirname(self.path), exist_ok=True)
        with open(self.path, "w", newline="") as f:
            w = csv.writer(f)
            w.writerow(["name", "us_per_call", "derived"])
            w.writerows(self.rows)

    def save_json(self, path: str) -> None:
        """Machine-readable per-benchmark results (perf trajectory across
        PRs), every row stamped with measurement provenance."""
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        prov = provenance()
        payload = [{"name": n, "us_per_call": float(us), "derived": d, **prov}
                   for n, us, d in self.rows]
        with open(path, "w") as f:
            json.dump(payload, f, indent=2)
            f.write("\n")


def rows_to_json(stdout_text: str, path: str,
                 prov: dict | None = None) -> int:
    """Parse ``name,us_per_call,derived`` CSV rows from captured benchmark
    stdout and write them as JSON; returns the number of rows written.
    ``prov`` (runner-side provenance) is stamped onto every row — the
    scraping parent never imported jax, so it passes what it knows."""
    rows = []
    for line in stdout_text.splitlines():
        parts = line.split(",", 2)
        # Benchmark rows are "<bench>/<case>,<float>,..."; requiring the
        # slash filters stray library output that happens to contain commas.
        if len(parts) < 2 or line.startswith("#") or "/" not in parts[0]:
            continue
        try:
            us = float(parts[1])
        except ValueError:
            continue
        rows.append({"name": parts[0], "us_per_call": us,
                     "derived": parts[2] if len(parts) > 2 else "",
                     **(prov or {})})
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        json.dump(rows, f, indent=2)
        f.write("\n")
    return len(rows)
