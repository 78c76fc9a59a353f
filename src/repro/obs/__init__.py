"""Unified observability: span tracing, metrics exposition, break-even checks.

The subsystem that makes the paper's amortization argument *visible in a
live run* instead of only in offline benchmark sweeps:

- ``spans``           process-global ``TRACER`` — spans that every
                      ``jax.profiler`` capture sees, and a ring of spans
                      and instants covering INIT (bakes, autotune bursts,
                      store ops), EXECUTE (plan start/wait, steps, the
                      serve host loop) and runtime events (swaps, chaos,
                      resharding)
- ``trace_export``    Chrome-trace/Perfetto JSON + JSONL exporters and the
                      structural validator CI's ``obs-smoke`` job runs
- ``counters``        process-wide named counters (``COUNTERS``): decode
                      steps and experts touched by ``ServeEngine``,
                      alltoallv epochs and their wire bytes
- ``metrics``         Prometheus text exposition (+ ``MetricsServer`` for
                      ``--metrics-port``) over INIT counters, epoch rings,
                      swap log, serving counters and break-even residuals
- ``breakeven_check`` stored Eq. 1-3 fits vs observed steady-state epochs
                      (``breakeven_residual``)

CLI: ``python -m repro.obs {report,trace,metrics}``.
"""

from .spans import TRACER, SpanBuffer, Tracer      # noqa: I001 — dependency-free, first
from .breakeven_check import breakeven_residual, check_breakeven
from .counters import COUNTERS
from .metrics import MetricsServer, render_metrics, write_metrics
from .trace_export import (TraceValidationError, chrome_trace, validate_trace,
                           write_jsonl, write_trace)

__all__ = [
    "TRACER",
    "COUNTERS",
    "Tracer",
    "SpanBuffer",
    "chrome_trace",
    "write_trace",
    "write_jsonl",
    "validate_trace",
    "TraceValidationError",
    "render_metrics",
    "write_metrics",
    "MetricsServer",
    "breakeven_residual",
    "check_breakeven",
]
