"""Span-based tracing core: the one event model every subsystem records into.

The paper's amortization argument (Eq. 1-3) says a persistent plan pays a
one-time INIT cost and then runs metadata-free epochs.  ``_init_stats``
counts the INIT-side work and ``_exec_stats`` rings the EXECUTE-side wall
times, but neither shows *where a run's time actually goes* — this module
does: every interesting interval becomes a **span** (name, category, start,
duration, thread, attributes), every interesting moment an **instant
event**, and both land in one process-global buffer that exports to
Chrome-trace/Perfetto JSON (``obs.trace_export``), Prometheus text
(``obs.metrics``), and JSONL.

Span taxonomy (the categories the exporters and the trace validator key on):

  ``init``           one whole plan INIT (``AlltoallvPlan.__init__``);
                     args carry digest/variant/warm so a warm INIT is
                     checkable: it must contain zero bake/burst children
  ``init.bake``      host-side table bakes (``baked_index_tables`` /
                     ``hier_two_stage_schedule``)
  ``init.autotune``  ``variant="auto"`` sweeps and their measurement bursts
  ``store``          plan-store get/put/CAS-merge, attributed with backend
                     root and hit/miss outcome
  ``execute``        ``plan.start`` / ``plan.wait``, recorded epochs,
                     train steps, and the ``serve.*`` host steps of
                     ``ServeEngine.generate``
  ``runtime``        re-plan triggers, hot-swaps, recovery, chaos
                     injections, elastic resharding (mostly instants)

Hot-path discipline
-------------------

Every ``TRACER.span`` also enters a ``jax.profiler.TraceAnnotation`` of
its name, so whenever a profiler session is running (``jax.profiler.trace``,
an xprof capture, the chip benchmark's ``--trace 1`` run) the span shows on
the calling thread's host line, on the same clock as the device ops.  The
annotation gets the name only, never the args: with no profiler running
and the ring off, a span costs one annotation enter/exit (well under a
microsecond).

The ring is **off by default**: ``TRACER.enabled`` (one attribute load)
guards every record.  Enabled, a finished span is one tuple stored into a
slot of a **preallocated ring** — the same storage discipline as
``core._exec_stats.EpochRing``: no locks on the record path (the slot
index comes from an ``itertools.count``, whose ``next`` is atomic under
the GIL, so concurrent writers — the re-plan background thread and the
step loop — never tear a record; a full ring overwrites oldest-first).
``emit_span`` and ``instant`` record into the ring only: they never reach
the profiler.  The measured overhead contract lives in
``benchmarks/resilience.py`` (``steady_traced`` row): tracing on must stay
within ~2% of a bare epoch.
"""

from __future__ import annotations

import itertools
import threading
import time

from jax.profiler import TraceAnnotation

DEFAULT_SPAN_CAPACITY = 1 << 16

# Span kinds (the ``ph`` phase the Chrome exporter emits).
COMPLETE = "X"        # a closed interval: ts + dur
INSTANT = "i"         # a moment: ts only


class SpanBuffer:
    """Preallocated ring of finished span records.

    A record is the tuple ``(name, cat, ph, ts_s, dur_s, tid, args)`` with
    times in seconds on the tracer's clock.  ``emit`` is lock-free (slot
    index from an atomic counter); ``snapshot`` returns the retained
    records oldest-first and may lose in-flight writes — acceptable for an
    observability buffer, never for correctness data."""

    __slots__ = ("capacity", "_slots", "_idx")

    def __init__(self, capacity: int = DEFAULT_SPAN_CAPACITY):
        self.capacity = int(capacity)
        self._slots = [None] * self.capacity
        self._idx = itertools.count()

    def emit(self, rec: tuple) -> None:
        self._slots[next(self._idx) % self.capacity] = rec

    def snapshot(self) -> list[tuple]:
        """Retained records, oldest-first by timestamp."""
        recs = [s for s in self._slots if s is not None]
        recs.sort(key=lambda r: r[3])
        return recs


class _SpanCtx:
    """Context manager for one recorded span; ``.args`` is mutable until
    exit, so a body can attach outcomes (warm/hit/variant) it only knows at
    the end."""

    __slots__ = ("_tracer", "name", "cat", "args", "_t0", "_annotation")

    def __init__(self, tracer: "Tracer", name: str, cat: str, args: dict):
        self._tracer = tracer
        self.name = name
        self.cat = cat
        self.args = args

    def __enter__(self) -> "_SpanCtx":
        self._annotation = TraceAnnotation(self.name)
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        t1 = time.perf_counter()
        self._annotation.__exit__(exc_type, exc, tb)
        if exc is not None:
            self.args["error"] = repr(exc)
        self._tracer._emit(self.name, self.cat, COMPLETE,
                           self._t0, t1 - self._t0, self.args)


class _Annotation(TraceAnnotation):
    """A span with the ring off: the profiler annotation alone.  ``args``
    is a shared scratch dict, so bodies that attach outcomes need no
    branch on ``TRACER.enabled``."""

    args: dict = {}


class Tracer:
    """Process-global span recorder (singleton ``TRACER``).

    ``enable(capacity)`` arms the ring; until then spans are profiler
    annotations only and nothing is recorded.
    Timestamps are ``perf_counter`` seconds relative to the enable call
    (``origin_unix`` maps them back to wall time for exporters)."""

    def __init__(self) -> None:
        self.enabled = False
        self.buffer: SpanBuffer | None = None
        self._t0 = 0.0
        self.origin_unix = 0.0
        self._thread_names: dict[int, str] = {}
        self._lock = threading.Lock()     # thread-name registry only

    # -- lifecycle -----------------------------------------------------------
    def enable(self, capacity: int = DEFAULT_SPAN_CAPACITY) -> "Tracer":
        self.buffer = SpanBuffer(capacity)
        self._t0 = time.perf_counter()
        self.origin_unix = time.time()
        self.enabled = True
        return self

    def disable(self) -> None:
        self.enabled = False

    def reset(self) -> None:
        self.enabled = False
        self.buffer = None
        with self._lock:
            self._thread_names.clear()

    # -- recording -----------------------------------------------------------
    def span(self, name: str, cat: str, **args) -> "_SpanCtx | _Annotation":
        """``with TRACER.span("table_bake", "init.bake", p=64): ...``

        Always a ``jax.profiler.TraceAnnotation(name)``; also recorded in
        the ring, with ``args``, when the tracer is enabled."""
        if not self.enabled:
            return _Annotation(name)
        return _SpanCtx(self, name, cat, args)

    def emit_span(self, name: str, cat: str, t0: float, t1: float,
                  args: dict | None = None) -> None:
        """Record an already-timed interval (``t0``/``t1`` are
        ``perf_counter`` readings) into the ring.  Ring only: the interval
        is over by the time it is known, so it never reaches a profiler
        trace — use ``span`` for anything a profiler should see."""
        if self.enabled:
            self._emit(name, cat, COMPLETE, t0, t1 - t0, args)

    def instant(self, name: str, cat: str, **args) -> None:
        """Record a moment (hot-swap landed, chaos fault fired, ...)."""
        if self.enabled:
            self._emit(name, cat, INSTANT, time.perf_counter(), 0.0, args)

    def _emit(self, name: str, cat: str, ph: str, t0: float, dur: float,
              args: dict | None) -> None:
        tid = threading.get_ident()
        if tid not in self._thread_names:
            with self._lock:
                self._thread_names[tid] = threading.current_thread().name
        self.buffer.emit((name, cat, ph, t0 - self._t0, dur, tid, args))

    # -- reading -------------------------------------------------------------
    def snapshot(self) -> dict:
        """Everything an exporter needs, as plain data: retained records,
        thread names, and the wall-clock origin."""
        with self._lock:
            names = dict(self._thread_names)
        return {"records": self.buffer.snapshot() if self.buffer else [],
                "thread_names": names,
                "origin_unix": self.origin_unix}


TRACER = Tracer()


def enabled() -> bool:
    return TRACER.enabled
