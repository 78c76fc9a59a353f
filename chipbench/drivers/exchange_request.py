"""The exchange cell's closed loop of epochs (`exchange.run`), reported as
requests: for an HPC code one request is one epoch, one `plan.start` then
`plan.wait`, one in flight.

`request_ms` is the window over the epochs completed in it; the epoch in
flight when `--seconds` is reached finishes, and all of its time counts.
`setup_s` runs from process start to the first timed epoch (plan INIT, the
payloads and one untimed epoch of each).  The variant the engine picked is
printed on standard error.

Around the window the driver takes the deltas of the program's
`a2a.epochs` and `a2a.wire_bytes` counters, for `a2a.wire_pad_share`; a
program without them leaves both out.  In a traced run it counts the
`plan.start` spans inside the window and hands that count to the per-epoch
readers in place of the host's, or no count (so they read nothing) when the
two differ by more than 1%.
"""

from __future__ import annotations

import contextlib
import sys

from chipbench import harness
from chipbench.drivers import exchange

COUNTED = ("a2a.epochs", "a2a.wire_bytes")
EPOCH_SPAN = "plan.start"
EPOCH_AGREE = 0.01


class _Counted:
    """The harness's spec, with the program's counters read around its
    window (the window itself, compiles check included, is the spec's)."""

    def __init__(self, spec: harness.Spec):
        self._spec = spec
        self.deltas: dict = {}

    def __getattr__(self, name):
        return getattr(self._spec, name)

    @contextlib.contextmanager
    def window(self):
        counters = _counters()
        before = counters.snapshot() if counters else {}
        with self._spec.window():
            yield
        after = counters.snapshot() if counters else {}
        self.deltas = {k: after.get(k, 0) - before.get(k, 0) for k in COUNTED}


def _counters():
    try:
        from repro.obs.counters import COUNTERS
    except ImportError:
        return None
    return COUNTERS


def traced_epochs(trace_dir: str) -> int | None:
    """`plan.start` spans inside the window on the benchmark's host thread,
    or None when the trace holds no window."""
    from chipbench import trace as tr
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(tr.find_xplane(trace_dir))
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            window, starts = None, []
            for ev in line.events:
                if ev.name == tr.WINDOW_SPAN:
                    window = (int(ev.start_ns), int(ev.end_ns))
                elif ev.name == EPOCH_SPAN:
                    starts.append(int(ev.start_ns))
            if window is not None:
                return sum(window[0] <= s < window[1] for s in starts)
    return None


def run(spec: harness.Spec) -> harness.Outcome:
    counted = _Counted(spec)
    out = exchange.run(counted)
    lay = dict(out.layer)
    print(f"exchange: variant {lay['variant']}", file=sys.stderr)
    epochs = counted.deltas.get("a2a.epochs", 0)
    if epochs:
        lay["a2a_epochs"] = epochs
        lay["a2a_wire_bytes"] = counted.deltas["a2a.wire_bytes"]
    if spec.trace_dir:
        host, traced = lay["epochs"], traced_epochs(spec.trace_dir)
        agree = traced is not None and abs(traced - host) <= EPOCH_AGREE * host
        print(f"exchange: {traced} epochs traced, {host} counted by the host"
              + ("" if agree else "; per-epoch readers read nothing"),
              file=sys.stderr)
        lay["host_epochs"] = host
        lay["epochs"] = traced if agree else None
    metrics = {"request_ms": out.metrics["epoch_us"] * 1e-3,
               "setup_s": out.metrics["setup_s"]}
    return harness.Outcome(metrics, out.attempted, out.failed, out.checks,
                           lay, out.memory_peak_bytes)
