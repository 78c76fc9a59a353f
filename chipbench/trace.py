"""Reduce a profiler trace (`.xplane.pb`) to device busy time and op classes.

Device planes are `/device:TPU:<n>`.  On each, the `XLA Ops` line holds one
event per executed HLO op and the `XLA Modules` line one event per program
execution.  Busy time is the union of op intervals inside the traced window
(nested or overlapping ops count once).  The window is the host span that
the benchmark opens around its timed loop (`WINDOW_SPAN`); host spans of
the benchmark and of the runtime on the host planes say what the host was
doing in each idle gap.

An op event is named by its HLO instruction text (`%fusion.3 = bf16[..]
fusion(..), ..`); the reduction keeps the instruction's name (`fusion.3`)
and its opcode (`fusion`).  Ops fall into three classes by opcode:
`collective` (all-to-all, collective-permute, ragged all-to-all,
all-gather, all-reduce, reduce-scatter and their async halves), `mosaic`
(a Pallas kernel: a TPU custom call) and `other`.  Control-flow ops
(`while`, `conditional`, `call`) span the ops of their bodies: they count
towards busy time but not towards any class or the list of top ops, so no
time is counted twice.
"""

from __future__ import annotations

import dataclasses
import functools
import glob
import os
import re
from collections import defaultdict

WINDOW_SPAN = "chipbench.window"
COLLECTIVES = ("all-to-all", "ragged-all-to-all", "collective-permute",
               "all-gather", "all-reduce", "reduce-scatter", "send", "recv")
CONTAINERS = ("while", "conditional", "call")
_INSTR = re.compile(r"^%?([\w.-]+)(?: = .*?[ )]([a-z][a-z0-9-]*)\()?")


@functools.lru_cache(maxsize=1 << 16)
def parse_op(text: str) -> tuple[str, str]:
    """(instruction name, opcode) of an `XLA Ops` event name."""
    m = _INSTR.match(text)
    if not m:
        return text, text
    name = m.group(1)
    return name, m.group(2) or name.split(".")[0]


def op_class(opcode: str) -> str:
    """`collective`, `mosaic`, `control` or `other` for an opcode."""
    base = opcode.removesuffix("-start").removesuffix("-done")
    if base in COLLECTIVES:
        return "collective"
    if opcode == "custom-call":
        return "mosaic"
    if opcode in CONTAINERS:
        return "control"
    return "other"


def merged(intervals) -> list[tuple[int, int]]:
    """The union of [start, end) intervals as sorted disjoint intervals."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


@dataclasses.dataclass
class Device:
    name: str
    busy_ns: int
    ops: dict            # op name -> [count, ns, class]
    class_ns: dict       # class -> ns
    modules: dict        # module name -> [count, ns]
    busy: list           # merged busy intervals (ns)


@dataclasses.dataclass
class Summary:
    window: tuple[int, int]
    devices: list[Device]
    host_spans: list      # (name, start_ns, end_ns) of the benchmark's thread

    @property
    def window_ns(self) -> int:
        return self.window[1] - self.window[0]

    @property
    def window_s(self) -> float:
        return self.window_ns * 1e-9

    def fullest(self) -> Device:
        return max(self.devices, key=lambda d: d.busy_ns)

    def busy_s_mean(self) -> float:
        return sum(d.busy_ns for d in self.devices) / len(self.devices) * 1e-9

    def module_ns(self, device: Device, pattern: str) -> tuple[int, int]:
        """(executions, ns) of the modules whose name matches `pattern`."""
        rx = re.compile(pattern)
        n = ns = 0
        for name, (c, t) in device.modules.items():
            if rx.search(name):
                n += c
                ns += t
        return n, ns

    def top_ops(self, k: int = 10) -> list[list]:
        """The device ops that took most time, averaged over devices."""
        tot: dict = defaultdict(int)
        for d in self.devices:
            for name, (_, ns, _) in d.ops.items():
                tot[name] += ns
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[name, ns / len(self.devices) * 1e-9] for name, ns in top]

    def idle_gaps(self, k: int = 10) -> list[list]:
        """Idle time of the fullest device, summed by the innermost span of
        the benchmark's host thread that covers each gap's midpoint
        (`host idle` where none does)."""
        dev = self.fullest()
        t0, t1 = self.window
        mids, prev = [], t0
        for s, e in dev.busy:
            if s > prev:
                mids.append(((prev + s) // 2, s - prev))
            prev = max(prev, e)
        if t1 > prev:
            mids.append(((prev + t1) // 2, t1 - prev))
        spans = sorted(self.host_spans, key=lambda x: (x[1], -x[2]))
        by: dict = defaultdict(int)
        stack: list = []
        i = 0
        for mid, ns in sorted(mids):
            while i < len(spans) and spans[i][1] <= mid:
                name, s, e = spans[i]
                while stack and stack[-1][1] <= s:
                    stack.pop()
                stack.append((name, e))
                i += 1
            while stack and stack[-1][1] < mid:
                stack.pop()
            by[stack[-1][0] if stack else "host idle"] += ns
        top = sorted(by.items(), key=lambda kv: -kv[1])[:k]
        return [[name, ns * 1e-9] for name, ns in top]


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def summarize(path: str, window_span: str = WINDOW_SPAN) -> Summary:
    """Read one `.xplane.pb` and reduce it over the window span."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    window = None
    host_spans = []
    device_planes = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            device_planes.append(plane)
            continue
        if not plane.name.startswith("/host:") or window is not None:
            continue
        for line in plane.lines:
            events = [(ev.name, int(ev.start_ns), int(ev.end_ns))
                      for ev in line.events if ev.duration_ns > 0]
            spans = [x for x in events if x[0] == window_span]
            if spans:
                window = spans[0][1:]
                host_spans = [x for x in events if x[0] != window_span]
                break
    if window is None:
        raise ValueError(f"trace has no {window_span!r} host span")
    t0, t1 = window
    devices = []
    for plane in device_planes:
        ops: dict = {}
        class_ns: dict = defaultdict(int)
        modules: dict = {}
        intervals = []
        for line in plane.lines:
            if line.name == "XLA Ops":
                for ev in line.events:
                    s, e = max(int(ev.start_ns), t0), min(int(ev.end_ns), t1)
                    if e <= s:
                        continue
                    intervals.append((s, e))
                    name, opcode = parse_op(ev.name)
                    cls = op_class(opcode)
                    if cls == "control":
                        continue
                    rec = ops.setdefault(name, [0, 0, cls])
                    rec[0] += 1
                    rec[1] += e - s
                    class_ns[cls] += e - s
            elif line.name == "XLA Modules":
                for ev in line.events:
                    s, e = max(int(ev.start_ns), t0), min(int(ev.end_ns), t1)
                    if e <= s:
                        continue
                    rec = modules.setdefault(ev.name, [0, 0])
                    rec[0] += 1
                    rec[1] += e - s
        busy = merged(intervals)
        devices.append(Device(plane.name, sum(e - s for s, e in busy), ops,
                              dict(class_ns), modules, busy))
    if not devices:
        raise ValueError("trace has no /device:TPU plane")
    inside = [(n, s, e) for n, s, e in host_spans if e > t0 and s < t1]
    return Summary(window, devices, inside)
