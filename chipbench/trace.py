"""Reduction of a profiler trace of the measured window.

The JAX profiler writes one ``.xplane.pb`` per session. Each chip is a
plane ``/device:TPU:<n>`` whose line ``XLA Modules`` holds one event per
program execution (named ``jit_<function>(<fingerprint>)``) and whose
line ``XLA Ops`` holds the operations inside them. Host annotations
(``jax.profiler.TraceAnnotation``, which the benchmark's tracer wraps
around every span of the program) are on the plane ``/host:CPU``, on the
same clock. The window is marked there by the annotations
``chipbench.window_open`` and ``chipbench.window_close``.
"""
from __future__ import annotations

import bisect
import collections
import glob
import json
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]          # name, start ns, end ns

HERE = os.path.dirname(os.path.abspath(__file__))
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPEN, CLOSE = "chipbench.window_open", "chipbench.window_close"
SPAN_NAMES = ("worker_round", "compress_roundtrip", "server_commit",
              "server_commit_batch", "eval", "checkpoint")


def load_ops() -> dict:
    """Program names by role (``ops.json``): regular expressions over the
    module names, and over op names inside those modules."""
    with open(os.path.join(HERE, "ops.json")) as f:
        return json.load(f)


class Busy:
    """The union of a chip's op intervals, for busy time over any
    stretch in O(log n)."""

    def __init__(self, intervals: Sequence[Tuple[float, float]]):
        merged: List[List[float]] = []
        for s, e in sorted(intervals):
            if merged and s <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], e)
            else:
                merged.append([s, e])
        self.starts = [m[0] for m in merged]
        self.ends = [m[1] for m in merged]
        self.cum = [0.0]
        for s, e in merged:
            self.cum.append(self.cum[-1] + (e - s))

    def _upto(self, t: float) -> float:
        i = bisect.bisect_right(self.starts, t)
        if i == 0:
            return 0.0
        return self.cum[i - 1] + min(t, self.ends[i - 1]) - self.starts[i - 1]

    def within(self, a: float, b: float) -> float:
        return self._upto(b) - self._upto(a)


def base_name(module: str) -> str:
    return module.split("(", 1)[0]


class Device:
    """One chip's module and op events."""

    def __init__(self, modules: List[Event], ops: List[Event]):
        self.modules = sorted(modules, key=lambda e: e[1])
        self.ops = sorted(ops, key=lambda e: e[1])
        self._starts = [m[1] for m in self.modules]

    def module_of(self, t: float) -> Optional[str]:
        i = bisect.bisect_right(self._starts, t) - 1
        if i >= 0 and self.modules[i][2] >= t:
            return self.modules[i][0]
        return None


class TracedRun:
    """What the per-layer metrics read: the traced window on the trace's
    clock, each chip's events in it, the host annotations, and the
    benchmark's own record of the program's spans in the window (host
    clock: name, start, end, arrivals in the commit)."""

    def __init__(self, *, cell, spans, peaks: dict, tokens: int,
                 n_chips: int, window: Tuple[float, float],
                 devices: List[Device], host: List[Event],
                 ops: Optional[dict] = None):
        self.cell, self.spans, self.peaks = cell, spans, peaks
        self.tokens, self.n_chips = tokens, n_chips
        self.lo, self.hi = window
        self.window_s = (self.hi - self.lo) / 1e9
        self.devices = devices
        self.host = host
        self.ops = ops if ops is not None else load_ops()

    @classmethod
    def load(cls, trace_dir: str, **kw) -> "TracedRun":
        from jax.profiler import ProfileData
        files = sorted(glob.glob(os.path.join(
            trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
        if not files:
            raise FileNotFoundError(f"no profile under {trace_dir}")
        data = ProfileData.from_file(files[-1])
        devices: Dict[int, Device] = {}
        host: List[Event] = []
        for plane in data.planes:
            m = DEVICE_PLANE.match(plane.name)
            if m:
                lines = {ln.name: [(e.name, e.start_ns, e.end_ns)
                                   for e in ln.events]
                         for ln in plane.lines
                         if ln.name in ("XLA Modules", "XLA Ops")}
                devices[int(m.group(1))] = Device(
                    lines.get("XLA Modules", []), lines.get("XLA Ops", []))
            elif plane.name == HOST_PLANE:
                for ln in plane.lines:
                    host.extend((e.name, e.start_ns, e.end_ns)
                                for e in ln.events
                                if e.name in SPAN_NAMES
                                or e.name in (OPEN, CLOSE))
        return cls.from_events(devices=[devices[i] for i in sorted(devices)],
                               host=host, **kw)

    @classmethod
    def from_events(cls, *, devices: List[Device], host: List[Event],
                    n_chips: int, **kw) -> "TracedRun":
        marks = {name: s for name, s, _ in host if name in (OPEN, CLOSE)}
        if OPEN not in marks or CLOSE not in marks:
            raise ValueError("the trace lacks the window markers")
        return cls(devices=devices[:n_chips], n_chips=n_chips,
                   host=[h for h in host if h[0] in SPAN_NAMES],
                   window=(marks[OPEN], marks[CLOSE]), **kw)

    # ------------------------------------------------------------ device
    def _inside(self, events: List[Event]) -> List[Event]:
        return [e for e in events if e[1] >= self.lo and e[2] <= self.hi]

    def busy_s(self) -> Optional[float]:
        """Seconds in which an operation ran, averaged over the chips."""
        if not self.devices:
            return None
        busy = [Busy([(s, e) for _, s, e in d.ops]).within(self.lo, self.hi)
                for d in self.devices]
        return sum(busy) / len(busy) / 1e9

    def module_seconds(self, role: str) -> Tuple[float, int]:
        """Device seconds and executions of the programs of ``role``."""
        pat = re.compile(self.ops[role]["module"])
        hits = [e for d in self.devices for e in self._inside(d.modules)
                if pat.search(e[0])]
        return sum(e - s for _, s, e in hits) / 1e9, len(hits)

    def kernel_seconds(self, role: str) -> Tuple[float, int]:
        """Device seconds and count of the ops of ``role`` inside its
        programs (e.g. the Pallas kernels of the commit)."""
        mod = re.compile(self.ops[role]["module"])
        op = re.compile(self.ops[role]["op"])
        total, n = 0.0, 0
        for d in self.devices:
            for name, s, e in self._inside(d.ops):
                if op.search(name):
                    owner = d.module_of(s)
                    if owner is not None and mod.search(owner):
                        total += e - s
                        n += 1
        return total / 1e9, n

    # -------------------------------------------------------------- host
    def span_ms(self, name: str) -> List[float]:
        return [(t1 - t0) * 1e3 for n, t0, t1, _ in self.spans if n == name]

    def arrivals(self) -> List[int]:
        """Arrivals of each commit in the window."""
        return [k for n, _, _, k in self.spans if n.startswith("server_commit")]

    def _labels(self, edges: List[float]) -> List[str]:
        """The innermost host span over each stretch between edges."""
        spans = sorted(self.host, key=lambda h: h[1])
        out, active, i = [], [], 0
        for a, b in zip(edges, edges[1:]):
            mid = (a + b) / 2
            while i < len(spans) and spans[i][1] <= mid:
                active.append(spans[i])
                i += 1
            active = [h for h in active if h[2] >= mid]
            out.append(min(active, key=lambda h: h[2] - h[1])[0]
                       if active else "no program span")
        return out

    def breakdown(self) -> dict:
        """The device programs that took most time (all chips), and the
        first chip's idle time in the window by the innermost program span
        the host was in at the time."""
        per = collections.Counter()
        for d in self.devices:
            for name, s, e in self._inside(d.modules):
                per[base_name(name)] += (e - s) / 1e9
        idle = collections.Counter()
        for d in self.devices[:1]:
            busy = Busy([(s, e) for _, s, e in d.ops])
            edges = sorted({self.lo, self.hi} | {
                t for _, s, e in self.host for t in (s, e)
                if self.lo < t < self.hi})
            for (a, b), label in zip(zip(edges, edges[1:]),
                                     self._labels(edges)):
                idle[label] += ((b - a) - busy.within(a, b)) / 1e9
        return {"device_ops": [[k, v] for k, v in per.most_common(10)],
                "idle_gaps": [[k, v] for k, v in idle.most_common(10)]}
