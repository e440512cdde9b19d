"""Reduction of the program's own spans in a profiler trace.

The program writes ``echopfl/<layer>[/<stage>]`` host annotations while a
profiler session records (``repro.common.tracing``), with its counts as
event stats. This module reads them from the newest ``.xplane.pb`` of the
traced window (the directory ``measure.py`` writes and removes only after
the metric readers ran) and reduces them:

* spans are clipped to the ``chipbench/window`` annotation;
* a span's self time is its duration less the part its child spans cover
  on the same thread line;
* the device's idle time that a stage span covers, by exact interval
  overlap, over all of its idle time in the window, averaged over devices
  (a stage is any program span but ``superstep``, ``install``, ``train``
  and ``ingest``).

The window and the device's busy intervals come from
:mod:`chipbench.trace`. A trace without program spans (a program that
writes none) reduces to ``None``, and so does an untraced run.
"""
from __future__ import annotations

import dataclasses
import functools
import glob
import os

from . import trace
from .spec import BENCH_DIR

PREFIX = "echopfl/"
TRACE_DIR = BENCH_DIR / "out" / "trace"  # where measure.py has the profiler write
NOT_STAGES = frozenset({"superstep", "install", "train", "ingest"})


@dataclasses.dataclass(frozen=True)
class Span:
    name: str  # without the prefix
    line: tuple  # (plane name, line index): one thread
    start_ns: float
    end_ns: float
    stats: dict = dataclasses.field(default_factory=dict, compare=False)

    @property
    def dur_ns(self) -> float:
        return self.end_ns - self.start_ns


def newest(trace_dir) -> str | None:
    """The newest ``.xplane.pb`` under ``trace_dir`` (the file
    :func:`chipbench.trace.load_events` reads), or None."""
    files = sorted(glob.glob(os.path.join(str(trace_dir), "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def load_spans(path) -> list[Span]:
    """The program's spans in the ``.xplane.pb`` at ``path``."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/device:"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(PREFIX):
                    out.append(Span(e.name[len(PREFIX):], (plane.name, i), float(e.start_ns),
                                    float(e.start_ns + e.duration_ns), dict(e.stats)))
    return out


def clip(spans: list[Span], t0: float, t1: float) -> list[Span]:
    """Spans cut to ``[t0, t1]``; those wholly outside are dropped."""
    return [dataclasses.replace(s, start_ns=max(s.start_ns, t0), end_ns=min(s.end_ns, t1))
            for s in spans if s.end_ns > t0 and s.start_ns < t1]


def self_ns(spans: list[Span]) -> list[float]:
    """Self time of each span, in the order given: its duration less what
    its direct children (the spans nested in it on its thread line) cover."""
    out = [s.dur_ns for s in spans]
    lines: dict = {}
    for i, s in enumerate(spans):
        lines.setdefault(s.line, []).append(i)
    for idx in lines.values():
        stack: list[int] = []
        for i in sorted(idx, key=lambda i: (spans[i].start_ns, -spans[i].end_ns)):
            s = spans[i]
            while stack and spans[stack[-1]].end_ns < s.end_ns:
                stack.pop()
            if stack:
                out[stack[-1]] -= s.dur_ns
            stack.append(i)
    return out


def overlap_ns(a: list, b: list) -> float:
    """Length of the intersection of two sorted lists of disjoint intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_intervals(busy: list, t0: float, t1: float) -> list:
    """The complement of sorted disjoint ``busy`` intervals in ``[t0, t1]``."""
    edges = [t0] + [x for iv in busy for x in iv] + [t1]
    return [(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s]


def idle_explained(busy_by_device: dict, spans: list[Span], t0: float, t1: float) -> float | None:
    """Share of the device's idle time in ``[t0, t1]`` that a stage span
    covers, averaged over devices; None when no device was idle."""
    stages = trace._union([(s.start_ns, s.end_ns) for s in spans if s.name not in NOT_STAGES])
    shares = []
    for busy in busy_by_device.values():
        idle = idle_intervals(busy, t0, t1)
        idle_ns = sum(e - s for s, e in idle)
        if idle_ns > 0:
            shares.append(overlap_ns(idle, stages) / idle_ns)
    return sum(shares) / len(shares) if shares else None


@dataclasses.dataclass
class ProgramTrace:
    spans: list  # clipped to the window
    self_ns: list  # aligned with spans
    busy: dict  # device plane -> busy intervals in the window
    t0: float
    t1: float

    def of(self, name: str) -> list[Span]:
        return [s for s in self.spans if s.name == name]

    def total_ms(self, name: str) -> float:
        return sum(s.dur_ns for s in self.of(name)) / 1e6

    def self_ms(self, name: str) -> float:
        return sum(t for s, t in zip(self.spans, self.self_ns) if s.name == name) / 1e6

    def idle_explained(self) -> float | None:
        return idle_explained(self.busy, self.spans, self.t0, self.t1)


def reduce(events: list, spans: list[Span]) -> ProgramTrace | None:
    """The program's spans against the device events and the window of
    :func:`chipbench.trace.load_events`; None without program spans."""
    t0, t1 = trace.window_of(events)
    spans = clip(spans, t0, t1)
    if not spans:
        return None
    busy = {p: trace.busy_intervals(events, t0, t1, p) for p in trace.device_planes(events)}
    return ProgramTrace(spans, self_ns(spans), busy, t0, t1)


@functools.lru_cache(maxsize=1)
def _read(path: str, mtime_ns: int) -> ProgramTrace | None:
    # <trace dir>/plugins/profile/<run>/<host>.xplane.pb: load_events reads
    # the newest file under the trace dir, which is ``path``
    trace_dir = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(path))))
    return reduce(trace.load_events(trace_dir), load_spans(path))


def of(run) -> ProgramTrace | None:
    """The reduced program spans of a traced run (read once per trace);
    None for an untraced run or a program that writes no spans."""
    if run.trace is None:
        return None
    path = newest(TRACE_DIR)
    if path is None:
        return None
    return _read(path, os.stat(path).st_mtime_ns)
