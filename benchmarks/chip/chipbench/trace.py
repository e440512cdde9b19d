"""Reduction of a profiler trace to the benchmark's device numbers.

:func:`load_events` turns the ``.xplane.pb`` the JAX profiler writes into
plain :class:`Event` records; everything after that works on those records,
so the reduction is checked on a small recorded trace in the tests.

* The window is the ``chipbench/window`` host annotation the harness writes.
* Device busy time is the union of the intervals of the device's operation
  events (the ``XLA Ops`` line of each ``/device:`` plane), clipped to the
  window and averaged over the devices.
* Module time sums the ``XLA Modules`` events of each program by name.
* Each idle gap of the device is attributed to the harness span
  (``chipbench/<layer>``) the host was in at the gap's midpoint, or to
  ``loop`` (the simulator's own host work) when it was in none.
"""
from __future__ import annotations

import dataclasses
import glob
import os

SPAN_PREFIX = "chipbench/"
WINDOW = SPAN_PREFIX + "window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def load_events(trace_dir: str) -> list[Event]:
    """Device events and the harness's host annotations of the newest trace
    under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    out = []
    for plane in ProfileData.from_file(files[-1]).planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for e in line.events:
                if device or e.name.startswith(SPAN_PREFIX):
                    out.append(Event(plane.name, line.name, e.name, float(e.start_ns), float(e.duration_ns)))
    return out


def window_of(events: list[Event]) -> tuple[float, float]:
    w = [e for e in events if e.name == WINDOW]
    if len(w) != 1:
        raise ValueError(f"expected one {WINDOW} annotation, found {len(w)}")
    return w[0].start_ns, w[0].end_ns


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def busy_intervals(events, t0, t1, plane):
    clipped = [(max(e.start_ns, t0), min(e.end_ns, t1)) for e in events
               if e.plane == plane and e.line == OPS_LINE and e.end_ns > t0 and e.start_ns < t1]
    return _union([iv for iv in clipped if iv[1] > iv[0]])


def device_planes(events) -> list[str]:
    return sorted({e.plane for e in events if e.line == OPS_LINE})


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float  # averaged over devices
    module_s: dict  # program name -> device seconds in the window (summed over devices)
    idle_gaps: list  # [(host span, seconds)], longest first
    devices: int


def summarize(events: list[Event]) -> TraceSummary:
    t0, t1 = window_of(events)
    planes = device_planes(events)
    if not planes:
        raise ValueError("the trace holds no device operation events")
    busy_total = 0.0
    gaps = []
    spans = sorted((e.start_ns, e.end_ns, e.name[len(SPAN_PREFIX):]) for e in events
                   if e.name.startswith(SPAN_PREFIX) and e.name != WINDOW)
    for plane in planes:
        busy = busy_intervals(events, t0, t1, plane)
        busy_total += sum(e - s for s, e in busy)
        edges = [t0] + [x for iv in busy for x in iv] + [t1]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append((_span_at(spans, (s + e) / 2), (e - s) / 1e9))
    module_s: dict = {}
    for e in events:
        if e.line == MODULES_LINE and e.end_ns > t0 and e.start_ns < t1:
            d = min(e.end_ns, t1) - max(e.start_ns, t0)
            module_s[e.name] = module_s.get(e.name, 0.0) + d / 1e9
    gaps.sort(key=lambda g: -g[1])
    return TraceSummary(
        window_s=(t1 - t0) / 1e9,
        busy_s=busy_total / len(planes) / 1e9,
        module_s=module_s,
        idle_gaps=gaps,
        devices=len(planes),
    )


def _span_at(spans, t) -> str:
    """The innermost harness span open at ``t``, else ``loop``."""
    best = None
    for s, e, name in spans:
        if s > t:
            break
        if e >= t and (best is None or s >= best[0]):
            best = (s, name)
    return best[1] if best else "loop"


def module_seconds(summary: TraceSummary, needle: str) -> float | None:
    """Device seconds of the programs whose trace name contains ``needle``;
    None when none ran in the window."""
    hits = [v for k, v in summary.module_s.items() if needle in k]
    return sum(hits) if hits else None
