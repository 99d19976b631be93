"""From a profiler trace to device busy and idle time, per-kernel device
time and the longest idle gaps by the harness's host spans.

The trace is JAX's ``.xplane.pb``; :func:`load_events` flattens it into
:class:`Event` rows so the arithmetic below can be tested on events made
by hand. Device planes are named ``/device:TPU:<n>``; their "XLA Ops" line
holds one event per operation run, their "XLA Modules" line one per
compiled program run. Host spans (``jax.profiler.TraceAnnotation``) sit on
the ``/host:CPU`` plane, on the same clock.
"""
from __future__ import annotations

import collections
import glob
import os
import re
from typing import Iterable, NamedTuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
WINDOW_SPAN = "bench.window"
NAME_CHARS = 200    # an op's name is its HLO text: keep the head of it
# control flow whose op event spans the ops of its body
CONTAINER = re.compile(r"^%(while|conditional|call)[.\s]")


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def load_events(trace_dir: str) -> list[Event]:
    """Every event of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(max(files, key=os.path.getmtime))
    return [Event(p.name, ln.name, e.name, float(e.start_ns),
                  float(e.duration_ns))
            for p in pd.planes for ln in p.lines for e in ln.events]


def union(intervals: Iterable[tuple[float, float]]) -> list[list[float]]:
    """Sorted, merged [start, end] intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals, lo: float, hi: float) -> list[list[float]]:
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if e > lo and s < hi]


def window_of(events: list[Event]) -> tuple[float, float]:
    """[start, end] ns of the harness's window span."""
    spans = [e for e in events
             if e.plane == HOST_PLANE and e.name == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    return min(e.start_ns for e in spans), max(e.end_ns for e in spans)


def device_planes(events: list[Event]) -> list[str]:
    return sorted({e.plane for e in events if DEVICE_PLANE.match(e.plane)})


def ops(events: list[Event], plane: str, lo: float, hi: float):
    return [e for e in events if e.plane == plane and e.line == OPS_LINE
            and e.end_ns > lo and e.start_ns < hi]


def busy_ns(events: list[Event], plane: str, lo: float, hi: float) -> float:
    """Length of the union of one device's op intervals in [lo, hi]."""
    busy = clip(union((e.start_ns, e.end_ns)
                      for e in ops(events, plane, lo, hi)), lo, hi)
    return sum(e - s for s, e in busy)


def line_time_ns(events: list[Event], line: str, pattern: str,
                 lo: float, hi: float) -> float:
    """Summed device time of the events on ``line`` of every device
    plane whose name matches ``pattern``, clipped to [lo, hi]."""
    rx = re.compile(pattern)
    return sum(min(e.end_ns, hi) - max(e.start_ns, lo) for e in events
               if DEVICE_PLANE.match(e.plane) and e.line == line
               and rx.search(e.name) and e.end_ns > lo and e.start_ns < hi)


def top_ops(events: list[Event], lo: float, hi: float, n: int = 10):
    """[[op name, seconds], ...]: the ``n`` op names that took the most
    device time in [lo, hi], summed over devices (a loop or call is left
    out: its time is its body's ops')."""
    tot: dict[str, float] = collections.defaultdict(float)
    for p in device_planes(events):
        for e in ops(events, p, lo, hi):
            if not CONTAINER.match(e.name):
                tot[e.name] += min(e.end_ns, hi) - max(e.start_ns, lo)
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k[:NAME_CHARS], v * 1e-9] for k, v in top]


def idle_gaps(events: list[Event], plane: str, lo: float, hi: float,
              span_names: Iterable[str], n: int = 10):
    """[[host span, seconds], ...]: the ``n`` longest intervals in
    [lo, hi] in which the device ran nothing, each named by the host span
    of ``span_names`` that overlaps it most ("other" where none does)."""
    names = set(span_names)
    spans = [e for e in events if e.plane == HOST_PLANE and e.name in names
             and e.end_ns > lo and e.start_ns < hi]
    busy = clip(union((e.start_ns, e.end_ns)
                      for e in ops(events, plane, lo, hi)), lo, hi)
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:n]:
        best, best_ov = "other", 0.0
        for sp in spans:
            ov = min(e, sp.end_ns) - max(s, sp.start_ns)
            if ov > best_ov:
                best, best_ov = sp.name, ov
        out.append([best, (e - s) * 1e-9])
    return out


class Reduced(NamedTuple):
    busy_s: float          # device busy time, averaged over devices
    window_s: float        # length of the traced window
    device_ops: list       # [[op, seconds], ...]
    idle_gaps: list        # [[host span, seconds], ...] of device 0
    events: list           # the events, for the metric readers
    lo: float
    hi: float

    def op_time_s(self, pattern: str) -> float:
        return line_time_ns(self.events, OPS_LINE, pattern, self.lo,
                            self.hi) * 1e-9

    def module_time_s(self, pattern: str) -> float:
        return line_time_ns(self.events, MODULES_LINE, pattern, self.lo,
                            self.hi) * 1e-9


def reduce(events: list[Event], span_names: Iterable[str],
           device_ids: Iterable[int] | None = None) -> Reduced:
    """The window's device time, over the planes of ``device_ids`` (the
    cell's own chips; every device plane where None): a chip of the host
    that the cell does not use is no idle time of the cell's."""
    lo, hi = window_of(events)
    planes = device_planes(events)
    if device_ids is not None:
        ids = {f"/device:TPU:{i}" for i in device_ids}
        planes = [p for p in planes if p in ids]
    if not planes:
        raise ValueError("no device plane of the cell's in the trace")
    busy = sum(busy_ns(events, p, lo, hi) for p in planes) / len(planes)
    return Reduced(busy * 1e-9, (hi - lo) * 1e-9, top_ops(events, lo, hi),
                   idle_gaps(events, planes[0], lo, hi, span_names),
                   events, lo, hi)
