"""Share of the traced window in which the device ran no operation while
the served entry's own set-up or finish ran on the host, in %: the spans
``search.setup`` (scheduler, pool, staging and the warm-up dispatch of
every call) and ``search.finish`` (end-of-call counters and the output
arrays). Idle under the spans is |spans U busy| - |busy|, both unions
clipped to the window, averaged over the device planes as
``device_idle_share.*`` averages busy time. None when none of the spans
lies in the window, so a renamed span reads as missing, not as 0."""

import trace_reduce as tr

LAYER = "served entry"
SOURCE = "device_trace"
MOVES = "latency_p50_s"
# the program's span names (``repro.core.spans``)
SPANS = ("search.setup", "search.finish")


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def read(ctx):
    t = ctx.trace
    if t is None or t.window_s <= 0:
        return None
    host = [(e.start_ns, e.end_ns) for e in t.events
            if e.plane == tr.HOST_PLANE and e.name in SPANS]
    if not tr.clip(host, t.lo, t.hi):
        return None
    planes = tr.device_planes(t.events)
    idle = 0.0
    for p in planes:
        busy = [(e.start_ns, e.end_ns)
                for e in tr.ops(t.events, p, t.lo, t.hi)]
        idle += (length(tr.clip(tr.union(host + busy), t.lo, t.hi))
                 - length(tr.clip(tr.union(busy), t.lo, t.hi)))
    return 100.0 * idle / len(planes) / (t.hi - t.lo)
