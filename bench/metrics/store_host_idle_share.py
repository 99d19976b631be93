"""Share of the traced window in which the device ran no operation while
the host worked on the tiered page store's chunk boundary, in %: the span
``search.pages`` (the bitmap and pool transfer, ``PageStore.boundary``'s
note, commit, demand fetch and prefetch staging, and the consts refresh).
Idle under the span is |span U busy| - |busy|, both unions clipped to the
window, averaged over the device planes, as in
``boundary_host_idle_share.*``. None when the span is not in the window:
a resident index writes none."""

import trace_reduce as tr

LAYER = "page store"
SOURCE = "device_trace"
MOVES = "qps"
# the program's span name (``repro.core.spans.PAGES``)
SPANS = ("search.pages",)


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
