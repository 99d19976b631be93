"""Round-chunk dispatches from the host per query served
(``StreamStats.host_dispatches`` over the window's queries)."""

LAYER = "scheduler"
SOURCE = "program_counter"
MOVES = "latency_p95_s"


def read(ctx):
    q = ctx.counters["queries"]
    return ctx.counters["dispatches"] / q if q else None
