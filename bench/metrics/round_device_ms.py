"""Device time of one engine round (``core/engine.py``), in ms: the
device time of the round-chunk programs in the trace over the rounds the
window stepped."""

LAYER = "engine round"
SOURCE = "device_trace"
MOVES = "qps"
MODULE = r"engine_run_chunk"


def read(ctx):
    if ctx.trace is None or not ctx.counters["rounds"]:
        return None
    t = ctx.trace.module_time_s(MODULE)
    return 1e3 * t / ctx.counters["rounds"] if t > 0 else None
