"""Share of the traced window in which the device ran no operation:
1 - (union of the device's op intervals) / (window length), in %."""

LAYER = "device"
SOURCE = "device_trace"
MOVES = "latency_p95_s"


def read(ctx):
    if ctx.trace is None or ctx.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s / ctx.trace.window_s)
