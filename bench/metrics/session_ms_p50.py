"""Median host-clock time of one call of the served entry
(``stream_search``), in ms: from the call to the return of its results,
which ends in their transfer to the host."""

import numpy as np

LAYER = "served entry"
SOURCE = "host_clock"
MOVES = "latency_p95_s"


def read(ctx):
    if not ctx.served.call_s:
        return None
    return 1e3 * float(np.median(ctx.served.call_s))
