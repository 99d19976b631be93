"""99th percentile of the window's query latency, in ms: the slow calls
that the end-to-end 95th percentile does not reach. A 30-s window at 400
queries/s leaves 120 queries beyond it, a few calls' worth, too few to
hold a bound, so it is read in the traced run and bounds nothing."""

import numpy as np

LAYER = "front end"
SOURCE = "host_clock"
MOVES = "latency_p95_s"


def read(ctx):
    if not ctx.served.latency_s:
        return None
    return 1e3 * float(np.percentile(np.concatenate(ctx.served.latency_s),
                                     99))
