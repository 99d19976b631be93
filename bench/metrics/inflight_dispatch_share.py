"""Share of the window's round-chunk dispatches that the scheduler
launched before it had synced the chunk before them, in %: 100 x the
sum of ``StreamStats.inflight_dispatches`` over the sum of
``StreamStats.host_dispatches``, over the window's calls. None where
the program does not count launches ahead of the sync."""

LAYER = "scheduler"
SOURCE = "program_counter"
MOVES = "qps"


def read(ctx):
    stats = ctx.served.stats
    if not all(hasattr(s, "inflight_dispatches") for s in stats):
        return None
    launched = sum(s.host_dispatches for s in stats)
    if not launched:
        return None
    return 100.0 * sum(s.inflight_dispatches for s in stats) / launched
