"""Mean share of the scheduler's slot pool that held a live query, over
every round of the serving clock of the window's calls, in %
(``StreamStats.occupancy_trace``, busy and idle rounds)."""

LAYER = "scheduler"
SOURCE = "program_counter"
MOVES = "qps"


def read(ctx):
    occ = ctx.counters["occupancy"]
    return None if occ is None else 100.0 * occ
