"""Pages the tiered page store moved from host RAM to the device per
query served in the window: (``demand_fetches`` + ``prefetch_issued``) /
``queries``, the store's own counters over the window. None without a
tiered store."""

LAYER = "page store"
SOURCE = "program_counter"
MOVES = "qps"


def read(ctx):
    c = ctx.counters
    if "demand_fetches" not in c or not c.get("queries"):
        return None
    return (c["demand_fetches"] + c["prefetch_issued"]) / c["queries"]
