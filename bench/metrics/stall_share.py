"""Share of the window's live row-rounds in which a row of the tiered
page store stalled, in %: 100 x ``stalls`` (the stall rounds of the
window's queries, ``StreamStats.stalls``) / ``live_row_rounds`` (live
rows summed over the window's rounds). None without a tiered store."""

LAYER = "page store"
SOURCE = "program_counter"
MOVES = "qps"


def read(ctx):
    stalls = ctx.counters.get("stalls")
    live = ctx.counters.get("live_row_rounds")
    if stalls is None or not live:
        return None
    return 100.0 * stalls / live
