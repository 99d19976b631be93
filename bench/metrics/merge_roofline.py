"""Share of the roofline the bitonic sort and merge kernels
(``kernels/topk``) ran at, in %: the bytes of the candidate lists the
window's merges must read and write (live rows per round from the
scheduler's ``occupancy_trace``, times (2 L + M) entries of 8 bytes, M =
W (degree + speculation width)) over peak bandwidth, divided by the
kernels' device time in the trace."""

import work

LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "qps"
# the ops of the two bitonic Pallas calls, named after their jitted
# wrappers ("%vmap_jit_bitonic_sort__.N", "%vmap_jit_bitonic_merge__.N")
KERNEL = r"bitonic_(sort|merge).*tpu_custom_call"


def read(ctx):
    if ctx.trace is None or not ctx.peaks:
        return None
    t = ctx.trace.op_time_s(KERNEL)
    nbytes = work.merge_bytes(
        ctx.counters["live_row_rounds"], int(ctx.cfg["L"]),
        int(ctx.cfg["W"]), int(ctx.cfg["degree"]))
    share = work.roofline_share(0.0, nbytes, t, ctx.peaks)
    return None if share is None else share[0]
