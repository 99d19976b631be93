"""Share of the roofline the distance kernel (``kernels/distance``) ran
at, in %: the least time the chip needs for the window's distance work
over the kernel's device time in the trace. The work is the algorithm's:
2 d FLOP for every distance the traversal computed (the sum of the
queries' ``n_dist``), and every unique page read (``pages_unique``) as
its bytes. Memory bound at these shapes."""

import work

LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "qps"
# The compiler names the distance kernel's op after its call site
# ("%closed_call.N"), not after the kernel: it is the Pallas call whose
# first operand is the 1-D page-id list and second the (tiles, QB, d)
# query tiles.
KERNEL = (r"custom-call\(s32\[\d+\]\{[^}]*\} %[^,]+, "
          r"f32\[\d+,\d+,\d+\].*tpu_custom_call")


def read(ctx):
    if ctx.trace is None or not ctx.peaks:
        return None
    t = ctx.trace.op_time_s(KERNEL)
    flops, nbytes = work.distance_work(
        ctx.counters["n_dist"], int(ctx.cfg["dim"]),
        ctx.counters["pages_unique"], int(ctx.cfg["page_size"]))
    share = work.roofline_share(flops, nbytes, t, ctx.peaks)
    return None if share is None else share[0]
