"""The work a served window asked of each kernel, counted from the
algorithm and the traversal's own counts, not from the kernel's shapes.

A later implementation that does the same search is measured against the
same work, however it pads, tiles or coalesces.
"""
from __future__ import annotations

F32 = 4
ENTRY = 8   # one candidate: a float32 distance and an int32 id


def distance_work(n_dist: int, dim: int, pages_unique: int,
                  page_size: int) -> tuple[float, float]:
    """(FLOP, bytes) of the distances a window computed.

    A distance of a query to a vector costs 2 d FLOP (d products, d
    sums). The vectors come from pages: every unique page read is
    ``page_size`` vectors of d float32 and their squared norms."""
    flops = 2.0 * dim * n_dist
    page_bytes = page_size * (dim + 1) * F32
    return flops, float(pages_unique) * page_bytes


def merge_bytes(live_row_rounds: int, L: int, W: int, degree: int,
                spec_width: int = 0) -> float:
    """Bytes of the candidate lists the merges of a window read and
    write: per live row and round, the L-long list and its M proposals
    (M = W (degree + spec width)) are read and the new L-long list is
    written, each entry a distance and an id."""
    M = W * (degree + spec_width)
    return float(live_row_rounds) * (2 * L + M) * ENTRY


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peaks: dict) -> tuple[float, str] | None:
    """(share of the roofline in %, the bound) for work that took
    ``seconds`` of kernel time; None where there is no time to divide
    by. The least time is the larger of FLOP over peak FLOP/s and bytes
    over peak bandwidth."""
    if seconds <= 0:
        return None
    t_compute = flops / peaks["flops_bf16"]
    t_memory = nbytes / peaks["hbm_bytes_per_s"]
    bound = "compute" if t_compute > t_memory else "memory"
    return 100.0 * max(t_compute, t_memory) / seconds, bound
