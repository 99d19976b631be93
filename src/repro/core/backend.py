"""Pluggable kernel backend for the engine's two hot paths.

Every distance computation and every candidate merge in the repo funnels
through a :class:`KernelBackend`, which owns

  * **mode selection** — ``auto | pallas | interpret | ref | jnp``.
    ``auto`` resolves to ``pallas`` on TPU and ``ref`` elsewhere; the
    remaining modes pin a layer of the kernel stack explicitly:

        oracle (core/ref_search.py, numpy)       — pure-python semantics
          -> ``jnp``        inline XLA ops       — the fused fast path on
                                                   CPU/GPU (gather + dot,
                                                   lax.sort)
          -> ``ref``        kernels/*/ref.py     — the kernels' jnp
                                                   oracles behind the same
                                                   tiling/padding as Pallas
          -> ``interpret``  Pallas, interpreted  — kernel code, no TPU
          -> ``pallas``     Pallas, compiled     — the SiN/SSD-FPGA analogue

    All five produce bit-identical results on integer-valued vectors
    (proven in tests/test_backend_dispatch.py and tests/test_engine*.py).

  * **tile padding** — queries pad to hardware-friendly tiles
    (kernels/distance/ops.py::pad_tiles), sort widths pad to the next
    power of two with (BIG_DIST, ID_SENTINEL) filler that lexicographically
    sorts after every real entry (kernels/topk/ops.py::sort_op).

  * **dispatch** for the two kernels:
      - paged SiN distance  (kernels/distance) — one grid step = one NAND
        page read; assignments are regrouped by physical page first so
        consecutive steps hit the Pallas copy-elision fast path (the
        paper's ``pageLocBit``). With ``coalesce_qb > 0`` the regrouped
        assignments are further packed into per-page query tiles of
        width ``coalesce_qb``: one page read serves up to that many
        same-page assignments (the Allocator's two-level scheduling),
        shrinking the grid from #assignments to
        ``coalesce_num_tiles(...)`` steps.
      - lexicographic bitonic sort + merge (kernels/topk) — (dist, id)
        2-key networks with payload lanes, used for the candidate-list
        merge. ``merge_pairs`` runs a single merge pass over two
        already-sorted lists instead of re-sorting sorted data. Bool
        payloads (the ``expanded`` flags) are packed to i32 for the VPU.

The dataclass is frozen + hashable so it can live inside jit-static
arguments (EngineParams carries one as ``kernel_mode``/``coalesce_qb``).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.kernels.distance.ops import (coalesce_num_tiles,
                                        coalesced_distance_op,
                                        paged_distance_op)
from repro.kernels.topk.ops import merge_sorted_op, sort_op
from repro.kernels.topk.ref import bitonic_sort_ref
from repro.utils import BIG_DIST, cdiv

MODES = ("auto", "pallas", "interpret", "ref", "jnp")


def resolve_mode(mode: str) -> str:
    """'auto' -> 'pallas' on TPU, 'ref' elsewhere; other modes unchanged."""
    if mode not in MODES:
        raise ValueError(f"kernel mode {mode!r} not in {MODES}")
    if mode == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "ref"
    return mode


@dataclasses.dataclass(frozen=True)
class KernelBackend:
    """Mode selection + padding + dispatch for the hot kernels.

    mode         : see :data:`MODES`; resolved lazily so a config built on
                   the host applies to whatever backend jit runs on.
    coalesce_qb  : per-page query-tile width for ``item_distances``:
                   up to this many same-page assignments share one page
                   read. 0 keeps the per-item path (one grid step per
                   assignment). Use a multiple of 8 on TPU (f32 sublane).
    coalesce_min_reuse : minimum static page-reuse estimate
                   (items / store pages) at which the coalesced tiles
                   engage; workloads below it (near-unique pages) run
                   the per-item grid, which beats mostly-empty tiles.
    """

    mode: str = "auto"
    coalesce_qb: int = 8
    coalesce_min_reuse: float = 2.0

    def __post_init__(self):
        if self.mode not in MODES:
            raise ValueError(f"kernel mode {self.mode!r} not in {MODES}")
        if self.coalesce_qb < 0:
            raise ValueError(
                f"coalesce_qb must be >= 0, got {self.coalesce_qb}")

    @property
    def resolved(self) -> str:
        return resolve_mode(self.mode)

    @property
    def inline(self) -> bool:
        """True when hot paths use inline jnp ops instead of the kernels."""
        return self.resolved == "jnp"

    # -- merge/sort ---------------------------------------------------------
    def sort_pairs(self, dists: jax.Array, ids: jax.Array,
                   *payload: jax.Array):
        """Ascending lexicographic (dist, id) row sort, payload carried.

        The payload lanes follow their (dist, id) pair through the sort.
        Ties — identical (dist, id) — must carry identical payloads for
        the unstable bitonic network to agree with stable lax.sort; the
        engine guarantees this (duplicate ids never survive dedup, and
        sentinel slots are never marked expanded).
        """
        mode = self.resolved
        if mode == "jnp":
            return bitonic_sort_ref(dists, ids, *payload)
        packed = tuple(p.astype(jnp.int32) if p.dtype == jnp.bool_ else p
                       for p in payload)
        out = sort_op(dists, ids, *packed, mode=mode)
        restored = tuple(o.astype(p.dtype) for o, p in zip(out[2:], payload))
        return (out[0], out[1]) + restored

    def merge_pairs(self, d_a: jax.Array, i_a: jax.Array,
                    d_b: jax.Array, i_b: jax.Array,
                    pay_a: tuple = (), pay_b: tuple = ()):
        """Merge two already (dist, id)-sorted row sets into sorted rows.

        The Gather-stage fast path: a single bitonic merge pass
        (O(n log n) comparators) over concat(A, reversed B) instead of
        re-running the full sorting network on data that is already
        sorted. Payload lanes pair up across the two sides (the
        candidate list's ``expanded`` flags on the A side, zeros for the
        fresh proposals on the B side). Same tie discipline as
        :meth:`sort_pairs`: equal (dist, id) pairs carry equal payloads.
        """
        mode = self.resolved
        if mode == "jnp":
            cat = tuple(jnp.concatenate([a, b], axis=-1)
                        for a, b in zip((d_a, i_a) + tuple(pay_a),
                                        (d_b, i_b) + tuple(pay_b)))
            return bitonic_sort_ref(*cat)
        packed_a = tuple(p.astype(jnp.int32) if p.dtype == jnp.bool_ else p
                         for p in pay_a)
        packed_b = tuple(p.astype(jnp.int32) if p.dtype == jnp.bool_ else p
                         for p in pay_b)
        out = merge_sorted_op(d_a, i_a, d_b, i_b, pay_a=packed_a,
                              pay_b=packed_b, mode=mode)
        restored = tuple(o.astype(p.dtype) for o, p in zip(out[2:], pay_a))
        return (out[0], out[1]) + restored

    def merge_unsorted(self, d_a: jax.Array, i_a: jax.Array,
                       d_b: jax.Array, i_b: jax.Array,
                       pay_a: tuple = (), pay_b: tuple = ()):
        """Merge sorted rows A with **unsorted** rows B into sorted rows
        — the candidate-list update's real shape (A is the sorted list,
        B the fresh proposals as they arrived).

        Kernel modes pre-sort B with the bitonic network and run the
        single ``merge_pairs`` pass: sorting only the small side plus
        one merge beats re-running the full network on the
        concatenation (BENCH_kernels merge-vs-resort, ref ~1.2x).
        Inline jnp mode re-sorts the concatenation directly —
        ``lax.sort`` has no merge primitive, so a "merge" spelled as
        sort(B) + sort(concat) does strictly more work than one sort
        (the 0.76x regression this method removes); the smoke gate
        asserts every non-inline mode stays >= 1.0x of its own resort
        baseline."""
        if self.inline:
            cat = tuple(jnp.concatenate([a, b], axis=-1)
                        for a, b in zip((d_a, i_a) + tuple(pay_a),
                                        (d_b, i_b) + tuple(pay_b)))
            return bitonic_sort_ref(*cat)
        sb = self.sort_pairs(d_b, i_b, *pay_b)
        return self.merge_pairs(d_a, i_a, sb[0], sb[1], pay_a=pay_a,
                                pay_b=tuple(sb[2:]))

    # -- distance -----------------------------------------------------------
    def coalesce_active(self, items: int, npages: int) -> bool:
        """Whether ``item_distances`` engages the coalesced per-page
        query tiles for ``items`` assignments over an ``npages``-page
        store. The static reuse estimate ``items / npages`` (mean
        assignments per page if every page were touched) must clear
        ``coalesce_min_reuse``: below it nearly every tile is a partial
        (BENCH_kernels dup=1: 48.5 ms coalesced at occupancy 0.062 vs
        28.2 ms per-item), so the backend falls back to the per-item
        grid. Both shapes are static, so the choice is jit-safe."""
        return (self.coalesce_qb > 0
                and items >= self.coalesce_min_reuse * max(1, npages))

    def distance_grid_steps(self, items: int, npages: int) -> int:
        """Static grid-step (page-read) count ``item_distances`` launches
        in kernel modes for ``items`` assignments over ``npages`` pages —
        the perf metric the duplicate-page benchmark sweeps."""
        if self.coalesce_active(items, npages):
            return coalesce_num_tiles(items, npages, self.coalesce_qb)
        return items

    def distance_lanes(self, items: int, npages: int) -> int:
        """Static query lanes ``item_distances`` computes for ``items``
        assignments over ``npages`` pages: tiles x tile width when the
        coalesced tiles engage, else one lane per assignment."""
        if self.inline or not self.coalesce_active(items, npages):
            return items
        return self.distance_grid_steps(items, npages) * self.coalesce_qb

    def coalesce_occupancy(self, items: int, npages: int) -> float:
        """Fraction of coalesced-tile query lanes holding a real
        assignment: ``items / (grid_steps * qb)``. 1.0 means every page
        read serves a full qb-wide tile; low values mean the static
        tile bound is paying for mostly-empty partial tiles (the
        ROADMAP two-pass-packing lever's headroom metric). The per-item
        paths (qb == 0, or the low-reuse fallback) are width-1 tiles,
        occupancy 1.0 by construction.
        """
        qb = self.coalesce_qb
        if qb <= 0 or items <= 0 or not self.coalesce_active(items,
                                                             npages):
            return 1.0
        return items / (self.distance_grid_steps(items, npages) * qb)

    def paged_distance(self, page_ids, queries, qq, db, vnorm) -> jax.Array:
        """(T, QB, d) query tiles x (NP, P, d) paged db -> (T, QB, P)."""
        mode = self.resolved
        return paged_distance_op(page_ids, queries, qq, db, vnorm,
                                 mode="ref" if mode == "jnp" else mode)

    def item_distances(self, ppage, slot, mask, qvec, qq, db, vnorm):
        """Per-assignment squared-L2 distances where the vectors live.

        ppage/slot/mask/qq : (I,) physical page, slot-in-page, validity,
                             per-item query self-dot
        qvec               : (I, d) per-item query payload
        db, vnorm          : (NP, P, d), (NP, P) shard-resident store
        returns            : (I,) f32; masked items get BIG_DIST.

        Kernel modes regroup the assignments by physical page (the
        Allocator's dynamic scheduling), segment the regrouped stream
        into per-page query tiles of width ``coalesce_qb``, and one
        (qb, d) x (d, P) grid step serves the whole tile — one page read
        for up to qb assignments (two-level scheduling). A direct
        scatter of the original positions undoes the regrouping (one
        sort total — no argsort-of-argsort inverse permutation).
        ``coalesce_qb == 0`` is the per-item path: width-1 tiles, one
        (1, d) x (d, P) page read per assignment — consecutive items on
        the same page still reuse the page buffer via Pallas copy
        elision.
        """
        if self.inline:
            v = db[ppage, slot].astype(jnp.float32)
            vn = vnorm[ppage, slot]
            qv = jnp.sum(qvec.astype(jnp.float32) * v, axis=-1)
            dist = qq - 2.0 * qv + vn
            return jnp.where(mask, dist, BIG_DIST)
        # low-reuse fallback: qb=1 is the per-item grid (width-1 tiles)
        qb = (max(1, self.coalesce_qb)
              if self.coalesce_active(ppage.shape[0], db.shape[0]) else 1)
        return coalesced_distance_op(
            ppage, slot, mask, qvec, qq, db, vnorm,
            qb=qb, mode=self.resolved)


def paged_view(db: jax.Array, vnorm: jax.Array, page_size: int):
    """Reshape a flat (N, d) store into the paged (NP, P, d) layout the
    SiN kernel reads, zero-padding the tail page."""
    n, d = db.shape
    npages = cdiv(n, page_size)
    pad = npages * page_size - n
    if pad:
        db = jnp.concatenate([db, jnp.zeros((pad, d), db.dtype)], axis=0)
        vnorm = jnp.concatenate([vnorm, jnp.zeros((pad,), vnorm.dtype)])
    return (db.reshape(npages, page_size, d),
            vnorm.reshape(npages, page_size))
