"""Open-loop streaming-serving benchmark: what query-level scheduling
buys over frozen batches, and what hit-rate speculation saves.

Poisson arrivals drive the streaming scheduler (core/scheduler.py) over
a deliberately *skewed* query mix — half the queries are near-duplicates
of database points (converge in a handful of rounds), half are far
uniform-random points (run to the round cap) — the regime where a frozen
batch wastes the most: its fast queries sit done, occupying rows of
every remaining round's distance/merge/a2a work until the slowest
straggler finishes. Three disciplines are measured on identical
workloads:

  * ``frozen``  — admit only into an all-free pool (the host-issued
    synchronous batches of the computational-storage baseline, Kim et
    al. arXiv:2207.05241);
  * ``refill``  — continuous admission: retire finished queries each
    round, refill freed slots immediately (NDSEARCH's query-level
    scheduling, §V);
  * ``dynamic`` — refill + the per-query hit-rate speculation
    controller (§V-B) on top of the same static ``spec_max``.

Reported per discipline: slot occupancy, round-normalized throughput
(queries/round), sustained wall QPS, p50/p95/p99 latency, unique page
reads, recall. A static ``spec_width`` sweep rides along so the
controller has a best-static baseline to beat on page reads, and a
``round_chunk`` sweep measures the host-sync model: engine rounds per
host dispatch vs host dispatches/query and wall QPS, on both the sim
stepper and (when enough devices are visible) the shard_map stepper —
with **in-jit admission** (``engine_run_chunk_admit``: the pending
queue lives on device and freed slots reseat inside the chunk) against
the host-paced admission baseline (``injit off``: chunk length
collapses toward one round while the queue drains, the PR-4 model).
Results land in machine-readable ``BENCH_serving.json``.

A tiered-page-store leg always rides along: throughput vs device-
resident fraction (1.0 -> 0.25) on a paced-arrival workload, prefetch
vs demand-only at each tiered point, with the fraction-1.0
bit-identity gate and the half-residency prefetch-must-win gate under
``--smoke`` (see ``tiered_leg``).

``--chaos`` adds the robustness sweep: goodput vs offered load against
the bounded admission ring under both overload policies (``shed`` and
``block``), a mid-run 1-of-8 shard kill under an in-jit deadline
(recall bounded below by the truncated-query fraction), corrupted page
reads quarantined by the guard, and an armed-but-idle gate — every
robustness feature enabled but not firing must be bit-identical to the
plain serving path.

``--smoke`` shrinks the workload and *asserts* the streaming
invariants — refill occupancy/throughput above frozen, controller page
reads at or below controller-off at equal recall, the dispatch gate
(chunked execution must match per-round queries/round with strictly
fewer host syncs), and the in-jit-admission gate (identical round
schedule and bit-identical per-query results vs host admission, with
strictly fewer host dispatches, on the refill and shard_map legs) — so
CI fails loudly on a scheduling regression.
"""
from __future__ import annotations

import os

# before any jax import: split the host CPU so the shard_map stepper
# leg has a real multi-device mesh to run on (no-op if already set;
# 8 covers --shards above the default 4 — beyond that the leg is
# skipped with a printed note)
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import argparse
import dataclasses
import json
import time

import numpy as np

from benchmarks.common import emit
from repro.core.engine import EngineParams, pack_for_engine
from repro.core.graph import brute_force_topk, build_vamana, recall_at_k
from repro.core.luncsr import Geometry, LUNCSR, pack_index
from repro.core.metrics import stream_summary
from repro.core.ref_search import SearchParams
from repro.core.scheduler import poisson_arrivals, stream_search
from repro.data.vectors import VectorDataset


def skewed_queries(db: np.ndarray, nq: int, seed: int = 1):
    """Half near-duplicates of db rows (fast queries), half uniform
    random in the data's bounding box (stragglers) — maximally skewed
    per-query round counts, interleaved so every admission wave mixes
    both kinds."""
    rng = np.random.default_rng(seed)
    d = db.shape[1]
    n_fast = nq // 2                       # even slots get the stragglers
    rows = rng.integers(0, db.shape[0], n_fast)
    fast = db[rows] + 0.01 * rng.standard_normal((n_fast, d))
    lo, hi = db.min(axis=0), db.max(axis=0)
    slow = rng.uniform(lo, hi, (nq - n_fast, d))
    q = np.empty((nq, d), np.float32)
    q[0::2] = slow                         # ceil(nq/2) rows — exact fit
    q[1::2] = fast                         # floor(nq/2) rows
    return q


def build_workload(*, n, d, nq, shards, page_size, r, spec_max, seed):
    ds = VectorDataset("serve-bench", n=n, dim=d, clusters=max(8, n // 128),
                       seed=seed)
    db = ds.materialize()
    adj, medoid = build_vamana(db, r=r, seed=seed)
    geo = Geometry(num_shards=shards, page_size=page_size,
                   pages_per_block=4, dim=d)
    packed = pack_index(
        LUNCSR.from_adjacency(db, adj, geo, entry=medoid,
                              pref_width=spec_max), max_degree=r)
    queries = skewed_queries(db, nq, seed=seed + 1)
    return db, packed, queries


def _scenario(consts, geom, params, entry, queries, *, slots, arrivals,
              dynamic_spec, refill, true_ids, k, round_chunk=1,
              mesh=None, injit_admit=None):
    # the scheduler warms the stepper itself (compile_s in the row);
    # sustained_qps and wall latency measure steady state
    ids, dists, st = stream_search(
        consts, geom, params, entry, queries, num_slots=slots,
        arrivals=arrivals, dynamic_spec=dynamic_spec, refill=refill,
        round_chunk=round_chunk, mesh=mesh, injit_admit=injit_admit)
    row = stream_summary(st)
    row["recall"] = round(float(recall_at_k(ids[:, :k], true_ids)), 4)
    return row, (ids, dists)


def routed_workload(*, n, d, shards, nq, seed):
    """Clustered mixture + shard-straddling queries — the regime
    two-tier routing targets (IVF-style spatial shards).  Each query is
    the midpoint of two points drawn from two random distinct clusters,
    so its ground truth straddles exactly two shards: R=1 hits a recall
    cliff, R=2 covers both sides with two short local legs, and the
    random pairing keeps the per-shard leg load balanced (in-cluster
    queries would concentrate every second-choice leg on whichever
    shard is globally most central)."""
    rng = np.random.default_rng(seed + 7)
    centers = rng.standard_normal((shards, d)).astype(np.float32) * 8.0
    m = n // shards
    blocks = [centers[i] + rng.standard_normal((m, d)).astype(np.float32)
              for i in range(shards)]
    db = np.concatenate(blocks)[rng.permutation(n)]
    qa = rng.integers(0, shards, nq)
    qb = (qa + 1 + rng.integers(0, shards - 1, nq)) % shards
    pa = np.stack([blocks[c][rng.integers(0, m)] for c in qa])
    pb = np.stack([blocks[c][rng.integers(0, m)] for c in qb])
    q = (pa + pb) / 2 + 0.05 * rng.standard_normal((nq, d))
    return db, q.astype(np.float32)


def routed_leg(*, n, d, nq, shards, page_size, r, L, k, slots,
               kernel_mode, seed):
    """Routed-vs-fanout sweep (R in {1, 2, S} at ``shards`` shards).

    Same packed index for every row; only the admission strategy
    differs.  R=2 runs with leg_L=k — a per-leg list of just k suffices
    because each leg is seeded at its shard's medoid, inside the right
    cluster, while fan-out pays the global traversal from the entry
    medoid at full L.  The R=S row collapses to a single leg with the
    global entry and must stay bit-identical to fan-out."""
    from repro.core.router import build_routed_index
    from repro.core.scheduler import routed_stream_search

    db, queries = routed_workload(n=n, d=d, shards=shards, nq=nq, seed=seed)
    ri = build_routed_index(db, shards=shards, page_size=page_size, r=r,
                            centroids_per_shard=8, seed=seed,
                            kernel_mode=kernel_mode)
    consts, geom, entry = pack_for_engine(ri.packed)
    sp = SearchParams(L=L, W=1, k=k)
    params = EngineParams.lossless(sp, slots, ri.packed.max_degree,
                                   kernel_mode=kernel_mode)
    true_ids, _ = brute_force_topk(ri.db, queries, k)
    arrivals = np.zeros(nq, np.int64)

    def row_of(ids, st):
        row = stream_summary(st)
        row["recall"] = round(float(recall_at_k(
            np.asarray(ids)[:, :k], true_ids)), 4)
        row["pages_per_query"] = round(st.pages_unique / nq, 2)
        return row

    i0, d0, st0 = stream_search(consts, geom, params, entry, queries,
                                num_slots=slots, arrivals=arrivals,
                                refill=True)
    rows = {"fanout": row_of(i0, st0)}
    fanout_out = (np.asarray(i0), np.asarray(d0))
    routed_out = {}
    for label, topr, leg_l in (("R=1", 1, None), ("R=2", 2, k),
                               (f"R={shards}", shards, None)):
        ids, dists, st = routed_stream_search(
            consts, geom, params, entry, queries, router=ri.router,
            topr=topr, num_slots=slots, arrivals=arrivals,
            shard_entries=ri.shard_entries, leg_L=leg_l)
        row = row_of(ids, st)
        row["topr"] = topr
        row["leg_L"] = leg_l
        rows[label] = row
        routed_out[label] = (np.asarray(ids), np.asarray(dists))
    return rows, fanout_out, routed_out


def tiered_leg(*, kernel_mode, seed, smoke):
    """Tiered-page-store sweep: throughput vs resident fraction.

    A paced-arrival serving workload (Poisson ~0.25 queries/round, 2
    slots/shard) runs with the device frame cache shrunk from the full
    store (fraction 1.0) down to a quarter, with double-buffered
    speculative prefetch on and off at each tiered point:

      * fraction 1.0 must be **bit-identical** to the untiered path —
        the translation table is the identity, no stall can occur
        (gated under ``--smoke``);
      * at fraction 0.5 speculative prefetch must beat demand-only
        fetching: nonzero prefetch hit rate, strictly fewer stall
        rounds, more queries per clock round (the smoke gate);
      * fraction 0.25 is reported for the curve: the per-chunk working
        set approaches the whole cache there, so prefetch degenerates
        toward demand-only (the pressure throttle in
        ``PageStore._stage`` backs speculation off as demand fetches
        consume the shard's slack).

    Clock rounds (busy + idle) are the throughput denominator: stalls
    stretch a query's wall time even when the round schedule stays
    dense, and paced arrivals leave idle gaps a faster store can close.
    The arrival pacing matters — under an all-at-round-0 closed batch
    the working set is every in-flight query's frontier at once and
    *any* speculative install evicts a demanded page (zero-sum); the
    open-loop regime is where the paper's prefetch overlap pays."""
    from repro.core.pagestore import PageStore
    from repro.launch.search import build_index

    n, d, nq, shards = 2048, 32, 48, 4
    page_size, rdeg, slots, K = 8, 8, 2, 4
    ds = VectorDataset("tiered-bench", n=n, dim=d, clusters=8, seed=seed)
    db0 = ds.materialize()
    queries = ds.queries(nq, seed=seed + 1)
    db, packed = build_index(db0, shards=shards, page_size=page_size,
                             r=rdeg, pref_width=2, seed=seed)
    consts, geom, entry = pack_for_engine(packed)
    sp = SearchParams(L=16, W=1, k=8)
    params = EngineParams.lossless(sp, slots, packed.max_degree,
                                   spec_width=2, kernel_mode=kernel_mode)
    NP = consts["db"].shape[1]
    pt = dataclasses.replace(params, store_pages=NP)
    arrivals = poisson_arrivals(0.25, nq, seed + 7)
    skw = dict(num_slots=slots, round_chunk=K, arrivals=arrivals)

    base_i, base_d, base_st = stream_search(consts, geom, params, entry,
                                            queries, **skw)

    def one(pdev, prefetch):
        ps = PageStore(consts, geom, pdev, w_select=sp.W,
                       prefetch=prefetch)
        ids, dists, st = stream_search(consts, geom, pt, entry, queries,
                                       pagestore=ps, **skw)
        clock = st.total_rounds + st.idle_rounds
        row = stream_summary(st)
        row.update(device_pages=ps.P_dev,
                   resident_fraction=round(ps.resident_fraction, 4),
                   prefetch=prefetch, clock_rounds=clock,
                   queries_per_clock_round=round(nq / max(clock, 1), 4),
                   **ps.counters())
        return row, (np.asarray(ids), np.asarray(dists))

    fracs = (1.0, 0.5) if smoke else (1.0, 0.75, 0.5, 0.25)
    rows, outs = [], {}
    for frac in fracs:
        pdev = max(1, int(round(NP * frac)))
        for prefetch in ((True,) if frac == 1.0 else (True, False)):
            row, out = one(pdev, prefetch)
            rows.append(row)
            outs[(frac, prefetch)] = (row, out)

    emit([[row["resident_fraction"], row["device_pages"],
           row["prefetch"], row["stalls"],
           row["stall_rounds_per_query"], row["prefetch_hit_rate"],
           row["clock_rounds"], row["queries_per_clock_round"],
           row["sustained_qps"]] for row in rows],
         ["fraction", "frames", "prefetch", "stalls", "stalls/query",
          "hit_rate", "clock", "q/clock_round", "qps"],
         f"tiered page store (NP={NP} pages/shard, paced arrivals, "
         f"{shards}x{slots} slots, chunk={K})")

    if smoke:
        full_row, (fi, fd) = outs[(1.0, True)]
        np.testing.assert_array_equal(
            fi, np.asarray(base_i),
            err_msg="tiered fraction 1.0 changed result ids vs the "
                    "untiered path")
        np.testing.assert_array_equal(
            fd, np.asarray(base_d),
            err_msg="tiered fraction 1.0 changed distances vs the "
                    "untiered path")
        assert full_row["stalls"] == 0, (
            f"fraction 1.0 must never stall (identity translation "
            f"table): {full_row['stalls']} stall rounds")
        on, (oi, _) = outs[(0.5, True)]
        off, (xi, _) = outs[(0.5, False)]
        np.testing.assert_array_equal(
            oi, np.asarray(base_i),
            err_msg="tiered fraction 0.5 changed final result ids — "
                    "stalls may delay, never corrupt")
        np.testing.assert_array_equal(
            xi, np.asarray(base_i),
            err_msg="demand-only fraction 0.5 changed final result ids")
        assert on["prefetch_hit_rate"] > off["prefetch_hit_rate"], (
            f"speculative prefetch must land hits demand-only cannot: "
            f"{on['prefetch_hit_rate']} vs {off['prefetch_hit_rate']}")
        assert on["stalls"] < off["stalls"], (
            f"prefetch on must stall strictly less than demand-only at "
            f"half residency: {on['stalls']} vs {off['stalls']}")
        assert (on["queries_per_clock_round"]
                > off["queries_per_clock_round"]), (
            f"prefetch on must sustain more queries/clock-round than "
            f"demand-only at half residency: "
            f"{on['queries_per_clock_round']} vs "
            f"{off['queries_per_clock_round']}")

    half_on = outs[(0.5, True)][0]
    half_off = outs[(0.5, False)][0]
    return rows, {
        "tiered_full_identity": bool(
            np.array_equal(outs[(1.0, True)][1][0], np.asarray(base_i))
            and outs[(1.0, True)][0]["stalls"] == 0),
        "tiered_half_stall_ratio": round(
            half_on["stalls"] / max(half_off["stalls"], 1), 4),
        "tiered_half_qpcr_ratio": round(
            half_on["queries_per_clock_round"]
            / max(half_off["queries_per_clock_round"], 1e-9), 4),
        "tiered_half_hit_rate": half_on["prefetch_hit_rate"],
    }


def live_leg(*, kernel_mode, seed, smoke):
    """Live-index serving sweep (epoch-versioned store): what streaming
    inserts, tombstone deletes and background reorders cost the serving
    path.

    Three sessions on one workload (paced Poisson arrivals):

      * ``frozen``      — the plain packed index (baseline);
      * ``zero-churn``  — live machinery armed (``delta_cap`` > 0) but
        no mutation ever applied: must be **bit-identical** to frozen
        (ids, dists, dispatch count — the zero-cost-when-idle
        contract, gated under ``--smoke``);
      * ``churn``       — a Poisson insert/delete schedule with
        periodic background reindexes swapping in mid-session: p99
        latency (rounds) must stay within 1.25x the frozen session's
        (zero-downtime gate), the stepper must compile exactly once
        across every swap, and recall against the *final* live dataset
        must hold within 0.15 of a cold rebuild over that same data.
    """
    from repro.analysis.compile_guard import CompileGuard
    from repro.core.live import build_live_index, mutation_schedule
    from repro.launch.search import build_index

    n, d, nq, shards = 2048, 32, 48, 4
    page_size, rdeg, slots, K = 8, 8, 2, 4
    k = 8
    ds = VectorDataset("live-bench", n=n, dim=d, clusters=8, seed=seed)
    db0 = ds.materialize()
    queries = ds.queries(nq, seed=seed + 1)
    arrivals = poisson_arrivals(0.25, nq, seed + 7)
    sp = SearchParams(L=16, W=1, k=k)
    skw = dict(num_slots=slots, round_chunk=K, arrivals=arrivals)

    db, packed = build_index(db0, shards=shards, page_size=page_size,
                             r=rdeg, seed=seed)
    consts, geom, entry = pack_for_engine(packed)
    params = EngineParams.lossless(sp, slots, packed.max_degree,
                                   kernel_mode=kernel_mode)
    base_i, base_d, base_st = stream_search(consts, geom, params, entry,
                                            queries, **skw)
    base_row = stream_summary(base_st)

    def live_session(schedule, refresh_every, label):
        live = build_live_index(db0, shards=shards, page_size=page_size,
                                r=rdeg, delta_cap=8, seed=seed,
                                refresh_every=refresh_every,
                                schedule=schedule)
        lc, lg, le = pack_for_engine(live.ep.packed)
        lp = dataclasses.replace(
            EngineParams.lossless(sp, slots, rdeg,
                                  kernel_mode=kernel_mode), delta_cap=8)
        with CompileGuard() as cg:
            ids, dists, st = stream_search(lc, lg, lp, le, queries,
                                           live=live, **skw)
        row = stream_summary(st)
        row.update(label=label,
                   stepper_compiles=cg.count("engine_run_chunk_admit"))
        return row, (np.asarray(ids), np.asarray(dists)), live

    zc_row, zc_out, _ = live_session(None, 0, "zero-churn")

    horizon = max(int(arrivals.max()) + 1, 2 * nq)
    sched = mutation_schedule(0.35, 0.1, horizon, d, seed=seed + 5,
                              ref=db0)
    ch_row, ch_out, ch_live = live_session(sched, 8, "churn")

    # recall vs the final live dataset, against a cold rebuild over
    # exactly that data (the background reorder must not leave the
    # graph meaningfully worse than a from-scratch build)
    vecs, exts = ch_live.final_dataset()
    pos, _ = brute_force_topk(vecs, queries, k)
    ch_row["recall"] = round(float(recall_at_k(ch_out[0], exts[pos])), 4)
    dbr, cpacked = build_index(vecs, shards=shards, page_size=page_size,
                               r=rdeg, seed=seed)
    cc, cgm, ce = pack_for_engine(cpacked)
    cold_params = EngineParams.lossless(sp, slots, rdeg,
                                        kernel_mode=kernel_mode)
    cold_i, _, _ = stream_search(cc, cgm, cold_params, ce, queries, **skw)
    posr, _ = brute_force_topk(dbr, queries, k)
    cold_recall = round(float(recall_at_k(np.asarray(cold_i), posr)), 4)

    p99_ratio = round(
        ch_row["latency_rounds"]["p99"]
        / max(base_row["latency_rounds"]["p99"], 1e-9), 4)
    zero_churn_identity = bool(
        np.array_equal(zc_out[0], np.asarray(base_i))
        and np.array_equal(zc_out[1], np.asarray(base_d))
        # the frozen call runs ahead, so it adds zero-round chunks
        and zc_row["host_dispatches"] - zc_row["empty_dispatches"]
        == base_row["host_dispatches"] - base_row["empty_dispatches"])

    emit([["frozen", 0, 0, 0, 0,
           base_row["latency_rounds"]["p99"],
           base_row["host_dispatches"], "-"],
          ["zero-churn", 0, 0, 0, 0,
           zc_row["latency_rounds"]["p99"], zc_row["host_dispatches"],
           zc_row["stepper_compiles"]],
          ["churn", ch_row["epoch_swaps"], ch_row["delta_hits"],
           ch_row["tombstoned"], ch_row["swap_stall_rounds"],
           ch_row["latency_rounds"]["p99"], ch_row["host_dispatches"],
           ch_row["stepper_compiles"]]],
         ["session", "swaps", "delta_hits", "tombstoned", "swap_stall",
          "p99_rounds", "dispatches", "compiles"],
         f"live index (n0={n}, delta_cap=8, refresh_every=8, paced "
         f"arrivals, {shards}x{slots} slots, chunk={K})")

    checks = {
        "live_zero_churn_identity": zero_churn_identity,
        "live_p99_ratio": p99_ratio,
        "live_epoch_swaps": ch_row["epoch_swaps"],
        "live_stepper_compiles": ch_row["stepper_compiles"],
        "live_recall": ch_row["recall"],
        "live_cold_rebuild_recall": cold_recall,
        "live_recall_delta": round(ch_row["recall"] - cold_recall, 4),
    }
    if smoke:
        assert zero_churn_identity, (
            "a zero-churn live session must be bit-identical to the "
            "frozen path (ids, dists, dispatch count)")
        assert zc_row["stepper_compiles"] == 1
        assert ch_row["epoch_swaps"] >= 2, (
            f"the churn session must exercise >= 2 epoch swaps, got "
            f"{ch_row['epoch_swaps']}")
        assert ch_row["stepper_compiles"] == 1, (
            f"epoch swaps must not recompile the stepper: "
            f"{ch_row['stepper_compiles']} compiles")
        assert p99_ratio <= 1.25, (
            f"p99 latency while background reorders run must stay "
            f"within 1.25x steady state: ratio {p99_ratio}")
        assert ch_row["recall"] >= cold_recall - 0.15, (
            f"post-churn recall {ch_row['recall']} fell more than 0.15 "
            f"below the cold rebuild's {cold_recall}")
    return [base_row, zc_row, ch_row], checks


def compile_guard_leg(*, kernel_mode, seed, smoke):
    """One-warmup-compile gate (analysis layer 3): a fresh serving
    session — multi-chunk, ring-bounded admission, half-resident tiered
    store, every consts view swapped at every boundary — must dispatch
    against exactly one ``engine_run_chunk_admit`` compilation: the
    warmup's.  Workload shapes are unique to this leg (d=40) so the
    process-wide jit cache cannot have pre-warmed the signature and
    cannot mask a recompile either way."""
    from repro.analysis.compile_guard import CompileGuard
    from repro.core.pagestore import PageStore
    from repro.launch.search import build_index

    n, d, nq, shards = 1024, 40, 24, 2
    page_size, slots, K, ring = 8, 3, 2, 6
    ds = VectorDataset("guard-bench", n=n, dim=d, clusters=8, seed=seed)
    queries = ds.queries(nq, seed=seed + 1)
    _, packed = build_index(ds.materialize(), shards=shards,
                            page_size=page_size, r=8, pref_width=2,
                            seed=seed)
    consts, geom, entry = pack_for_engine(packed)
    sp = SearchParams(L=12, W=1, k=8)
    params = EngineParams.lossless(sp, slots, packed.max_degree,
                                   spec_width=2, kernel_mode=kernel_mode)
    NP = consts["db"].shape[1]
    params = dataclasses.replace(params, store_pages=NP)
    ps = PageStore(consts, geom, max(1, NP // 2), w_select=sp.W)
    arrivals = poisson_arrivals(0.5, nq, seed + 3)
    with CompileGuard() as cg:
        _, _, st = stream_search(consts, geom, params, entry, queries,
                                 num_slots=slots, round_chunk=K,
                                 arrivals=arrivals, injit_admit=True,
                                 ring_capacity=ring, pagestore=ps)
    n_compiles = cg.count("engine_run_chunk_admit")
    row = {"stepper_compiles": n_compiles,
           "host_dispatches": st.host_dispatches,
           "compile_s": round(st.compile_s, 3),
           "resident_fraction": round(ps.resident_fraction, 4)}
    emit([[n_compiles, st.host_dispatches, row["compile_s"],
           row["resident_fraction"], ring]],
         ["stepper_compiles", "dispatches", "compile_s", "resident",
          "ring"],
         "compile guard (one warmup compile covers every dispatch)")
    if smoke:
        assert st.host_dispatches > 1, (
            "guard leg degenerated to a single dispatch; the claim "
            "needs a multi-chunk session")
        assert n_compiles == 1, (
            "one warmup compile must cover every dispatch of the "
            f"session: saw {n_compiles} engine_run_chunk_admit "
            f"compilations over {st.host_dispatches} dispatches: "
            f"{[x for x in cg.names if 'chunk' in x]}")
    return row


def chaos_leg(*, n, d, nq, page_size, r, L, k, kernel_mode, seed,
              smoke):
    """Overload + fault chaos sweep on an 8-shard workload (the
    robustness PR's evidence):

      * **overload** — offered load at multiple factors of the measured
        clean capacity, against a bounded admission ring under both
        policies: ``shed`` trades completeness for bounded latency
        (goodput-vs-offered-load curve), ``block`` serves everything
        with backpressure.
      * **shard kill** — 1 of 8 shards dies mid-run under a deadline:
        every query must retire, untouched queries bit-exact, and the
        recall drop is bounded by the truncated-query fraction (each
        force-retired query loses at most its own 1/nq of recall).
      * **corruption** — NaN page reads at a deterministic rate with
        the guard on: quarantined > 0, zero NaN in any output.
      * **armed-but-idle identity** — deadline no query reaches + guard
        + full-stream ring must be bit-identical to the plain refill
        path (the zero-cost-when-off contract, gated end to end).

    With ``smoke`` the invariants are hard asserts (the CI chaos gate);
    the rows land in BENCH_serving.json either way."""
    from repro.ft.inject import fault_plan

    shards, slots = 8, 4
    db, packed, queries = build_workload(
        n=n, d=d, nq=nq, shards=shards, page_size=page_size, r=r,
        spec_max=0, seed=seed + 11)
    consts, geom, entry = pack_for_engine(packed)
    sp = SearchParams(L=L, W=1, k=k)
    true_ids, _ = brute_force_topk(db, queries, k)

    def params_of(**kw):
        p = EngineParams.lossless(sp, slots, packed.max_degree,
                                  kernel_mode=kernel_mode)
        return dataclasses.replace(p, **kw) if kw else p

    def rec(ids):
        return round(float(recall_at_k(np.asarray(ids)[:, :k],
                                       true_ids)), 4)

    base = params_of()
    arr0 = np.zeros(nq, np.int64)
    skw = dict(num_slots=slots, round_chunk=8)
    ref_i, ref_d, ref_st = stream_search(consts, geom, base, entry,
                                         queries, arrivals=arr0, **skw)
    clean_recall = rec(ref_i)
    cap_qpr = stream_summary(ref_st)["queries_per_round"]

    # -- armed-but-idle identity: every robustness feature on, none
    # firing — must be the plain refill path bit for bit
    armed = params_of(deadline_rounds=10**6, guard_nonfinite=True)
    ai, ad, ast = stream_search(consts, geom, armed, entry, queries,
                                arrivals=arr0, ring_capacity=nq,
                                overload="block", **skw)
    identity = {
        "ids_equal": bool(np.array_equal(np.asarray(ai),
                                         np.asarray(ref_i))),
        "dists_equal": bool(np.array_equal(np.asarray(ad),
                                           np.asarray(ref_d))),
        "rounds_equal": ast.total_rounds == ref_st.total_rounds,
        "dispatches_equal": ast.host_dispatches == ref_st.host_dispatches,
        "dispatches_per_query": round(ast.host_dispatches / nq, 3),
    }

    # -- overload: goodput vs offered load, shed and block.  The ring
    # holds twice the slot pool: deep enough to never shed at <= 1x
    # capacity, shallow enough that sustained overload overflows it
    # well before the stream ends
    factors = (1.0, 3.0) if smoke else (0.5, 1.0, 2.0, 4.0)
    ring = 2 * slots
    overload_rows = {"shed": [], "block": []}
    for policy in ("shed", "block"):
        for factor in factors:
            arr = poisson_arrivals(cap_qpr * factor, nq, seed + 13)
            ids_o, _, st_o = stream_search(
                consts, geom, base, entry, queries, arrivals=arr,
                ring_capacity=ring, overload=policy, **skw)
            row = stream_summary(st_o)
            overload_rows[policy].append({
                "offered_factor": factor,
                "offered_rate": round(cap_qpr * factor, 3),
                "retired": row["queries"], "shed": row["shed"],
                "goodput": row["goodput"],
                "p99_latency_rounds": row["latency_rounds"]["p99"],
            })

    # -- shard kill mid-run, deadline above natural convergence
    max_srv = max(q.service_rounds for q in ref_st.results)
    kill_round = max(2, min(q.service_rounds for q in ref_st.results))
    dl = max_srv + 4
    kp = params_of(deadline_rounds=dl,
                   faults=fault_plan(shards).kill(3, kill_round))
    ki, kd, kst = stream_search(consts, geom, kp, entry, queries,
                                arrivals=arr0, **skw)
    kill_row = {
        "killed_shard": 3, "kill_round": kill_round,
        "deadline_rounds": dl, "retired": len(kst.results),
        "truncated": kst.truncated, "recall": rec(ki),
        "clean_recall": clean_recall,
        "recall_floor": round(clean_recall - kst.truncated / nq, 4),
        "nan_in_output": bool(np.isnan(np.asarray(kd)).any()),
    }

    # -- corruption + guard: quarantine instead of poisoning the merge
    cp = params_of(guard_nonfinite=True,
                   faults=fault_plan(shards).corrupt(0.02, "nan",
                                                     seed=seed + 17))
    ci, cd, cst = stream_search(consts, geom, cp, entry, queries,
                                arrivals=arr0, **skw)
    corrupt_row = {
        "corrupt_rate": 0.02, "mode": "nan",
        "quarantined": cst.quarantined, "retired": len(cst.results),
        "recall": rec(ci), "clean_recall": clean_recall,
        "nan_in_output": bool(np.isnan(np.asarray(cd)).any()),
    }

    emit([[p, row["offered_factor"], row["offered_rate"],
           row["retired"], row["shed"], row["goodput"],
           row["p99_latency_rounds"]]
          for p in ("shed", "block") for row in overload_rows[p]],
         ["policy", "factor", "rate", "retired", "shed", "goodput",
          "p99_rounds"],
         f"overload sweep (ring={ring}, capacity={cap_qpr} q/round)")
    emit([[kill_row["killed_shard"], kill_row["kill_round"],
           kill_row["truncated"], kill_row["recall"],
           kill_row["recall_floor"], corrupt_row["quarantined"],
           corrupt_row["recall"]]],
         ["killed", "at_round", "truncated", "kill_recall",
          "recall_floor", "quarantined", "corrupt_recall"],
         f"fault injection (1 of {shards} shards killed mid-run; 2% "
         f"NaN page reads + guard)")

    if smoke:
        for key, ok in identity.items():
            if key.endswith("_equal"):
                assert ok, (
                    f"armed-but-idle robustness must be bit-identical "
                    f"to the plain path: {key} failed")
        hi = overload_rows["shed"][-1]
        assert hi["shed"] > 0, (
            f"shed policy must reject under {hi['offered_factor']}x "
            f"overload with a {ring}-deep ring")
        for row in overload_rows["shed"]:
            assert row["goodput"] > 0, (
                f"goodput collapsed to 0 at {row['offered_factor']}x "
                f"offered load — shedding must protect admitted queries")
            assert row["retired"] + row["shed"] == nq
        for row in overload_rows["block"]:
            assert row["shed"] == 0 and row["retired"] == nq, (
                f"block policy must serve the whole stream: {row}")
        assert kill_row["retired"] == nq, (
            "shard kill: every query must retire (deadline force-"
            "retire), none may hang")
        assert kill_row["truncated"] > 0
        assert not kill_row["nan_in_output"]
        assert kill_row["recall"] >= kill_row["recall_floor"] - 1e-6, (
            f"kill recall {kill_row['recall']} fell below the "
            f"truncated-fraction floor {kill_row['recall_floor']}")
        assert corrupt_row["quarantined"] > 0, (
            "corruption ran but the guard quarantined nothing")
        assert corrupt_row["retired"] == nq
        assert not corrupt_row["nan_in_output"], (
            "NaN page reads leaked into the output top-k")

    return {"capacity_queries_per_round": cap_qpr,
            "identity_when_off": identity,
            "overload": overload_rows,
            "shard_kill": kill_row,
            "corruption": corrupt_row}


def run(*, nq=128, n=4096, d=48, shards=4, slots=8, page_size=64, r=16,
        spec_max=8, L=32, rate=2.0, kernel_mode="jnp", seed=0,
        round_chunk=1, smoke=False, chaos=False, live=False,
        out_json="BENCH_serving.json"):
    if smoke:
        nq, n, slots, rate = 64, 2048, 4, 0.0
    db, packed, queries = build_workload(
        n=n, d=d, nq=nq, shards=shards, page_size=page_size, r=r,
        spec_max=spec_max, seed=seed)
    consts, geom, entry = pack_for_engine(packed)
    sp = SearchParams(L=L, W=1, k=10)
    true_ids, _ = brute_force_topk(db, queries, 10)

    arrivals = poisson_arrivals(rate, nq, seed + 2)

    def params_for(spec):
        return EngineParams.lossless(sp, slots, packed.max_degree,
                                     spec_width=spec,
                                     kernel_mode=kernel_mode)

    p_max = params_for(spec_max)
    kw = dict(slots=slots, arrivals=arrivals, true_ids=true_ids, k=10)
    scenarios = {}
    t0 = time.time()
    scenarios["frozen"], _ = _scenario(
        consts, geom, p_max, entry, queries, dynamic_spec=False,
        refill=False, round_chunk=round_chunk, **kw)
    scenarios["refill"], _ = _scenario(
        consts, geom, p_max, entry, queries, dynamic_spec=False,
        refill=True, round_chunk=round_chunk, **kw)
    scenarios["dynamic"], _ = _scenario(
        consts, geom, p_max, entry, queries, dynamic_spec=True,
        refill=True, round_chunk=round_chunk, **kw)

    # static spec sweep (refill on): the controller's best-static bar
    sweep = []
    for spec in sorted({0, spec_max // 2, spec_max}):
        row, _ = _scenario(consts, geom, params_for(spec), entry, queries,
                           dynamic_spec=False, refill=True,
                           round_chunk=round_chunk, **kw)
        row["spec"] = spec
        sweep.append(row)

    # round_chunk sweep: rounds per host dispatch vs dispatches/query
    # and wall QPS. refill (continuous admission) runs with in-jit
    # admission — the device-side pending queue keeps the chunk running
    # through retirements and arrivals — and against the host-admission
    # baseline (injit off: budget capped at the next arrival +
    # stop-on-finish, so chunk length collapses while the queue drains,
    # the PR-4 model). frozen (synchronous waves, the paper's
    # computational-storage baseline) keeps the host-side all-free
    # gate — chunks break on wave boundaries, so dispatches drop ~K x.
    def chunk_leg(ks, refill, mesh=None, injit=None):
        rows = []
        for K in ks:
            row, out = _scenario(consts, geom, p_max, entry, queries,
                                 dynamic_spec=False, refill=refill,
                                 round_chunk=K, mesh=mesh,
                                 injit_admit=injit, **kw)
            rows.append(({"round_chunk": K, **row}, out))
        return rows

    def rows_only(leg):
        return [row for row, _ in leg]

    chunk_ks = (1, 8) if smoke else (1, 2, 4, 8, 16)
    leg_refill = chunk_leg(chunk_ks, refill=True)
    leg_hostadm = chunk_leg(chunk_ks, refill=True, injit=False)
    leg_frozen = chunk_leg((1, chunk_ks[-1]), refill=False)
    chunk_refill = rows_only(leg_refill)
    chunk_hostadm = rows_only(leg_hostadm)
    chunk_frozen = rows_only(leg_frozen)
    import jax
    leg_shard, leg_shard_hostadm = [], []
    if jax.device_count() >= shards:
        from repro.launch.mesh import make_engine_mesh
        mesh = make_engine_mesh(num=shards)
        leg_shard = chunk_leg((1, chunk_ks[-1]), refill=True, mesh=mesh)
        leg_shard_hostadm = chunk_leg((chunk_ks[-1],), refill=True,
                                      mesh=mesh, injit=False)
    else:  # no silent gaps: record why the leg is absent
        print(f"[shard_map chunk leg skipped: {jax.device_count()} "
              f"device(s) < {shards} shards]")
    chunk_shard = rows_only(leg_shard)
    chunk_shard_hostadm = rows_only(leg_shard_hostadm)

    # routed-vs-fanout sweep: two-tier routing at 8 shards on its own
    # clustered workload.  The dataset size is pinned (not the headline
    # n): R=2's leg_L=k operating point is tuned to the per-shard graph
    # depth, and scaling n without retuning leg_L moves the
    # pages-vs-recall crossover — the sweep demonstrates the routing
    # win at its gated configuration, not a scaling law.
    routed_shards, routed_n = 8, 2048
    routed_rows, routed_fanout_out, routed_out = {}, None, {}
    if routed_n % (routed_shards * page_size) == 0:
        routed_rows, routed_fanout_out, routed_out = routed_leg(
            n=routed_n, d=d, nq=nq, shards=routed_shards,
            page_size=page_size, r=max(r, routed_shards), L=L, k=10,
            slots=4, kernel_mode=kernel_mode, seed=seed)
    else:
        print(f"[routed leg skipped: n={routed_n} not on the "
              f"{routed_shards}x{page_size} grid]")

    # tiered page store: throughput vs resident fraction, prefetch vs
    # demand-only, with the fraction-1.0 bit-identity gate
    tiered_rows, tiered_checks = tiered_leg(
        kernel_mode=kernel_mode, seed=seed, smoke=smoke)

    # compile guard: machine-check that one warmup compile covers every
    # dispatch of a ring + tiered serving session (analysis layer 3)
    guard_row = compile_guard_leg(kernel_mode=kernel_mode, seed=seed,
                                  smoke=smoke)

    # live index: zero-churn identity, the p99-under-reorder gate, and
    # compile-once across epoch swaps (opt-in like chaos — it builds
    # three extra indexes)
    live_rows, live_checks = [], {}
    if live:
        live_rows, live_checks = live_leg(
            kernel_mode=kernel_mode, seed=seed, smoke=smoke)

    # chaos sweep: overload shedding/backpressure against the bounded
    # admission ring, a mid-run shard kill under a deadline, corrupted
    # page reads behind the guard, and the armed-but-idle identity gate
    chaos_rows = {}
    if chaos:
        chaos_rows = chaos_leg(
            n=min(n, 2048), d=d, nq=nq, page_size=page_size, r=r, L=L,
            k=10, kernel_mode=kernel_mode, seed=seed, smoke=smoke)

    emit([[name, s["occupancy"], s["queries_per_round"],
           s["sustained_qps"], s["latency_rounds"]["p50"],
           s["latency_rounds"]["p99"], s["pages_unique"], s["recall"]]
          for name, s in scenarios.items()],
         ["discipline", "occupancy", "q/round", "qps", "p50_rounds",
          "p99_rounds", "pages", "recall"],
         f"streaming disciplines (nq={nq} slots={shards}x{slots} "
         f"rate={rate} spec_max={spec_max} round_chunk={round_chunk})")
    emit([[row["spec"], row["pages_unique"], row["recall"],
           row["queries_per_round"]] for row in sweep],
         ["spec_width", "pages", "recall", "q/round"],
         "static speculation sweep (refill on)")
    for label, leg in (("refill, in-jit admission", chunk_refill),
                       ("refill, host admission", chunk_hostadm),
                       ("frozen", chunk_frozen),
                       ("shard_map refill, in-jit", chunk_shard),
                       ("shard_map refill, host adm", chunk_shard_hostadm)):
        if leg:
            emit([[row["round_chunk"], row["host_dispatches"],
                   row["dispatches_per_query"], row["rounds_per_dispatch"],
                   row["queries_per_round"], row["sustained_qps"]]
                  for row in leg],
                 ["chunk", "dispatches", "disp/query", "rounds/disp",
                  "q/round", "qps"],
                 f"round-chunk sweep ({label} stepper leg)")

    if routed_rows:
        emit([[name, row.get("topr", routed_shards), row.get("leg_L") or L,
               row["pages_per_query"], row["queries_per_round"],
               row["sustained_qps"], row["recall"]]
              for name, row in routed_rows.items()],
             ["leg", "R", "leg_L", "pages/query", "q/round", "qps",
              "recall"],
             f"routed vs fan-out (clustered workload, "
             f"{routed_shards} shards, n={routed_n})")

    checks = {
        "chunk_dispatch_reduction_refill": round(
            chunk_refill[0]["host_dispatches"]
            / max(chunk_refill[-1]["host_dispatches"], 1), 3),
        "chunk_dispatch_reduction_frozen": round(
            chunk_frozen[0]["host_dispatches"]
            / max(chunk_frozen[-1]["host_dispatches"], 1), 3),
        "injit_dispatch_reduction_refill": round(
            chunk_hostadm[-1]["host_dispatches"]
            / max(chunk_refill[-1]["host_dispatches"], 1), 3),
        "chunk_qpr_ratio": round(
            chunk_refill[-1]["queries_per_round"]
            / max(chunk_refill[0]["queries_per_round"], 1e-9), 4),
        "occupancy_gain": round(scenarios["refill"]["occupancy"]
                                / max(scenarios["frozen"]["occupancy"],
                                      1e-9), 3),
        "throughput_gain": round(
            scenarios["refill"]["queries_per_round"]
            / max(scenarios["frozen"]["queries_per_round"], 1e-9), 3),
        "dynamic_vs_static_pages": round(
            scenarios["dynamic"]["pages_unique"]
            / max(scenarios["refill"]["pages_unique"], 1), 4),
        "dynamic_vs_best_static_pages": round(
            scenarios["dynamic"]["pages_unique"]
            / max(min(r["pages_unique"] for r in sweep), 1), 4),
        "dynamic_recall_delta": round(
            scenarios["dynamic"]["recall"]
            - scenarios["refill"]["recall"], 4),
    }
    if chunk_shard:
        checks["injit_dispatch_reduction_shard"] = round(
            chunk_shard_hostadm[-1]["host_dispatches"]
            / max(chunk_shard[-1]["host_dispatches"], 1), 3)
    if routed_rows:
        fo, r2 = routed_rows["fanout"], routed_rows["R=2"]
        checks["routed_r2_pages_ratio"] = round(
            r2["pages_per_query"] / max(fo["pages_per_query"], 1e-9), 4)
        checks["routed_r2_qpr_ratio"] = round(
            r2["queries_per_round"]
            / max(fo["queries_per_round"], 1e-9), 4)
        checks["routed_r2_recall_delta"] = round(
            r2["recall"] - fo["recall"], 4)
    checks.update(tiered_checks)
    checks.update(live_checks)
    checks["compile_guard_stepper_compiles"] = guard_row[
        "stepper_compiles"]
    results = {
        "config": {"nq": nq, "n": n, "d": d, "shards": shards,
                   "slots": slots, "rate": rate, "spec_max": spec_max,
                   "L": L, "kernel_mode": kernel_mode,
                   "round_chunk": round_chunk, "smoke": smoke,
                   "wall_s": round(time.time() - t0, 1),
                   "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S")},
        "scenarios": scenarios,
        "static_spec_sweep": sweep,
        "round_chunk_sweep": {"refill": chunk_refill,
                              "refill_host_admission": chunk_hostadm,
                              "frozen": chunk_frozen,
                              "shard_map": chunk_shard,
                              "shard_map_host_admission":
                                  chunk_shard_hostadm},
        "routed_sweep": routed_rows,
        "tiered_sweep": tiered_rows,
        "compile_guard": guard_row,
        "live_sweep": live_rows,
        "chaos": chaos_rows,
        "checks": checks,
    }
    if out_json:
        # written before the smoke asserts so a regression still leaves
        # the per-discipline numbers behind for diagnosis
        with open(out_json, "w") as f:
            json.dump(results, f, indent=1)
        print(f"[wrote {out_json}]")

    if smoke:
        fr, re_, dy = (scenarios[s] for s in ("frozen", "refill",
                                              "dynamic"))
        assert re_["occupancy"] > fr["occupancy"], (
            f"refill must beat frozen-batch occupancy: "
            f"{re_['occupancy']} vs {fr['occupancy']}")
        assert re_["queries_per_round"] > fr["queries_per_round"], (
            f"refill must beat frozen-batch round-throughput: "
            f"{re_['queries_per_round']} vs {fr['queries_per_round']}")
        assert dy["pages_unique"] <= re_["pages_unique"], (
            f"controller-on must not read more pages than controller-off "
            f"at the same spec_max: {dy['pages_unique']} vs "
            f"{re_['pages_unique']}")
        assert dy["recall"] >= re_["recall"] - 0.02, (
            f"controller must hold recall within 2pt of controller-off: "
            f"{dy['recall']} vs {re_['recall']}")
        # dispatch gate: device-paced chunks must match the per-round
        # schedule's round-throughput while syncing the host strictly
        # less (the whole point of engine_run_chunk)
        for leg in (chunk_refill, chunk_hostadm, chunk_frozen,
                    chunk_shard):
            if not leg:
                continue
            pr, ch = leg[0], leg[-1]
            assert ch["queries_per_round"] >= pr["queries_per_round"], (
                f"chunked (K={ch['round_chunk']}) must not lose "
                f"round-throughput vs per-round: "
                f"{ch['queries_per_round']} vs {pr['queries_per_round']}")
            assert ch["host_dispatches"] < pr["host_dispatches"], (
                f"chunked (K={ch['round_chunk']}) must sync the host "
                f"strictly less than per-round: "
                f"{ch['host_dispatches']} vs {pr['host_dispatches']}")
            assert ch["total_rounds"] == pr["total_rounds"], (
                f"chunking must not change the engine-round schedule: "
                f"{ch['total_rounds']} vs {pr['total_rounds']}")
        # in-jit-admission gate: the device-side pending queue must
        # reproduce host admission exactly — same round schedule, bit-
        # identical per-query results — while syncing the host strictly
        # less (it deletes the stop-on-finish exits that collapse chunk
        # length while the queue drains)
        injit_legs = [("refill", leg_refill[-1], leg_hostadm[-1])]
        if leg_shard:
            injit_legs.append(("shard_map", leg_shard[-1],
                               leg_shard_hostadm[-1]))
        # routing gate: at 8 shards, R=2 must read strictly fewer
        # pages/query and sustain more queries/round than all-shard
        # fan-out without giving up recall@k, and R=S must stay
        # bit-identical to the fan-out leg (same per-query trajectory,
        # only the admission strategy differs)
        if routed_rows:
            fo, r2 = routed_rows["fanout"], routed_rows["R=2"]
            assert r2["pages_per_query"] < fo["pages_per_query"], (
                f"routed R=2 must read strictly fewer pages/query than "
                f"fan-out: {r2['pages_per_query']} vs "
                f"{fo['pages_per_query']}")
            assert r2["queries_per_round"] > fo["queries_per_round"], (
                f"routed R=2 must sustain more queries/round than "
                f"fan-out: {r2['queries_per_round']} vs "
                f"{fo['queries_per_round']}")
            assert r2["recall"] >= fo["recall"] - 0.02, (
                f"routed R=2 must hold fan-out recall: {r2['recall']} "
                f"vs {fo['recall']}")
            rs_ids, rs_dists = routed_out[f"R={routed_shards}"]
            np.testing.assert_array_equal(
                rs_ids, routed_fanout_out[0],
                err_msg="R=S routed changed result ids vs fan-out")
            np.testing.assert_array_equal(
                rs_dists, routed_fanout_out[1],
                err_msg="R=S routed changed distances vs fan-out")
        for label, (row_on, out_on), (row_off, out_off) in injit_legs:
            np.testing.assert_array_equal(
                out_on[0], out_off[0],
                err_msg=f"{label}: in-jit admission changed result ids")
            np.testing.assert_array_equal(
                out_on[1], out_off[1],
                err_msg=f"{label}: in-jit admission changed distances")
            assert row_on["total_rounds"] == row_off["total_rounds"], (
                f"{label}: in-jit admission changed the round schedule: "
                f"{row_on['total_rounds']} vs {row_off['total_rounds']}")
            assert (row_on["queries_per_round"]
                    == row_off["queries_per_round"]), (
                f"{label}: in-jit admission changed round-throughput: "
                f"{row_on['queries_per_round']} vs "
                f"{row_off['queries_per_round']}")
            assert (row_on["host_dispatches"]
                    < row_off["host_dispatches"]), (
                f"{label}: in-jit admission must sync the host strictly "
                f"less than host admission at the same K: "
                f"{row_on['host_dispatches']} vs "
                f"{row_off['host_dispatches']}")
    return results


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny run + hard asserts on the streaming "
                         "invariants (the CI regression gate)")
    ap.add_argument("--queries", type=int, default=128)
    ap.add_argument("--n", type=int, default=4096)
    ap.add_argument("--shards", type=int, default=4)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--rate", type=float, default=2.0)
    ap.add_argument("--spec-max", type=int, default=8)
    ap.add_argument("--kernel-mode", default="jnp",
                    choices=["auto", "pallas", "interpret", "ref", "jnp"])
    ap.add_argument("--round-chunk", type=int, default=1,
                    help="rounds per device dispatch for the headline "
                         "discipline scenarios (the chunk sweep always "
                         "runs; 1 keeps the host-paced baseline)")
    ap.add_argument("--chaos", action="store_true",
                    help="add the robustness sweep: goodput vs offered "
                         "load under shed/block overload policies, a "
                         "mid-run shard kill under a deadline, NaN page "
                         "reads behind the guard, and the armed-but-"
                         "idle bit-identity gate")
    ap.add_argument("--live", action="store_true",
                    help="add the live-index sweep: zero-churn "
                         "bit-identity, p99 latency while background "
                         "reorders run (must stay within 1.25x steady "
                         "state under --smoke), compile-once across "
                         "epoch swaps, and post-churn recall vs a cold "
                         "rebuild")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="BENCH_serving.json")
    args = ap.parse_args(argv)
    run(nq=args.queries, n=args.n, shards=args.shards, slots=args.slots,
        rate=args.rate, spec_max=args.spec_max,
        kernel_mode=args.kernel_mode, round_chunk=args.round_chunk,
        seed=args.seed, smoke=args.smoke, chaos=args.chaos,
        live=args.live, out_json=args.out)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
