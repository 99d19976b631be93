"""ANNS driver — the paper's workload end-to-end.

Builds a Vamana (DiskANN-style) or HNSW-lite index over a synthetic
dataset, applies the two-level scheduling (static: degree-ascending BFS
reorder + plane-aware mapping; dynamic: batch-wise allocating +
speculation), runs the distributed NDSearch engine and reports
recall@k / QPS / locality stats.

  PYTHONPATH=src python -m repro.launch.search --dataset sift-1b \
      --queries 256 --shards 8 --spec 4
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

import numpy as np

from repro.core.engine import EngineParams, pack_for_engine, search_sim
from repro.core.graph import build_vamana, brute_force_topk, recall_at_k
from repro.core.luncsr import Geometry, LUNCSR, pack_index
from repro.core.ref_search import SearchParams
from repro.core.reorder import apply_reordering, degree_ascending_bfs
from repro.data.vectors import PAPER_DATASETS, VectorDataset
from repro.launch.compile_cache import enable_compile_cache


def build_index(db: np.ndarray, *, shards: int, page_size: int, r: int,
                reorder: str = "ours", pref_width: int = 0, seed: int = 0):
    adj, medoid = build_vamana(db, r=r, seed=seed)
    if reorder == "ours":
        order = degree_ascending_bfs(adj)
        db, adj, medoid = apply_reordering(db, adj, order, entry=medoid)
    geom = Geometry(num_shards=shards, page_size=page_size,
                    pages_per_block=4, dim=db.shape[1], stripe="striped")
    idx = LUNCSR.from_adjacency(db, adj, geom, entry=medoid,
                                pref_width=pref_width)
    return db, pack_index(idx, max_degree=r)


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="sift-1b",
                    choices=sorted(PAPER_DATASETS) + ["tiny"])
    ap.add_argument("--n", type=int, default=0, help="override dataset size")
    ap.add_argument("--queries", type=int, default=256)
    ap.add_argument("--shards", type=int, default=8)
    ap.add_argument("--page-size", type=int, default=64)
    ap.add_argument("--degree", type=int, default=16)
    ap.add_argument("--L", type=int, default=32)
    ap.add_argument("--W", type=int, default=1)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--spec", type=int, default=0,
                    help="speculative 2nd-order prefetch width")
    ap.add_argument("--reorder", default="ours", choices=["ours", "none"])
    ap.add_argument("--kernel-mode", default="jnp",
                    choices=["auto", "pallas", "interpret", "ref", "jnp"],
                    help="hot-path backend: inline jnp vs the SiN/bitonic "
                         "kernels (auto = pallas on TPU, ref elsewhere)")
    ap.add_argument("--coalesce-qb", type=int, default=8,
                    help="per-page query-tile width in kernel modes: one "
                         "page read serves up to this many assignments "
                         "(0 = one page read per assignment)")
    ap.add_argument("--stream", action="store_true",
                    help="streaming scheduler: fixed slot pool, finished "
                         "queries retire + freed slots refill every round "
                         "(continuous batching) instead of one frozen "
                         "batch per call")
    ap.add_argument("--slots", type=int, default=8,
                    help="streaming: query slots per shard")
    ap.add_argument("--arrival-rate", type=float, default=0.0,
                    help="streaming: mean Poisson arrivals per engine "
                         "round (0 = all queries arrive at round 0)")
    ap.add_argument("--spec-dynamic", action="store_true",
                    help="streaming: adapt each query's speculation "
                         "width to its observed hit rate (paper §V-B) "
                         "instead of the static --spec width")
    ap.add_argument("--spec-page-w", type=float, default=0.0,
                    help="streaming: page-efficiency weight for the "
                         "dynamic controller (0 = hit-rate only)")
    ap.add_argument("--topr", type=int, default=0,
                    help="streaming: two-tier routing — coarse-route "
                         "each query to its top-R shards, one leg per "
                         "shard, fused top-k at retire (0 = all-shard "
                         "fan-out; replaces the striped index with a "
                         "spatially partitioned one)")
    ap.add_argument("--leg-L", type=int, default=0,
                    help="streaming routed: per-leg candidate-list "
                         "length (0 = auto from per-shard graph "
                         "depth: k + 2*log_deg(n/S))")
    ap.add_argument("--device-pages", type=int, default=0,
                    help="streaming: tiered page store — device-"
                         "resident vector pages per shard, rest cold "
                         "in host RAM (0 = untiered)")
    ap.add_argument("--prefetch", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="streaming tiered: speculative prefetch at "
                         "chunk boundaries (--no-prefetch = "
                         "demand-only)")
    ap.add_argument("--prefetch-page-w", type=float, default=1.0,
                    help="streaming tiered: stored-prefetch-list "
                         "weight in the prediction score")
    ap.add_argument("--round-chunk", type=int, default=8,
                    help="streaming: engine rounds per device dispatch "
                         "(engine_run_chunk); the host syncs only at "
                         "chunk boundaries. Any value yields the exact "
                         "per-round schedule (1 = host-paced rounds)")
    ap.add_argument("--injit-admit", default="auto",
                    choices=["auto", "on", "off"],
                    help="streaming: seat arrived queries from a "
                         "device-side pending queue inside the round "
                         "chunk (auto = on whenever refill admission "
                         "is active; off = PR-4-style host-paced "
                         "admission with stop-on-finish chunks)")
    ap.add_argument("--insert-rate", type=float, default=0.0,
                    help="streaming live index: mean Poisson vector "
                         "inserts per engine round (needs --delta-cap)")
    ap.add_argument("--delete-rate", type=float, default=0.0,
                    help="streaming live index: mean Poisson tombstone "
                         "deletes per engine round (needs --delta-cap)")
    ap.add_argument("--delta-cap", type=int, default=0,
                    help="streaming live index: delta-segment rows; a "
                         "full delta forces a background reindex "
                         "(0 = frozen index)")
    ap.add_argument("--refresh-every", type=int, default=0,
                    help="streaming live index: reindex + epoch swap "
                         "after this many mutations (0 = only when "
                         "the delta fills)")
    ap.add_argument("--deadline-rounds", type=int, default=0,
                    help="streaming: force-retire a query after this "
                         "many serving rounds in a slot (truncated "
                         "best-so-far results; 0 = no deadline)")
    ap.add_argument("--ring", type=int, default=0,
                    help="streaming: bounded device admission ring "
                         "(0 = stage the whole stream)")
    ap.add_argument("--overload", default="block",
                    choices=["block", "shed"],
                    help="streaming: full-ring policy — backpressure "
                         "or reject-and-count")
    ap.add_argument("--kill-shard", action="append", default=[],
                    metavar="S:R",
                    help="streaming fault injection: shard S dies at "
                         "round R (repeatable; needs --deadline-rounds)")
    ap.add_argument("--delay-shard", action="append", default=[],
                    metavar="S:R:D",
                    help="streaming fault injection: shard S stalls D "
                         "rounds from round R (repeatable)")
    ap.add_argument("--corrupt-pages", type=float, default=0.0,
                    help="streaming fault injection: corrupt this "
                         "fraction of page reads")
    ap.add_argument("--corrupt-mode", default="nan",
                    choices=["nan", "neg"])
    ap.add_argument("--nan-guard", action="store_true",
                    help="streaming: quarantine non-finite/garbage "
                         "distances to BIG_DIST before the merge")
    ap.add_argument("--down-shards", default="",
                    help="streaming routed: comma-separated shard ids "
                         "known down — degraded fusion over the rest")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="")
    return ap.parse_args(argv)


@dataclasses.dataclass
class BuiltIndex:
    """What :func:`build` makes from the CLI arguments: the dataset's
    name, the queries, the (reordered) vectors, the packed index, and —
    for routed or live serving — the routed or live index."""

    name: str
    queries: np.ndarray
    db: np.ndarray
    packed: object
    routed: object
    live: object
    build_s: float


def build(args: argparse.Namespace) -> BuiltIndex:
    """Materialize the dataset and build the index the arguments ask for
    (host-side graph build; its seconds are ``build_s``)."""
    if args.dataset == "tiny":
        ds = VectorDataset("tiny", n=args.n or 2048, dim=64, clusters=16)
    else:
        ds = PAPER_DATASETS[args.dataset]
        if args.n:
            ds = dataclasses.replace(ds, n=args.n)
    db0 = ds.materialize()
    queries = ds.queries(args.queries, seed=args.seed + 1)
    print(f"dataset {ds.name}: n={db0.shape[0]} d={db0.shape[1]}")

    t0 = time.time()
    routed = None
    live = None
    if args.delta_cap > 0:
        if not args.stream:
            raise SystemExit("--delta-cap requires --stream (the live "
                             "index is a serving-path feature)")
        if args.topr > 0 and args.topr < args.shards:
            raise SystemExit("live index needs --topr >= --shards "
                             "(shard-local legs cannot mask the delta)")
        from repro.launch.serve_stream import build_live_session
        live = build_live_session(
            db0, shards=args.shards, page_size=args.page_size,
            r=args.degree, insert_rate=args.insert_rate,
            delete_rate=args.delete_rate, delta_cap=args.delta_cap,
            refresh_every=args.refresh_every,
            arrival_rate=args.arrival_rate, nq=args.queries,
            arrivals_seed=args.seed + 2, pref_width=args.spec,
            seed=args.seed, with_router=args.topr > 0,
            kernel_mode=args.kernel_mode)
        db, packed = db0, live.ep.packed
        print(f"live index built in {time.time() - t0:.1f}s "
              f"(capacity={live.capacity}, delta_cap={args.delta_cap}, "
              f"scheduled mutations={len(live.schedule)})")
    elif args.topr > 0:
        if not args.stream:
            raise SystemExit("--topr requires --stream (routing is a "
                             "serving-path feature)")
        from repro.core.router import build_routed_index
        grid = args.shards * args.page_size
        routed = build_routed_index(
            db0[:db0.shape[0] // grid * grid], shards=args.shards,
            page_size=args.page_size, r=max(args.degree, args.shards),
            pref_width=args.spec, seed=args.seed,
            kernel_mode=args.kernel_mode)
        db, packed = routed.db, routed.packed
        print(f"routed index built in {time.time() - t0:.1f}s "
              f"(shards={args.shards}, spec={args.spec})")
    else:
        db, packed = build_index(
            db0, shards=args.shards, page_size=args.page_size,
            r=args.degree, reorder=args.reorder, pref_width=args.spec,
            seed=args.seed)
        print(f"index built in {time.time() - t0:.1f}s "
              f"(reorder={args.reorder}, spec={args.spec})")
    return BuiltIndex(ds.name, queries, db, packed, routed, live,
                      time.time() - t0)


def serve(args: argparse.Namespace, built: BuiltIndex):
    """Run the search the arguments describe over a built index.

    Returns ``(report, ids)``: the JSON-able report :func:`main` prints
    and the (queries, k) result ids."""
    queries, db, packed = built.queries, built.db, built.packed
    routed, live = built.routed, built.live
    consts, geom, entry = pack_for_engine(packed)
    sp = SearchParams(L=args.L, W=args.W, k=args.k)
    S = args.shards

    if args.stream:
        # lazy import: serve_stream imports build_index from this module
        from repro.launch.serve_stream import stream_report

        params = EngineParams.lossless(
            sp, args.slots, packed.max_degree, spec_width=args.spec,
            kernel_mode=args.kernel_mode, coalesce_qb=args.coalesce_qb)
        from repro.ft.inject import parse_fault_args
        faults = parse_fault_args(
            args.shards, kill=args.kill_shard, delay=args.delay_shard,
            corrupt_rate=args.corrupt_pages,
            corrupt_mode=args.corrupt_mode, seed=args.seed)
        if (args.deadline_rounds or args.nan_guard or faults is not None
                or live is not None):
            params = dataclasses.replace(
                params, deadline_rounds=args.deadline_rounds,
                guard_nonfinite=args.nan_guard, faults=faults,
                delta_cap=args.delta_cap)
        down = ([int(s) for s in args.down_shards.split(",")]
                if args.down_shards else None)
        report, ids = stream_report(
            consts, geom, params, entry, db, queries[:args.queries],
            slots=args.slots, arrival_rate=args.arrival_rate,
            seed=args.seed + 2, dynamic_spec=args.spec_dynamic,
            round_chunk=args.round_chunk,
            injit_admit={"auto": None, "on": True,
                         "off": False}[args.injit_admit],
            routed=routed, topr=args.topr, leg_L=args.leg_L or None,
            spec_page_w=args.spec_page_w, ring_capacity=args.ring,
            overload=args.overload, down_shards=down,
            device_pages=args.device_pages, prefetch=args.prefetch,
            prefetch_page_w=args.prefetch_page_w, live=live)
        return {"dataset": built.name, "mode": "stream",
                "kernel_mode": args.kernel_mode, "n": int(db.shape[0]),
                **report}, ids

    params = EngineParams.lossless(
        sp, -(-args.queries // args.shards), args.degree,
        spec_width=args.spec, kernel_mode=args.kernel_mode,
        coalesce_qb=args.coalesce_qb)
    qs = args.queries - args.queries % S or S
    qsh = queries[:qs].reshape(S, qs // S, -1)  # jit stages the transfer

    t0 = time.time()
    ids, dists, stats = search_sim(consts, qsh, *entry, params, geom)
    ids = np.asarray(ids).reshape(qs, -1)
    dt = time.time() - t0
    true_ids, _ = brute_force_topk(db, queries[:qs], args.k)
    rec = recall_at_k(ids, true_ids)
    res = {
        "dataset": built.name, "kernel_mode": args.kernel_mode,
        "coalesce_qb": args.coalesce_qb,
        "n": int(db.shape[0]), "queries": qs,
        "recall@k": round(float(rec), 4), "qps": round(qs / dt, 1),
        "rounds": int(np.asarray(stats["total_rounds"]).max()),
        "mean_dists_per_query": float(np.asarray(stats["n_dist"]).mean()),
        "pages_unique": int(np.asarray(stats["pages_unique"]).sum()),
        "items_recv": int(np.asarray(stats["items_recv"]).sum()),
    }
    return res, ids


def main(argv=None):
    enable_compile_cache()
    args = parse_args(argv)
    res, _ = serve(args, build(args))
    print(json.dumps(res, indent=1))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(res, f, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
