"""The serving benchmark: one cell of ``BENCHMARK.json``, one run.

    python bench/run.py --workload deep96.batch --seed 7 --seconds 10 --trace 0

A cell names a configuration (``bench/configs/<config>.json``: the
deployment, its index and serving settings, and the limits of its
correctness numbers) and a traffic mix (``bench/traffic/<mix>.json``, read
by ``frontend.py``). Per-layer metrics are readers of their own,
``bench/metrics/<metric>.py``, found by the metric's name. Adding a
configuration, a mix or a metric takes new files and no edit here.

A configuration may state its index layout under ``"layout"``. Without
it the index is wholly resident on the cell's chip. With
``{"device_pages": <frames a shard holds on the device>}`` the index is
tiered: a shard's vector pages live in host RAM and that many device
frames cache them, through one ``repro.core.pagestore.PageStore`` built
in set-up and kept warm across the window's calls. A cell on more than
one chip serves through the shard_map stepper over a mesh of its chips,
one shard a chip; a cell on one chip through the one-device (sim)
stepper. The checks are the same for every layout.

A run: load the seed's index from ``bench/.index_cache``, or build it there
with the program's graph build in a child process; refuse anything but a
TPU; make the seed's query pool; place the index on the device; warm up
the cell's shapes with one call of the served entry (all of this is
``setup_s``); serve the seed's traffic for ``--seconds`` through
``repro.core.scheduler.stream_search``, one call per request, with
compiles counted; then check the graph and every answer against the plain
references and print one JSON line. With ``--trace 1`` the window runs
under the profiler and the line carries the cell's per-layer metrics
instead of its end-to-end ones.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

BENCH_DIR = pathlib.Path(__file__).resolve().parent
# libtpu would otherwise log to a fixed path outside the checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, str(BENCH_DIR.parent / "src"))
sys.path.insert(0, str(BENCH_DIR))

import frontend  # noqa: E402
import index  # noqa: E402
import reference  # noqa: E402
import trace_reduce  # noqa: E402
from data import STREAM_SAMPLE, VectorDataset, rng_for  # noqa: E402


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, flush=True)


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_reader(bench_dir: pathlib.Path, name: str):
    """The per-layer metric ``name``: ``metrics/<name>.py``."""
    path = bench_dir / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(spec: dict, cell: str, trace: bool) -> list[dict]:
    """The metrics a run of ``cell`` reports: its end-to-end ones, or
    with a trace its per-layer ones."""
    group = spec["per_layer"] if trace else spec["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def require_chips(count: int, allow_cpu: bool):
    """The cell's own devices: the first ``count`` JAX sees."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" and not allow_cpu:
        raise NoChip(f"no TPU: JAX sees {devs[0].platform} devices; the "
                     f"benchmark does not run elsewhere")
    if len(devs) < count:
        raise NoChip(f"the cell needs {count} chips, JAX sees {len(devs)}")
    return devs[:count]


def serving_layout(cfg: dict, chips: int):
    """The configuration's ``layout``, checked before the index is built:
    None where the index is wholly resident, else the tiered page store's
    device frames a shard. A cell on several chips takes one shard a
    chip."""
    name, shards = cfg["name"], int(cfg["shards"])
    if chips > 1 and shards != chips:
        raise ValueError(
            f"config {name}: 'shards' is {shards}, but the shard_map "
            f"stepper over the cell's {chips} chips takes one shard a "
            f"chip, {chips}")
    layout = cfg.get("layout")
    if layout is None:
        return None
    if not isinstance(layout, dict) or list(layout) != ["device_pages"]:
        raise ValueError(f"config {name}: 'layout' must be "
                         f"{{\"device_pages\": <frames>}}, is {layout!r}")
    pages = layout["device_pages"]
    per_shard = index.geometry(cfg).pages_per_shard(int(cfg["n"]))
    if type(pages) is not int or not 1 <= pages <= per_shard:
        raise ValueError(
            f"config {name}: 'layout.device_pages' is {pages!r}, must be "
            f"1 to {per_shard}, the pages a shard holds")
    return pages


def enable_compile_cache(bench_dir: pathlib.Path) -> str:
    """JAX's persistent compile cache at a fixed path in the checkout,
    unless ``JAX_COMPILATION_CACHE_DIR`` names one."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        bench_dir / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


@dataclasses.dataclass
class Context:
    """What a per-layer metric reader may read."""

    cfg: dict
    served: frontend.Served
    counters: dict
    trace: object           # trace_reduce.Reduced, or None
    peaks: dict


def window_counters(served: frontend.Served, slots: int,
                    shards: int) -> dict:
    """The scheduler's counts over the window's calls."""
    st = served.stats
    results = [r for s in st for r in s.results]
    busy = sum(s.total_rounds for s in st)
    clock = busy + sum(s.idle_rounds for s in st)
    live = sum(int(np.sum(s.occupancy_trace)) for s in st)
    return {
        "calls": len(st),
        "queries": served.answered,
        "rounds": busy,
        "dispatches": sum(s.host_dispatches for s in st),
        "live_row_rounds": live,
        "occupancy": live / (shards * slots * clock) if clock else None,
        "n_dist": sum(r.n_dist for r in results),
        "pages_unique": sum(s.pages_unique for s in st),
        "retired": len(results),
    }


def store_counters(store, before: dict, served: frontend.Served) -> dict:
    """The tiered page store's counts over the window: its own counters
    after the window less before it (``StreamStats.prefetch_hits`` is the
    store's running total, so it is taken here too), the stall rounds of
    the window's calls, and the share of a shard's pages on the device."""
    after = store.counters()
    out = {k: after[k] - before[k] for k in after}
    out["stalls"] = sum(s.stalls for s in served.stats)
    out["resident_fraction"] = store.resident_fraction
    return out


class GcPauses:
    """The garbage collector's pauses while it is installed, in seconds
    (a diagnostic for host stalls in the window; logged, not reported)."""

    def __init__(self):
        self.pauses: list[float] = []
        self.full = 0
        self._t = None

    def __call__(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.pauses.append(time.perf_counter() - self._t)
            self.full += info["generation"] == 2
            self._t = None

    def __enter__(self):
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self)

    def summary(self) -> dict:
        return {"collections": len(self.pauses), "full": self.full,
                "total_s": sum(self.pauses),
                "max_s": max(self.pauses, default=0.0)}


def longest_calls(served: frontend.Served, n: int = 3) -> list:
    """[seconds, queries, rounds, start] of the window's longest calls,
    the start in seconds after the process began: a call long for its
    rounds is a host stall, not a queue."""
    rows = zip(served.call_s, map(len, served.qidx),
               (s.total_rounds for s in served.stats), served.call_at)
    return sorted(([float(t), q, r, float(a) - T_START]
                   for t, q, r, a in rows), reverse=True)[:n]


def graph_faults(cfg, seed, db, adj, entry) -> int:
    """Vertices at which the served index breaks what the build
    guarantees: a vector that is not one of the seed's collection, a
    neighbour out of range, twice or itself, no neighbour, or no path
    from the entry. The traversal is compared with the reference on this
    graph; this checks the graph."""
    n, r = adj.shape
    want = index.collection(cfg, seed)
    if db.shape != want.shape or r != int(cfg["degree"]):
        return n
    # the served vectors, as a set, are the collection's
    have = np.lexsort(db.T[::-1])
    fault = np.zeros(n, bool)
    fault[have] = (db[have] != want[np.lexsort(want.T[::-1])]).any(axis=1)
    live = adj != reference.INVALID
    fault |= ~live.any(axis=1)
    fault |= (live & ((adj < 0) | (adj >= n))).any(axis=1)
    fault |= (live & (adj == np.arange(n)[:, None])).any(axis=1)
    srt = np.sort(np.where(live, adj, -1 - np.arange(r)), axis=1)
    fault |= (srt[:, 1:] == srt[:, :-1]).any(axis=1)
    seen = np.zeros(n, bool)
    seen[entry] = True
    front = np.asarray([entry])
    while front.size:
        nxt = adj[front][live[front]]
        nxt = np.unique(nxt[(nxt >= 0) & (nxt < n)])
        front = nxt[~seen[nxt]]
        seen[front] = True
    fault |= ~seen
    return int(fault.sum())


def check_answers(cfg, db, adj, entry, pool_q, served, seed, limits):
    """The comparison that decides ``correct``; returns (checks, failed,
    recall@k). Every answer is checked for validity and for agreement
    with the other answers to the same query; each query's answer is
    checked against float64 distances of its ids, exact top-k for
    recall, and, on a sample drawn from the seed, the lockstep
    reference on the same graph."""
    k = int(cfg["k"])
    idx = np.concatenate(served.qidx)
    ids = np.concatenate(served.ids).astype(np.int64)
    dists = np.concatenate(served.dists)
    n = db.shape[0]
    bad = ((ids < 0) | (ids >= n)).any(axis=1)
    srt = np.sort(ids, axis=1)
    bad |= (srt[:, 1:] == srt[:, :-1]).any(axis=1)
    failed = int(bad.sum()) + (served.due - len(idx))

    uniq, first, inv = np.unique(idx, return_index=True,
                                 return_inverse=True)
    c_ids, c_d = ids[first], dists[first]
    inconsistent = int(((ids != c_ids[inv]).any(axis=1)
                        | (dists != c_d[inv]).any(axis=1)).sum())

    q = pool_q[uniq]
    ex = reference.exact_sq_dists(db, q, c_ids)
    vv = (db[np.clip(c_ids, 0, n - 1)].astype(np.float64) ** 2).sum(-1)
    scale = (q.astype(np.float64) ** 2).sum(-1)[:, None] + vv
    err = np.abs(c_d - ex) / scale
    dist_err = float(np.nanmax(err)) if np.isfinite(err).any() else 1.0

    truth, _ = reference.brute_force_topk(db, q, k)
    hits = np.asarray([len(set(a.tolist()) & set(b.tolist()))
                       for a, b in zip(c_ids, truth)], np.float64)
    recall = float((hits[inv] / k).mean())

    m = min(int(cfg["reference_sample"]), len(uniq))
    pick = np.sort(rng_for(seed, STREAM_SAMPLE).choice(len(uniq), m,
                                                       replace=False))
    overlap = []
    for j in pick:
        r_ids, _ = reference.lockstep_search(
            db, adj, q[j], entry, int(cfg["L"]), int(cfg["W"]), k)
        overlap.append(len(set(r_ids.tolist()) & set(c_ids[j].tolist())))
    ref_miss = 1.0 - float(np.mean(overlap)) / k

    checks = {
        "failed": (failed, 0),
        "inconsistent": (inconsistent, 0),
        "dist_err": (dist_err, float(limits["dist_err"])),
        "ref_miss": (ref_miss, float(limits["ref_miss"])),
    }
    return checks, failed, recall


def run_cell(spec: dict, cell_name: str, seed: int, seconds: float,
             trace: bool, *, bench_dir: pathlib.Path = BENCH_DIR,
             allow_cpu: bool = False) -> dict:
    """One run of one cell; returns the result line's object."""
    cells = {c["name"]: c for c in spec["workloads"]}
    if cell_name not in cells:
        raise KeyError(f"no cell {cell_name!r} in the benchmark; have "
                       f"{sorted(cells)}")
    cell = cells[cell_name]
    chips = int(cell["chips"])
    cfg = load_json(bench_dir / "configs" / f"{cell['config']}.json")
    device_pages = serving_layout(cfg, chips)
    mix = frontend.load(bench_dir / "traffic" / f"{cell['traffic']}.json")
    metrics = cell_metrics(spec, cell_name, trace)
    readers = ({m["name"]: load_reader(bench_dir, m["name"])
                for m in metrics} if trace else {})

    with index.Build(cfg, seed, bench_dir / ".index_cache",
                     bench_dir.parent) as build:
        devs = require_chips(chips, allow_cpu)
        db, adj, entry, build_s, cached = build.result()
    import jax

    from repro.analysis.compile_guard import CompileGuard
    from repro.core import scheduler
    from repro.core.engine import EngineParams, pack_for_engine
    from repro.core.ref_search import SearchParams

    import peaks as peak_table
    peaks = (peak_table.peaks_for(devs[0].device_kind)
             if devs[0].platform == "tpu" else {})
    log(f"device: {devs[0].platform} {devs[0].device_kind} x{len(devs)}; "
        f"compile cache {enable_compile_cache(bench_dir)}")

    log(f"index {cfg['name']} n={db.shape[0]} d={db.shape[1]}: "
        f"{'loaded from cache' if cached else 'built'} in {build_s:.3f} s")
    packed = index.pack(cfg, db, adj, entry)
    consts, geom, entry_dev = pack_for_engine(packed)
    S, slots = int(cfg["shards"]), int(cfg["slots_per_shard"])
    params = EngineParams.lossless(
        SearchParams(L=int(cfg["L"]), W=int(cfg["W"]), k=int(cfg["k"])),
        slots, packed.max_degree, kernel_mode=cfg["kernel_mode"],
        coalesce_qb=int(cfg["coalesce_qb"]))

    # the layout's arguments of the served entry; none where the index
    # is resident on one chip
    layout_kw, store = {}, None
    if device_pages is not None:
        from repro.core.pagestore import PageStore

        store = PageStore(consts, geom, device_pages,
                          w_select=int(cfg["W"]))
        params = dataclasses.replace(params, store_pages=store.num_pages)
        layout_kw["pagestore"] = store
    if chips > 1:
        from repro.launch.mesh import make_engine_mesh

        # over the first ``chips`` devices JAX sees: ``devs``
        layout_kw["mesh"] = make_engine_mesh("lun", num=chips)

    pool = int(cfg["query_pool"])
    pool_q = VectorDataset.from_config(cfg).queries(pool, seed)
    pool_slots = S * slots
    ring = frontend.ring_capacity(mix, pool_slots)

    def call(rows):
        return scheduler.stream_search(
            consts, geom, params, entry_dev, pool_q[rows],
            num_slots=slots, round_chunk=int(cfg["round_chunk"]),
            ring_capacity=ring, **layout_kw)

    call(frontend.first_request(mix, pool, pool_slots, seed))
    # what set-up made lives on; the collector need not walk it again
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - T_START
    log(f"setup_s={setup_s:.3f} (ring {ring}, pool {pool_slots} slots)")

    store_before = store.counters() if store is not None else None
    tdir = bench_dir / ".traces" / f"{cell_name}-{os.getpid()}"
    span = jax.profiler.TraceAnnotation
    with CompileGuard() as guard, GcPauses() as pauses:
        if trace:
            jax.profiler.start_trace(str(tdir))
        with span(trace_reduce.WINDOW_SPAN):
            if mix["loop"] == "closed":
                served = frontend.closed_loop(call, mix, pool, pool_slots,
                                              seed, seconds, span)
            else:
                served = frontend.open_loop(call, mix, pool, seed,
                                            seconds, span)
        if trace:
            jax.profiler.stop_trace()
    gc.unfreeze()
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    log(f"window: {served.answered} queries in {len(served.stats)} calls, "
        f"{served.window_s:.3f} s; compiles in window: {guard.total}")

    reduced = None
    if trace:
        events = trace_reduce.load_events(str(tdir))
        reduced = trace_reduce.reduce(events, frontend.SPANS,
                                      [d.id for d in devs])
        shutil.rmtree(tdir, ignore_errors=True)
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
    counters = window_counters(served, slots, S)
    if store is not None:
        counters.update(store_counters(store, store_before, served))

    checks, failed, recall = check_answers(
        cfg, db, adj, entry, pool_q, served, seed, cfg["limits"])
    checks = {"graph_faults": (graph_faults(cfg, seed, db, adj, entry), 0),
              **checks}
    correct = all(v <= lim for v, lim in checks.values())

    lat = np.concatenate(served.latency_s)
    e2e = {"qps": served.answered / served.window_s,
           "latency_p50_s": float(np.percentile(lat, 50)),
           "latency_p95_s": float(np.percentile(lat, 95)),
           "latency_p99_s": float(np.percentile(lat, 99)),
           "recall_at_10": recall, "setup_s": setup_s}
    out_metrics = {}
    ctx = Context(cfg, served, counters, reduced, peaks)
    for m in metrics:
        v = readers[m["name"]].read(ctx) if trace else e2e[m["name"]]
        if v is not None:
            out_metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    result = {"correct": bool(correct), "attempted": int(served.due),
              "failed": int(failed), "metrics": out_metrics,
              "device": device}
    if trace:
        result["breakdown"] = {"device_ops": reduced.device_ops,
                               "idle_gaps": reduced.idle_gaps}
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, (v, lim) in checks.items()}
    result["_log"] = {"compiles_in_window": guard.total,
                      "build_s": build_s, "index_cached": cached,
                      "window_s": served.window_s, "e2e": e2e,
                      "counters": counters, "gc": pauses.summary(),
                      "longest_calls": longest_calls(served)}
    return result


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def emit(result: dict) -> None:
    """stderr: each compared number beside its limit, as the last lines;
    stdout: the result line, last."""
    extra = result.pop("_log")
    log(json.dumps(extra))
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


def main(argv=None, *, bench_dir: pathlib.Path = BENCH_DIR,
         allow_cpu: bool = False) -> int:
    args = parse_args(argv)
    spec = load_json(bench_dir.parent / "BENCHMARK.json")
    try:
        result = run_cell(spec, args.workload, args.seed, args.seconds,
                          bool(args.trace), bench_dir=bench_dir,
                          allow_cpu=allow_cpu)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
