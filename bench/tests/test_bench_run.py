"""A whole run of the harness at a tiny size on the CPU, through the
tests' own switch (``allow_cpu``): the command itself refuses the CPU.

Checks that a sound run is ``correct``; that the lower-precision control
(the reference in bfloat16, put in the served entry's place), each fault
the served path can have (an answer altered where it is produced, half of
a request left unanswered) and each fault of the served graph make
``correct`` false; and that a configuration, a traffic mix and a
per-layer metric dropped in as new files are found by name."""
import contextlib
import io
import json
import pathlib
import shutil
import sys

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import run  # noqa: E402
import trace_reduce  # noqa: E402

SEED = 2**31 + 17
TINY = {"n": 512, "degree": 16, "L": 16, "shards": 2, "page_size": 8,
        "slots_per_shard": 4, "query_pool": 64, "reference_sample": 16}


@pytest.fixture(scope="module")
def bench_dir(tmp_path_factory):
    """A copy of the benchmark at tiny sizes, beside the program."""
    root = tmp_path_factory.mktemp("checkout")
    (root / "src").symlink_to(BENCH.parent / "src")
    dst = root / "bench"
    shutil.copytree(BENCH, dst, ignore=shutil.ignore_patterns(
        ".index_cache", ".jax_cache", ".traces", ".plans", "tests",
        "__pycache__"))
    for path in (dst / "configs").glob("*.json"):
        cfg = json.loads(path.read_text())
        cfg.update(TINY)
        path.write_text(json.dumps(cfg))
    shutil.copy(BENCH.parent / "BENCHMARK.json", root / "BENCHMARK.json")
    return dst


def spec_of(bench_dir):
    return json.loads((bench_dir.parent / "BENCHMARK.json").read_text())


def run_tiny(bench_dir, cell, seconds=1.0, trace=False, **kw):
    return run.run_cell(spec_of(bench_dir), cell, SEED, seconds, trace,
                        bench_dir=bench_dir, allow_cpu=True, **kw)


def test_command_refuses_the_cpu(bench_dir):
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = run.main(["--workload", "deep96.batch", "--seed", "1",
                       "--seconds", "1"], bench_dir=bench_dir)
    assert rc != 0
    assert "no TPU" in err.getvalue()
    assert "{" not in out.getvalue()


@pytest.mark.parametrize("cell", ["deep96.batch", "deep96.stream"])
def test_sound_run_is_correct(bench_dir, cell):
    res = run_tiny(bench_dir, cell)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    want = {m["name"] for m in run.cell_metrics(spec_of(bench_dir), cell,
                                                False)}
    assert set(res["metrics"]) == want
    assert res["_log"]["compiles_in_window"] == 0
    assert list(res)[-2:] == ["checks", "_log"]


def test_control_in_bfloat16_is_not_correct(bench_dir, monkeypatch):
    import control
    import index
    from repro.core import scheduler

    cfg = json.loads((bench_dir / "configs" / "deep96.json").read_text())
    with index.Build(cfg, SEED, bench_dir / ".index_cache",
                     bench_dir.parent) as build:
        db, adj, entry, _, _ = build.result()
    monkeypatch.setattr(scheduler, "stream_search",
                        control.bf16_stream_search(db, adj, entry, cfg))
    res = run_tiny(bench_dir, "deep96.batch")
    assert not res["correct"]
    dist_err = res["checks"]["dist_err"]
    assert dist_err["value"] > dist_err["limit"]


def _broken(monkeypatch, alter):
    from repro.core import scheduler

    real = scheduler.stream_search

    def broken(*a, **kw):
        ids, dists, stats = real(*a, **kw)
        return alter(ids.copy(), dists.copy()) + (stats,)

    monkeypatch.setattr(scheduler, "stream_search", broken)


def test_altered_answer_is_not_correct(bench_dir, monkeypatch):
    def alter(ids, dists):
        ids[0, 0] = (ids[0, 0] + 1) % TINY["n"]
        return ids, dists

    _broken(monkeypatch, alter)
    res = run_tiny(bench_dir, "deep96.batch")
    assert not res["correct"]


def test_half_a_request_left_out_is_not_correct(bench_dir, monkeypatch):
    def alter(ids, dists):
        ids[len(ids) // 2:] = -1
        dists[len(ids) // 2:] = 0.0
        return ids, dists

    _broken(monkeypatch, alter)
    res = run_tiny(bench_dir, "deep96.batch")
    assert not res["correct"] and res["failed"] > 0


def _cut_off(db, adj, entry):
    """No edge leads to one vertex any more."""
    v = int(adj[entry][adj[entry] >= 0][0])
    adj[adj == v] = -1
    return db, adj, entry


def _self_loop(db, adj, entry):
    adj[3, 0] = 3
    return db, adj, entry


def _vector_swapped(db, adj, entry):
    db[5] = db[6]
    return db, adj, entry


@pytest.mark.parametrize("fault", [_cut_off, _self_loop, _vector_swapped])
def test_graph_fault_is_not_correct(bench_dir, monkeypatch, fault):
    import index

    real = index.Build.result

    def broken(self):
        db, adj, entry, build_s, cached = real(self)
        return fault(db.copy(), adj.copy(), entry) + (build_s, cached)

    monkeypatch.setattr(index.Build, "result", broken)
    res = run_tiny(bench_dir, "deep96.batch")
    assert not res["correct"]
    assert res["checks"]["graph_faults"]["value"] > 0


def test_new_config_mix_and_metric_are_found_by_name(bench_dir,
                                                     monkeypatch):
    cfg = json.loads((bench_dir / "configs" / "deep96.json").read_text())
    cfg.update(name="wide128", dim=128)
    (bench_dir / "configs" / "wide128.json").write_text(json.dumps(cfg))
    (bench_dir / "traffic" / "pairs.json").write_text(json.dumps(
        {"loop": "closed", "request_pools": 2}))
    (bench_dir / "metrics" / "calls_per_window.py").write_text(
        'LAYER = "served entry"\nSOURCE = "program_counter"\n'
        'MOVES = "qps"\n\n\ndef read(ctx):\n'
        '    return ctx.counters["calls"]\n')
    spec = spec_of(bench_dir)
    spec["workloads"].append({"name": "wide128.pairs", "config": "wide128",
                              "traffic": "pairs", "chips": 1, "why": "x"})
    spec["end_to_end"][0]["workloads"].append("wide128.pairs")
    spec["per_layer"].append({
        "name": "calls_per_window", "unit": "calls", "better": "higher",
        "source": "program_counter", "layer": "served entry",
        "moves": "qps", "workloads": ["wide128.pairs"]})
    spec_path = bench_dir.parent / "BENCHMARK.json"
    spec_path.write_text(json.dumps(spec))

    res = run.run_cell(spec, "wide128.pairs", SEED, 1.0, False,
                       bench_dir=bench_dir, allow_cpu=True)
    assert res["correct"] and "qps" in res["metrics"]
    # per-layer metrics read the trace; on the CPU there is no device
    # plane, so the reduction is stood in for by a fixed one
    fake = trace_reduce.Reduced(0.5, 1.0, [], [], [], 0.0, 1.0)
    monkeypatch.setattr(trace_reduce, "reduce",
                        lambda ev, spans, device_ids: fake)
    monkeypatch.setattr(trace_reduce, "load_events", lambda d: [])
    res = run.run_cell(spec, "wide128.pairs", SEED, 1.0, True,
                       bench_dir=bench_dir, allow_cpu=True)
    assert res["correct"]
    assert res["metrics"]["calls_per_window"]["value"] >= 1
    assert set(res["metrics"]) == {"calls_per_window"}
    assert res["device"]["busy_s"] == 0.5
    assert np.isfinite(res["device"]["window_s"])
