"""The scheduler's ``inflight_dispatch_share`` readers: the share of a
window's dispatches launched before the previous chunk was synced. A
resident call whose queue is staged once runs ahead; a call with a
tiered page store launches only after its chunk's page boundary, so
reads 0; stats without the counter (a program that does not run ahead)
read None. The configured cells report it on a traced run."""
import dataclasses
import pathlib
import sys
import types

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH / "tests"))

import run  # noqa: E402
from test_bench_run import (bench_dir, run_tiny,  # noqa: E402,F401
                            spec_of)
from test_bench_store_metrics import device_under_sync  # noqa: E402,F401

NAMES = ("inflight_dispatch_share", "inflight_dispatch_share.stream")


@pytest.fixture(scope="module")
def small():
    from repro.core.engine import EngineParams, pack_for_engine
    from repro.core.graph import build_vamana
    from repro.core.luncsr import Geometry, LUNCSR, pack_index
    from repro.core.ref_search import SearchParams

    rng = np.random.default_rng(11)
    n, d, S = 256, 16, 2
    db = rng.integers(-8, 9, size=(n, d)).astype(np.float32)
    queries = rng.integers(-8, 9, size=(24, d)).astype(np.float32)
    adj, medoid = build_vamana(db, r=8, alpha=1.2, seed=11)
    geo = Geometry(num_shards=S, page_size=16, pages_per_block=2, dim=d)
    packed = pack_index(LUNCSR.from_adjacency(db, adj, geo, entry=medoid),
                        max_degree=8)
    consts, geom, entry = pack_for_engine(packed)
    params = EngineParams.lossless(SearchParams(L=12, W=1, k=5), 2,
                                   geom.max_degree)
    return consts, geom, params, entry, queries


def _calls(small, frames=0, n=2):
    from repro.core.pagestore import PageStore
    from repro.core.scheduler import stream_search

    consts, geom, params, entry, queries = small
    kw = {}
    if frames:
        store = PageStore(consts, geom, frames, w_select=1)
        params = dataclasses.replace(params, store_pages=store.num_pages)
        kw["pagestore"] = store
    return [stream_search(consts, geom, params, entry, queries,
                          num_slots=2, round_chunk=4, ring_capacity=32,
                          **kw)[2] for _ in range(n)]


def _read(name, stats):
    ctx = types.SimpleNamespace(served=types.SimpleNamespace(stats=stats))
    return run.load_reader(BENCH, name).read(ctx)


@pytest.mark.parametrize("name", NAMES)
def test_share_of_a_call_run_ahead(small, name):
    stats = _calls(small)
    got = _read(name, stats)
    ahead = sum(s.inflight_dispatches for s in stats)
    assert ahead > 0
    assert got == pytest.approx(
        100.0 * ahead / sum(s.host_dispatches for s in stats))
    # every launch but each call's first and its zero-round tail's
    # successor runs ahead: all calls here are busy to the end
    assert 0.0 < got < 100.0


@pytest.mark.parametrize("name", NAMES)
def test_share_is_zero_with_a_page_store(small, name):
    stats = _calls(small, frames=2)
    assert all(s.host_dispatches > 1 for s in stats)
    assert _read(name, stats) == 0.0


@pytest.mark.parametrize("name", NAMES)
def test_share_is_none_without_the_counter(small, name):
    old = [types.SimpleNamespace(host_dispatches=s.host_dispatches)
           for s in _calls(small, n=1)]
    assert _read(name, old) is None
    assert _read(name, []) is None


def test_entries_and_readers_agree():
    spec = spec_of(BENCH)
    metrics = {m["name"]: m for m in spec["per_layer"]}
    cells = {w["name"] for w in spec["workloads"]}
    listed = set()
    for name in NAMES:
        m = metrics[name]
        reader = run.load_reader(BENCH, name)
        assert (reader.LAYER, reader.SOURCE, reader.MOVES) == (
            m["layer"], m["source"], m["moves"]) == (
            "scheduler", "program_counter", m["moves"])
        assert (m["unit"], m["better"]) == ("%", "higher")
        listed |= set(m["workloads"])
        for cell in m["workloads"]:     # each cell reports what it moves
            e2e = {e["name"]: e for e in spec["end_to_end"]}[m["moves"]]
            assert cell in e2e.get("workloads", cells)
    assert listed == cells


@pytest.mark.parametrize("cell,name", [
    ("deep96.batch", "inflight_dispatch_share"),
    ("deep96.stream", "inflight_dispatch_share.stream")])
def test_traced_run_reports_the_share(bench_dir, device_under_sync, cell,
                                      name):
    res = run_tiny(bench_dir, cell, trace=True)
    assert res["correct"], res["checks"]
    assert 0.0 < res["metrics"][name]["value"] < 100.0
    assert res["_log"]["compiles_in_window"] == 0
