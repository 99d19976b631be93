"""The tiered configuration ``msturing100-tiered`` and the page store's
three readers (``store_host_idle_share``, ``stall_share``,
``pages_fetched_per_query``): the configuration loads and its layout
validates; on a traced CPU run of its batch cell at the tests' tiny
sizes each reader reads a value in range; on a resident run each reads
None. A tiered store also serves, ``correct``, where a round's pages
outgrow its frames."""
import json
import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH / "tests"))

import index  # noqa: E402
import run  # noqa: E402
import trace_reduce  # noqa: E402
from test_bench_layout import add_cell, tiered  # noqa: E402
from test_bench_run import (SEED, bench_dir, run_tiny,  # noqa: E402,F401
                            spec_of)

CELL = "msturing100-tiered.batch"
READERS = ("store_host_idle_share", "stall_share",
           "pages_fetched_per_query")
# TINY's index: 512 vectors in 8-vector pages over 2 shards, 32 pages a
# shard; 16 frames do not hold a round's pages on the CPU
OUTGROWN = 16


def test_config_loads_and_its_layout_validates():
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    (entry,) = [c for c in spec["configs"]
                if c["name"] == "msturing100-tiered"]
    cfg = json.loads((BENCH.parent / entry["file"]).read_text())
    assert cfg["name"] == entry["name"] and cfg["source"] == entry["source"]
    assert (cfg["dim"], cfg["metric"], cfg["k"]) == (100, "l2", 10)
    assert set(entry["reduced"]) == set(cfg["reduced"]) == {"n", "degree"}
    assert index.geometry(cfg).pages_per_shard(cfg["n"]) == 16
    assert run.serving_layout(cfg, 1) == 4          # 4 of 16: 25%
    (cell,) = [w for w in spec["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "msturing100-tiered", "batch", 1)
    metrics = {m["name"]: m for m in spec["per_layer"]}
    for name in READERS:
        assert metrics[name]["workloads"] == [CELL]
        assert metrics[name]["layer"] == "page store"
        reader = run.load_reader(BENCH, name)
        assert (reader.LAYER, reader.SOURCE, reader.MOVES) == (
            "page store", metrics[name]["source"], metrics[name]["moves"])


@pytest.fixture
def device_under_sync(monkeypatch):
    """Traced runs on the CPU: the host spans are real, the device plane
    is stood in for by one op laid over each ``search.sync`` span, so
    the device is idle under every other span of the program."""
    real = trace_reduce.reduce

    def reduce(events, span_names, device_ids):
        placed = [trace_reduce.Event("/device:TPU:0", trace_reduce.OPS_LINE,
                                     "fusion.1", e.start_ns, e.dur_ns)
                  for e in events if e.plane == trace_reduce.HOST_PLANE
                  and e.name == "search.sync"]
        return real(events + placed, span_names, [0])

    monkeypatch.setattr(trace_reduce, "reduce", reduce)


def test_readers_read_the_tiered_cell(bench_dir, device_under_sync):
    res = run_tiny(bench_dir, CELL, trace=True)
    assert res["correct"], res["checks"]
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert 0.0 < got["store_host_idle_share"] < 100.0
    assert 0.0 < got["stall_share"] < 100.0
    c = res["_log"]["counters"]
    assert got["pages_fetched_per_query"] == pytest.approx(
        (c["demand_fetches"] + c["prefetch_issued"]) / c["queries"])
    assert got["pages_fetched_per_query"] > 0.0
    # the scheduler's and the served entry's readers read the tiered
    # cell as they read the resident one
    for name in ("slot_occupancy", "call_host_idle_share.batch",
                 "boundary_host_idle_share.batch"):
        assert 0.0 < got[name] <= 100.0, name
    assert res["_log"]["compiles_in_window"] == 0


def test_readers_read_none_on_a_resident_run(bench_dir, device_under_sync):
    spec = spec_of(bench_dir)
    for m in spec["per_layer"]:
        if m["name"] in READERS:
            m["workloads"].append("deep96.batch")
    res = run.run_cell(spec, "deep96.batch", 2**31 + 17, 1.0, True,
                       bench_dir=bench_dir, allow_cpu=True)
    assert res["correct"]
    assert not set(READERS) & set(res["metrics"])
    assert "boundary_host_idle_share.batch" in res["metrics"]


def test_tiered_store_serves_where_a_round_outgrows_the_frames(
        bench_dir):
    spec = add_cell(bench_dir, "tier16", layout=tiered(OUTGROWN))
    res = run.run_cell(spec, "tier16.batch", SEED, 1.0, False,
                       bench_dir=bench_dir, allow_cpu=True)
    assert res["correct"], res["checks"]
    c = res["_log"]["counters"]
    assert c["resident_fraction"] == OUTGROWN / 32
    assert c["demand_fetches"] > 0 and c["stalls"] > 0
