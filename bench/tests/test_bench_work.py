"""The roofline work counts against hand-computed values, the peaks
table, and the benchmark file against the metric readers it names."""
import json
import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import peaks  # noqa: E402
import work  # noqa: E402

SPEC = json.loads((BENCH.parent / "BENCHMARK.json").read_text())


def test_distance_work_by_hand():
    # 1000 distances at d=96: 2*96 FLOP each; 10 pages of 64 vectors,
    # each 96 floats and a norm, 4 bytes a float
    flops, nbytes = work.distance_work(1000, 96, 10, 64)
    assert flops == 192_000
    assert nbytes == 10 * 64 * 97 * 4


def test_merge_bytes_by_hand():
    # 100 live row-rounds, L=32, W=1, degree 32: (64 + 32) entries of 8 B
    assert work.merge_bytes(100, 32, 1, 32) == 100 * 96 * 8
    assert work.merge_bytes(100, 32, 2, 32, spec_width=4) == 100 * 136 * 8


def test_roofline_share_takes_the_larger_bound():
    pk = {"flops_bf16": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.roofline_share(50.0, 20.0, 4.0, pk) == (50.0, "memory")
    assert work.roofline_share(400.0, 20.0, 8.0, pk) == (50.0, "compute")
    assert work.roofline_share(1.0, 1.0, 0.0, pk) is None


def test_peaks_refuse_unknown_device():
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v9 imaginary")


@pytest.mark.parametrize("metric", SPEC["per_layer"], ids=lambda m: m["name"])
def test_reader_file_matches_the_benchmark(metric):
    import run

    mod = run.load_reader(BENCH, metric["name"])
    assert (mod.LAYER, mod.SOURCE, mod.MOVES) == (
        metric["layer"], metric["source"], metric["moves"])
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    for cell in metric["workloads"]:
        assert cell in e2e[metric["moves"]].get("workloads", [cell])


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda c: c["name"])
def test_every_cell_reports_setup_another_metric_and_a_layer(cell):
    import run

    e2e = {m["name"] for m in run.cell_metrics(SPEC, cell["name"], False)}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert run.cell_metrics(SPEC, cell["name"], True)
    assert (BENCH / "configs" / f"{cell['config']}.json").exists()
    assert (BENCH / "traffic" / f"{cell['traffic']}.json").exists()


def test_latency_p99_reader_takes_every_query_of_the_window():
    import types

    import numpy as np
    import run

    mod = run.load_reader(BENCH, "latency_p99_ms")
    # 200 queries in two calls: 198 at 0.1 s, the two slowest at 1 and 2 s
    lat = [np.full(100, 0.1), np.r_[np.full(98, 0.1), 1.0, 2.0]]
    ctx = types.SimpleNamespace(served=types.SimpleNamespace(latency_s=lat))
    assert mod.read(ctx) == pytest.approx(1e3 * np.percentile(
        np.concatenate(lat), 99))
    assert mod.read(ctx) > 100.0
    ctx.served.latency_s = []
    assert mod.read(ctx) is None
