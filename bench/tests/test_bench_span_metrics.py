"""The four readers of device idle under the program's host spans
(``call_host_idle_share.*``, ``boundary_host_idle_share.*``): on events
made by hand, and on a real CPU trace of the served entry's spans with
device ops placed by hand, which pins the names the readers match to the
names the program writes."""
import pathlib
import sys
import types

import jax
import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import run  # noqa: E402
import trace_reduce as tr  # noqa: E402

READERS = ["call_host_idle_share.batch", "call_host_idle_share.stream",
           "boundary_host_idle_share.batch",
           "boundary_host_idle_share.stream"]
DEV = "/device:TPU:0"


def host(name, start, dur):
    return tr.Event(tr.HOST_PLANE, "python", name, float(start), float(dur))


def op(start, dur):
    return tr.Event(DEV, tr.OPS_LINE, "fusion.1", float(start), float(dur))


def share(name, events):
    """The reader's value over a window of [100, 1100] ns."""
    red = tr.reduce([host(tr.WINDOW_SPAN, 100, 1000)] + events, [])
    return run.load_reader(BENCH, name).read(
        types.SimpleNamespace(trace=red))


@pytest.mark.parametrize("name", READERS)
def test_idle_under_spans(name):
    spans = run.load_reader(BENCH, name).SPANS
    # the span [200, 400] is busy over [250, 300]: idle 150 of 1000
    got = share(name, [host(spans[0], 200, 200), op(250, 50),
                       op(600, 100)])
    assert got == pytest.approx(15.0)


@pytest.mark.parametrize("name", READERS)
def test_overlapping_spans_count_once(name):
    spans = run.load_reader(BENCH, name).SPANS
    # [200, 400] and [300, 500]: one interval of 300
    got = share(name, [host(spans[0], 200, 200),
                       host(spans[-1], 300, 200), op(900, 10)])
    assert got == pytest.approx(30.0)


@pytest.mark.parametrize("name", READERS)
def test_spans_clipped_to_window(name):
    spans = run.load_reader(BENCH, name).SPANS
    # [0, 200] and [1000, 1300] lie 100 each inside [100, 1100]
    got = share(name, [host(spans[0], 0, 200),
                       host(spans[-1], 1000, 300), op(500, 10)])
    assert got == pytest.approx(20.0)


@pytest.mark.parametrize("name", READERS)
def test_no_span_reads_none(name):
    # other spans, and the reader's own spans outside the window only
    spans = run.load_reader(BENCH, name).SPANS
    got = share(name, [host("stream_search", 100, 1000),
                       host("search.sync", 200, 100),
                       host(spans[0], 1200, 100), op(500, 10)])
    assert got is None


def test_readers_match_the_program_spans(tmp_path):
    """Device ops laid exactly over every ``search.sync`` span of two
    real calls: the device is idle under every other child span, so each
    reader reads the summed length of its own spans."""
    from repro.core.engine import EngineParams, pack_for_engine
    from repro.core.graph import build_vamana
    from repro.core.luncsr import Geometry, LUNCSR, pack_index
    from repro.core.ref_search import SearchParams
    from repro.core.scheduler import stream_search

    rng = np.random.default_rng(7)
    db = rng.integers(-8, 9, size=(128, 8)).astype(np.float32)
    queries = rng.integers(-8, 9, size=(12, 8)).astype(np.float32)
    adj, medoid = build_vamana(db, r=8, alpha=1.2, seed=7)
    geo = Geometry(num_shards=2, page_size=16, pages_per_block=2, dim=8)
    packed = pack_index(LUNCSR.from_adjacency(db, adj, geo, entry=medoid),
                        max_degree=8)
    consts, geom, entry = pack_for_engine(packed)
    params = EngineParams.lossless(SearchParams(L=8, W=1, k=4), 2,
                                   geom.max_degree)
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(2):
            stream_search(consts, geom, params, entry, queries, num_slots=2,
                          round_chunk=2, ring_capacity=4)

    events = tr.load_events(str(tmp_path))
    prog = [e for e in events if e.plane == tr.HOST_PLANE
            and e.name.startswith("search.")]
    calls = sorted((e for e in prog if e.name == "search.call"),
                   key=lambda e: e.start_ns)
    assert len(calls) == 2
    lo = min(e.start_ns for e in calls)
    hi = max(e.end_ns for e in calls)
    # the readers name every span the program writes but the call, the
    # warm-up nested in set-up, and the sync, where the device works
    read = {n for name in READERS
            for n in run.load_reader(BENCH, name).SPANS}
    assert read | {"search.call", "search.warmup", "search.sync"} == {
        e.name for e in prog}
    placed = [tr.Event(DEV, tr.OPS_LINE, "fusion.1", e.start_ns, e.dur_ns)
              for e in prog if e.name == "search.sync"]
    red = tr.reduce(events + placed + [host(tr.WINDOW_SPAN, lo, hi - lo)],
                    [])
    ctx = types.SimpleNamespace(trace=red)
    total = 0.0
    for name in READERS:
        reader = run.load_reader(BENCH, name)
        want = sum(e.dur_ns for e in prog if e.name in reader.SPANS)
        assert want > 0
        got = reader.read(ctx)
        assert got == pytest.approx(100.0 * want / (hi - lo), rel=1e-6)
        total += got / 2           # each share is read in two cells
    sync = sum(e.dur_ns for e in placed)
    # the two shares and the device's time under search.sync fill the
    # window but for the gap between the calls and the span boundaries
    gap = calls[1].start_ns - calls[0].end_ns
    assert total + 100.0 * (sync + gap) / (hi - lo) >= 99.0
