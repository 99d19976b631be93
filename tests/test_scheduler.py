"""Streaming scheduler == one-shot engine, bit for bit, plus the
retire/refill slot-reuse and dynamic-speculation machinery."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import (EngineParams, engine_admit, engine_init,
                               engine_round, make_stepper,
                               pack_for_engine, search_sim)
from repro.core.graph import build_vamana, brute_force_topk, recall_at_k
from repro.core.luncsr import Geometry, LUNCSR, pack_index
from repro.core.ref_search import SearchParams
from repro.core.scheduler import SpecController, stream_search

INVALID = -1


def _dataset(n=1024, d=32, nq=32, S=4, page=32, seed=0, pref_width=8):
    rng = np.random.default_rng(seed)
    db = rng.integers(-8, 9, size=(n, d)).astype(np.float32)
    queries = rng.integers(-8, 9, size=(nq, d)).astype(np.float32)
    adj, medoid = build_vamana(db, r=12, alpha=1.2, seed=seed)
    geo = Geometry(num_shards=S, page_size=page, pages_per_block=2, dim=d)
    index = LUNCSR.from_adjacency(db, adj, geo, entry=medoid,
                                  pref_width=pref_width)
    packed = pack_index(index, max_degree=12)
    return db, queries, packed


@pytest.fixture(scope="module")
def ds():
    return _dataset()


def _oneshot(consts, geom, entry, queries, sp, spec=0):
    """Reference per-query results from the frozen-batch driver."""
    S = geom.num_shards
    nq = queries.shape[0]
    params = EngineParams.lossless(sp, nq // S, geom.max_degree,
                                   spec_width=spec)
    qsh = jnp.asarray(queries.reshape(S, nq // S, -1))
    i, d, _ = search_sim(consts, qsh, *entry, params, geom)
    return (np.asarray(i).reshape(nq, -1), np.asarray(d).reshape(nq, -1))


# ---------------------------------------------------------------------------
# Bit-identity: streaming admission == one-shot, any arrivals/slots/chunks,
# host-paced or in-jit admission
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("injit", [False, True])
@pytest.mark.parametrize("slots,spec,chunk",
                         [(1, 0, 1), (3, 0, 3), (8, 4, 8), (3, 4, 8)])
def test_stream_matches_oneshot_bitexact(ds, slots, spec, chunk, injit):
    db, queries, packed = ds
    consts, geom, entry = pack_for_engine(packed)
    sp = SearchParams(L=16, W=1, k=10)
    ref_i, ref_d = _oneshot(consts, geom, entry, queries, sp, spec)
    params = EngineParams.lossless(sp, slots, geom.max_degree,
                                   spec_width=spec)
    rng = np.random.default_rng(slots + spec)
    arrivals = rng.integers(0, 20, queries.shape[0])
    ids, dists, st = stream_search(consts, geom, params, entry, queries,
                                   num_slots=slots, arrivals=arrivals,
                                   round_chunk=chunk, injit_admit=injit)
    np.testing.assert_array_equal(ids, ref_i)
    np.testing.assert_array_equal(dists, ref_d)
    assert len(st.results) == queries.shape[0]


def test_stream_property_arrival_orders(ds):
    """Hypothesis: any arrival order, slot count, arrival spacing,
    round-chunk size and admission path (host-paced vs in-jit) produce
    bit-identical per-query results to one-shot search_sim."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    db, queries, packed = ds
    consts, geom, entry = pack_for_engine(packed)
    sp = SearchParams(L=8, W=1, k=5)
    nq = 8
    q = queries[:nq]
    S = geom.num_shards
    params_ref = EngineParams.lossless(sp, nq // S, geom.max_degree)
    qsh = jnp.asarray(q.reshape(S, nq // S, -1))
    i, d, _ = search_sim(consts, qsh, *entry, params_ref, geom)
    ref_i = np.asarray(i).reshape(nq, -1)
    ref_d = np.asarray(d).reshape(nq, -1)

    @given(st.integers(1, 4),
           st.lists(st.integers(0, 12), min_size=nq, max_size=nq),
           st.sampled_from([1, 3, 8]),
           st.booleans(),
           st.randoms(use_true_random=False))
    @settings(max_examples=10, deadline=None)
    def check(slots, gaps, chunk, injit, rnd):
        order = list(range(nq))
        rnd.shuffle(order)
        arrivals = np.zeros(nq, np.int64)
        arrivals[order] = np.cumsum(gaps)   # shuffled admission order
        params = EngineParams.lossless(sp, slots, geom.max_degree)
        ids, dists, _ = stream_search(consts, geom, params, entry, q,
                                      num_slots=slots, arrivals=arrivals,
                                      round_chunk=chunk,
                                      injit_admit=injit)
        np.testing.assert_array_equal(ids, ref_i)
        np.testing.assert_array_equal(dists, ref_d)

    check()


# ---------------------------------------------------------------------------
# In-jit round chunks: same schedule, same accounting, fewer host syncs
# ---------------------------------------------------------------------------
def _result_records(st):
    return {r.qid: (tuple(r.ids), tuple(r.dists), r.service_rounds,
                    r.n_dist, r.admit_round, r.retire_round)
            for r in st.results}


@pytest.mark.parametrize("injit", [False, True])
@pytest.mark.parametrize("dynamic", [False, True])
def test_chunked_matches_per_round_exact(ds, dynamic, injit):
    """round_chunk > 1 reproduces the per-round scheduler exactly:
    every QueryResult field (ids/dists/service_rounds/n_dist and the
    admit/retire round accounting), the engine-round schedule, the
    occupancy and speculation traces — with strictly fewer host
    dispatches. The dynamic leg proves the in-jit SpecController port
    steps identically to the host rule at chunk boundaries; the injit
    leg proves the device-side pending queue seats queries on exactly
    the rounds the host admission loop would."""
    db, queries, packed = ds
    consts, geom, entry = pack_for_engine(packed)
    sp = SearchParams(L=16, W=1, k=10)
    params = EngineParams.lossless(sp, 3, geom.max_degree, spec_width=8)
    arrivals = np.random.default_rng(3).integers(0, 15, queries.shape[0])

    def run(chunk, inj=injit):
        _, _, st = stream_search(consts, geom, params, entry, queries,
                                 num_slots=3, arrivals=arrivals,
                                 dynamic_spec=dynamic, round_chunk=chunk,
                                 injit_admit=inj)
        return st

    base = run(1, inj=False)
    for chunk in (3, 8):
        st = run(chunk)
        assert _result_records(st) == _result_records(base)
        assert st.total_rounds == base.total_rounds
        assert st.occupancy_trace == base.occupancy_trace
        assert st.spec_trace == base.spec_trace
        assert st.host_dispatches < base.host_dispatches


def test_injit_admission_drops_dispatches(ds):
    """The device-side pending queue deletes the stop-on-finish early
    exits and arrival-capped budgets: at the same round_chunk the
    in-jit path must reproduce the host-admission schedule bit-exactly
    with strictly fewer host dispatches (the tentpole claim), and the
    chunk must actually run multiple rounds per dispatch while the
    queue drains."""
    db, queries, packed = ds
    consts, geom, entry = pack_for_engine(packed)
    sp = SearchParams(L=16, W=1, k=10)
    params = EngineParams.lossless(sp, 3, geom.max_degree, spec_width=8)
    arrivals = np.random.default_rng(3).integers(0, 15, queries.shape[0])

    def run(inj):
        _, _, st = stream_search(consts, geom, params, entry, queries,
                                 num_slots=3, arrivals=arrivals,
                                 round_chunk=8, injit_admit=inj)
        return st

    st_on, st_off = run(True), run(False)
    assert _result_records(st_on) == _result_records(st_off)
    assert st_on.total_rounds == st_off.total_rounds
    assert st_on.occupancy_trace == st_off.occupancy_trace
    assert st_on.host_dispatches < st_off.host_dispatches
    # with continuous arrivals the queue keeps slots busy: dispatches
    # approach total_rounds / K instead of one-per-finish
    assert (st_on.total_rounds / st_on.host_dispatches
            > st_off.total_rounds / st_off.host_dispatches)


def test_chunked_frozen_matches_per_round(ds):
    """The frozen-batch discipline chunks too (waves break chunks via
    the in-jit all-done exit), keeping the exact schedule."""
    db, queries, packed = ds
    consts, geom, entry = pack_for_engine(packed)
    sp = SearchParams(L=16, W=1, k=10)
    params = EngineParams.lossless(sp, 2, geom.max_degree)

    def run(chunk):
        _, _, st = stream_search(consts, geom, params, entry,
                                 queries[:16], num_slots=2, refill=False,
                                 round_chunk=chunk)
        return st

    base, chunked = run(1), run(8)
    assert _result_records(chunked) == _result_records(base)
    assert chunked.total_rounds == base.total_rounds
    assert chunked.occupancy_trace == base.occupancy_trace
    assert chunked.host_dispatches < base.host_dispatches


# ---------------------------------------------------------------------------
# Retire/refill slot reuse: stale state must be fully reset
# ---------------------------------------------------------------------------
def test_admit_resets_slot_state(ds):
    """A slot that served query A and is re-admitted with query B must
    carry no trace of A: candidate list, expanded flags, bloom and the
    per-query counters all restart from the fresh-init values."""
    db, queries, packed = ds
    consts, geom, entry = pack_for_engine(packed)
    sp = SearchParams(L=16, W=1, k=10)
    params = EngineParams.lossless(sp, 2, geom.max_degree)
    S = geom.num_shards
    qA = jnp.asarray(np.tile(queries[0], (S, 2, 1)))
    qB = jnp.asarray(np.tile(queries[1], (S, 2, 1)))

    state = engine_init(consts, qA, *entry, params=params, geom=geom)
    for _ in range(5):   # pollute the pool with A's progress
        state = engine_round(consts, state, qA, 0, params=params, geom=geom)
    assert int(np.asarray(state.n_dist).sum()) > 0

    mask = jnp.ones((S, 2), bool)
    readmit, qbuf = engine_admit(state, qA, mask, qB, *entry,
                                 params=params, geom=geom)
    fresh = engine_init(consts, qB, *entry, params=params, geom=geom)
    for leaf_r, leaf_f, name in zip(readmit, fresh, state._fields):
        if name in ("items_recv", "distance_lanes", "pages_unique", "drops_b",
                    "props_sent"):
            continue   # shard-cumulative counters survive by design
        np.testing.assert_array_equal(np.asarray(leaf_r),
                                      np.asarray(leaf_f), err_msg=name)
    np.testing.assert_array_equal(np.asarray(qbuf), np.asarray(qB))


def test_slot_reuse_end_to_end(ds):
    """num_slots=1 forces every query through the same slot row."""
    db, queries, packed = ds
    consts, geom, entry = pack_for_engine(packed)
    sp = SearchParams(L=16, W=1, k=10)
    ref_i, ref_d = _oneshot(consts, geom, entry, queries[:8], sp)
    params = EngineParams.lossless(sp, 1, geom.max_degree)
    ids, dists, st = stream_search(consts, geom, params, entry,
                                   queries[:8], num_slots=1)
    np.testing.assert_array_equal(ids, ref_i)
    np.testing.assert_array_equal(dists, ref_d)
    # more queries than pool rows (S shards x 1 slot): rows were reused
    assert len(st.results) > packed.geometry.num_shards


# ---------------------------------------------------------------------------
# Scheduler behaviour: refill occupancy, frozen baseline, controller
# ---------------------------------------------------------------------------
def test_refill_beats_frozen_occupancy(ds):
    db, queries, packed = ds
    consts, geom, entry = pack_for_engine(packed)
    sp = SearchParams(L=16, W=1, k=10)
    params = EngineParams.lossless(sp, 2, geom.max_degree)
    _, _, st_refill = stream_search(consts, geom, params, entry, queries,
                                    num_slots=2)
    _, _, st_frozen = stream_search(consts, geom, params, entry, queries,
                                    num_slots=2, refill=False)
    assert st_refill.occupancy > st_frozen.occupancy
    assert st_refill.total_rounds <= st_frozen.total_rounds


def test_dynamic_spec_reduces_pages_same_recall():
    """On the clustered serving workload (the bench_serving --smoke
    config) the per-query controller reads no more pages than the
    static spec_max run, at recall within 2pt."""
    from repro.data.vectors import VectorDataset

    ds = VectorDataset("sched-dyn", n=2048, dim=48, clusters=16, seed=0)
    db = ds.materialize()
    queries = ds.queries(48, seed=1)
    adj, medoid = build_vamana(db, r=16, seed=0)
    geo = Geometry(num_shards=4, page_size=64, pages_per_block=4, dim=48)
    packed = pack_index(
        LUNCSR.from_adjacency(db, adj, geo, entry=medoid, pref_width=8),
        max_degree=16)
    consts, geom, entry = pack_for_engine(packed)
    sp = SearchParams(L=32, W=1, k=10)
    params = EngineParams.lossless(sp, 4, geom.max_degree, spec_width=8)
    ids_s, _, st_s = stream_search(consts, geom, params, entry, queries,
                                   num_slots=4)
    ids_d, _, st_d = stream_search(consts, geom, params, entry, queries,
                                   num_slots=4, dynamic_spec=True)
    assert st_d.pages_unique <= st_s.pages_unique
    true_i, _ = brute_force_topk(db, queries, 10)
    assert (recall_at_k(ids_d, true_i)
            >= recall_at_k(ids_s, true_i) - 0.02)
    # the controller actually moved widths (not pinned at spec_max)
    assert min(st_d.spec_trace) < params.spec_width


def test_spec_controller_bounds():
    ctrl = SpecController(spec_max=8, W=1, max_degree=12)
    worked = np.ones((2, 3), bool)
    w = ctrl.update(np.full((2, 3), 20), worked)
    assert (w == 8).all()                    # fresh frontier: full width
    for _ in range(8):                       # acceptance collapses ...
        w = ctrl.update(np.zeros((2, 3)), worked)
        assert ((w >= 0) & (w <= 8)).all()
    assert (ctrl.spec_w == 0).all()          # ... width ramps to 0
    ctrl.reset_rows(np.asarray([[True, False, False],
                                [False, False, False]]))
    assert ctrl.spec_w[0, 0] == 8            # fresh query at full width
    assert ctrl.spec_w[1, 1] == 0


def test_spec_controller_normalizes_by_used_width():
    """The docstring formula: hit = accepted / (W * (max_degree +
    spec_w_used)) — `update` must normalize by the widths that were
    used in the round (read before being overwritten), the ordering
    contract the in-jit chunk port relies on."""
    ctrl = SpecController(spec_max=8, W=2, max_degree=12)
    worked = np.ones((1, 1), bool)
    served_at_max = 2 * (12 + 8)
    # full acceptance at the used width -> hit 1.0 -> stays at max
    w = ctrl.update(np.full((1, 1), served_at_max), worked)
    assert w[0, 0] == 8 and ctrl._hit[0, 0] == pytest.approx(1.0)
    # width moved: the next update must normalize by the *new* width.
    # Feed zero so width drops, then full-acceptance-at-width-0 counts.
    ctrl.update(np.zeros((1, 1)), worked)
    used = int(ctrl.spec_w[0, 0])
    assert used < 8
    before = ctrl._hit[0, 0]
    ctrl.update(np.full((1, 1), 2 * (12 + used)), worked)
    # a full hit at the smaller served width reads as rate 1.0
    assert ctrl._hit[0, 0] == pytest.approx(0.5 * before + 0.5 * 1.0)


# ---------------------------------------------------------------------------
# Serving-metrics regressions: empty runs, compile accounting
# ---------------------------------------------------------------------------
def test_stream_summary_empty_run(ds):
    """A run that retires zero queries (0-query stream_search) must
    produce a zeroed summary, not an np.percentile crash."""
    from repro.core.metrics import latency_percentiles, stream_summary

    assert latency_percentiles([]) == {"p50": 0.0, "p95": 0.0,
                                       "p99": 0.0, "mean": 0.0}
    db, queries, packed = ds
    consts, geom, entry = pack_for_engine(packed)
    sp = SearchParams(L=16, W=1, k=10)
    params = EngineParams.lossless(sp, 2, geom.max_degree)
    ids, dists, st = stream_search(
        consts, geom, params, entry,
        np.zeros((0, queries.shape[1]), np.float32), num_slots=2)
    assert ids.shape == (0, 10) and dists.shape == (0, 10)
    summ = stream_summary(st)
    assert summ["queries"] == 0
    assert summ["sustained_qps"] == 0.0
    assert summ["dispatches_per_query"] == 0.0
    assert summ["latency_rounds"]["p99"] == 0.0
    assert summ["wall_latency_ms"]["p99"] == 0.0


def test_stream_wall_excludes_compile(ds):
    """The stepper warmup keeps the one-time jit compile out of wall_s
    and the first queries' wall latency; compile_s is reported
    separately in stream_summary."""
    from repro.core.metrics import stream_summary

    db, queries, packed = ds
    consts, geom, entry = pack_for_engine(packed)
    sp = SearchParams(L=16, W=1, k=10)
    params = EngineParams.lossless(sp, 2, geom.max_degree)
    _, _, st = stream_search(consts, geom, params, entry, queries[:8],
                             num_slots=2, round_chunk=4)
    assert st.compile_s >= 0.0
    assert st.wall_s > 0.0
    summ = stream_summary(st)
    assert summ["compile_s"] == round(st.compile_s, 3)
    assert summ["host_dispatches"] == st.host_dispatches > 0
    # wall latencies are steady-state: no query's admit->retire span
    # can exceed the whole steady-state run
    assert max(r.wall_latency_s for r in st.results) <= st.wall_s + 0.5


@pytest.mark.parametrize("injit,chunk", [(False, 1), (False, 8),
                                         (True, 1), (True, 8)])
def test_idle_rounds_stay_on_the_clock(ds, injit, chunk):
    """Two bursts separated by a long gap: the pool drains, the
    scheduler jumps the clock to the second burst, and the skipped
    rounds must be counted (idle_rounds) — occupancy and
    queries_per_round read over the full serving clock, not just the
    busy rounds (which would overstate both under sparse arrivals).
    Every admission/chunking path must account the same idle gap."""
    from repro.core.metrics import stream_summary

    db, queries, packed = ds
    consts, geom, entry = pack_for_engine(packed)
    sp = SearchParams(L=16, W=1, k=10)
    params = EngineParams.lossless(sp, 2, geom.max_degree)
    nq = 16
    arrivals = np.concatenate([np.zeros(nq // 2, np.int64),
                               np.full(nq // 2, 500, np.int64)])
    _, _, st = stream_search(consts, geom, params, entry, queries[:nq],
                             num_slots=2, arrivals=arrivals,
                             round_chunk=chunk, injit_admit=injit)
    assert st.idle_rounds > 0
    clock = st.total_rounds + st.idle_rounds
    # the serving clock spans the gap to the second burst
    assert clock >= 500
    busy_only = sum(st.occupancy_trace) / max(
        len(st.occupancy_trace) * geom.num_shards * 2, 1)
    assert st.occupancy < busy_only      # idle time dilutes occupancy
    assert st.occupancy == pytest.approx(
        sum(st.occupancy_trace) / (clock * geom.num_shards * 2))
    summ = stream_summary(st)
    assert summ["idle_rounds"] == st.idle_rounds
    assert summ["queries_per_round"] == round(nq / clock, 3)
    # second-burst queries were admitted on the post-gap clock
    by_qid = st.by_qid()
    assert all(by_qid[q].admit_round >= 500 for q in range(nq // 2, nq))
    # the idle accounting is schedule-invariant: per-round host
    # admission sees the identical gap
    _, _, base = stream_search(consts, geom, params, entry, queries[:nq],
                               num_slots=2, arrivals=arrivals,
                               round_chunk=1, injit_admit=False)
    assert st.idle_rounds == base.idle_rounds
    assert st.total_rounds == base.total_rounds


def test_stream_summary_covers_stats_fields(ds):
    """Every scalar StreamStats field must surface in stream_summary —
    the report silently dropped props_sent once; freeze the contract so
    the next added counter can't be dropped."""
    import dataclasses

    from repro.core.metrics import stream_summary
    from repro.core.scheduler import StreamStats

    db, queries, packed = ds
    consts, geom, entry = pack_for_engine(packed)
    sp = SearchParams(L=16, W=1, k=10)
    params = EngineParams.lossless(sp, 2, geom.max_degree)
    _, _, st = stream_search(consts, geom, params, entry, queries[:8],
                             num_slots=2)
    summ = stream_summary(st)
    per_round_lists = {"results", "occupancy_trace", "spec_trace"}
    for f in dataclasses.fields(StreamStats):
        if f.name in per_round_lists:
            continue
        assert f.name in summ, (
            f"stream_summary dropped StreamStats.{f.name}")
    assert summ["props_sent"] == st.props_sent > 0
    # robustness counters are part of the frozen contract (and a clean
    # run must report them at rest)
    assert summ["shed"] == 0 and summ["truncated"] == 0
    assert summ["quarantined"] == 0 and summ["legs_fused_hist"] == []
    assert summ["goodput"] == 1.0
    # tiered-page-store counters joined the frozen contract: an
    # untiered run reports them at rest (fully resident, no stalls)
    assert summ["stalls"] == 0 and summ["stall_rounds_per_query"] == 0.0
    assert summ["prefetch_hits"] == 0 and summ["prefetch_issued"] == 0
    assert summ["prefetch_hit_rate"] == 0.0
    assert summ["resident_fraction"] == 1.0
    # live-index counters joined the frozen contract: a frozen-index
    # run reports them at rest (no delta, no deletes, no swaps)
    assert summ["delta_hits"] == 0 and summ["tombstoned"] == 0
    assert summ["epoch_swaps"] == 0 and summ["swap_stall_rounds"] == 0


def test_goodput_counts_each_query_once():
    """Goodput regression: a query that is both truncated and had
    quarantined distances is still exactly one non-clean retirement —
    `truncated` is a per-result flag and `quarantined` counts corrupt
    distance lanes, so neither can double-count a query in the goodput
    denominator (retired clean / offered, offered = retired + shed)."""
    import dataclasses

    from repro.core.metrics import stream_summary
    from repro.core.scheduler import QueryResult, StreamStats

    def qr(qid, truncated):
        return QueryResult(
            qid=qid, ids=np.zeros(4, np.int32),
            dists=np.zeros(4, np.float32), arrival_round=0,
            admit_round=0, retire_round=5, service_rounds=5, n_dist=10,
            wall_latency_s=0.1, truncated=truncated)

    # 4 retired (1 truncated — the same query also tripped the
    # quarantine guard twice) + 2 shed: offered = 6, clean = 3
    st = StreamStats(
        results=[qr(0, False), qr(1, True), qr(2, False), qr(3, False)],
        total_rounds=10, occupancy=0.5, occupancy_trace=[],
        pages_unique=1, items_recv=1, props_sent=1, drops_b=0,
        spec_trace=[], wall_s=1.0, shed=2, truncated=1, quarantined=2)
    summ = stream_summary(st)
    assert summ["goodput"] == round(3 / 6, 4)
    # quarantined distances never enter the denominator: only
    # retirement (once per query) and shed do
    st2 = dataclasses.replace(st, quarantined=10**6)
    assert stream_summary(st2)["goodput"] == summ["goodput"]


def test_default_leg_l_tracks_shard_depth():
    """The routed per-leg list length derives from per-shard graph
    depth (k + 2*ceil(log_deg n_shard)) — monotone in shard size,
    shrinking in graph degree, independent of the global L."""
    from repro.core.scheduler import default_leg_L

    assert default_leg_L(128, 8, 8) == 8 + 2 * 3
    assert default_leg_L(256, 16, 10) == 10 + 2 * 2
    # monotone non-decreasing in n_shard at fixed degree/k
    vals = [default_leg_L(n, 8, 8) for n in (2, 64, 512, 4096, 2**15)]
    assert vals == sorted(vals)
    # deeper graphs (smaller degree) need longer lists
    assert default_leg_L(4096, 4, 8) > default_leg_L(4096, 32, 8)
    # degenerate sizes stay sane: at least k result seats + headroom
    assert default_leg_L(1, 2, 5) >= 5
    assert default_leg_L(1, 1, 5) >= 5


def test_routed_leg_l_override_wins(ds):
    """An explicit leg_L must override the auto default: the two runs
    differ observably (per-leg list length bounds n_dist), and the
    explicit value reproduces itself bit for bit."""
    from repro.core.router import build_routed_index
    from repro.core.scheduler import routed_stream_search

    rng = np.random.default_rng(3)
    n, d, S = 512, 16, 4
    db = rng.standard_normal((n, d)).astype(np.float32)
    queries = rng.standard_normal((6, d)).astype(np.float32)
    ri = build_routed_index(db, shards=S, page_size=16, r=8, seed=0)
    consts, geom, entry = pack_for_engine(ri.packed)
    sp = SearchParams(L=16, W=1, k=4)
    params = EngineParams.lossless(sp, 2, ri.packed.max_degree)

    def run(leg_l):
        ids, dists, st = routed_stream_search(
            consts, geom, params, entry, queries, router=ri.router,
            topr=2, num_slots=2, shard_entries=ri.shard_entries,
            leg_L=leg_l)
        return (np.asarray(ids), np.asarray(dists),
                sum(r.n_dist for r in st.results))

    auto_i, auto_d, auto_nd = run(None)
    big_i, big_d, big_nd = run(16)
    # the override took effect: a 16-entry leg list does strictly more
    # distance work than the auto default (k + 2*depth < 16 here)
    assert big_nd > auto_nd
    # and the explicit value is reproducible
    again_i, again_d, again_nd = run(16)
    np.testing.assert_array_equal(big_i, again_i)
    np.testing.assert_array_equal(big_d, again_d)
    assert big_nd == again_nd


def test_poisson_arrivals_rounds_half_up():
    """poisson_arrivals must round the cumulative gaps, not floor them
    (flooring shifts every arrival ~0.5 rounds early, biasing the
    realized rate above the requested one): the integer clock must sit
    within half a round of the exact float clock on average, and the
    realized mean rate must match the request over a long horizon."""
    from repro.core.scheduler import poisson_arrivals

    rate, n, seed = 0.25, 4096, 7
    arr = poisson_arrivals(rate, n, seed=seed)
    assert arr.dtype == np.int64 and (np.diff(arr) >= 0).all()
    # same rng stream as the implementation -> the exact float clock
    exact = np.cumsum(
        np.random.default_rng(seed).exponential(1.0 / rate, n))
    err = (arr - exact).mean()
    assert abs(err) < 0.05, f"biased clock: mean shift {err:.3f}"
    realized = n / arr[-1]
    assert abs(realized - rate) / rate < 0.02, (
        f"realized rate {realized:.4f} != requested {rate}")
    assert poisson_arrivals(0.0, 5).tolist() == [0] * 5


def test_stats_shapes_unified(ds):
    """total_rounds is per-shard (S,) in the sim driver (matching the
    distributed driver) so consumers never special-case."""
    db, queries, packed = ds
    consts, geom, entry = pack_for_engine(packed)
    sp = SearchParams(L=16, W=1, k=10)
    S = geom.num_shards
    params = EngineParams.lossless(sp, queries.shape[0] // S,
                                   geom.max_degree)
    qsh = jnp.asarray(queries.reshape(S, -1, queries.shape[1]))
    _, _, stats = search_sim(consts, qsh, *entry, params, geom)
    assert np.asarray(stats["total_rounds"]).shape == (S,)
    assert (np.asarray(stats["total_rounds"])
            == np.asarray(stats["total_rounds"])[0]).all()


def test_engine_retire_matches_search_sim_finalize(ds):
    """Stepping rounds manually + engine_retire == search_sim."""
    db, queries, packed = ds
    consts, geom, entry = pack_for_engine(packed)
    sp = SearchParams(L=16, W=1, k=10)
    S = geom.num_shards
    nq = queries.shape[0]
    params = EngineParams.lossless(sp, nq // S, geom.max_degree)
    qsh = jnp.asarray(queries.reshape(S, nq // S, -1))
    ref_i, ref_d, ref_stats = search_sim(consts, qsh, *entry, params, geom)

    stepper = make_stepper(params, geom)
    state = stepper.init(consts, qsh, *entry)
    t = 0
    while (~np.asarray(state.done)).any() and t < sp.rounds_cap:
        state = stepper.round(consts, state, qsh, params.spec_width)
        t += 1
    out_i, out_d, stats = stepper.retire(state)
    np.testing.assert_array_equal(np.asarray(out_i), np.asarray(ref_i))
    np.testing.assert_array_equal(np.asarray(out_d), np.asarray(ref_d))
    np.testing.assert_array_equal(np.asarray(stats["rounds"]),
                                  np.asarray(ref_stats["rounds"]))
    assert t == int(np.asarray(ref_stats["total_rounds"])[0])


def test_stream_kernel_mode_ref_bitexact(ds):
    """The scheduler composes with the kernel backend: ref mode streams
    bit-identically to the inline jnp one-shot driver."""
    db, queries, packed = ds
    consts, geom, entry = pack_for_engine(packed)
    sp = SearchParams(L=16, W=1, k=10)
    ref_i, ref_d = _oneshot(consts, geom, entry, queries[:16], sp)
    params = EngineParams.lossless(sp, 4, geom.max_degree,
                                   kernel_mode="ref")
    ids, dists, _ = stream_search(consts, geom, params, entry,
                                  queries[:16], num_slots=4)
    np.testing.assert_array_equal(ids, ref_i)
    np.testing.assert_array_equal(dists, ref_d)


# ---------------------------------------------------------------------------
# Robustness: deadlines, bounded admission ring, overload policies, faults
# ---------------------------------------------------------------------------
def _robust_params(sp, slots, geom, **kw):
    import dataclasses

    return dataclasses.replace(
        EngineParams.lossless(sp, slots, geom.max_degree), **kw)


@pytest.mark.parametrize("injit", [False, True])
def test_deadline_force_retires(ds, injit):
    """Every query retires at most deadline_rounds after admission,
    flagged truncated with finite best-so-far results, on both the
    host-paced and in-jit admission paths."""
    db, queries, packed = ds
    consts, geom, entry = pack_for_engine(packed)
    sp = SearchParams(L=16, W=1, k=10)
    params = _robust_params(sp, 2, geom, deadline_rounds=3)
    ids, dists, st = stream_search(consts, geom, params, entry,
                                   queries[:16], num_slots=2,
                                   round_chunk=8, injit_admit=injit)
    assert len(st.results) == 16
    assert st.truncated == 16      # 3 rounds is far below convergence
    for r in st.results:
        assert r.truncated
        assert r.retire_round - r.admit_round == 3
        assert r.service_rounds == 3
        # best-so-far top-k, not garbage: the entry point at least
        assert (r.ids != INVALID).any()
        assert np.isfinite(r.dists[r.ids != INVALID]).all()


@pytest.mark.parametrize("injit", [False, True])
def test_deadline_off_bit_identity(ds, injit):
    """A deadline no query ever reaches is bit-identical to no
    deadline at all — the whole deadline column is pure plumbing until
    it fires (schedule, traces and accounting included)."""
    db, queries, packed = ds
    consts, geom, entry = pack_for_engine(packed)
    sp = SearchParams(L=16, W=1, k=10)
    arrivals = np.random.default_rng(5).integers(0, 12, 16)

    def run(params):
        _, _, st = stream_search(consts, geom, params, entry,
                                 queries[:16], num_slots=3,
                                 arrivals=arrivals, round_chunk=8,
                                 injit_admit=injit)
        return st

    base = run(EngineParams.lossless(sp, 3, geom.max_degree))
    huge = run(_robust_params(sp, 3, geom, deadline_rounds=10**6))
    assert _result_records(huge) == _result_records(base)
    assert huge.total_rounds == base.total_rounds
    assert huge.occupancy_trace == base.occupancy_trace
    assert huge.truncated == 0


def test_ring_full_capacity_bit_identity(ds):
    """A ring holding the whole stream reproduces the unbounded staging
    path exactly: schedule, traces, accounting — the sliding window at
    C >= N is the stage-everything path by construction."""
    db, queries, packed = ds
    consts, geom, entry = pack_for_engine(packed)
    sp = SearchParams(L=16, W=1, k=10)
    params = EngineParams.lossless(sp, 3, geom.max_degree)
    arrivals = np.random.default_rng(6).integers(0, 15, queries.shape[0])

    def run(ring):
        _, _, st = stream_search(consts, geom, params, entry, queries,
                                 num_slots=3, arrivals=arrivals,
                                 round_chunk=8, ring_capacity=ring)
        return st

    base = run(0)
    ringed = run(queries.shape[0])
    assert _result_records(ringed) == _result_records(base)
    assert ringed.total_rounds == base.total_rounds
    assert ringed.occupancy_trace == base.occupancy_trace
    assert ringed.shed == 0


def test_ring_block_property_any_capacity(ds):
    """Hypothesis: under the block policy, any ring capacity >= 1
    serves every query with bit-identical per-query results (admission
    order is arrival order either way; the window only bounds device
    memory, adding backpressure rounds at worst)."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    db, queries, packed = ds
    consts, geom, entry = pack_for_engine(packed)
    sp = SearchParams(L=8, W=1, k=5)
    nq = 12
    q = queries[:nq]
    params = EngineParams.lossless(sp, 2, geom.max_degree)
    arrivals = np.random.default_rng(9).integers(0, 8, nq)
    ref_i, ref_d, ref_st = stream_search(
        consts, geom, params, entry, q, num_slots=2, arrivals=arrivals,
        round_chunk=8)

    @given(st.integers(1, nq + 4))
    @settings(max_examples=8, deadline=None)
    def check(ring):
        ids, dists, stx = stream_search(
            consts, geom, params, entry, q, num_slots=2,
            arrivals=arrivals, round_chunk=8, ring_capacity=ring,
            overload="block")
        np.testing.assert_array_equal(ids, ref_i)
        np.testing.assert_array_equal(dists, ref_d)
        assert stx.shed == 0 and len(stx.results) == nq

    check()


def test_ring_shed_overload(ds):
    """Shed policy under a burst far beyond ring capacity: overflow
    queries are rejected and counted, every admitted query still
    retires with exact results, and shed + retired covers the stream.
    Shed queries keep INVALID rows in the wrapper output."""
    db, queries, packed = ds
    consts, geom, entry = pack_for_engine(packed)
    sp = SearchParams(L=16, W=1, k=10)
    params = EngineParams.lossless(sp, 1, geom.max_degree)
    nq = queries.shape[0]
    arrivals = np.zeros(nq, np.int64)          # one burst at round 0
    ids, dists, st = stream_search(consts, geom, params, entry, queries,
                                   num_slots=1, arrivals=arrivals,
                                   round_chunk=8, ring_capacity=4,
                                   overload="shed")
    assert st.shed > 0
    assert st.shed + len(st.results) == nq
    served = {r.qid for r in st.results}
    ref_i, ref_d, _ = stream_search(consts, geom, params, entry, queries,
                                    num_slots=1, arrivals=arrivals,
                                    round_chunk=8)
    for r in st.results:     # admitted queries are exact
        np.testing.assert_array_equal(r.ids, ref_i[r.qid])
    for qid in range(nq):
        if qid not in served:
            assert (ids[qid] == INVALID).all()


def test_ring_validation(ds):
    """Ring knobs are validated at construction: bad policy names, the
    host-paced path and routed serving are all rejected."""
    from repro.core.scheduler import StreamScheduler

    db, queries, packed = ds
    consts, geom, entry = pack_for_engine(packed)
    sp = SearchParams(L=16, W=1, k=10)
    params = EngineParams.lossless(sp, 2, geom.max_degree)
    with pytest.raises(ValueError, match="overload"):
        StreamScheduler(consts, geom, params, entry, num_slots=2,
                        overload="panic")
    with pytest.raises(ValueError, match="in-jit"):
        StreamScheduler(consts, geom, params, entry, num_slots=2,
                        injit_admit=False, ring_capacity=4)
    with pytest.raises(ValueError, match="routed"):
        StreamScheduler(consts, geom, params, entry, num_slots=2,
                        routed=True, ring_capacity=4)


def test_fault_kill_shard_retires_all(ds):
    """Kill one shard mid-run (with a deadline): every query still
    retires — rows on the dead shard age to the deadline and force-
    retire truncated; rows elsewhere finish clean and bit-exact."""
    db, queries, packed = ds
    consts, geom, entry = pack_for_engine(packed)
    from repro.ft.inject import fault_plan

    sp = SearchParams(L=16, W=1, k=10)
    nq = 16
    clean = EngineParams.lossless(sp, 2, geom.max_degree)
    ref_i, _, ref_st = stream_search(consts, geom, clean, entry,
                                     queries[:nq], num_slots=2,
                                     round_chunk=8)
    # a deadline no healthy query reaches: only stalled rows truncate
    dl = max(r.service_rounds for r in ref_st.results) + 4
    faults = fault_plan(geom.num_shards).kill(1, 4)
    params = _robust_params(sp, 2, geom, deadline_rounds=dl,
                            faults=faults)
    ids, dists, st = stream_search(consts, geom, params, entry,
                                   queries[:nq], num_slots=2,
                                   round_chunk=8)
    assert len(st.results) == nq               # nothing hangs
    assert 0 < st.truncated < nq               # shard 1's rows only
    for r in st.results:
        if r.truncated:
            # aged on the serving clock to the deadline while stalled
            assert r.retire_round - r.admit_round == dl
            assert r.service_rounds < dl
        else:
            np.testing.assert_array_equal(r.ids, ref_i[r.qid])


def test_fault_delay_is_transparent(ds):
    """A transient stall preserves traversal state: results are
    bit-identical to the healthy run, only the stalled rows' serving-
    clock latency grows by the delay."""
    db, queries, packed = ds
    consts, geom, entry = pack_for_engine(packed)
    from repro.ft.inject import fault_plan

    sp = SearchParams(L=16, W=1, k=10)
    nq = 16
    clean = EngineParams.lossless(sp, 2, geom.max_degree)
    ref_i, ref_d, ref_st = stream_search(consts, geom, clean, entry,
                                         queries[:nq], num_slots=2,
                                         round_chunk=8)
    faults = fault_plan(geom.num_shards).delay(0, 2, 5)
    params = _robust_params(sp, 2, geom, faults=faults)
    ids, dists, st = stream_search(consts, geom, params, entry,
                                   queries[:nq], num_slots=2,
                                   round_chunk=8)
    np.testing.assert_array_equal(ids, ref_i)
    np.testing.assert_array_equal(dists, ref_d)
    assert st.truncated == 0
    lat = {r.qid: r.latency_rounds for r in st.results}
    ref_lat = {r.qid: r.latency_rounds for r in ref_st.results}
    assert all(lat[q] >= ref_lat[q] for q in lat)
    assert any(lat[q] > ref_lat[q] for q in lat)   # someone stalled
    svc = {r.qid: r.service_rounds for r in st.results}
    ref_svc = {r.qid: r.service_rounds for r in ref_st.results}
    assert svc == ref_svc        # worked rounds unchanged by the stall


def test_fault_corruption_guard(ds):
    """Deterministic page corruption + guard: corrupt reads are
    quarantined and counted, outputs stay finite, every query retires.
    The same plan without the guard is the negative control: garbage
    reaches the results."""
    db, queries, packed = ds
    consts, geom, entry = pack_for_engine(packed)
    from repro.ft.inject import fault_plan

    sp = SearchParams(L=16, W=1, k=10)
    nq = 16
    faults = fault_plan(geom.num_shards).corrupt(0.08, "neg", seed=3)
    guarded = _robust_params(sp, 2, geom, faults=faults,
                             guard_nonfinite=True)
    ids, dists, st = stream_search(consts, geom, guarded, entry,
                                   queries[:nq], num_slots=2,
                                   round_chunk=8)
    assert len(st.results) == nq
    assert st.quarantined > 0
    assert np.isfinite(dists[ids != INVALID]).all()
    assert (dists[ids != INVALID] >= 0).all()     # no negative garbage
    unguarded = _robust_params(sp, 2, geom, faults=faults)
    _, dists_u, st_u = stream_search(consts, geom, unguarded, entry,
                                     queries[:nq], num_slots=2,
                                     round_chunk=8)
    assert st_u.quarantined == 0
    assert (np.asarray(dists_u) < 0).any()        # garbage got through


def test_guard_identity_on_clean_data(ds):
    """guard_nonfinite on clean data is the identity — the quarantine
    predicate never fires, results and accounting are bit-identical."""
    db, queries, packed = ds
    consts, geom, entry = pack_for_engine(packed)
    sp = SearchParams(L=16, W=1, k=10)
    nq = 16
    base_p = EngineParams.lossless(sp, 2, geom.max_degree)
    ref_i, ref_d, base = stream_search(consts, geom, base_p, entry,
                                       queries[:nq], num_slots=2,
                                       round_chunk=8)
    guarded = _robust_params(sp, 2, geom, guard_nonfinite=True)
    ids, dists, st = stream_search(consts, geom, guarded, entry,
                                   queries[:nq], num_slots=2,
                                   round_chunk=8)
    np.testing.assert_array_equal(ids, ref_i)
    np.testing.assert_array_equal(dists, ref_d)
    assert st.quarantined == 0
    assert _result_records(st) == _result_records(base)


def test_fault_validation(ds):
    """Hazardous fault configs are rejected up front: a kill with no
    deadline would hang the host loop; stalls need the in-jit serving
    clock; a spec sized for the wrong mesh is caught."""
    from repro.core.scheduler import StreamScheduler
    from repro.ft.inject import fault_plan

    db, queries, packed = ds
    consts, geom, entry = pack_for_engine(packed)
    sp = SearchParams(L=16, W=1, k=10)
    S = geom.num_shards
    kill = fault_plan(S).kill(0, 5)
    params = _robust_params(sp, 2, geom, faults=kill)
    with pytest.raises(ValueError, match="deadline"):
        StreamScheduler(consts, geom, params, entry, num_slots=2)
    ok = _robust_params(sp, 2, geom, faults=kill, deadline_rounds=8)
    with pytest.raises(ValueError, match="in-jit"):
        StreamScheduler(consts, geom, ok, entry, num_slots=2,
                        injit_admit=False)
    wrong = _robust_params(sp, 2, geom, deadline_rounds=8,
                           faults=fault_plan(S + 1).kill(0, 5))
    with pytest.raises(ValueError, match="num_shards"):
        StreamScheduler(consts, geom, wrong, entry, num_slots=2)


def test_session_compiles_stepper_exactly_once():
    """Every retire/refill/admit boundary re-dispatches the same jitted
    stepper: a staggered-arrival in-jit session must trigger exactly one
    engine_run_chunk_admit compilation (the warmup), however many chunks
    the host loop runs."""
    from repro.analysis.compile_guard import CompileGuard

    # Shapes unique to this test: jit caches are process-wide, so
    # reusing the module fixture's dims could hide (or zero) the count.
    db, queries, packed = _dataset(n=768, d=28, nq=20, S=2, page=16,
                                   seed=5)
    consts, geom, entry = pack_for_engine(packed)
    sp = SearchParams(L=12, W=1, k=8)
    params = EngineParams.lossless(sp, 2, geom.max_degree, spec_width=4)
    arrivals = np.random.default_rng(7).integers(0, 12, queries.shape[0])

    with CompileGuard() as cg:
        ids, dists, st = stream_search(
            consts, geom, params, entry, queries, num_slots=2,
            arrivals=arrivals, round_chunk=4, injit_admit=True)

    n = cg.count("engine_run_chunk_admit")
    assert n == 1, (f"expected exactly the warmup compile, saw {n}: "
                    f"{[x for x in cg.names if 'chunk' in x]}")
    # and the one compile really amortized over a multi-chunk session
    assert st.host_dispatches > 1
    assert st.total_rounds > 4
    assert len(st.results) == queries.shape[0]


@pytest.mark.parametrize("kernel_mode,qb", [("ref", 8), ("jnp", 8)])
def test_distance_lanes_counts_tiles_per_round(ds, kernel_mode, qb):
    """StreamStats.distance_lanes (and stream_summary's) is the sim
    round's static distance lanes — tiles x tile width of the one
    distance call over all shards' proposals, or one lane per proposal
    inline — times the rounds stepped; items_recv never exceeds it."""
    from repro.core.metrics import stream_summary
    from repro.kernels.distance.ops import coalesce_num_tiles

    db, queries, packed = ds
    consts, geom, entry = pack_for_engine(packed)
    sp = SearchParams(L=16, W=1, k=10)
    slots = 4
    params = EngineParams.lossless(sp, slots, geom.max_degree,
                                   kernel_mode=kernel_mode, coalesce_qb=qb)
    arrivals = np.random.default_rng(3).integers(0, 10, queries.shape[0])
    _, _, st = stream_search(consts, geom, params, entry, queries,
                             num_slots=slots, arrivals=arrivals,
                             round_chunk=4)
    S, NP = geom.num_shards, geom.pages_per_shard
    items = S * slots * sp.W * geom.max_degree
    if kernel_mode == "jnp":
        per_round = items
    else:
        assert items >= 2 * S * NP          # the coalesced tiles engage
        per_round = coalesce_num_tiles(items, S * NP, qb) * qb
    assert st.total_rounds > 0
    assert st.distance_lanes == per_round * st.total_rounds
    assert 0 < st.items_recv <= st.distance_lanes
    assert stream_summary(st)["distance_lanes"] == st.distance_lanes
