"""Tiered page store: full-residency bit-identity, the resident answers
at every residency, eviction metadata consistency, stall accounting and
prefetch-hit attribution (core/pagestore.py + the scheduler's
chunk-boundary hook)."""
import dataclasses

import numpy as np
import pytest

from repro.core.engine import EngineParams, pack_for_engine
from repro.core.graph import build_vamana
from repro.core.luncsr import Geometry, LUNCSR, pack_index
from repro.core.pagestore import PageStore
from repro.core.ref_search import SearchParams, lockstep_search_batch
from repro.core.scheduler import stream_search


def _dataset(n=1024, d=32, nq=12, S=4, page=8, seed=0):
    rng = np.random.default_rng(seed)
    db = rng.integers(-8, 9, size=(n, d)).astype(np.float32)
    queries = rng.integers(-8, 9, size=(nq, d)).astype(np.float32)
    adj, medoid = build_vamana(db, r=8, alpha=1.2, seed=seed)
    geo = Geometry(num_shards=S, page_size=page, pages_per_block=2, dim=d)
    index = LUNCSR.from_adjacency(db, adj, geo, entry=medoid, pref_width=2)
    return db, queries, pack_index(index, max_degree=8)


@pytest.fixture(scope="module")
def ds():
    return _dataset()


def _run(ds, *, pagestore=None, store=False, slots=2, chunk=2,
         arrivals=None, spec=2):
    db, queries, packed = ds
    consts, geom, entry = pack_for_engine(packed)
    sp = SearchParams(L=8, W=1, k=5)
    params = EngineParams.lossless(sp, slots, geom.max_degree,
                                   spec_width=spec)
    if store:
        params = dataclasses.replace(
            params, store_pages=consts["db"].shape[1])
    ids, dists, st = stream_search(consts, geom, params, entry, queries,
                                   num_slots=slots, round_chunk=chunk,
                                   arrivals=arrivals, pagestore=pagestore)
    return np.asarray(ids), np.asarray(dists), st


def _store(ds, device_pages, **kw):
    _, _, packed = ds
    consts, geom, _ = pack_for_engine(packed)
    return PageStore(consts, geom, device_pages, w_select=1, **kw)


def _schedule(st):
    """The observable round schedule: per-query service/retire records."""
    return {r.qid: (r.admit_round, r.retire_round, r.service_rounds,
                    r.n_dist) for r in st.results}


# ---------------------------------------------------------------------------
# Full residency (P_dev >= NP) is the identity configuration: every
# array the kernel sees is the untiered one, bit for bit
# ---------------------------------------------------------------------------
def test_full_residency_bitidentical_property(ds):
    """Hypothesis: any arrival spacing and any cache size at or above
    the page count produce results, schedule and host-dispatch count
    bit-identical to the device-resident path. Slot/chunk shapes are
    pinned to two configs so the property explores arrival orders and
    cache sizes (free) rather than stepper recompiles (seconds each);
    the slot/chunk space itself is covered by the scheduler's own
    bit-identity property."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st_

    _, queries, packed = ds
    nq = queries.shape[0]
    consts, _, _ = pack_for_engine(packed)
    NP = consts["db"].shape[1]

    @given(st_.sampled_from([(2, 2), (1, 4)]),
           st_.sampled_from([0, 3]),
           st_.lists(st_.integers(0, 6), min_size=nq, max_size=nq))
    @settings(max_examples=6, deadline=None)
    def check(shape, extra, gaps):
        slots, chunk = shape
        arrivals = np.cumsum(gaps).astype(np.int64)
        ref_i, ref_d, ref_st = _run(ds, slots=slots, chunk=chunk,
                                    arrivals=arrivals)
        ps = _store(ds, NP + extra)
        ids, dists, st = _run(ds, pagestore=ps, store=True, slots=slots,
                              chunk=chunk, arrivals=arrivals)
        np.testing.assert_array_equal(ids, ref_i)
        np.testing.assert_array_equal(dists, ref_d)
        assert st.total_rounds == ref_st.total_rounds
        assert st.host_dispatches == ref_st.host_dispatches
        assert _schedule(st) == _schedule(ref_st)
        assert st.stalls == 0
        assert all(r.stall_rounds == 0 for r in st.results)
        assert ps.counters()["page_misses"] == 0
        assert ps.counters()["demand_fetches"] == 0

    check()


@pytest.mark.parametrize("prefetch", [False, True])
def test_partial_residency_same_results_slower_clock(ds, prefetch):
    """Half the pages resident: final per-query results must still match
    the untiered path exactly (stalls delay, never corrupt), stalls
    must be counted, and every stall shows up in some query's
    stall_rounds."""
    ref_i, ref_d, _ = _run(ds)
    _, _, packed = ds
    consts, _, _ = pack_for_engine(packed)
    NP = consts["db"].shape[1]
    ps = _store(ds, NP // 2, prefetch=prefetch)
    ids, dists, st = _run(ds, pagestore=ps, store=True)
    np.testing.assert_array_equal(ids, ref_i)
    np.testing.assert_array_equal(dists, ref_d)
    assert st.stalls > 0
    assert st.stalls == sum(r.stall_rounds for r in st.results)
    c = ps.counters()
    assert c["page_misses"] > 0 and c["demand_fetches"] > 0
    if prefetch:
        assert c["prefetch_hits"] <= c["prefetch_issued"]
    else:
        assert c["prefetch_issued"] == 0 and c["prefetch_hits"] == 0


def test_stall_accounting_stretches_clock_not_service(ds):
    """stall_rounds = rounds a query aged without working: the rounds a
    query actually works (service_rounds) are exactly the untiered
    service time — a stalled round is masked, not re-done — while its
    residency span stretches by exactly its own stalls:
    retire - admit == service + stalls."""
    _, _, ref_st = _run(ds)
    _, _, packed = ds
    consts, _, _ = pack_for_engine(packed)
    NP = consts["db"].shape[1]
    ps = _store(ds, NP // 2, prefetch=False)
    _, _, st = _run(ds, pagestore=ps, store=True)
    assert st.stalls > 0
    ref_srv = {r.qid: r.service_rounds for r in ref_st.results}
    for r in st.results:
        assert r.stall_rounds >= 0
        assert r.service_rounds == ref_srv[r.qid]
        assert r.retire_round - r.admit_round == \
            r.service_rounds + r.stall_rounds


@pytest.fixture(scope="module")
def resident(ds):
    return _run(ds)


@pytest.mark.parametrize("frames", ["1", "2", "half", "all"])
def test_tiered_stream_serves_the_resident_answers(ds, resident, frames):
    """At every residency, down to one frame a shard, the tiered stream
    returns the resident run's ids and distances, and each query works
    the resident run's rounds: a stalled row keeps the distances it read
    and merges once every lane has one, so no residency livelocks. The
    vectors are integers, so a distance is exact whatever page tiles the
    kernel groups it in, and the distances are compared bit for bit.
    The longest wait at chunk boundaries is bounded by the rows' own
    stall rounds."""
    ref_i, ref_d, ref_st = resident
    NP = _store(ds, 1).num_pages
    D = {"1": 1, "2": 2, "half": NP // 2, "all": NP}[frames]
    ids, dists, st = _run(ds, pagestore=_store(ds, D), store=True)
    np.testing.assert_array_equal(ids, ref_i)
    np.testing.assert_array_equal(dists, ref_d)
    if D == NP:
        assert st.stalls == 0 and st.stall_boundaries_max == 0
    else:
        assert st.stalls > 0
    ref_srv = {r.qid: r.service_rounds for r in ref_st.results}
    assert {r.qid: r.service_rounds for r in st.results} == ref_srv
    assert st.stall_boundaries_max <= max(r.stall_rounds
                                          for r in st.results)
    if D <= 2:
        assert st.stall_boundaries_max > 0


def test_two_frames_serve_the_lockstep_answers(ds):
    """Two frames a shard, far below a round's pages: every query gets
    the numpy lockstep reference's ids and distances (integer vectors,
    so exact), as the resident engine does."""
    db, queries, packed = ds
    adj, _ = build_vamana(db, r=8, alpha=1.2, seed=0)
    sp = SearchParams(L=8, W=1, k=5)
    ref_i, ref_d, _ = lockstep_search_batch(db, adj, queries,
                                            packed.entry, sp)
    ids, dists, st = _run(ds, pagestore=_store(ds, 2), store=True,
                          spec=0)
    assert st.stalls > 0
    np.testing.assert_array_equal(ids, ref_i)
    np.testing.assert_array_equal(dists, ref_d)


def test_store_compiles_nothing_after_its_warm_up(ds):
    """The store compiles its frame scatter at every payload size when
    it is built, and a call of the served entry compiles its steppers:
    a second call at partial residency, whose boundaries fetch payloads
    of other sizes, compiles nothing."""
    from repro.analysis.compile_guard import CompileGuard

    _, queries, _ = ds
    ps = _store(ds, 2)
    _run(ds, pagestore=ps, store=True)
    before = ps.counters()["demand_fetches"]
    with CompileGuard() as guard:
        _, _, st = _run(ds, pagestore=ps, store=True,
                        arrivals=np.arange(len(queries)) * 3)
    assert ps.counters()["demand_fetches"] > before and st.stalls > 0
    assert guard.total == 0, guard.names


# ---------------------------------------------------------------------------
# Residency metadata: eviction keeps ttab <-> frame_page a bijection and
# the frame payload equal to the cold tier
# ---------------------------------------------------------------------------
def _check_consistent(ps):
    for s in range(ps.S):
        resident = np.flatnonzero(ps.ttab[s] >= 0)
        frames = ps.ttab[s, resident]
        assert len(set(frames.tolist())) == len(frames)  # injective
        assert (ps.frame_page[s, frames] == resident).all()
        occupied = np.flatnonzero(ps.frame_page[s] >= 0)
        assert set(frames.tolist()) == set(occupied.tolist())


def test_eviction_correctness(ds):
    """Demand-fetching more pages than frames forces eviction: the
    translation table stays a bijection, the demanded pages land
    resident, the displaced pages unmap, and the device frame payload
    matches the cold tier row for row."""
    _, _, packed = ds
    consts, geom, _ = pack_for_engine(packed)
    NP = consts["db"].shape[1]
    pdev = 4
    ps = PageStore(consts, geom, pdev, w_select=1, prefetch=False)
    S, Qs, L = ps.S, 2, 4
    no_cands = (np.full((S, Qs, L), -1, np.int32),
                np.zeros((S, Qs, L), bool), np.ones((S, Qs), bool))

    touch = np.zeros((S, NP), bool)
    miss = np.zeros((S, NP), bool)
    want = list(range(pdev, pdev + 3))        # 3 non-resident pages
    miss[0, want] = True
    ps.boundary(touch, miss, np.zeros_like(miss, np.int32), *no_cands)
    _check_consistent(ps)
    assert (ps.ttab[0, want] >= 0).all()      # all demanded now resident
    assert ps.counters()["demand_fetches"] == 3
    assert (ps.ttab[0] >= 0).sum() == pdev    # capacity held: 3 evicted
    for s in range(S):
        for page in np.flatnonzero(ps.ttab[s] >= 0):
            f = ps.ttab[s, page]
            np.testing.assert_array_equal(
                np.asarray(ps.frames[s, f]), ps.cold_db[s, page])
            np.testing.assert_array_equal(
                np.asarray(ps.vnf[s, f]), ps.cold_vn[s, page])

    # a page touched this chunk holds its frame (second-chance ref bit)
    touch2 = np.zeros((S, NP), bool)
    touch2[0, want[0]] = True
    miss2 = np.zeros((S, NP), bool)
    miss2[0, pdev + 3] = True                 # one more demand
    ps.boundary(touch2, miss2, np.zeros_like(miss2, np.int32), *no_cands)
    _check_consistent(ps)
    assert ps.ttab[0, want[0]] >= 0, "touched page was evicted"
    assert ps.ttab[0, pdev + 3] >= 0


@pytest.mark.parametrize("frames", [1, 2])
def test_demand_serves_the_oldest_waiting_row_first(ds, frames):
    """More pages missed than a shard has frames: the demand fetch
    installs the pages in descending order of the wait key of the
    oldest row that missed each, not in page order."""
    _, _, packed = ds
    consts, geom, _ = pack_for_engine(packed)
    NP = consts["db"].shape[1]
    ps = PageStore(consts, geom, frames, w_select=1, prefetch=False)
    S = ps.S
    no_cands = (np.full((S, 1, 4), -1, np.int32),
                np.zeros((S, 1, 4), bool), np.ones((S, 1), bool))
    miss = np.zeros((S, NP), bool)
    wait = np.zeros((S, NP), np.int32)
    pages = [NP - 3, NP - 2, NP - 1]
    miss[0, pages] = True
    wait[0, pages] = [1, 9, 4]
    ps.boundary(np.zeros((S, NP), bool), miss, wait, *no_cands)
    _check_consistent(ps)
    resident = set(np.flatnonzero(ps.ttab[0] >= 0).tolist())
    assert resident == set([NP - 2, NP - 1][:frames])
    assert ps.counters()["demand_fetches"] == frames


def test_prefetch_hit_counting_fixed_traversal(ds):
    """Deterministic stage -> commit -> touch sequence: a staged page
    only becomes resident at the *next* boundary (double buffering),
    its first touch counts exactly one prefetch hit, later touches
    count none (the attribution flag clears on first use)."""
    _, _, packed = ds
    consts, geom, _ = pack_for_engine(packed)
    NP = consts["db"].shape[1]
    ps = PageStore(consts, geom, NP // 2, w_select=1, prefetch_pages=2)
    target = NP - 1                           # not resident at startup
    assert ps.ttab[0, target] < 0
    score = np.zeros((ps.S, NP))
    score[0, target] = 5.0
    ps._predict = lambda *a: score            # fixed traversal signal
    S = ps.S
    no_cands = (np.full((S, 1, 4), -1, np.int32),
                np.zeros((S, 1, 4), bool), np.ones((S, 1), bool))
    quiet = np.zeros((S, NP), bool)
    nowait = np.zeros_like(quiet, np.int32)

    ps.boundary(quiet, quiet, nowait, *no_cands)  # stages target
    assert ps.counters()["prefetch_issued"] == 1
    assert ps.ttab[0, target] < 0             # staged, not yet resident
    ps.boundary(quiet, quiet, nowait, *no_cands)  # commits target
    _check_consistent(ps)
    f = ps.ttab[0, target]
    assert f >= 0 and ps.by_prefetch[0, f]
    np.testing.assert_array_equal(np.asarray(ps.frames[0, f]),
                                  ps.cold_db[0, target])
    touch = np.zeros((S, NP), bool)
    touch[0, target] = True
    ps.boundary(touch, quiet, nowait, *no_cands)  # first use: one hit
    assert ps.counters()["prefetch_hits"] == 1
    ps.boundary(touch, quiet, nowait, *no_cands)  # reuse: no double count
    assert ps.counters()["prefetch_hits"] == 1
    assert ps.counters()["page_misses"] == 0


def test_store_requires_matching_scheduler_config(ds):
    """The scheduler validates the params <-> pagestore pairing: a
    tiered params without a store (or a store with mismatched
    store_pages) is a configuration error, not silent garbage."""
    db, queries, packed = ds
    consts, geom, entry = pack_for_engine(packed)
    sp = SearchParams(L=8, W=1, k=5)
    params = EngineParams.lossless(sp, 2, geom.max_degree)
    NP = consts["db"].shape[1]
    tiered = dataclasses.replace(params, store_pages=NP)
    with pytest.raises(ValueError, match="pagestore"):
        stream_search(consts, geom, tiered, entry, queries, num_slots=2)
    ps = PageStore(consts, geom, NP, w_select=1)
    with pytest.raises(ValueError, match="store_pages"):
        stream_search(consts, geom, params, entry, queries, num_slots=2,
                      pagestore=ps)
