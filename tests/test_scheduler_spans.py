"""The served entry's host spans (``core/spans.py``), read back from a
real profiler trace: one ``search.call`` per call of ``stream_search``,
tiled by set-up, four spans per round-chunk dispatch (five with a tiered
page store) and finish, every span carrying its call's id; a call that
runs ahead launches chunk c+1 before it accounts chunk c."""
import collections
import dataclasses
import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import spans
from repro.core.engine import EngineParams, pack_for_engine
from repro.core.graph import build_vamana
from repro.core.luncsr import Geometry, LUNCSR, pack_index
from repro.core.pagestore import PageStore
from repro.core.ref_search import SearchParams
from repro.core.scheduler import stream_search

HOST_PLANE = "/host:CPU"
PER_DISPATCH = (spans.STAGE, spans.DISPATCH, spans.SYNC, spans.ACCOUNT)
CHILDREN = (spans.SETUP,) + PER_DISPATCH + (spans.PAGES, spans.FINISH)
NAMES = set(CHILDREN) | {spans.CALL, spans.WARMUP}


@pytest.fixture(scope="module")
def index():
    rng = np.random.default_rng(3)
    n, d, S = 256, 16, 2
    db = rng.integers(-8, 9, size=(n, d)).astype(np.float32)
    queries = rng.integers(-8, 9, size=(24, d)).astype(np.float32)
    adj, medoid = build_vamana(db, r=8, alpha=1.2, seed=3)
    geo = Geometry(num_shards=S, page_size=16, pages_per_block=2, dim=d)
    packed = pack_index(LUNCSR.from_adjacency(db, adj, geo, entry=medoid),
                        max_degree=8)
    consts, geom, entry = pack_for_engine(packed)
    return consts, geom, entry, queries


def _spans(trace_dir):
    """[(name, start, end, {id: value})] of the program's spans."""
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for p in ProfileData.from_file(path).planes:
        if p.name != HOST_PLANE:
            continue
        for ln in p.lines:
            for e in ln.events:
                if e.name in NAMES:
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return out


def _covered(intervals):
    total, end = 0.0, -np.inf
    for s, e in sorted(intervals):
        total += max(0.0, e - max(s, end))
        end = max(end, e)
    return total


@pytest.mark.parametrize("injit,ring,frames", [
    (True, 8, 0), (False, 0, 0), (True, 8, 2), (False, 0, 2),
    (True, 0, 0), (True, 0, 2)],
    ids=["injit-ring", "host-paced", "injit-tiered", "host-paced-tiered",
         "injit-ahead", "injit-tiered-ahead"])
def test_spans_tile_each_call(index, tmp_path, injit, ring, frames):
    consts, geom, entry, queries = index
    params = EngineParams.lossless(SearchParams(L=12, W=1, k=5), 2,
                                   geom.max_degree)
    kw = {}
    if frames:
        store = PageStore(consts, geom, frames, w_select=1)
        params = dataclasses.replace(params, store_pages=store.num_pages)
        kw["pagestore"] = store
    arrivals = np.random.default_rng(5).integers(0, 30, len(queries))
    with jax.profiler.trace(str(tmp_path)):
        stats = [stream_search(consts, geom, params, entry, queries,
                               num_slots=2, arrivals=arrivals,
                               round_chunk=4, injit_admit=injit,
                               ring_capacity=ring, **kw)[2]
                 for _ in range(2)]
    assert all(st.host_dispatches > 1 for st in stats)
    if frames:
        assert all(st.stalls > 0 for st in stats)

    by_call = collections.defaultdict(list)
    for sp in _spans(str(tmp_path)):
        by_call[sp[3]["call"]].append(sp)
    assert len(by_call) == 2
    for call, st in zip(sorted(by_call), stats):
        got = by_call[call]
        count = collections.Counter(name for name, *_ in got)
        for name in (spans.CALL, spans.SETUP, spans.WARMUP, spans.FINISH):
            assert count[name] == 1, (name, count)
        for name in PER_DISPATCH + ((spans.PAGES,) if frames else ()):
            assert count[name] == st.host_dispatches, (name, count)
            chunks = sorted(ids["chunk"] for n, _, _, ids in got
                            if n == name)
            assert chunks == list(range(st.host_dispatches))
        at = {(n, ids["chunk"]): (s, e) for n, s, e, ids in got
              if n in PER_DISPATCH + (spans.PAGES,)}
        if frames:
            # the store's boundary lies between the chunk's sync and
            # its account
            for c in range(st.host_dispatches):
                assert (at[spans.SYNC, c][1] <= at[spans.PAGES, c][0]
                        and at[spans.PAGES, c][1]
                        <= at[spans.ACCOUNT, c][0])
            # the store sees chunk c's misses before chunk c+1 launches
            for c in range(st.host_dispatches - 1):
                assert at[spans.PAGES, c][1] <= at[spans.DISPATCH, c + 1][0]
        else:
            assert count[spans.PAGES] == 0
        if injit and not ring and not frames:
            # run ahead: chunk c+1 launches before chunk c is accounted
            for c in range(st.host_dispatches - 1):
                assert at[spans.DISPATCH, c + 1][0] < at[spans.ACCOUNT, c][0]
        first = {name: (s, e) for name, s, e, _ in got}
        c0, c1 = first[spans.CALL]
        assert all(c0 <= s and e <= c1 for _, s, e, _ in got)
        w0, w1 = first[spans.WARMUP]
        s0, s1 = first[spans.SETUP]
        assert s0 <= w0 and w1 <= s1
        children = [(s, e) for name, s, e, _ in got if name in CHILDREN]
        assert _covered(children) >= 0.9 * (c1 - c0)
        # the children follow one another without overlap
        assert _covered(children) == pytest.approx(
            sum(e - s for s, e in children))
