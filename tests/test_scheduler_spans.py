"""The served entry's host spans (``core/spans.py``), read back from a
real profiler trace: one ``search.call`` per call of ``stream_search``,
tiled by set-up, four spans per round-chunk dispatch and finish, every
span carrying its call's id."""
import collections
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
from repro.core.ref_search import SearchParams
from repro.core.scheduler import stream_search

HOST_PLANE = "/host:CPU"
PER_DISPATCH = (spans.STAGE, spans.DISPATCH, spans.SYNC, spans.ACCOUNT)
CHILDREN = (spans.SETUP,) + PER_DISPATCH + (spans.FINISH,)
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


@pytest.mark.parametrize("injit,ring", [(True, 8), (False, 0)],
                         ids=["injit-ring", "host-paced"])
def test_spans_tile_each_call(index, tmp_path, injit, ring):
    consts, geom, entry, queries = index
    params = EngineParams.lossless(SearchParams(L=12, W=1, k=5), 2,
                                   geom.max_degree)
    arrivals = np.random.default_rng(5).integers(0, 30, len(queries))
    with jax.profiler.trace(str(tmp_path)):
        stats = [stream_search(consts, geom, params, entry, queries,
                               num_slots=2, arrivals=arrivals,
                               round_chunk=4, injit_admit=injit,
                               ring_capacity=ring)[2] for _ in range(2)]
    assert all(st.host_dispatches > 1 for st in stats)

    by_call = collections.defaultdict(list)
    for sp in _spans(str(tmp_path)):
        by_call[sp[3]["call"]].append(sp)
    assert len(by_call) == 2
    for call, st in zip(sorted(by_call), stats):
        got = by_call[call]
        count = collections.Counter(name for name, *_ in got)
        for name in (spans.CALL, spans.SETUP, spans.WARMUP, spans.FINISH):
            assert count[name] == 1, (name, count)
        for name in PER_DISPATCH:
            assert count[name] == st.host_dispatches, (name, count)
            chunks = sorted(ids["chunk"] for n, _, _, ids in got
                            if n == name)
            assert chunks == list(range(st.host_dispatches))
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
