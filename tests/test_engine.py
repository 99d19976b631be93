"""Distributed engine (sim mode) vs single-shard traversal + paper claims."""
import numpy as np
import pytest

from repro.core.engine import (EngineGeom, EngineParams, pack_for_engine,
                               search_sim)
from repro.core.graph import build_vamana, brute_force_topk, recall_at_k
from repro.core.luncsr import Geometry, LUNCSR, pack_index
from repro.core.ref_search import SearchParams
from repro.core.traversal import search as traversal_search

INVALID = -1


def _dataset(n=1024, d=32, nq=32, S=4, page=32, seed=0, pref_width=8,
             int_valued=True):
    rng = np.random.default_rng(seed)
    if int_valued:
        db = rng.integers(-8, 9, size=(n, d)).astype(np.float32)
        queries = rng.integers(-8, 9, size=(nq, d)).astype(np.float32)
    else:
        db = rng.standard_normal((n, d)).astype(np.float32)
        queries = rng.standard_normal((nq, d)).astype(np.float32)
    adj, medoid = build_vamana(db, r=12, alpha=1.2, seed=seed)
    geo = Geometry(num_shards=S, page_size=page, pages_per_block=2, dim=d)
    index = LUNCSR.from_adjacency(db, adj, geo, entry=medoid,
                                  pref_width=pref_width)
    packed = pack_index(index, max_degree=12)
    return db, queries, adj, medoid, packed


@pytest.fixture(scope="module")
def ds():
    return _dataset()


def _shard_queries(queries, S):
    nq, d = queries.shape
    assert nq % S == 0
    return queries.reshape(S, nq // S, d)


@pytest.mark.parametrize("W", [1, 2])
def test_engine_sim_matches_traversal_bitexact(ds, W):
    db, queries, adj, medoid, packed = ds
    consts, geom, (evec, enorm, eid) = pack_for_engine(packed)
    sp = SearchParams(L=16, W=W, k=10)
    S = geom.num_shards
    qsh = _shard_queries(queries, S)
    params = EngineParams.lossless(sp, qsh.shape[1], geom.max_degree)
    out_i, out_d, stats = search_sim(consts, qsh, evec, enorm, eid,
                                     params, geom)
    vnorm = (db.astype(np.float64) ** 2).sum(-1).astype(np.float32)
    ref_i, ref_d, ref_stats = traversal_search(db, adj, vnorm, queries,
                                               medoid, sp)
    np.testing.assert_array_equal(
        np.asarray(out_i).reshape(-1, sp.k), np.asarray(ref_i))
    np.testing.assert_array_equal(
        np.asarray(out_d).reshape(-1, sp.k), np.asarray(ref_d))
    np.testing.assert_array_equal(
        np.asarray(stats["rounds"]).reshape(-1),
        np.asarray(ref_stats["rounds"]))


def test_engine_gather_vectors_baseline_same_results(ds):
    """Baseline mode moves vectors instead of distances: identical output."""
    db, queries, adj, medoid, packed = ds
    consts, geom, entry = pack_for_engine(packed)
    sp = SearchParams(L=16, W=1, k=10)
    qsh = _shard_queries(queries, geom.num_shards)
    p_nd = EngineParams.lossless(sp, qsh.shape[1], geom.max_degree)
    import dataclasses
    p_gv = dataclasses.replace(p_nd, gather_vectors=True)
    i1, d1, _ = search_sim(consts, qsh, *entry, p_nd, geom)
    i2, d2, _ = search_sim(consts, qsh, *entry, p_gv, geom)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_array_equal(np.asarray(d1), np.asarray(d2))


def test_engine_refresh_invariance(ds):
    """Block-level refresh moves physical pages; results must not change."""
    from repro.core.refresh import refresh_blocks
    db, queries, adj, medoid, packed = ds
    sp = SearchParams(L=16, W=1, k=10)
    consts, geom, entry = pack_for_engine(packed)
    qsh = _shard_queries(queries, geom.num_shards)
    params = EngineParams.lossless(sp, qsh.shape[1], geom.max_degree)
    i1, d1, _ = search_sim(consts, qsh, *entry, params, geom)

    rng = np.random.default_rng(42)
    refreshed = refresh_blocks(packed, rng, frac=0.5)
    assert not np.array_equal(refreshed.blk_perm, packed.blk_perm)
    consts2, geom2, entry2 = pack_for_engine(refreshed)
    i2, d2, _ = search_sim(consts2, qsh, *entry2, params, geom2)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_array_equal(np.asarray(d1), np.asarray(d2))


def test_engine_speculative_prefetch(ds):
    """Spec searching: fewer rounds, more distance computations (Fig 17)."""
    db, queries, adj, medoid, packed = ds
    consts, geom, entry = pack_for_engine(packed)
    sp = SearchParams(L=16, W=1, k=10)
    qsh = _shard_queries(queries, geom.num_shards)
    p0 = EngineParams.lossless(sp, qsh.shape[1], geom.max_degree)
    p1 = EngineParams.lossless(sp, qsh.shape[1], geom.max_degree,
                               spec_width=8)
    i0, _, s0 = search_sim(consts, qsh, *entry, p0, geom)
    i1, _, s1 = search_sim(consts, qsh, *entry, p1, geom)
    assert int(np.asarray(s1["rounds"]).sum()) < \
        int(np.asarray(s0["rounds"]).sum())
    assert int(np.asarray(s1["n_dist"]).sum()) > \
        int(np.asarray(s0["n_dist"]).sum())
    true_i, _ = brute_force_topk(db, queries, k=10)
    r0 = recall_at_k(np.asarray(i0).reshape(-1, 10), true_i)
    r1 = recall_at_k(np.asarray(i1).reshape(-1, 10), true_i)
    # extra speculative distance work must not hurt result quality
    assert r1 >= r0 - 0.01, (r1, r0)


def test_engine_capacity_overflow_drops_counted(ds):
    db, queries, adj, medoid, packed = ds
    consts, geom, entry = pack_for_engine(packed)
    sp = SearchParams(L=16, W=1, k=10)
    qsh = _shard_queries(queries, geom.num_shards)
    tight = EngineParams(search=sp, capacity_a=qsh.shape[1],
                         capacity_b=8)   # deliberately tiny phase-B queues
    i, d, stats = search_sim(consts, qsh, *entry, tight, geom)
    assert int(np.asarray(stats["drops_b"]).sum()) > 0
    # results remain valid (ids in range), recall degrades but stays sane
    ids = np.asarray(i).reshape(-1, 10)
    assert ((ids >= -1) & (ids < db.shape[0])).all()
    true_i, _ = brute_force_topk(db, queries, k=10)
    assert recall_at_k(ids, true_i) >= 0.3


def test_engine_page_locality_stats(ds):
    """Dynamic allocating shares page reads: unique <= items."""
    db, queries, adj, medoid, packed = ds
    consts, geom, entry = pack_for_engine(packed)
    sp = SearchParams(L=16, W=1, k=10)
    qsh = _shard_queries(queries, geom.num_shards)
    params = EngineParams.lossless(sp, qsh.shape[1], geom.max_degree)
    _, _, stats = search_sim(consts, qsh, *entry, params, geom)
    items = int(np.asarray(stats["items_recv"]).sum())
    uniq = int(np.asarray(stats["pages_unique"]).sum())
    assert 0 < uniq < items, (uniq, items)


def test_engine_sequential_striping(ds):
    """'sequential' placement (no multi-plane interleave ablation) works."""
    db, queries, adj, medoid, _ = ds
    geo = Geometry(num_shards=4, page_size=32, pages_per_block=2,
                   dim=32, stripe="sequential")
    index = LUNCSR.from_adjacency(db, adj, geo, entry=medoid)
    packed = pack_index(index, max_degree=12)
    consts, geom, entry = pack_for_engine(packed)
    sp = SearchParams(L=16, W=1, k=10)
    qsh = _shard_queries(queries, 4)
    params = EngineParams.lossless(sp, qsh.shape[1], geom.max_degree)
    out_i, out_d, _ = search_sim(consts, qsh, *entry, params, geom)
    vnorm = (db.astype(np.float64) ** 2).sum(-1).astype(np.float32)
    ref_i, ref_d, _ = traversal_search(db, adj, vnorm, queries, medoid, sp)
    np.testing.assert_array_equal(
        np.asarray(out_i).reshape(-1, sp.k), np.asarray(ref_i))


def test_payload_bf16_near_exact():
    """bf16 query payloads halve the a2a bytes; distances stay within
    bf16 rounding of the f32 path and the returned ids are stable on
    well-separated data."""
    import dataclasses

    import jax.numpy as jnp
    import numpy as np

    from repro.core.engine import EngineParams, pack_for_engine, search_sim
    from repro.core.graph import build_vamana
    from repro.core.luncsr import Geometry, LUNCSR, pack_index
    from repro.core.ref_search import SearchParams
    from repro.data.vectors import VectorDataset

    ds = VectorDataset("pay", n=1024, dim=32, clusters=8, intrinsic=8)
    db = ds.materialize()
    q = ds.queries(16)
    adj, medoid = build_vamana(db, r=8)
    geom = Geometry(num_shards=4, page_size=32, pages_per_block=4, dim=32)
    packed = pack_index(
        LUNCSR.from_adjacency(db, adj, geom, entry=medoid), max_degree=8)
    consts, egeom, entry = pack_for_engine(packed)
    sp = SearchParams(L=16, W=1, k=5)
    base = EngineParams.lossless(sp, 4, 8)
    bf = dataclasses.replace(base, payload_bf16=True)
    qsh = jnp.asarray(q.reshape(4, 4, -1))
    i0, d0, _ = search_sim(consts, qsh, *entry, base, egeom)
    i1, d1, _ = search_sim(consts, qsh, *entry, bf, egeom)
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d0),
                               rtol=2e-2, atol=2e-2)
    agree = (np.asarray(i0) == np.asarray(i1)).mean()
    assert agree > 0.9, agree


def _bucketed_search(consts, qsh, entry, params, geom):
    """search_distributed's loop over the bucketed round body
    (engine._round: phases C-E through (S, capacity_b) buckets and
    lax.all_to_all) with the shard axis a vmap axis, so S shards
    exchange on one device."""
    import jax
    import jax.numpy as jnp

    from repro.core import engine

    def a2a(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.lax.all_to_all(x, "lun", 0, 0), tree)

    def shard_round(state, db, vnorm, adj, pref, blk_perm, q):
        lc = {"db": db, "vnorm": vnorm, "adj": adj, "pref": pref,
              "blk_perm": blk_perm, "queries": q,
              "qq": jnp.sum(q ** 2, axis=-1)}
        return engine._round(state, lc, params, geom, a2a,
                             my_shard=jax.lax.axis_index("lun"))

    vround = jax.jit(jax.vmap(shard_round, axis_name="lun"))
    qq = jnp.sum(qsh ** 2, axis=-1)
    state = jax.vmap(lambda q, qn: engine._init_state(
        q, qn, *entry, params))(qsh, qq)
    t = 0
    while bool((~state.done).any()) and t < params.search.rounds_cap:
        state = vround(state, consts["db"], consts["vnorm"], consts["adj"],
                       consts["pref"], consts["blk_perm"], qsh)
        t += 1
    out_i, out_d, stats = jax.vmap(
        lambda s: engine._finalize(s, params.search.k))(state)
    stats["total_rounds"] = np.full(qsh.shape[0], t)
    return out_i, out_d, stats


@pytest.mark.parametrize("case", ["lossless", "capacity_b8", "spec8",
                                  "local_only", "sequential", "mesh1",
                                  "mesh1_capacity_b8"])
def test_compact_sim_matches_bucketed(ds, case):
    """search_sim's flat phases C-E (no per-destination buckets) against
    the bucketed shard_map stages and, where the traversal is the same,
    the single-shard traversal: ids, distances and every per-shard
    phase-D/C counter bit for bit on integer-valued vectors. The
    ``mesh1`` cases run search_distributed itself on a one-device mesh
    (one shard); the others run its round body over four shards."""
    import dataclasses

    from repro.core.engine import search_distributed
    from repro.launch.mesh import make_engine_mesh

    db, queries, adj, medoid, packed = ds
    if case == "sequential" or case.startswith("mesh1"):
        geo = Geometry(num_shards=1 if case.startswith("mesh1") else 4,
                       page_size=32, pages_per_block=2, dim=32,
                       stripe="sequential" if case == "sequential"
                       else "striped")
        packed = pack_index(LUNCSR.from_adjacency(db, adj, geo,
                                                  entry=medoid),
                            max_degree=12)
    consts, geom, entry = pack_for_engine(packed)
    sp = SearchParams(L=16, W=2, k=10)
    qsh = _shard_queries(queries, geom.num_shards)
    params = EngineParams.lossless(sp, qsh.shape[1], geom.max_degree,
                                   spec_width=8 if case == "spec8" else 0,
                                   kernel_mode="ref")
    if case.endswith("capacity_b8"):
        params = dataclasses.replace(params, capacity_b=8)
    if case == "local_only":
        params = dataclasses.replace(params, local_only=True)

    si, sd, ss = search_sim(consts, qsh, *entry, params, geom)
    if case.startswith("mesh1"):
        bi, bd, bs = search_distributed(consts, qsh, *entry, params, geom,
                                        make_engine_mesh(num=1))
    else:
        bi, bd, bs = _bucketed_search(consts, qsh, entry, params, geom)
    np.testing.assert_array_equal(np.asarray(si), np.asarray(bi))
    np.testing.assert_array_equal(np.asarray(sd), np.asarray(bd))
    for name in ("rounds", "n_dist", "items_recv", "pages_unique",
                 "drops_b", "props_sent", "total_rounds"):
        np.testing.assert_array_equal(np.asarray(ss[name]),
                                      np.asarray(bs[name]), err_msg=name)
    drops = int(np.asarray(ss["drops_b"]).sum())
    assert (drops > 0) == case.endswith("capacity_b8"), drops
    if case in ("lossless", "sequential", "mesh1"):
        vnorm = (db.astype(np.float64) ** 2).sum(-1).astype(np.float32)
        ref_i, ref_d, _ = traversal_search(db, adj, vnorm, queries, medoid,
                                           sp)
        np.testing.assert_array_equal(
            np.asarray(si).reshape(-1, sp.k), np.asarray(ref_i))
        np.testing.assert_array_equal(
            np.asarray(sd).reshape(-1, sp.k), np.asarray(ref_d))


def _deep96_chunk_admit_jaxpr():
    """engine_run_chunk_admit traced at the deep96 geometry (8 shards,
    32 slots a shard, degree 32, 16 pages of 64 a shard, d=96, qb=8,
    ``ref`` kernels) from shapes alone."""
    import jax
    import jax.numpy as jnp

    from repro.core.engine import (EngineGeom, engine_init,
                                   engine_run_chunk_admit)
    from repro.core.scheduler import _NULL_CFG

    S, Qs, R, NP, P, d, n = 8, 32, 32, 16, 64, 96, 8192
    geom = EngineGeom(num_shards=S, page_size=P, pages_per_block=4,
                      pages_per_shard=NP, dim=d, max_degree=R,
                      spec_stored=0, n=n)
    params = EngineParams.lossless(SearchParams(L=32, W=1, k=10), Qs, R,
                                   kernel_mode="ref", coalesce_qb=8)
    f32, i32 = jnp.float32, jnp.int32
    sds = jax.ShapeDtypeStruct
    consts = {"db": sds((S, NP, P, d), f32), "vnorm": sds((S, NP, P), f32),
              "adj": sds((S, NP * P, R), i32),
              "pref": sds((S, NP * P, 0), i32),
              "blk_perm": sds((S, NP // 4), i32)}
    queries = sds((S, Qs, d), f32)
    entry = (sds((d,), f32), sds((), f32), sds((), i32))
    state = jax.eval_shape(
        lambda c, q, *e: engine_init(c, q, *e, params=params, geom=geom),
        consts, queries, *entry)
    spec_state = (sds((S, Qs), i32),) + (sds((S, Qs), f32),) * 4
    traced = engine_run_chunk_admit.trace(
        consts, state, queries, spec_state, _NULL_CFG, 8,
        sds((512, d), f32), sds((512,), i32), 0, 0, *entry,
        params=params, geom=geom, K=8)
    return traced.jaxpr.jaxpr, params


def test_sim_round_has_no_bucket_axis_at_deep96_shape():
    """The sim round holds no (S, S, capacity_b) bucket and runs one
    distance stage of coalesce_num_tiles(S*Qs*M, S*NP, qb) tiles, not S
    stages of coalesce_num_tiles(S*capacity_b, NP, qb)."""
    from repro.analysis.jaxpr_audit import _walk_eqns
    from repro.kernels.distance.ops import coalesce_num_tiles

    jaxpr, params = _deep96_chunk_admit_jaxpr()
    S, C, d, qb = 8, params.capacity_b, 96, 8
    assert C == 1024
    shapes = set()
    for eqn in _walk_eqns(jaxpr):
        for v in list(eqn.invars) + list(eqn.outvars):
            shape = getattr(getattr(v, "aval", None), "shape", None)
            if shape is not None:
                shapes.add(tuple(shape))

    def has_run(shape, run):
        return any(shape[i:i + len(run)] == run
                   for i in range(len(shape) - len(run) + 1))

    buckets = [s for s in shapes
               if has_run(s, (S, S, C)) or S * S * C in s]
    assert not buckets, buckets
    compact = coalesce_num_tiles(S * 32 * 32, S * 16, qb)
    per_owner = coalesce_num_tiles(S * C, 16, qb)
    assert (compact, per_owner) == (1136, 1038)
    assert (compact, qb, d) in shapes
    assert not [s for s in shapes if per_owner in s]
