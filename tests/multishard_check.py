"""Subprocess body for test_engine_multishard: shard_map == sim, 8 devices.

Run as: XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python tests/multishard_check.py
"""
import dataclasses
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np          # noqa: E402
import jax                  # noqa: E402

from repro.core.engine import (EngineParams, pack_for_engine,      # noqa: E402
                               search_distributed, search_sim)
from repro.core.graph import build_vamana                          # noqa: E402
from repro.core.luncsr import Geometry, LUNCSR, pack_index         # noqa: E402
from repro.core.ref_search import SearchParams                     # noqa: E402
from repro.launch.mesh import make_engine_mesh                     # noqa: E402


def main():
    assert jax.device_count() == 8, jax.device_count()
    rng = np.random.default_rng(0)
    n, d, nq, S = 2048, 32, 64, 8
    db = rng.integers(-8, 9, size=(n, d)).astype(np.float32)
    queries = rng.integers(-8, 9, size=(nq, d)).astype(np.float32)
    adj, medoid = build_vamana(db, r=12, alpha=1.2, seed=0)
    geo = Geometry(num_shards=S, page_size=32, pages_per_block=2, dim=d)
    index = LUNCSR.from_adjacency(db, adj, geo, entry=medoid, pref_width=4)
    packed = pack_index(index, max_degree=12)
    consts, geom, entry = pack_for_engine(packed)
    qsh = queries.reshape(S, nq // S, d)

    mesh = make_engine_mesh()
    # (spec_width, kernel_mode, capacity_b): the ref legs drive distance
    # + merge through the kernel backend's paged/bitonic path under
    # shard_map; capacity_b=8 makes the drop rule fire on both drivers
    for spec, kernel_mode, cap_b in ((0, "jnp", None), (4, "jnp", None),
                                     (4, "ref", None), (0, "ref", 8)):
        sp = SearchParams(L=16, W=2, k=10)
        params = EngineParams.lossless(sp, qsh.shape[1], geom.max_degree,
                                       spec_width=spec,
                                       kernel_mode=kernel_mode)
        if cap_b is not None:
            params = dataclasses.replace(params, capacity_b=cap_b)
        si, sd, ss = search_sim(consts, qsh, *entry, params, geom)
        di, dd, dst = search_distributed(consts, qsh, *entry, params, geom,
                                         mesh)
        np.testing.assert_array_equal(np.asarray(si), np.asarray(di))
        np.testing.assert_array_equal(np.asarray(sd), np.asarray(dd))
        np.testing.assert_array_equal(np.asarray(ss["rounds"]),
                                      np.asarray(dst["rounds"]))
        for name in ("pages_unique", "items_recv", "drops_b",
                     "props_sent"):
            np.testing.assert_array_equal(np.asarray(ss[name]),
                                          np.asarray(dst[name]),
                                          err_msg=name)
        assert (int(np.asarray(ss["drops_b"]).sum()) > 0) == (cap_b == 8)
        # satellite: both drivers report total_rounds per shard, same shape
        assert (np.asarray(ss["total_rounds"]).shape
                == np.asarray(dst["total_rounds"]).shape == (S,))
        np.testing.assert_array_equal(np.asarray(ss["total_rounds"]),
                                      np.asarray(dst["total_rounds"]))
        print(f"spec={spec} kernel_mode={kernel_mode} capacity_b="
              f"{params.capacity_b}: shard_map == sim OK "
              f"(rounds={int(np.asarray(ss['rounds']).sum())})")

    # streaming scheduler over the shard_map stepper: the distributed
    # round must stream bit-identically to the one-shot sim driver
    from repro.core.scheduler import stream_search             # noqa: E402

    sp = SearchParams(L=16, W=2, k=10)
    params_ref = EngineParams.lossless(sp, qsh.shape[1], geom.max_degree,
                                       spec_width=4)
    si, sd, _ = search_sim(consts, qsh, *entry, params_ref, geom)
    params_st = EngineParams.lossless(sp, 3, geom.max_degree, spec_width=4)
    arrivals = np.random.default_rng(5).integers(0, 8, nq)
    for dyn in (False, True):
        ids, dists, st = stream_search(
            consts, geom, params_st, entry, queries, num_slots=3,
            arrivals=arrivals, dynamic_spec=dyn, mesh=mesh)
        if not dyn:   # controller-off streaming is bit-identical
            np.testing.assert_array_equal(ids, np.asarray(si).reshape(nq, -1))
            np.testing.assert_array_equal(dists,
                                          np.asarray(sd).reshape(nq, -1))
        assert len(st.results) == nq
    print(f"streaming shard_map stepper == one-shot sim OK "
          f"(rounds={st.total_rounds}, occ={st.occupancy:.2f})")

    # chunked shard_map stepper: engine_run_chunk's psum-lockstep
    # while_loop must reproduce the per-round shard_map schedule
    # exactly — same results, same accounting, fewer host syncs
    def records(st):
        return {r.qid: (tuple(r.ids), tuple(r.dists), r.service_rounds,
                        r.n_dist, r.admit_round, r.retire_round)
                for r in st.results}

    for dyn in (False, True):
        runs = {}
        for chunk in (1, 4):
            ids, dists, st = stream_search(
                consts, geom, params_st, entry, queries, num_slots=3,
                arrivals=arrivals, dynamic_spec=dyn, mesh=mesh,
                round_chunk=chunk, injit_admit=False)
            if not dyn:
                np.testing.assert_array_equal(
                    ids, np.asarray(si).reshape(nq, -1))
                np.testing.assert_array_equal(
                    dists, np.asarray(sd).reshape(nq, -1))
            runs[chunk] = st
        assert records(runs[4]) == records(runs[1])
        assert runs[4].total_rounds == runs[1].total_rounds
        assert runs[4].occupancy_trace == runs[1].occupancy_trace
        assert runs[4].spec_trace == runs[1].spec_trace
        assert runs[4].host_dispatches < runs[1].host_dispatches
        print(f"chunked shard_map stepper (dyn={dyn}) == per-round OK "
              f"(dispatches {runs[1].host_dispatches} -> "
              f"{runs[4].host_dispatches})")

    # in-jit admission under shard_map: the device-side pending queue
    # (global row-major seating via all_gather'd free ranks) must
    # reproduce the host-admission schedule bit-exactly — per-query
    # records, round schedule, occupancy/spec traces — with strictly
    # fewer host dispatches than PR 4's stop-on-finish path at the
    # same round_chunk
    for dyn in (False, True):
        runs = {}
        for injit in (False, True):
            ids, dists, st = stream_search(
                consts, geom, params_st, entry, queries, num_slots=3,
                arrivals=arrivals, dynamic_spec=dyn, mesh=mesh,
                round_chunk=4, injit_admit=injit)
            if not dyn:
                np.testing.assert_array_equal(
                    ids, np.asarray(si).reshape(nq, -1))
                np.testing.assert_array_equal(
                    dists, np.asarray(sd).reshape(nq, -1))
            runs[injit] = st
        assert records(runs[True]) == records(runs[False])
        assert runs[True].total_rounds == runs[False].total_rounds
        assert runs[True].occupancy_trace == runs[False].occupancy_trace
        assert runs[True].spec_trace == runs[False].spec_trace
        assert runs[True].idle_rounds == runs[False].idle_rounds
        assert runs[True].host_dispatches < runs[False].host_dispatches
        print(f"in-jit admission shard_map (dyn={dyn}) == host admission "
              f"OK (dispatches {runs[False].host_dispatches} -> "
              f"{runs[True].host_dispatches})")
    print("MULTISHARD_OK")


if __name__ == "__main__":
    main()
