"""Compile the hot path for a described TPU v5e, with no chip attached.

Interpret mode accepts kernels that compiled Mosaic refuses (block
shapes off the (8, 128) tile, reversed slices, bool compares), so these
tests lower the Pallas kernels with ``interpret=False`` and the
pallas-mode serving stepper for a ``v5e:2x2`` topology and compile them
with the TPU compiler. Nothing runs; a refusal fails the test.

Shapes follow ``chip_smoke.py``: 64-vector pages, coalesced tiles of 8
and the per-item width 1, the engine's bitonic row widths, n=16384 over
8 shards (one chip) or 4 (the 2x2 mesh); the distance kernel also at
the other big-ann widths (d=96 DEEP, 100 MSSPACEV, 128 BIGANN, 200
Text2Image).
"""
import dataclasses
import functools
import os

import jax
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

from repro.core.backend import KernelBackend
from repro.core.engine import EngineParams, make_stepper, pack_for_engine
from repro.core.luncsr import Geometry, LUNCSR, pack_index
from repro.core.pagestore import PageStore
from repro.core.ref_search import SearchParams
from repro.core.scheduler import StreamScheduler
from repro.kernels.distance.ops import coalesced_distance_op
from repro.kernels.topk.kernel import bitonic_merge, bitonic_sort

N, PAGE, DEGREE, SLOTS, CHUNK, PENDING = 16384, 64, 16, 8, 8, 256


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(x, sharding):
    return jax.ShapeDtypeStruct(np.shape(x), x.dtype, sharding=sharding)


def _compile(fn, args, sharding):
    """Lower ``fn`` for the described chip and compile it there."""
    specs = jax.tree.map(lambda x: _spec(x, sharding), args)
    return jax.jit(fn).lower(*specs).compile()


@pytest.mark.parametrize("qb", [1, 8])
@pytest.mark.parametrize("d", [96, 100, 128, 200])
def test_distance_kernel_compiles(one_chip, d, qb):
    items, npages = 1024, 32
    args = (np.zeros(items, np.int32), np.zeros(items, np.int32),
            np.ones(items, bool), np.zeros((items, d), np.float32),
            np.zeros(items, np.float32),
            np.zeros((npages, PAGE, d), np.float32),
            np.zeros((npages, PAGE), np.float32))
    fn = functools.partial(coalesced_distance_op, qb=qb, mode="pallas")
    assert "tpu_custom_call" in _compile(fn, args, one_chip).as_text()


@pytest.mark.parametrize("width", [16, 32, 64, 128])
@pytest.mark.parametrize("kernel", [bitonic_sort, bitonic_merge],
                         ids=["sort", "merge"])
def test_bitonic_kernel_compiles(one_chip, kernel, width):
    rows = 8 * SLOTS      # the sim pool's S * Qs candidate rows
    args = (np.zeros((rows, width), np.float32),
            np.zeros((rows, width), np.int32),
            np.zeros((rows, width), np.int32))
    fn = functools.partial(kernel, interpret=False)
    assert "tpu_custom_call" in _compile(fn, args, one_chip).as_text()


@pytest.mark.parametrize("L,M", [(32, 16), (32, 20)])
def test_candidate_merge_compiles(one_chip, L, M):
    """The Gather stage's merge: sort the fresh proposals, then one
    bitonic merge pass against the sorted list (payload lane packed)."""
    rows = 8 * SLOTS
    args = (np.zeros((rows, L), np.float32), np.zeros((rows, L), np.int32),
            np.zeros((rows, M), np.float32), np.zeros((rows, M), np.int32),
            (np.zeros((rows, L), bool),), (np.zeros((rows, M), bool),))
    fn = KernelBackend(mode="pallas").merge_unsorted
    assert _compile(fn, args, one_chip).as_text().count(
        "tpu_custom_call") >= 2


def _scheduler(shards, device_pages=0):
    """A pallas-mode scheduler over a random graph at the smoke's shapes
    (the compile needs shapes, not a navigable graph)."""
    rng = np.random.default_rng(0)
    db = rng.standard_normal((N, 96)).astype(np.float32)
    adj = rng.integers(0, N, (N, DEGREE)).astype(np.int32)
    geo = Geometry(num_shards=shards, page_size=PAGE, pages_per_block=4,
                   dim=96)
    packed = pack_index(LUNCSR.from_adjacency(db, adj, geo),
                        max_degree=DEGREE)
    consts, geom, entry = pack_for_engine(packed)
    params = EngineParams.lossless(SearchParams(L=32, W=1, k=10), SLOTS,
                                   DEGREE, kernel_mode="pallas")
    store = None
    if device_pages:
        store = PageStore(consts, geom, device_pages, w_select=1)
        params = dataclasses.replace(params, store_pages=store.num_pages)
    return StreamScheduler(consts, geom, params, entry, num_slots=SLOTS,
                           round_chunk=CHUNK, pagestore=store)


def _chunk_admit_args(sched):
    """The in-jit admission chunk's arguments, as the scheduler's
    warmup passes them."""
    state, qbuf = sched._fresh_pool(96)
    spec_state, cfg, _ = sched._spec_inputs((sched.S, SLOTS))
    pend = (np.zeros((PENDING, 96), np.float32),
            np.zeros((PENDING,), np.int32))
    return (sched.consts, state, qbuf, spec_state, cfg, np.int32(1), pend,
            np.int32(PENDING), np.int32(0), sched.entry)


@pytest.mark.parametrize("device_pages", [0, 16], ids=["resident",
                                                      "half_tiered"])
def test_pallas_stepper_compiles(one_chip, device_pages):
    """engine_run_chunk_admit in pallas mode on one chip, S=8 shards:
    the fully resident store and the tiered store at half residency."""
    sched = _scheduler(8, device_pages)
    compiled = _compile(sched.stepper.run_chunk_admit,
                        _chunk_admit_args(sched), one_chip)
    # distance, proposal sort and candidate merge kernels
    assert compiled.as_text().count("tpu_custom_call") >= 3


def test_pallas_mesh_stepper_compiles(topo):
    """The shard_map stepper over the 2x2 mesh, S=4: one shard per chip,
    exchanges as all-to-all."""
    mesh = Mesh(np.array(topo.devices[:4]), ("lun",))
    sched = _scheduler(4)
    stepper = make_stepper(sched.params, sched.geom, mesh=mesh,
                           round_chunk=CHUNK)
    args = _chunk_admit_args(sched)
    shard = NamedSharding(mesh, PartitionSpec("lun"))
    rep = NamedSharding(mesh, PartitionSpec())
    specs = jax.tree.map(
        lambda x: _spec(x, shard if np.ndim(x) and np.shape(x)[0] == 4
                        else rep), args)
    compiled = jax.jit(stepper.run_chunk_admit).lower(*specs).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-to-all" in text
    # the store is split four ways, not held whole by one chip
    db_bytes = int(np.prod(sched.consts["db"].shape)) * 4
    assert compiled.memory_analysis().argument_size_in_bytes < db_bytes
