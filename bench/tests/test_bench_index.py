"""The index cache and the data it is built from: the key follows the
seed and the build's sources, a child process builds what a second load
reads, and each seed draws its own points from the configuration's
distribution."""
import pathlib
import shutil
import sys

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import index  # noqa: E402
from data import VectorDataset  # noqa: E402

CFG = {"name": "tiny", "dim": 16, "n": 256, "degree": 8,
       "shards": 2, "page_size": 8,
       "generator": {"clusters": 4, "intrinsic": 4, "spread": 0.35,
                     "ambient_noise": 0.02, "seed": 2**31 + 5}}
SEED = 2**32 + 11


def _fake_repo(tmp_path):
    for rel in index.BUILD_SOURCES:
        dst = tmp_path / rel
        dst.parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(index.REPO / rel, dst)
    return tmp_path


def test_key_changes_with_a_build_source(tmp_path):
    repo = _fake_repo(tmp_path)
    k0 = index.cache_key(CFG, SEED, repo)
    assert index.cache_key(CFG, SEED, repo) == k0
    other_data = dict(CFG, generator=dict(CFG["generator"], seed=8))
    assert index.cache_key(other_data, SEED, repo) != k0
    assert index.cache_key(CFG, SEED + 1, repo) != k0
    assert index.cache_key(dict(CFG, degree=16), SEED, repo) != k0
    # serving settings do not rebuild the index
    assert index.cache_key(dict(CFG, shards=4), SEED, repo) == k0
    for rel in index.BUILD_SOURCES:
        path = repo / rel
        text = path.read_text()
        path.write_text(text + "\n# changed\n")
        assert index.cache_key(CFG, SEED, repo) != k0, rel
        path.write_text(text)
    assert index.cache_key(CFG, SEED, repo) == k0


def test_child_builds_what_a_second_load_reads(tmp_path):
    with index.Build(CFG, SEED, tmp_path) as build:
        assert build.proc is not None
        db, adj, entry, _, cached = build.result()
    assert not cached
    with index.Build(CFG, SEED, tmp_path) as again:
        assert again.proc is None
        db2, adj2, entry2, _, cached2 = again.result()
    assert cached2 and entry2 == entry
    np.testing.assert_array_equal(db2, db)
    np.testing.assert_array_equal(adj2, adj)
    assert db.shape == (256, 16) and adj.shape == (256, 8)
    # the served vectors are the seed's collection, reordered
    want = index.collection(CFG, SEED)
    np.testing.assert_array_equal(np.sort(db, axis=0), np.sort(want, axis=0))
    packed = index.pack(CFG, db, adj, entry)
    assert packed.db.shape[0] == 2


def test_failed_build_raises(tmp_path):
    broken = dict(CFG, degree="many")
    with index.Build(broken, SEED, tmp_path) as build:
        with pytest.raises(RuntimeError, match="index build"):
            build.result()


def test_seed_draws_points_from_a_fixed_distribution():
    ds = VectorDataset.from_config(CFG)
    a, b = ds.collection(128, SEED), ds.collection(128, SEED + 1)
    np.testing.assert_array_equal(a, ds.collection(128, SEED))
    assert not np.array_equal(a, b)
    assert not np.array_equal(ds.queries(32, SEED), ds.queries(32, SEED + 1))
    assert not np.array_equal(ds.queries(32, SEED), a[:32])
    # both seeds' points lie on the configuration's subspace
    basis = ds._basis()
    for x in (a, b):
        off = x - (x @ basis.T) @ basis
        assert np.abs(off).max() < 10 * ds.ambient_noise
