"""The index a run serves: made by the program's own graph build from the
collection its seed draws, and cached in the checkout.

A deployment builds its index once and its serving processes load it, the
way a compile cache works. The cache key is the configuration's build
sizes and data, the seed, and a hash of the source files of the build, so
a change to the build code rebuilds. What is cached is the build's output
(vectors in the served order, adjacency, entry vertex); packing it into
pages is cheap and is redone at every load.

The build runs in a child process, started before the serving process
touches JAX, so its Python heap and threads never live beside the window:
a run that builds serves like a run that loads. As a script:

    python bench/index.py CONFIG_JSON SEED OUT.npz
"""
from __future__ import annotations

import hashlib
import json
import os
import pathlib
import subprocess
import sys
import time

import numpy as np

BENCH_DIR = pathlib.Path(__file__).resolve().parent
REPO = BENCH_DIR.parent
CACHE_DIR = BENCH_DIR / ".index_cache"
BUILD_SOURCES = ("src/repro/core/graph.py", "src/repro/core/reorder.py",
                 "src/repro/core/luncsr.py", "bench/data.py")
BUILD_KEYS = ("dim", "n", "degree", "generator")


def cache_key(cfg: dict, seed: int, repo: pathlib.Path = REPO) -> str:
    h = hashlib.sha256()
    h.update(json.dumps({k: cfg[k] for k in BUILD_KEYS},
                        sort_keys=True).encode())
    h.update(str(int(seed)).encode())
    for rel in BUILD_SOURCES:
        h.update(rel.encode())
        h.update((repo / rel).read_bytes())
    return h.hexdigest()[:24]


def collection(cfg: dict, seed: int) -> np.ndarray:
    """The seed's collection, in generation order."""
    from data import VectorDataset

    return VectorDataset.from_config(cfg).collection(int(cfg["n"]), seed)


def build(cfg: dict, seed: int):
    """(vectors, adjacency, entry) in the served vertex order."""
    from repro.core.graph import build_vamana
    from repro.core.reorder import apply_reordering, degree_ascending_bfs

    db = collection(cfg, seed)
    adj, medoid = build_vamana(db, r=int(cfg["degree"]),
                               seed=int(seed) % 2**32)
    order = degree_ascending_bfs(adj)
    return apply_reordering(db, adj, order, entry=medoid)


class Build:
    """The index of one configuration and seed: found in the cache, or
    built into it by a child process that starts at once. ``result()``
    waits for it; leaving the ``with`` block stops a build still
    running."""

    def __init__(self, cfg: dict, seed: int, cache_dir=CACHE_DIR,
                 repo: pathlib.Path = REPO):
        self.path = (pathlib.Path(cache_dir)
                     / f"{cfg['name']}-{cache_key(cfg, seed, repo)}.npz")
        self.cached = self.path.exists()
        self.t0 = time.perf_counter()
        self.proc = None
        if not self.cached:
            self.path.parent.mkdir(parents=True, exist_ok=True)
            self.proc = subprocess.Popen(
                [sys.executable, str(BENCH_DIR / "index.py"),
                 json.dumps(cfg), str(int(seed)), str(self.path)],
                stdout=subprocess.DEVNULL,
                env=dict(os.environ, JAX_PLATFORMS="cpu"))

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.stop()

    def stop(self) -> None:
        if self.proc is not None and self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()

    def result(self):
        """(vectors, adjacency, entry, seconds to build or load, cached?)."""
        if self.cached:
            self.t0 = time.perf_counter()
        if self.proc is not None:
            rc = self.proc.wait()
            if rc != 0:
                raise RuntimeError(f"index build exited with {rc}")
        with np.load(self.path) as z:
            db, adj, entry = z["db"], z["adj"], int(z["entry"])
        return db, adj, entry, time.perf_counter() - self.t0, self.cached


def geometry(cfg: dict):
    """The program's store geometry for the configuration's index."""
    from repro.core.luncsr import Geometry

    return Geometry(num_shards=int(cfg["shards"]),
                    page_size=int(cfg["page_size"]), pages_per_block=4,
                    dim=int(cfg["dim"]), stripe="striped")


def pack(cfg: dict, db: np.ndarray, adj: np.ndarray, entry: int):
    """The program's paged, sharded layout of the index."""
    from repro.core.luncsr import LUNCSR, pack_index

    idx = LUNCSR.from_adjacency(db, adj, geometry(cfg), entry=entry,
                                pref_width=0)
    return pack_index(idx, max_degree=int(cfg["degree"]))


def main(argv) -> int:
    cfg_json, seed, out = argv
    sys.path.insert(0, str(REPO / "src"))
    db, adj, entry = build(json.loads(cfg_json), int(seed))
    out = pathlib.Path(out)
    tmp = out.with_name(f"{out.stem}.{os.getpid()}.tmp.npz")
    np.savez(tmp, db=db, adj=adj, entry=np.int64(entry))
    os.replace(tmp, out)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
