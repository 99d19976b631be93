"""Synthetic vector collections and query pools of a deployment.

A copy of the program's clustered generator (``repro.data.vectors``), kept
with the benchmark so that no change to the program can move the data a
cell is measured on. Points live on an ``intrinsic``-dimensional subspace
of the ``dim``-wide space (a random orthonormal embedding), around
Gaussian cluster centres, with a little ambient noise: real embedding
collections have an intrinsic dimension of ten to twenty-odd.

The configuration fixes the distribution, as a public dataset is one
distribution: its ``generator.seed`` draws the cluster centres and the
embedding. A run's ``--seed`` draws every point from it: the collection,
the query pool, the traffic's order and arrivals (``frontend.py``) and the
correctness sample. Each seed therefore serves an index and queries of its
own, from the same distribution at the same sizes.
"""
from __future__ import annotations

import dataclasses

import numpy as np

# independent random streams drawn from one seed
STREAM_COLLECTION = 0
STREAM_QUERIES = 1
STREAM_TRAFFIC = 2
STREAM_SAMPLE = 3
STREAM_ARRIVALS = 4


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """A generator for one purpose of one seed (any whole number)."""
    return np.random.default_rng([int(seed) % 2**63, stream])


@dataclasses.dataclass(frozen=True)
class VectorDataset:
    dim: int
    clusters: int = 32
    spread: float = 0.35
    intrinsic: int = 8
    ambient_noise: float = 0.02
    seed: int = 0

    @classmethod
    def from_config(cls, cfg: dict) -> "VectorDataset":
        g = cfg["generator"]
        return cls(dim=int(cfg["dim"]), clusters=int(g["clusters"]),
                   spread=float(g["spread"]), intrinsic=int(g["intrinsic"]),
                   ambient_noise=float(g["ambient_noise"]),
                   seed=int(g["seed"]))

    def _basis(self) -> np.ndarray:
        rng = np.random.default_rng(self.seed + 7919)
        a = rng.standard_normal((self.intrinsic, self.dim))
        q, _ = np.linalg.qr(a.T)                       # (dim, intrinsic)
        return q.T                                     # orthonormal rows

    def _centers(self) -> np.ndarray:
        rng = np.random.default_rng(self.seed)
        return rng.standard_normal((self.clusters, self.intrinsic))

    def sample(self, num: int, rng: np.random.Generator) -> np.ndarray:
        centers = self._centers()
        assign = rng.integers(0, self.clusters, size=num)
        z = centers[assign] + self.spread * rng.standard_normal(
            (num, self.intrinsic))
        x = z @ self._basis()
        x += self.ambient_noise * rng.standard_normal((num, self.dim))
        return x.astype(np.float32)

    def collection(self, n: int, seed: int) -> np.ndarray:
        return self.sample(n, rng_for(seed, STREAM_COLLECTION))

    def queries(self, num: int, seed: int) -> np.ndarray:
        return self.sample(num, rng_for(seed, STREAM_QUERIES))
