"""Plain references for the correctness comparison.

Copies of the program's numpy oracles (``repro.core.ref_search.
lockstep_search`` and ``repro.core.graph.brute_force_topk``), kept with
the benchmark so that no change to the program can move them. They import
nothing of the program.

``lockstep_search`` is the algorithm the engine implements: W best
unexpanded candidates expanded per round, a two-hash bloom filter as the
visited set, within-round duplicates dropped, an L-long candidate list in
(dist, id) order, squared L2 as q.q - 2 q.v + v.v in float32, and an end
when no unexpanded candidate is left (or after 4 L / W rounds).
"""
from __future__ import annotations

import numpy as np

INVALID = -1
ID_SENTINEL = np.int64(2**31 - 1)
BIG = np.float32(3.0e38)

_H1 = np.uint32(0x9E3779B1)
_H2 = np.uint32(0x85EBCA77)


def _bloom_pos(ids: np.ndarray, num_bits: int):
    u = ids.astype(np.uint32)
    with np.errstate(over="ignore"):
        h1 = (u * _H1) >> np.uint32(7)
        h2 = ((u + np.uint32(1)) * _H2) >> np.uint32(5)
    mask = np.uint32(num_bits - 1)
    return (h1 & mask).astype(np.int64), (h2 & mask).astype(np.int64)


def _bloom_insert(bloom: np.ndarray, ids: np.ndarray) -> None:
    for p in _bloom_pos(ids, bloom.size * 32):
        np.bitwise_or.at(bloom, p // 32,
                         np.uint32(1) << (p % 32).astype(np.uint32))


def _bloom_query(bloom: np.ndarray, ids: np.ndarray) -> np.ndarray:
    p1, p2 = _bloom_pos(ids, bloom.size * 32)
    h1 = (bloom[p1 // 32] >> (p1 % 32).astype(np.uint32)) & np.uint32(1)
    h2 = (bloom[p2 // 32] >> (p2 % 32).astype(np.uint32)) & np.uint32(1)
    return (h1 & h2).astype(bool)


def sq_dist_f32(q: np.ndarray, v: np.ndarray) -> np.ndarray:
    """float32 q.q - 2 q.v + v.v of one query against rows of ``v``."""
    q = q.astype(np.float32)
    v = v.astype(np.float32)
    qq = np.float32((q * q).sum())
    vv = (v * v).sum(axis=-1, dtype=np.float32)
    return qq - np.float32(2.0) * (v @ q) + vv


def lockstep_search(db: np.ndarray, adj: np.ndarray, query: np.ndarray,
                    entry: int, L: int, W: int, k: int,
                    bloom_words: int = 64):
    """One query. Returns (ids (k,), dists (k,)), INVALID-padded."""
    bloom = np.zeros(bloom_words, dtype=np.uint32)
    cand_d = np.full(L, BIG, dtype=np.float32)
    cand_i = np.full(L, ID_SENTINEL, dtype=np.int64)
    cand_e = np.zeros(L, dtype=bool)
    cand_d[0] = sq_dist_f32(query, db[entry][None])[0]
    cand_i[0] = entry
    _bloom_insert(bloom, np.asarray([entry]))

    for _ in range(4 * L // max(W, 1)):
        unexp = (~cand_e) & (cand_i != ID_SENTINEL)
        if not unexp.any():
            break
        sel = np.where(unexp)[0][:W]
        cand_e[sel] = True
        props: list[int] = []
        seen: set[int] = set()
        for p in sel:
            for u in adj[int(cand_i[p])]:
                u = int(u)
                if u == INVALID or u in seen:
                    continue
                seen.add(u)
                props.append(u)
        ids = np.asarray(props, dtype=np.int64)
        if ids.size:
            ids = ids[~_bloom_query(bloom, ids)]
        if ids.size:
            d = sq_dist_f32(query, db[ids])
            _bloom_insert(bloom, ids)
            dd = np.concatenate([cand_d, d]).astype(np.float32)
            ii = np.concatenate([cand_i, ids])
            ee = np.concatenate([cand_e, np.zeros(ids.size, bool)])
            order = np.lexsort((ii, dd))[:L]
            cand_d, cand_i, cand_e = dd[order], ii[order], ee[order]
    ok = cand_i != ID_SENTINEL
    return np.where(ok, cand_i, INVALID)[:k], cand_d[:k]


def pairwise_sq_dists(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """(n, d), (m, d) -> (n, m) squared L2 in float64."""
    a64 = a.astype(np.float64)
    b64 = b.astype(np.float64)
    d = ((a64 * a64).sum(-1)[:, None] + (b64 * b64).sum(-1)[None, :]
         - 2.0 * (a64 @ b64.T))
    return np.maximum(d, 0.0)


def brute_force_topk(db: np.ndarray, queries: np.ndarray, k: int,
                     block: int = 4096):
    """Exact top-k (ids, squared distances) per query, in float64."""
    nq = queries.shape[0]
    best_d = np.full((nq, k), np.inf)
    best_i = np.full((nq, k), INVALID, dtype=np.int64)
    for s in range(0, db.shape[0], block):
        d = pairwise_sq_dists(queries, db[s: s + block])
        ids = np.broadcast_to(np.arange(s, s + d.shape[1]), d.shape)
        alld = np.concatenate([best_d, d], axis=1)
        alli = np.concatenate([best_i, ids], axis=1)
        sel = np.argsort(alld, axis=1, kind="stable")[:, :k]
        best_d = np.take_along_axis(alld, sel, 1)
        best_i = np.take_along_axis(alli, sel, 1)
    return best_i, best_d


def exact_sq_dists(db: np.ndarray, queries: np.ndarray,
                   ids: np.ndarray) -> np.ndarray:
    """float64 squared distance of each query to each of its listed ids
    (INVALID ids give NaN)."""
    ok = ids >= 0
    v = db[np.where(ok, ids, 0)].astype(np.float64)       # (nq, k, d)
    q = queries.astype(np.float64)[:, None, :]
    d = ((v - q) ** 2).sum(-1)
    return np.where(ok, d, np.nan)


def recall(found: np.ndarray, truth: np.ndarray) -> float:
    """Mean share of each row of ``truth`` found in the same row of
    ``found``."""
    k = truth.shape[1]
    hits = sum(len(set(f.tolist()) & set(t.tolist()))
               for f, t in zip(found, truth))
    return hits / (truth.shape[0] * k)
