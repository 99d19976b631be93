"""Single-shard batched best-first traversal (JAX, lax.while_loop).

This is the device-resident form of ``core.ref_search.lockstep_search``:
identical round semantics, batched over queries, jittable. It doubles as

  * the correctness oracle's device twin (bit-exact on integer-valued
    vectors — tested in tests/test_traversal.py), and
  * the "CPU/GPU baseline" analogue for the benchmarks: all feature
    vectors live in one memory space, no routing, no filtering.

The distributed engine (core/engine.py) reuses the per-query primitives
exported here: ``select_expand``, ``dedup_in_round``, ``merge_candidates``.

Hot paths (distance + merge) dispatch through a
:class:`repro.core.backend.KernelBackend`: the default inline-jnp mode is
the fused XLA path, while ``ref``/``interpret``/``pallas`` route the same
math through the paged SiN distance and bitonic merge kernels
(kernels/{distance,topk}) — bit-identical on integer-valued vectors.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.backend import KernelBackend, paged_view
from repro.core.ref_search import SearchParams
from repro.utils import BIG_DIST, bloom_insert, bloom_query

_JNP = KernelBackend(mode="jnp")

INVALID = -1
ID_SENTINEL = jnp.int32(2**31 - 1)


class TraversalState(NamedTuple):
    cand_d: jax.Array      # (Q, L) f32 ascending
    cand_i: jax.Array      # (Q, L) i32, ID_SENTINEL-padded
    cand_e: jax.Array      # (Q, L) bool, expanded flags
    bloom: jax.Array       # (Q, W32) u32 visited bloom
    done: jax.Array        # (Q,) bool
    rounds: jax.Array      # (Q,) i32 rounds in which this query did work
    n_dist: jax.Array      # (Q,) i32 distance computations
    page_acc: jax.Array    # (Q,) i32 unique-page touches summed over rounds
    t: jax.Array           # () i32 global round counter


# ---------------------------------------------------------------------------
# Shared per-query primitives (also used by core/engine.py)
# ---------------------------------------------------------------------------
def sort_by_dist_id(d: jax.Array, i: jax.Array, *others: jax.Array,
                    backend: KernelBackend | None = None):
    """Ascending lexicographic (dist, id) sort along the last axis.

    ``others`` ride along as payload lanes. With no backend (or inline
    mode) this is lax.sort(num_keys=2); kernel modes run the bitonic
    sorting network on power-of-two padded rows.
    """
    backend = backend or _JNP
    if backend.inline:
        return jax.lax.sort((d, i) + others, num_keys=2)
    lead = d.shape[:-1]
    m = d.shape[-1]
    flat = backend.sort_pairs(
        d.reshape(-1, m), i.reshape(-1, m),
        *(o.reshape(-1, m) for o in others))
    return tuple(x.reshape(lead + (m,)) for x in flat)


def select_expand(cand_d, cand_i, cand_e, W: int):
    """Pick the best W valid unexpanded candidates per query.

    Returns (sel_ids (Q,W) i32, sel_valid (Q,W) bool, cand_e' with the
    selected positions marked expanded).
    """
    Q, L = cand_i.shape
    valid_unexp = (~cand_e) & (cand_i != ID_SENTINEL)
    pos = jnp.where(valid_unexp, jnp.arange(L, dtype=jnp.int32)[None, :],
                    jnp.int32(L))
    pos = jnp.sort(pos, axis=-1)[:, :W]                       # (Q, W)
    sel_valid = pos < L
    safe = jnp.minimum(pos, L - 1)
    sel_ids = jnp.take_along_axis(cand_i, safe, axis=1)
    sel_ids = jnp.where(sel_valid, sel_ids, ID_SENTINEL)
    onehot = (pos[:, :, None] == jnp.arange(L, dtype=jnp.int32)[None, None, :])
    cand_e = cand_e | onehot.any(axis=1)
    return sel_ids, sel_valid, cand_e


def dedup_in_round(ids: jax.Array, valid: jax.Array) -> jax.Array:
    """Drop duplicate proposals within a round (first occurrence wins).

    ids/valid: (..., M). Returns updated valid.
    """
    eq = (ids[..., :, None] == ids[..., None, :])
    eq &= valid[..., :, None] & valid[..., None, :]
    m = ids.shape[-1]
    earlier = jnp.tril(jnp.ones((m, m), dtype=bool), k=-1)
    dup = (eq & earlier).any(axis=-1)
    return valid & ~dup


def merge_candidates(cand_d, cand_i, cand_e, new_d, new_i, new_valid, L: int,
                     backend: KernelBackend | None = None):
    """Merge proposals into the candidate list; keep best L by (dist, id).

    The candidate list is always sorted (established at init, preserved
    here), so kernel modes top-L-sort only the M fresh proposals and run
    a single bitonic *merge* pass against the sorted list — the Gather
    stage never re-sorts sorted data. Inline mode keeps the fused
    concat + lax.sort. The ``expanded`` flags travel through as a
    payload lane (zeros on the proposal side)."""
    backend = backend or _JNP
    new_d = jnp.where(new_valid, new_d, BIG_DIST)
    new_i = jnp.where(new_valid, new_i, ID_SENTINEL)
    new_e = jnp.zeros(new_i.shape, dtype=bool)
    if backend.inline:
        d = jnp.concatenate([cand_d, new_d], axis=-1)
        i = jnp.concatenate([cand_i, new_i], axis=-1)
        e = jnp.concatenate([cand_e, new_e], axis=-1)
        d, i, e = sort_by_dist_id(d, i, e, backend=backend)
        return d[..., :L], i[..., :L], e[..., :L]
    lead = cand_d.shape[:-1]
    lc, m = cand_d.shape[-1], new_d.shape[-1]
    d, i, e = backend.merge_unsorted(
        cand_d.reshape(-1, lc), cand_i.reshape(-1, lc),
        new_d.reshape(-1, m), new_i.reshape(-1, m),
        pay_a=(cand_e.reshape(-1, lc),),
        pay_b=(new_e.reshape(-1, m),))
    return (d.reshape(lead + (lc + m,))[..., :L],
            i.reshape(lead + (lc + m,))[..., :L],
            e.reshape(lead + (lc + m,))[..., :L])


def count_unique_pages(ids, valid, page_size: int):
    """#unique pages among valid ids, per query. ids: (Q, M)."""
    pages = jnp.where(valid, ids // page_size, ID_SENTINEL)
    pages = jnp.sort(pages, axis=-1)
    first = jnp.concatenate(
        [jnp.ones(pages.shape[:-1] + (1,), dtype=bool),
         pages[..., 1:] != pages[..., :-1]], axis=-1)
    return (first & (pages != ID_SENTINEL)).sum(axis=-1).astype(jnp.int32)


def squared_dists(queries, qq, vecs, vnorm,
                  backend: KernelBackend | None = None):
    """q.q - 2 q.v + v.v ; queries (Q,d), vecs (Q,M,d), vnorm (Q,M).

    Kernel modes treat each query's gathered candidate set as one "page"
    ((Q, M, d) is a (NP=Q, P=M, d) paged store) and run the SiN distance
    kernel over it; inline mode is the fused einsum. Compiled ``pallas``
    mode inherits the kernel's TPU lane-alignment requirements on M/d."""
    backend = backend or _JNP
    if backend.inline:
        qv = jnp.einsum("qd,qmd->qm", queries, vecs,
                        precision=jax.lax.Precision.HIGHEST,
                        preferred_element_type=jnp.float32)
        return qq[:, None] - 2.0 * qv + vnorm
    Q = queries.shape[0]
    out = backend.paged_distance(
        jnp.arange(Q, dtype=jnp.int32), queries[:, None, :], qq[:, None],
        vecs, vnorm)                                       # (Q, 1, M)
    return out[:, 0, :]


# ---------------------------------------------------------------------------
# Single-shard search
# ---------------------------------------------------------------------------
def init_state(db, vnorm, queries, entry, params: SearchParams) -> TraversalState:
    Q = queries.shape[0]
    L = params.L
    qq = jnp.sum(queries * queries, axis=-1)
    e_ids = jnp.full((Q, 1), entry, dtype=jnp.int32)
    e_d = squared_dists(queries, qq, db[e_ids], vnorm[e_ids])  # (Q, 1)
    cand_d = jnp.concatenate(
        [e_d, jnp.full((Q, L - 1), BIG_DIST, jnp.float32)], axis=1)
    cand_i = jnp.concatenate(
        [e_ids, jnp.full((Q, L - 1), ID_SENTINEL, jnp.int32)], axis=1)
    cand_e = jnp.zeros((Q, L), dtype=bool)
    bloom = jnp.zeros((Q, params.bloom_words), dtype=jnp.uint32)
    bloom = bloom_insert(bloom, e_ids, jnp.ones((Q, 1), dtype=bool))
    zeros = jnp.zeros((Q,), jnp.int32)
    return TraversalState(cand_d, cand_i, cand_e, bloom, zeros.astype(bool),
                          zeros, zeros, zeros, jnp.int32(0))


@functools.partial(jax.jit,
                   static_argnames=("params", "page_size", "kernel_mode",
                                    "coalesce_qb"))
def search(db: jax.Array, adj: jax.Array, vnorm: jax.Array,
           queries: jax.Array, entry, params: SearchParams,
           page_size: int = 256, kernel_mode: str = "jnp",
           coalesce_qb: int = 8):
    """Batched best-first search on a single shard.

    db (N,d) f32 | adj (N,R) i32 INVALID-padded | vnorm (N,) f32 | queries
    (Q,d) f32. Returns (ids (Q,k) i32, dists (Q,k) f32, stats dict).

    ``kernel_mode`` selects the backend for the distance + merge hot
    paths: the default inline ``jnp`` path, or the SiN/bitonic kernels
    (``ref``/``interpret``/``pallas``/``auto``) on the page-granular view
    of ``db`` — identical results, proven bit-exact on integer vectors.
    ``coalesce_qb`` sets the per-page query-tile width in kernel modes
    (0 = one page read per assignment; see KernelBackend).
    """
    backend = KernelBackend(mode=kernel_mode, coalesce_qb=coalesce_qb)
    Q, d = queries.shape
    L, W, R = params.L, params.W, adj.shape[1]
    qq = jnp.sum(queries * queries, axis=-1)
    n = db.shape[0]
    if not backend.inline:
        db_pg, vnorm_pg = paged_view(db, vnorm, page_size)

    def round_fn(state: TraversalState) -> TraversalState:
        sel_ids, sel_valid, cand_e = select_expand(
            state.cand_d, state.cand_i, state.cand_e, W)
        active = ~state.done
        sel_valid &= active[:, None]
        # fetch neighbors of the selected entries
        safe_sel = jnp.clip(sel_ids, 0, n - 1)
        nbrs = adj[safe_sel]                               # (Q, W, R)
        nbrs = nbrs.reshape(Q, W * R)
        valid = (nbrs != INVALID) & jnp.repeat(sel_valid, R, axis=1)
        valid = dedup_in_round(nbrs, valid)
        valid &= ~bloom_query(state.bloom, nbrs)
        # distance computation — the SiN kernel point. Inline mode is the
        # local gather + dot; kernel modes issue page reads on the paged
        # view of db (page-sorted, coalesced into per-page query tiles).
        safe = jnp.clip(nbrs, 0, n - 1)
        if backend.inline:
            dists = squared_dists(queries, qq, db[safe], vnorm[safe])
        else:
            qidx = jnp.repeat(jnp.arange(Q, dtype=jnp.int32), nbrs.shape[1])
            flat = safe.reshape(-1)
            dists = backend.item_distances(
                flat // page_size, flat % page_size, valid.reshape(-1),
                queries[qidx], qq[qidx], db_pg, vnorm_pg).reshape(nbrs.shape)
        dists = jnp.where(valid, dists, BIG_DIST)
        bloom = bloom_insert(state.bloom, nbrs, valid)
        cand_d, cand_i, cand_e = merge_candidates(
            state.cand_d, state.cand_i, cand_e, dists, nbrs, valid, L,
            backend=backend)
        # freeze finished queries
        keep = state.done
        cand_d = jnp.where(keep[:, None], state.cand_d, cand_d)
        cand_i = jnp.where(keep[:, None], state.cand_i, cand_i)
        cand_e = jnp.where(keep[:, None], state.cand_e, cand_e)
        bloom = jnp.where(keep[:, None], state.bloom, bloom)
        worked = active
        rounds = state.rounds + worked.astype(jnp.int32)
        n_dist = state.n_dist + jnp.where(worked, valid.sum(-1), 0).astype(jnp.int32)
        page_acc = state.page_acc + jnp.where(
            worked, count_unique_pages(nbrs, valid, page_size), 0).astype(jnp.int32)
        done = state.done | ~((~cand_e) & (cand_i != ID_SENTINEL)).any(axis=1)
        return TraversalState(cand_d, cand_i, cand_e, bloom, done,
                              rounds, n_dist, page_acc, state.t + 1)

    def cond_fn(state: TraversalState):
        return (~state.done).any() & (state.t < params.rounds_cap)

    state0 = init_state(db, vnorm, queries, entry, params)
    # the entry vertex starts unexpanded; done is false unless L == 0
    state = jax.lax.while_loop(cond_fn, round_fn, state0)

    k = params.k
    out_i = jnp.where(state.cand_i[:, :k] != ID_SENTINEL,
                      state.cand_i[:, :k], INVALID)
    out_d = state.cand_d[:, :k]
    stats = {
        "rounds": state.rounds,
        "n_dist": state.n_dist,
        "page_accesses": state.page_acc,
        "total_rounds": state.t,
    }
    return out_i, out_d, stats


def gather_baseline_bytes(params: SearchParams, d: int, dtype_bytes: int = 4,
                          R: int = 32) -> dict:
    """Napkin traffic model of one expansion, for the filtering claim.

    'gather' = SmartSSD-only-like design: move R full vectors to the query.
    'ndsearch' = move the query vector + ids out, scalar dists back.
    """
    gather = R * d * dtype_bytes
    ndsearch = d * dtype_bytes + R * 4 + R * 4
    return {"gather_bytes": gather, "ndsearch_bytes": ndsearch,
            "filter_ratio": gather / ndsearch}
