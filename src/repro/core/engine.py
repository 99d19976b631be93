"""Distributed NDSearch engine (§IV dataflow + §V processing model).

Queries live on their *home* shard (the paper's SSD-controller query
property table, made SPMD); vectors + adjacency live sharded across all
devices ("LUN groups"). One search round is the paper's Allocating ->
Searching -> Gathering pipeline:

  phase A (Vgenerator): route the ids of the best-W unexpanded candidates
      to their owner shards (all_to_all); owners return adjacency rows
      (+ speculative 2nd-order prefetch lists) from the sharded LUNCSR.
  phase B (Allocator + SiN): bucket (query vec, candidate id) assignments
      by candidate owner with bounded capacity (dropped-on-overflow ==
      bounded LUN queues), all_to_all; owners translate logical id ->
      physical (page, slot) via blk_perm arithmetic (no FTL translation),
      compute distances where the vectors live, and return *scalar*
      distances ("filtering") — or, in `gather_vectors` baseline mode,
      the raw feature vectors (the SmartSSD-only/DiskANN-host design the
      paper compares against; same results, ~R*d/(d+2R) times the bytes).
  merge (Gather + Sort): bloom-insert computed proposals, bitonic-merge
      into candidate lists, refresh termination mask.

The stage functions ``_fa_select`` / ``_fb_adjacency`` (phase A),
``_fc_propose`` (phase B's proposals) and ``_fe_merge`` (the merge) are
shared by two drivers:

  * ``search_sim``          — the shard axis is a leading array axis;
                              all_to_all == swapaxes. Runs on one device.
                              The distance exchange runs over the
                              round's own proposals: one distance call
                              over every shard's flat proposal list,
                              the owner folded into the page key, no
                              per-destination buckets
                              (:func:`_fd_distance_flat`).
  * ``search_distributed``  — shard_map over a 1-D "lun" mesh with
                              lax.all_to_all. Multi-device SPMD; real
                              collectives need fixed per-destination
                              buffers, so ``_fc_bucket`` /
                              ``_fd_distance`` / ``_fe_gather`` post
                              ``capacity_b``-slot buckets (as the sim
                              driver still does for the tiered store
                              and the ``gather_vectors`` baseline).

``capacity_b`` bounds what one source may send one owner in a round
(proposals ranked beyond it are dropped and counted in ``drops_b``) on
both drivers; on the sim driver over a resident store it sizes no
buffer.

Equality sim == distributed == single-shard traversal (lossless capacity,
spec off) is tested in tests/test_engine*.py.

Hot paths dispatch through ``EngineParams.kernel_mode`` (a
:class:`repro.core.backend.KernelBackend`): phase-B distances become
paged SiN kernel reads grouped by physical page, and the merge runs the
bitonic network — or the inline jnp equivalents in ``jnp`` mode. All
modes are bit-identical on integer-valued vectors
(tests/test_backend_dispatch.py).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.backend import KernelBackend
from repro.core.dispatch import (bucket_mask, compute_ranks,
                                 gather_from_buckets, scatter_to_buckets)
from repro.core.luncsr import PackedIndex
from repro.core.ref_search import SearchParams
from repro.core.traversal import (ID_SENTINEL, dedup_in_round,
                                  merge_candidates, select_expand)
from repro.ft import inject as ftinject
from repro.ft.guard import quarantine_distances
from repro.ft.inject import NEVER, FaultSpec
from repro.utils import BIG_DIST, bloom_insert, bloom_query

INVALID = -1


@dataclasses.dataclass(frozen=True)
class EngineGeom:
    """Static placement arithmetic (the Allocator's address generator)."""

    num_shards: int
    page_size: int
    pages_per_block: int
    pages_per_shard: int
    dim: int
    max_degree: int
    spec_stored: int
    n: int
    stripe: str = "striped"

    @staticmethod
    def from_packed(packed: PackedIndex) -> "EngineGeom":
        g = packed.geometry
        return EngineGeom(
            num_shards=g.num_shards, page_size=g.page_size,
            pages_per_block=g.pages_per_block,
            pages_per_shard=packed.pages_per_shard, dim=packed.db.shape[-1],
            max_degree=packed.max_degree, spec_stored=packed.pref.shape[-1],
            n=packed.n, stripe=g.stripe)

    def owner(self, vid):
        gp = vid // self.page_size
        if self.stripe == "striped":
            return (gp % self.num_shards).astype(jnp.int32)
        return (gp // self.pages_per_shard).astype(jnp.int32)

    def local_page(self, vid):
        gp = vid // self.page_size
        if self.stripe == "striped":
            return gp // self.num_shards
        return gp % self.pages_per_shard

    def logical_slot(self, vid):
        return self.local_page(vid) * self.page_size + vid % self.page_size

    def phys_page(self, vid, blk_perm, owner=None):
        """Physical page of ``vid`` on its owner: ``blk_perm`` is the
        owner's (B,) block permutation, or all shards' (S, B) with
        ``owner`` the per-item shard index."""
        lpage = self.local_page(vid)
        blk = lpage // self.pages_per_block
        pib = lpage % self.pages_per_block
        blk = jnp.clip(blk, 0, blk_perm.shape[-1] - 1)
        base = blk_perm[blk] if owner is None else blk_perm[owner, blk]
        return base * self.pages_per_block + pib


@dataclasses.dataclass(frozen=True)
class EngineParams:
    """Static engine configuration."""

    search: SearchParams
    capacity_a: int                 # phase-A request slots per destination
    capacity_b: int                 # proposals one source may send one
                                    # owner a round (more are dropped,
                                    # drops_b); sizes the shard_map
                                    # driver's buckets, no buffer on the
                                    # sim driver over a resident store
    sort_by_page: bool = True       # dynamic allocating (page-locality stats)
    spec_width: int = 0             # 2nd-order speculative prefetch width
    gather_vectors: bool = False    # baseline: move vectors, not distances
    payload_bf16: bool = False      # halve a2a bytes: bf16 query payloads
    kernel_mode: str = "jnp"        # hot-path backend: auto|pallas|interpret
                                    # |ref|jnp (core/backend.py)
    coalesce_qb: int = 8            # per-page query-tile width in kernel
                                    # modes: one page read serves up to
                                    # this many assignments (0 = per-item)
    local_only: bool = False        # routed legs: drop proposals owned by
                                    # other shards, so a slot row traverses
                                    # only its home shard's subgraph
                                    # (core/router.py two-tier search)
    deadline_rounds: int = 0        # force-retire a row once it has aged
                                    # this many serving-clock rounds since
                                    # admission (best-so-far top-k, the
                                    # `truncated` flag set); 0 = no
                                    # deadline — bit-identical schedules
    guard_nonfinite: bool = False   # quarantine corrupt (NaN/-inf-ish)
                                    # phase-B distances to BIG_DIST and
                                    # count them instead of letting them
                                    # enter the bitonic merge (ft/guard.py)
    faults: FaultSpec | None = None  # deterministic fault plan (ft/
                                    # inject.py): shard kills/delays apply
                                    # at in-jit round boundaries (admission
                                    # path only), page corruption in the
                                    # phase-B distance read. None compiles
                                    # zero extra ops.
    store_pages: int = 0            # tiered page store (core/pagestore.py):
                                    # logical pages per shard when the
                                    # phase-B distance read goes through a
                                    # residency translation table
                                    # (consts["ttab"]) into a fixed-
                                    # capacity device frame buffer — a
                                    # non-resident page stalls its owner
                                    # queries for the round instead of
                                    # reading garbage. 0 = device-resident
                                    # store, zero extra ops (bit-identical
                                    # to every pre-tiered path).
    delta_cap: int = 0              # live index (core/live.py): rows of
                                    # the append-only delta segment that
                                    # retirement brute-force-scans
                                    # alongside the main candidate list,
                                    # after masking tombstoned ids. The
                                    # delta/tombstone consts are traced
                                    # arrays of fixed shape, so inserts,
                                    # deletes and epoch swaps never
                                    # change the stepper signature.
                                    # 0 = frozen index, zero extra ops
                                    # (byte-identical traces).

    @property
    def backend(self) -> KernelBackend:
        return KernelBackend(mode=self.kernel_mode,
                             coalesce_qb=self.coalesce_qb)

    @staticmethod
    def lossless(search: SearchParams, queries_per_shard: int,
                 max_degree: int, spec_width: int = 0,
                 **kw) -> "EngineParams":
        """Capacities that can never overflow (for exactness tests)."""
        m = queries_per_shard * search.W * (max_degree + spec_width)
        return EngineParams(
            search=search,
            capacity_a=queries_per_shard * search.W,
            capacity_b=m, spec_width=spec_width, **kw)


class EngineState(NamedTuple):
    cand_d: jax.Array    # (Qs, L)
    cand_i: jax.Array    # (Qs, L)
    cand_e: jax.Array    # (Qs, L)
    bloom: jax.Array     # (Qs, W32)
    done: jax.Array      # (Qs,)
    rounds: jax.Array    # (Qs,)  rounds the row actually worked
    n_dist: jax.Array    # (Qs,)
    age: jax.Array       # (Qs,)  serving-clock rounds since admission —
                         # advances even while the row's shard is
                         # stalled (== rounds when nothing ever stalls)
    deadline: jax.Array  # (Qs,)  age at which the row is force-retired
                         # (NEVER when no deadline is configured)
    truncated: jax.Array  # (Qs,) bool — retired by deadline with its
                          # best-so-far top-k, not by convergence
    items_recv: jax.Array    # () items received by this shard's SiN
    distance_lanes: jax.Array  # () query lanes the distance stage
                               # computed (tiles x tile width, static per
                               # round); the sim driver's one stage for
                               # all shards books its lanes on shard 0
    pages_unique: jax.Array  # () unique page reads (dynamic allocating)
    drops_b: jax.Array       # () phase-B overflow drops at this source
    props_sent: jax.Array    # () accepted proposals sent by this source
    quarantined: jax.Array   # () corrupt distances quarantined to
                             # BIG_DIST by the guard (guard_nonfinite)
    page_touch: jax.Array    # (store_pages,) bool — logical pages this
                             # shard served from resident frames since
                             # the last chunk boundary ((0,) when the
                             # tiered store is off)
    page_miss: jax.Array     # (store_pages,) bool — logical pages
                             # demanded but not resident (the demand-
                             # fetch set the scheduler serves at the
                             # next chunk boundary)


# ---------------------------------------------------------------------------
# Stage functions — all operate on one shard's local arrays.
# ---------------------------------------------------------------------------
def _init_state(queries, qq, entry_vec, entry_norm, entry_id,
                params: EngineParams) -> EngineState:
    sp = params.search
    Qs = queries.shape[0]
    L = sp.L
    # multiply+reduce, not `@`: XLA lowers a dot differently standalone
    # (engine_admit) vs inside a while_loop body (the in-chunk admission
    # stage), which costs 1 ULP of cross-path bit-identity on real-
    # valued data; an explicit reduction lowers the same way in both
    e_d = (qq - 2.0 * jnp.sum(queries * entry_vec.astype(jnp.float32),
                              axis=-1)
           + entry_norm)                                   # (Qs,)
    cand_d = jnp.concatenate(
        [e_d[:, None], jnp.full((Qs, L - 1), BIG_DIST, jnp.float32)], axis=1)
    cand_i = jnp.concatenate(
        [jnp.full((Qs, 1), entry_id, jnp.int32),
         jnp.full((Qs, L - 1), ID_SENTINEL, jnp.int32)], axis=1)
    cand_e = jnp.zeros((Qs, L), dtype=bool)
    bloom = jnp.zeros((Qs, sp.bloom_words), dtype=jnp.uint32)
    bloom = bloom_insert(bloom, cand_i[:, :1],
                         jnp.ones((Qs, 1), dtype=bool))
    z = jnp.zeros((Qs,), jnp.int32)
    zs = jnp.int32(0)
    dl = params.deadline_rounds if params.deadline_rounds > 0 else NEVER
    pz = jnp.zeros((params.store_pages,), bool)
    return EngineState(cand_d, cand_i, cand_e, bloom, z.astype(bool),
                       z, z, z, jnp.full((Qs,), dl, jnp.int32),
                       z.astype(bool), zs, zs, zs, zs, zs, zs, pz, pz)


def _fa_select(state: EngineState, params: EngineParams, geom: EngineGeom):
    """Select W best unexpanded; bucket their ids by owner (phase A send)."""
    sp = params.search
    sel_ids, sel_valid, cand_e2 = select_expand(
        state.cand_d, state.cand_i, state.cand_e, sp.W)
    sel_valid &= ~state.done[:, None]
    vid = sel_ids.reshape(-1)                      # (Qs*W,)
    valid = sel_valid.reshape(-1)
    safe = jnp.clip(vid, 0, geom.n - 1)
    dest = jnp.where(valid, geom.owner(safe), 0)
    rank, _ = compute_ranks(dest, valid, geom.num_shards)
    valid &= rank < params.capacity_a              # lossless by default
    send = {
        "vid": scatter_to_buckets(dest, rank, valid, vid,
                                  geom.num_shards, params.capacity_a,
                                  fill=INVALID),
        "mask": bucket_mask(dest, rank, valid, geom.num_shards,
                            params.capacity_a),
    }
    keep = {"dest": dest, "rank": rank, "valid": valid, "cand_e2": cand_e2}
    return send, keep


def _fb_adjacency(recv, adj, pref, params: EngineParams, geom: EngineGeom):
    """Owner: serve adjacency rows (+ prefetch lists) for requested ids."""
    vid = recv["vid"]                              # (S, C_A)
    mask = recv["mask"]
    safe = jnp.clip(vid, 0, geom.n - 1)
    lslot = jnp.clip(geom.logical_slot(safe), 0, adj.shape[0] - 1)
    nbrs = jnp.where(mask[..., None], adj[lslot], INVALID)
    send = {"nbrs": nbrs}
    if params.spec_width > 0:
        pr = pref[lslot][..., :params.spec_width]
        send["pref"] = jnp.where(mask[..., None], pr, INVALID)
    return send


def _fc_propose(state: EngineState, keep_a, recv_b, spec_w, my_shard,
                params: EngineParams, geom: EngineGeom):
    """Build proposals, dedup + bloom-filter, rank them by owner.

    ``spec_w`` is the *dynamic* speculation width — a traced i32, scalar
    or per-query (Qs,), in [0, params.spec_width]. Shapes stay static at
    the configured maximum; prefetch columns at or beyond a query's
    width are masked to INVALID, which is bit-identical to running that
    query at the smaller static width (masked proposals never survive
    dedup/ranking). The streaming scheduler's controller shrinks each
    query's width as its own hit rate decays, without recompiling.

    ``my_shard`` is this shard's index, only read when
    ``params.local_only`` — routed legs drop every proposal owned by
    another shard *before* ranking, so a leg's traversal (and all of
    its phase-D distance work) stays on its home shard and an idle
    shard receives nothing. With ``local_only=False`` the mask is never
    built and the stage is bit-for-bit the fan-out stage.

    A proposal is sent (``ok``) when its rank among this source's
    proposals to the same owner is below ``capacity_b``; the rest are
    dropped and counted. :func:`_fc_bucket` lays the sent ones out in
    per-owner buckets for the shard_map exchange.
    """
    sp = params.search
    Qs = state.done.shape[0]
    W, R = sp.W, geom.max_degree
    nbrs = gather_from_buckets(recv_b["nbrs"], keep_a["dest"],
                               keep_a["rank"], keep_a["valid"],
                               params.capacity_a)       # (Qs*W, R)
    nbrs = jnp.where(keep_a["valid"][:, None], nbrs, INVALID)
    props = nbrs.reshape(Qs, W * R)
    if params.spec_width > 0:
        pr = gather_from_buckets(recv_b["pref"], keep_a["dest"],
                                 keep_a["rank"], keep_a["valid"],
                                 params.capacity_a)
        pr = jnp.where(keep_a["valid"][:, None], pr, INVALID)
        pr = pr.reshape(Qs, W * params.spec_width)
        col = (jnp.arange(W * params.spec_width, dtype=jnp.int32)
               % params.spec_width)                     # col within group
        keep_col = col[None, :] < jnp.broadcast_to(
            jnp.asarray(spec_w, jnp.int32), (Qs,))[:, None]
        props = jnp.concatenate(
            [props, jnp.where(keep_col, pr, INVALID)], axis=1)
    valid = props != INVALID
    valid = dedup_in_round(props, valid)
    valid &= ~bloom_query(state.bloom, props)

    flat_vid = props.reshape(-1)
    flat_valid = valid.reshape(-1)
    safe = jnp.clip(flat_vid, 0, geom.n - 1)
    own = geom.owner(safe)
    if params.local_only:
        flat_valid &= own == jnp.asarray(my_shard, jnp.int32)
    dest = jnp.where(flat_valid, own, 0)
    rank, _ = compute_ranks(dest, flat_valid, geom.num_shards)
    ok = flat_valid & (rank < params.capacity_b)
    drops = (flat_valid & ~ok).sum().astype(jnp.int32)
    return {"dest": dest, "rank": rank, "ok": ok, "props": props,
            "valid": valid, "drops": drops}


def _fc_bucket(keep_c, queries, qq, params: EngineParams,
               geom: EngineGeom):
    """Phase-C send for the bucketed exchange: the sent proposals (and
    their query payloads) scattered into (S, capacity_b) per-owner
    buckets."""
    Qs, M = keep_c["props"].shape
    dest, rank, ok = keep_c["dest"], keep_c["rank"], keep_c["ok"]
    S, C = geom.num_shards, params.capacity_b
    send = {
        "vid": scatter_to_buckets(dest, rank, ok, keep_c["props"].reshape(-1),
                                  S, C, fill=INVALID),
        "mask": bucket_mask(dest, rank, ok, S, C),
    }
    if not params.gather_vectors:
        qidx = jnp.repeat(jnp.arange(Qs, dtype=jnp.int32), M)
        qpay = queries[qidx]
        if params.payload_bf16:
            qpay = qpay.astype(jnp.bfloat16)
        send["qvec"] = scatter_to_buckets(dest, rank, ok, qpay, S, C)
        send["qq"] = scatter_to_buckets(dest, rank, ok, qq[qidx], S, C)
    return send


def _fd_distance(recv, db, vnorm, blk_perm, my_shard,
                 params: EngineParams, geom: EngineGeom, ttab=None):
    """Owner SiN: translate id -> physical page/slot, compute distances.

    In gather_vectors mode returns the raw vectors instead (baseline).
    Also counts page-buffer statistics: unique pages (dynamic allocating
    shares a page read across assignments) vs raw items (no sharing).

    ``my_shard`` is this shard's index — only read when a fault plan
    with page corruption is configured, to salt the deterministic
    bad-page hash (ft/inject.py): a corrupted read returns NaN or a
    huge-negative distance exactly as damaged media would, on every
    visit to that page. Corruption models the SiN distance read path,
    so the gather_vectors baseline is exempt.

    With the tiered page store (``params.store_pages > 0``) ``db`` /
    ``vnorm`` are the fixed-capacity device *frame* buffers and
    ``ttab`` the (store_pages,) residency translation table: the read
    goes through :meth:`KernelBackend.translated_item_distances`, a
    ``"miss"`` lane rides the reply so the requester can stall queries
    that demanded a cold page, and the stage additionally returns the
    shard's per-chunk page touch/miss bitmaps (the prefetcher's demand
    + hit-accounting signal). An identity table over a full store is
    bit-identical to the untranslated read.
    """
    vid = recv["vid"]                              # (S, C_B)
    mask = recv["mask"]
    S, C = vid.shape
    flat_vid = jnp.clip(vid.reshape(-1), 0, geom.n - 1)
    flat_mask = mask.reshape(-1)
    ppage = geom.phys_page(flat_vid, blk_perm)
    npages = params.store_pages if params.store_pages else db.shape[0]
    ppage = jnp.clip(ppage, 0, npages - 1)
    slot = flat_vid % geom.page_size

    items = flat_mask.sum().astype(jnp.int32)
    sorted_pages = jnp.sort(jnp.where(flat_mask, ppage, jnp.int32(2**30)))
    first = jnp.concatenate(
        [jnp.ones((1,), bool), sorted_pages[1:] != sorted_pages[:-1]])
    uniq = (first & (sorted_pages != 2**30)).sum().astype(jnp.int32)

    if params.store_pages:
        if params.gather_vectors:
            raise NotImplementedError(
                "the gather_vectors baseline moves raw vectors, not "
                "page reads — it has no tiered page store")
        dist, resident = params.backend.translated_item_distances(
            ttab, ppage, slot, flat_mask, recv["qvec"].reshape(S * C, -1),
            recv["qq"].reshape(-1), db, vnorm)
        if params.faults is not None and params.faults.any_corrupt:
            bad = ftinject.bad_page_mask(params.faults, ppage, my_shard)
            dist = jnp.where(bad & flat_mask,
                             ftinject.corrupt_value(params.faults), dist)
        missed = flat_mask & ~resident
        send = {"dist": dist.reshape(S, C), "miss": missed.reshape(S, C)}
        # per-chunk page bitmaps: scatter True at the touched/missed
        # logical pages (masked lanes write OOB and drop)
        touch = jnp.zeros((npages,), bool).at[
            jnp.where(flat_mask & resident, ppage, npages)].set(
            True, mode="drop")
        pmiss = jnp.zeros((npages,), bool).at[
            jnp.where(missed, ppage, npages)].set(True, mode="drop")
        return send, items, uniq, touch, pmiss

    if params.gather_vectors:
        v = db[ppage, slot].astype(jnp.float32)    # (S*C, d)
        vn = vnorm[ppage, slot]
        send = {"vec": jnp.where(flat_mask[:, None], v, 0.0).reshape(S, C, -1),
                "vn": jnp.where(flat_mask, vn, 0.0).reshape(S, C)}
    else:
        dist = params.backend.item_distances(
            ppage, slot, flat_mask, recv["qvec"].reshape(S * C, -1),
            recv["qq"].reshape(-1), db, vnorm)
        if params.faults is not None and params.faults.any_corrupt:
            bad = ftinject.bad_page_mask(params.faults, ppage, my_shard)
            dist = jnp.where(bad & flat_mask,
                             ftinject.corrupt_value(params.faults), dist)
        send = {"dist": dist.reshape(S, C)}
    return send, items, uniq


def _fd_distance_flat(keep_c, queries, qq, db, vnorm, blk_perm,
                      params: EngineParams, geom: EngineGeom):
    """Phase D of every shard at once (sim driver, resident store): one
    :meth:`KernelBackend.item_distances` call over the flat
    (S*Qs*M,) list of all shards' proposals, against the (S*NP, P, d)
    view of the shard-major store, with the owner folded into the page
    key (``owner * NP + page``). Query tiles are packed from
    ``queries[s, q]`` directly; nothing is bucketed.

    ``keep_c`` leaves, ``queries`` (S, Qs, d) and ``qq`` (S, Qs) carry
    the shard axis. Returns (dist (S, Qs*M) in proposal order, BIG_DIST
    where not sent; per-owner items (S,) and unique pages (S,), equal
    to the bucketed :func:`_fd_distance`'s counts; distance lanes (S,),
    the stage's static count booked on shard 0).
    """
    S, Qs, d = queries.shape
    NP, P = vnorm.shape[1:]
    M = keep_c["props"].shape[-1]
    ok = keep_c["ok"].reshape(-1)
    vid = jnp.clip(keep_c["props"].reshape(-1), 0, geom.n - 1)
    owner = keep_c["dest"].reshape(-1)         # == geom.owner where ok
    ppage = jnp.clip(geom.phys_page(vid, blk_perm, owner), 0, NP - 1)
    gpage = owner * NP + ppage
    qrow = jnp.repeat(jnp.arange(S * Qs, dtype=jnp.int32), M)
    qvec = queries.reshape(S * Qs, d)[qrow]
    if params.payload_bf16:
        qvec = qvec.astype(jnp.bfloat16)
    dist = params.backend.item_distances(
        gpage, vid % geom.page_size, ok, qvec, qq.reshape(-1)[qrow],
        db.reshape(S * NP, P, d), vnorm.reshape(S * NP, P))
    if params.faults is not None and params.faults.any_corrupt:
        bad = ftinject.bad_page_mask(params.faults, ppage, owner)
        dist = jnp.where(bad & ok, ftinject.corrupt_value(params.faults),
                         dist)
    items = ((owner[:, None] == jnp.arange(S, dtype=jnp.int32))
             & ok[:, None]).sum(axis=0).astype(jnp.int32)
    touched = jnp.zeros((S * NP,), bool).at[
        jnp.where(ok, gpage, S * NP)].set(True, mode="drop")
    uniq = touched.reshape(S, NP).sum(axis=1).astype(jnp.int32)
    lanes = jnp.zeros((S,), jnp.int32).at[0].set(
        params.backend.distance_lanes(ok.shape[0], S * NP))
    return dist.reshape(S, Qs * M), items, uniq, lanes


def _fe_gather(recv_d, keep_c, queries, qq, params: EngineParams):
    """Requester, bucketed exchange: each proposal's reply out of its
    owner's bucket, in proposal order — (dist (Qs*M,), miss (Qs*M,)
    bool or None). In the gather_vectors baseline the requester
    computes the distances from the returned vectors."""
    dest, rank, ok = keep_c["dest"], keep_c["rank"], keep_c["ok"]
    C = params.capacity_b
    if params.gather_vectors:
        vec = gather_from_buckets(recv_d["vec"], dest, rank, ok, C)
        vn = gather_from_buckets(recv_d["vn"], dest, rank, ok, C)
        Qs, M = keep_c["props"].shape
        qidx = jnp.repeat(jnp.arange(Qs, dtype=jnp.int32), M)
        qv = jnp.sum(queries[qidx].astype(jnp.float32) * vec, axis=-1)
        dist = qq[qidx] - 2.0 * qv + vn
    else:
        dist = gather_from_buckets(recv_d["dist"], dest, rank, ok, C)
    miss = (gather_from_buckets(recv_d["miss"], dest, rank, ok, C)
            if params.store_pages else None)
    return dist, miss


def _bucketed_lanes(params: EngineParams, geom: EngineGeom, npages: int,
                    Qs: int, M: int) -> int:
    """Static distance lanes one shard's bucketed phase D computes: its
    S*capacity_b receive slots through ``item_distances``, or, in the
    gather_vectors baseline, the requester's Qs*M per-item dots."""
    if params.gather_vectors:
        return Qs * M
    return params.backend.distance_lanes(
        geom.num_shards * params.capacity_b, npages)


def _fe_merge(state: EngineState, keep_a, keep_c, dist, items, uniq,
              lanes, miss=None, page_touch=None, page_miss=None,
              params: EngineParams = None, geom: EngineGeom = None):
    """Requester: bloom-insert, merge, re-terminate.

    ``dist`` holds each proposal's distance in proposal order (Qs*M,),
    read off the flat phase-D result on the sim driver or out of the
    reply buckets (:func:`_fe_gather`); ``items`` / ``uniq`` / ``lanes``
    are this shard's phase-D counts.

    Tiered store (``params.store_pages > 0``): ``miss`` marks
    assignments whose page was not device-resident. A query
    with any missed assignment **stalls** — its entire round is masked
    exactly like a ``done`` row's (candidates, bloom, rounds, n_dist
    all restored), so next round it re-selects the same frontier and
    re-proposes the same set, by which time the scheduler has demand-
    fetched the page at the chunk boundary. Stalled rounds show up as
    ``age - rounds`` (the serving clock advances, the work clock does
    not). ``page_touch`` / ``page_miss`` are this shard's stage-D
    bitmaps, OR-accumulated into the state for the boundary fetcher.
    """
    Qs, L = state.cand_d.shape
    props = keep_c["props"]                        # (Qs, M)
    M = props.shape[1]
    accepted = keep_c["ok"].reshape(Qs, M)
    dist = jnp.where(accepted, dist.reshape(Qs, M), BIG_DIST)
    if params.store_pages:
        # any missed page stalls the whole query for the round: mask it
        # like a done row (state restored below) so it retries the
        # identical round after the boundary fetch. A live row always
        # has an unexpanded candidate (else it would be done), so a
        # stalled row can never be re-terminated by the done update.
        stall = ((miss.reshape(Qs, M) & accepted).any(axis=1)
                 & ~state.done)
        keep = state.done | stall
        acc_eff = accepted & ~stall[:, None]
    else:
        keep = state.done
        acc_eff = accepted
    quar = jnp.int32(0)
    if params.guard_nonfinite:
        # corrupt reads become worthless-but-harmless candidates: they
        # still count as accepted proposals (the read happened) but a
        # BIG_DIST entry can never displace a real one in the merge
        dist, quar = quarantine_distances(dist, acc_eff, BIG_DIST)

    bloom = bloom_insert(state.bloom, props, accepted)
    cand_d, cand_i, cand_e = merge_candidates(
        state.cand_d, state.cand_i, keep_a["cand_e2"], dist, props,
        accepted, L, backend=params.backend)
    worked = ~keep
    cand_d = jnp.where(keep[:, None], state.cand_d, cand_d)
    cand_i = jnp.where(keep[:, None], state.cand_i, cand_i)
    cand_e = jnp.where(keep[:, None], state.cand_e, cand_e)
    bloom = jnp.where(keep[:, None], state.bloom, bloom)
    rounds = state.rounds + worked.astype(jnp.int32)
    n_dist = state.n_dist + jnp.where(worked, acc_eff.sum(-1), 0
                                      ).astype(jnp.int32)
    done = state.done | ~((~cand_e) & (cand_i != ID_SENTINEL)).any(axis=1)
    if params.store_pages:
        p_touch = state.page_touch | page_touch
        p_miss = state.page_miss | page_miss
    else:
        p_touch, p_miss = state.page_touch, state.page_miss
    return EngineState(
        cand_d, cand_i, cand_e, bloom, done, rounds, n_dist,
        state.age, state.deadline, state.truncated,
        state.items_recv + items, state.distance_lanes + lanes,
        state.pages_unique + uniq, state.drops_b + keep_c["drops"],
        state.props_sent + acc_eff.sum().astype(jnp.int32),
        state.quarantined + quar, p_touch, p_miss)


# ---------------------------------------------------------------------------
# Round body, parameterized by the communication primitive.
# ---------------------------------------------------------------------------
def _round(state, consts, params: EngineParams, geom: EngineGeom, a2a,
           spec_w=None, my_shard=None):
    if params.store_pages:
        # residency translation + boundary fetches are wired through the
        # sim stepper (core/scheduler.py StreamScheduler(pagestore=...));
        # the shard_map leg keeps the device-resident store
        raise NotImplementedError(
            "tiered page store (store_pages > 0) runs on the sim "
            "driver only")
    if spec_w is None:
        spec_w = jnp.int32(params.spec_width)
    if my_shard is None:
        my_shard = jnp.int32(0)
    queries, qq = consts["queries"], consts["qq"]
    send_a, keep_a = _fa_select(state, params, geom)
    recv_a = a2a(send_a)
    send_b = _fb_adjacency(recv_a, consts["adj"], consts["pref"],
                           params, geom)
    recv_b = a2a(send_b)
    keep_c = _fc_propose(state, keep_a, recv_b, spec_w, my_shard, params,
                         geom)
    recv_c = a2a(_fc_bucket(keep_c, queries, qq, params, geom))
    send_d, items, uniq = _fd_distance(recv_c, consts["db"], consts["vnorm"],
                                       consts["blk_perm"], my_shard, params,
                                       geom)
    dist, _ = _fe_gather(a2a(send_d), keep_c, queries, qq, params)
    lanes = _bucketed_lanes(params, geom, consts["db"].shape[0],
                            *keep_c["props"].shape)
    return _fe_merge(state, keep_a, keep_c, dist, items, uniq,
                     jnp.int32(lanes), params=params, geom=geom)


def _finalize(state: EngineState, k: int):
    out_i = jnp.where(state.cand_i[:, :k] != ID_SENTINEL,
                      state.cand_i[:, :k], INVALID)
    out_d = state.cand_d[:, :k]
    stats = {
        "rounds": state.rounds, "n_dist": state.n_dist,
        "items_recv": state.items_recv,
        "distance_lanes": state.distance_lanes,
        "pages_unique": state.pages_unique,
        "drops_b": state.drops_b, "props_sent": state.props_sent,
        "truncated": state.truncated, "quarantined": state.quarantined,
    }
    return out_i, out_d, stats


def _finalize_live(state: EngineState, queries, tombs, delta_vec,
                   delta_norm, delta_live, k: int):
    """Live-index retire (one shard): mask tombstones, merge the delta.

    Three steps, each chosen so a zero-churn session stays bit-identical
    to :func:`_finalize`:

      1. tombstoned candidates are **stable-partitioned** to the back of
         the full length-L list (all-False flags -> identity permutation)
         and overwritten with (ID_SENTINEL, BIG_DIST), so a leaked
         tombstone can never survive in the first k;
      2. the delta segment is brute-force scanned with the same
         mul+reduce distance expression as :func:`_init_state` (the
         1-ULP cross-path contract); dead rows score BIG_DIST; live
         rows get global ids ``capacity + row``;
      3. [main k | delta] is merged by a **stable** argsort — main is
         already sorted ascending and wins ties, so an at-rest delta
         (all BIG_DIST) reproduces the frozen output exactly.
    """
    ids = state.cand_i                                      # (Qs, L)
    cap = tombs.shape[0]
    dead = tombs[jnp.clip(ids, 0, cap - 1)] & (ids != ID_SENTINEL)
    order = jnp.argsort(dead, axis=-1, stable=True)
    ci = jnp.take_along_axis(ids, order, axis=-1)
    cd = jnp.take_along_axis(state.cand_d, order, axis=-1)
    dd = jnp.take_along_axis(dead, order, axis=-1)
    main_i = jnp.where(dd, ID_SENTINEL, ci)[:, :k]
    main_d = jnp.where(dd, BIG_DIST, cd)[:, :k]

    qq = jnp.sum(queries.astype(jnp.float32) ** 2, axis=-1)
    dn = delta_vec.shape[0]
    d_d = (qq[:, None]
           - 2.0 * jnp.sum(queries[:, None, :].astype(jnp.float32)
                           * delta_vec[None].astype(jnp.float32), axis=-1)
           + delta_norm[None])
    d_d = jnp.where(delta_live[None, :], d_d, BIG_DIST)
    d_i = jnp.where(delta_live,
                    cap + jnp.arange(dn, dtype=jnp.int32),
                    ID_SENTINEL)
    d_i = jnp.broadcast_to(d_i[None], (ids.shape[0], dn))

    all_d = jnp.concatenate([main_d, d_d], axis=-1)
    all_i = jnp.concatenate([main_i, d_i], axis=-1)
    ord2 = jnp.argsort(all_d, axis=-1, stable=True)
    out_d = jnp.take_along_axis(all_d, ord2, axis=-1)[:, :k]
    out_i = jnp.take_along_axis(all_i, ord2, axis=-1)[:, :k]
    out_i = jnp.where(out_i != ID_SENTINEL, out_i, INVALID)
    stats = {
        "rounds": state.rounds, "n_dist": state.n_dist,
        "items_recv": state.items_recv,
        "distance_lanes": state.distance_lanes,
        "pages_unique": state.pages_unique,
        "drops_b": state.drops_b, "props_sent": state.props_sent,
        "truncated": state.truncated, "quarantined": state.quarantined,
    }
    return out_i, out_d, stats


# ---------------------------------------------------------------------------
# Drivers
# ---------------------------------------------------------------------------
def pack_for_engine(packed: PackedIndex):
    """PackedIndex -> (device consts dict with leading shard axis, geom)."""
    import numpy as np

    geom = EngineGeom.from_packed(packed)
    consts = {
        "db": jnp.asarray(packed.db),
        "vnorm": jnp.asarray(packed.vnorm),
        "adj": jnp.asarray(packed.adj),
        "pref": jnp.asarray(packed.pref),
        "blk_perm": jnp.asarray(packed.blk_perm),
    }
    # locate the entry vertex's physical position on its shard
    from repro.core.refresh import physical_page_of
    s, p, sl = physical_page_of(packed, np.asarray([packed.entry]))
    ev = packed.db[int(s[0]), int(p[0]), int(sl[0])]
    en = packed.vnorm[int(s[0]), int(p[0]), int(sl[0])]
    return consts, geom, (jnp.asarray(ev, jnp.float32), jnp.float32(en),
                          jnp.int32(packed.entry))


def _sim_round(state, consts, queries, qq, spec_w, params: EngineParams,
               geom: EngineGeom):
    """One engine round in sim comm: vmapped stages, all_to_all == swapaxes.

    The shard axis leads every array. Shared by the one-shot
    ``search_sim`` while_loop and the streaming stepper's
    :func:`engine_round`.

    ``_fa_select``, ``_fb_adjacency`` and ``_fc_propose`` run per shard
    as on the shard_map driver. The distance stage then runs over the
    round's own proposals (:func:`_fd_distance_flat`): one distance call
    over the (S*Qs*M,) list, with no (S, S, capacity_b) buckets to
    scatter, tile or gather, and ``_fe_merge`` reads each proposal's
    distance off its result. The tiered store (``store_pages > 0``) and the
    ``gather_vectors`` baseline keep the bucketed stages, whose reply
    lanes (``miss``, raw vectors) the flat stage does not carry."""

    def a2a(tree):
        return jax.tree_util.tree_map(lambda x: jnp.swapaxes(x, 0, 1), tree)

    vfa = jax.vmap(functools.partial(_fa_select, params=params, geom=geom))
    vfb = jax.vmap(functools.partial(_fb_adjacency, params=params, geom=geom),
                   in_axes=(0, 0, 0))
    vfc = jax.vmap(functools.partial(_fc_propose, params=params, geom=geom))
    vfe = jax.vmap(functools.partial(_fe_merge, params=params, geom=geom))

    S = state.done.shape[0]
    shard_ids = jnp.arange(S, dtype=jnp.int32)
    send_a, keep_a = vfa(state)
    recv_a = a2a(send_a)
    send_b = vfb(recv_a, consts["adj"], consts["pref"])
    recv_b = a2a(send_b)
    keep_c = vfc(state, keep_a, recv_b, spec_w, shard_ids)
    if not (params.store_pages or params.gather_vectors):
        dist, items, uniq, lanes = _fd_distance_flat(
            keep_c, queries, qq, consts["db"], consts["vnorm"],
            consts["blk_perm"], params, geom)
        return vfe(state, keep_a, keep_c, dist, items, uniq, lanes)

    vbucket = jax.vmap(functools.partial(_fc_bucket, params=params,
                                         geom=geom))
    recv_c = a2a(vbucket(keep_c, queries, qq))
    touch = pmiss = None
    if params.store_pages:
        # tiered store: stage D reads frames through the translation
        # table and returns per-shard touch/miss bitmaps, which the
        # merge accumulates into the state (and stalls missed queries)
        vfd = jax.vmap(
            lambda recv, db, vn, bp, ms, tt: _fd_distance(
                recv, db, vn, bp, ms, params, geom, tt))
        send_d, items, uniq, touch, pmiss = vfd(
            recv_c, consts["db"], consts["vnorm"], consts["blk_perm"],
            shard_ids, consts["ttab"])
    else:
        vfd = jax.vmap(functools.partial(_fd_distance, params=params,
                                         geom=geom))
        send_d, items, uniq = vfd(recv_c, consts["db"], consts["vnorm"],
                                  consts["blk_perm"], shard_ids)
    dist, miss = jax.vmap(functools.partial(_fe_gather, params=params))(
        a2a(send_d), keep_c, queries, qq)
    lanes = jnp.full((S,), _bucketed_lanes(
        params, geom, consts["db"].shape[1], *keep_c["props"].shape[1:]),
        jnp.int32)
    return vfe(state, keep_a, keep_c, dist, items, uniq, lanes, miss, touch,
               pmiss)


@functools.partial(jax.jit, static_argnames=("params", "geom"))
def search_sim(consts, queries, entry_vec, entry_norm, entry_id,
               params: EngineParams, geom: EngineGeom):
    """Single-device simulation: shard axis leads every array."""
    qq = jnp.sum(queries.astype(jnp.float32) ** 2, axis=-1)   # (S, Qs)

    state0 = jax.vmap(
        lambda q, qn: _init_state(q, qn, entry_vec, entry_norm, entry_id,
                                  params))(queries, qq)
    spec_w = jnp.full(queries.shape[:2], params.spec_width, jnp.int32)

    def body(carry):
        state, t = carry
        state = _sim_round(state, consts, queries, qq, spec_w, params, geom)
        return state, t + 1

    def cond(carry):
        state, t = carry
        return (~state.done).any() & (t < params.search.rounds_cap)

    state, t = jax.lax.while_loop(cond, body, (state0, jnp.int32(0)))
    out_i, out_d, stats = jax.vmap(lambda s: _finalize(s, params.search.k)
                                   )(state)
    # per-shard like the distributed driver (all shards step in lockstep,
    # so the broadcast is exact) — consumers never special-case the driver
    stats["total_rounds"] = jnp.broadcast_to(t, (queries.shape[0],))
    return out_i, out_d, stats


# ---------------------------------------------------------------------------
# Dynamic speculation — the pure per-round width rule.
# ---------------------------------------------------------------------------
def spec_update(spec_w, hit, peak, accepted, worked, cfg,
                pages_delta=None, phit=None, ppeak=None):
    """One controller step of the paper's dynamic speculative search
    (§V-B), as pure jnp so it runs both on the host (SpecController.update)
    and inside :func:`engine_run_chunk`'s round loop.

    Ordering contract: ``spec_w`` must be the widths that were *used* in
    the round that produced ``accepted`` — the per-query acceptance rate

        hit_q = accepted_q / (W * (max_degree + spec_w_used_q))

    normalizes this round's accepted proposals by the adjacency (+
    speculation) entries actually served at those widths. The returned
    widths apply to the *next* round.

    ``cfg`` is ``(spec_max, W, max_degree, floor, ceil, ema[, page_w])``
    — see :class:`repro.core.scheduler.SpecController`. All math is
    float32 so the host and in-jit paths are bit-identical.

    ``pages_delta`` is the round's unique-page-read delta of the row's
    shard (the engine's ``pages_unique`` counter — a shard-level
    counter, so the signal is shared by the shard's rows). It feeds a
    second normalized rate, pages-efficiency

        p_q = accepted_q / max(pages_delta, 1)

    tracked by the same EMA/peak machinery (``phit``/``ppeak``), and the
    final width fraction is damped by it with weight ``page_w``:

        frac = frac_hit * (1 - page_w + page_w * frac_page)

    so widths that still win proposals but touch many fresh pages narrow
    earlier. ``page_w = 0`` multiplies by exactly 1.0f — bit-identical
    to the hit-rate-only rule. Returns the 5-leaf controller state
    ``(spec_w, hit, peak, phit, ppeak)``.
    """
    spec_max, w_sel, max_degree, floor, ceil, ema = cfg[:6]
    page_w = (jnp.asarray(cfg[6], jnp.float32) if len(cfg) > 6
              else jnp.float32(0.0))
    spec_max = jnp.asarray(spec_max, jnp.int32)
    served = (jnp.asarray(w_sel, jnp.int32)
              * (jnp.asarray(max_degree, jnp.int32) + spec_w))
    floor = jnp.asarray(floor, jnp.float32)
    ceil = jnp.asarray(ceil, jnp.float32)
    ema = jnp.asarray(ema, jnp.float32)
    h = (accepted.astype(jnp.float32)
         / jnp.maximum(served, 1).astype(jnp.float32))
    first = worked & (hit < 0)
    upd = worked & ~first
    hit = jnp.where(first, h,
                    jnp.where(upd, ema * h + (1.0 - ema) * hit, hit))
    peak = jnp.maximum(peak, hit)
    ratio = hit / jnp.maximum(peak, 1e-9)
    frac = jnp.clip((ratio - floor)
                    / jnp.maximum(ceil - floor, 1e-9), 0.0, 1.0)
    if phit is None:
        phit = jnp.full_like(hit, -1.0)
        ppeak = jnp.zeros_like(peak)
    if pages_delta is not None:
        pd = jnp.broadcast_to(
            jnp.asarray(pages_delta, jnp.int32).reshape(
                jnp.shape(pages_delta) + (1,) * (hit.ndim - jnp.ndim(
                    pages_delta))), hit.shape)
        p = (accepted.astype(jnp.float32)
             / jnp.maximum(pd, 1).astype(jnp.float32))
        first_p = worked & (phit < 0)
        upd_p = worked & ~first_p
        phit = jnp.where(first_p, p,
                         jnp.where(upd_p, ema * p + (1.0 - ema) * phit,
                                   phit))
        ppeak = jnp.maximum(ppeak, phit)
        ratio_p = phit / jnp.maximum(ppeak, 1e-9)
        frac_p = jnp.clip((ratio_p - floor)
                          / jnp.maximum(ceil - floor, 1e-9), 0.0, 1.0)
        frac = frac * (1.0 - page_w + page_w * frac_p)
    width = jnp.rint(spec_max.astype(jnp.float32) * frac).astype(jnp.int32)
    return jnp.where(worked, width, spec_w), hit, peak, phit, ppeak


# ---------------------------------------------------------------------------
# Round-stepper API — the streaming scheduler's engine surface.
#
# ``engine_init`` / ``engine_round`` / ``engine_admit`` / ``engine_retire``
# operate on an EngineState whose shard axis leads every leaf, so the
# state can persist across jitted calls: a host-side loop owns the round
# counter, retires finished slot rows and refills them with fresh queries
# between rounds (core/scheduler.py). ``engine_run_chunk`` moves that
# inner loop into jit: up to K rounds run as one device-paced while_loop
# (dynamic speculation updating per round in-jit), so the host syncs
# only at chunk boundaries. ``engine_run_chunk_admit`` moves admission
# in too — a device-side pending queue seats arrived queries into freed
# slots at every in-jit round boundary, so the chunk runs straight
# through retirements and arrivals. ``make_stepper`` bundles them, and
# swaps the round's communication for shard_map lax.all_to_all when
# given a mesh — the sim and distributed paths step through the same
# stages.
# ---------------------------------------------------------------------------
class EngineStepper(NamedTuple):
    """(init, round, admit, retire, run_chunk, run_chunk_admit)
    closures over static params/geom; ``round_chunk`` records the
    static K the chunk stages were compiled for (their budgets are
    clamped to that K)."""

    init: callable       # (consts, queries, evec, enorm, eid) -> EngineState
    round: callable      # (consts, state, queries, spec_w) -> EngineState
    admit: callable      # (state, queries, admit_mask, new_q, evec, enorm,
                         #  eid) -> (EngineState, queries')
    retire: callable     # (state) -> (ids, dists, per-slot stats)
    run_chunk: callable = None
                         # (consts, state, queries, spec_state, spec_cfg,
                         #  budget, stop_on_finish, dynamic=False) ->
                         #  (EngineState, spec_state', steps,
                         #   live_cnt (K,), width_sum (K,))
    round_chunk: int = 1
    run_chunk_admit: callable = None
                         # (consts, state, queries, spec_state, spec_cfg,
                         #  budget, (pend_q, pend_arr), cursor, t0, entry,
                         #  dynamic=False) ->
                         #  (EngineState, queries', spec_state', steps,
                         #   live_cnt (K,), width_sum (K,),
                         #   admit_qidx (K, S, Qs), ret_i (K, S, Qs, k),
                         #   ret_d (K, S, Qs, k), ret_rounds (K, S, Qs),
                         #   ret_ndist (K, S, Qs), ret_age (K, S, Qs),
                         #   ret_trunc (K, S, Qs), cursor')


@functools.partial(jax.jit, static_argnames=("params", "geom"))
def engine_init(consts, queries, entry_vec, entry_norm, entry_id,
                params: EngineParams, geom: EngineGeom) -> EngineState:
    """Fresh state for a (S, Qs, d) slot pool (per-row == one-shot init).

    ``entry_vec`` is either the global entry vertex ((d,), every shard
    seeds there) or per-shard entries ((S, d), routed legs seed at their
    home shard's local medoid)."""
    del consts, geom
    qq = jnp.sum(queries.astype(jnp.float32) ** 2, axis=-1)
    ax = 0 if jnp.ndim(entry_vec) == 2 else None
    return jax.vmap(
        lambda q, qn, ev, en, ei: _init_state(q, qn, ev, en, ei, params),
        in_axes=(0, 0, ax, ax, ax))(queries, qq, entry_vec, entry_norm,
                                    entry_id)


@functools.partial(jax.jit, static_argnames=("params", "geom"))
def engine_round(consts, state: EngineState, queries, spec_w,
                 params: EngineParams, geom: EngineGeom) -> EngineState:
    """One Allocating -> Searching -> Gathering round (sim comm).

    ``spec_w`` is the dynamic per-query speculation width: scalar or
    (S, Qs) i32 in [0, params.spec_width] (scalars broadcast)."""
    qq = jnp.sum(queries.astype(jnp.float32) ** 2, axis=-1)
    spec_w = jnp.broadcast_to(jnp.asarray(spec_w, jnp.int32),
                              queries.shape[:2])
    return _sim_round(state, consts, queries, qq, spec_w, params, geom)


def _admit_rows(state: EngineState, queries, admit_mask, new_q,
                entry_vec, entry_norm, entry_id, params: EngineParams):
    """One shard's slot-refill math, shared verbatim by the jitted
    host-side :func:`engine_admit` and the in-jit admission stage of
    :func:`engine_run_chunk_admit` (host-admitted and chunk-admitted
    rows are bit-identical because this is the one place the reset
    lives). Rows where ``admit_mask`` restart from the entry vertex
    with the vectors in ``new_q``; every per-query leaf is rebuilt by
    the same ``_init_state`` math as the one-shot drivers; the
    shard-cumulative counters pass through untouched."""
    q = jnp.where(admit_mask[..., None], new_q, queries)
    qq = jnp.sum(q.astype(jnp.float32) ** 2, axis=-1)
    fresh = _init_state(q, qq, entry_vec, entry_norm, entry_id, params)

    def rows(cur, new):
        m = admit_mask.reshape(admit_mask.shape
                               + (1,) * (cur.ndim - admit_mask.ndim))
        return jnp.where(m, new, cur)

    state = EngineState(
        rows(state.cand_d, fresh.cand_d), rows(state.cand_i, fresh.cand_i),
        rows(state.cand_e, fresh.cand_e), rows(state.bloom, fresh.bloom),
        jnp.where(admit_mask, False, state.done),
        jnp.where(admit_mask, 0, state.rounds),
        jnp.where(admit_mask, 0, state.n_dist),
        jnp.where(admit_mask, 0, state.age),
        jnp.where(admit_mask, fresh.deadline, state.deadline),
        jnp.where(admit_mask, False, state.truncated),
        state.items_recv, state.distance_lanes, state.pages_unique,
        state.drops_b, state.props_sent, state.quarantined,
        state.page_touch, state.page_miss)
    return state, q


@functools.partial(jax.jit, static_argnames=("params", "geom"))
def engine_admit(state: EngineState, queries, admit_mask, new_q,
                 entry_vec, entry_norm, entry_id,
                 params: EngineParams, geom: EngineGeom):
    """Refill freed slots: rows where ``admit_mask`` restart from the
    entry vertex with the vectors in ``new_q`` (slot compaction by
    replacement — freed rows never ride along as padding).

    Every per-query leaf of the admitted rows — candidate list, expanded
    flags, bloom, done/rounds/n_dist — is rebuilt from scratch by the
    same ``_init_state`` math as the one-shot drivers, so a reused slot
    is bit-identical to a fresh one. Shard-level cumulative counters
    (items_recv, distance_lanes, pages_unique, drops_b, props_sent) are
    preserved.
    Returns the new state and the updated (S, Qs, d) query buffer.
    ``entry_vec`` may be per-shard ((S, d)) as in :func:`engine_init`.
    """
    del geom
    ax = 0 if jnp.ndim(entry_vec) == 2 else None
    return jax.vmap(functools.partial(_admit_rows, params=params),
                    in_axes=(0, 0, 0, 0, ax, ax, ax))(
        state, queries, admit_mask, new_q, entry_vec, entry_norm,
        entry_id)


@functools.partial(jax.jit, static_argnames=("k",))
def engine_retire(state: EngineState, k: int):
    """Per-slot results + stats; the host slices the retiring rows."""
    return jax.vmap(lambda s: _finalize(s, k))(state)


#: consts keys a live index adds next to db/vnorm/adj/pref/blk_perm.
LIVE_CONST_KEYS = ("tombs", "delta_vec", "delta_norm", "delta_live")


@functools.partial(jax.jit, static_argnames=("k",))
def engine_retire_live(state: EngineState, queries, tombs, delta_vec,
                       delta_norm, delta_live, k: int):
    """:func:`engine_retire` through :func:`_finalize_live`: tombstones
    masked, delta segment merged. The delta/tombstone arrays are traced,
    so inserts/deletes/epoch swaps retrace nothing."""
    return jax.vmap(
        lambda s, q: _finalize_live(s, q, tombs, delta_vec, delta_norm,
                                    delta_live, k))(state, queries)


def _chunk_round(carry, round_fn, rounds_cap, dynamic, spec_cfg,
                 stall=None):
    """One in-chunk round, shared by the sim and shard_map while_loop
    bodies (sim-vs-shard_map bit-identity depends on this being the one
    place the loop-body semantics live): record the per-round traces,
    step the round, park rows hitting the per-query round cap at the
    exact boundary the per-round scheduler would retire them, and — in
    dynamic mode — step the speculation widths with the served widths
    (ordering contract of :func:`spec_update`) and the round's unique-
    page delta (the page-efficiency signal; a no-op at page_w=0).

    ``stall`` (None, or a bool broadcastable against ``done``) marks
    rows whose shard is not serving this round (ft/inject.py kill/delay
    plans): they are parked for the round — no phase work, no merge, no
    ``rounds`` advance — and un-parked afterwards with their traversal
    state intact. The serving clock still ages every live row, stalled
    or not, so the in-jit deadline below can retire rows a dead shard
    will never finish: the degraded-fusion contract is "R legs become
    R-f legs", never a stall."""
    st, sw, hi, pk, phi, ppk, prev_nd, prev_pg, j, lc, ws = carry
    worked = ~st.done
    lc = lc.at[j].set(worked.sum().astype(jnp.int32))
    ws = ws.at[j].set(jnp.where(worked, sw, 0).sum().astype(jnp.int32))
    if stall is None:
        st = round_fn(st, sw)
    else:
        pre_done = st.done
        st = st._replace(done=st.done | stall)
        st = round_fn(st, sw)
        st = st._replace(done=jnp.where(stall, pre_done, st.done))
    st = st._replace(done=st.done | (st.rounds >= rounds_cap))
    # in-jit deadline: age every row that was live at round entry, then
    # force-retire the ones at their deadline with best-so-far top-k.
    # A row that converged this very round keeps truncated=False (its
    # natural finish wins the tie); with no deadline configured the
    # comparison never fires and the schedule is bit-identical.
    age = st.age + worked.astype(jnp.int32)
    hit = ~st.done & (age >= st.deadline)
    st = st._replace(age=age, done=st.done | hit,
                     truncated=st.truncated | hit)
    if dynamic:
        sw, hi, pk, phi, ppk = spec_update(
            sw, hi, pk, st.n_dist - prev_nd, worked, spec_cfg,
            st.pages_unique - prev_pg, phi, ppk)
    return (st, sw, hi, pk, phi, ppk, st.n_dist, st.pages_unique, j + 1,
            lc, ws)


@functools.partial(jax.jit,
                   static_argnames=("params", "geom", "K", "dynamic"))
def engine_run_chunk(consts, state: EngineState, queries, spec_state,
                     spec_cfg, budget, stop_on_finish,
                     params: EngineParams, geom: EngineGeom, K: int,
                     dynamic: bool = False):
    """Run up to ``K`` engine rounds inside one jit call (sim comm).

    The paper's near-data model keeps the host off the round-to-round
    critical path (§V): instead of re-entering Python after every
    Allocating->Searching->Gathering round, the scheduler launches a
    *chunk* and the device paces itself through a ``lax.while_loop``.
    Per-round semantics are identical to K calls of :func:`engine_round`
    with the host controller in between:

      * rows reaching ``rounds_cap`` are parked (``done=True``) at the
        same round boundary the per-round scheduler would retire them —
        a capped row never works a single extra round;
      * with ``dynamic=True`` the speculation widths step through
        :func:`spec_update` after every round, so per-query widths keep
        adapting *inside* the chunk (``spec_state`` is the controller's
        ``(spec_w, hit, peak, page_hit, page_peak)`` 5-tuple,
        ``spec_cfg`` its parameters).

    Early exit, both traced (no recompiles):

      * ``budget`` (i32 <= K) bounds the chunk — the host caps it to the
        next pending arrival so admission timing stays exact;
      * every live row finishing mid-chunk ends the chunk;
      * ``stop_on_finish`` (bool) ends the chunk as soon as *any* row
        that was live at entry finishes — the host sets it whenever
        unadmitted queries remain, so a freed slot is refilled on
        exactly the round the per-round scheduler would have.

    This is the *host-paced-admission* chunk: the exits above collapse
    chunk length toward one round while the pending queue drains.
    :func:`engine_run_chunk_admit` removes them by seating arrivals
    in-jit; this variant remains the frozen-mode path (whose all-free
    admission gate is host-side) and the ``injit_admit=False``
    comparison baseline.

    Returns ``(state, spec_state', steps, live_cnt, width_sum)`` where
    ``steps`` is the number of rounds actually run and ``live_cnt`` /
    ``width_sum`` are (K,) per-round traces (live rows, summed widths
    over live rows) from which the host reconstructs exact occupancy and
    speculation traces without per-round syncs.
    """
    qq = jnp.sum(queries.astype(jnp.float32) ** 2, axis=-1)
    spec_w, hit, peak, phit, ppeak = spec_state
    spec_w = jnp.broadcast_to(jnp.asarray(spec_w, jnp.int32),
                              queries.shape[:2])
    live0 = ~state.done
    budget = jnp.minimum(jnp.asarray(budget, jnp.int32), jnp.int32(K))
    stop = jnp.asarray(stop_on_finish, bool)

    def round_fn(st, sw):
        return _sim_round(st, consts, queries, qq, sw, params, geom)

    def cond(carry):
        st, _, _, _, _, _, _, _, j, _, _ = carry
        fin_any = (st.done & live0).any()
        return (j < budget) & (~st.done).any() & ~(stop & fin_any)

    def body(carry):
        return _chunk_round(carry, round_fn, params.search.rounds_cap,
                            dynamic, spec_cfg)

    zeros_k = jnp.zeros((K,), jnp.int32)
    (state, spec_w, hit, peak, phit, ppeak, _, _, steps, live_cnt,
     width_sum) = jax.lax.while_loop(
        cond, body, (state, spec_w, hit, peak, phit, ppeak, state.n_dist,
                     state.pages_unique, jnp.int32(0), zeros_k, zeros_k))
    return (state, (spec_w, hit, peak, phit, ppeak), steps, live_cnt,
            width_sum)


def _seat_pending(free, cursor, avail, offset, pend_q, queries_rows):
    """Seat arrived pending queries into free slot rows, in the host
    staging order (row-major over the global pool, pending taken in
    arrival order): a free row whose global free-rank (``offset`` +
    local exclusive rank) is below ``avail`` takes pending entry
    ``cursor + rank``. ``free``/``queries_rows`` are this shard's (or
    the flattened pool's) rows; ``offset`` is the number of free rows
    on lower-index shards (0 for the flattened sim pool). Returns
    (seat mask, seated pending indices with -1 holes, updated query
    rows)."""
    rank = offset + jnp.cumsum(free.astype(jnp.int32)) - 1
    seat = free & (rank < avail)
    pidx = jnp.where(seat, cursor + rank, jnp.int32(-1))
    safe = jnp.clip(pidx, 0, pend_q.shape[0] - 1)
    new_q = jnp.where(seat[:, None], pend_q[safe], queries_rows)
    return seat, pidx, new_q


def _pending_avail(pend_arr, cursor, tnow):
    """Pending entries whose arrival round has passed and that the
    cursor has not yet consumed (``pend_arr`` is sorted by arrival, so
    the arrived count is a prefix count — binary-searched, this runs
    twice per in-jit round on the while_loop's hot path)."""
    arrived = jnp.searchsorted(pend_arr, tnow,
                               side="right").astype(jnp.int32)
    return jnp.maximum(arrived - cursor, 0)


@functools.partial(jax.jit,
                   static_argnames=("params", "geom", "K", "dynamic"))
def engine_run_chunk_admit(consts, state: EngineState, queries, spec_state,
                           spec_cfg, budget, pend_q, pend_arr, cursor, t0,
                           entry_vec, entry_norm, entry_id,
                           params: EngineParams, geom: EngineGeom, K: int,
                           dynamic: bool = False):
    """:func:`engine_run_chunk` with an **in-chunk admission stage**
    (sim comm): the pending queue lives on device (``pend_q`` (N, d)
    query vectors and ``pend_arr`` (N,) arrival rounds, both sorted by
    arrival; ``cursor`` is the first unadmitted entry and ``t0`` the
    global round at chunk entry), and every round boundary seats
    arrived entries into free (``done``) slot rows before stepping —
    the last host-paced path of the scheduler (admission) moves in-jit,
    so the chunk no longer needs the ``stop_on_finish`` early exit or
    an arrival-capped budget while the queue drains (§V: the SSD
    refills its own pipeline without consulting the host).

    Per-boundary semantics are exactly the per-round host scheduler's:

      * seating order is the host staging order — free rows row-major
        over the (S, Qs) pool, pending entries in arrival order — via
        the same cumulative-rank math (:func:`_seat_pending`);
      * a seated row is reset by :func:`_admit_rows`, the *same* math
        the host-side :func:`engine_admit` runs, and (``dynamic=True``)
        its controller row restarts at full width exactly like
        ``SpecController.reset_rows``;
      * a freed-and-reseated row's results would be overwritten, so the
        chunk records per-boundary **admit traces**: the pending index
        seated per slot (``admit_qidx``, -1 elsewhere) plus the
        pre-admission finalize/rounds/n_dist of every row (``ret_*``) —
        the host replays the boundaries in order to reconstruct
        ``owner``/``admit_t``/``retire_round`` and emit evicted rows'
        results bit-exactly at the next chunk boundary.

    The chunk exits early (traced) only when there is genuinely nothing
    to do: no live row and no pending entry arrived by the current
    boundary. Idle gaps (pool empty until a future arrival) stay
    host-side — the scheduler jumps the serving clock without a
    dispatch.

    With a fault plan on ``params`` (ft/inject.py), shard kill/delay
    windows are evaluated against the global round ``t0 + j`` at every
    boundary: a stalled shard's rows do no phase work that round but
    keep aging, so the in-jit deadline retires them (``ret_age`` /
    ``ret_trunc`` extend the evict traces with the serving-clock age
    and truncation flag the host needs for exact accounting). This is
    the only chunk driver that knows the global round, which is why
    stall faults require the in-jit admission path.

    Returns ``(state, queries', spec_state', steps, live_cnt,
    width_sum, admit_qidx, ret_i, ret_d, ret_rounds, ret_ndist,
    ret_age, ret_trunc, cursor')``; the query buffer rides in the
    carry because admission rewrites it mid-chunk.
    """
    k = params.search.k
    S, Qs = state.done.shape
    stall_fn = None
    if params.faults is not None and params.faults.any_stall:
        if params.faults.num_shards != S:
            raise ValueError(
                f"fault plan covers {params.faults.num_shards} shards "
                f"but the pool has {S}")
        def stall_fn(t):
            return ftinject.stall_at(params.faults, t)[:, None]  # (S, 1)
    spec_w, hit, peak, phit, ppeak = spec_state
    spec_w = jnp.broadcast_to(jnp.asarray(spec_w, jnp.int32), (S, Qs))
    budget = jnp.minimum(jnp.asarray(budget, jnp.int32), jnp.int32(K))
    cursor = jnp.asarray(cursor, jnp.int32)
    t0 = jnp.asarray(t0, jnp.int32)
    pend_arr = jnp.asarray(pend_arr, jnp.int32)
    spec_max = jnp.asarray(spec_cfg[0], jnp.int32)
    # routed mode: per-shard pending queues ((S, Np) arrivals, (S,)
    # cursors) seat each shard's rows independently at offset 0 — no
    # cross-shard free-rank coupling; and per-shard entries ((S, d)
    # vectors) seed each shard's rows at its own subgraph entry. Both
    # are static shape decisions, so one traced function serves both.
    per_shard = pend_arr.ndim == 2
    entry_ax = 0 if jnp.ndim(entry_vec) == 2 else None
    vadmit = jax.vmap(functools.partial(_admit_rows, params=params),
                      in_axes=(0, 0, 0, 0, entry_ax, entry_ax, entry_ax))
    # evicted rows' results are captured pre-admission; with a live
    # index (static delta_cap > 0) the capture masks tombstones and
    # merges the delta so a mid-chunk eviction honours deletes exactly
    # like a host-side retire. delta_cap == 0 keeps the original
    # closure untouched: byte-identical trace to the frozen path.
    if params.delta_cap > 0:
        vfin_live = jax.vmap(
            lambda s, qr: _finalize_live(
                s, qr, consts["tombs"], consts["delta_vec"],
                consts["delta_norm"], consts["delta_live"], k)[:2])

        def capture_fin(st, q):
            return vfin_live(st, q)
    else:
        vfin = jax.vmap(lambda s: _finalize(s, k)[:2])

        def capture_fin(st, q):
            return vfin(st)
    if per_shard:
        avail_of = jax.vmap(_pending_avail, in_axes=(0, 0, None))
        vseat = jax.vmap(_seat_pending,
                         in_axes=(0, 0, 0, None, 0, 0))
    else:
        avail_of = _pending_avail

    def cond(carry):
        st, q, sw, hi, pk, phi, ppk, cur, prev_nd, prev_pg, j = carry[:11]
        avail = avail_of(pend_arr, cur, t0 + j)
        return ((j < budget)
                & ((~st.done).any() | (avail.sum() > 0)))

    def body(carry):
        (st, q, sw, hi, pk, phi, ppk, cur, prev_nd, prev_pg, j, lc, ws,
         aq, ri, rd, rr, rn, ra, rt) = carry
        # -- boundary j (global round t0 + j): record the would-be-
        # evicted rows' results, then seat arrived pending queries
        fin_i, fin_d = capture_fin(st, q)
        ri = ri.at[j].set(fin_i)
        rd = rd.at[j].set(fin_d)
        rr = rr.at[j].set(st.rounds)
        rn = rn.at[j].set(st.n_dist)
        ra = ra.at[j].set(st.age)
        rt = rt.at[j].set(st.truncated)
        if per_shard:
            seat, pidx, new_q = vseat(
                st.done, cur, avail_of(pend_arr, cur, t0 + j),
                jnp.int32(0), pend_q, q)
            mask = seat
            cur = cur + seat.sum(axis=1).astype(jnp.int32)
            aq = aq.at[j].set(pidx)
            st, q = vadmit(st, q, mask, new_q, entry_vec, entry_norm,
                           entry_id)
        else:
            seat, pidx, new_q = _seat_pending(
                st.done.reshape(-1), cur,
                avail_of(pend_arr, cur, t0 + j), 0, pend_q,
                q.reshape(S * Qs, -1))
            mask = seat.reshape(S, Qs)
            st, q = vadmit(st, q, mask, new_q.reshape(S, Qs, -1),
                           entry_vec, entry_norm, entry_id)
            cur = cur + seat.sum().astype(jnp.int32)
            aq = aq.at[j].set(pidx.reshape(S, Qs))
        if dynamic:   # fresh rows restart the controller at full width
            sw = jnp.where(mask, spec_max, sw)
            hi = jnp.where(mask, jnp.float32(-1.0), hi)
            pk = jnp.where(mask, jnp.float32(0.0), pk)
            phi = jnp.where(mask, jnp.float32(-1.0), phi)
            ppk = jnp.where(mask, jnp.float32(0.0), ppk)
        # -- the round itself: same shared body as engine_run_chunk.
        # prev_nd must be the post-admission n_dist: seated rows were
        # reset to 0, and their accepted-count delta (spec_update) must
        # start from 0 exactly like a host-admitted fresh row's would
        # (non-admitted rows' n_dist only moves in rounds, so this is
        # the carried value for them either way).
        qq = jnp.sum(q.astype(jnp.float32) ** 2, axis=-1)
        st, sw, hi, pk, phi, ppk, prev_nd, prev_pg, j, lc, ws = \
            _chunk_round(
                (st, sw, hi, pk, phi, ppk, st.n_dist, st.pages_unique,
                 j, lc, ws),
                lambda s, w: _sim_round(s, consts, q, qq, w, params,
                                        geom),
                params.search.rounds_cap, dynamic, spec_cfg,
                stall=None if stall_fn is None else stall_fn(t0 + j))
        return (st, q, sw, hi, pk, phi, ppk, cur, prev_nd, prev_pg, j,
                lc, ws, aq, ri, rd, rr, rn, ra, rt)

    zeros_k = jnp.zeros((K,), jnp.int32)
    zeros_sq = jnp.zeros((K, S, Qs), jnp.int32)
    carry = (state, queries, spec_w, hit, peak, phit, ppeak, cursor,
             state.n_dist, state.pages_unique, jnp.int32(0), zeros_k,
             zeros_k, jnp.full((K, S, Qs), -1, jnp.int32),
             jnp.full((K, S, Qs, k), INVALID, jnp.int32),
             jnp.zeros((K, S, Qs, k), jnp.float32), zeros_sq, zeros_sq,
             zeros_sq, jnp.zeros((K, S, Qs), bool))
    (state, queries, spec_w, hit, peak, phit, ppeak, cursor, _, _, steps,
     live_cnt, width_sum, admit_qidx, ret_i, ret_d, ret_rounds,
     ret_ndist, ret_age, ret_trunc) = jax.lax.while_loop(cond, body,
                                                         carry)
    return (state, queries, (spec_w, hit, peak, phit, ppeak), steps,
            live_cnt, width_sum, admit_qidx, ret_i, ret_d, ret_rounds,
            ret_ndist, ret_age, ret_trunc, cursor)


def _shard_map_fn(fn, mesh, in_specs, out_specs):
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def make_stepper(params: EngineParams, geom: EngineGeom, mesh=None,
                 axis_name: str = "lun", round_chunk: int = 1,
                 routed: bool = False) -> EngineStepper:
    """Bundle the stepper closures; with a mesh, the round/chunk
    communicates via shard_map lax.all_to_all instead of the sim
    swapaxes (init, admit and retire are per-row math with no
    communication, so the sim forms serve both paths). ``round_chunk``
    is the static K of :func:`engine_run_chunk` — the most rounds one
    ``run_chunk`` dispatch may run before the host is consulted.

    ``routed=True`` selects the two-tier serving layout on the mesh
    leg (core/router.py): pending queues, admission cursors and entry
    vertices are **per-shard** (leading S axis, sharded over the mesh)
    and each shard seats its own queue at offset 0 with a local cursor
    — no all_gather free-rank coupling — so every shard runs an
    independent admission schedule. The sim leg needs no flag: it
    dispatches on the pending/entry array ranks at trace time."""
    K = max(1, int(round_chunk))
    init = functools.partial(engine_init, params=params, geom=geom)
    admit = functools.partial(engine_admit, params=params, geom=geom)
    retire = functools.partial(engine_retire, k=params.search.k)
    if mesh is None:
        rnd = functools.partial(engine_round, params=params, geom=geom)

        def run_chunk(consts, state, queries, spec_state, spec_cfg,
                      budget, stop_on_finish, dynamic=False):
            return engine_run_chunk(consts, state, queries, spec_state,
                                    spec_cfg, budget, stop_on_finish,
                                    params=params, geom=geom, K=K,
                                    dynamic=dynamic)

        def run_chunk_admit(consts, state, queries, spec_state, spec_cfg,
                            budget, pend, cursor, t0, entry,
                            dynamic=False):
            pend_q, pend_arr = pend
            return engine_run_chunk_admit(
                consts, state, queries, spec_state, spec_cfg, budget,
                pend_q, pend_arr, cursor, t0, *entry, params=params,
                geom=geom, K=K, dynamic=dynamic)

        return EngineStepper(init, rnd, admit, retire, run_chunk, K,
                             run_chunk_admit)

    from jax.sharding import PartitionSpec as P

    def a2a(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.lax.all_to_all(x, axis_name, 0, 0), tree)

    nleaves = len(EngineState._fields)
    sp = params.search

    # -- admission under shard_map: per-row math with no communication,
    # but run per-shard so its float reductions (_init_state's entry
    # distance, qq) see the exact same shapes the in-chunk admission
    # stage computes with — host-admitted and chunk-admitted rows stay
    # bit-identical on the distributed path, not just on integer data.
    def local_admit(q, mask, new_q, evec, enorm, eid, *leaves):
        state = EngineState(*(leaf[0] for leaf in leaves))
        if routed:   # per-shard entry: this shard's local medoid
            evec, enorm, eid = evec[0], enorm[0], eid[0]
        st, ql = _admit_rows(state, q[0], mask[0], new_q[0], evec,
                             enorm, eid, params)
        return tuple(leaf[None] for leaf in st), ql[None]

    entry_specs = ((P(axis_name),) if routed else (P(),)) * 3
    f_admit = jax.jit(_shard_map_fn(
        local_admit, mesh,
        (P(axis_name),) * 3 + entry_specs + (P(axis_name),) * nleaves,
        ((P(axis_name),) * nleaves, P(axis_name))))

    def admit(state, queries, admit_mask, new_q, evec, enorm, eid):
        leaves, q = f_admit(queries, admit_mask, new_q, evec, enorm,
                            eid, *state)
        return EngineState(*leaves), q

    def local_round(db, vnorm, adj, pref, blk_perm, q, spec_w, *leaves):
        lc = {"db": db[0], "vnorm": vnorm[0], "adj": adj[0],
              "pref": pref[0], "blk_perm": blk_perm[0]}
        ql = q[0]
        lc["queries"] = ql
        lc["qq"] = jnp.sum(ql.astype(jnp.float32) ** 2, axis=-1)
        state = EngineState(*(leaf[0] for leaf in leaves))
        state = _round(state, lc, params, geom, a2a, spec_w[0],
                       jax.lax.axis_index(axis_name))
        return tuple(leaf[None] for leaf in state)

    in_specs = (P(axis_name),) * 7 + (P(axis_name),) * nleaves
    out_specs = (P(axis_name),) * nleaves
    f = jax.jit(_shard_map_fn(local_round, mesh, in_specs, out_specs))

    def rnd(consts, state, queries, spec_w):
        spec_w = jnp.broadcast_to(jnp.asarray(spec_w, jnp.int32),
                                  queries.shape[:2])
        leaves = f(consts["db"], consts["vnorm"], consts["adj"],
                   consts["pref"], consts["blk_perm"], queries,
                   spec_w, *state)
        return EngineState(*leaves)

    # -- chunked round loop under shard_map: the while_loop's exit tests
    # are psum-reduced so every shard steps in lockstep, exactly like
    # search_distributed's global-active while_loop.
    def make_local_chunk(dynamic):
        def local_chunk(db, vnorm, adj, pref, blk_perm, q, spec_w, hit,
                        peak, phit, ppeak, cfg, budget, stop, *leaves):
            lc = {"db": db[0], "vnorm": vnorm[0], "adj": adj[0],
                  "pref": pref[0], "blk_perm": blk_perm[0]}
            ql = q[0]
            lc["queries"] = ql
            lc["qq"] = jnp.sum(ql.astype(jnp.float32) ** 2, axis=-1)
            state = EngineState(*(leaf[0] for leaf in leaves))
            sw, hi, pk = spec_w[0], hit[0], peak[0]
            phi, ppk = phit[0], ppeak[0]
            live0 = ~state.done
            bud = jnp.minimum(jnp.asarray(budget, jnp.int32), jnp.int32(K))
            myidx = jax.lax.axis_index(axis_name)

            def round_fn(st, sw):
                return _round(st, lc, params, geom, a2a, sw, myidx)

            def gsum(x):
                return jax.lax.psum(x.sum().astype(jnp.int32), axis_name)

            def cond(carry):
                j, active, fin = carry[8], carry[9], carry[10]
                return ((j < bud) & (active > 0)
                        & ~(stop.astype(bool) & (fin > 0)))

            def body(carry):
                (st, sw, hi, pk, phi, ppk, prev_nd, prev_pg, j, _, _,
                 lcnt, wsum) = carry
                (st, sw, hi, pk, phi, ppk, prev_nd, prev_pg, j, lcnt,
                 wsum) = _chunk_round(
                    (st, sw, hi, pk, phi, ppk, prev_nd, prev_pg, j, lcnt,
                     wsum), round_fn, sp.rounds_cap, dynamic, cfg)
                # globally-reduced exit tests keep the shards in lockstep
                return (st, sw, hi, pk, phi, ppk, prev_nd, prev_pg, j,
                        gsum(~st.done), gsum(st.done & live0), lcnt, wsum)

            zeros_k = jnp.zeros((K,), jnp.int32)
            carry = (state, sw, hi, pk, phi, ppk, state.n_dist,
                     state.pages_unique, jnp.int32(0), gsum(~state.done),
                     jnp.int32(0), zeros_k, zeros_k)
            (st, sw, hi, pk, phi, ppk, _, _, steps, _, _, lcnt,
             wsum) = jax.lax.while_loop(cond, body, carry)
            return (tuple(leaf[None] for leaf in st), sw[None], hi[None],
                    pk[None], phi[None], ppk[None], steps[None],
                    lcnt[None], wsum[None])

        return local_chunk

    chunk_in = ((P(axis_name),) * 11 + (P(),) * 3
                + (P(axis_name),) * nleaves)
    chunk_out = ((P(axis_name),) * nleaves,) + (P(axis_name),) * 8
    chunk_fns = {}
    for dyn in (False, True):
        chunk_fns[dyn] = jax.jit(_shard_map_fn(
            make_local_chunk(dyn), mesh, chunk_in, chunk_out))

    def run_chunk(consts, state, queries, spec_state, spec_cfg, budget,
                  stop_on_finish, dynamic=False):
        sw, hi, pk, phi, ppk = spec_state
        sw = jnp.broadcast_to(jnp.asarray(sw, jnp.int32),
                              queries.shape[:2])
        cfg = tuple(jnp.asarray(c) for c in spec_cfg)
        (leaves, sw, hi, pk, phi, ppk, steps, lcnt,
         wsum) = chunk_fns[bool(dynamic)](
            consts["db"], consts["vnorm"], consts["adj"], consts["pref"],
            consts["blk_perm"], queries, sw, hi, pk, phi, ppk, cfg,
            jnp.asarray(budget, jnp.int32), jnp.asarray(stop_on_finish),
            *state)
        # steps is replicated (lockstep cond); traces are per-shard
        # partial sums — reduce on the host side of the boundary
        return (EngineState(*leaves), (sw, hi, pk, phi, ppk), steps[0],
                lcnt.sum(axis=0), wsum.sum(axis=0))

    # -- in-chunk admission under shard_map: every shard seats its own
    # rows of the globally-ordered admission (free ranks offset by the
    # free counts of lower-index shards via all_gather), so the seating
    # is exactly the host's row-major staging over the (S, Qs) pool;
    # the while_loop exit tests stay psum-lockstep.
    k_out = sp.k

    def make_local_chunk_admit(dynamic):
        def local_chunk_admit(db, vnorm, adj, pref, blk_perm, q, spec_w,
                              hit, peak, phit, ppeak, cfg, budget,
                              pend_q, pend_arr, cursor, t0, evec, enorm,
                              eid, *leaves):
            base = {"db": db[0], "vnorm": vnorm[0], "adj": adj[0],
                    "pref": pref[0], "blk_perm": blk_perm[0]}
            state = EngineState(*(leaf[0] for leaf in leaves))
            ql = q[0]
            sw, hi, pk = spec_w[0], hit[0], peak[0]
            phi, ppk = phit[0], ppeak[0]
            Qs = state.done.shape[0]
            bud = jnp.minimum(jnp.asarray(budget, jnp.int32), jnp.int32(K))
            t0i = jnp.asarray(t0, jnp.int32)
            spec_max = jnp.asarray(cfg[0], jnp.int32)
            myidx = jax.lax.axis_index(axis_name)
            stall_fn = None
            if params.faults is not None and params.faults.any_stall:
                def stall_fn(t):   # this shard's own stall bit (scalar)
                    return ftinject.stall_at(params.faults, t)[myidx]
            if routed:
                # routed: this shard's own queue / cursor / entry block
                pq = pend_q[0]
                parr = jnp.asarray(pend_arr[0], jnp.int32)
                cur0 = jnp.asarray(cursor[0], jnp.int32)
                evec, enorm, eid = evec[0], enorm[0], eid[0]
            else:
                pq = pend_q
                parr = jnp.asarray(pend_arr, jnp.int32)
                cur0 = jnp.asarray(cursor, jnp.int32)

            def gsum(x):
                return jax.lax.psum(x.sum().astype(jnp.int32), axis_name)

            def cond(carry):
                cur, j, active = carry[7], carry[10], carry[11]
                avail = _pending_avail(parr, cur, t0i + j)
                if routed:   # lockstep exit test over per-shard queues
                    avail = jax.lax.psum(avail, axis_name)
                return (j < bud) & ((active > 0) | (avail > 0))

            def body(carry):
                (st, ql, sw, hi, pk, phi, ppk, cur, prev_nd, prev_pg, j,
                 _, lcnt, wsum, aq, ri, rd, rr, rn, ra, rt) = carry
                fin_i, fin_d, _ = _finalize(st, k_out)
                ri = ri.at[j].set(fin_i)
                rd = rd.at[j].set(fin_d)
                rr = rr.at[j].set(st.rounds)
                rn = rn.at[j].set(st.n_dist)
                ra = ra.at[j].set(st.age)
                rt = rt.at[j].set(st.truncated)
                avail = _pending_avail(parr, cur, t0i + j)
                if routed:
                    # independent per-shard schedule: local free ranks
                    # at offset 0, local cursor — no cross-shard
                    # coupling on the admission path
                    offset = jnp.int32(0)
                else:
                    # global row-major free ranks: offset this shard's
                    # by the free counts on lower-index shards
                    counts = jax.lax.all_gather(
                        st.done.sum().astype(jnp.int32), axis_name)
                    offset = jnp.sum(jnp.where(
                        jnp.arange(counts.shape[0]) < myidx, counts, 0))
                seat, pidx, new_q = _seat_pending(
                    st.done, cur, avail, offset, pq, ql)
                st, ql = _admit_rows(st, ql, seat, new_q, evec, enorm,
                                     eid, params)
                cur = cur + (seat.sum().astype(jnp.int32) if routed
                             else gsum(seat))
                aq = aq.at[j].set(pidx)
                if dynamic:
                    sw = jnp.where(seat, spec_max, sw)
                    hi = jnp.where(seat, jnp.float32(-1.0), hi)
                    pk = jnp.where(seat, jnp.float32(0.0), pk)
                    phi = jnp.where(seat, jnp.float32(-1.0), phi)
                    ppk = jnp.where(seat, jnp.float32(0.0), ppk)
                lc = dict(base, queries=ql,
                          qq=jnp.sum(ql.astype(jnp.float32) ** 2, -1))
                # post-admission n_dist as prev_nd: seated rows' spec
                # deltas must start from 0 (see engine_run_chunk_admit)
                (st, sw, hi, pk, phi, ppk, prev_nd, prev_pg, j, lcnt,
                 wsum) = _chunk_round(
                    (st, sw, hi, pk, phi, ppk, st.n_dist,
                     st.pages_unique, j, lcnt, wsum),
                    lambda s, w: _round(s, lc, params, geom, a2a, w,
                                        myidx),
                    sp.rounds_cap, dynamic, cfg,
                    stall=None if stall_fn is None
                    else stall_fn(t0i + j))
                return (st, ql, sw, hi, pk, phi, ppk, cur, prev_nd,
                        prev_pg, j, gsum(~st.done), lcnt, wsum,
                        aq, ri, rd, rr, rn, ra, rt)

            zeros_k = jnp.zeros((K,), jnp.int32)
            zeros_kq = jnp.zeros((K, Qs), jnp.int32)
            carry = (state, ql, sw, hi, pk, phi, ppk, cur0, state.n_dist,
                     state.pages_unique, jnp.int32(0), gsum(~state.done),
                     zeros_k, zeros_k,
                     jnp.full((K, Qs), -1, jnp.int32),
                     jnp.full((K, Qs, k_out), INVALID, jnp.int32),
                     jnp.zeros((K, Qs, k_out), jnp.float32),
                     zeros_kq, zeros_kq, zeros_kq,
                     jnp.zeros((K, Qs), bool))
            (st, ql, sw, hi, pk, phi, ppk, cur, _, _, steps, _, lcnt,
             wsum, aq, ri, rd, rr, rn, ra, rt) = jax.lax.while_loop(
                cond, body, carry)
            return (tuple(leaf[None] for leaf in st), ql[None], sw[None],
                    hi[None], pk[None], phi[None], ppk[None],
                    steps[None], lcnt[None], wsum[None], aq[None],
                    ri[None], rd[None], rr[None], rn[None], ra[None],
                    rt[None], cur[None])

        return local_chunk_admit

    if routed:
        # pend_q / pend_arr / cursor / entry carry a leading S axis
        tail = (P(), P(), P(axis_name), P(axis_name), P(axis_name),
                P(), P(axis_name), P(axis_name), P(axis_name))
    else:
        tail = (P(),) * 9
    admit_in = (P(axis_name),) * 11 + tail + (P(axis_name),) * nleaves
    admit_out = ((P(axis_name),) * nleaves,) + (P(axis_name),) * 17
    admit_fns = {}
    for dyn in (False, True):
        admit_fns[dyn] = jax.jit(_shard_map_fn(
            make_local_chunk_admit(dyn), mesh, admit_in, admit_out))

    def run_chunk_admit(consts, state, queries, spec_state, spec_cfg,
                        budget, pend, cursor, t0, entry, dynamic=False):
        pend_q, pend_arr = pend
        sw, hi, pk, phi, ppk = spec_state
        sw = jnp.broadcast_to(jnp.asarray(sw, jnp.int32),
                              queries.shape[:2])
        cfg = tuple(jnp.asarray(c) for c in spec_cfg)
        (leaves, q, sw, hi, pk, phi, ppk, steps, lcnt, wsum, aq, ri, rd,
         rr, rn, ra, rt, cur) = admit_fns[bool(dynamic)](
            consts["db"], consts["vnorm"], consts["adj"], consts["pref"],
            consts["blk_perm"], queries, sw, hi, pk, phi, ppk, cfg,
            jnp.asarray(budget, jnp.int32), jnp.asarray(pend_q),
            jnp.asarray(pend_arr, jnp.int32),
            jnp.asarray(cursor, jnp.int32), jnp.asarray(t0, jnp.int32),
            *entry, *state)
        # steps is replicated (lockstep cond); cursors are replicated
        # too on the fan-out path (gsum'd) but per-shard when routed;
        # live/width traces are per-shard partial sums; the admit/evict
        # traces come back shard-major — normalize to the sim leg's
        # (K, S, Qs[, k]) layout
        return (EngineState(*leaves), q, (sw, hi, pk, phi, ppk),
                steps[0], lcnt.sum(axis=0), wsum.sum(axis=0),
                jnp.swapaxes(aq, 0, 1), jnp.swapaxes(ri, 0, 1),
                jnp.swapaxes(rd, 0, 1), jnp.swapaxes(rr, 0, 1),
                jnp.swapaxes(rn, 0, 1), jnp.swapaxes(ra, 0, 1),
                jnp.swapaxes(rt, 0, 1), cur if routed else cur[0])

    return EngineStepper(init, rnd, admit, retire, run_chunk, K,
                         run_chunk_admit)


def search_distributed(consts, queries, entry_vec, entry_norm, entry_id,
                       params: EngineParams, geom: EngineGeom, mesh,
                       axis_name: str = "lun"):
    """shard_map driver over a 1-D mesh; same stages, lax.all_to_all."""
    from jax.sharding import PartitionSpec as P

    def a2a(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.lax.all_to_all(x, axis_name, 0, 0), tree)

    def local_fn(db, vnorm, adj, pref, blk_perm, q, evec, enorm, eid):
        # shard_map hands (1, ...) blocks; work on the squeezed shard view
        lc = {"db": db[0], "vnorm": vnorm[0], "adj": adj[0],
              "pref": pref[0], "blk_perm": blk_perm[0]}
        ql = q[0]
        qq = jnp.sum(ql.astype(jnp.float32) ** 2, axis=-1)
        lc["queries"] = ql
        lc["qq"] = qq
        state0 = _init_state(ql, qq, evec, enorm, eid, params)
        active0 = jax.lax.psum((~state0.done).sum(), axis_name)

        def body(carry):
            state, t, _ = carry
            state = _round(state, lc, params, geom, a2a,
                           my_shard=jax.lax.axis_index(axis_name))
            active = jax.lax.psum((~state.done).sum(), axis_name)
            return state, t + 1, active

        def cond(carry):
            _, t, active = carry
            return (active > 0) & (t < params.search.rounds_cap)

        state, t, _ = jax.lax.while_loop(
            cond, body, (state0, jnp.int32(0), active0))
        out_i, out_d, stats = _finalize(state, params.search.k)
        stats = {k: v[None] for k, v in stats.items()}
        stats["total_rounds"] = t[None]
        return out_i[None], out_d[None], stats

    in_specs = (P(axis_name), P(axis_name), P(axis_name), P(axis_name),
                P(axis_name), P(axis_name), P(), P(), P())
    out_specs = (P(axis_name), P(axis_name), P(axis_name))
    f = _shard_map_fn(local_fn, mesh, in_specs, out_specs)
    return jax.jit(f)(consts["db"], consts["vnorm"], consts["adj"],
                      consts["pref"], consts["blk_perm"], queries,
                      entry_vec, entry_norm, entry_id)
