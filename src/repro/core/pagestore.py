"""Tiered page store: device frame cache over a host-RAM cold tier.

The paper's regime is data that does not fit in fast memory — traversal
reads stream from a capacity tier and the accelerator hides that
latency by overlapping fetches with compute. This module is the jax
version of that: the per-shard vector pages (``consts["db"]`` /
``consts["vnorm"]``) live cold in host RAM as numpy arrays, and a
fixed-capacity **frame buffer** of ``device_pages`` pages per shard is
the only device-resident copy. A translation table ``ttab`` (shape
``(S, NP)``, logical/physical page -> device frame, ``-1`` when not
resident) is handed to the engine through ``consts``; the round's
distance read goes through it (``engine._fd_distance_flat``), and a
row with a lane on a non-resident page stalls for the round (masked
merge, retried next round) instead of reading garbage. The row keeps
the distances it did read, so it needs only one of its pages resident
at a time, and merges once every lane has its distance.

Residency is managed **only at round-chunk boundaries**, on the host:

1. *note* — fold the chunk's ``page_touch`` / ``page_miss`` bitmaps
   into hit/miss counters, second-chance (clock) reference bits, and
   prefetch-hit attribution.
2. *commit* — scatter the payload staged at the *previous* boundary
   into its reserved frames (the ``device_put`` ran while the chunk
   computed, so the transfer is already overlapped; the scatter donates
   the frame buffer, keeping device memory flat).
3. *demand* — fetch the pages the chunk missed that are still not
   resident, evicting clock victims, in descending order of the wait
   key of the oldest row that missed each (``engine._wait_keys``), as
   long as a shard has an unpinned frame. The oldest stalled row leads
   every shard's order, so it gains at least one lane a boundary until
   its round merges: no row starves, whatever ``device_pages`` is.
   This is the synchronous, on-critical-path tier: misses already cost
   stall rounds.
4. *stage* — rank non-resident pages by the speculation signal (one-
   step traversal lookahead over the pool's candidate lists: adjacency
   neighbors weight 1, speculative prefetch-list neighbors weight
   ``page_w`` — the PR 6 page-efficiency machinery), reserve frames for
   the top ``prefetch_pages`` per shard, and ``device_put`` their
   payload asynchronously. The reserved frame keeps serving its old
   page until the commit at the next boundary.

``device_pages >= NP`` degenerates to an identity table over the full
store — every argument the kernel sees is bit-identical to the
untiered path (tested by hypothesis property).

The graph metadata (``adj`` / ``pref``) stays fully device-resident:
only the vector pages — the capacity term that actually scales with
the dataset — tier. Distributed (shard_map) serving does not support
the tiered store; the sim driver owns it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engine import INVALID, EngineGeom
from repro.core.traversal import ID_SENTINEL

# ID_SENTINEL is a device scalar; comparing host arrays against it would
# promote the whole predictor to traced jax ops (and warn on float64)
_SENTINEL = int(ID_SENTINEL)


def _pow2_pad(n: int) -> int:
    """Next power of two >= n (>= 1) — bounds scatter recompiles."""
    p = 1
    while p < n:
        p *= 2
    return p


@functools.partial(jax.jit, donate_argnums=(0, 1),
                   static_argnames=("pdev",))
def _scatter_frames(frames, vnf, sidx, fidx, pay_db, pay_vn, *, pdev):
    """Install payload rows into (shard, frame) slots; ``fidx == pdev``
    rows are padding holes (dropped). Donates the frame buffers so the
    device footprint stays flat across fetches."""
    del pdev   # static: distinguishes hole index across cache sizes
    frames = frames.at[sidx, fidx].set(pay_db, mode="drop")
    vnf = vnf.at[sidx, fidx].set(pay_vn, mode="drop")
    return frames, vnf


class PageStore:
    """Host-side residency manager for the tiered page store.

    Parameters
    ----------
    consts : dict
        The engine consts (full, untiered). ``db`` / ``vnorm`` are
        copied to host numpy as the cold tier; ``adj`` / ``pref`` /
        ``blk_perm`` are kept (host copies) for the prefetch
        predictor's one-step lookahead.
    geom : EngineGeom
        Placement arithmetic (ported to numpy here for the predictor).
    device_pages : int
        Frames per shard (``P_dev``). Clamped to ``NP``; ``>= NP`` is
        the bit-identical identity configuration.
    w_select : int
        The engine's selection width W — the lookahead expands the
        first W unexpanded candidates per row, mirroring
        ``_fa_select``.
    prefetch : bool
        False = demand-only fetching (the bench baseline).
    page_w : float
        Weight of speculative prefetch-list neighbors in the
        prediction score (adjacency neighbors weigh 1.0).
    prefetch_pages : int | None
        Staged pages per shard per boundary; default ``max(1,
        P_dev // 4)``.
    lookahead : int
        Expansion horizon in *rounds*: the predictor scores the pages
        the next ``lookahead`` rounds of expansions will read. A page
        staged at boundary k commits at boundary k+1 and serves chunk
        k+2 — one full chunk of latency — so this should span about
        two round-chunks.
    decay : float
        Per-round score decay across the expansion horizon.
    """

    def __init__(self, consts, geom: EngineGeom, device_pages: int, *,
                 w_select: int, prefetch: bool = True,
                 page_w: float = 1.0, prefetch_pages: int | None = None,
                 lookahead: int = 16, skip: int = 0,
                 decay: float = 0.95):
        self.cold_db = np.asarray(consts["db"])
        self.cold_vn = np.asarray(consts["vnorm"])
        self.adj = np.asarray(consts["adj"])
        self.pref = np.asarray(consts["pref"])
        self.blk_perm = np.asarray(consts["blk_perm"])
        self.S, self.NP, self.P, self.d = self.cold_db.shape
        if device_pages < 1:
            raise ValueError("device_pages must be >= 1")
        self.P_dev = int(min(device_pages, self.NP))
        self.geom = geom
        self.W = int(w_select)
        self.prefetch = bool(prefetch)
        self.page_w = float(page_w)
        self.budget = int(prefetch_pages if prefetch_pages
                          else max(1, self.P_dev // 4))
        self.lookahead = int(lookahead)
        self.skip = int(skip)
        self.decay = float(decay)

        # residency state: identity prefix resident at startup
        self.ttab = np.full((self.S, self.NP), -1, np.int32)
        self.ttab[:, :self.P_dev] = np.arange(self.P_dev, dtype=np.int32)
        self.frame_page = np.tile(
            np.arange(self.P_dev, dtype=np.int32), (self.S, 1))
        self.ref = np.zeros((self.S, self.P_dev), bool)
        self.hand = np.zeros((self.S,), np.int64)
        self.by_prefetch = np.zeros((self.S, self.P_dev), bool)
        self.reserved = np.zeros((self.S, self.P_dev), bool)
        self._staged = None          # (meta, device payload)

        self.page_hits = 0
        self.page_misses = 0
        self.demand_fetches = 0
        self.prefetch_issued = 0
        self.prefetch_hits = 0

        self.frames = jnp.asarray(self.cold_db[:, :self.P_dev])
        self.vnf = jnp.asarray(self.cold_vn[:, :self.P_dev])
        # compile the scatter at every payload size a boundary can send
        # (a power of two up to every frame), so none compiles while
        # serving; all-hole payloads leave the frames as they are
        u = 1
        while True:
            self._scatter(self._pad_payload([], u))
            if u >= self.S * self.P_dev:
                break
            u *= 2

    # -- geometry (numpy ports of EngineGeom's jnp arithmetic) ----------
    def _owner(self, vid):
        gp = vid // self.geom.page_size
        if self.geom.stripe == "striped":
            return (gp % self.S).astype(np.int32)
        return (gp // self.geom.pages_per_shard).astype(np.int32)

    def _local_page(self, vid):
        gp = vid // self.geom.page_size
        if self.geom.stripe == "striped":
            return gp // self.S
        return gp % self.geom.pages_per_shard

    def _phys_page(self, vid, owner):
        ppb = self.geom.pages_per_block
        lpage = self._local_page(vid)
        blk = np.clip(lpage // ppb, 0, self.blk_perm.shape[1] - 1)
        return self.blk_perm[owner, blk] * ppb + lpage % ppb

    # -- public surface -------------------------------------------------
    @property
    def num_pages(self) -> int:
        return self.NP

    @property
    def resident_fraction(self) -> float:
        return self.P_dev / self.NP

    def device_view(self):
        """Consts overrides: frame buffer + translation table."""
        return {"db": self.frames, "vnorm": self.vnf,
                "ttab": jnp.asarray(self.ttab)}

    def counters(self):
        return {"page_hits": int(self.page_hits),
                "page_misses": int(self.page_misses),
                "demand_fetches": int(self.demand_fetches),
                "prefetch_issued": int(self.prefetch_issued),
                "prefetch_hits": int(self.prefetch_hits)}

    def swap_epoch(self, consts):
        """Epoch swap (live index): adopt a new epoch's cold tier and
        restage every resident frame from it via the existing donated
        scatter — no new compiles, no shape change, no device-memory
        growth. Residency (ttab / frame_page / clock state) is
        preserved: the cache keeps the same *pages* resident, now with
        the new epoch's contents. In-flight staged payload from the old
        epoch is dropped (its reservations are released) — it would
        commit stale bytes. Returns the refreshed consts overrides.
        """
        cold_db = np.asarray(consts["db"])
        cold_vn = np.asarray(consts["vnorm"])
        if cold_db.shape != self.cold_db.shape:
            raise ValueError(
                f"epoch swap changed the store shape: {cold_db.shape} "
                f"!= {self.cold_db.shape} (pack every epoch at the "
                "session capacity)")
        self.cold_db = cold_db
        self.cold_vn = cold_vn
        self.adj = np.asarray(consts["adj"])
        self.pref = np.asarray(consts["pref"])
        self.blk_perm = np.asarray(consts["blk_perm"])
        self._staged = None
        self.reserved[:] = False
        rows = [(s, int(self.frame_page[s, f]), f)
                for s in range(self.S) for f in range(self.P_dev)
                if self.frame_page[s, f] >= 0]
        if rows:
            self._scatter(self._push_payload(rows))
        return self.device_view()

    def boundary(self, touch, miss, wait, cand_i, cand_e, done):
        """Process one round-chunk boundary; returns consts overrides.

        ``touch`` / ``miss``: (S, NP) bool bitmaps accumulated by the
        engine since the last boundary; ``wait``: (S, NP) the largest
        wait key of a row that missed each page. ``cand_i`` /
        ``cand_e`` / ``done``: the pool state the predictor looks ahead
        from.
        """
        touch = np.asarray(touch)
        miss = np.asarray(miss)
        wait = np.asarray(wait)
        pinned = np.zeros((self.S, self.P_dev), bool)

        self._note(touch)
        self.page_misses += int(miss.sum())
        self._commit(pinned)
        demand_s = np.zeros((self.S,), np.int64)
        self._demand(miss, wait, pinned, demand_s)
        if self.prefetch:
            self._stage(np.asarray(cand_i), np.asarray(cand_e),
                        np.asarray(done), pinned, demand_s)
        return self.device_view()

    # -- boundary stages ------------------------------------------------
    def _note(self, touch):
        self.page_hits += int(touch.sum())
        for s in range(self.S):
            f = self.ttab[s, touch[s]]
            f = f[f >= 0]
            self.prefetch_hits += int(self.by_prefetch[s, f].sum())
            self.by_prefetch[s, f] = False
            self.ref[s, f] = True

    def _commit(self, pinned):
        if self._staged is None:
            return
        meta, payload = self._staged
        self._staged = None
        self._scatter(payload)
        for s, page, f in meta:
            old = self.frame_page[s, f]
            if old >= 0:
                self.ttab[s, old] = -1
            self.frame_page[s, f] = page
            self.ttab[s, page] = f
            self.by_prefetch[s, f] = True
            self.reserved[s, f] = False
            self.ref[s, f] = False
            pinned[s, f] = True

    def _victim(self, s, pinned):
        """Second-chance clock over shard s's frames; -1 if all pinned."""
        for _ in range(2 * self.P_dev + 1):
            f = int(self.hand[s] % self.P_dev)
            self.hand[s] += 1
            if pinned[s, f] or self.reserved[s, f]:
                continue
            if self.ref[s, f]:
                self.ref[s, f] = False
                continue
            return f
        return -1

    def _install_meta(self, s, page, f):
        old = self.frame_page[s, f]
        if old >= 0:
            self.ttab[s, old] = -1
        self.frame_page[s, f] = page
        self.ttab[s, page] = f
        self.by_prefetch[s, f] = False
        self.ref[s, f] = True

    def _scatter(self, payload):
        self.frames, self.vnf = _scatter_frames(
            self.frames, self.vnf, *payload, pdev=self.P_dev)

    def _push_payload(self, rows):
        """rows: list of (s, page, f). The pow2-padded payload on the
        device (holes at fidx == P_dev drop when scattered)."""
        return self._pad_payload(rows, _pow2_pad(len(rows)))

    def _pad_payload(self, rows, u):
        """``rows`` in a payload of ``u`` slots, the rest holes."""
        sidx = np.zeros((u,), np.int32)
        fidx = np.full((u,), self.P_dev, np.int32)
        pay_db = np.zeros((u, self.P, self.d), self.cold_db.dtype)
        pay_vn = np.zeros((u, self.P), self.cold_vn.dtype)
        for j, (s, page, f) in enumerate(rows):
            sidx[j], fidx[j] = s, f
            pay_db[j] = self.cold_db[s, page]
            pay_vn[j] = self.cold_vn[s, page]
        return (jax.device_put(sidx), jax.device_put(fidx),
                jax.device_put(pay_db), jax.device_put(pay_vn))

    def _demand(self, miss, wait, pinned, demand_s):
        rows = []
        for s in range(self.S):
            pages = np.flatnonzero(miss[s] & (self.ttab[s] < 0))
            for page in pages[np.argsort(-wait[s, pages], kind="stable")]:
                f = self._victim(s, pinned)
                if f < 0:
                    break
                self._install_meta(s, int(page), f)
                pinned[s, f] = True
                demand_s[s] += 1
                rows.append((s, int(page), f))
        if rows:
            self._scatter(self._push_payload(rows))
            self.demand_fetches += len(rows)

    def _stage(self, cand_i, cand_e, done, pinned, demand_s):
        """Score-guided staging: a speculative page may only displace a
        frame whose own page scores strictly lower — and never a frame
        touched in the chunk just finished (``ref``) or pinned/reserved
        this boundary. Blind clock eviction here poisons the cache: the
        predictor is a ranking signal, so an incoming page that ranks
        below everything resident is not worth a fetch at all.

        Pressure throttle: each demand install this boundary already
        consumed one frame of the shard's cache slack, so the
        speculative budget backs off by that count — under thrash
        (working set >> frames) speculation adds churn without adding
        hits, and the throttle shuts it off exactly there."""
        buds = self.budget - 2 * demand_s
        if (buds <= 0).all():
            return
        score = self._predict(cand_i, cand_e, done)
        meta = []
        for s in range(self.S):
            bud = int(buds[s])
            if bud <= 0:
                continue
            sc = score[s].copy()
            sc[self.ttab[s] >= 0] = 0.0          # already resident
            cands = np.argsort(-sc, kind="stable")[:bud]
            cands = [int(p) for p in cands if sc[p] > 0.0]
            if not cands:
                continue
            evictable = np.flatnonzero(~pinned[s] & ~self.reserved[s]
                                       & ~self.ref[s])
            if evictable.size == 0:
                continue
            fscore = score[s][self.frame_page[s, evictable]]
            forder = evictable[np.argsort(fscore, kind="stable")]
            for page, f in zip(cands, forder):
                if sc[page] <= score[s][self.frame_page[s, f]]:
                    break    # both lists sorted: no later pair wins
                # reserve only: the frame keeps serving its old page
                # until the commit at the next boundary
                self.reserved[s, int(f)] = True
                meta.append((s, page, int(f)))
        if not meta:
            return
        self._staged = (meta, self._push_payload(meta))
        self.prefetch_issued += len(meta)

    def _predict(self, cand_i, cand_e, done):
        """Expansion-queue lookahead -> (S, NP) page demand score.

        ``_fa_select`` always expands the W best *unexpanded*
        candidates, and the lists are distance-sorted — so the
        unexpanded candidate at rank r is, to first order, the
        expansion ``r // W`` rounds from now, and the pages its
        adjacency row (weight 1.0) and stored prefetch list (weight
        ``page_w``) live on are exactly what phase B will read that
        round. Scoring the next ``lookahead`` rounds of this queue
        with a per-round ``decay`` predicts the read set over the
        whole double-buffer latency without walking the graph (a
        multi-hop walk diffuses into the whole neighborhood within a
        few hops; the queue is the traversal's own ranking of where
        it is actually going). New merges do perturb the queue's tail
        — that is what the decay and the score-guided eviction in
        ``_stage`` absorb.
        """
        score = np.zeros((self.S, self.NP), np.float64)
        valid = ((cand_i != _SENTINEL) & ~cand_e
                 & ~done[:, :, None])                    # (S, Qs, L)
        rank = np.cumsum(valid, axis=-1) - 1
        W = max(self.W, 1)
        # ranks below `skip` rounds expand before a staged page could
        # possibly arrive — their pages are the demand path's job, so
        # scoring them only spends budget on fetches that change
        # nothing (`skip` rounds the stage->commit latency up)
        pick = (valid & (rank >= self.skip * W)
                & (rank < self.lookahead * W))
        vids = cand_i[pick].astype(np.int64)
        wts = self.decay ** (rank[pick] // W).astype(np.float64)
        ok = (vids >= 0) & (vids < self.geom.n)
        vids, wts = vids[ok], wts[ok]
        if vids.size == 0:
            return score
        own = self._owner(vids)
        lslot = np.clip(self._local_page(vids) * self.geom.page_size
                        + vids % self.geom.page_size,
                        0, self.adj.shape[1] - 1)
        for nbrs, pw in ((self.adj[own, lslot], 1.0),
                         (self.pref[own, lslot], self.page_w)):
            if pw <= 0.0:
                continue
            nn = nbrs.astype(np.int64)                   # (V, R)
            nw = np.broadcast_to(wts[:, None] * pw, nn.shape)
            m = (nn != INVALID) & (nn >= 0) & (nn < self.geom.n)
            nn, nw = nn[m], nw[m]
            if nn.size == 0:
                continue
            no = self._owner(nn)
            pp = np.clip(self._phys_page(nn, no), 0, self.NP - 1)
            np.add.at(score, (no, pp), nw)
        return score
