"""Streaming query scheduler on top of the engine's round-stepper API.

NDSEARCH keeps the SEARSSD pipeline saturated by scheduling at the
*query* level, not the batch level (§V): finished queries leave the
pipeline immediately and fresh ones take their place, and the
speculative-search width adapts to the observed hit rate instead of
being fixed up front. The frozen-batch drivers (``search_sim`` /
``search_distributed``) violate both — finished queries occupy rows in
every remaining round's distance/merge/all_to_all work, and
``spec_width`` is a static knob.

This module closes the gap with three pieces over the stepper
(`engine_init / engine_run_chunk[_admit] / engine_admit /
engine_retire`):

  * **slot pool + continuous admission** — a fixed (S, Qs) pool of query
    slots. At every chunk boundary, rows whose query finished are
    *retired* (results emitted with per-query latency) and refilled
    from a pending queue via ``engine_admit`` (slot compaction by
    replacement): whenever the queue is non-empty, every row of every
    round's phase work is a live query, never padding.
  * **dynamic speculation** — a :class:`SpecController` watches the
    per-round deltas of the per-query ``n_dist`` counter the state
    already carries and adjusts the traced ``spec_w`` argument between
    0 and the static ``params.spec_width``: wide while the frontier is
    fresh (speculated 2nd-order neighbors mostly survive the bloom
    filter), narrow as acceptance collapses near convergence — cutting
    page reads the late speculation would have wasted. The update rule
    is pure jnp (:func:`repro.core.engine.spec_update`) so it keeps
    stepping per round *inside* a chunk.
  * **open-loop arrivals** — queries carry arrival *rounds* (the
    simulation clock is engine rounds); the scheduler admits a query
    once its arrival round has passed and a slot is free, and records
    wait + service latency per query.

**Host-sync model** (``round_chunk`` + ``injit_admit``): the inner
loop is device-paced, *including admission*. Each dispatch of
``engine_run_chunk_admit`` runs up to ``round_chunk`` engine rounds in
one jit'd ``while_loop``; the pending queue is pre-staged on device
(query vectors + arrival rounds sorted by arrival, a traced cursor),
and every in-jit round boundary seats arrived queries into freed slots
by the same ``engine_admit`` math and the same staging order the host
would use — so the chunk advances the serving clock straight through
arrivals and finishes, and the host syncs only at chunk boundaries
(``total_rounds / round_chunk`` dispatches when the pool stays busy).
The schedule stays *exactly* the per-round schedule: a seated row
evicts a finished one, whose results/rounds/n_dist were captured in
per-boundary admit traces, and the host replays those traces at the
chunk boundary to reconstruct ``owner``/``admit_t``/``retire_round``
(``retire_round = admit_round + rounds``) bit-exactly; per-round
live-count/width traces reconstruct occupancy and speculation traces
per round, not per boundary. Where the next chunk needs nothing of that
replay, the host enqueues it from the previous chunk's device outputs
first and replays while it runs (:meth:`StreamScheduler.run`'s boundary
order).

What remains host-side: **result emission** (QueryResult records are
materialized from the traces at chunk boundaries), the **frozen-mode
all-free gate** (``refill=False`` admits only into an all-free pool, a
global condition the host checks between waves — in-jit admission is a
refill-mode device path), **idle-clock jumps** (an empty pool with no
arrived query skips ahead to the next arrival without a dispatch;
the skipped rounds are counted as ``idle_rounds``), and **wall-clock
stamps** (a query admitted mid-chunk is stamped with the chunk's
launch wall time — round-accurate latency is exact, wall latency is
chunk-granular by construction).

``injit_admit=False`` falls back to the host-paced admission loop
(PR 4's model): the chunk budget is capped at the next pending arrival
and ``stop_on_finish`` ends the chunk on the first freed slot whenever
unadmitted queries remain, so chunk length collapses toward one round
while the queue drains — the measured dispatch gap is the point of the
in-jit path (``benchmarks/bench_serving.py`` round-chunk sweeps).

Per-query results are **bit-identical** to the one-shot drivers under
lossless capacities: every stage's per-row math depends only on that
row's own state, so which queries co-occupy the pool — and when they
were admitted — cannot change a query's trajectory
(tests/test_scheduler.py property-tests this over arrival orders, slot
counts and round_chunk sizes).

``refill=False`` degrades the scheduler to the frozen-batch discipline
(admit only into an all-free pool, like the fixed synchronous batches
of the computational-storage baseline the paper compares against) so
benchmarks can measure exactly what compaction buys.
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import spans
from repro.core.engine import (EngineGeom, EngineParams, EngineStepper,
                               engine_retire_live, make_stepper,
                               spec_update)
from repro.core.metrics import slot_occupancy
from repro.core.traversal import ID_SENTINEL
from repro.ft.inject import NEVER
from repro.utils import BIG_DIST, bloom_insert

INVALID = -1
_SENTINEL = int(ID_SENTINEL)    # host mirror (module scope: no per-call sync)


@dataclasses.dataclass
class SpecController:
    """Per-query hit-rate-driven speculation widths (the paper's dynamic
    speculative search, §V-B).

    Each slot row keeps its own width. Per round, ``update`` sees each
    query's accepted-proposal count for that round (the delta of the
    engine's per-query ``n_dist`` counter) and derives the query's own
    acceptance rate

        hit_q = accepted_q / (W * (max_degree + spec_w_used_q))

    where ``W * (max_degree + spec_w_used_q)`` is the number of
    adjacency (+ speculation) entries the engine actually served that
    query in the round — so ``hit_q`` is the fraction that survived
    dedup + bloom filtering. **Ordering contract:** ``update`` must see
    the widths that were *used* in the round that produced ``accepted``
    — it reads ``self.spec_w`` *before* overwriting it, and the in-jit
    port (:func:`repro.core.engine.spec_update`, called per round
    inside ``engine_run_chunk``) takes the used widths as an explicit
    argument for the same reason. The rate is *self-normalizing*: each
    query's smoothed hit is compared against its own running peak, so
    the policy transfers across datasets whose absolute acceptance
    levels differ. Width follows the normalized rate linearly between
    ``floor`` and ``ceil``: a fresh query (ratio near 1) keeps the full
    ``spec_max`` — preserving the cross-round page coalescing
    speculation buys early — while a converging query, whose
    speculation mostly re-proposes bloom-visited vertices or fetches
    pages it will never rank, ramps down to 0. The engine masks each
    query's prefetch columns beyond its current width, so widths move
    per round without recompiling.

    The update math itself lives in :func:`repro.core.engine.
    spec_update` (pure jnp, float32) — this class is the host-side
    mirror that carries ``(spec_w, hit, peak)`` across chunk boundaries
    and resets rows at admission, guaranteeing the per-round
    (``round_chunk=1``) and in-chunk controllers are bit-identical.
    """

    spec_max: int
    W: int
    max_degree: int
    floor: float = 0.2      # normalized hit at/below which spec_w -> 0
    ceil: float = 0.6       # normalized hit at/above which spec_w -> max
    ema: float = 0.5        # smoothing of the per-round hit estimate
    page_w: float = 0.0     # weight of the page-efficiency signal
                            # (accepted / fresh unique pages, normalized
                            # against its own peak like the hit rate):
                            # widths that win proposals but touch many
                            # fresh pages narrow. 0 keeps the pure
                            # hit-rate rule bit-identical.
    spec_w: np.ndarray = dataclasses.field(default=None, repr=False)
    _hit: np.ndarray = dataclasses.field(default=None, repr=False)
    _peak: np.ndarray = dataclasses.field(default=None, repr=False)
    _phit: np.ndarray = dataclasses.field(default=None, repr=False)
    _ppeak: np.ndarray = dataclasses.field(default=None, repr=False)

    @property
    def cfg(self):
        """The static rule parameters, dtyped for the traced jnp rule."""
        return (np.int32(self.spec_max), np.int32(self.W),
                np.int32(self.max_degree), np.float32(self.floor),
                np.float32(self.ceil), np.float32(self.ema),
                np.float32(self.page_w))

    def _ensure(self, shape):
        if self.spec_w is None or self.spec_w.shape != shape:
            self.spec_w = np.full(shape, self.spec_max, np.int32)
            self._hit = np.full(shape, -1.0, np.float32)
            self._peak = np.zeros(shape, np.float32)
            self._phit = np.full(shape, -1.0, np.float32)
            self._ppeak = np.zeros(shape, np.float32)

    def reset_rows(self, mask: np.ndarray):
        """Fresh queries restart at full width (called at admission)."""
        self._ensure(mask.shape)
        self.spec_w[mask] = self.spec_max
        self._hit[mask] = -1.0
        self._peak[mask] = 0.0
        self._phit[mask] = -1.0
        self._ppeak[mask] = 0.0

    def state(self):
        return (jnp.asarray(self.spec_w), jnp.asarray(self._hit),
                jnp.asarray(self._peak), jnp.asarray(self._phit),
                jnp.asarray(self._ppeak))

    def store(self, spec_state):
        """Adopt the post-chunk controller state from the device."""
        sw, hi, pk, phi, ppk = jax.device_get(spec_state)
        # np.array: keep private mutable copies (reset_rows mutates
        # them in place at admission); device_get batches the five
        # buffers into one transfer
        self.spec_w = np.array(sw, np.int32)
        self._hit = np.array(hi, np.float32)
        self._peak = np.array(pk, np.float32)
        self._phit = np.array(phi, np.float32)
        self._ppeak = np.array(ppk, np.float32)

    def update(self, accepted: np.ndarray, worked: np.ndarray,
               pages_delta=None) -> np.ndarray:
        """accepted: (S, Qs) this-round accepted proposals per slot;
        worked: (S, Qs) rows that were live this round; pages_delta:
        this round's fresh unique-page count per shard ((S,), the
        page-efficiency signal — ignored at page_w=0). ``self.spec_w``
        must still hold the widths used in that round (see class doc)."""
        self._ensure(np.shape(accepted))
        spec_state = spec_update(
            jnp.asarray(self.spec_w), jnp.asarray(self._hit),
            jnp.asarray(self._peak), jnp.asarray(accepted, jnp.int32),
            jnp.asarray(worked, bool), self.cfg,
            None if pages_delta is None
            else jnp.asarray(pages_delta, jnp.int32),
            jnp.asarray(self._phit), jnp.asarray(self._ppeak))
        self.store(spec_state)
        return self.spec_w


# cfg placeholder handed to the chunk when no controller is attached
# (dynamic=False never reads it, but the traced signature needs leaves)
_NULL_CFG = (np.int32(0), np.int32(1), np.int32(1),
             np.float32(0.0), np.float32(1.0), np.float32(0.5),
             np.float32(0.0))


@dataclasses.dataclass
class QueryResult:
    """Per-query record emitted at retirement."""

    qid: int
    ids: np.ndarray           # (k,) i32
    dists: np.ndarray         # (k,) f32
    arrival_round: int
    admit_round: int
    retire_round: int
    service_rounds: int       # rounds the query actually worked
    n_dist: int
    wall_latency_s: float     # admit -> retire wall clock
    truncated: bool = False   # retired incomplete: deadline hit, or a
                              # routed leg dropped/deadlined — the ids
                              # are the best-so-far, not a converged
                              # traversal
    legs_fused: int = 0       # routed: legs that finished cleanly and
                              # were fused (0 on the flat path)
    coverage: float = 1.0     # routed: legs_fused / R — the fraction
                              # of the query's routed shards actually
                              # searched to completion
    stall_rounds: int = 0     # serving-clock rounds the query aged
                              # without working: tiered-store page
                              # misses (core/pagestore.py) and fault
                              # stalls both mask the row's round while
                              # its age advances (routed: summed over
                              # legs)

    @property
    def wait_rounds(self) -> int:
        return self.admit_round - self.arrival_round

    @property
    def latency_rounds(self) -> int:
        return self.retire_round - self.arrival_round


@dataclasses.dataclass
class StreamStats:
    """Aggregate scheduler run statistics."""

    results: list             # [QueryResult] in retirement order
    total_rounds: int         # engine rounds stepped (busy rounds)
    occupancy: float          # mean live-slots / total-slots over the
                              # full serving clock (busy + idle rounds)
    occupancy_trace: list     # per-busy-round live-slot counts
    pages_unique: int         # cumulative unique page reads
    items_recv: int
    props_sent: int
    drops_b: int
    spec_trace: list          # mean spec_w over live rows, each round
    wall_s: float             # steady-state wall clock (excl. compile)
    host_dispatches: int = 0  # engine_run_chunk launches (host syncs)
    compile_s: float = 0.0    # this call's warm-up dispatch, seconds (a
                              # compile only the first time a shape is
                              # seen; the search.warmup span)
    idle_rounds: int = 0      # serving-clock rounds the pool sat empty
                              # waiting for an arrival (no engine work)
    injit_admit: bool = False  # admission path the run actually used
                               # (the scheduler's resolved flag)
    legs: int = 0             # routed serving: slot-pool rows served
                              # (N queries x R target shards); 0 = the
                              # scheduler ran one row per query
    items_by_shard: list = dataclasses.field(default_factory=list)
                              # per-shard items_recv — the routed path's
                              # work-skew/idle-shard evidence
    distance_lanes: int = 0   # query lanes the distance stage computed
                              # (tiles x tile width, summed over rounds
                              # and shards); items_recv / distance_lanes
                              # is the stage's lane occupancy
    shed: int = 0             # queries rejected by the shed overload
                              # policy (admission ring full at arrival)
    truncated: int = 0        # queries retired incomplete: deadline
                              # force-retire, or routed legs lost to a
                              # down shard / leg deadline
    quarantined: int = 0      # corrupt distances quarantined to
                              # BIG_DIST by the guard instead of
                              # entering the merge (guard_nonfinite)
    legs_fused_hist: list = dataclasses.field(default_factory=list)
                              # routed: legs_fused histogram, index f =
                              # queries whose f legs finished cleanly
                              # (length R+1; empty on the flat path)
    stalls: int = 0           # total stall rounds across retired
                              # queries (sum of QueryResult.
                              # stall_rounds) — tiered-store page
                              # misses and fault stalls
    prefetch_hits: int = 0    # tiered store: prefetched pages that
                              # were actually touched before eviction
    prefetch_issued: int = 0  # tiered store: pages staged by the
                              # speculative prefetcher
    resident_fraction: float = 1.0
                              # tiered store: device frames / logical
                              # pages per shard (1.0 = fully resident
                              # or no tiered store)
    stall_boundaries_max: int = 0
                              # tiered store: the most consecutive chunk
                              # boundaries any row of the call waited
                              # without merging a round (on pages, or on
                              # a fault-stalled shard)
    delta_hits: int = 0       # live index: retired result entries
                              # served from the delta segment
    tombstoned: int = 0       # live index: deletes applied during the
                              # run (main tombstones + killed delta rows)
    epoch_swaps: int = 0      # live index: background reindexes swapped
                              # in at chunk boundaries during the run
    swap_stall_rounds: int = 0
                              # live index: worked rounds discarded at
                              # swaps — rows whose whole frontier died
                              # with the old epoch restart from the new
                              # entry (translated rows discard nothing)
    inflight_dispatches: int = 0
                              # chunks launched before the previous
                              # chunk was synced (the run-ahead order)
    empty_dispatches: int = 0
                              # chunks that ran zero rounds: a call run
                              # ahead ends with one, and pays one at
                              # each idle gap in its arrivals

    def by_qid(self):
        return {r.qid: r for r in self.results}


def _clock_after(steps, t0):
    """The serving round a chunk ends at, ``t0 + steps``, computed on
    the device: the next chunk's ``t0`` when the host runs ahead."""
    return steps + t0


class _Book:
    """The host's replay of the slot pool over one call: which query
    holds each (shard, slot) row and since which round, the serving
    clock, and what the call has retired and stepped so far."""

    def __init__(self, arrivals, shape, map_result=None):
        self.arrivals = arrivals
        self.map_result = map_result        # live index: internal -> external ids
        self.owner = np.full(shape, INVALID, np.int64)   # slot -> qid
        self.admit_t = np.zeros(shape, np.int64)
        self.admit_wall = np.zeros(shape, np.float64)
        # live index: serving age carried across swap restarts (zeroed
        # at every seat; identically zero without swaps)
        self.age_base = np.zeros(shape, np.int64)
        self.rounds_base = np.zeros(shape, np.int64)
        self.t = 0                          # serving clock, rounds
        self.stepped = 0                    # engine rounds run
        self.results: list[QueryResult] = []
        self.occ_trace: list[int] = []
        self.spec_trace: list[float] = []

    def seat(self, s, r, qid, t, wall):
        self.owner[s, r] = qid
        self.admit_t[s, r] = t
        self.admit_wall[s, r] = wall
        self.age_base[s, r] = 0
        self.rounds_base[s, r] = 0

    def retire_row(self, s, r, ids, dists, age, rounds, n_dist, trunc,
                   wall):
        """Emit row (s, r)'s query and free the row. Exact even when the
        finish was mid-chunk: the row aged ``age`` consecutive serving
        rounds from admission (== ``rounds`` worked unless a fault or a
        page miss stalled it)."""
        ids, dists = ids.copy(), dists.copy()
        if self.map_result is not None:
            ids, dists = self.map_result(ids, dists)
        qid = int(self.owner[s, r])
        age = int(self.age_base[s, r] + age)
        rounds = int(self.rounds_base[s, r] + rounds)
        self.results.append(QueryResult(
            qid=qid, ids=ids, dists=dists,
            arrival_round=int(self.arrivals[qid]),
            admit_round=int(self.admit_t[s, r]),
            retire_round=int(self.admit_t[s, r]) + age,
            service_rounds=rounds, n_dist=int(n_dist),
            wall_latency_s=wall - self.admit_wall[s, r],
            truncated=bool(trunc), stall_rounds=age - rounds))
        self.owner[s, r] = INVALID
        self.age_base[s, r] = 0
        self.rounds_base[s, r] = 0


@dataclasses.dataclass
class _Chunk:
    """One launched round chunk: its index within the call, its outputs
    (device arrays until the host accounts it), and what the host needs
    to replay it."""

    cid: int
    launch_wall: Optional[float]
    state: object
    qbuf: object
    spec_state: tuple
    steps: object
    live_cnt: object
    width_sum: object
    trace: tuple = ()         # in-jit: admit_qidx and the six ret_* traces
    cur: object = None        # in-jit: the cursor after the chunk
    t0: object = None         # in-jit: the serving round at entry
    seat_qid: np.ndarray = None   # pending index -> qid ((S, cap) routed)
    fin: tuple = None         # the pool's (ids, dists), enqueued ahead

    def reads(self):
        """Every device value the account reads."""
        st = self.state
        return (self.live_cnt, self.width_sum,
                (st.done, st.rounds, st.n_dist, st.age, st.truncated),
                self.trace, self.cur, self.fin)


class StreamScheduler:
    """Continuous-batching scheduler over a fixed (S, Qs) slot pool.

    ``round_chunk`` sets how many engine rounds one device dispatch may
    run before the host is consulted (see the module docstring's
    host-sync model); any value produces the exact per-round schedule.
    ``injit_admit`` selects the device-side pending queue (None = on
    whenever ``refill`` is — frozen mode always keeps the host-side
    all-free gate, so the flag is a no-op there).
    """

    def __init__(self, consts, geom: EngineGeom, params: EngineParams,
                 entry, num_slots: int, mesh=None, axis_name: str = "lun",
                 controller: Optional[SpecController] = None,
                 refill: bool = True, round_chunk: int = 1,
                 stepper: Optional[EngineStepper] = None,
                 injit_admit: Optional[bool] = None,
                 routed: bool = False, ring_capacity: int = 0,
                 overload: str = "block", pagestore=None, live=None):
        if num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        if round_chunk < 1:
            raise ValueError(
                f"round_chunk must be >= 1, got {round_chunk}")
        if routed and not refill:
            # per-shard schedules are the point of routing; the frozen
            # all-free gate is a global condition that contradicts it
            raise ValueError("routed serving requires refill=True")
        if overload not in ("shed", "block"):
            raise ValueError(
                f"overload must be 'shed' or 'block', got {overload!r}")
        if ring_capacity < 0:
            raise ValueError(
                f"ring_capacity must be >= 0, got {ring_capacity}")
        self.pagestore = pagestore
        if pagestore is not None:
            # tiered page store: sim driver only (the distributed round
            # body refuses store_pages > 0), flat pool only (routed
            # legs re-enter the scheduler; tier the flat leg instead)
            if mesh is not None:
                raise ValueError(
                    "the tiered page store runs on the sim driver only "
                    "(mesh must be None)")
            if routed:
                raise ValueError(
                    "routed serving does not support the tiered page "
                    "store")
            if params.store_pages != pagestore.num_pages:
                raise ValueError(
                    f"params.store_pages={params.store_pages} != "
                    f"pagestore.num_pages={pagestore.num_pages}")
            if pagestore.S != geom.num_shards:
                raise ValueError(
                    f"pagestore built for {pagestore.S} shards, "
                    f"geom has {geom.num_shards}")
            # the scheduler's consts view swaps the full-resident pages
            # for the frame buffer + translation table; boundary() keeps
            # this view current as residency changes
            consts = dict(consts)
            consts.update(pagestore.device_view())
        elif params.store_pages > 0:
            raise ValueError(
                "params.store_pages > 0 needs a PageStore (pass "
                "pagestore=...) to own the translation table")
        self.live = live
        if live is not None:
            # live index (core/live.py): sim driver only — the
            # distributed round body has no delta/tombstone stage, and
            # swaps mutate host-owned consts. The caller's consts must
            # describe live's *current* epoch (with a pagestore, its
            # cold tier too); mid-run swaps are the scheduler's job.
            if mesh is not None:
                raise ValueError("the live index runs on the sim driver "
                                 "only (mesh must be None)")
            if params.delta_cap <= 0:
                raise ValueError(
                    "a live index needs params.delta_cap > 0 (the "
                    "static gate that compiles the delta-merge retire)")
            if params.delta_cap != live.delta_cap:
                raise ValueError(
                    f"params.delta_cap={params.delta_cap} != "
                    f"live.delta_cap={live.delta_cap}")
            if geom.n != live.capacity:
                raise ValueError(
                    f"geom.n={geom.n} != live capacity "
                    f"{live.capacity} (pack at the session capacity)")
            consts = dict(consts)
            consts.update(live.live_consts())
        elif params.delta_cap > 0:
            raise ValueError(
                "params.delta_cap > 0 needs a LiveIndex (pass live=...)")
        self.consts = consts
        self.geom = geom
        self.params = params
        self.entry = entry                       # (evec, enorm, eid)
        self.num_slots = num_slots               # per shard
        self.controller = controller
        self.refill = refill
        self.routed = routed
        self.round_chunk = round_chunk
        self.stepper = stepper or make_stepper(params, geom, mesh=mesh,
                                               axis_name=axis_name,
                                               round_chunk=round_chunk,
                                               routed=routed)
        if self.stepper.run_chunk is None:
            raise ValueError("stepper lacks a run_chunk stage — build it "
                             "via make_stepper(..., round_chunk=K)")
        if self.stepper.round_chunk < round_chunk:
            # engine_run_chunk clamps its budget to the stepper's own
            # static K; a smaller K would silently degrade to per-round
            raise ValueError(
                f"stepper was compiled for round_chunk="
                f"{self.stepper.round_chunk} < requested {round_chunk}")
        want_injit = refill if injit_admit is None \
            else bool(injit_admit) and refill
        if want_injit and self.stepper.run_chunk_admit is None:
            if injit_admit:   # explicitly requested, not the default
                raise ValueError(
                    "injit_admit=True needs a stepper with a "
                    "run_chunk_admit stage (make_stepper builds one)")
            want_injit = False
        self.injit_admit = want_injit
        self.S = geom.num_shards
        if ring_capacity > 0:
            if not self.injit_admit:
                raise ValueError(
                    "ring_capacity > 0 bounds the *device* pending "
                    "queue — it needs the in-jit admission path "
                    "(refill=True, injit_admit not disabled)")
            if routed:
                raise ValueError(
                    "ring_capacity applies to the flat pending queue; "
                    "routed serving stages per-shard queues whose "
                    "device footprint is already bounded by the "
                    "bucket capacity")
        if params.faults is not None:
            f = params.faults
            if f.num_shards != self.S:
                raise ValueError(
                    f"faults.num_shards={f.num_shards} != "
                    f"num_shards={self.S}")
            if f.any_stall and not self.injit_admit:
                raise ValueError(
                    "fault stalls (kill/delay) are evaluated on the "
                    "in-jit serving clock — run with the in-jit "
                    "admission path (refill=True, injit_admit not "
                    "disabled)")
            if f.any_kill and params.deadline_rounds == 0:
                raise ValueError(
                    "a killed shard never finishes its rows: set "
                    "deadline_rounds > 0 so they force-retire with "
                    "best-so-far results instead of hanging the run")
        self.ring_capacity = int(ring_capacity)
        self.overload = overload

    # -- host-side pool bookkeeping -----------------------------------------
    def _fresh_pool(self, d: int):
        S, Qs = self.S, self.num_slots
        queries = jnp.zeros((S, Qs, d), jnp.float32)
        state = self.stepper.init(self.consts, queries, *self.entry)
        # empty slots are parked: done=True rows do no phase work
        state = state._replace(done=jnp.ones((S, Qs), bool))
        return state, queries

    def _spec_inputs(self, shape):
        """(spec_state, cfg, dynamic) for the chunk: the controller's
        mirrors, or a constant-width 5-tuple when no controller."""
        if self.controller is not None:
            self.controller._ensure(shape)
            return self.controller.state(), self.controller.cfg, True
        if getattr(self, "_static_spec", None) is None:
            w = jnp.full(shape, self.params.spec_width, jnp.int32)
            z = jnp.zeros(shape, jnp.float32)
            self._static_spec = (w, z, z, z, z)
        return self._static_spec, _NULL_CFG, False

    def _retire(self, state, qbuf):
        """Per-slot results: plain finalize, or the live-index finalize
        (tombstone mask + delta merge) when a live index is attached.
        Zero churn keeps the live path bit-identical to the plain one
        (stable partition and merge — see ``_finalize_live``)."""
        if self.live is None:
            return self.stepper.retire(state)
        return engine_retire_live(
            state, qbuf, self.consts["tombs"], self.consts["delta_vec"],
            self.consts["delta_norm"], self.consts["delta_live"],
            k=self.params.search.k)

    def _swap_epoch(self, state, qbuf, owner, age_base, rounds_base):
        """Adopt a freshly reindexed epoch mid-session (live index).

        The consts swap is pure content (every epoch packs at the
        session capacity): device-resident consts are replaced; with a
        tiered store, the cold tier swaps and resident frames restage
        through the existing donated scatter. No stepper retraces.

        In-flight rows keep serving across the swap: each owned row's
        candidate list is translated old-internal -> new-internal via
        the external-id bridge, dead entries (deleted or reordered
        away) are compacted out (the list stays sorted — distances are
        content-identical across epochs), and the bloom filter is
        rebuilt over the surviving frontier on device. A row whose
        whole frontier died restarts from the new entry — its worked
        rounds are the swap's ``swap_stall_rounds`` and its served age
        carries over via ``age_base``/``rounds_base`` so latency
        accounting stays exact. Returns (state, qbuf, discarded
        rounds)."""
        live = self.live
        mc = live.main_consts()
        if self.pagestore is not None:
            self.consts.update(
                {k: mc[k] for k in ("adj", "pref", "blk_perm")})
            self.consts.update(self.pagestore.swap_epoch(mc))
        else:
            self.consts.update(mc)
        ev, en, ei = live.device_entry()
        if jnp.ndim(self.entry[0]) == 2:      # routed broadcast entries
            Sn = self.S
            ev = jnp.broadcast_to(ev[None], (Sn,) + ev.shape)
            en = jnp.broadcast_to(jnp.asarray(en)[None], (Sn,))
            ei = jnp.broadcast_to(jnp.asarray(ei)[None], (Sn,))
        self.entry = (ev, en, ei)

        trans = live.take_translation()
        rows = np.argwhere(owner != INVALID)
        if trans is None or rows.size == 0:
            return state, qbuf, 0
        sent = _SENTINEL
        ci, cd, ce, ages, rnds = jax.device_get(
            (state.cand_i, state.cand_d, state.cand_e, state.age,
             state.rounds))
        ci = np.array(ci)
        cd = np.array(cd)
        ce = np.array(ce)
        tr = np.asarray(trans)
        tmask = np.zeros(owner.shape, bool)
        dead_rows = np.zeros(owner.shape, bool)
        for s, r in rows:
            row_i = ci[s, r]
            valid = row_i != sent
            t_ids = np.where(
                valid, tr[np.clip(row_i, 0, tr.shape[0] - 1)], -1)
            keep = t_ids >= 0
            m = int(keep.sum())
            if m == 0:
                dead_rows[s, r] = True
                continue
            kd = cd[s, r][keep].copy()
            ke = ce[s, r][keep].copy()
            ci[s, r, :m] = t_ids[keep]
            ci[s, r, m:] = sent
            cd[s, r, :m] = kd
            cd[s, r, m:] = BIG_DIST
            ce[s, r, :m] = ke
            ce[s, r, m:] = False
            tmask[s, r] = True
        if tmask.any():
            jm = jnp.asarray(tmask)
            ci_j = jnp.asarray(ci)
            Sn, Qs, L = ci.shape
            flat = ci_j.reshape(Sn * Qs, L)
            fvalid = ((flat != ID_SENTINEL)
                      & jm.reshape(-1)[:, None])
            bl = bloom_insert(
                jnp.zeros(state.bloom.shape,
                          jnp.uint32).reshape(Sn * Qs, -1),
                flat, fvalid).reshape(state.bloom.shape)
            w3 = jm[..., None]
            state = state._replace(
                cand_i=jnp.where(w3, ci_j, state.cand_i),
                cand_d=jnp.where(w3, jnp.asarray(cd), state.cand_d),
                cand_e=jnp.where(w3, jnp.asarray(ce), state.cand_e),
                bloom=jnp.where(w3, bl, state.bloom))
        stall = 0
        if dead_rows.any():
            stall = int(rnds[dead_rows].sum())
            age_base[dead_rows] += ages[dead_rows]
            rounds_base[dead_rows] += rnds[dead_rows]
            state, qbuf = self.stepper.admit(
                state, qbuf, jnp.asarray(dead_rows), qbuf, *self.entry)
            if self.controller is not None:
                self.controller.reset_rows(dead_rows)
        return state, qbuf, stall

    def _page_boundary(self, state, steps, watch):
        """The tiered store's chunk boundary, in the ``search.pages``
        span: fold the chunk's touch/miss bitmaps into residency, commit
        the payload staged at the previous boundary (its device_put
        overlapped this chunk's compute), demand-fetch the misses oldest
        waiting row first, stage the next speculative fetch set, and
        refresh the consts view the next dispatch traces against.
        Returns the state with its bitmaps cleared."""
        (touch, miss, wait, cand_i, cand_e, done, rounds,
         age) = jax.device_get(
            (state.page_touch, state.page_miss, state.page_wait,
             state.cand_i, state.cand_e, state.done, state.rounds,
             state.age))
        self.consts.update(self.pagestore.boundary(
            touch, miss, wait, cand_i, cand_e, done))
        watch.update(done, rounds, age, steps)
        pz = jnp.zeros_like(state.page_touch)
        return state._replace(page_touch=pz, page_miss=pz,
                              page_wait=jnp.zeros_like(state.page_wait))

    def _warmup(self, state, qbuf, pend=None):
        """Compile the dispatch path actually used by :meth:`run` —
        admit/run_chunk/retire, or run_chunk_admit/retire when ``pend``
        (the staged device queue) is given — on shape-matched dummies,
        so ``wall_s`` and the first queries' wall latency measure
        steady state, not the one-time jit compile (mirrors serve.py's
        prefill/decode warmup). Returns the seconds spent."""
        S, Qs = self.S, self.num_slots
        t0 = time.perf_counter()
        spec_state, cfg, dyn = self._spec_inputs((S, Qs))
        if pend is not None:
            # compile on the real staged queue (its shape fixes the
            # trace) with an exhausted cursor and an all-parked pool:
            # the while_loop compiles but runs zero rounds, admitting
            # and mutating nothing — outputs are discarded anyway. The
            # cursor and clock go in as int32 scalars, the type both a
            # host value and a chunk's device output have in the window
            if np.ndim(pend[1]) == 2:   # routed: per-shard cursors
                done_cur = jnp.full((pend[1].shape[0],),
                                    pend[1].shape[1], jnp.int32)
            else:
                done_cur = np.int32(pend[1].shape[0])
            out = self.stepper.run_chunk_admit(
                self.consts, state, qbuf, spec_state, cfg, 1, pend,
                done_cur, np.int32(0), self.entry, dynamic=dyn)
            ids, dists, _ = self._retire(state, qbuf)
            if self.live is not None:
                # epoch-swap restarts admit host-side even on the
                # in-jit path — warm it so a mid-session swap costs no
                # compile (the p99-under-refresh contract)
                zmask = jnp.zeros((S, Qs), bool)
                astate, _ = self.stepper.admit(state, qbuf, zmask, qbuf,
                                               *self.entry)
                jax.block_until_ready(astate.done)
            jax.block_until_ready((out[0].done, out[13], ids, dists,
                                   _clock_after(out[3], np.int32(0))))
            return time.perf_counter() - t0
        zmask = jnp.zeros((S, Qs), bool)
        wstate, wq = self.stepper.admit(state, qbuf, zmask, qbuf,
                                        *self.entry)
        # the pool is all-parked, so the while_loop body compiles but
        # runs zero rounds — values are untouched and discarded anyway
        out = self.stepper.run_chunk(self.consts, wstate, wq, spec_state,
                                     cfg, 1, False, dynamic=dyn)
        ids, dists, _ = self._retire(wstate, wq)
        jax.block_until_ready((out[0].done, ids, dists))
        return time.perf_counter() - t0

    def _dispatch_admit(self, cid, state, qbuf, spec, pend, cursor, t0,
                        seat_qid, ahead):
        """Enqueue one in-jit admission chunk. ``cursor`` and ``t0`` are
        int32 scalars: host values, or the previous chunk's device
        outputs (one compiled program serves both). With ``ahead`` the
        next chunk is enqueued before this one is accounted, so the
        pool's retire is enqueued here, ahead of the next chunk on the
        device, and the host copies of everything the account reads
        start as soon as this chunk ends."""
        spec_state, cfg, dyn = spec
        launch_wall = time.perf_counter()
        (state, qbuf, spec_state, steps, live_cnt, width_sum, *trace,
         cur) = self.stepper.run_chunk_admit(
            self.consts, state, qbuf, spec_state, cfg, self.round_chunk,
            pend, cursor, t0, self.entry, dynamic=dyn)
        ch = _Chunk(cid, launch_wall, state, qbuf, spec_state, steps,
                    live_cnt, width_sum, tuple(trace), cur, t0, seat_qid)
        if ahead:
            ch.fin = self._retire(state, qbuf)[:2]
            for a in jax.tree_util.tree_leaves((steps, ch.reads())):
                a.copy_to_host_async()
        return ch

    def _enqueue_next(self, ch, cid, pend, phases):
        """Stage and enqueue the chunk after ``ch`` from ``ch``'s device
        outputs alone: the pool, the query buffer, the widths, the
        cursor and the clock ``t0 + steps``. The host has not accounted
        ``ch`` yet; the device decides every seat and retirement."""
        phases(spans.STAGE, chunk=cid)
        spec = (ch.spec_state,) + self._spec_inputs((self.S,
                                                     self.num_slots))[1:]
        t0 = _clock_after(ch.steps, ch.t0)
        phases(spans.DISPATCH, chunk=cid)
        return self._dispatch_admit(cid, ch.state, ch.qbuf, spec, pend,
                                    ch.cur, t0, ch.seat_qid, True)

    def _account(self, book, ch, steps):
        """The host's side of one synced chunk, the same in both
        boundary orders: replay the in-jit admit traces (emit each
        finished row a seat evicted, record the seat), advance the
        serving clock, extend the occupancy and width traces, and retire
        the rows the chunk left finished. One batched transfer brings
        what it reads. Returns the chunk's cursor on the host (None for
        a host-paced chunk)."""
        (live_cnt, width_sum, (done, rounds, n_dist, age, trunc), trace,
         cur, fin) = jax.device_get(ch.reads())
        if trace:
            now_wall = time.perf_counter()
            (admit_qidx, ret_i, ret_d, ret_rounds, ret_ndist, ret_age,
             ret_trunc) = trace
            routed = ch.seat_qid.ndim == 2
            for j in range(steps):
                for s, r in np.argwhere(admit_qidx[j] >= 0):
                    if book.owner[s, r] != INVALID:
                        # the seated query evicted a finished row —
                        # emit it from the boundary-j capture (bit-
                        # identical to a host-side retire on that round)
                        book.retire_row(
                            s, r, ret_i[j, s, r], ret_d[j, s, r],
                            ret_age[j, s, r], ret_rounds[j, s, r],
                            ret_ndist[j, s, r], ret_trunc[j, s, r],
                            now_wall)
                    # routed: pidx indexes shard s's own queue
                    p = admit_qidx[j, s, r]
                    book.seat(s, r, int(ch.seat_qid[s, p] if routed
                                        else ch.seat_qid[p]),
                              book.t + j, ch.launch_wall)
        book.t += steps
        book.stepped += steps
        if self.controller is not None:
            self.controller.store(ch.spec_state)
        live_cnt = live_cnt[:steps]
        book.occ_trace.extend(int(c) for c in live_cnt)
        book.spec_trace.extend(ws / c for ws, c in
                               zip(width_sum[:steps],
                                   np.maximum(live_cnt, 1)))
        # -- retire finished rows (the chunk already parked rows that
        # hit the per-query round cap, at the exact round boundary the
        # per-round scheduler would have)
        done_rows = (book.owner != INVALID) & done
        if done_rows.any():
            if fin is None:
                fin = jax.device_get(self._retire(ch.state, ch.qbuf)[:2])
            out_i, out_d = fin
            now_wall = time.perf_counter()
            for s, r in np.argwhere(done_rows):
                book.retire_row(s, r, out_i[s, r], out_d[s, r], age[s, r],
                                rounds[s, r], n_dist[s, r], trunc[s, r],
                                now_wall)
        return cur

    def run(self, queries: np.ndarray,
            arrivals: Optional[np.ndarray] = None,
            target_shards: Optional[np.ndarray] = None,
            phases: Optional[spans.Phases] = None) -> StreamStats:
        """Serve ``queries`` (N, d); ``arrivals`` are arrival rounds
        (default: all at round 0). Returns per-query results + metrics.

        ``phases`` is the caller's span sequence of the call, its
        ``search.setup`` open (see :mod:`repro.core.spans`); without it
        the run writes a ``search.call`` of its own.

        ``target_shards`` (N,) switches to **routed admission** (needs
        ``routed=True`` at construction): row i may only be seated in
        shard ``target_shards[i]``'s slot rows, each shard drains its
        own arrival-ordered queue independently, and a shard with no
        routed work stays parked — the two-tier serving discipline
        (``routed_stream_search`` fans queries into per-shard legs and
        fuses their top-k).

        **Boundary order.** Where every input of the next chunk is a
        device value — in-jit admission from a queue staged once, with
        no host hook between chunks — the host enqueues chunk j+1 from
        chunk j's outputs and then syncs and accounts chunk j while
        j+1 runs (with a page store, after chunk j's page boundary: the
        store must see j's misses first). Otherwise (routed, host-paced,
        a ring restaged every chunk, the shed policy, a live index, a
        host speculation controller) the host accounts chunk j before
        it stages j+1. The host learns that the call is done only when
        it accounts the last retirement, so a call run ahead ends with
        one chunk of zero rounds, and so does each idle gap in its
        arrivals (``StreamStats.empty_dispatches``)."""
        if phases is None:
            call = spans.next_call()
            with spans.span(spans.CALL, call=call), \
                    spans.Phases(call) as phases:
                phases(spans.SETUP)
                return self.run(queries, arrivals, target_shards, phases)
        queries = np.asarray(queries, np.float32)
        N, d = queries.shape
        arrivals = (np.zeros(N, np.int64) if arrivals is None
                    else np.asarray(arrivals, np.int64))
        order = np.argsort(arrivals, kind="stable")
        routed = target_shards is not None
        if routed and not self.routed:
            raise ValueError("pass routed=True at construction to "
                             "serve per-shard target_shards")
        S, Qs = self.S, self.num_slots
        K = self.round_chunk
        idle = 0                                      # empty-pool rounds
        dispatches = 0                                # run_chunk launches
        inflight = 0                  # launched before the previous sync
        empty = 0                     # chunks that ran zero rounds
        injit = self.injit_admit and N > 0
        # bounded admission ring (flat in-jit path only): a queue longer
        # than the ring slides through a window of `ring` staged
        # queries, restaged at each chunk boundary — memory stays flat
        # however long the stream is. A queue that fits is staged once.
        ring = self.ring_capacity if injit and not routed else 0
        restage = N > ring > 0
        ahead = (injit and not routed and not restage
                 and self.live is None and self.controller is None
                 and self.overload == "block")
        staged: list[int] = []        # ring window: qids, arrival order
        shed_qids: list[int] = []     # rejected by the shed policy
        stream_pos = 0                # ring cursor into `order`
        pend = None
        seat_qid = order              # pending index -> qid
        if routed:
            # per-shard admission queues, staged once via the Allocator
            # discipline (dispatch.py bucket scatter) in arrival order:
            # shard s's queue holds its own legs, arrival-sorted, and
            # is drained by shard s's cursor alone
            from repro.core.dispatch import (compute_ranks,
                                             scatter_to_buckets)
            tgt = np.asarray(target_shards, np.int32)
            dest = jnp.asarray(tgt[order])
            valid = jnp.ones(N, bool)
            rank, counts = compute_ranks(dest, valid, S)
            counts = jax.device_get(counts)
            cap = max(1, int(counts.max()))
            # INT32_MAX padding sorts after every real arrival, so the
            # in-jit searchsorted never sees a hole. One explicit
            # transfer brings both staging tables to the host together
            # (pre-serving setup: the clock has not started yet).
            legidx, arr_by_shard = jax.device_get((
                scatter_to_buckets(
                    dest, rank, valid, jnp.asarray(order.astype(np.int32)),
                    S, cap, fill=np.int32(INVALID)),   # (S, cap) -> row id
                scatter_to_buckets(
                    dest, rank, valid,
                    jnp.asarray(arrivals[order], jnp.int32), S, cap,
                    fill=np.int32(2**31 - 1))))
            next_qs = np.zeros(S, np.int64)       # per-shard cursors
            seat_qid = legidx
            if injit:
                pend = (scatter_to_buckets(
                    dest, rank, valid, jnp.asarray(queries[order]), S,
                    cap), jnp.asarray(arr_by_shard))
        elif injit and not restage:
            # device-side pending queue, staged once in admission order,
            # padded to the ring's length: its fixed shape lets one
            # compile serve every call. NEVER-padded tails sort after
            # every real arrival for the in-jit searchsorted.
            rows = max(N, ring)
            wq = np.zeros((rows, d), np.float32)
            wa = np.full((rows,), NEVER, np.int32)
            wq[:N] = queries[order]
            wa[:N] = arrivals[order]
            pend = (jnp.asarray(wq), jnp.asarray(wa))

        state, qbuf = self._fresh_pool(d)
        warm_pend = pend
        if restage:
            # the per-dispatch windows all share this (ring, d) shape,
            # so one warmup compile covers every dispatch (built on the
            # host: a call staged once compiles no fill of this shape)
            warm_pend = (jnp.asarray(np.zeros((ring, d), np.float32)),
                         jnp.asarray(np.full((ring,), NEVER, np.int32)))
        with spans.span(spans.WARMUP, call=phases.call):
            compile_s = self._warmup(state, qbuf, warm_pend)
        watch = _StallWatch()
        book = _Book(arrivals, (S, Qs), None if self.live is None
                     else self.live.map_result)
        epoch_swaps = 0
        swap_stall = 0
        live_del0 = self.live.deletes if self.live is not None else 0
        live_hit0 = self.live.delta_hits if self.live is not None else 0
        if self.live is not None:
            # pick up direct-API mutations applied since construction
            self.consts.update(self.live.live_consts())
        next_q = 0                                    # cursor into order
        wall0 = time.perf_counter()

        def next_arrival():
            """Earliest arrival round among unadmitted queries (None
            once every queue is drained)."""
            if routed:
                nas = [arr_by_shard[s, next_qs[s]] for s in range(S)
                       if next_qs[s] < counts[s]]
                return int(min(nas)) if nas else None
            if restage:
                if staged:
                    return int(arrivals[staged[0]])
                return (int(arrivals[order[stream_pos]])
                        if stream_pos < N else None)
            return int(arrivals[order[next_q]]) if next_q < N else None

        def pool_idle():
            """No query holds a row and none has arrived: a chunk
            launched now runs zero rounds."""
            na = next_arrival()
            return ((book.owner == INVALID).all()
                    and not (na is not None and na <= book.t))

        chunk = None          # launched, not yet accounted
        while chunk is not None or len(book.results) + len(shed_qids) < N:
            if chunk is None:
                # an idle-clock jump keeps this dispatch's stage open
                phases(spans.STAGE, chunk=dispatches)
                t = book.t
                owner = book.owner
                if self.live is not None and self.live.due(t):
                    # -- live-index boundary: apply every scheduled
                    # insert/delete due by the serving clock; a
                    # triggered reindex (refresh_every, or a full delta)
                    # swaps in here — the one place the pool is between
                    # dispatches
                    changed, nswaps = self.live.advance(t)
                    if nswaps:
                        epoch_swaps += nswaps
                        state, qbuf, lost = self._swap_epoch(
                            state, qbuf, owner, book.age_base,
                            book.rounds_base)
                        swap_stall += lost
                    if changed:
                        self.consts.update(self.live.live_consts())
                if not injit and routed:
                    # -- host-paced routed admission: each shard fills
                    # its own free rows from its own arrived queue
                    mask = np.zeros((S, Qs), bool)
                    new_q = np.zeros((S, Qs, d), np.float32)
                    now_wall = time.perf_counter()
                    for s in range(S):
                        free_rows = np.flatnonzero(owner[s] == INVALID)
                        i = 0
                        while (i < len(free_rows)
                               and next_qs[s] < counts[s]
                               and arr_by_shard[s, next_qs[s]] <= t):
                            qid = int(legidx[s, next_qs[s]])
                            r = free_rows[i]
                            mask[s, r] = True
                            new_q[s, r] = queries[qid]
                            book.seat(s, r, qid, t, now_wall)
                            next_qs[s] += 1
                            i += 1
                    if mask.any():
                        state, qbuf = self.stepper.admit(
                            state, qbuf, jnp.asarray(mask),
                            jnp.asarray(new_q), *self.entry)
                        if self.controller is not None:
                            self.controller.reset_rows(mask)
                elif not injit:
                    # -- host-paced admission: fill free slots from the
                    # arrived pending queue (the in-jit path seats these
                    # inside the chunk instead)
                    free = np.argwhere(owner == INVALID)
                    pool_all_free = len(free) == S * Qs
                    can_admit = self.refill or pool_all_free
                    staged = []
                    while (can_admit and len(staged) < len(free)
                           and next_q < N and arrivals[order[next_q]] <= t):
                        staged.append(order[next_q])
                        next_q += 1
                    if staged:
                        mask = np.zeros((S, Qs), bool)
                        new_q = np.zeros((S, Qs, d), np.float32)
                        now_wall = time.perf_counter()
                        for (s, r), qid in zip(free[:len(staged)], staged):
                            mask[s, r] = True
                            new_q[s, r] = queries[qid]
                            book.seat(s, r, qid, t, now_wall)
                        state, qbuf = self.stepper.admit(
                            state, qbuf, jnp.asarray(mask),
                            jnp.asarray(new_q), *self.entry)
                        if self.controller is not None:
                            self.controller.reset_rows(mask)

                live = int((owner != INVALID).sum())
                na = next_arrival()
                arrived_now = na is not None and na <= t
                if live == 0 and not (injit and arrived_now):
                    # pool idle until the next arrival: jump the serving
                    # clock without a dispatch. The skipped rounds ran
                    # no engine work but they are real serving time —
                    # count them so occupancy/throughput read over the
                    # full clock
                    nt = max(t + 1, na) if na is not None else t + 1
                    idle += nt - t
                    book.t = nt
                    continue

                spec = self._spec_inputs((S, Qs))
                if injit:
                    # -- device-paced chunk incl. admission: full
                    # budget, no stop-on-finish — freed slots are
                    # reseated in-jit at the exact boundary, and the
                    # admit/evict traces let the host replay the
                    # accounting afterwards
                    if restage:
                        # -- bounded ring: slide the window forward
                        # (refill in arrival order while seats are
                        # free), then — if shedding — reject every query
                        # that has *arrived* while the ring is full.
                        # Shed decisions are chunk-granular: an arrival
                        # mid-chunk is judged against the ring state at
                        # the next boundary.
                        while len(staged) < ring and stream_pos < N:
                            staged.append(int(order[stream_pos]))
                            stream_pos += 1
                        if self.overload == "shed":
                            while (len(staged) == ring and stream_pos < N
                                   and arrivals[order[stream_pos]] <= t):
                                shed_qids.append(int(order[stream_pos]))
                                stream_pos += 1
                        # restage the window (constant (ring, d) shape,
                        # so the warmup compile is reused)
                        seat_qid = np.asarray(staged, np.int64)
                        wq = np.zeros((ring, d), np.float32)
                        wa = np.full((ring,), NEVER, np.int32)
                        wq[:len(staged)] = queries[seat_qid]
                        wa[:len(staged)] = arrivals[seat_qid]
                        pend = (jnp.asarray(wq), jnp.asarray(wa))
                        cursor = np.int32(0)
                    elif routed:
                        cursor = jnp.asarray(next_qs, jnp.int32)
                    else:
                        cursor = np.int32(next_q)
                    phases(spans.DISPATCH, chunk=dispatches)
                    chunk = self._dispatch_admit(
                        dispatches, state, qbuf, spec, pend, cursor,
                        np.int32(t), seat_qid, ahead)
                else:
                    # -- host-paced admission needs the chunk to wake
                    # exactly when admission could matter. Free slots ->
                    # nothing can be admitted before the next arrival
                    # (the admission loop above drained everything
                    # <= t), so cap the chunk at that arrival and let
                    # mid-chunk finishes park. Full pool -> a finish may
                    # seat a waiting or imminent arrival, so stop in-jit
                    # on the first finish. Both keep the schedule
                    # identical to round_chunk=1. (frozen mode admits
                    # only into an all-free pool, which the in-jit
                    # every-live-row-done exit already detects)
                    budget = K
                    stop_on_finish = False
                    if routed:
                        # per-shard queues: a freed row only helps a
                        # waiting leg if it frees on that leg's own
                        # shard — a global stop-on-finish can't tell, so
                        # pace per-round (budget 1) while an arrived leg
                        # waits and wake exactly at the next arrival
                        # otherwise
                        if na is not None:
                            budget = max(1, min(K, na - t))
                    elif self.refill and na is not None:
                        if live < S * Qs:
                            budget = max(1, min(K, na - t))
                        else:
                            stop_on_finish = na <= t + K
                    phases(spans.DISPATCH, chunk=dispatches)
                    spec_state, cfg, dyn = spec
                    out = self.stepper.run_chunk(
                        self.consts, state, qbuf, spec_state, cfg,
                        budget, stop_on_finish, dynamic=dyn)
                    chunk = _Chunk(dispatches, None, out[0], qbuf, *out[1:])
                dispatches += 1
            # -- the chunk boundary. Run ahead: enqueue the next chunk
            # before this one is synced (resident) or right after its
            # page boundary (tiered), unless this chunk began with every
            # query retired or the pool idle: it then runs zero rounds,
            # and so would the next
            go = (ahead and len(book.results) < N and not pool_idle())
            nxt = None
            if go and self.pagestore is None:
                nxt = self._enqueue_next(chunk, dispatches, pend, phases)
                dispatches += 1
                inflight += 1
            phases(spans.SYNC, chunk=chunk.cid)
            # the chunk boundary's one sync: everything else transfers
            # in one batch in the account
            steps = int(jax.device_get(chunk.steps))
            if self.pagestore is not None:
                phases(spans.PAGES, chunk=chunk.cid)
                if steps:
                    chunk.state = self._page_boundary(chunk.state, steps,
                                                      watch)
            if go and self.pagestore is not None:
                nxt = self._enqueue_next(chunk, dispatches, pend, phases)
                dispatches += 1
            phases(spans.ACCOUNT, chunk=chunk.cid)
            empty += steps == 0
            cur = self._account(book, chunk, steps)
            if injit and routed:
                next_qs = cur.astype(np.int64)
            elif restage:
                del staged[:int(cur)]   # consumed window seats
            elif injit:
                next_q = int(cur)
            state, qbuf = chunk.state, chunk.qbuf
            chunk = nxt

        phases(spans.FINISH)
        # end-of-session counters: one transfer for the whole summary
        (pages_unique, items_recv, lanes, props_sent, drops_b,
         quarantined) = jax.device_get(
            (state.pages_unique, state.items_recv, state.distance_lanes,
             state.props_sent, state.drops_b, state.quarantined))
        results = book.results
        return StreamStats(
            results=results, total_rounds=book.stepped,
            occupancy=slot_occupancy(book.occ_trace, S * Qs,
                                     book.stepped + idle),
            occupancy_trace=book.occ_trace,
            pages_unique=int(pages_unique.sum()),
            items_recv=int(items_recv.sum()),
            props_sent=int(props_sent.sum()),
            drops_b=int(drops_b.sum()),
            spec_trace=book.spec_trace,
            wall_s=time.perf_counter() - wall0,
            host_dispatches=dispatches, compile_s=compile_s,
            idle_rounds=idle, injit_admit=self.injit_admit,
            items_by_shard=[int(x) for x in np.ravel(items_recv)],
            distance_lanes=int(lanes.sum()),
            shed=len(shed_qids),
            truncated=sum(1 for r in results if r.truncated),
            quarantined=int(quarantined.sum()),
            stalls=sum(r.stall_rounds for r in results),
            prefetch_hits=(self.pagestore.prefetch_hits
                           if self.pagestore is not None else 0),
            prefetch_issued=(self.pagestore.prefetch_issued
                             if self.pagestore is not None else 0),
            resident_fraction=(self.pagestore.resident_fraction
                               if self.pagestore is not None else 1.0),
            stall_boundaries_max=watch.most,
            delta_hits=(self.live.delta_hits - live_hit0
                        if self.live is not None else 0),
            tombstoned=(self.live.deletes - live_del0
                        if self.live is not None else 0),
            epoch_swaps=epoch_swaps,
            swap_stall_rounds=swap_stall,
            inflight_dispatches=inflight, empty_dispatches=empty)


class _StallWatch:
    """The most consecutive chunk boundaries a row waited without
    merging a round: a row live at two boundaries in a row, the same
    query (it aged through the whole chunk), with its ``rounds``
    unchanged, waited the chunk through."""

    def __init__(self):
        self.prev = None
        self.count = None
        self.most = 0

    def update(self, done, rounds, age, steps):
        if self.prev is None:
            self.count = np.zeros(done.shape, np.int64)
        else:
            pdone, prounds, page = self.prev
            waited = (~pdone & ~done & (age == page + steps)
                      & (rounds == prounds))
            self.count = np.where(waited, self.count + 1, 0)
            self.most = max(self.most, int(self.count.max()))
        self.prev = (done, rounds, age)


def poisson_arrivals(rate: float, n: int, seed: int = 0) -> np.ndarray:
    """Open-loop arrival rounds: ``rate`` mean arrivals per engine
    round (exponential inter-arrival gaps). rate <= 0 -> all at 0.

    Cumulative gaps are rounded half-up to the integer round clock —
    truncation (plain ``astype``) would floor every arrival ~0.5 rounds
    early, biasing the realized arrival rate above the requested one in
    any measurement window."""
    if rate <= 0:
        return np.zeros(n, np.int64)
    rng = np.random.default_rng(seed)
    gaps = rng.exponential(1.0 / rate, n)
    return np.floor(np.cumsum(gaps) + 0.5).astype(np.int64)


def _make_controller(params, geom, dynamic_spec, spec_page_w=0.0):
    if not dynamic_spec:
        return None
    if params.spec_width <= 0:
        raise ValueError(
            "dynamic_spec needs a speculation budget to adapt: set "
            "spec_width > 0 (it is the controller's maximum width)")
    return SpecController(spec_max=params.spec_width,
                          W=params.search.W,
                          max_degree=geom.max_degree,
                          page_w=float(spec_page_w))


def default_leg_L(n_shard: int, max_degree: int, k: int) -> int:
    """Routed per-leg candidate-list length from per-shard graph depth.

    A Vamana-style leg converges after roughly the shard graph's
    greedy-path depth ``log_R(n_shard)`` hops, each hop displacing at
    most a few frontier entries — so the list needs the k result seats
    plus headroom proportional to that depth, *independent of the
    global L the caller tuned for the full graph*. The old default
    ``max(k, L // R)`` silently moved the pages-vs-recall crossover
    whenever the shard graphs got deeper (PR 6 caveat); this one tracks
    the shard size directly. ``--leg-L`` stays the explicit override.
    """
    depth = math.ceil(math.log(max(n_shard, 2))
                      / math.log(max(max_degree, 2)))
    return k + 2 * depth


def stream_search(consts, geom, params, entry, queries,
                  num_slots: int, arrivals=None, mesh=None,
                  dynamic_spec: bool = False, refill: bool = True,
                  round_chunk: int = 1, injit_admit=None,
                  spec_page_w: float = 0.0, ring_capacity: int = 0,
                  overload: str = "block", pagestore=None, live=None):
    """Convenience wrapper: run the streaming scheduler and return
    (ids (N, k), dists (N, k), StreamStats) in query order.  A query
    shed by the overload policy keeps its INVALID/0 row in the output
    (check ``stats.shed`` / absence from ``stats.results``). With
    ``live`` the returned ids are external ids (stable across epoch
    swaps; identical to internal ids in a zero-churn session).

    The call writes the host spans of :mod:`repro.core.spans`."""
    call = spans.next_call()
    with spans.span(spans.CALL, call=call), spans.Phases(call) as phases:
        phases(spans.SETUP)
        ctrl = _make_controller(params, geom, dynamic_spec, spec_page_w)
        sched = StreamScheduler(consts, geom, params, entry,
                                num_slots=num_slots, mesh=mesh,
                                controller=ctrl, refill=refill,
                                round_chunk=round_chunk,
                                injit_admit=injit_admit,
                                ring_capacity=ring_capacity,
                                overload=overload, pagestore=pagestore,
                                live=live)
        stats = sched.run(queries, arrivals, phases=phases)
        k = params.search.k
        n = np.asarray(queries).shape[0]
        ids = np.full((n, k), INVALID, np.int32)
        dists = np.zeros((n, k), np.float32)
        for r in stats.results:
            ids[r.qid] = r.ids
            dists[r.qid] = r.dists
    return ids, dists, stats


def routed_stream_search(consts, geom, params, entry, queries, *,
                         router, topr: int, num_slots: int,
                         arrivals=None, mesh=None,
                         dynamic_spec: bool = False,
                         round_chunk: int = 1, injit_admit=None,
                         shard_entries=None, leg_L=None,
                         spec_page_w: float = 0.0, down_shards=None,
                         live=None):
    """Two-tier routed serving (core/router.py): coarse-route each
    query to its top-R shards, serve one *leg* per (query, shard) on
    that shard's independent slot schedule, and fuse the per-leg top-k
    at retire time through the backend's bitonic merge tree.

    ``topr >= num_shards`` degenerates to the all-shard fan-out
    semantics: one leg per query (global proposals, global entry) —
    per-query results are bit-identical to :func:`stream_search` by
    admission-order invariance, the routed layer only changing *where*
    the row sits. ``topr < num_shards`` confines each leg to its home
    shard's subgraph (``local_only``) seeded at that shard's own medoid
    (``shard_entries``, as built by ``build_routed_index``), with the
    per-leg candidate list scaled to ``leg_L`` (default
    :func:`default_leg_L` — derived from the per-shard graph depth, so
    deeper shard graphs don't silently move the pages-vs-recall
    crossover).

    Returns (ids (N, k), dists (N, k), StreamStats) in query order;
    ``stats.results`` holds fused per-query records (``n_dist`` summed
    over legs, latency = the slowest leg — a query retires only when
    all its legs have) and ``stats.legs`` the slot rows served.

    **Degraded fusion** (``down_shards``): legs routed to a shard in
    ``down_shards`` are dropped host-side before scheduling — the
    healthy R-f legs run normally and the query fuses whatever
    finished, reporting ``legs_fused`` / ``coverage`` and
    ``truncated=True`` instead of stalling on a shard that will never
    answer.  A shard that dies *mid-run* is the engine's job instead:
    inject a kill via ``params.faults`` (with ``deadline_rounds`` set)
    and its legs force-retire with best-so-far results, landing in the
    same degraded-fusion accounting because a deadlined leg is a
    non-clean leg.  A query whose every leg is down retires at its
    arrival round with all-INVALID ids, coverage 0.
    """
    from repro.core.router import BIG_DIST, fuse_topk

    queries = np.asarray(queries, np.float32)
    N = queries.shape[0]
    S = geom.num_shards
    k = params.search.k
    arrivals = (np.zeros(N, np.int64) if arrivals is None
                else np.asarray(arrivals, np.int64))
    topr = int(topr)
    if topr < 1:
        raise ValueError(f"topr must be >= 1, got {topr}")
    if live is not None and topr < S:
        # legs on topr < S shard-local subgraphs would each merge the
        # full delta segment, duplicating delta ids across the fused
        # top-k (and the shard partition itself changes on every swap);
        # only the degenerate one-leg-per-query branch is live-safe
        raise ValueError("live index requires topr >= num_shards "
                         "(shard-local legs cannot mask a shared delta)")
    if topr >= S:
        R = 1
        targets = np.asarray(router.route(queries, 1))
        leg_params = params
        sh_entry = tuple(
            jnp.asarray(np.broadcast_to(
                np.asarray(a), (S,) + np.shape(np.asarray(a))))
            for a in entry)
    else:
        R = topr
        if shard_entries is None:
            raise ValueError(
                "topr < num_shards needs per-shard entries "
                "(shard_entries; build_routed_index provides them)")
        targets = np.asarray(router.route(queries, R))
        lg = (int(leg_L) if leg_L
              else default_leg_L(geom.n // S, geom.max_degree, k))
        leg_params = dataclasses.replace(
            params,
            search=dataclasses.replace(params.search, L=max(k, lg)),
            local_only=True)
        sh_entry = tuple(jnp.asarray(a) for a in shard_entries)

    # leg rows: query i's leg j is row i*R + j, inheriting the query's
    # vector and arrival and targeting its j-th routed shard
    leg_q = np.repeat(queries, R, axis=0)
    leg_arr = np.repeat(arrivals, R)
    leg_tgt = targets[:, :R].reshape(-1).astype(np.int32)

    # degraded routing: drop legs whose target shard is known-down —
    # the scheduler only ever sees alive legs, so nothing can stall on
    # a dead shard's never-draining queue
    down = np.zeros(S, bool)
    if down_shards is not None:
        ds = np.asarray(down_shards, np.int64).reshape(-1)
        if ds.size and (ds.min() < 0 or ds.max() >= S):
            raise ValueError(f"down_shards must be in [0, {S}), "
                             f"got {sorted(set(ds.tolist()))}")
        down[ds] = True
        if down.all():
            raise ValueError("every shard is down — nothing to serve")
    alive_rows = np.flatnonzero(~down[leg_tgt])
    # leg row id -> its position (= qid) in the scheduled alive subset
    pos_of = {int(row): p for p, row in enumerate(alive_rows)}

    ctrl = _make_controller(leg_params, geom, dynamic_spec, spec_page_w)
    sched = StreamScheduler(consts, geom, leg_params, sh_entry,
                            num_slots=num_slots, mesh=mesh,
                            controller=ctrl, refill=True,
                            round_chunk=round_chunk,
                            injit_admit=injit_admit, routed=True,
                            live=live)
    leg_stats = sched.run(leg_q[alive_rows], leg_arr[alive_rows],
                          target_shards=leg_tgt[alive_rows])

    by = leg_stats.by_qid()
    leg_i = np.full((N, R, k), INVALID, np.int32)
    leg_d = np.zeros((N, R, k), np.float32)
    for p, rec in by.items():
        row = int(alive_rows[p])
        leg_i[row // R, row % R] = rec.ids
        leg_d[row // R, row % R] = rec.dists
    if R == 1:
        ids, dists = leg_i[:, 0].copy(), leg_d[:, 0].copy()
        # match fuse_topk's padding contract on the degenerate path: a
        # dropped/absent leg reads (INVALID, BIG_DIST), not stale 0.0
        dists[ids == INVALID] = BIG_DIST
    else:
        di, ii = fuse_topk(leg_d, leg_i, leg_params.backend)
        dists, ids = np.asarray(di), np.asarray(ii)

    results = []
    hist = [0] * (R + 1)       # index f: queries with f clean legs
    for i in range(N):
        legs = [by[pos_of[i * R + j]] for j in range(R)
                if i * R + j in pos_of]
        # a leg is *fused cleanly* if it ran and converged; a deadlined
        # (truncated) leg still contributed its best-so-far candidates
        # but the query's coverage no longer spans that shard's subgraph
        fused = sum(1 for lr in legs if not lr.truncated)
        hist[fused] += 1
        if legs:
            results.append(QueryResult(
                qid=i, ids=ids[i].copy(), dists=dists[i].copy(),
                arrival_round=int(arrivals[i]),
                admit_round=min(lr.admit_round for lr in legs),
                retire_round=max(lr.retire_round for lr in legs),
                service_rounds=max(lr.service_rounds for lr in legs),
                n_dist=sum(lr.n_dist for lr in legs),
                wall_latency_s=max(lr.wall_latency_s for lr in legs),
                truncated=fused < R, legs_fused=fused,
                coverage=fused / R,
                stall_rounds=sum(lr.stall_rounds for lr in legs)))
        else:
            # every routed shard down: retire immediately, empty-handed
            results.append(QueryResult(
                qid=i, ids=ids[i].copy(), dists=dists[i].copy(),
                arrival_round=int(arrivals[i]),
                admit_round=int(arrivals[i]),
                retire_round=int(arrivals[i]), service_rounds=0,
                n_dist=0, wall_latency_s=0.0, truncated=True,
                legs_fused=0, coverage=0.0))
    results.sort(key=lambda r: (r.retire_round, r.qid))
    stats = dataclasses.replace(
        leg_stats, results=results, legs=len(alive_rows),
        truncated=sum(1 for r in results if r.truncated),
        legs_fused_hist=hist,
        stalls=sum(r.stall_rounds for r in results))
    return ids, dists, stats
