"""The one traffic generator, and the front end that drives the served
entry with it.

A traffic mix is a data file, ``traffic/<mix>.json``, read here:

* ``"loop": "closed"`` -- one client submits its query set in requests of
  ``request_pools`` times the engine's slot pool, all due at once, and
  sends the next when the last returns (big-ann-benchmarks' batch mode).
  The query set is the seed's query pool in a seed-drawn order, cycled.
* ``"loop": "open"`` -- single queries arrive on a schedule whatever the
  server does: ``rate_qps`` times the window's seconds of them, spread
  over the window by exponential gaps (Poisson arrivals). Every seed
  offers the same set of gaps, one fixed draw, in an order of its own,
  and its own queries (its pool, cycled to that count) in an order of
  its own: the same load, the bursts in other places. The program has
  no accept-forever server yet, so this front end stands in for one: it
  merges every query that is due into the next call of the served
  entry.

``call(idx)`` serves the pool rows ``idx`` and returns what the served
entry returned. Latency runs from a query's due time to the return of the
call that answered it.
"""
from __future__ import annotations

import dataclasses
import json
import time

import numpy as np

from data import STREAM_ARRIVALS, STREAM_TRAFFIC, rng_for

SPANS = ("request.prepare", "stream_search", "frontend.wait")
GAPS_SEED = 0  # the one set of gaps every seed offers, in its own order


@dataclasses.dataclass
class Served:
    """What one window served, in call order."""

    qidx: list = dataclasses.field(default_factory=list)      # per call
    ids: list = dataclasses.field(default_factory=list)
    dists: list = dataclasses.field(default_factory=list)
    stats: list = dataclasses.field(default_factory=list)
    latency_s: list = dataclasses.field(default_factory=list)
    call_s: list = dataclasses.field(default_factory=list)
    call_at: list = dataclasses.field(default_factory=list)  # clock at start
    window_s: float = 0.0
    due: int = 0            # queries due in the window

    @property
    def answered(self) -> int:
        return sum(len(q) for q in self.qidx)


def load(path) -> dict:
    with open(path) as f:
        mix = json.load(f)
    if mix.get("loop") not in ("closed", "open"):
        raise ValueError(f"{path}: loop must be 'closed' or 'open'")
    return mix


def request_size(mix: dict, pool_slots: int) -> int:
    return int(mix.get("request_pools", 1)) * pool_slots


def ring_capacity(mix: dict, pool_slots: int) -> int:
    """The admission ring's fixed staged length: one request for the
    closed loop, ``ring_pools`` slot pools for the open loop."""
    if mix["loop"] == "closed":
        return request_size(mix, pool_slots)
    return int(mix["ring_pools"]) * pool_slots


def schedule(mix: dict, pool: int, seconds: float, seed: int):
    """Open loop: (due seconds, pool rows), in due order."""
    n = int(round(float(mix["rate_qps"]) * seconds))
    gaps = rng_for(GAPS_SEED, STREAM_ARRIVALS).exponential(1.0, n + 1)
    gaps = rng_for(seed, STREAM_ARRIVALS).permutation(gaps)
    due = np.cumsum(gaps)[:n] * (seconds / gaps.sum())
    rows = rng_for(seed, STREAM_TRAFFIC).permutation(
        np.resize(np.arange(pool), n))
    return due, rows


def requests(mix: dict, pool: int, pool_slots: int, seed: int):
    """Closed loop: endless pool-row requests in a seed-drawn order."""
    size = request_size(mix, pool_slots)
    order = rng_for(seed, STREAM_TRAFFIC).permutation(pool)
    order = np.concatenate([order, order[:size]])   # wrap round once
    start = 0
    while True:
        yield order[start:start + size]
        start = (start + size) % pool


def first_request(mix: dict, pool: int, pool_slots: int, seed: int):
    """The pool rows of the first call the traffic makes (warm-up)."""
    if mix["loop"] == "closed":
        return next(requests(mix, pool, pool_slots, seed))
    return schedule(mix, pool, 1.0, seed)[1][:1]


def _record(out: Served, idx, res, ts, te, due_abs):
    ids, dists, stats = res
    out.qidx.append(idx)
    out.ids.append(ids)
    out.dists.append(dists)
    out.stats.append(stats)
    out.call_s.append(te - ts)
    out.call_at.append(ts)
    out.latency_s.append(te - due_abs)


def closed_loop(call, mix, pool, pool_slots, seed, seconds, span):
    """Submit requests back to back; the window ends at the first
    completion after ``seconds``."""
    out = Served()
    reqs = requests(mix, pool, pool_slots, seed)
    t0 = time.perf_counter()
    while True:
        with span("request.prepare"):
            idx = next(reqs)
        ts = time.perf_counter()
        with span("stream_search"):
            res = call(idx)
        te = time.perf_counter()
        _record(out, idx, res, ts, te, np.full(len(idx), ts))
        out.due += len(idx)
        if te - t0 >= seconds:
            break
    out.window_s = te - t0
    return out


def open_loop(call, mix, pool, seed, seconds, span):
    """Serve every query due in ``seconds``; the window closes when the
    last of them is answered."""
    out = Served()
    due, rows = schedule(mix, pool, seconds, seed)
    out.due = len(due)
    t0 = time.perf_counter()
    nxt = 0
    while nxt < len(due):
        now = time.perf_counter() - t0
        if due[nxt] > now:
            with span("frontend.wait"):
                time.sleep(due[nxt] - now)
            continue
        with span("request.prepare"):
            end = int(np.searchsorted(due, now, side="right"))
            idx = rows[nxt:end]
        ts = time.perf_counter()
        with span("stream_search"):
            res = call(idx)
        te = time.perf_counter()
        _record(out, idx, res, ts, te, t0 + due[nxt:end])
        nxt = end
    out.window_s = time.perf_counter() - t0
    return out
