"""The one traffic generator: every seed offers the same load in an order
of its own."""
import pathlib
import sys

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import frontend  # noqa: E402

OPEN = {"loop": "open", "rate_qps": 400, "ring_pools": 2}
CLOSED = {"loop": "closed", "request_pools": 2}
SEEDS = (2**31 + 3, 2**33 + 9)


def test_open_loop_offers_one_set_of_gaps_in_each_seeds_order():
    (due_a, rows_a), (due_b, rows_b) = (frontend.schedule(OPEN, 64, 5.0, s)
                                        for s in SEEDS)
    assert len(due_a) == len(due_b) == 2000
    assert (np.diff(due_a) >= 0).all() and due_a[-1] <= 5.0
    assert not np.allclose(due_a, due_b)
    # each seed's gaps are the one fixed set (less one), in its order
    full = np.sort(frontend.rng_for(
        frontend.GAPS_SEED, frontend.STREAM_ARRIVALS).exponential(1.0, 2001))
    for due in (due_a, due_b):
        gaps = np.diff(np.concatenate([[0.0], due]))
        gaps = np.sort(gaps) * (full.sum() / 5.0)
        near = np.searchsorted(full, gaps).clip(1, len(full) - 1)
        err = np.minimum(abs(full[near] - gaps), abs(full[near - 1] - gaps))
        assert err.max() < 1e-3 * full.mean()
    assert not np.array_equal(rows_a, rows_b)
    for rows in (rows_a, rows_b):
        np.testing.assert_array_equal(np.bincount(rows, minlength=64),
                                      np.full(64, 2000 // 64 + 0)
                                      + (np.arange(64) < 2000 % 64))
    due_a2, rows_a2 = frontend.schedule(OPEN, 64, 5.0, SEEDS[0])
    np.testing.assert_array_equal(due_a2, due_a)
    np.testing.assert_array_equal(rows_a2, rows_a)


def test_closed_loop_cycles_the_pool_in_each_seeds_order():
    got = []
    for s in SEEDS:
        reqs = frontend.requests(CLOSED, 64, 16, s)
        got.append(np.concatenate([next(reqs) for _ in range(4)]))
        assert len(got[-1]) == 128
        np.testing.assert_array_equal(np.bincount(got[-1], minlength=64),
                                      np.full(64, 2))
    assert not np.array_equal(*got)
