"""jit'd public wrappers: padding to power-of-two, top-k slicing, merge.

``sort_op`` and ``merge_sorted_op`` are the dispatch points the
:mod:`repro.core.backend` layer calls: they own the pad-to-power-of-two
discipline ((BIG_DIST, ID_SENTINEL) filler sorts after every real entry,
payload lanes pad with zeros) and route to the Pallas networks or the
lax.sort oracle by mode. ``merge_sorted_op`` is the Gather stage's fast
path: two already-sorted lists become one bitonic row and a single
merge pass — no re-sorting of sorted data.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.topk.kernel import bitonic_merge, bitonic_sort
from repro.kernels.topk.ref import bitonic_merge_ref, bitonic_sort_ref
from repro.utils import BIG_DIST, next_pow2

ID_SENTINEL = jnp.int32(2**31 - 1)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def sort_op(dists: jax.Array, ids: jax.Array, *payload: jax.Array,
            mode: str = "auto"):
    """Lexicographic sort rows of (dists, ids); pads M to a power of two.

    Payload lanes (same (B, M) shape, i32/f32) ride along unsorted-key;
    they pad with zeros — padded entries sort after all real ones because
    the key filler is (BIG_DIST, ID_SENTINEL), so the padding never mixes
    into the returned M-prefix.
    """
    B, M = dists.shape
    m2 = next_pow2(M)
    if m2 != M:
        pad_d = jnp.full((B, m2 - M), BIG_DIST, dists.dtype)
        pad_i = jnp.full((B, m2 - M), ID_SENTINEL, ids.dtype)
        dists = jnp.concatenate([dists, pad_d], axis=1)
        ids = jnp.concatenate([ids, pad_i], axis=1)
        payload = tuple(
            jnp.concatenate([p, jnp.zeros((B, m2 - M), p.dtype)], axis=1)
            for p in payload)
    if mode == "auto":
        mode = "pallas" if _on_tpu() else "ref"
    if mode == "ref":
        out = bitonic_sort_ref(dists, ids, *payload)
    else:
        out = bitonic_sort(dists, ids, *payload,
                           interpret=(mode == "interpret"))
    return tuple(x[:, :M] for x in out)


def topk_op(dists: jax.Array, ids: jax.Array, k: int, mode: str = "auto"):
    d, i = sort_op(dists, ids, mode=mode)
    return d[:, :k], i[:, :k]


def merge_sorted_op(d_a: jax.Array, i_a: jax.Array,
                    d_b: jax.Array, i_b: jax.Array,
                    pay_a: tuple = (), pay_b: tuple = (),
                    mode: str = "auto"):
    """Merge two per-row ascending (dist, id)-sorted lists into one.

    d_a/i_a : (B, LA) sorted rows (e.g. the candidate list)
    d_b/i_b : (B, LB) sorted rows (e.g. this round's sorted proposals)
    pay_a/pay_b : matching payload-lane tuples ((B, LA) / (B, LB) each)
    returns : (d, i, *pay) of width LA + LB, fully sorted.

    Construction: concat(A, filler, reversed(B)) padded to the next
    power of two is bitonic — ascending into the (BIG_DIST, ID_SENTINEL)
    peak, then descending — so a single O(n log n) merge pass sorts it,
    instead of re-running the full O(n log^2 n) network over data that
    is already sorted. Filler sorts after every real entry, so the
    returned (LA + LB)-prefix is exactly the merged real rows.
    """
    if len(pay_a) != len(pay_b):
        raise ValueError(f"payload lanes must pair up across the two "
                         f"sides: {len(pay_a)} vs {len(pay_b)}")
    B, la = d_a.shape
    lb = d_b.shape[1]
    m2 = next_pow2(la + lb)
    padw = m2 - la - lb
    pad_d = jnp.full((B, padw), BIG_DIST, d_a.dtype)
    pad_i = jnp.full((B, padw), ID_SENTINEL, i_a.dtype)
    d = jnp.concatenate([d_a, pad_d, d_b[:, ::-1]], axis=1)
    i = jnp.concatenate([i_a, pad_i, i_b[:, ::-1]], axis=1)
    pay = tuple(
        jnp.concatenate([pa, jnp.zeros((B, padw), pa.dtype), pb[:, ::-1]],
                        axis=1)
        for pa, pb in zip(pay_a, pay_b))
    if mode == "auto":
        mode = "pallas" if _on_tpu() else "ref"
    if mode == "ref":
        out = bitonic_merge_ref(d, i, *pay)
    else:
        out = bitonic_merge(d, i, *pay, interpret=(mode == "interpret"))
    return tuple(x[:, :la + lb] for x in out)
