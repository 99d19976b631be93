"""Bitonic sort / top-k kernel (§IV-A "bitonic sorting" on the FPGA) — Pallas.

The paper offloads top-k selection to a bitonic sorting network on the
SmartSSD FPGA. TPU-native form: an in-VMEM bitonic network over (dist, id)
pairs, fully vectorized — each compare-exchange stage is two lane rotations
+ selects over the whole row, so the VPU executes a stage in O(M) lanes.

Lexicographic (dist, then id) ordering makes the network deterministic and
bit-identical to ``jax.lax.sort(num_keys=2)`` (the ref oracle).

The sort keys are always the (dist, id) pair; any number of extra
*payload* lanes ride along through the same compare-exchange network (the
engine uses one to keep the candidate lists' ``expanded`` flags aligned
with their (dist, id) entries). Payloads must be VPU-friendly dtypes
(i32/f32); the backend layer packs bools.

Shapes: (B, M) with M a power of two; grid over blocks of ``block_b``
rows so arbitrarily many lists sort in one launch.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _partner(x, stride: int, is_lower, roll):
    """Value at lane index idx ^ stride: a pair's lower element reads
    ``stride`` lanes up, its upper element ``stride`` lanes down. Two
    lane rotations and a select — the form Mosaic lowers (a reversed
    slice or a reshape that splits the lane dimension does not).
    ``roll`` has ``jnp.roll`` semantics: ``pltpu.roll`` inside the
    kernel, ``jnp.roll`` in the ref oracle."""
    axis = x.ndim - 1
    m = x.shape[axis]
    return jnp.where(is_lower, roll(x, m - stride, axis),
                     roll(x, stride, axis))


def _cmp_exchange(d, i, pay, j: int, k: int, roll):
    """One bitonic stage: partner = idx ^ (1<<j); ascending iff bit k unset.

    ``pay`` is a tuple of payload arrays swapped with the (d, i) keys.
    """
    stride = 1 << j
    idx = jax.lax.broadcasted_iota(jnp.int32, d.shape, d.ndim - 1)
    is_lower = (idx & stride) == 0
    # ascending half keeps min in the lower slot; descending the max.
    # keep_min = (bit j == bit k) of idx, spelled in i32 and logic ops
    # (Mosaic has no bool == bool)
    keep_min = ((idx >> j) & 1) == ((idx >> k) & 1)
    dp = _partner(d, stride, is_lower, roll)
    ip = _partner(i, stride, is_lower, roll)
    partner_less = (dp < d) | ((dp == d) & (ip < i))
    take_partner = (keep_min & partner_less) | (~keep_min & ~partner_less)
    d = jnp.where(take_partner, dp, d)
    i = jnp.where(take_partner, ip, i)
    pay = tuple(jnp.where(take_partner, _partner(p, stride, is_lower, roll),
                          p) for p in pay)
    return d, i, pay


def _bitonic_body(*refs):
    n = len(refs) // 2
    ins, outs = refs[:n], refs[n:]
    d = ins[0][...]
    i = ins[1][...]
    pay = tuple(r[...] for r in ins[2:])
    m = d.shape[-1]
    stages = int(math.log2(m))
    for k in range(1, stages + 1):
        for j in range(k - 1, -1, -1):
            d, i, pay = _cmp_exchange(d, i, pay, j, k, pltpu.roll)
    outs[0][...] = d
    outs[1][...] = i
    for r, p in zip(outs[2:], pay):
        r[...] = p


def merge_network(d, i, pay, roll=jnp.roll):
    """The final merge pass alone: sorts any *bitonic* row ascending.

    With k = log2(m), bit k is never set inside a row, so every
    compare-exchange runs ascending — exactly the last k-loop iteration
    of ``_bitonic_body``: log2(m) stages instead of the full network's
    log2(m)*(log2(m)+1)/2. Shared by the Pallas body (``pltpu.roll``)
    and the ref oracle (``jnp.roll``) so both tiers run the same
    comparator count.
    """
    m = d.shape[-1]
    stages = int(math.log2(m))
    for j in range(stages - 1, -1, -1):
        d, i, pay = _cmp_exchange(d, i, pay, j, stages, roll)
    return d, i, pay


def _merge_body(*refs):
    n = len(refs) // 2
    ins, outs = refs[:n], refs[n:]
    d, i, pay = merge_network(ins[0][...], ins[1][...],
                              tuple(r[...] for r in ins[2:]), pltpu.roll)
    outs[0][...] = d
    outs[1][...] = i
    for r, p in zip(outs[2:], pay):
        r[...] = p


def _launch_rows(body, dists, ids, payload, interpret: bool, block_b: int):
    B, M = dists.shape
    if M & (M - 1):
        raise ValueError(f"row width M={M} must be a power of two")
    # rows are independent: zero-pad B to a whole number of row blocks
    # and drop the padding on the way out. Compiled Mosaic needs
    # block_b to be a multiple of the 8-row sublane tile.
    Bp = B + (-B % block_b)
    operands = tuple(jnp.pad(x, ((0, Bp - B), (0, 0)))
                     for x in (dists, ids) + payload)
    spec = pl.BlockSpec((block_b, M), lambda b: (b, 0))
    out = pl.pallas_call(
        body,
        grid=(Bp // block_b,),
        in_specs=[spec] * len(operands),
        out_specs=[spec] * len(operands),
        out_shape=[jax.ShapeDtypeStruct((Bp, M), x.dtype)
                   for x in operands],
        interpret=interpret,
    )(*operands)
    return tuple(o[:B] for o in out)


@functools.partial(jax.jit, static_argnames=("interpret", "block_b"))
def bitonic_sort(dists: jax.Array, ids: jax.Array, *payload: jax.Array,
                 interpret: bool = True, block_b: int = 8):
    """Ascending lexicographic (dist, id) sort of each row.

    dists: (B, M) f32, ids: (B, M) i32, M a power of two. Rows run
    ``block_b`` per grid step (a multiple of 8 when compiled). Extra
    ``payload`` arrays (same shape) are permuted alongside the keys.
    """
    return _launch_rows(_bitonic_body, dists, ids, payload, interpret,
                        block_b)


@functools.partial(jax.jit, static_argnames=("interpret", "block_b"))
def bitonic_merge(dists: jax.Array, ids: jax.Array, *payload: jax.Array,
                  interpret: bool = True, block_b: int = 8):
    """Single merge pass over rows that are already *bitonic* in
    lexicographic (dist, id) order (ascending run then descending run).

    Same shapes/contract as :func:`bitonic_sort`, but only the final
    log2(M) compare-exchange stages run — O(M log M) comparators instead
    of the full network's O(M log^2 M). The caller (kernels.topk.ops.
    ``merge_sorted_op``) builds the bitonic row from two sorted lists.
    """
    return _launch_rows(_merge_body, dists, ids, payload, interpret,
                        block_b)
