"""SiN distance kernel (§IV-C4) — Pallas TPU.

The paper's LUN-level accelerator reads one NAND page into the page buffer
and MACs a batch of queries against every vector in it. TPU-native form:

  * one grid step  = one "page read": BlockSpec pulls page ``page_ids[i]``
    of the shard-resident db (HBM) into VMEM,
  * the MAC group  = MXU matmul  (QB, d) @ (d, P)  in f32 accumulation,
  * the page buffer= VMEM block. Because the dispatcher sorts tiles by
    page id (dynamic scheduling, §VI-B1), consecutive grid steps that
    name the same page hit Pallas' pipeline copy-elision: the HBM->VMEM
    fetch is skipped exactly like the paper's ``pageLocBit`` fast path.

Distances use  q.q - 2 q.v + v.v ; qq and vnorm are precomputed so the
kernel is a single MXU op + broadcast adds per page.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_ROWS = 8   # f32 sublane tile: vnorm rows per block


def _distance_kernel(page_ids_ref, q_ref, qq_ref, db_ref, vnorm_ref, o_ref):
    i = pl.program_id(0)
    q = q_ref[0]                      # (QB, d)
    page = db_ref[0]                  # (P, d)
    qv = jax.lax.dot_general(
        q, page, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)          # (QB, P)
    # vnorm arrives as the 8-row (sublane-tile) block holding this
    # step's page; pick the page's own row
    vn = vnorm_ref[pl.ds(page_ids_ref[i] % _ROWS, 1), :]    # (1, P)
    o_ref[0] = (qq_ref[0].astype(jnp.float32)
                - 2.0 * qv
                + vn.astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_distances(page_ids: jax.Array, queries: jax.Array, qq: jax.Array,
                    db: jax.Array, vnorm: jax.Array,
                    interpret: bool = True) -> jax.Array:
    """Compute per-tile query->page squared-L2 distances.

    page_ids : (T,)        i32  page read per tile (scalar-prefetched)
    queries  : (T, QB, d)  f32/bf16  query tiles (dispatcher-grouped)
    qq       : (T, QB)     f32  per-query self dot
    db       : (NP, P, d)  f32/bf16  shard vector store (paged)
    vnorm    : (NP, P)     f32  per-vector self dot
    returns  : (T, QB, P)  f32

    Every block's last two dimensions are either whole array dimensions
    or multiples of the (8, 128) tile, as compiled Mosaic requires: qq
    rides as (T, QB, 1) columns and vnorm as 8-row blocks.
    """
    T, QB, d = queries.shape
    NP, P, _ = db.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(T,),
        in_specs=[
            pl.BlockSpec((1, QB, d), lambda i, pid: (i, 0, 0)),
            pl.BlockSpec((1, QB, 1), lambda i, pid: (i, 0, 0)),
            pl.BlockSpec((1, P, d), lambda i, pid: (pid[i], 0, 0)),
            pl.BlockSpec((_ROWS, P), lambda i, pid: (pid[i] // _ROWS, 0)),
        ],
        out_specs=pl.BlockSpec((1, QB, P), lambda i, pid: (i, 0, 0)),
    )
    return pl.pallas_call(
        _distance_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, QB, P), jnp.float32),
        interpret=interpret,
    )(page_ids, queries, qq[:, :, None], db, vnorm)
