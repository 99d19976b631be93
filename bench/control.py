"""The lower-precision control: the plain reference put in the served
entry's place, computed one precision below what the configuration
states (bfloat16 for float32).

The program's own bfloat16 path (``EngineParams.payload_bf16``) does not
compile with the Pallas distance kernel (Mosaic refuses a bf16 x f32
matmul), so the reference stands in for it. The benchmark's own runs
never use this; its tests do, and so does its reading on the chip.
"""
from __future__ import annotations

import types

import ml_dtypes
import numpy as np

import reference


def to_bf16(x: np.ndarray) -> np.ndarray:
    """Round float32 values to bfloat16, held as float32."""
    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float32)


def bf16_stream_search(db: np.ndarray, adj: np.ndarray, entry: int,
                       cfg: dict):
    """A stand-in for ``repro.core.scheduler.stream_search`` that answers
    every query with the lockstep reference over bfloat16 vectors."""
    db16 = to_bf16(db)
    L, W, k = int(cfg["L"]), int(cfg["W"]), int(cfg["k"])

    def stream_search(consts, geom, params, entry_dev, queries, **kw):
        q16 = to_bf16(queries)
        ids = np.empty((len(q16), k), np.int32)
        dists = np.empty((len(q16), k), np.float32)
        for i, q in enumerate(q16):
            ids[i], dists[i] = reference.lockstep_search(db16, adj, q,
                                                         entry, L, W, k)
        stats = types.SimpleNamespace(
            results=[], total_rounds=0, idle_rounds=0, occupancy_trace=[],
            host_dispatches=0, pages_unique=0)
        return ids, dists, stats

    return stream_search
