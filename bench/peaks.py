"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` that JAX reports.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.

There is no published float32 peak. The engine's distances are float32
matrix products at ``Precision.HIGHEST``, which the MXU runs as several
bf16 passes, so a share taken against the bf16 peak reads low wherever
compute is the bound. The distance and merge work of a round is far
below the compute bound, so their rooflines are set by bandwidth.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud TPU v5e documentation",
    },
}


def peaks_for(device_kind: str) -> dict:
    """The peak table of one chip; an unknown chip is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
