"""The yardstick's peaks and the operation and byte counts that are
divided by them.

Peaks are keyed by the ``device_kind`` JAX reports.  A kind that is not
in the table is an error: a share of an unknown peak is no number.
"""
from __future__ import annotations

from typing import Dict

#: Google Cloud documentation, "TPU v5e" (system architecture page):
#: 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}
PEAK_SOURCE = "Google Cloud documentation, TPU v5e"


def peak(device_kind: str) -> Dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak for device kind {device_kind!r}; known: "
                       f"{sorted(PEAKS)}") from None


def gram_strip_counts(n: int, k: int, c: int) -> Dict[str, float]:
    """Operations and bytes the K-row Eq. 9 strip needs, without
    padding: read the N×C Δb buffer, the K refreshed rows and the
    [norm, Ĥ] stats of both, write the K×N clipped cosine.  FLOPs: the
    K×N×C dot products, two per multiply-add, and the divide by the
    norms' product (two per entry)."""
    f32 = 4
    nbytes = f32 * (n * c + k * c + 2 * n + 2 * k + k * n)
    flops = 2.0 * k * n * c + 2.0 * k * n
    return {"flops": flops, "bytes": float(nbytes)}


def roofline_seconds(counts: Dict[str, float],
                     pk: Dict[str, float]) -> Dict[str, float]:
    """The least time the chip needs for ``counts``, and which bound
    sets it."""
    t_flops = counts["flops"] / pk["bf16_flops"]
    t_bytes = counts["bytes"] / pk["hbm_bytes_per_s"]
    return {"seconds": max(t_flops, t_bytes),
            "bound": "compute" if t_flops >= t_bytes else "memory"}
