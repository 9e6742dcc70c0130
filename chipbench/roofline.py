"""Work of the algorithm and the chip's peaks, for roofline shares.

The peaks table (``peaks.json``) is keyed by ``device_kind``; a device that
is not in it is an error, never a default.  Work is counted from shapes,
for the algorithm and not for an implementation, so a kernel swapped for
another reads against the same work.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; have {sorted(table)}")
    return table[device_kind]


def matvec_work(n: int, m: int, k: int) -> tuple[float, float]:
    """(flops, bytes) of one WLSH table matvec K~ beta over n points, m
    instances and k right-hand sides: read each (instance, point) slot
    (int32) and coefficient (f32) once, read beta and write the result once;
    a multiply-add to scatter and another to gather per (instance, point,
    column).  The (m, B) table is not counted: a fused matvec never writes
    it, and padding is not work."""
    return 4.0 * m * n * k, 8.0 * m * n + 2.0 * 4.0 * n * k


def featurize_flops(n: int, m: int, d: int) -> float:
    """Flops of hashing n points under m instances in R^d: a subtract,
    divide, round and bucket weight, a weight product and two hash
    multiply-adds per (instance, point, coordinate)."""
    return 8.0 * m * n * d


def least_seconds(flops: float, nbytes: float, pk: dict) -> float:
    """The larger of compute time at the bf16 peak and HBM time at peak
    bandwidth."""
    return max(flops / pk["bf16_flops_per_s"], nbytes / pk["hbm_bytes_per_s"])
