"""The benchmark's own arithmetic: percentiles and rates over a window,
the bytes a request's results require, and the chip's published peaks.

Kept with the benchmark so that every change is measured by the same
arithmetic; nothing here imports the program.
"""
from __future__ import annotations

import math

#: published peaks of one chip, keyed by ``jax.Device.device_kind``
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bw": 819e9,
        "hbm_bytes": 16e9,
        "source": 'Google Cloud documentation, "TPU v5e" (per chip)'},
}

FLOAT32_BYTES = 4


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a chip that is not in ``PEAKS``
    raises, since a share of another chip's peak would be wrong."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None


def percentile(values, q: float) -> float:
    """The nearest-rank ``q``-th percentile of all ``values``: the
    smallest value with at least ``q`` percent of the sample at or below
    it.  Taken over the whole sample, never over chunks of it."""
    if not values:
        raise ValueError("percentile of an empty sample")
    if not 0 < q <= 100:
        raise ValueError(f"q must be in (0, 100], got {q}")
    ordered = sorted(values)
    rank = math.ceil(q / 100 * len(ordered))
    return ordered[max(rank, 1) - 1]


def rate(count: int, seconds: float) -> float:
    """Work over all the time of the window."""
    if seconds <= 0:
        raise ValueError(f"window of {seconds} s")
    return count / seconds


def plan_kind(spec) -> str:
    """The plan a request's results require: ``projective`` with a
    perspective divide or a cull, ``diag`` for translate/scale/affine
    only, else ``matrix``."""
    kinds = {p[0] for p in spec}
    if kinds & {"P", "C", "PERSP"}:
        return "projective"
    return "diag" if kinds <= {"T", "S", "A"} else "matrix"


def fold_words(kind: str, dim: int) -> int:
    """Parameter words one request of ``kind`` needs: ``(s, t)``,
    ``(A, t)`` or ``(H, lo, hi)``."""
    if kind == "diag":
        return 2 * dim
    if kind == "matrix":
        return dim * dim + dim
    return (dim + 1) ** 2 + 2 * dim


def payload_bytes(kind: str, dim: int, n_points: int) -> int:
    """HBM bytes one request's results require, whatever implements
    them: its points in and out as float32, one byte of cull mask per
    point of a projective request, and its fold words.  Padding to a
    bucket's length and the chip's lane tiling are not required by the
    results and are not counted."""
    points = 2 * n_points * dim * FLOAT32_BYTES
    mask = n_points if kind == "projective" else 0
    return points + mask + fold_words(kind, dim) * FLOAT32_BYTES
