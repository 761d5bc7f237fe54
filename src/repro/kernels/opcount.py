"""HBM-traffic accounting for the eager kernel-dispatch path.

The paper's central perf argument is byte economy: a composite transform
that runs as one pass over the RC array moves (k+1)x fewer frame-buffer
bytes than k sequential primitive passes.  This module makes the same
accounting observable on the TPU mapping: every public op entry records
``(op_name, bytes_moved)`` -- bytes read from plus written to HBM under
the memory-bound model (inputs + outputs, parameters included) -- while a
``counting()`` scope is active.

Records fire when the op *entry* executes, i.e. on every call on the
eager path but only once (at trace time) under ``jax.jit``.  That is the
intended use: tests compare eager sequential dispatch
against the fused chain path, whose single record is emitted by
``TransformChain.apply`` outside the jitted plan.
"""
from __future__ import annotations

import contextlib

_ACTIVE: list[tuple[str, int]] | None = None


@contextlib.contextmanager
def counting():
    """Collect ``(op, nbytes)`` records emitted inside the scope."""
    global _ACTIVE
    prev, records = _ACTIVE, []
    _ACTIVE = records
    try:
        yield records
    finally:
        _ACTIVE = prev


def record(op: str, nbytes: int) -> None:
    if _ACTIVE is not None:
        _ACTIVE.append((op, int(nbytes)))


def total_bytes(records: list[tuple[str, int]]) -> int:
    return sum(b for _, b in records)


def chain_param_words(d: int, kind: str) -> int:
    """Composed-parameter words of one folded chain, by plan kind: (s, t)
    for diag, (A, t) for matrix, (H, lo, hi) for projective.  The ONE
    table -- ``TransformChain``'s byte records, the serving engine's
    packed accounting, and the autotune cost model all read it here, so
    the three cannot drift."""
    return {"diag": 2 * d, "matrix": d * d + d,
            "projective": (d + 1) ** 2 + 2 * d}[kind]


def chain_passes(kind: str) -> int:
    """HBM passes of one fused chain launch: read + write, plus the
    point-buffer-width cull-mask write for projective plans."""
    return 3 if kind == "projective" else 2


def fused_chain_bytes(n_points: int, d: int, *, itemsize: int = 4,
                      kind: str = "matrix") -> int:
    """HBM bytes moved by ONE fused single-chain launch over (N, d)
    points (memory-bound model): the point buffer once in and once out
    (plus the mask pass for projective plans) and the composed-parameter
    words, at ``itemsize`` bytes per word -- 4 on the float32 lane, 2 on
    the int16 fixed-point lane (the lane's whole perf case: the same
    chain moves half the bytes).  The ONE formula shared by
    ``TransformChain``'s records, the autotune cost model, and the
    fixed-point lane's f32-vs-q comparison (``tests/test_fixedpoint.py``)."""
    return (chain_passes(kind) * n_points * d * itemsize
            + chain_param_words(d, kind) * itemsize)


def packed_chain_bytes(bsz: int, lpad: int, d: int, *, itemsize: int = 4,
                       kind: str = "matrix") -> int:
    """HBM bytes moved by one packed-batch chain launch (memory-bound model).

    A bucket of ``bsz`` requests packed to ``lpad`` points each moves the
    padded point buffer once in and once out (2*B*L*d*itemsize) plus the
    per-request folded parameters -- (d, d) + (d,) words for a ``matrix``
    plan, (d,) + (d,) for a ``diag`` plan, and (d+1)^2 homogeneous words
    plus the 2d cull bounds for a ``projective`` plan (which also writes
    a third, mask-sized pass: the in-kernel frustum-cull mask leaves at
    point-buffer width).  Per-request dispatch of the same bucket moves
    2*sum(n_i)*d*itemsize payload bytes but pays one launch per request;
    the packed launch trades (lpad - n_i) rows of padding per request for
    a Bx launch reduction.  The serving engine records this number per
    launch, so tests can assert both sides of that trade (waste cap,
    launch economy).
    """
    return (chain_passes(kind) * bsz * lpad * d * itemsize
            + bsz * chain_param_words(d, kind) * itemsize)
