"""Pallas TPU tiled matmul -- the paper's section-5.3 matrix mapping.

The MorphoSys mapping streams rows of A through the context plane while rows
of B are broadcast to the array, accumulating in each cell's output register.
The MXU analogue: A and B tiles stream HBM->VMEM along the contraction grid
axis ("arbitrary" semantics = sequential, revisiting the same output block),
accumulating into an fp32 VMEM scratch -- the cell output register writ
large.  Block shapes default to MXU-native (128, 128) output tiles with a
512-deep K panel; working set 2*(bm*bk + bk*bn) + bm*bn*4 bytes stays well
under VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.util import (LANES, SUBLANES, for_lane_chunks, lane_shift,
                                pad_axis, pick_block, stage_flat,
                                stage_packed)


def _matmul_kernel(x_ref, y_ref, o_ref, acc_ref, *, nk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], y_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == nk - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret", "out_dtype"))
def matmul_2d(x: jnp.ndarray, y: jnp.ndarray, *, bm: int = 128, bn: int = 128,
              bk: int = 512, interpret: bool = False,
              out_dtype=None) -> jnp.ndarray:
    """C = X @ Y for X (M, K), Y (K, N); fp32 accumulation."""
    m, k = x.shape
    k2, n = y.shape
    assert k == k2, (x.shape, y.shape)
    out_dtype = out_dtype or x.dtype
    bm = pick_block(m, bm, SUBLANES)
    bn = pick_block(n, bn, LANES)
    bk = pick_block(k, bk, LANES)
    xp = pad_axis(pad_axis(x, 0, bm), 1, bk)
    yp = pad_axis(pad_axis(y, 0, bk), 1, bn)
    mp, kp = xp.shape
    np_ = yp.shape[1]
    nk = kp // bk
    out = pl.pallas_call(
        functools.partial(_matmul_kernel, nk=nk),
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        grid=(mp // bm, np_ // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xp, yp)
    return out[:m, :n]


# -- fused transform-chain kernel (the paper's one-pass composite) -----------
#
# A folded chain q = p @ A + t over (N, d) points with d in {2, 3} would
# waste 128/d of the lane bandwidth if lowered through the tiled matmul
# (the trailing dim pads 2 -> 128).  Instead the point buffer is kept
# flat and lane-dense: flat index j = point*d + coord, and
#
#   out[j] = sum_m x[point*d + m] * A[m, c] + t[c],   c = j mod d,
#
# becomes 2d-1 lane-rolled multiply-adds against precomputed d-periodic
# coefficient rows C_delta[j] = A[c+delta, c] (zero where c+delta falls
# outside [0, d)).  Rolls never mix points because chain_width(d) is a
# multiple of d, and wrapped lanes always carry a zero coefficient.  One
# HBM read of the points, one write, pure VPU work.

def _chain_matrix_kernel(x_ref, c_ref, t_ref, o_ref, *, d: int):
    x = x_ref[...]
    c = c_ref[...]
    acc = jnp.zeros_like(x) + t_ref[...]
    for i, delta in enumerate(range(-(d - 1), d)):
        acc = acc + lane_shift(x, delta) * c[i:i + 1, :]
    o_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("d", "interpret", "block_rows",
                                              "lane_target"))
def chain_matrix_1d(flat: jnp.ndarray, a: jnp.ndarray, t: jnp.ndarray,
                    *, d: int, interpret: bool = False,
                    block_rows: int | None = None,
                    lane_target: int | None = None) -> jnp.ndarray:
    """Fused q = p @ A + t on the flat (N*d,) point buffer; A (d, d), t (d,).

    ``block_rows``/``lane_target`` are the autotuner's launch parameters
    (``None`` = historical defaults).  They steer staging only; the 2d-1
    rolled-MAC schedule per lane is identical under any staging, so every
    configuration produces bit-identical results."""
    (l,) = flat.shape
    if l == 0:
        return flat
    xp, lane_coord, bm, w = stage_flat(flat, d, block_rows=block_rows,
                                       lane_target=lane_target)
    coef = pad_axis(_coef_rows(a.astype(flat.dtype), lane_coord, d),
                    0, SUBLANES)                            # (8, w)
    trow = t.astype(flat.dtype)[lane_coord].reshape(1, w)
    out = pl.pallas_call(
        functools.partial(_chain_matrix_kernel, d=d),
        out_shape=jax.ShapeDtypeStruct(xp.shape, flat.dtype),
        grid=(xp.shape[0] // bm,),
        in_specs=[
            pl.BlockSpec((bm, w), lambda i: (i, 0)),
            pl.BlockSpec((SUBLANES, w), lambda i: (0, 0)),  # coefficient rows
            pl.BlockSpec((1, w), lambda i: (0, 0)),         # translation row
        ],
        out_specs=pl.BlockSpec((bm, w), lambda i: (i, 0)),
        interpret=interpret,
    )(xp, coef, trow)
    return out.reshape(-1)[:l]


def _coef_rows(a: jnp.ndarray, lane_coord: jnp.ndarray, d: int) -> jnp.ndarray:
    """The 2d-1 d-periodic coefficient patterns C_delta[j] = A[c+delta, c]
    for one composed matrix ``a`` (zero where c+delta falls outside [0, d));
    returns (2d-1, g) with g = len(lane_coord).  Shared by the single-chain
    and batched (vmapped) lowerings so the MAC schedule cannot diverge."""
    rows = []
    for delta in range(-(d - 1), d):
        src = lane_coord + delta
        valid = (src >= 0) & (src < d)
        rows.append(jnp.where(valid, a[jnp.clip(src, 0, d - 1), lane_coord],
                              jnp.zeros((), a.dtype)))
    return jnp.stack(rows)


def _chain_matrix_batch_kernel(x_ref, c_ref, t_ref, o_ref, *, d: int, g: int):
    def chunk(lanes):
        x = x_ref[:, lanes]                          # (bm, g) of bm requests
        acc = jnp.zeros_like(x) + t_ref[...]
        for i, delta in enumerate(range(-(d - 1), d)):
            acc = acc + lane_shift(x, delta) * c_ref[:, i * g:(i + 1) * g]
        o_ref[:, lanes] = acc

    for_lane_chunks(x_ref.shape[1], g, chunk)


@functools.partial(jax.jit, static_argnames=("interpret", "block_rows"))
def chain_matrix_batch_2d(pts3: jnp.ndarray, a: jnp.ndarray, t: jnp.ndarray,
                          *, interpret: bool = False,
                          block_rows: int | None = None) -> jnp.ndarray:
    """Batched folded general chains: q[b] = p[b] @ A[b] + t[b].

    ``pts3`` is a packed (B, L, d) batch (one serving request per row,
    padded to a common L); ``a`` (B, d, d) / ``t`` (B, d) are per-request
    folded parameters.  Same 2d-1 lane-rolled MAC schedule as
    ``chain_matrix_1d`` -- rolls stay inside one g-lane chunk of a block
    row, so they never mix requests, and wrapped lanes always meet a zero
    coefficient -- but the coefficient rows are *row-aligned* (request b's
    block row meets request b's coefficients), making a whole plan bucket
    one launch.
    ``block_rows`` pins the batch-axis block (the autotuner's knob;
    ``None`` = VMEM-budget heuristic).
    """
    b, l, d = pts3.shape
    if b == 0 or l == 0:
        return pts3
    xp, lane_coord, bm, g = stage_packed(pts3, d, block_rows=block_rows)
    coef = jax.vmap(lambda ab: _coef_rows(ab, lane_coord, d))(
        a.astype(pts3.dtype))                        # (B, 2d-1, g)
    coef = pad_axis(coef.reshape(b, (2 * d - 1) * g), 0, bm)
    trow = pad_axis(t.astype(pts3.dtype)[:, lane_coord], 0, bm)
    out = pl.pallas_call(
        functools.partial(_chain_matrix_batch_kernel, d=d, g=g),
        out_shape=jax.ShapeDtypeStruct(xp.shape, pts3.dtype),
        grid=(xp.shape[0] // bm,),
        in_specs=[
            pl.BlockSpec((bm, xp.shape[1]), lambda i: (i, 0)),
            pl.BlockSpec((bm, (2 * d - 1) * g), lambda i: (i, 0)),
            pl.BlockSpec((bm, g), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bm, xp.shape[1]), lambda i: (i, 0)),
        interpret=interpret,
    )(xp, coef, trow)
    return out[:b, :l * d].reshape(b, l, d)
