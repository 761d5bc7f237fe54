"""Pallas TPU kernels for the paper's vector-vector / vector-scalar ops.

This is the direct TPU re-expression of sections 5.1-5.2: the context word
becomes the kernel body, the column broadcast becomes the grid, and the
double-banked frame buffer becomes the (automatically double-buffered)
HBM->VMEM block pipeline that `BlockSpec` index maps describe.

Three bodies cover the public ops:

  * ``_affine_kernel``  -- y = s (.) x + t with s, t broadcast row
    parameters staged once per column block (the "context word immediate"
    of Table 2, generalised from a scalar to a (1, bn) vector);
  * ``_vecadd_kernel``  -- y = x (+) z elementwise, both operands streamed
    through the double-buffered pipeline (Table 1's dbcdc);
  * ``_chain_diag_kernel`` -- the folded *diagonal* transform chain
    y[j] = s[j mod d] * x[j] + t[j mod d] over the flattened (N, d) point
    buffer.  The per-coordinate scale/shift pattern is tiled across the
    lane axis host-side, so an arbitrary translate/scale/affine chain is
    one lane-dense VPU pass: one HBM read of the points, one write, no
    per-point lane padding and no MXU involvement;
  * ``_chain_diag_batch_kernel`` -- the batched form used by the serving
    engine: each block row is a different request's flat point buffer and
    the parameter rows are row-aligned (request b meets its own folded
    (s, t)), so a whole plan bucket of heterogeneous requests is a single
    launch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.util import (LANES, SUBLANES, for_lane_chunks, pad2d,
                                pad_axis, pick_block, stage_flat, stage_packed)


def _affine_kernel(x_ref, s_ref, t_ref, o_ref):
    o_ref[...] = x_ref[...] * s_ref[...] + t_ref[...]


def _vecadd_kernel(x_ref, z_ref, o_ref):
    o_ref[...] = x_ref[...] + z_ref[...]


def _blocks(m: int, n: int) -> tuple[int, int]:
    return pick_block(m, 256, SUBLANES), pick_block(n, 512, LANES)


@functools.partial(jax.jit, static_argnames=("interpret",))
def affine_2d(x: jnp.ndarray, s: jnp.ndarray, t: jnp.ndarray,
              *, interpret: bool = False) -> jnp.ndarray:
    """y = s*x + t for x (M, N); s, t are (1, N) row parameters."""
    m, n = x.shape
    bm, bn = _blocks(m, n)
    xp = pad2d(x, bm, bn)
    sp = pad2d(s.reshape(1, n).astype(x.dtype), 1, bn)
    tp = pad2d(t.reshape(1, n).astype(x.dtype), 1, bn)
    mp, np_ = xp.shape
    out = pl.pallas_call(
        _affine_kernel,
        out_shape=jax.ShapeDtypeStruct((mp, np_), x.dtype),
        grid=(mp // bm, np_ // bn),
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),   # context-word params
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        interpret=interpret,
    )(xp, sp, tp)
    return out[:m, :n]


def _chain_diag_kernel(x_ref, s_ref, t_ref, o_ref):
    o_ref[...] = x_ref[...] * s_ref[...] + t_ref[...]


@functools.partial(jax.jit, static_argnames=("d", "interpret", "block_rows",
                                              "lane_target"))
def chain_diag_1d(flat: jnp.ndarray, s: jnp.ndarray, t: jnp.ndarray,
                  *, d: int, interpret: bool = False,
                  block_rows: int | None = None,
                  lane_target: int | None = None) -> jnp.ndarray:
    """Folded diagonal chain on the flat point buffer: y = s*x + t per coord.

    ``flat`` is an (N*d,) view of an (N, d) point array; ``s``/``t`` are
    (d,) per-coordinate parameters.  The buffer is reshaped to rows of
    ``w = chain_width(d)`` lanes (w a multiple of d, so points never
    straddle a block edge) and the d-periodic parameter pattern is tiled
    into (1, w) context-word rows staged once per block.
    ``block_rows``/``lane_target`` are the autotuner's launch parameters
    (``None`` = historical defaults); they steer staging only, never
    arithmetic, so every configuration is bit-identical.
    """
    (l,) = flat.shape
    if l == 0:
        return flat
    xp, lane_coord, bm, w = stage_flat(flat, d, block_rows=block_rows,
                                       lane_target=lane_target)
    srow = s.astype(flat.dtype)[lane_coord].reshape(1, w)
    trow = t.astype(flat.dtype)[lane_coord].reshape(1, w)
    out = pl.pallas_call(
        _chain_diag_kernel,
        out_shape=jax.ShapeDtypeStruct(xp.shape, flat.dtype),
        grid=(xp.shape[0] // bm,),
        in_specs=[
            pl.BlockSpec((bm, w), lambda i: (i, 0)),
            pl.BlockSpec((1, w), lambda i: (0, 0)),   # context-word params
            pl.BlockSpec((1, w), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, w), lambda i: (i, 0)),
        interpret=interpret,
    )(xp, srow, trow)
    return out.reshape(-1)[:l]


def _chain_diag_batch_kernel(x_ref, s_ref, t_ref, o_ref, *, g: int):
    def chunk(lanes):
        # a (bm, g) chunk of bm requests meets their row-aligned params
        o_ref[:, lanes] = x_ref[:, lanes] * s_ref[...] + t_ref[...]

    for_lane_chunks(x_ref.shape[1], g, chunk)


@functools.partial(jax.jit, static_argnames=("interpret", "block_rows"))
def chain_diag_batch_2d(pts3: jnp.ndarray, s: jnp.ndarray, t: jnp.ndarray,
                        *, interpret: bool = False,
                        block_rows: int | None = None) -> jnp.ndarray:
    """Batched folded diagonal chains: q[b] = s[b] (.) p[b] + t[b].

    ``pts3`` is a packed (B, L, d) batch (one serving request per row,
    padded to a common L); ``s``/``t`` are (B, d) per-request folded
    parameters.  Each batch row streams through the same one-pass VPU
    body as ``chain_diag_1d``, but the context-word parameter rows are
    *row-aligned* rather than broadcast: request b's block row meets
    request b's (g,)-tiled parameters, so B heterogeneous requests are
    one kernel launch.  ``block_rows`` pins the batch-axis block (the
    autotuner's knob; ``None`` = VMEM-budget heuristic).
    """
    b, l, d = pts3.shape
    if b == 0 or l == 0:
        return pts3
    xp, lane_coord, bm, g = stage_packed(pts3, d, block_rows=block_rows)
    srow = pad_axis(s.astype(pts3.dtype)[:, lane_coord], 0, bm)     # (Bp, g)
    trow = pad_axis(t.astype(pts3.dtype)[:, lane_coord], 0, bm)
    out = pl.pallas_call(
        functools.partial(_chain_diag_batch_kernel, g=g),
        out_shape=jax.ShapeDtypeStruct(xp.shape, pts3.dtype),
        grid=(xp.shape[0] // bm,),
        in_specs=[
            pl.BlockSpec((bm, xp.shape[1]), lambda i: (i, 0)),
            pl.BlockSpec((bm, g), lambda i: (i, 0)),  # row-aligned params
            pl.BlockSpec((bm, g), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bm, xp.shape[1]), lambda i: (i, 0)),
        interpret=interpret,
    )(xp, srow, trow)
    return out[:b, :l * d].reshape(b, l, d)


@functools.partial(jax.jit, static_argnames=("interpret",))
def vecadd_2d(x: jnp.ndarray, z: jnp.ndarray, *, interpret: bool = False) -> jnp.ndarray:
    """y = x + z elementwise for x, z (M, N) (Table 1 translation)."""
    m, n = x.shape
    bm, bn = _blocks(m, n)
    xp, zp = pad2d(x, bm, bn), pad2d(z.astype(x.dtype), bm, bn)
    mp, np_ = xp.shape
    out = pl.pallas_call(
        _vecadd_kernel,
        out_shape=jax.ShapeDtypeStruct((mp, np_), x.dtype),
        grid=(mp // bm, np_ // bn),
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        interpret=interpret,
    )(xp, zp)
    return out[:m, :n]
