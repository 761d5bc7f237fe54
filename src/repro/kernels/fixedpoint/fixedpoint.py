"""Pallas TPU kernels for fused fixed-point (Qm.n) transform chains.

The M1's RC array executes the paper's transforms on 16-bit integer ALUs;
this module is that datapath on the TPU mapping.  The kernels mirror the
float chain kernels lane for lane -- ``chain_diag_1d_q`` is
``chain_diag_1d`` and ``chain_matrix_1d_q`` is ``chain_matrix_1d`` with
the same staging (``stage_flat``/``stage_packed``), the same d-periodic
context-word parameter rows, and the same 2d-1 lane-rolled MAC schedule
(``_coef_rows`` is literally shared) -- but the arithmetic is the M1's:

  * the point buffer lives in HBM as int16 Qm.n words -- HALF the bytes
    per point of the float32 lane, which is the whole perf case;
  * multiply-accumulate runs in int32 (products carry scale 2**2n; the
    translation row is aligned up by ``<< n``), exact and
    order-independent, so every backend is bit-identical;
  * ONE requantising shift ``(acc + 2**(n-1)) >> n`` brings the result
    back to Qm.n, and the store wraps to int16 -- wrap-around, never
    saturation, exactly like ``core.morphosys.rc_array`` (at n = 0 the
    shift vanishes and the lane IS the emulator's integer datapath).

``block_rows``/``lane_target`` are the autotuner's launch parameters, as
on the float kernels: staging-only, never arithmetic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.matmul.matmul import _coef_rows
from repro.kernels.util import (SUBLANES, for_lane_chunks, lane_shift,
                                pad_axis, stage_flat, stage_packed)


def _requant_store(acc, n_frac: int):
    """The single requantising shift + int16 wrap (see module docstring)."""
    if n_frac:
        acc = (acc + jnp.int32(1 << (n_frac - 1))) >> n_frac
    return acc.astype(jnp.int16)


def _chain_diag_q_kernel(x_ref, s_ref, t_ref, o_ref, *, n_frac: int):
    x = x_ref[...].astype(jnp.int32)
    s = s_ref[...].astype(jnp.int32)
    t = t_ref[...].astype(jnp.int32) << n_frac
    o_ref[...] = _requant_store(x * s + t, n_frac)


@functools.partial(jax.jit, static_argnames=("d", "n_frac", "interpret",
                                             "block_rows", "lane_target"))
def chain_diag_1d_q(flat: jnp.ndarray, s: jnp.ndarray, t: jnp.ndarray,
                    *, d: int, n_frac: int, interpret: bool = False,
                    block_rows: int | None = None,
                    lane_target: int | None = None) -> jnp.ndarray:
    """Folded diagonal chain on the flat int16 Qm.n point buffer.

    ``flat`` is an (N*d,) int16 view of (N, d) points; ``s``/``t`` are
    (d,) int16 Qm.n words.  Same staging as ``chain_diag_1d`` (rows of
    ``chain_width(d)`` lanes, d-periodic parameter rows staged once per
    block); int32 MAC + one shift per lane.  One HBM read of the points,
    one write -- at HALF the float32 byte volume."""
    (l,) = flat.shape
    if l == 0:
        return flat
    xp, lane_coord, bm, w = stage_flat(flat, d, block_rows=block_rows,
                                       lane_target=lane_target)
    srow = s.astype(jnp.int16)[lane_coord].reshape(1, w)
    trow = t.astype(jnp.int16)[lane_coord].reshape(1, w)
    out = pl.pallas_call(
        functools.partial(_chain_diag_q_kernel, n_frac=n_frac),
        out_shape=jax.ShapeDtypeStruct(xp.shape, jnp.int16),
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


def _chain_matrix_q_kernel(x_ref, c_ref, t_ref, o_ref, *, d: int,
                           n_frac: int):
    x = x_ref[...].astype(jnp.int32)
    c = c_ref[...].astype(jnp.int32)
    acc = jnp.zeros_like(x) + (t_ref[...].astype(jnp.int32) << n_frac)
    for i, delta in enumerate(range(-(d - 1), d)):
        acc = acc + lane_shift(x, delta) * c[i:i + 1, :]
    o_ref[...] = _requant_store(acc, n_frac)


@functools.partial(jax.jit, static_argnames=("d", "n_frac", "interpret",
                                             "block_rows", "lane_target"))
def chain_matrix_1d_q(flat: jnp.ndarray, a: jnp.ndarray, t: jnp.ndarray,
                      *, d: int, n_frac: int, interpret: bool = False,
                      block_rows: int | None = None,
                      lane_target: int | None = None) -> jnp.ndarray:
    """Fused q = requant(p @ A + t) on the flat int16 buffer; A (d, d),
    t (d,) int16 Qm.n words.  The 2d-1 rolled-MAC schedule is the float
    kernel's (``_coef_rows`` shared), so the two lanes cannot diverge in
    anything but arithmetic width."""
    (l,) = flat.shape
    if l == 0:
        return flat
    xp, lane_coord, bm, w = stage_flat(flat, d, block_rows=block_rows,
                                       lane_target=lane_target)
    coef = pad_axis(_coef_rows(a.astype(jnp.int16), lane_coord, d),
                    0, SUBLANES)                            # (8, w)
    trow = t.astype(jnp.int16)[lane_coord].reshape(1, w)
    out = pl.pallas_call(
        functools.partial(_chain_matrix_q_kernel, d=d, n_frac=n_frac),
        out_shape=jax.ShapeDtypeStruct(xp.shape, jnp.int16),
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


def _chain_diag_batch_q_kernel(x_ref, s_ref, t_ref, o_ref, *, g: int,
                               n_frac: int):
    def chunk(lanes):
        # a (bm, g) chunk of bm requests meets their row-aligned params
        x = x_ref[:, lanes].astype(jnp.int32)
        s = s_ref[...].astype(jnp.int32)
        t = t_ref[...].astype(jnp.int32) << n_frac
        o_ref[:, lanes] = _requant_store(x * s + t, n_frac)

    for_lane_chunks(x_ref.shape[1], g, chunk)


@functools.partial(jax.jit, static_argnames=("n_frac", "interpret",
                                             "block_rows"))
def chain_diag_batch_2d_q(pts3: jnp.ndarray, s: jnp.ndarray, t: jnp.ndarray,
                          *, n_frac: int, interpret: bool = False,
                          block_rows: int | None = None) -> jnp.ndarray:
    """Batched folded diagonal chains on a packed int16 (B, L, d) batch;
    ``s``/``t`` are (B, d) per-request Qm.n words, row-aligned with the
    batch exactly like ``chain_diag_batch_2d``."""
    b, l, d = pts3.shape
    if b == 0 or l == 0:
        return pts3
    xp, lane_coord, bm, g = stage_packed(pts3, d, block_rows=block_rows)
    srow = pad_axis(s.astype(jnp.int16)[:, lane_coord], 0, bm)      # (Bp, g)
    trow = pad_axis(t.astype(jnp.int16)[:, lane_coord], 0, bm)
    out = pl.pallas_call(
        functools.partial(_chain_diag_batch_q_kernel, g=g, n_frac=n_frac),
        out_shape=jax.ShapeDtypeStruct(xp.shape, jnp.int16),
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


def _chain_matrix_batch_q_kernel(x_ref, c_ref, t_ref, o_ref, *, d: int,
                                 g: int, n_frac: int):
    def chunk(lanes):
        x = x_ref[:, lanes].astype(jnp.int32)        # (bm, g) of bm requests
        acc = jnp.zeros_like(x) + (t_ref[...].astype(jnp.int32) << n_frac)
        for i, delta in enumerate(range(-(d - 1), d)):
            c = c_ref[:, i * g:(i + 1) * g].astype(jnp.int32)
            acc = acc + lane_shift(x, delta) * c
        o_ref[:, lanes] = _requant_store(acc, n_frac)

    for_lane_chunks(x_ref.shape[1], g, chunk)


@functools.partial(jax.jit, static_argnames=("n_frac", "interpret",
                                             "block_rows"))
def chain_matrix_batch_2d_q(pts3: jnp.ndarray, a: jnp.ndarray,
                            t: jnp.ndarray, *, n_frac: int,
                            interpret: bool = False,
                            block_rows: int | None = None) -> jnp.ndarray:
    """Batched folded general chains on a packed int16 (B, L, d) batch;
    ``a`` (B, d, d) / ``t`` (B, d) are per-request Qm.n words.  Same
    row-aligned 2d-1 rolled-MAC schedule as ``chain_matrix_batch_2d``
    (rolls never mix requests; wrapped lanes meet zero coefficients)."""
    b, l, d = pts3.shape
    if b == 0 or l == 0:
        return pts3
    xp, lane_coord, bm, g = stage_packed(pts3, d, block_rows=block_rows)
    coef = jax.vmap(lambda ab: _coef_rows(ab, lane_coord, d))(
        a.astype(jnp.int16))                         # (B, 2d-1, g)
    coef = pad_axis(coef.reshape(b, (2 * d - 1) * g), 0, bm)
    trow = pad_axis(t.astype(jnp.int16)[:, lane_coord], 0, bm)
    out = pl.pallas_call(
        functools.partial(_chain_matrix_batch_q_kernel, d=d, g=g,
                          n_frac=n_frac),
        out_shape=jax.ShapeDtypeStruct(xp.shape, jnp.int16),
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
