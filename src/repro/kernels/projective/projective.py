"""Pallas TPU kernels for fused projective (homogeneous) transform chains.

The graphics companion paper maps full 2D/3D viewing pipelines -- model
affines, camera, perspective/orthographic projection, cull, viewport --
onto the same RC array as the source paper's affine primitives.  Here the
whole folded pipeline is ONE lane-dense kernel over the flattened point
buffer, extending the ``chain_matrix_1d`` discipline with a second rolled
MAC set and an in-kernel divide:

  * the linear block H[:d, :d] applies as the usual 2d-1 lane-rolled
    multiply-adds against d-periodic coefficient rows;
  * the perspective column H[:d, d] applies as a SECOND set of 2d-1 rolled
    MACs producing each point's homogeneous w on every one of its lanes;
  * the divide q = acc / w happens in-register (w <= 0 divides by 1 and is
    masked out), followed by the axis-aligned cull test against per-lane
    lo/hi bounds rows;
  * the per-lane inlier bits are AND-reduced across each point's d lanes
    with the same roll trick (wrapped or cross-point lanes contribute a
    neutral 1), so the emitted mask is constant over a point's lanes.

One HBM read of the points, one write of the projected points, one write
of the mask -- no homogeneous-coordinate materialisation, no padding of
the d-wide trailing axis to 128 lanes, and still pure VPU work.  The
batched forms are row-aligned like ``chain_matrix_batch_2d``: request b's
block row meets request b's folded (H, lo, hi), so a whole serving bucket
of heterogeneous projective requests is a single launch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.util import (RESIDENT_TILE_ROWS, SUBLANES, for_lane_chunks,
                                lane_group, lane_shift, pad_axis, stage_flat,
                                stage_packed)


def _proj_rows(h: jnp.ndarray, lane_coord: jnp.ndarray, d: int):
    """The rolled-MAC coefficient patterns for one homogeneous ``h``:
    linear rows C_delta[j] = H[c+delta, c], perspective rows
    W_delta[j] = H[c+delta, d], and the 0/1 same-point validity rows
    G_delta[j] = [0 <= c+delta < d] (shared by the single-chain and
    batched lowerings so the MAC and mask schedules cannot diverge).
    Returns three (2d-1, g) stacks with g = len(lane_coord)."""
    rows, wrows, grows = [], [], []
    for delta in range(-(d - 1), d):
        src = lane_coord + delta
        valid = (src >= 0) & (src < d)
        srcc = jnp.clip(src, 0, d - 1)
        zero = jnp.zeros((), h.dtype)
        rows.append(jnp.where(valid, h[srcc, lane_coord], zero))
        wrows.append(jnp.where(valid, h[srcc, d], zero))
        grows.append(valid.astype(h.dtype))
    return jnp.stack(rows), jnp.stack(wrows), jnp.stack(grows)


def _chain_project_kernel(x_ref, c_ref, wc_ref, g_ref, p_ref, o_ref, m_ref,
                          *, d: int):
    x = x_ref[...]
    p = p_ref[...]                   # rows: t, w-translation, lo, hi
    acc = jnp.zeros_like(x) + p[0:1, :]
    wacc = jnp.zeros_like(x) + p[1:2, :]
    for i, delta in enumerate(range(-(d - 1), d)):
        xr = lane_shift(x, delta)
        acc = acc + xr * c_ref[i:i + 1, :]
        wacc = wacc + xr * wc_ref[i:i + 1, :]
    w_ok = wacc > 0.0
    v = acc / jnp.where(w_ok, wacc, jnp.ones_like(wacc))
    inl = jnp.where(w_ok & (v >= p[2:3, :]) & (v <= p[3:4, :]),
                    jnp.ones_like(x), jnp.zeros_like(x))
    mask = jnp.ones_like(x)
    for i, delta in enumerate(range(-(d - 1), d)):
        g = g_ref[i:i + 1, :]
        mask = mask * (lane_shift(inl, delta) * g + (1.0 - g))
    o_ref[...] = v
    m_ref[...] = mask


@functools.partial(jax.jit, static_argnames=("d", "interpret", "block_rows",
                                             "lane_target"))
def chain_project_1d(flat: jnp.ndarray, h: jnp.ndarray, lo: jnp.ndarray,
                     hi: jnp.ndarray, *, d: int, interpret: bool = False,
                     block_rows: int | None = None,
                     lane_target: int | None = None):
    """Fused projective chain on the flat (N*d,) point buffer.

    ``h`` is the folded (d+1, d+1) homogeneous matrix (row-vector
    convention), ``lo``/``hi`` the (d,) cull bounds.  Returns the projected
    flat buffer and a flat per-lane mask (constant across each point's d
    lanes; 1.0 = inside).  ``block_rows``/``lane_target`` are the
    autotuner's launch parameters (``None`` = historical defaults); they
    steer staging only -- the MAC/divide schedule per lane is identical
    under any staging, so every configuration is bit-identical."""
    (l,) = flat.shape
    if l == 0:
        return flat, flat
    xp, lane_coord, bm, w = stage_flat(flat, d, block_rows=block_rows,
                                       lane_target=lane_target)
    hc = h.astype(flat.dtype)
    coef, wcoef, gmask = _proj_rows(hc, lane_coord, d)
    prow = jnp.stack([hc[d, :d][lane_coord],
                      jnp.broadcast_to(hc[d, d], (w,)),
                      lo.astype(flat.dtype)[lane_coord],
                      hi.astype(flat.dtype)[lane_coord]])
    out, mask = pl.pallas_call(
        functools.partial(_chain_project_kernel, d=d),
        out_shape=[jax.ShapeDtypeStruct(xp.shape, flat.dtype)] * 2,
        grid=(xp.shape[0] // bm,),
        in_specs=[
            pl.BlockSpec((bm, w), lambda i: (i, 0)),
            pl.BlockSpec((SUBLANES, w), lambda i: (0, 0)),  # linear rows
            pl.BlockSpec((SUBLANES, w), lambda i: (0, 0)),  # perspective rows
            pl.BlockSpec((SUBLANES, w), lambda i: (0, 0)),  # same-point rows
            pl.BlockSpec((SUBLANES, w), lambda i: (0, 0)),  # t/wt/lo/hi rows
        ],
        out_specs=[pl.BlockSpec((bm, w), lambda i: (i, 0))] * 2,
        interpret=interpret,
    )(xp, pad_axis(coef, 0, SUBLANES), pad_axis(wcoef, 0, SUBLANES),
      pad_axis(gmask, 0, SUBLANES), pad_axis(prow, 0, SUBLANES))
    return out.reshape(-1)[:l], mask.reshape(-1)[:l]


def _project_chunk(x, c_ref, wc_ref, g_ref, p_ref, *, d: int, g: int):
    """The batch kernels' schedule over one (r, g) chunk of points:
    2d-1 rolled MACs for the numerator and for w, the guarded divide,
    the cull test and the AND-reduced mask.  Each parameter ref holds
    g-lane rows (linear, perspective, same-point; p_ref: t, wt, lo,
    hi), either one row per chunk row or one row for the whole chunk,
    broadcast; the arithmetic of every lane is the same either way."""
    acc = jnp.zeros_like(x) + p_ref[:, 0:g]
    wacc = jnp.zeros_like(x) + p_ref[:, g:2 * g]
    for i, delta in enumerate(range(-(d - 1), d)):
        xr = lane_shift(x, delta)
        acc = acc + xr * c_ref[:, i * g:(i + 1) * g]
        wacc = wacc + xr * wc_ref[:, i * g:(i + 1) * g]
    w_ok = wacc > 0.0
    v = acc / jnp.where(w_ok, wacc, jnp.ones_like(wacc))
    inl = jnp.where(w_ok & (v >= p_ref[:, 2 * g:3 * g])
                    & (v <= p_ref[:, 3 * g:4 * g]),
                    jnp.ones_like(v), jnp.zeros_like(v))
    mask = jnp.ones_like(inl)
    for i, delta in enumerate(range(-(d - 1), d)):
        gm = g_ref[:, i * g:(i + 1) * g]
        mask = mask * (lane_shift(inl, delta) * gm + (1.0 - gm))
    return v, mask


def _chain_project_batch_kernel(x_ref, c_ref, wc_ref, g_ref, p_ref, o_ref,
                                m_ref, *, d: int, g: int):
    def chunk(lanes):                   # (bm, g): one chunk of bm requests
        v, mask = _project_chunk(x_ref[:, lanes], c_ref, wc_ref, g_ref,
                                 p_ref, d=d, g=g)
        o_ref[:, lanes] = v
        m_ref[:, lanes] = mask

    for_lane_chunks(x_ref.shape[1], g, chunk)


def _chain_project_instanced_kernel(x_ref, c_ref, wc_ref, g_ref, p_ref, o_ref,
                                    m_ref, *, d: int, g: int):
    def chunk(rows):                    # (8, g): 8 rows of the shared mesh
        v, mask = _project_chunk(x_ref[rows, :], c_ref, wc_ref, g_ref,
                                 p_ref, d=d, g=g)
        o_ref[rows, :] = v
        m_ref[rows, :] = mask

    for_lane_chunks(x_ref.shape[0], SUBLANES, chunk)


def _proj_batch_rows(h, lo, hi, lane_coord, d: int, g: int):
    """Each request's g-lane parameter rows, row-aligned: linear and
    perspective coefficients ((B, (2d-1)g) each), the same-point rows
    (one row, equal for every request) and t, wt, lo, hi ((B, 4g))."""
    coef, wcoef, gmask = jax.vmap(
        lambda hb: _proj_rows(hb, lane_coord, d))(h)   # (B, 2d-1, g) each
    b = h.shape[0]
    prow = jnp.concatenate([
        h[:, d, :d][:, lane_coord],
        jnp.broadcast_to(h[:, d, d][:, None], (b, g)),
        lo.astype(h.dtype)[:, lane_coord],
        hi.astype(h.dtype)[:, lane_coord]], axis=1)
    return (coef.reshape(b, (2 * d - 1) * g),
            wcoef.reshape(b, (2 * d - 1) * g),
            gmask[:1].reshape(1, (2 * d - 1) * g), prow)


@functools.partial(jax.jit, static_argnames=("interpret", "block_rows"))
def chain_project_batch_2d(pts3: jnp.ndarray, h: jnp.ndarray,
                           lo: jnp.ndarray, hi: jnp.ndarray, *,
                           interpret: bool = False,
                           block_rows: int | None = None):
    """Batched folded projective chains: one launch for a whole bucket.

    ``pts3`` is a packed (B, L, d) batch (one serving request per row,
    padded to a common L); ``h`` (B, d+1, d+1) / ``lo``/``hi`` (B, d) are
    per-request folded parameters.  Same rolled MAC + divide + mask
    schedule as ``chain_project_1d`` -- rolls stay inside one g-lane chunk
    of a block row, so they never mix requests -- but every
    coefficient/bounds row is *row-aligned* (request b's block row meets
    request b's parameters).
    Returns the projected (B, L, d) batch and a (B, L) float mask.
    ``block_rows`` pins the batch-axis block (``None`` = VMEM heuristic).
    """
    b, l, d = pts3.shape
    if b == 0 or l == 0:
        return pts3, jnp.zeros((b, l), pts3.dtype)
    xp, lane_coord, bm, g = stage_packed(pts3, d, block_rows=block_rows)
    coef, wcoef, grow, prow = _proj_batch_rows(h.astype(pts3.dtype), lo, hi,
                                               lane_coord, d, g)
    coef, wcoef, prow = (pad_axis(a, 0, bm) for a in (coef, wcoef, prow))
    out, mask = pl.pallas_call(
        functools.partial(_chain_project_batch_kernel, d=d, g=g),
        out_shape=[jax.ShapeDtypeStruct(xp.shape, pts3.dtype)] * 2,
        grid=(xp.shape[0] // bm,),
        in_specs=[
            pl.BlockSpec((bm, xp.shape[1]), lambda i: (i, 0)),
            pl.BlockSpec((bm, (2 * d - 1) * g), lambda i: (i, 0)),
            pl.BlockSpec((bm, (2 * d - 1) * g), lambda i: (i, 0)),
            pl.BlockSpec((1, (2 * d - 1) * g), lambda i: (0, 0)),
            pl.BlockSpec((bm, 4 * g), lambda i: (i, 0)),
        ],
        out_specs=[pl.BlockSpec((bm, xp.shape[1]), lambda i: (i, 0))] * 2,
        interpret=interpret,
    )(xp, coef, wcoef, grow, prow)
    out = out[:b, :l * d].reshape(b, l, d)
    mask = mask[:b, :l * d].reshape(b, l, d)[:, :, 0]
    return out, mask


@functools.partial(jax.jit, static_argnames=("interpret",))
def chain_project_instanced_2d(x: jnp.ndarray, h: jnp.ndarray,
                               lo: jnp.ndarray, hi: jnp.ndarray, *,
                               interpret: bool = False):
    """Instanced folded projective chains: one shared point buffer under
    B folded chains, one launch.

    ``x`` is a resident point buffer already in the kernel's layout:
    ``(rows, g)`` with ``g = lane_group(d)`` and ``rows`` from
    ``util.resident_rows``, the flat ``(N*d,)`` points zero-padded; ``h``
    (B, d+1, d+1) / ``lo``/``hi`` (B, d) are each instance's folded
    parameters.  The schedule is the batch kernel's (``_project_chunk``)
    over chunks of 8 rows of the buffer, each row 128 whole points, with
    instance b's parameter rows broadcast over the chunk.  The grid runs
    tiles of rows outermost and instances innermost; the buffer's block
    index ignores the instance, so each tile is read once for all B
    instances, and a block stays one tile however long the mesh is.
    Returns the projected points and a float mask, both (B, rows, g) in
    the buffer's layout (the mask constant over each point's d lanes):
    instance b's point i sits at flat words ``[i*d, (i+1)*d)`` of row b.
    """
    rows, g = x.shape
    b, d = h.shape[0], h.shape[-1] - 1
    if g != lane_group(d) or rows % SUBLANES:
        raise ValueError(f"a {d}-D resident buffer is (rows, {lane_group(d)})"
                         f" with rows a multiple of {SUBLANES}, got {x.shape}")
    tr = min(rows, RESIDENT_TILE_ROWS)
    lane_coord = jnp.arange(g) % d
    coef, wcoef, grow, prow = _proj_batch_rows(h.astype(x.dtype), lo, hi,
                                               lane_coord, d, g)
    k = (2 * d - 1) * g

    def per_instance(width):
        return pl.BlockSpec((None, 1, width), lambda j, i: (i, 0, 0))

    out, mask = pl.pallas_call(
        functools.partial(_chain_project_instanced_kernel, d=d, g=g),
        out_shape=[jax.ShapeDtypeStruct((b, rows, g), x.dtype)] * 2,
        grid=(rows // tr, b),
        in_specs=[
            pl.BlockSpec((tr, g), lambda j, i: (j, 0)),    # shared points
            per_instance(k),                               # linear rows
            per_instance(k),                               # perspective rows
            pl.BlockSpec((1, k), lambda j, i: (0, 0)),     # same-point rows
            per_instance(4 * g),                           # t/wt/lo/hi rows
        ],
        out_specs=[pl.BlockSpec((None, tr, g), lambda j, i: (i, j, 0))] * 2,
        interpret=interpret,
    )(x, coef[:, None], wcoef[:, None], grow, prow[:, None])
    return out, mask
