"""Shared kernel utilities: alignment, padding, block-size selection.

TPU alignment discipline (the MorphoSys analogue of "one column = 8 cells"):
last dim in multiples of 128 lanes, second-to-last in multiples of 8
sublanes; MXU tiles are 128x128.  ``ops.py`` wrappers pad to block multiples
and slice back so the public API stays shape-polymorphic.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8
#: rows of ``lane_group(d)`` lanes in one block of a resident point
#: buffer (``resident_rows``): 512 rows of 384 float32 lanes are 768 KiB
RESIDENT_TILE_ROWS = 512


def lane_shift(x: jnp.ndarray, delta: int) -> jnp.ndarray:
    """``out[:, j] = x[:, (j + delta) mod w]`` along the lane axis -- the
    rolled-MAC schedule's ``jnp.roll(x, -delta, axis=-1)``, lowered to the
    hardware lane rotate (``pltpu.roll``, which takes only non-negative
    shifts and has ``jnp.roll``'s direction).  ``delta == 0`` returns ``x``
    untouched: Mosaic refuses the zero-length slice a zero roll lowers to.
    Same data movement as ``jnp.roll`` on every backend, so arithmetic is
    unchanged."""
    if delta == 0:
        return x
    return pltpu.roll(x, (-delta) % x.shape[-1], x.ndim - 1)


def for_lane_chunks(width: int, g: int, body) -> None:
    """Run ``body(lanes)`` over the ``g``-lane chunks of a ``width``-lane
    block row, in a loop, so a batch kernel's code and its temporaries
    stay one chunk wide however long the requests are.  ``g`` is
    ``lane_group(d)``: a multiple of 128 (chunks are whole vregs) and of
    ``d`` (a chunk edge is a point edge, so a roll that wraps inside one
    chunk only moves lanes of another point onto lanes whose coefficient
    is zero).  The instanced kernel walks the rows of its block the same
    way, ``SUBLANES`` rows a chunk."""
    def step(r, carry):
        body(pl.ds(pl.multiple_of(r * g, g), g))
        return carry
    jax.lax.fori_loop(0, width // g, step, 0)


def round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def cdiv(a: int, b: int) -> int:
    return (a + b - 1) // b


def pick_block(dim: int, preferred: int, align: int) -> int:
    """Largest aligned block <= preferred that is reasonable for ``dim``."""
    if dim <= preferred:
        return round_up(dim, align)
    return preferred


def lane_group(d: int) -> int:
    """lcm(d, LANES): the smallest lane width at which a d-wide point
    pattern is periodic and no point straddles a row edge.  The one
    source of truth for this quantity -- the chain stagers AND the
    autotune cost model build on it, so they cannot drift apart."""
    return d * LANES // math.gcd(d, LANES)


def packed_budget_rows(wr: int, itemsize: int) -> int:
    """Batch-axis block-row heuristic for ``stage_packed``: as many
    sublane-aligned rows as keep one ``wr``-lane input block inside a
    2 MiB VMEM budget (shared with the autotune cost model's feasibility
    and step accounting)."""
    budget_rows = max(1, (1 << 21) // (wr * max(1, itemsize)))
    return max(SUBLANES, budget_rows // SUBLANES * SUBLANES)


def chain_width(d: int, target: int = 512) -> int:
    """Lane width for the flattened point-buffer chain kernels.

    The fused transform-chain kernels view an (N, d) point array as one
    flat buffer reshaped to rows of ``w`` lanes, so ``w`` must be a
    multiple of both the lane count (alignment) and ``d`` (no point may
    straddle a row/block edge).  The smallest such width is
    lcm(d, LANES), scaled up toward ``target`` lanes per row.  ``target``
    is the autotuner's lane-packing knob (``KernelConfig.lane_target``).
    """
    base = lane_group(d)
    return base * max(1, target // base)


def stage_flat(flat: jnp.ndarray, d: int, *, block_rows: int | None = None,
               lane_target: int | None = None):
    """Stage a flat (N*d,) point buffer for the chain kernels: pad and
    reshape to (rows_p, w) blocks of ``w = chain_width(d)`` lanes and
    return ``(xp, lane_coord, bm, w)`` where ``lane_coord[j] = j % d`` is
    the coordinate index of each lane (for building d-periodic parameter
    rows).  Shared by ``chain_diag_1d`` and ``chain_matrix_1d`` so the
    blocking/padding discipline cannot diverge between them.
    ``block_rows``/``lane_target`` are the tuned launch parameters;
    ``None`` keeps the historical defaults (256-row blocks, ~512 lanes).
    Block choice never changes arithmetic -- the per-lane op sequence is
    identical under any staging, so tuned and default results are
    bit-identical."""
    (l,) = flat.shape
    w = chain_width(d, target=lane_target or 512)
    rows = cdiv(l, w)
    bm = pick_block(rows, block_rows or 256, SUBLANES)
    rows_p = round_up(rows, bm)
    xp = jnp.pad(flat, (0, rows_p * w - l)).reshape(rows_p, w)
    lane_coord = jnp.arange(w) % d
    return xp, lane_coord, bm, w


def stage_packed(pts3: jnp.ndarray, d: int, *, block_rows: int | None = None):
    """Stage a packed (B, L, d) point batch for the batched chain kernels.

    Each batch row is one request's flat point buffer (the serving engine's
    pack/pad product).  Rows are padded to ``wr`` lanes where ``wr`` is a
    multiple of ``g = lcm(d, LANES)`` -- so the per-coordinate parameter
    pattern is ``g``-periodic along every row and no point straddles a row
    edge -- and the batch dim is padded to a ``bm``-row block.  With
    ``block_rows=None`` (the default), ``bm`` shrinks as rows widen so an
    input block stays within a fixed VMEM budget (oversized single rows
    are the serving engine's shard cap's problem, not this stager's); a
    tuned ``block_rows`` pins the batch block directly.  Returns
    ``(xp (Bp, wr), lane_coord (g,), bm, g)`` with ``lane_coord[j] = j % d``.
    """
    b, l, _ = pts3.shape
    g = lane_group(d)
    wr = round_up(max(l * d, g), g)
    if block_rows is None:
        block_rows = packed_budget_rows(wr, pts3.dtype.itemsize)
    # a 16-bit block's native tile is (16, 128): two words per sublane
    align = SUBLANES * max(1, 4 // pts3.dtype.itemsize)
    bm = pick_block(b, max(align, round_up(block_rows, align)), align)
    bp = round_up(b, bm)
    flat = pts3.reshape(b, l * d)
    xp = jnp.pad(flat, ((0, bp - b), (0, wr - l * d)))
    lane_coord = jnp.arange(g) % d
    return xp, lane_coord, bm, g


def resident_rows(words: int, d: int) -> int:
    """Rows of ``lane_group(d)`` lanes that hold a flat buffer of
    ``words`` point words in the resident layout (``GeometryServer.upload``,
    ``chain_project_instanced_2d``): whole points in every row, the
    rows a multiple of ``SUBLANES``, and a whole number of
    ``RESIDENT_TILE_ROWS`` blocks once they take more than one.  Fixed
    by the buffer's own length, not by a bucket's size class."""
    rows = round_up(cdiv(max(words, 1), lane_group(d)), SUBLANES)
    return rows if rows <= RESIDENT_TILE_ROWS \
        else round_up(rows, RESIDENT_TILE_ROWS)


def pad_axis(x: jnp.ndarray, axis: int, multiple: int,
             value: float = 0.0) -> jnp.ndarray:
    size = x.shape[axis]
    target = round_up(size, multiple)
    if target == size:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, target - size)
    return jnp.pad(x, pads, constant_values=value)


def pad2d(x: jnp.ndarray, bm: int, bn: int) -> jnp.ndarray:
    return pad_axis(pad_axis(x, -2, bm), -1, bn)
