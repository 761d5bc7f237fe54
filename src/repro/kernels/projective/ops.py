"""Public entries for the projective chain family (homogeneous viewing
chains: camera -> projection -> cull -> viewport collapsed to one matrix).

Both entries return ``(projected, inside)`` -- the perspective-divided
points plus the boolean frustum-cull mask (w > 0 and every coordinate
inside the folded [lo, hi] bounds; bounds tests are inclusive, so points
exactly on a frustum plane count as inside).  Backend dispatch per
``repro.kernels.dispatch``; chain-level HBM byte accounting happens in
``TransformChain.apply``/``project`` and the serving engine (these entries
are called under jit inside compiled plans).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.autotune.cache import KernelConfig
from repro.kernels import dispatch
from repro.kernels.projective import projective as K
from repro.kernels.projective import ref


def _bounds(lo, hi, d: int, batch: tuple = ()):
    shape = batch + (d,)
    lo = jnp.full(shape, -jnp.inf, jnp.float32) if lo is None \
        else jnp.broadcast_to(jnp.asarray(lo, jnp.float32), shape)
    hi = jnp.full(shape, jnp.inf, jnp.float32) if hi is None \
        else jnp.broadcast_to(jnp.asarray(hi, jnp.float32), shape)
    return lo, hi


def chain_project(points: jnp.ndarray, h: jnp.ndarray, lo=None, hi=None, *,
                  backend: str | None = None,
                  config: KernelConfig | None = None):
    """Folded projective chain q = divide([p, 1] @ H) in one fused pass.

    ``points`` is (..., d); ``h`` the composed (d+1, d+1) homogeneous
    matrix (row-vector convention); ``lo``/``hi`` optional (d,) cull
    bounds (``None`` = unbounded).  Returns ``(projected (..., d),
    inside (...,) bool)``.  Lowering target for projective
    ``TransformChain`` plans: one HBM read of the points, one write of the
    projected points, one write of the mask -- the divide and the cull
    never leave the kernel.  ``config`` carries tuned launch parameters;
    any config is bit-identical to any other (staging-only knobs).
    """
    b = dispatch.resolve(backend)
    d = points.shape[-1]
    h = jnp.asarray(h)
    lo, hi = _bounds(lo, hi, d)
    if b == "ref":
        return ref.chain_project(points, h, lo, hi)
    cfg = config or KernelConfig("chain_project")
    out, mask = K.chain_project_1d(points.reshape(-1), h, lo, hi, d=d,
                                   interpret=(b == "interpret"),
                                   block_rows=cfg.block_rows,
                                   lane_target=cfg.lane_target)
    return out.reshape(points.shape), \
        (mask.reshape(-1, d)[:, 0] != 0).reshape(points.shape[:-1])


def chain_project_batch(pts3: jnp.ndarray, h: jnp.ndarray, lo=None, hi=None,
                        *, backend: str | None = None,
                        config: KernelConfig | None = None):
    """Batched folded projective chains: one launch per serving bucket.

    ``pts3`` is a packed (B, L, d) batch -- one serving request per row,
    padded to a common length L; ``h`` (B, d+1, d+1) / ``lo``/``hi``
    (B, d) are per-request folded parameters.  Returns ``(projected
    (B, L, d), inside (B, L) bool)``.  On ``ref`` the oracle is the
    per-request ``chain_project`` under ``jax.vmap`` (same unrolled op
    order per row -- the serving engine's equality contract), on
    ``pallas``/``interpret`` the row-aligned ``chain_project_batch_2d``
    kernel.  Called under jit inside the serving engine's compiled bucket
    plans; packed-batch byte accounting happens there.
    """
    b = dispatch.resolve(backend)
    bsz, _, d = pts3.shape
    h = jnp.broadcast_to(jnp.asarray(h), (bsz, d + 1, d + 1))
    lo, hi = _bounds(lo, hi, d, batch=(bsz,))
    if b == "ref":
        return jax.vmap(ref.chain_project)(pts3, h, lo, hi)
    cfg = config or KernelConfig("chain_project_batch")
    out, mask = K.chain_project_batch_2d(pts3, h, lo, hi,
                                         interpret=(b == "interpret"),
                                         block_rows=cfg.block_rows)
    return out, mask != 0


def chain_project_instanced(x: jnp.ndarray, h: jnp.ndarray, lo=None,
                            hi=None, *, backend: str | None = None):
    """Instanced folded projective chains: B instances of one resident
    point buffer, one launch.

    ``x`` is the buffer in the kernel's layout, ``(rows, g)`` with
    ``g = lane_group(d)`` (``GeometryServer.upload`` builds it: the flat
    points zero-padded to ``util.resident_rows`` rows); ``h``
    (B, d+1, d+1) / ``lo``/``hi`` (B, d) are the instances' folded
    parameters.  Returns ``(projected (B, rows, g), inside (B, rows, g)
    bool)`` in the buffer's layout, ``inside`` constant over each
    point's d lanes.  On ``ref`` the oracle is ``ref.chain_project``
    over the buffer's points under ``jax.vmap``, laid out the same way;
    on ``pallas``/``interpret`` the ``chain_project_instanced_2d``
    kernel."""
    b = dispatch.resolve(backend)
    h = jnp.asarray(h)
    bsz, d = h.shape[0], h.shape[-1] - 1
    lo, hi = _bounds(lo, hi, d, batch=(bsz,))
    if b == "ref":
        out, inside = jax.vmap(ref.chain_project, in_axes=(None, 0, 0, 0))(
            x.reshape(-1, d), h, lo, hi)
        shape = (bsz,) + x.shape
        return out.reshape(shape), \
            jnp.repeat(inside, d, axis=-1).reshape(shape)
    out, mask = K.chain_project_instanced_2d(x, h, lo, hi,
                                             interpret=(b == "interpret"))
    return out, mask != 0
