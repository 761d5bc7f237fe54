"""Transform engine -- the paper's "graphics acceleration library" in JAX.

Section 7 of the paper: "The discussed findings are part of a complete
graphics acceleration library using the M1 reconfigurable system."  This
module is that library re-expressed for TPU: the three primitive classes
(vector-vector, vector-scalar, matrix) as composable JAX transforms, each
dispatched to the corresponding Pallas kernel on TPU (ref oracle on CPU).

Points are row vectors: (..., 2) in 2D, (..., 3) in 3D, and every
transform right-multiplies (q = p @ M), so chaining builder calls in
reading order is exactly the paper's "General Composite Algorithm using
Matrix Algorithm" -- without ever materialising homogeneous coordinates:
the composed matrix exists only as folded (A, t) plan parameters, and the
homogeneous (d+1, d+1) form is built on demand by ``.matrix``.

Composite transforms
--------------------
Composites are compiled, not interpreted: ``Transform2D``/``Transform3D``
are thin builders over :class:`repro.core.transform_chain.TransformChain`,
the paper's one-pass composite as a small chain compiler.  Builder calls
(``then_translate``/``then_scale``/``then_rotate``) only append to a lazy
IR -- no 3x3 matmuls, no allocation.  At ``apply`` the chain folds
algebraically (adjacent translates sum, scales multiply, scale+translate
fuse into one affine; pure-diagonal chains never touch the MXU) and lowers
to a single fused lane-dense Pallas kernel: one HBM read of the points and
one write for the *whole* chain, versus one read+write per primitive under
sequential dispatch.  Compiled plans are cached by chain structure +
backend, so the serving hot path (same chain shape, fresh parameter values
per request) neither re-folds nor retraces; ``TransformChain.apply_many``
maps one cached plan over a leading batch axis in one launch.  The byte
economy (one HBM pass for the whole chain) is held by
``tests/test_transform_chain.py``.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from repro.core.transform_chain import TransformChain
from repro.kernels import affine as k_affine
from repro.kernels import rotate2d as k_rotate2d
from repro.kernels import scale as k_scale
from repro.kernels import translate as k_translate
from repro.kernels import vecadd as k_vecadd


# -- primitive transforms (paper sections 5.1-5.3) ---------------------------

def translate(points: jnp.ndarray, t, *, backend=None) -> jnp.ndarray:
    """q = p + t (vector-vector; Table 1)."""
    return k_translate(points, jnp.asarray(t, points.dtype), backend=backend)


def scale(points: jnp.ndarray, s, *, backend=None) -> jnp.ndarray:
    """q = S x p, diagonal S (vector-scalar; Table 2)."""
    return k_scale(points, jnp.asarray(s, points.dtype), backend=backend)


def rotate(points: jnp.ndarray, theta, *, backend=None) -> jnp.ndarray:
    """q = p @ R(theta), row-vector form (matrix algorithm; section 5.3)."""
    return k_rotate2d(points, theta, backend=backend)


def affine(points: jnp.ndarray, s, t, *, backend=None) -> jnp.ndarray:
    """q = S x p + t fused (beyond-paper fusion of 5.1 + 5.2)."""
    return k_affine(points, jnp.asarray(s, points.dtype),
                    jnp.asarray(t, points.dtype), backend=backend)


def vecadd(u: jnp.ndarray, v: jnp.ndarray, *, backend=None) -> jnp.ndarray:
    """Elementwise u + v, the raw Table 1 op."""
    return k_vecadd(u, v, backend=backend)


# -- composite transforms (paper's "General Composite Algorithm") ------------

@dataclasses.dataclass(frozen=True)
class Transform2D:
    """Composite 2D transform: ``then_*`` builders append in application
    order (first call applied first -- under the row-vector convention
    that IS the paper's right-multiplied matrix chain).  Builders are lazy
    (IR append only); ``apply`` runs the folded chain as one fused kernel
    pass via the plan cache."""
    chain: TransformChain

    @staticmethod
    def identity() -> "Transform2D":
        return Transform2D(TransformChain.identity(2))

    @staticmethod
    def from_matrix(m: jnp.ndarray) -> "Transform2D":
        """Wrap an explicit (3, 3) homogeneous matrix (row-vector form)."""
        return Transform2D(TransformChain.identity(2).matrix(m))

    def then_translate(self, tx, ty) -> "Transform2D":
        return Transform2D(self.chain.translate(tx, ty))

    def then_scale(self, sx, sy) -> "Transform2D":
        return Transform2D(self.chain.scale(sx, sy))

    def then_rotate(self, theta) -> "Transform2D":
        return Transform2D(self.chain.rotate(theta))

    @property
    def matrix(self) -> jnp.ndarray:
        """The composed (3, 3) homogeneous matrix (materialised on demand;
        building it is no longer part of the apply path)."""
        return self.chain.as_homogeneous()

    def apply(self, points: jnp.ndarray, *, backend=None) -> jnp.ndarray:
        """points (..., 2) -> (..., 2) in one fused HBM pass."""
        return self.chain.apply(points, backend=backend)


@dataclasses.dataclass(frozen=True)
class Transform3D:
    """3D homogeneous composite on (..., 3) points; same lazy chain IR and
    fused one-pass lowering as :class:`Transform2D` (the companion paper's
    MorphoSys 3D pipeline mapping)."""
    chain: TransformChain

    @staticmethod
    def identity() -> "Transform3D":
        return Transform3D(TransformChain.identity(3))

    @staticmethod
    def from_matrix(m: jnp.ndarray) -> "Transform3D":
        """Wrap an explicit (4, 4) homogeneous matrix (row-vector form)."""
        return Transform3D(TransformChain.identity(3).matrix(m))

    def then_translate(self, tx, ty, tz) -> "Transform3D":
        return Transform3D(self.chain.translate(tx, ty, tz))

    def then_scale(self, sx, sy, sz) -> "Transform3D":
        return Transform3D(self.chain.scale(sx, sy, sz))

    def then_rotate(self, theta, axis) -> "Transform3D":
        return Transform3D(self.chain.rotate(theta, axis=axis))

    @property
    def matrix(self) -> jnp.ndarray:
        return self.chain.as_homogeneous()

    def apply(self, points: jnp.ndarray, *, backend=None) -> jnp.ndarray:
        return self.chain.apply(points, backend=backend)
