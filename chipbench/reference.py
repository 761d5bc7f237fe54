"""The plain reference and the comparison that decides ``correct``.

A request's ``spec`` lists the primitives the traffic drew, with their
parameters as drawn (float64):

  ("T", t)  ("S", s)  ("R", axis, theta)  ("A", s, t)  ("M", m)
  ("P", m)  ("C", lo, hi)  ("LOOKAT", eye, target, up)
  ("PERSP", fov_y, aspect, near, far)  ("VIEWPORT", x, y, w, h, z0, z1)

Points are row vectors in homogeneous form, ``[p, 1] @ H``.  The
reference composes one float64 matrix per primitive, applies the chain
to each point, divides by ``w`` where ``w > 0`` (a point behind the
centre of projection keeps its numerator, and is outside), and culls at
the place the ``C`` primitive sits: inside means ``w > 0`` and every
coordinate within ``[lo, hi]``, bounds included.  It imports nothing of
the program and takes nothing the program made.

Error is measured in float32 rounding units of each coordinate's own
scale: the first-order bound ``(|num| + |v| |w|) / |w|`` of the
magnitudes that enter it, where ``|num|`` and ``|w|`` take the
magnitudes through every primitive -- ``|p| |M1| |M2| ... |Mk|``, the
forward error bound of a product of matrices -- so a chain whose
composed matrix cancels is judged by the terms that cancelled, as any
float32 composition of it rounds them.  A rotation's magnitude also
holds ``|theta| |dR/dtheta|``: its angle rounded to float32 moves every
entry by up to that, which a rotation by nearly pi turns into most of
its sine.  Points whose ``w`` lies within
``W_MARGIN`` of zero relative to its magnitudes have no stable value
and are not compared; neither is the mask of a point within the error
limit of a cull plane.
"""
from __future__ import annotations

import dataclasses

import numpy as np

EPS = 2.0 ** -23            # float32 unit of the configuration's precision
W_MARGIN = 2.0 ** -10       # |w| below this share of its scale: undecided


def _rot(dim: int, axis, theta: float) -> np.ndarray:
    """Counter-clockwise rotation by ``theta`` (right-handed about
    ``axis`` in 3-D), as a row-vector matrix: ``q = p @ R``."""
    c, s = np.cos(theta), np.sin(theta)
    if dim == 2:
        col = np.array([[c, -s], [s, c]])
    else:
        col = np.eye(3)
        i, j = [(1, 2), (2, 0), (0, 1)][axis]
        col[i, i] = col[j, j] = c
        col[i, j], col[j, i] = -s, s
    return col.T


def _affine(a: np.ndarray, t: np.ndarray) -> np.ndarray:
    d = len(t)
    h = np.eye(d + 1)
    h[:d, :d] = a
    h[d, :d] = t
    return h


def _look_at(eye, target, up) -> np.ndarray:
    """World to camera: the camera at ``eye`` looks down its -z axis at
    ``target``, with ``up`` in its y-z plane."""
    eye, target, up = (np.asarray(v, np.float64) for v in (eye, target, up))
    z = eye - target
    z /= np.linalg.norm(z)
    x = np.cross(up, z)
    x /= np.linalg.norm(x)
    y = np.cross(z, x)
    rot = np.stack([x, y, z], axis=1)
    return _affine(rot, -eye @ rot)


def _perspective(fov_y, aspect, near, far) -> np.ndarray:
    """OpenGL clip space: camera z = -near, -far map to NDC -1, +1 and
    w is the distance in front of the eye."""
    f = 1.0 / np.tan(fov_y / 2.0)
    h = np.zeros((4, 4))
    h[0, 0] = f / aspect
    h[1, 1] = f
    h[2, 2] = (near + far) / (near - far)
    h[2, 3] = -1.0
    h[3, 2] = 2.0 * near * far / (near - far)
    return h


def _viewport(x, y, w, h, z0, z1) -> np.ndarray:
    """NDC [-1, 1] to [x, x + w] by [y, y + h] by [z0, z1]."""
    s = np.array([w / 2.0, h / 2.0, (z1 - z0) / 2.0])
    t = np.array([x + w / 2.0, y + h / 2.0, (z0 + z1) / 2.0])
    return _affine(np.diag(s), t)


def _matrix(prim, dim: int) -> np.ndarray:
    kind, args = prim[0], prim[1:]
    vec = (lambda v: np.broadcast_to(np.asarray(v, np.float64), (dim,)))
    if kind == "T":
        return _affine(np.eye(dim), vec(args[0]))
    if kind == "S":
        return _affine(np.diag(vec(args[0])), np.zeros(dim))
    if kind == "A":
        return _affine(np.diag(vec(args[0])), vec(args[1]))
    if kind == "R":
        return _affine(_rot(dim, args[0], args[1]), np.zeros(dim))
    if kind in ("M", "P"):
        return np.asarray(args[0], np.float64)
    if kind == "LOOKAT":
        return _look_at(*args)
    if kind == "PERSP":
        return _perspective(*args)
    if kind == "VIEWPORT":
        return _viewport(*args)
    raise ValueError(f"unknown primitive {kind!r}")


def _magnitude(prim, dim: int, m: np.ndarray) -> np.ndarray:
    """What a primitive's matrix ``m`` may move by, in rounding units:
    its entries, and for a rotation what its angle's rounding moves."""
    if prim[0] != "R":
        return np.abs(m)
    axis, theta = prim[1], prim[2]
    turn = np.abs(_affine(_rot(dim, axis, theta + np.pi / 2), np.zeros(dim)))
    if dim == 3:
        turn[axis, axis] = 0.0          # the axis itself does not turn
    turn[dim, dim] = 0.0
    return np.abs(m) + abs(theta) * turn


@dataclasses.dataclass
class Composed:
    """A chain as float64 matrices: ``pre`` up to the cull, ``post``
    after it, and the cull bounds (``None`` without a cull); each with
    the product of its primitives' magnitudes (``*_abs``)."""
    dim: int
    full: np.ndarray
    full_abs: np.ndarray
    pre: np.ndarray
    post: np.ndarray
    post_abs: np.ndarray
    projective: bool
    lo: np.ndarray | None = None
    hi: np.ndarray | None = None


def compose(spec, dim: int) -> Composed:
    """Multiply the primitives' matrices in order, in float64, and their
    magnitudes alike."""
    h, h_abs = np.eye(dim + 1), np.eye(dim + 1)
    pre, pre_abs, lo, hi = None, None, None, None
    proj = any(prim[0] in ("P", "C", "PERSP") for prim in spec)
    for prim in spec:
        if prim[0] == "C":
            pre, pre_abs = h, h_abs
            lo = np.broadcast_to(np.asarray(prim[1], np.float64), (dim,))
            hi = np.broadcast_to(np.asarray(prim[2], np.float64), (dim,))
            h, h_abs = np.eye(dim + 1), np.eye(dim + 1)
            continue
        m = _matrix(prim, dim)
        h, h_abs = h @ m, h_abs @ _magnitude(prim, dim, m)
    if pre is None:
        return Composed(dim, h, h_abs, h, np.eye(dim + 1), np.eye(dim + 1),
                        proj)
    return Composed(dim, pre @ h, pre_abs @ h_abs, pre, h, h_abs, proj,
                    lo, hi)


def _homog(p: np.ndarray) -> np.ndarray:
    return np.concatenate([p, np.ones((len(p), 1))], axis=1)


def _divide(ph: np.ndarray, d: int):
    w = ph[:, d]
    pos = w > 0
    safe = np.where(pos, w, 1.0)
    return np.where(pos[:, None], ph[:, :d] / safe[:, None], ph[:, :d]), pos


def expect(spec, points: np.ndarray, limit: float) -> dict:
    """What a request's results must be: the value, its scale, and the
    cull mask, with which points are decided enough to compare."""
    d = points.shape[-1]
    comp = compose(spec, d)
    p = _homog(np.asarray(points, np.float64).reshape(-1, d))
    ph = p @ comp.full
    mag = np.abs(p) @ comp.full_abs
    v, pos = _divide(ph, d)
    w, mw = np.abs(ph[:, d]), mag[:, d]
    scale = np.where(pos[:, None],
                     (mag[:, :d] + np.abs(v) * mw[:, None])
                     / np.where(pos, w, 1.0)[:, None], mag[:, :d])
    decided = w > W_MARGIN * mw
    out = {"ref": v, "scale": scale, "decided": decided,
           "projective": comp.projective}
    if not comp.projective:
        return out
    if comp.lo is None:
        out["inside"], out["mask_decided"] = pos, decided
        return out
    ndc, pos_c = _divide(p @ comp.pre, d)
    out["inside"] = pos_c & np.all((ndc >= comp.lo) & (ndc <= comp.hi),
                                   axis=1)
    # the bounds where the output is tested, and their own scale
    b = _homog(np.stack([comp.lo, comp.hi])) @ comp.post
    bmag = np.abs(_homog(np.stack([comp.lo, comp.hi]))) @ comp.post_abs
    blo, bhi = np.minimum(b[0, :d], b[1, :d]), np.maximum(b[0, :d], b[1, :d])
    margin = limit * EPS * (scale + bmag[:, :d].max(axis=0))
    clear = np.all((np.abs(v - blo) > margin) & (np.abs(v - bhi) > margin),
                   axis=1)
    out["mask_decided"] = decided & (clear | ~pos)
    return out


@dataclasses.dataclass
class Tally:
    """The numbers compared, over the checked requests."""
    err_ulps: float = 0.0          # worst error in float32 units of scale
    mask_mismatches: int = 0       # decided cull bits that differ
    failed: int = 0                # errors, wrong shapes, missing masks
    checked_requests: int = 0
    checked_points: int = 0
    undecided_points: int = 0      # |w| too near 0: value not compared

    def add(self, spec, points: np.ndarray, served, mask, limit: float):
        """Compare one served result (and its mask) with the reference."""
        self.checked_requests += 1
        out = np.asarray(served)       # an error object reads as shape ()
        if out.shape != points.shape or out.dtype != np.float32:
            self.failed += 1
            return
        exp = expect(spec, points, limit)
        d = points.shape[-1]
        out = out.reshape(-1, d).astype(np.float64)
        keep = exp["decided"]
        self.checked_points += int(keep.sum())
        self.undecided_points += int((~keep).sum())
        with np.errstate(invalid="ignore", over="ignore"):
            err = np.abs(out - exp["ref"])[keep] / (
                EPS * np.maximum(exp["scale"][keep], 1e-300))
        if err.size:
            worst = float(np.max(np.where(np.isfinite(err), err, np.inf)))
            self.err_ulps = max(self.err_ulps, worst)
        if exp["projective"]:
            if mask is None:
                self.failed += 1
                return
            m = np.asarray(mask).reshape(-1)
            md = exp["mask_decided"]
            self.mask_mismatches += int((m[md] != exp["inside"][md]).sum())

    def numbers(self, limits: dict, fallbacks: int) -> dict:
        """Each number compared, beside its limit."""
        return {
            "err_ulps": {"value": self.err_ulps, "limit": limits["err_ulps"]},
            "mask_mismatches": {"value": self.mask_mismatches, "limit": 0},
            "failed": {"value": self.failed, "limit": 0},
            "fallbacks": {"value": fallbacks, "limit": 0},
        }


def within(numbers: dict) -> bool:
    return all(n["value"] <= n["limit"] for n in numbers.values())


def control(spec, points: np.ndarray):
    """The reference put in the program's place one precision below the
    configuration's float32: points and the composed matrix rounded to
    bfloat16, products accumulated in float32 on the default device (a
    default-precision MXU pass).  Returns ``(points, mask or None)``."""
    import jax.numpy as jnp
    d = points.shape[-1]
    comp = compose(spec, d)
    flat = _homog(np.asarray(points, np.float64).reshape(-1, d))
    n = len(flat)
    # rows padded to a power of two, so few shapes compile
    rows = np.zeros((max(8, 1 << (n - 1).bit_length()), d + 1))
    rows[:n] = flat
    ph = jnp.dot(jnp.asarray(rows, jnp.bfloat16),
                 jnp.asarray(comp.full, jnp.bfloat16),
                 preferred_element_type=jnp.float32)[:n]
    w = ph[:, d]
    pos = w > 0
    v = jnp.where(pos[:, None], ph[:, :d] / jnp.where(pos, w, 1.0)[:, None],
                  ph[:, :d])
    out = np.asarray(v, np.float32).reshape(points.shape)
    if not comp.projective:
        return out, None
    inside = pos
    if comp.lo is not None:
        b = _homog(np.stack([comp.lo, comp.hi])) @ comp.post
        blo = jnp.asarray(np.minimum(b[0, :d], b[1, :d]), jnp.float32)
        bhi = jnp.asarray(np.maximum(b[0, :d], b[1, :d]), jnp.float32)
        inside = pos & jnp.all((v >= blo) & (v <= bhi), axis=1)
    return out, np.asarray(inside)
