"""Fused transform-chain compiler: the paper's one-pass composite, lazily.

The paper's "General Composite Algorithm using Matrix Algorithm" collapses
an arbitrary translate/scale/rotate pipeline into a single pass over the RC
array.  ``TransformChain`` is that idea as a small compiler:

  1. **IR** -- builder calls (``translate``/``scale``/``rotate``/``affine``/
     ``matrix``, 2D and 3D homogeneous) record primitives only; no jnp work
     happens until ``apply``, so composing a chain is allocation-free.
  2. **Fold** -- the recorded chain folds algebraically: adjacent translates
     sum, scales multiply, scale+translate fuse into one affine (s, t), and
     anything containing a rotation or a custom matrix folds into a single
     composed (A, t) pair.  Chains whose structure is pure-diagonal
     (translate/scale/affine only) never build a matrix and never touch the
     MXU.  Chains containing a *projective* primitive (``projective``/
     ``cull`` -- the graphics companion paper's viewing pipelines) fold one
     level up, in homogeneous space: everything between perspective divides
     collapses into a single (d+1, d+1) matrix H plus axis-aligned cull
     bounds, because affines compose into H on either side of a divide
     (the divide is a projective equivalence) -- so a full model -> camera
     -> projection -> cull -> viewport chain is ONE (H, lo, hi) triple and
     ONE divide.  The fold itself is O(k d^2) scalar work and runs
     host-side in numpy -- one shared code path for single-request
     ``apply`` and the serving engine, so a request folds to bit-identical
     composed parameters however it is dispatched (see the folding section
     note).
  3. **Lower** -- the folded chain lowers to ONE fused lane-dense Pallas
     kernel over the flattened point buffer -- one HBM read of the points,
     one write, with the composed parameters staged as (1, w) context-word
     rows: ``kernels.chain_diag`` for diagonal plans, ``kernels.chain_apply``
     (2d-1 lane-rolled multiply-adds) for general plans, and
     ``kernels.chain_project`` (a second rolled MAC set for the homogeneous
     w + in-kernel divide + cull mask) for projective plans.  The plan
     kinds form a lattice -- diag (s, t) is the diagonal of matrix (A, t),
     which is the affine block of projective (H, lo, hi) -- and every
     structure lowers to the cheapest kind that can express it.
  4. **Plan cache** -- compiled plans are cached by *chain structure* +
     backend, and the jitted plan function takes the folded parameter
     values as arguments, so the serving hot path (same chain shape, fresh
     parameter values every request) recompiles and retraces nothing.

Byte economy vs. sequential primitive dispatch (k-long chain over N points
of dim d, itemsize 4): sequential moves ~2*k*N*d*4 bytes HBM<->VMEM; the
fused plan moves 2*N*d*4 + O(1).  ``kernels.opcount`` makes this testable.

``repro.serving.GeometryServer`` layers plan-bucketed batched serving on
top of this compiler: same-structure requests pack into one (B, L, d)
batch and execute through the batched forms of the same chain kernels --
one launch per bucket instead of one per request.

Affine chains also compile to a second execution lane:
``apply(..., dtype="q8.7")`` runs the M1-faithful int16 Qm.n fixed-point
plan (``kernels.fixedpoint``) -- the fold is the same shared host fold,
the folded parameters are quantised once per request
(``repro.quantize``), and the fused kernel moves HALF the HBM bytes per
point.  Projective chains stay float (the in-kernel divide has no
single-shift Qm.n form) and are rejected with a ValueError.
"""
from __future__ import annotations

import dataclasses
import typing

import jax
import jax.numpy as jnp
import numpy as np

from repro import errors, quantize
from repro.autotune import cache as tuning
from repro.kernels import dispatch, opcount
from repro.obs import trace as obst
from repro.kernels.affine import chain_diag as _k_chain_diag
from repro.kernels.fixedpoint import chain_apply_q as _k_chain_apply_q
from repro.kernels.fixedpoint import chain_diag_q as _k_chain_diag_q
from repro.kernels.matmul import chain_apply as _k_chain_apply
from repro.kernels.projective import chain_project as _k_chain_project

# primitive kinds: T translate, S scale, R rotate, A affine(s, t), M matrix,
# P projective (full homogeneous matrix), C cull (axis-aligned bounds)
_DIAG_KINDS = frozenset("TSA")
_PROJ_KINDS = frozenset("PC")
_AXES = {"x": 0, "y": 1, "z": 2}

#: plan-cache / trace statistics (observable by tests):
#:   compiles -- plans built (structure-level cache misses)
#:   hits     -- plans served from the cache
#:   traces   -- executions of a plan body under jax tracing (a cached plan
#:               applied at a seen shape/dtype must not bump this)
stats = {"compiles": 0, "hits": 0, "traces": 0}


def _count_trace(kernel: str, backend: str) -> None:
    """Jit re-trace bookkeeping: the counter plus (when a tracer is
    installed) a ``plan.trace`` instant -- re-traces are where compile
    latency hides, so the trace marks each one with its kernel."""
    stats["traces"] += 1
    trc = obst.active()
    if trc.enabled:
        trc.instant("plan.trace", cache="chain", kernel=kernel,
                    backend=backend)

_PLAN_CACHE: dict[tuple, "Plan"] = {}


def clear_plan_cache() -> None:
    """Drop all compiled plans (tests use this to start cold)."""
    _PLAN_CACHE.clear()


def reset_stats() -> None:
    """Zero every chain counter (tests snapshot deltas from here)."""
    for k in stats:
        stats[k] = 0


# -- folding (host-side numpy float32) ---------------------------------------
#
# The fold runs on the host, in numpy, NOT inside the jitted plan.  That is
# a determinism decision, not a performance one (either way it is O(k d^2)
# scalar work): XLA:CPU takes per-program freedom in fusing float
# multiply-adds (FMA contraction, operand association -- both observed, and
# neither controllable: optimization barriers and bitcast fences are folded
# away by the algebraic simplifier), so the "same" fold traced at two
# different batch shapes can differ in its last ULP.  One shared host fold
# means a request folds to *bit-identical* composed parameters whether it
# is applied alone or packed into a serving bucket, leaving the fused
# kernel application as the only XLA-shaped code -- see
# ``serving.engine`` for the resulting equality contract.

def _vec(v, dim: int) -> np.ndarray:
    v = np.asarray(v, np.float32)
    if v.ndim == 0:
        v = np.broadcast_to(v, (dim,))
    return v.reshape(dim)


def _rot(dim: int, axis: int, theta) -> np.ndarray:
    """Right-multiply (row-vector) rotation matrix: q = p @ R."""
    c = np.cos(np.float32(theta), dtype=np.float32)
    s = np.sin(np.float32(theta), dtype=np.float32)
    if dim == 2:
        return np.array([[c, s], [-s, c]], np.float32)
    r = np.eye(3, dtype=np.float32)
    i, j = [(1, 2), (2, 0), (0, 1)][axis]   # rotation plane for axis x/y/z
    r[i, i] = r[j, j] = c
    r[i, j], r[j, i] = s, -s
    return r


def _mat_parts(val, dim: int) -> tuple[np.ndarray, np.ndarray]:
    """Split a custom-matrix param into (A (d,d), t (d,)); accepts a (d, d)
    linear matrix or a (d+1, d+1) AFFINE homogeneous one (row-vector
    convention).  A homogeneous matrix with a nontrivial perspective
    column is rejected -- silently dropping that column would compute the
    wrong transform; such matrices belong in ``projective``."""
    m = np.asarray(val, np.float32)
    if m.shape == (dim + 1, dim + 1):
        # tolerance scaled to the matrix magnitude: computed affines may
        # carry round-off residue in the perspective column, which is
        # numerically irrelevant; a REAL perspective column is orders of
        # magnitude above it
        tol = 1e-6 * max(1.0, float(np.abs(m).max()))
        if np.any(np.abs(m[:dim, dim]) > tol) or abs(m[dim, dim] - 1.0) > tol:
            raise ValueError(
                "matrix() requires an affine homogeneous matrix (last "
                f"column [0, ..., 0, 1]); got last column {m[:, dim]} -- "
                "use projective() for perspective matrices")
        return m[:dim, :dim], m[dim, :dim]
    if m.shape == (dim, dim):
        return m, np.zeros((dim,), np.float32)
    raise ValueError(f"matrix must be ({dim},{dim}) or "
                     f"({dim + 1},{dim + 1}); got {m.shape}")


def _fold_diag(dim: int, kinds, params,
               carry=None) -> tuple[np.ndarray, np.ndarray]:
    """Fold a pure-diagonal chain to (s, t) with q = s (.) p + t.

    ``carry`` resumes the fold from a saved (s, t) state instead of the
    identity -- the loop body is shared, so resuming is bit-identical to
    folding the concatenated chain in one call (see ``fold_carry_extend``).
    """
    if carry is None:
        s = np.ones((dim,), np.float32)
        t = np.zeros((dim,), np.float32)
    else:
        s, t = carry
    for (kind, _), val in zip(kinds, params):
        if kind == "T":
            t = t + _vec(val, dim)
        elif kind == "S":
            v = _vec(val, dim)
            s, t = s * v, t * v
        else:                                   # "A": y = v*y + u
            v, u = _vec(val[0], dim), _vec(val[1], dim)
            s, t = s * v, t * v + u
    return s, t


def _fold_matrix(dim: int, kinds, params,
                 carry=None) -> tuple[np.ndarray, np.ndarray]:
    """Fold a general chain to (A, t) with q = p @ A + t.

    ``carry`` resumes the fold from a saved (A, t) state (same loop body
    as the from-identity fold, hence bit-identical -- see
    ``fold_carry_extend``)."""
    if carry is None:
        a = np.eye(dim, dtype=np.float32)
        t = np.zeros((dim,), np.float32)
    else:
        a, t = carry
    for (kind, axis), val in zip(kinds, params):
        if kind == "T":
            t = t + _vec(val, dim)
        elif kind == "S":
            v = _vec(val, dim)
            a, t = a * v[None, :], t * v
        elif kind == "A":
            v, u = _vec(val[0], dim), _vec(val[1], dim)
            a, t = a * v[None, :], t * v + u
        elif kind == "R":
            r = _rot(dim, axis, val)
            a, t = a @ r, t @ r
        else:                                   # "M"
            m, u = _mat_parts(val, dim)
            a, t = a @ m, t @ m + u
    return a, t


def _homo(dim: int, a: np.ndarray, t: np.ndarray) -> np.ndarray:
    """Embed an affine (A, t) as a (d+1, d+1) homogeneous row-vector
    matrix: [p, 1] @ H = [p @ A + t, 1]."""
    h = np.zeros((dim + 1, dim + 1), np.float32)
    h[:dim, :dim] = a
    h[dim, :dim] = t
    h[dim, dim] = 1.0
    return h


def _map_bounds(lo: np.ndarray, hi: np.ndarray, s: np.ndarray,
                t: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Push axis-aligned [lo, hi] bounds through a per-coordinate affine
    y = s*x + t (negative scales swap the endpoints).  Only called once a
    cull has been recorded, so the bounds are finite."""
    a, b = s * lo + t, s * hi + t
    return np.minimum(a, b), np.maximum(a, b)


def _fold_projective(dim: int, kinds, params, carry=None):
    """Fold a projective chain to (H (d+1, d+1), lo (d,), hi (d,)) with
    q = divide([p, 1] @ H) and an inclusive axis-aligned cull against
    [lo, hi] in the OUTPUT space (+-inf where no cull was recorded).

    The homogeneous composition collapses everything around the divide:
    a divide is a projective equivalence ([q/w, 1] ~ [q, w]), so affines
    and projectives on either side all fold into one H with one divide at
    the end.  Cull bounds are recorded in the coordinate space where the
    ``cull`` primitive sits and pushed forward through later diagonal
    primitives (a viewport map); a rotation, custom matrix, or projective
    AFTER a cull would need non-axis-aligned bounds and is rejected.

    Returns the full carry (h, lo, hi, culled); ``fold_structure`` strips
    the ``culled`` flag.  ``carry`` resumes from a saved state (same loop
    body, hence bit-identical -- see ``fold_carry_extend``); the carried
    ``culled`` flag keeps the after-cull primitive restrictions exact
    across the resume boundary.
    """
    if carry is None:
        h = np.eye(dim + 1, dtype=np.float32)
        lo = np.full((dim,), -np.inf, np.float32)
        hi = np.full((dim,), np.inf, np.float32)
        culled = False
    else:
        h, lo, hi, culled = carry
    for (kind, axis), val in zip(kinds, params):
        if kind == "C":
            lo = np.maximum(lo, _vec(val[0], dim))
            hi = np.minimum(hi, _vec(val[1], dim))
            culled = True
            continue
        if kind == "T":
            t = _vec(val, dim)
            hk = _homo(dim, np.eye(dim, dtype=np.float32), t)
            if culled:
                lo, hi = lo + t, hi + t
        elif kind == "S":
            s = _vec(val, dim)
            hk = _homo(dim, np.diag(s), np.zeros((dim,), np.float32))
            if culled:
                lo, hi = _map_bounds(lo, hi, s, np.float32(0.0))
        elif kind == "A":
            s, t = _vec(val[0], dim), _vec(val[1], dim)
            hk = _homo(dim, np.diag(s), t)
            if culled:
                lo, hi = _map_bounds(lo, hi, s, t)
        elif kind in ("R", "M"):
            if culled:
                raise ValueError(
                    "only translate/scale/affine may follow cull() in a "
                    f"projective chain (got {kind!r}): axis-aligned cull "
                    "bounds cannot fold through a rotation or custom matrix")
            if kind == "R":
                hk = _homo(dim, _rot(dim, axis, val),
                           np.zeros((dim,), np.float32))
            else:
                hk = _homo(dim, *_mat_parts(val, dim))
        else:                                   # "P"
            if culled:
                raise ValueError("a projective primitive cannot follow "
                                 "cull(): record the cull after the last "
                                 "projection instead")
            hk = np.asarray(val, np.float32)
            if hk.shape != (dim + 1, dim + 1):
                raise ValueError(f"projective matrix must be "
                                 f"({dim + 1},{dim + 1}); got {hk.shape}")
        h = (h @ hk).astype(np.float32)
    return h, lo, hi, culled


# -- traced-parameter fallback (jnp fold) ------------------------------------
#
# The host fold requires concrete parameter values.  When a caller traces
# chain *parameters* -- jax.grad over a rotation angle, jit over a pose --
# ``apply`` instead folds in jnp inside the caller's own trace and calls
# the fused kernel entry directly (plan caching is moot there: the caller's
# jit already owns compilation).  This path is differentiable; it is NOT
# part of the serving bit-identity contract, which is about concrete
# parameters.

def _vec_jnp(v, dim: int):
    v = jnp.asarray(v, jnp.float32)
    return jnp.broadcast_to(v, (dim,)) if v.ndim == 0 else v.reshape(dim)


def _rot_jnp(dim: int, axis: int, theta):
    c = jnp.cos(jnp.asarray(theta, jnp.float32))
    s = jnp.sin(jnp.asarray(theta, jnp.float32))
    if dim == 2:
        return jnp.eye(2, dtype=jnp.float32) * c + \
            jnp.array([[0.0, 1.0], [-1.0, 0.0]], jnp.float32) * s
    i, j = [(1, 2), (2, 0), (0, 1)][axis]
    r = jnp.eye(3, dtype=jnp.float32).at[i, i].set(c).at[j, j].set(c)
    return r.at[i, j].set(s).at[j, i].set(-s)


def _fold_jnp(dim: int, kinds, params):
    a = jnp.eye(dim, dtype=jnp.float32)
    t = jnp.zeros((dim,), jnp.float32)
    for (kind, axis), val in zip(kinds, params):
        if kind == "T":
            t = t + _vec_jnp(val, dim)
        elif kind == "S":
            v = _vec_jnp(val, dim)
            a, t = a * v[None, :], t * v
        elif kind == "A":
            v, u = _vec_jnp(val[0], dim), _vec_jnp(val[1], dim)
            a, t = a * v[None, :], t * v + u
        elif kind == "R":
            r = _rot_jnp(dim, axis, val)
            a, t = a @ r, t @ r
        else:                                   # "M"
            m = jnp.asarray(val, jnp.float32)
            if m.shape == (dim + 1, dim + 1):
                m, u = m[:dim, :dim], m[dim, :dim]
            else:
                u = jnp.zeros((dim,), jnp.float32)
            a, t = a @ m, t @ m + u
    return a, t


def _params_traced(params) -> bool:
    return any(isinstance(leaf, jax.core.Tracer)
               for leaf in jax.tree.leaves(params))


def structure_is_diagonal(structure: tuple) -> bool:
    """True if ``structure`` (a ``TransformChain.structure`` value) folds to
    a diagonal (s, t) plan -- translate/scale/affine primitives only."""
    _, kinds = structure
    return all(k in _DIAG_KINDS for k, _ in kinds)


def structure_is_projective(structure: tuple) -> bool:
    """True if ``structure`` folds to a projective (H, lo, hi) plan --
    it contains a projective matrix or a cull primitive."""
    _, kinds = structure
    return any(k in _PROJ_KINDS for k, _ in kinds)


def plan_kind_of(structure: tuple) -> str:
    """The plan-kind lattice resolution for a structure: the cheapest of
    diag < matrix < projective that can express it."""
    if structure_is_projective(structure):
        return "projective"
    return "diag" if structure_is_diagonal(structure) else "matrix"


def fold_structure(structure: tuple, params) -> tuple[np.ndarray, ...]:
    """Fold ONE parameter set for ``structure``: float32 (s, t) if the
    structure is diagonal, (A, t) if it is a general affine, and
    (H, lo, hi) if it is projective.  This host fold is shared verbatim
    by ``TransformChain.apply``, the serving engine's bucket packing, and
    (through the carry API below) the scene graph's cached subchain
    folds, so a request's composed parameters are bit-identical however
    it is dispatched."""
    kind = plan_kind_of(structure)
    dim = structure[0]
    carry = fold_carry_extend(kind, dim, fold_carry_identity(kind, dim),
                              structure[1], params)
    return fold_carry_finish(kind, carry)


# -- incremental (carry-state) folds ----------------------------------------
#
# The scene graph (``repro.scene``) folds shared chain prefixes once and
# extends each node's world fold from its parent's saved state.  For the
# bitwise contract to survive that, the incremental fold must BE the
# one-pass fold: the ``_fold_*`` loops above thread an explicit carry, and
# ``fold_carry_extend`` re-enters the same loop with a saved carry.  The
# op sequence is therefore *identical* to folding the concatenated chain
# in one ``fold_structure`` call -- bit-identical results by construction,
# not by accident of float algebra.  The carry per plan kind:
#
#   diag        (s, t)                  q = s (.) p + t
#   matrix      (A, t)                  q = p @ A + t
#   projective  (H, lo, hi, culled)     q = divide([p, 1] @ H), cull flag
#                                       carried so the after-cull
#                                       restrictions stay exact
#
# A subchain folds under the plan kind of the FULL chain it belongs to
# (the lattice diag < matrix < projective is monotone under
# concatenation), so a diagonal prefix extended by a rotating suffix is
# folded with the matrix loop from the start -- exactly what the one-pass
# fold of the whole chain does.

def fold_carry_identity(kind: str, dim: int) -> tuple:
    """The identity carry state for an incremental fold of plan kind
    ``kind`` ("diag" | "matrix" | "projective") in ``dim`` dimensions --
    ``fold_carry_extend`` from this state reproduces ``fold_structure``
    bit for bit."""
    if kind == "diag":
        return (np.ones((dim,), np.float32), np.zeros((dim,), np.float32))
    if kind == "matrix":
        return (np.eye(dim, dtype=np.float32),
                np.zeros((dim,), np.float32))
    if kind == "projective":
        return (np.eye(dim + 1, dtype=np.float32),
                np.full((dim,), -np.inf, np.float32),
                np.full((dim,), np.inf, np.float32),
                False)
    raise ValueError(f"unknown fold kind {kind!r}")


def fold_carry_extend(kind: str, dim: int, carry: tuple, kinds,
                      params) -> tuple:
    """Extend a saved fold carry by the primitives ``(kinds, params)`` --
    the incremental half of ``fold_structure``.  Running the concatenated
    chain through one ``fold_structure`` call and running it here in
    pieces execute the SAME loop over the same primitives, so the two are
    bit-identical; the scene graph's fold-CSE correctness rests on this.

    ``kind`` must be the plan kind of the FULL chain the pieces belong to
    (``plan_kind_of`` of the concatenated structure); a subchain whose
    primitives cannot be expressed under ``kind`` raises ``ValueError``.
    """
    have = {k for k, _ in kinds}
    if kind == "diag":
        if have - _DIAG_KINDS:
            raise ValueError(f"subchain kinds {have - _DIAG_KINDS} cannot "
                             "fold under a diagonal carry")
        return _fold_diag(dim, kinds, params, carry)
    if kind == "matrix":
        if have & _PROJ_KINDS:
            raise ValueError(f"subchain kinds {have & _PROJ_KINDS} cannot "
                             "fold under an affine-matrix carry")
        return _fold_matrix(dim, kinds, params, carry)
    if kind == "projective":
        return _fold_projective(dim, kinds, params, carry)
    raise ValueError(f"unknown fold kind {kind!r}")


def fold_carry_finish(kind: str, carry: tuple) -> tuple[np.ndarray, ...]:
    """Turn a fold carry into the public folded-parameter tuple that
    ``fold_structure`` returns (drops the projective carry's internal
    ``culled`` flag)."""
    return carry[:3] if kind == "projective" else carry


# -- plans -------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Plan:
    """A compiled chain: ``fn(folded, flat_points_2d) -> out`` (jitted),
    where ``folded`` is the host-folded (s, t) / (A, t) / (H, lo, hi)
    tuple.  Projective plans return ``(projected, mask)``.  Fixed-point
    plans (``qformat`` set) take int16 Qm.n words instead -- the folded
    parameters quantised once per request by ``quantize.quantize_fold``
    -- and return int16."""
    kind: str                      # "diag" | "matrix" | "projective"
    dim: int
    backend: str
    length: int                    # primitives folded into this plan
    fn: typing.Callable
    qformat: str | None = None     # Qm.n name for fixed-point plans


def _compile_q(structure: tuple, backend: str, qname: str) -> Plan:
    """Compile a fixed-point plan: the same body discipline as the float
    plans (tuning consult at trace time, one fused kernel), but lowering
    to the int16 ``chain_*_q`` kernels with the format's fraction count
    baked in as the requantising shift.  Only affine structures get here
    -- ``TransformChain`` rejects projective + dtype before lookup."""
    dim, kinds = structure
    kind = plan_kind_of(structure)
    fmt = quantize.as_qformat(qname)

    if kind == "diag":
        def body(folded_q, pts2):
            """Jitted q-format diagonal scale+translate over (N, dim)."""
            _count_trace("chain_diag_q", backend)
            s, t = folded_q
            cfg = tuning.config_for("chain_diag_q", backend, fmt.name,
                                    pts2.shape[0])
            return _k_chain_diag_q(pts2, s, t, n_frac=fmt.n,
                                   backend=backend, config=cfg)
    else:
        def body(folded_q, pts2):
            """Jitted q-format fused matmul+translate over (N, dim)."""
            _count_trace("chain_apply_q", backend)
            a, t = folded_q
            cfg = tuning.config_for("chain_apply_q", backend, fmt.name,
                                    pts2.shape[0])
            return _k_chain_apply_q(pts2, a, t, n_frac=fmt.n,
                                    backend=backend, config=cfg)

    return Plan(kind=kind, dim=dim, backend=backend, length=len(kinds),
                fn=jax.jit(body), qformat=fmt.name)


def _compile(structure: tuple, backend: str) -> Plan:
    dim, kinds = structure
    kind = plan_kind_of(structure)

    # The tuning-cache consult happens inside the plan body, i.e. at
    # TRACE time: point shapes are concrete there, so the lookup keys on
    # the actual size class, and a cached plan applied at a seen shape
    # re-consults nothing (the config is baked into the trace, which is
    # why ``repro.autotune.set_enabled`` clears the plan cache).  With
    # tuning disabled this returns the deterministic defaults; any config
    # is bit-identical (staging-only knobs), so tuned and untuned plans
    # agree bitwise.
    if kind == "diag":
        def body(folded, pts2):
            """Jitted diagonal scale+translate over (N, dim)."""
            _count_trace("chain_diag", backend)
            s, t = folded
            cfg = tuning.config_for("chain_diag", backend,
                                    str(pts2.dtype), pts2.shape[0])
            return _k_chain_diag(pts2, s, t, backend=backend, config=cfg)
    elif kind == "matrix":
        def body(folded, pts2):
            """Jitted fused matmul+translate over (N, dim)."""
            _count_trace("chain_apply", backend)
            a, t = folded
            cfg = tuning.config_for("chain_apply", backend,
                                    str(pts2.dtype), pts2.shape[0])
            return _k_chain_apply(pts2, a, t, backend=backend, config=cfg)
    else:
        def body(folded, pts2):
            """Jitted homography apply + perspective divide + cull."""
            _count_trace("chain_project", backend)
            h, lo, hi = folded
            cfg = tuning.config_for("chain_project", backend,
                                    str(pts2.dtype), pts2.shape[0])
            return _k_chain_project(pts2, h, lo, hi, backend=backend,
                                    config=cfg)

    return Plan(kind=kind, dim=dim, backend=backend, length=len(kinds),
                fn=jax.jit(body))


def _get_plan(structure: tuple, backend: str,
              qname: str | None = None) -> Plan:
    key = (structure, backend, qname)
    plan = _PLAN_CACHE.get(key)
    trc = obst.active()
    if plan is None:
        stats["compiles"] += 1
        if trc.enabled:
            trc.instant("plan.compile", cache="chain", backend=backend,
                        q=qname, length=len(structure))
        plan = _compile_q(structure, backend, qname) if qname is not None \
            else _compile(structure, backend)
        _PLAN_CACHE[key] = plan
    else:
        stats["hits"] += 1
        if trc.enabled:
            trc.instant("plan.hit", cache="chain", backend=backend,
                        q=qname, length=len(structure))
    return plan


# -- the chain IR ------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TransformChain:
    """Lazy composite-transform IR.  Builder methods append primitives
    without any jnp work; ``apply`` folds + lowers through the plan cache.

        chain = (TransformChain.identity(dim=2)
                 .scale(2.0, 0.5).rotate(0.3).translate(1.0, -2.0))
        q = chain.apply(points)            # one fused kernel launch
    """
    dim: int
    kinds: tuple = ()              # ((kind, axis), ...) -- the structure
    params: tuple = ()             # raw per-primitive parameter values

    @staticmethod
    def identity(dim: int = 2) -> "TransformChain":
        """An empty chain in ``dim`` dimensions (2 or 3)."""
        if dim not in (2, 3):
            raise ValueError(f"dim must be 2 or 3, got {dim}")
        return TransformChain(dim=dim)

    def _push(self, kind: str, axis: int, param) -> "TransformChain":
        return TransformChain(self.dim, self.kinds + ((kind, axis),),
                              self.params + (param,))

    def _vec_arg(self, name: str, args):
        if len(args) == 1:
            return args[0]
        if len(args) != self.dim:
            raise ValueError(f"{name} takes 1 or {self.dim} components, "
                             f"got {len(args)}")
        return tuple(args)

    def translate(self, *t) -> "TransformChain":
        """Append q = p + t (scalar broadcast or one component per dim)."""
        return self._push("T", -1, self._vec_arg("translate", t))

    def scale(self, *s) -> "TransformChain":
        """Append q = s (.) p (scalar or per-dim factors)."""
        return self._push("S", -1, self._vec_arg("scale", s))

    def rotate(self, theta, axis=None) -> "TransformChain":
        """Append a rotation by ``theta`` (radians).  3D chains name the
        axis (0/1/2 or "x"/"y"/"z"); 2D chains take none."""
        if self.dim == 2:
            if axis is not None:
                raise ValueError("2D rotations take no axis")
            return self._push("R", -1, theta)
        if axis is None:
            raise ValueError("3D rotations need axis= (0/1/2 or x/y/z)")
        ax = _AXES.get(axis, axis)
        if ax not in (0, 1, 2):
            raise ValueError(f"bad rotation axis {axis!r}")
        return self._push("R", ax, theta)

    def affine(self, s, t) -> "TransformChain":
        """Append the fused q = s (.) p + t (scalars or per-dim vectors)."""
        return self._push("A", -1, (s, t))

    def matrix(self, m) -> "TransformChain":
        """Append a custom (d, d) linear or (d+1, d+1) homogeneous matrix
        (row-vector convention: q = [p, 1] @ M).  The matrix must be
        affine (last column [0, ..., 0, 1]); use ``projective`` for a
        matrix with a nontrivial perspective column."""
        return self._push("M", -1, m)

    def projective(self, m) -> "TransformChain":
        """Append a full (d+1, d+1) projective matrix (row-vector
        convention) -- a perspective or orthographic projection.  The
        chain becomes *projective*: it folds in homogeneous space and its
        plan ends in ONE in-kernel perspective divide (consecutive
        projective/affine primitives keep collapsing into a single H --
        the divide is a projective equivalence)."""
        return self._push("P", -1, m)

    def cull(self, lo=-1.0, hi=1.0) -> "TransformChain":
        """Append an inclusive axis-aligned cull against [lo, hi]^d in the
        CURRENT coordinate space (the NDC frustum cull of a viewing
        pipeline; scalars broadcast, or pass per-dim vectors).  The chain
        becomes projective; its plan emits a per-point inside/outside mask
        (see ``project``).  Only translate/scale/affine (e.g. a viewport
        map) may follow a cull -- the bounds fold through those exactly."""
        return self._push("C", -1, (lo, hi))

    # -- introspection -------------------------------------------------------

    def __len__(self) -> int:
        return len(self.kinds)

    @property
    def structure(self) -> tuple:
        """Hashable plan-cache key component: dims + primitive kinds/axes
        (parameter *values* are plan operands, not part of the key)."""
        return (self.dim, self.kinds)

    @property
    def is_diagonal(self) -> bool:
        """True if the folded chain is diagonal (never touches the MXU)."""
        return all(k in _DIAG_KINDS for k, _ in self.kinds)

    @property
    def is_projective(self) -> bool:
        """True if the chain contains a projective/cull primitive: it
        folds in homogeneous space and its plan ends in a divide."""
        return any(k in _PROJ_KINDS for k, _ in self.kinds)

    @property
    def plan_kind(self) -> str:
        """The plan class this structure lowers to -- the cheapest rung of
        the diag < matrix < projective lattice that can express it:
        "diag" (VPU-only fused affine), "matrix" (lane-rolled
        q = p @ A + t), or "projective" (homogeneous MACs + divide +
        cull mask)."""
        return plan_kind_of(self.structure)

    def fold(self) -> tuple[np.ndarray, ...]:
        """The host fold this chain's plan consumes: float32 (s, t) for
        diagonal structures, (A, t) for general affine ones, (H, lo, hi)
        for projective ones.  Bit-identical wherever it is computed --
        ``apply``, the serving engine, a test -- because it is one shared
        numpy code path (see the folding section note)."""
        return fold_structure(self.structure, self.params)

    def folded(self) -> tuple[jnp.ndarray, jnp.ndarray]:
        """Eagerly fold to the composed (A (d,d), t (d,)) pair.  Projective
        chains have no affine form -- use ``fold``/``as_homogeneous``."""
        if self.is_projective:
            raise ValueError("projective chains have no (A, t) form; use "
                             "fold() or as_homogeneous()")
        if _params_traced(self.params):
            return _fold_jnp(self.dim, self.kinds, self.params)
        if self.is_diagonal:
            s, t = self.fold()
            return jnp.asarray(np.diag(s)), jnp.asarray(t)
        a, t = self.fold()
        return jnp.asarray(a), jnp.asarray(t)

    def as_homogeneous(self) -> jnp.ndarray:
        """The composed (d+1, d+1) homogeneous matrix (row-vector form).
        For projective chains this is the folded H (the cull bounds are
        not representable in the matrix; see ``fold``)."""
        if self.is_projective:
            h, _, _ = self.fold()
            return jnp.asarray(h)
        a, t = self.folded()
        d = self.dim
        h = jnp.zeros((d + 1, d + 1), jnp.float32)
        h = h.at[:d, :d].set(a).at[d, :d].set(t).at[d, d].set(1.0)
        return h

    # -- execution -----------------------------------------------------------

    def _plan(self, backend: str | None, qname: str | None = None) -> Plan:
        return _get_plan(self.structure, dispatch.resolve(backend), qname)

    def _record_fused(self, plan: Plan, flat: jnp.ndarray, d: int) -> None:
        # one shared table (opcount) for parameter words and HBM passes
        # per plan kind -- the same accounting costmodel.chain_cost
        # predicts and the serving engine records per packed launch.
        # Fixed-point plans move 2-byte words throughout (the suffix keeps
        # the lanes separately countable).
        suffix = "_q" if plan.qformat else ""
        opcount.record(
            f"chain_fused_{plan.kind}{suffix}",
            opcount.fused_chain_bytes(flat.shape[0], d, kind=plan.kind,
                                      itemsize=flat.dtype.itemsize))

    def _apply_q(self, points: jnp.ndarray, fmt,
                 backend: str | None) -> jnp.ndarray:
        """The fixed-point lane of ``apply``: fold in float (the SAME host
        fold), quantise the folded parameters once, and run the int16
        ``chain_*_q`` plan.  Float points are quantised in (saturating,
        via the traced jnp quantiser -- bit-identical to the host one, no
        device round-trip, and jit over the POINTS works) and the result
        dequantised back to float32; int16 points are treated as
        already-Qm.n words and come back as int16."""
        fmt = quantize.as_qformat(fmt)
        quantize.reject_projective(self.is_projective)
        if _params_traced(self.params):
            raise NotImplementedError(
                "fixed-point chains require concrete parameters (the fold "
                "is quantised host-side)")
        d = points.shape[-1]
        from_float = quantize.points_need_quantize(points.dtype)
        pts_q = fmt.quantize_jnp(points) if from_float \
            else jnp.asarray(points)
        flat = pts_q.reshape(-1, d)
        plan = self._plan(backend, fmt.name)
        self._record_fused(plan, flat, d)
        folded_q = quantize.quantize_fold(self.fold(), plan.kind, fmt)
        out = plan.fn(folded_q, flat).reshape(points.shape)
        return fmt.dequantize_jnp(out) if from_float else out

    def apply(self, points: jnp.ndarray, *, backend: str | None = None,
              dtype: str | None = None) -> jnp.ndarray:
        """Apply the folded chain to (..., d) points in one fused pass.

        Concrete parameters go through the cached plan (host fold, see the
        folding section note); parameters that are jax tracers fold in jnp
        inside the caller's trace instead, so grad/jit over chain
        parameters (pose optimisation) stays differentiable (affine chains
        only).  Projective chains return the projected points; use
        ``project`` to also get the frustum-cull mask.

        ``dtype`` selects the execution lane: ``None`` is the float32
        lane; a Qm.n name ("q8.7") runs the M1-faithful int16 fixed-point
        lane -- same fold, parameters quantised once per plan, half the
        HBM bytes per point (affine chains only; projective chains are
        rejected).

        Malformed points raise the typed ``repro.errors`` taxonomy at
        this boundary (``ShapeError`` / ``EmptyPointsError`` /
        ``DtypeError`` -- all ``ValueError`` subclasses) instead of
        detonating inside the fused kernel: empty point sets and float64
        buffers used to be silently accepted here."""
        errors.check_points(points, self.dim)
        d = points.shape[-1]
        if not self.kinds:
            return points
        if dtype is not None:
            return self._apply_q(points, dtype, backend)
        flat = points.reshape(-1, d)
        if _params_traced(self.params):
            if self.is_projective:
                raise NotImplementedError(
                    "projective chains require concrete parameters (the "
                    "homogeneous fold runs host-side)")
            # chain parameters are jax tracers (grad/jit over a pose):
            # fold in jnp inside the caller's trace, differentiably
            opcount.record("chain_fused_traced",
                           2 * flat.nbytes + 4 * (d * d + d))
            a, t = _fold_jnp(d, self.kinds, self.params)
            out = _k_chain_apply(flat, a, t, backend=backend)
            return out.reshape(points.shape)
        plan = self._plan(backend)
        self._record_fused(plan, flat, d)
        out = plan.fn(self.fold(), flat)
        if plan.kind == "projective":
            out = out[0]
        return out.reshape(points.shape)

    def project(self, points: jnp.ndarray, *, backend: str | None = None,
                dtype: str | None = None
                ) -> tuple[jnp.ndarray, jnp.ndarray]:
        """Apply the chain and return ``(projected (..., d), inside (...,)
        bool)`` -- the perspective-divided points plus the frustum-cull
        mask, still ONE fused kernel launch (the divide, the cull test,
        and the per-point mask reduction all happen in-kernel).  Affine
        chains project trivially: same result as ``apply``, mask all
        True.  ``dtype`` (the fixed-point lane) is affine-only, exactly
        as in ``apply``: a projective chain + dtype is rejected (by the
        delegated ``apply`` -- one intake rule, one spelling)."""
        if dtype is not None:
            return (self.apply(points, backend=backend, dtype=dtype),
                    jnp.ones(points.shape[:-1], bool))
        errors.check_points(points, self.dim)
        d = points.shape[-1]
        if not self.is_projective:
            return (self.apply(points, backend=backend),
                    jnp.ones(points.shape[:-1], bool))
        if _params_traced(self.params):
            raise NotImplementedError(
                "projective chains require concrete parameters (the "
                "homogeneous fold runs host-side)")
        flat = points.reshape(-1, d)
        plan = self._plan(backend)
        self._record_fused(plan, flat, d)
        out, mask = plan.fn(self.fold(), flat)
        return out.reshape(points.shape), mask.reshape(points.shape[:-1])

    def apply_many(self, points: jnp.ndarray, *, backend: str | None = None,
                   dtype: str | None = None) -> jnp.ndarray:
        """Map one compiled plan over a leading batch axis: (B, ..., d) in,
        (B, ..., d) out, still a single fused kernel launch (the batch is
        part of the flattened point buffer, not a loop of launches)."""
        if points.ndim < 3:
            raise ValueError("apply_many expects (B, ..., d) with ndim >= 3")
        return self.apply(points, backend=backend, dtype=dtype)
