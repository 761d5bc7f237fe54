"""GeometryServer: plan-bucketed batched serving of transform chains.

The ROADMAP north-star is heavy traffic: millions of small "apply this
composite transform to these points" requests.  Dispatching each one
through ``TransformChain.apply`` pays one kernel launch per request and
leaves the plan cache as the only amortisation.  This engine is the
missing server loop, built from the paper's M1 execution discipline:

  1. **Bucket** -- pending requests group by
     ``(dim, plan kind, backend, dtype, padded_length)``.  The plan
     identity ``(dim, kind)`` (``plan_identity``) + backend pick the
     compiled plan: a plan body reads nothing else of a chain, and each
     request's folds are its own operands, so chains of different
     structures but one ``(dim, kind)`` share a bucket and its launch
     (every request in a bucket hits ONE cached batch plan -- the
     context-memory discipline: load a context once, stream many
     operands through it); the size-bucketing policy
     (``bucketing.padded_length``: power-of-two grid refined under a
     waste cap) picks the padded length so padding waste per request
     stays below the cap.
  2. **Pack** -- each bucket's variable-length point sets pad/stack into
     one lane-dense (B, L, d) batch, and each request folds host-side
     through the SAME numpy fold ``apply`` uses
     (``TransformChain.fold``); the folded (A, t) pairs stack into the
     batch the kernels consume.
  3. **Launch** -- the whole bucket executes as a single fused kernel
     launch (``kernels.chain_diag_batch`` / ``chain_apply_batch`` /
     ``chain_project_batch`` -- the last for projective viewing-chain
     buckets, whose per-request results carry the in-kernel frustum-cull
     mask as ``Projected.mask``), the batched ``apply_many`` form of
     PR 1's one-HBM-pass chain kernels.
     Buckets whose packed batch exceeds the launch cap split into shards
     along the batch axis (and under ``jax.set_mesh`` each bucket's rows
     and folds are sharded over the mesh, one kernel per device).
  4. **Overlap** -- bucket k+1's host->device staging is dispatched while
     bucket k computes, the frame-buffer set-0/set-1 overlap of the paper:
     set 0 is the bucket the RC array (device) is computing on, set 1 is
     the bucket the DMA (host staging) is filling.

Equality contract vs. per-request ``apply`` (asserted by
``tests/test_serving.py``): the fold is bit-identical by construction (one
shared host code path); the fused application runs the same per-request
arithmetic, but XLA:CPU reserves per-program freedom in contracting float
multiply-adds, so across *different batch shapes* a float result may
differ by the rounding of one product -- on diagonal plans at most two
float32 epsilons of ``|p*s| + |t|``, on matrix and projective plans
float32-epsilon scale.  Results are bitwise deterministic for a fixed
bucket shape, and padded rows never contaminate payload rows (points are
row-independent).

Resident meshes: ``upload(points)`` validates a float32 point set once,
keeps a read-only host snapshot and puts the points on the device in
the instanced kernel's flat layout, and returns a ``Resident`` handle.
``submit(chain, handle)`` copies and packs nothing: projective requests
on one handle, plan identity and backend bucket together, each an instance
of the shared buffer, and run as one ``chain_project_instanced``
launch.  Diag and matrix chains on a handle take the host-array path on
its snapshot.  The projective kernel body
and the folds are the host-array path's, so results on a handle are
that path's bit for bit (``tests/test_resident.py``).

Fixed-point serving: ``submit(..., qformat="q8.7")`` routes a request
through the int16 Qm.n lane -- it buckets under the FORMAT (the dtype
slot of the bucket key), packs as int16 words through the same
``quantize.quantize_fold`` the chain compiler's q lane uses, and
launches the ``chain_*_batch_q`` kernels.  Integer arithmetic is exact
and order-independent, so the q lane's packed-vs-apply equality is
BITWISE on every plan kind (``tests/test_fixedpoint.py``) -- and each
packed launch moves 2-byte words, half the float32 HBM volume.

Fault tolerance (see ``docs/architecture.md`` section 6): ``submit`` is
the validation boundary -- malformed requests (bad shape, empty set,
float64, NaN/Inf points or folds, a q-format the error bound says would
wrap) raise the typed ``repro.errors`` taxonomy at intake instead of
detonating later inside a packed bucket.  ``flush`` contains failures
per LAUNCH: a bucket whose kernel launch fails (or whose output fails
the corruption check) never takes the other buckets down -- it walks a
recovery ladder of (1) bounded-exponential-backoff retries, (2) backend
degradation (``dispatch.fallback_ladder``: pallas -> ref, interpret -> ref),
and (3) bisection -- split the bucket in half and recover each half
independently -- which quarantines a poison request in O(log B)
launches instead of losing B-1 good ones.  A request whose singleton
launch still fails resolves to a typed ``LaunchError`` in its result
slot: every submitted request resolves to a result or a typed error,
never silence.  Every step is counted (``stats``/``BucketReport``) so
recovery is testable on exact numbers; ``serving.faults`` injects
deterministic faults to drive this machinery in tests.
"""
from __future__ import annotations

import dataclasses
import math
import time
import typing

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import errors, quantize
from repro.autotune import cache as tuning
from repro.core import transform_chain as tc
from repro.distributed import sharding
from repro.kernels import (chain_apply_batch, chain_apply_batch_q,
                           chain_diag_batch, chain_diag_batch_q,
                           chain_project_batch, chain_project_instanced,
                           dispatch, opcount, util)
from repro.obs import metrics as obsm
from repro.obs import trace as obst
from repro.serving import bucketing
from repro.serving import errors as serrors

#: serving statistics (observable by tests, benchmarks and the driver):
#:   plan_compiles -- batched plans built (one per distinct plan identity
#:                    (dim, kind) + backend + q-format + instanced)
#:   plan_hits     -- plans served from the cache
#:   traces        -- jit traces of plan bodies (new (B, L) shapes retrace;
#:                    a seen shape must not)
#:   launches      -- batched kernel launches DISPATCHED (shards, retries and
#:                    recovery launches included; injector-blocked attempts
#:                    are not -- they never reached the device)
#:   requests      -- requests served through flush()
#:   buckets       -- plan buckets executed
#:   bucket_structures -- distinct chain structures in each bucket, summed
#:                    over buckets (over ``buckets``: how many structures
#:                    share a plan bucket's launch)
#:   shards        -- extra launches from splitting oversized buckets
#:   payload_points / padded_points -- real vs padded points moved
#:   prefetches    -- launches whose device->host copy ``flush`` started
#:                    right after the plan call; equals ``launches`` on
#:                    a flush that needed no recovery (recovery's
#:                    launches unpack at once and are not prefetched)
#:   upload_bytes  -- host->device bytes each dispatched launch stages:
#:                    its packed points (none on a resident bucket) and
#:                    its folds, recovery's launches included
#:   host_operands -- host arrays each dispatched launch stages, counted
#:                    where ``upload_bytes`` counts their bytes: its one
#:                    fold row, and its packed points (none on a resident
#:                    bucket); each is its own host->device copy
#:   uploads       -- point sets put on the device by ``upload``
#:   resident_requests -- requests served through flush() as instances
#:                    of a handle (projective chains on a handle)
#: fault-tolerance counters (all deterministic under a seeded injector;
#: tests/test_faults.py::TestChaosSoak holds equal seeds to equal counts):
#:   rejected_requests  -- submissions refused with a typed RequestError
#:   q_fallbacks        -- q-lane requests rerouted to float32 because the
#:                         error bound predicted int16 wrap
#:   launch_failures    -- launch attempts that failed (injected or real)
#:   retries            -- re-attempts of a failing launch on the same rung
#:   backend_fallbacks  -- launches that succeeded on a degraded backend
#:   bisections         -- failing groups split in half to isolate poison
#:   recovered_requests -- requests that resolved OK after >= 1 failure
#:   failed_requests    -- requests resolved to a typed LaunchError
#: continuous-batching counters (incremented by serving.async_engine;
#: always 0 on the synchronous path):
#:   admitted_requests      -- requests past the admission gates
#:   queue_full_rejections  -- typed QueueFullError backpressure refusals
#:   rate_limit_rejections  -- typed RateLimitError token-bucket refusals
_STAT_KEYS = ("plan_compiles", "plan_hits", "traces", "launches",
              "requests", "buckets", "bucket_structures", "shards",
              "payload_points", "padded_points", "prefetches",
              "upload_bytes", "host_operands", "uploads",
              "resident_requests",
              "rejected_requests", "q_fallbacks", "launch_failures",
              "retries", "backend_fallbacks", "bisections",
              "recovered_requests", "failed_requests",
              "admitted_requests", "queue_full_rejections",
              "rate_limit_rejections")

#: the keys above that count SERVER activity (everything except the plan
#: cache, which is module-global like the cache it counts): each
#: GeometryServer keeps its own registry of these, and the module view
#: is their explicit cross-server aggregate
_SERVER_KEYS = tuple(k for k in _STAT_KEYS
                     if k not in ("plan_compiles", "plan_hits", "traces"))

#: the process-wide aggregate registry behind the module ``stats`` view
#: (obs.export.prometheus_text(REGISTRY) is the exposition entry point)
REGISTRY = obsm.MetricsRegistry("serving")

#: back-compat module view: a MutableMapping over REGISTRY counters with
#: the exact dict semantics the pre-obs ``stats`` dict had -- every
#: existing ``stats["launches"]`` read, ``+=`` and reset works unchanged
stats = obsm.StatsView(REGISTRY, _STAT_KEYS)

_BATCH_PLANS: dict[tuple, "BatchPlan"] = {}


def reset_stats() -> None:
    """Zero the module counters.  The counters are GLOBAL (shared by
    every server in the process); the documented invariant

        stats["launches"] == sum(r.launches for r in server.reports)

    therefore holds only for a single server whose lifetime starts at
    the reset -- use ``GeometryServer.reset_stats()``, which resets the
    module counters AND the server's accumulated report history in one
    step, when asserting it."""
    for k in stats:
        stats[k] = 0


def clear_plan_cache() -> None:
    """Drop all compiled batch plans (tests use this to start cold)."""
    _BATCH_PLANS.clear()


def _count_trace(kernel: str, backend: str, dtype: str, n: int) -> None:
    """Plan-body bookkeeping at jit-trace time (python side effects in a
    body run only under tracing): the traces counter, plus a plan.trace
    instant when the obs tracer is on -- retrace events are exactly the
    shape-cache misses the compiles/hits/traces discipline pins."""
    stats["traces"] += 1
    trc = obst.active()
    if trc.enabled:
        trc.instant("plan.trace", cache="serving", kernel=kernel,
                    backend=backend, dtype=dtype, n=n)


def _per_device(body, kind: str, dim: int, shared_points: bool = False):
    """A plan's jitted function: split the launch's fold row into its
    parts (``_fold_parts``), then run the bucket body on them, once per
    device when a mesh is set.  A Mosaic kernel cannot be partitioned
    by the compiler, so the batch axis is split by ``shard_map`` over
    the mesh's fsdp axes (``_stage`` placed the rows and their fold row
    that way) and each device traces the body at its own share of the
    rows.  Rows are independent and staging never changes arithmetic,
    so each row's result is the one a single device computes.
    ``shared_points``: the points are one resident buffer, replicated
    on every device (``upload``), and only the folds split."""
    def call(rows, pts3):
        parts = _fold_parts(rows, kind, dim)
        mesh = sharding.ambient_mesh()
        if mesh is None or mesh.size == 1:
            return body(parts, pts3)
        spec = P(sharding.axis_names(mesh)[0])
        return jax.shard_map(body, mesh=mesh,
                             in_specs=(spec, P() if shared_points else spec),
                             out_specs=spec, check_vma=False)(parts, pts3)
    return jax.jit(call)


class Projected(np.ndarray):
    """A projective request's serving result: the projected points as a
    plain ndarray (shape-compatible with ``TransformChain.apply``
    everywhere), with the per-point frustum-cull mask attached as
    ``.mask`` (bool, the request's leading shape; True = inside).  The
    mask rides along so existing consumers that treat results as arrays
    keep working unchanged.  ``.mask`` describes EXACTLY the array
    ``flush`` returned: derived arrays (slices, transposes, sorts, any
    indexing -- same-shaped or not) read ``.mask`` as ``None`` rather
    than inheriting a mask whose rows may no longer line up with
    theirs.  Slice the mask alongside the points instead:
    ``pts[sel], res.mask[sel]``."""

    def __array_finalize__(self, obj):
        # derived arrays NEVER inherit: a shape check cannot detect
        # same-shape reorderings (r[::-1], fancy indexing), so the only
        # honest mask is the one _projected() attaches explicitly
        self._mask = None

    @property
    def mask(self) -> np.ndarray | None:
        """The cull mask ``_projected()`` attached, or None on a view."""
        return self._mask

    @mask.setter
    def mask(self, value: np.ndarray | None) -> None:
        """Attach a cull mask (only ``_projected()`` should set this)."""
        self._mask = value


def _projected(points: np.ndarray, mask: np.ndarray) -> Projected:
    out = np.ascontiguousarray(points).view(Projected)
    out.mask = mask
    return out


@dataclasses.dataclass(frozen=True)
class BatchPlan:
    """A compiled bucket executor: ``fn(rows, pts3) -> out``
    (jitted), where ``rows`` is the bucket's host-folded per-request
    parameters as one ``(B, words)`` array (``_fold_row``): row i holds
    request i's fold parts flattened side by side -- (s, t), (A, t) or
    (H, lo, hi) -- which the plan splits on the device into
    (B,d) + (B,d), (B,d,d) + (B,d) or (B,d+1,d+1) + (B,d) + (B,d)
    (``_fold_parts``) before its body runs.  Projective plans return
    ``(projected (B,L,d), inside (B,L))``.  Fixed-point plans
    (``qformat`` set) take int16 Qm.n words, rows and points -- each
    request's fold quantised by ``quantize.quantize_fold`` at pack time
    -- and return int16.  Instanced plans take one resident buffer
    (``Resident.device``, ``(rows, lane_group(d))``) in place of
    ``pts3``, each row of folds an instance of it, and return
    ``(projected, inside)`` in the buffer's layout,
    ``(B, rows, lane_group(d))`` each; only projective chains have
    them."""
    kind: str                      # "diag" | "matrix" | "projective"
    dim: int
    backend: str
    fn: typing.Callable
    qformat: str | None = None     # Qm.n name for fixed-point plans
    instanced: bool = False        # takes a resident buffer


def plan_identity(chain: tc.TransformChain) -> tuple[int, str]:
    """What a batch plan depends on: the chain's dimension and plan kind.
    A plan body reads nothing else of a chain -- each request's folds
    are its own operands, of one shape per ``(dim, kind)`` -- so this
    pair keys the plan cache, the bucket key (``_bucket_key``) and the
    autotuner's launch count (``costmodel.workload_shape``)."""
    return chain.dim, chain.plan_kind


def _plan_tag(ident: tuple[int, str]) -> str:
    """A plan identity as reports and traces print it, e.g. ``2D:matrix``."""
    dim, kind = ident
    return f"{dim}D:{kind}"


def _fold_shapes(kind: str, dim: int) -> tuple:
    """The shapes of one request's fold parts, in fold order."""
    if kind == "diag":
        return (dim,), (dim,)
    if kind == "matrix":
        return (dim, dim), (dim,)
    return (dim + 1, dim + 1), (dim,), (dim,)


def _fold_parts(rows, kind: str, dim: int) -> tuple:
    """A launch's fold row (``_fold_row``) split on the device into its
    parts, ``(B,) + shape`` each in fold order: the values the batch
    kernels take, bit for bit.  Each part is a gather of its columns,
    not a slice: XLA:CPU fuses a slice into the kernel's own loop and
    may then contract its multiply-adds otherwise, where a gather
    leaves the kernel's program as it is with the parts passed in."""
    parts, at = [], 0
    for shape in _fold_shapes(kind, dim):
        size = math.prod(shape)
        cols = rows[:, np.arange(at, at + size)]
        parts.append(cols.reshape((len(rows),) + shape))
        at += size
    return tuple(parts)


def _compile_batch_q(ident: tuple[int, str], backend: str,
                     qname: str) -> BatchPlan:
    """Compile a fixed-point bucket executor: the same trace-time tuning
    consult as the float bodies, lowering to the int16 batch kernels with
    the format's fraction count as the requantising shift.  Projective
    chains never get here (``submit`` rejects chain + qformat)."""
    dim, kind = ident
    fmt = quantize.as_qformat(qname)

    if kind == "diag":
        def body(folded, pts3):
            """Jitted q-format diagonal transform over a (B, L) bucket."""
            _count_trace("chain_diag_batch_q", backend, fmt.name,
                         pts3.shape[0] * pts3.shape[1])
            s, t = folded
            cfg = tuning.config_for("chain_diag_batch_q", backend, fmt.name,
                                    pts3.shape[0] * pts3.shape[1])
            return chain_diag_batch_q(pts3, s, t, n_frac=fmt.n,
                                      backend=backend, config=cfg)
    else:
        def body(folded, pts3):
            """Jitted q-format matmul transform over a (B, L) bucket."""
            _count_trace("chain_apply_batch_q", backend, fmt.name,
                         pts3.shape[0] * pts3.shape[1])
            a, t = folded
            cfg = tuning.config_for("chain_apply_batch_q", backend, fmt.name,
                                    pts3.shape[0] * pts3.shape[1])
            return chain_apply_batch_q(pts3, a, t, n_frac=fmt.n,
                                       backend=backend, config=cfg)

    return BatchPlan(kind=kind, dim=dim, backend=backend,
                     fn=_per_device(body, kind, dim), qformat=fmt.name)


def _compile_batch(ident: tuple[int, str], backend: str,
                   instanced: bool = False) -> BatchPlan:
    dim, kind = ident

    # Tuning-cache consult at trace time, mirroring the chain compiler:
    # the packed (B, L) shape is concrete under the jit trace, so the
    # lookup keys on the bucket's real size class; staging-only knobs keep
    # every config bit-identical (see core.transform_chain._compile).
    if kind == "diag":
        def body(folded, pts3):
            """Jitted diagonal transform over a (B, L) bucket."""
            _count_trace("chain_diag_batch", backend, str(pts3.dtype),
                         pts3.shape[0] * pts3.shape[1])
            s, t = folded
            cfg = tuning.config_for("chain_diag_batch", backend,
                                    str(pts3.dtype),
                                    pts3.shape[0] * pts3.shape[1])
            return chain_diag_batch(pts3, s, t, backend=backend, config=cfg)
    elif kind == "matrix":
        def body(folded, pts3):
            """Jitted matmul transform over a (B, L) bucket."""
            _count_trace("chain_apply_batch", backend, str(pts3.dtype),
                         pts3.shape[0] * pts3.shape[1])
            a, t = folded
            cfg = tuning.config_for("chain_apply_batch", backend,
                                    str(pts3.dtype),
                                    pts3.shape[0] * pts3.shape[1])
            return chain_apply_batch(pts3, a, t, backend=backend, config=cfg)
    else:
        def body(folded, pts3):
            """Jitted projective transform + cull over a (B, L) bucket."""
            _count_trace("chain_project_batch", backend, str(pts3.dtype),
                         pts3.shape[0] * pts3.shape[1])
            h, lo, hi = folded
            cfg = tuning.config_for("chain_project_batch", backend,
                                    str(pts3.dtype),
                                    pts3.shape[0] * pts3.shape[1])
            return chain_project_batch(pts3, h, lo, hi, backend=backend,
                                       config=cfg)

    if instanced:
        body = _instanced_body(dim, backend)
    return BatchPlan(kind=kind, dim=dim, backend=backend,
                     fn=_per_device(body, kind, dim,
                                    shared_points=instanced),
                     instanced=instanced)


def _instanced_body(dim: int, backend: str):
    """A resident projective bucket's body: the instanced kernel over
    one shared buffer as uploaded, one fold an instance."""
    def body(folded, x):
        """Jitted projective transform + cull of B instances."""
        h, lo, hi = folded
        _count_trace("chain_project_instanced", backend, str(x.dtype),
                     len(h) * x.size // dim)
        return chain_project_instanced(x, h, lo, hi, backend=backend)
    return body


def get_batch_plan(ident: tuple[int, str], backend: str,
                   qname: str | None = None, *,
                   instanced: bool = False) -> BatchPlan:
    """The cached batch plan of a plan identity ``(dim, kind)``
    (``plan_identity``) on ``backend``.  It mirrors
    ``transform_chain._get_plan``'s compiles/hits discipline; the two
    caches stay separate because they count into different stats domains
    (chain compiler vs serving engine) and compile different bodies
    (one fold vs a batch's fold rows).  ``qname`` selects the
    fixed-point lane (a distinct cached plan, as a distinct dtype would
    be); ``instanced`` the resident-buffer plan."""
    key = (*ident, backend, qname, instanced)
    plan = _BATCH_PLANS.get(key)
    trc = obst.active()
    if plan is None:
        stats["plan_compiles"] += 1
        if trc.enabled:
            trc.instant("plan.compile", cache="serving",
                        plan=_plan_tag(ident), backend=backend, q=qname)
        plan = _compile_batch_q(ident, backend, qname) \
            if qname is not None \
            else _compile_batch(ident, backend, instanced)
        _BATCH_PLANS[key] = plan
    else:
        stats["plan_hits"] += 1
        if trc.enabled:
            trc.instant("plan.hit", cache="serving",
                        plan=_plan_tag(ident), backend=backend, q=qname)
    return plan


# -- the server --------------------------------------------------------------

@dataclasses.dataclass(frozen=True, eq=False)
class Resident:
    """A point set that ``GeometryServer.upload`` put on the device once:
    the handle ``submit`` takes in place of an array.

    ``host`` is a read-only float32 snapshot of the points as uploaded
    (the caller's array may change afterwards; results never do), kept
    for identity chains and recovery.  ``device`` holds the same points
    in the instanced kernel's layout: the flat ``(n*d,)`` words
    zero-padded to ``(util.resident_rows(n*d, d), util.lane_group(d))``,
    replicated over the mesh that was set at upload.  A handle compares
    and hashes by identity, so it names its own bucket."""
    host: np.ndarray
    device: jax.Array

    dtype = np.dtype(np.float32)

    @property
    def dim(self) -> int:
        """Coordinates per point."""
        return self.host.shape[-1]

    @property
    def n(self) -> int:
        """Points in the set."""
        return self.host.size // self.dim

    @property
    def nbytes(self) -> int:
        """Bytes of the device buffer."""
        return self.device.nbytes

    @property
    def lpad(self) -> int:
        """Points the device buffer holds, padding included: the length
        of every instance a launch computes."""
        return self.device.size // self.dim


def _place_resident(host: np.ndarray) -> jax.Array:
    """``host``'s points on the device in the resident layout; under a
    mesh set with ``jax.set_mesh``, replicated on every device of it."""
    d = host.shape[-1]
    flat = np.zeros((util.resident_rows(host.size, d), util.lane_group(d)),
                    np.float32)
    flat.reshape(-1)[:host.size] = host.reshape(-1)
    mesh = jax.sharding.get_mesh()
    if mesh.empty or mesh.size == 1:
        return jax.device_put(flat)
    return jax.device_put(flat, NamedSharding(mesh, P()))


@dataclasses.dataclass(frozen=True)
class FaultConfig:
    """Recovery policy knobs for one ``GeometryServer``.

    ``on_q_overflow`` decides what happens when ``quantize.error_bound``
    predicts a q-lane request would wrap int16:

      * ``"fallback"`` (default) -- serve the request through the float32
        lane instead (int16 submissions come back requantised int16, so
        the caller's contract holds); counted in ``stats["q_fallbacks"]``.
      * ``"reject"``  -- raise ``QRangeError`` at submit.
      * ``"wrap"``    -- legacy M1 semantics: no check, arithmetic wraps.
    """
    max_launch_attempts: int = 3   # per ladder rung, first attempt included
    backoff_base_s: float = 0.002  # sleep before retry k: base * factor**k
    backoff_factor: float = 2.0
    backoff_cap_s: float = 0.25
    validate_finite: bool = True   # reject NaN/Inf points/folds at submit
    validate_outputs: bool = True  # non-finite launch output => corruption
    on_q_overflow: str = "fallback"

    def __post_init__(self):
        if self.on_q_overflow not in ("fallback", "reject", "wrap"):
            raise ValueError(f"on_q_overflow must be fallback|reject|wrap, "
                             f"got {self.on_q_overflow!r}")
        if self.max_launch_attempts < 1:
            raise ValueError("max_launch_attempts must be >= 1")


@dataclasses.dataclass
class _Pending:
    ticket: int
    chain: tc.TransformChain
    points: np.ndarray             # original-shape host copy
    n: int                         # flattened point count
    fold: tuple | None = None      # host fold, computed once at submit
    qformat: quantize.QFormat | None = None   # fixed-point lane request
    dequantize: bool = False       # float submitted -> float32 back
    q_fallback: bool = False       # q request rerouted to the float lane
    requant: quantize.QFormat | None = None   # int16 caller: requantise out
    resident: Resident | None = None          # submitted on this handle


class _FailedLaunch:
    """Marker in the outs list: this launch raised instead of returning."""

    def __init__(self, err: Exception):
        self.err = err


@dataclasses.dataclass
class _Launch:
    """One scheduled launch (a whole bucket, or one shard of it), with
    everything recovery needs to re-pack and re-dispatch its requests."""
    ident: tuple                   # the plan identity (dim, kind)
    qname: str | None
    backend: str                   # the rung this flush started on
    lpad: int
    plan: BatchPlan
    rows: np.ndarray               # its folds, one row a request
    packed: np.ndarray | Resident  # the handle, on a resident bucket
    reqs: list
    report: "BucketReport"
    track: str = ""                # trace track: the bucket signature


@dataclasses.dataclass
class BucketReport:
    """Per-bucket accounting for one flush (the driver prints these)."""
    structure: str                 # the plan identity, e.g. "2D:matrix"
    kind: str                      # plan kind: diag | matrix | projective
    lpad: int                      # padded points per request
    requests: int
    payload_points: int
    padded_points: int
    launches: int = 0              # dispatched: 1 unless sharded/recovered
    backend: str = ""              # the rung the bucket started on
    final_backend: str = ""        # the rung its last success landed on
    retries: int = 0
    bisections: int = 0
    backend_fallbacks: int = 0
    recovered_requests: int = 0
    failed_requests: int = 0       # resolved to a typed LaunchError
    q_fallback_requests: int = 0   # q requests served through this float
    #                                bucket because the bound predicted wrap

    @property
    def waste(self) -> float:
        """Fraction of padded points that carried no payload."""
        return 1.0 - self.payload_points / max(1, self.padded_points)

    @property
    def launches_saved(self) -> int:
        """Kernel launches avoided by batching (requests - launches)."""
        return self.requests - self.launches


def _bucket_track(ident: tuple[int, str], backend: str, dt: str,
                  lpad: int) -> str:
    """The trace track (Perfetto timeline) name of one plan bucket."""
    return f"{_plan_tag(ident)}|{backend}|{dt}|{lpad}"


def _fold_row(folds: list, dtype) -> np.ndarray:
    """The requests' folds as one ``(B, words)`` array of the lane's
    dtype: row i holds request i's fold parts flattened side by side in
    fold order, which ``_fold_parts`` takes apart on the device.  A
    launch then stages its folds as one host array, where each array a
    plan call transfers costs its own host->device copy.  Every part of
    a fold already has the lane's dtype, so no value is cast."""
    rows = np.empty((len(folds), sum(np.size(p) for p in folds[0])), dtype)
    at = 0
    for part in zip(*folds):
        shape = np.shape(part[0])
        size = math.prod(shape)
        # a view: splitting the slice's contiguous last axis copies nothing
        rows[:, at:at + size].reshape((len(folds),) + shape)[...] = part
        at += size
    return rows


def _prefetch(out) -> None:
    """Start the device->host copy of every output of one launch (a
    projective launch has two: points and mask; a sharded output one
    copy per shard) without waiting for it; ``_fetch`` later reuses
    the copy instead of starting its own."""
    for leaf in jax.tree.leaves(out):
        leaf.copy_to_host_async()


def _fetch(plan: BatchPlan, out):
    """A launch's outputs as host arrays (for a projective plan, its
    points and its mask), waiting for the device where it has not
    finished, and for the copy ``_prefetch`` started."""
    if plan.kind == "projective":
        return np.asarray(out[0]), np.asarray(out[1])
    return np.asarray(out)


class GeometryServer:
    """Batched transform-serving engine over the PR 1 chain compiler.

        server = GeometryServer(backend="ref")
        tickets = [server.submit(chain_i, points_i) for ...]
        results = server.flush()        # one launch per plan bucket

    ``submit`` only records the request (host side, allocation-light);
    ``flush`` buckets, packs, and double-buffers the launches.  Results
    come back in submission order as host numpy arrays (serving results
    leave the device; per-request jax slicing would re-pay the dispatch
    overhead the batching removed), each with its request's original
    leading shape, matching ``chain_i.apply(points_i)`` under the module
    equality contract.
    """

    def __init__(self, *, backend: str | None = None,
                 min_len: int | None = None,
                 waste_cap: float | None = None,
                 max_points_per_launch: int | None = None,
                 fault_config: FaultConfig | None = None,
                 injector=None):
        self.backend = backend
        #: recovery policy (retry/backoff/ladder/q-overflow) -- see FaultConfig
        self.fault_config = fault_config or FaultConfig()
        #: optional seeded fault injector (serving.faults.FaultInjector);
        #: None in production -- the hooks below are no-ops without it
        self.injector = injector
        # size-grid knobs: explicit args win; unset knobs come from the
        # tuning cache when autotuning is enabled, else the historical
        # defaults (bucketing.MIN_LEN / WASTE_CAP) -- see bucketing.grid_for.
        # The explicit args are kept and re-resolved at every flush, so
        # toggling repro.autotune.set_enabled mid-life moves a server's
        # grid too (its plan caches are cleared by the same call).
        self._grid_args = (min_len, waste_cap)
        self.min_len, self.waste_cap, self.grid_source = bucketing.grid_for(
            dispatch.resolve(backend), min_len=min_len, waste_cap=waste_cap)
        #: shard cap: a bucket whose packed B*L exceeds this splits into
        #: multiple launches along the batch axis
        self.max_points_per_launch = max_points_per_launch
        #: this server's own typed registry: every server-scoped counter
        #: below is dual-written here and into the module aggregate
        #: (``stats``), so two servers in one process stop drifting into
        #: each other's numbers -- per-server truth lives here, and the
        #: module view is the EXPLICIT aggregate
        #: (``tests/test_obs.py::test_two_server_stats``); labeled
        #: bucket dimensions (plan kind, backend, dtype/qformat, size
        #: class) live here too
        self.metrics = obsm.MetricsRegistry("server")
        for k in _SERVER_KEYS:
            self.metrics.counter(k)
        self._pending: list[_Pending] = []
        self._ticket = 0
        self.last_report: list[BucketReport] = []
        #: every BucketReport this server ever produced (last_report is
        #: the latest flush's slice of it).  This is what makes the
        #: launch-accounting invariant hold ACROSS flush cycles --
        #: ``stats["launches"] == sum(r.launches for r in reports)`` for
        #: a single server whose lifetime starts at a stats reset
        #: (recovery launches included: recovery counts into the same
        #: BucketReport objects).  Cleared by ``reset_stats()``.
        self.reports: list[BucketReport] = []

    def _bump(self, name: str, n: int = 1) -> None:
        """Count one server-scoped event: this server's registry AND the
        module aggregate move together (dual-write keeps the historical
        reset semantics -- ``reset_stats()`` zeroes the aggregate without
        erasing any live server's own history)."""
        stats[name] += n
        self.metrics.counter(name).inc(n)

    # -- request intake ------------------------------------------------------

    def upload(self, points) -> Resident:
        """Put a point set on the device once; returns the handle that
        ``submit``, ``submit_scene`` and ``AsyncGeometryServer.submit_async``
        take in place of an array, for as many requests and flushes as the
        caller likes.  The points pass the intake boundary's checks
        (float32, (..., d), non-empty, finite) with its typed errors,
        and are copied: the handle holds a read-only host snapshot and
        the device buffer, so changing the caller's array afterwards
        changes no result.  Under a mesh set with ``jax.set_mesh`` the
        buffer is replicated on every device of it."""
        with obst.active().span("resident.upload"):
            shape = getattr(points, "shape", None)
            if not shape:
                raise errors.ShapeError(f"points are {shape}, not (n, d)")
            host = np.array(points, copy=True)
            errors.check_points(host, shape[-1])
            if host.dtype != np.float32:
                raise errors.DtypeError(
                    f"resident points are float32, got {host.dtype}")
            if self.fault_config.validate_finite \
                    and not np.isfinite(host).all():
                raise errors.NonFiniteError("points contain NaN/Inf")
            host.flags.writeable = False
            handle = Resident(host, _place_resident(host))
            self._bump("uploads")
        return handle

    def submit(self, chain: tc.TransformChain, points, *,
               qformat=None) -> int:
        """Queue one request; returns its ticket.  The next flush() returns
        results ordered by submission, one per queued request.

        ``qformat`` (a Qm.n name like "q8.7") routes the request through
        the fixed-point lane: it buckets under the format (not the
        submitted dtype), packs as int16 words (float points are
        quantised at pack time, int16 points are taken as already-Qm.n),
        and the result comes back dequantised float32 for float
        submissions, int16 for int16 ones.  Affine chains only --
        projective chains are rejected here, exactly as in
        ``TransformChain.apply``.

        ``points`` may be a ``Resident`` handle from ``upload`` in place
        of an array: nothing is copied or packed, and the request is
        one instance of the resident buffer.  The q-format lane refuses
        a handle (``DtypeError``).

        Submit is the isolation boundary: a malformed request (bad
        shape, empty point set, float64, NaN/Inf points or parameters, a
        q-format the error bound predicts would wrap under
        ``on_q_overflow="reject"``) raises a typed ``RequestError``
        carrying this request's ticket id HERE, before the request can
        reach a packed bucket and take its neighbours down with it."""
        return self.enqueue(self.validate(chain, points, qformat=qformat))

    def submit_scene(self, scene, name: str, points, *,
                     qformat=None) -> int:
        """Queue one request against a scene node: the chain is the
        node's world chain (``SceneGraph.world_chain``) and the fold is
        the scene's CACHED world fold, resolved through the shared
        ``FoldCache`` instead of refolded here -- thousands of requests
        attached under a common prefix fold that prefix once, not once
        per request.

        Everything downstream is the ordinary serving lane: the same
        (dim, kind, backend, dtype, size-class) bucket key, the same
        packed kernels, the same typed validation boundary, the same
        ``qformat=`` fixed-point routing (the cached fold quantises
        through ``quantize.quantize_fold`` at pack time exactly like a
        per-request fold).  The cached fold is bit-identical to
        ``chain.fold()`` by the carry-fold construction
        (``transform_chain.fold_carry_extend``), so results are bitwise
        equal to submitting ``scene.world_chain(name)`` through
        ``submit`` -- and to the per-request ``apply`` oracle under the
        engine's usual equality contract."""
        chain = scene.world_chain(name)
        fold = scene.world_fold(name) if len(chain) else None
        return self.enqueue(self.validate(chain, points, qformat=qformat,
                                          fold=fold))

    def validate(self, chain: tc.TransformChain, points, *,
                 qformat=None, fold=None) -> "_Pending":
        """The intake half of ``submit``: assign a ticket id, run the
        full validation boundary, and return the queue entry WITHOUT
        queueing it.  The continuous-batching front-end
        (``serving.async_engine``) uses this split -- it validates at
        arrival time but hands entries to ``enqueue`` only when its
        flush policy schedules them, so the two paths share one
        validation boundary and one ticket sequence.  Rejected
        submissions burn their id: the id in a typed error is never
        reused.

        ``fold`` injects precomputed folded parameters (the scene
        graph's cached world fold) in place of the ``chain.fold()`` this
        method would otherwise run; the injected fold MUST be
        bit-identical to ``chain.fold()`` -- the scene cache guarantees
        that by construction -- and passes through the same finiteness /
        q-overflow validation either way."""
        ticket = self._ticket
        self._ticket += 1
        trc = obst.active()
        sid = trc.begin("request.validate", ticket=ticket) \
            if trc.enabled else None
        try:
            p = self._validate(chain, points, qformat, ticket, fold=fold)
        except errors.RequestError as e:
            self._bump("rejected_requests")
            if sid is not None:
                trc.end(sid, outcome="rejected",
                        code=getattr(e, "code", type(e).__name__))
            raise
        if sid is not None:
            trc.end(sid, outcome="admitted",
                    kind=tc.plan_kind_of(chain.structure) if len(chain)
                    else "identity",
                    q=p.qformat.name if p.qformat is not None else None,
                    points=p.n)
        return p

    def enqueue(self, p: "_Pending") -> int:
        """Queue a ``validate``d entry for the next flush; returns its
        ticket.  ``submit`` is exactly ``enqueue(validate(...))``."""
        self._pending.append(p)
        return p.ticket

    def reset_stats(self) -> None:
        """Zero the module counters AND this server's accumulated report
        history together, so the cross-flush launch-accounting invariant
        (``stats["launches"] == sum(r.launches for r in self.reports)``,
        recovery launches included) restarts from a consistent origin.
        The module-level ``reset_stats`` alone cannot give that: it
        zeroes the global counters but leaves every server's report
        history counting launches from before the reset.  This server's
        own registry resets too (other servers' registries are theirs
        and stay untouched -- which is exactly why the aggregate and the
        per-server registries are separate objects)."""
        reset_stats()
        self.metrics.reset()
        self.reports = []
        self.last_report = []

    def _validate(self, chain: tc.TransformChain, points, qformat,
                  ticket: int, fold=None) -> _Pending:
        """Build the queue entry, raising the typed taxonomy on anything
        the packed lane could choke on later.  ``fold`` skips the
        ``chain.fold()`` recompute (scene-cached folds); every check
        downstream of the fold runs on the injected value unchanged."""
        if isinstance(points, Resident):
            return self._validate_resident(chain, points, qformat, ticket,
                                           fold)
        cfg = self.fault_config
        # a real copy, not a view: the queue must be immune to callers
        # mutating their buffer between submit and flush
        pts = np.array(points, copy=True)
        errors.check_points(pts, chain.dim, ticket=ticket)
        fmt = None
        dequant = False
        if qformat is not None:
            fmt = quantize.as_qformat(qformat)
            quantize.reject_projective(chain.is_projective)
            try:
                dequant = quantize.points_need_quantize(pts.dtype)
            except TypeError as e:
                raise errors.DtypeError(str(e), ticket=ticket) from None
        elif np.dtype(pts.dtype) != np.float32:
            raise errors.DtypeError(
                f"serving float lane is float32, got {np.dtype(pts.dtype)}; "
                f"cast before submit (or pass qformat= for int16)",
                ticket=ticket)
        if cfg.validate_finite and np.issubdtype(pts.dtype, np.floating) \
                and not np.isfinite(pts).all():
            raise errors.NonFiniteError(
                "points contain NaN/Inf", ticket=ticket)
        fold = self._fold(chain, fold, ticket)
        q_fallback = False
        requant = None
        if fmt is not None and fold is not None \
                and cfg.on_q_overflow != "wrap":
            kind = tc.plan_kind_of(chain.structure)
            x_vals = fmt.dequantize(pts) if not dequant else pts
            x_max = float(np.abs(x_vals).max())
            if cfg.on_q_overflow == "reject":
                quantize.ensure_fits(fold, kind, fmt, x_max, ticket=ticket)
            elif not quantize.fits(fold, kind, fmt, x_max):
                # degrade, don't wrap: reroute through the float32 lane.
                # int16 callers still get int16 back (requantised), so the
                # submit contract holds; only the arithmetic substrate
                # changed -- the same trade the backend ladder makes.
                self._bump("q_fallbacks")
                q_fallback = True
                if not dequant:
                    pts = fmt.dequantize(pts)
                    requant = fmt
                fmt = None
                dequant = False
        return _Pending(ticket, chain, pts, pts.size // chain.dim,
                        fold=fold, qformat=fmt, dequantize=dequant,
                        q_fallback=q_fallback, requant=requant)

    def _fold(self, chain: tc.TransformChain, fold, ticket: int):
        """The request's fold (``fold`` when injected), checked finite;
        None for an identity chain."""
        if not len(chain):
            return None
        if fold is None:
            fold = chain.fold()
        if self.fault_config.validate_finite:
            # projective folds legitimately carry +/-inf cull bounds
            parts = fold[:1] if chain.is_projective else fold
            if not all(np.isfinite(np.asarray(f)).all() for f in parts):
                raise errors.NonFiniteError(
                    "chain parameters fold to NaN/Inf", ticket=ticket)
        return fold

    def _validate_resident(self, chain: tc.TransformChain, handle: Resident,
                           qformat, ticket: int, fold) -> _Pending:
        """The queue entry of a request on a handle: the points were
        checked at ``upload``, so only the chain's dimension and the
        fold remain, and nothing is copied.  A projective chain is an
        instance of the device buffer; any other takes the host-array
        path on the read-only snapshot (an identity chain's result is a
        copy of it, the caller's to change)."""
        if qformat is not None:
            raise errors.DtypeError(
                "the q-format lane packs int16 words on the host; a "
                "resident handle holds float32 device words", ticket=ticket)
        errors.check_points(handle.host, chain.dim, ticket=ticket)
        points = handle.host if len(chain) else np.array(handle.host)
        return _Pending(ticket, chain, points, handle.n,
                        fold=self._fold(chain, fold, ticket),
                        resident=handle if chain.is_projective else None)

    def serve(self, items, *, qformat=None) -> list:
        """Convenience: submit an iterable of (chain, points), then flush."""
        for chain, points in items:
            self.submit(chain, points, qformat=qformat)
        return self.flush()

    @property
    def pending(self) -> int:
        """Requests submitted but not yet flushed."""
        return len(self._pending)

    # -- execution -----------------------------------------------------------

    def _bucket_key(self, p: _Pending, backend: str) -> tuple:
        """``(dim, kind, backend, dtype, padded length)``: requests that
        run one plan body on operands of one shape share a launch."""
        if p.resident is not None:
            # the handle fixes the length: it takes the size class's slot
            return (*plan_identity(p.chain), backend, "resident", p.resident)
        lpad = bucketing.padded_length(p.n, min_len=self.min_len,
                                       waste_cap=self.waste_cap)
        # fixed-point requests bucket under the FORMAT, not the submitted
        # dtype: a float-submitted and an int16-submitted q8.7 request
        # pack into the same int16 batch (only unpack differs)
        dt = p.qformat.name if p.qformat is not None \
            else np.dtype(p.points.dtype).str
        return (*plan_identity(p.chain), backend, dt, lpad)

    def _pack(self, reqs: list[_Pending], lpad: int, plan: BatchPlan):
        """Pack a bucket: (B, lpad, d) zero-padded points + each
        request's host-folded parameters in one row array (the same numpy
        fold ``TransformChain.apply`` runs, so the folds are bit-identical).
        Fixed-point buckets pack int16 Qm.n words -- float submissions
        quantise here, and each fold quantises through the same
        ``quantize.quantize_fold`` the chain compiler's q lane uses.
        Folds come precomputed from submit (``_Pending.fold``), so a
        recovery re-pack is bit-identical to the original pack."""
        dim = plan.dim
        if plan.qformat is not None:
            fmt = quantize.as_qformat(plan.qformat)
            packed = np.zeros((len(reqs), lpad, dim), np.int16)
            for i, r in enumerate(reqs):
                pts = r.points.reshape(-1, dim)
                packed[i, :r.n] = fmt.quantize(pts) if r.dequantize else pts
            folds = [quantize.quantize_fold(r.fold, plan.kind, fmt)
                     for r in reqs]
        else:
            dtype = reqs[0].points.dtype
            packed = np.zeros((len(reqs), lpad, dim), dtype)
            for i, r in enumerate(reqs):
                packed[i, :r.n] = r.points.reshape(-1, dim)
            folds = [r.fold for r in reqs]
        return _fold_row(folds, packed.dtype), packed

    @staticmethod
    def _bind(reqs: list[_Pending]):
        """Bind a resident bucket: its folds in one row array, as
        ``_pack`` stages them; the points are the handle's device
        buffer, so nothing else is built."""
        handle = reqs[0].resident
        return _fold_row([r.fold for r in reqs], handle.dtype), handle

    def _assemble(self, reqs: list[_Pending], lpad: int, plan: BatchPlan):
        """A bucket's launch operands: bound on a resident bucket,
        packed otherwise."""
        return self._bind(reqs) if plan.instanced \
            else self._pack(reqs, lpad, plan)

    def _chunks(self, n_reqs: int, lpad: int) -> list[slice]:
        """Shard an oversized bucket along the batch axis."""
        cap = self.max_points_per_launch
        if cap is None or n_reqs * lpad <= cap:
            return [slice(0, n_reqs)]
        rows = max(1, cap // lpad)
        return [slice(i, min(i + rows, n_reqs))
                for i in range(0, n_reqs, rows)]

    @staticmethod
    def _stage(rows, packed):
        """Host->device staging for one launch (the set-1 DMA).  When a
        device mesh is set (``jax.set_mesh``) the packed batch AND its
        fold row, one row a request, are placed sharded over the mesh's
        fsdp axes, so one launch spans the mesh and each device runs the
        kernel on its own rows (``_per_device``).  The batch pads with
        zero rows to a multiple of the fsdp width; unpack reads only the
        real rows.
        On a single device the arrays pass straight to the jitted plan,
        whose C++ argument path does the transfer -- an explicit
        ``device_put`` there is measurably pure python dispatch overhead
        (it dominated the flush profile).  A resident bucket stages its
        folds alone: its points are the handle's buffer, already on the
        device (replicated over the mesh by ``upload``)."""
        points = packed.device if isinstance(packed, Resident) else packed
        mesh = jax.sharding.get_mesh()
        if mesh.empty or mesh.size == 1:
            return (rows, points)
        fsdp, _ = sharding.axis_names(mesh)
        width = math.prod(mesh.shape[a] for a in fsdp)
        pad = -len(rows) % width

        def place(x):
            x = np.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))
            return jax.device_put(x, NamedSharding(mesh, P(fsdp)))
        if isinstance(packed, Resident):
            return (place(rows),
                    jax.device_put(points, NamedSharding(mesh, P())))
        return (place(rows), place(packed))

    # -- fault-injection hooks (no-ops without an injector) ------------------

    def _check_injected(self, reqs: list, rung_index: int,
                        attempt: int) -> None:
        """Raise ``InjectedFault`` when the seeded injector scheduled a
        launch failure for this (request group, rung, attempt)."""
        if self.injector is not None:
            self.injector.before_launch(
                tuple(r.ticket for r in reqs), rung_index, attempt)

    def _stage_attempt(self, plan: BatchPlan, rows, packed, reqs: list,
                       rung_index: int, attempt: int):
        """Staging with the corruption hook: the injector may flip words
        in the packed operand buffer on its way to the device.  Only
        float affine buckets are corruptible -- their outputs are
        finite-validatable; projective guarded divides and int16 words
        have no such invariant to check against."""
        inj = self.injector
        if inj is not None and plan.qformat is None \
                and plan.kind != "projective":
            packed = inj.corrupt_staging(
                packed, tuple(r.ticket for r in reqs), rung_index, attempt)
        return self._stage(rows, packed)

    def _count_launch(self, plan: BatchPlan, lpad: int, reqs: list,
                      rows: np.ndarray, packed: np.ndarray | Resident,
                      report: BucketReport, rung: int = 0, attempt: int = 0,
                      track: str | None = None) -> None:
        """Bookkeeping for one DISPATCHED launch (called after the
        injector gate: a blocked attempt never reached the device).
        This is the ONE place ``stats["launches"]`` moves, and the one
        place launch trace events come from, so the span-count invariant
        ``count("launch") == stats["launches"]`` holds by construction
        (``tests/test_obs.py`` pins it)."""
        # the _q suffix keeps the lanes separately countable, same
        # discipline as TransformChain._record_fused
        nbytes = opcount.packed_chain_bytes(
            len(reqs), lpad, plan.dim,
            itemsize=packed.dtype.itemsize, kind=plan.kind)
        opcount.record(
            f"serve_bucket_{plan.kind}{'_q' if plan.qformat else ''}",
            nbytes)
        self._bump("launches")
        # the folds, and the points unless they are the handle's own
        # buffer (recovery stages a rebuilt one)
        own = packed is reqs[0].resident
        self._bump("upload_bytes", rows.nbytes + (
            0 if own else packed.nbytes))
        self._bump("host_operands", 1 if own else 2)
        report.launches += 1
        trc = obst.active()
        if trc.enabled:
            # per-attempt annotation: backend rung, the launch's shape and
            # the opcount HBM bytes it moves -- what is at hand here; the
            # profiler derives the cost model's prediction from these
            # when it folds the stream, off the dispatch path
            trc.instant(
                "launch", tickets=tuple(r.ticket for r in reqs),
                track=track, backend=plan.backend, kind=plan.kind,
                q=plan.qformat, rung=rung, attempt=attempt,
                rows=len(reqs), lpad=lpad, dim=plan.dim,
                itemsize=packed.dtype.itemsize, hbm_bytes=nbytes)

    @staticmethod
    def _call(plan: BatchPlan, dev: tuple, track: str | None):
        """The jitted plan call of one counted launch; it transfers the
        host operands and enqueues the kernel.  Under a tracer it is the
        ``launch.call`` span, one per ``_count_launch``, marked
        ``traced=True`` when the call traced a new shape."""
        trc = obst.active()
        if not trc.enabled:
            return plan.fn(*dev)
        traces = stats["traces"]
        sid = trc.begin("launch.call", track=track)
        try:
            return plan.fn(*dev)
        finally:
            trc.end(sid, traced=stats["traces"] != traces)

    # -- flush: dispatch, unpack, recover ------------------------------------

    def flush(self) -> list:
        """Execute all pending requests; results in submission order.

        Failure containment: a launch that raises (at dispatch or at
        materialisation -- jax's async dispatch can surface device errors
        either place) or whose output fails the corruption check is set
        aside; every OTHER launch completes normally, then the failed
        groups walk the recovery ladder (``_recover``).  A request whose
        recovery exhausts resolves to a typed ``LaunchError`` in its
        result slot -- callers check with ``serving.is_error`` -- so the
        returned list always lines up 1:1 with submissions."""
        pending, self._pending = self._pending, []
        backend = dispatch.resolve(self.backend)
        trc = obst.active()
        fsid = trc.begin("flush", requests=len(pending)) \
            if trc.enabled else None
        # grid lookup keyed by this flush's traffic scale (largest request
        # length): grids are tuned per scale, so the lookup must say which
        # scale is being served
        self.min_len, self.waste_cap, self.grid_source = bucketing.grid_for(
            backend, min_len=self._grid_args[0],
            waste_cap=self._grid_args[1],
            n=max((p.n for p in pending), default=0))
        results: dict[int, typing.Any] = {}
        buckets: dict[tuple, list[_Pending]] = {}
        for p in pending:
            if len(p.chain) == 0:
                results[p.ticket] = p.points   # identity passthrough
                if trc.enabled:
                    trc.instant("request.resolve", ticket=p.ticket,
                                outcome="identity")
            else:                              # (empty sets reject at submit)
                buckets.setdefault(self._bucket_key(p, backend), []).append(p)

        # Build the launch list: one _Launch per shard.
        launches: list[_Launch] = []
        self.last_report = []
        for (dim, kind, bk, _dt, size), reqs in buckets.items():
            ident = (dim, kind)
            qname = reqs[0].qformat.name if reqs[0].qformat is not None \
                else None
            resident = reqs[0].resident
            lpad = size if resident is None else resident.lpad
            track = _bucket_track(ident, bk, _dt, lpad)
            bsid = trc.begin("bucket.assemble", track=track,
                             tickets=tuple(r.ticket for r in reqs),
                             rows=len(reqs), lpad=lpad) \
                if trc.enabled else None
            plan = get_batch_plan(ident, bk, qname,
                                  instanced=resident is not None)
            if trc.enabled:
                psid = trc.begin(
                    "bucket.bind" if plan.instanced else "bucket.pack",
                    track=track, rows=len(reqs), lpad=lpad, q=plan.qformat)
                rows, packed = self._assemble(reqs, lpad, plan)
                trc.end(psid)
            else:
                rows, packed = self._assemble(reqs, lpad, plan)
            chunks = self._chunks(len(reqs), lpad)
            payload = sum(r.n for r in reqs)
            report = BucketReport(
                structure=_plan_tag(ident), kind=plan.kind,
                lpad=lpad, requests=len(reqs), payload_points=payload,
                padded_points=len(reqs) * lpad, backend=bk,
                final_backend=bk,
                q_fallback_requests=sum(r.q_fallback for r in reqs))
            for sl in chunks:
                launches.append(_Launch(
                    ident=ident, qname=qname, backend=bk, lpad=lpad,
                    plan=plan,
                    rows=rows[sl],
                    packed=packed if resident is not None else packed[sl],
                    reqs=reqs[sl], report=report, track=track))
            self.last_report.append(report)
            self.reports.append(report)
            self._bump("buckets")
            structures = len({r.chain.structure for r in reqs})
            self._bump("bucket_structures", structures)
            self._bump("shards",
                       len(chunks) - 1 if len(chunks) > 1 else 0)
            self._bump("payload_points", payload)
            self._bump("padded_points", len(reqs) * lpad)
            if resident is not None:
                self._bump("resident_requests", len(reqs))
            # the labeled serving dimensions (plan kind, backend,
            # dtype/qformat, padded size class) -- per-server only: the
            # aggregate view stays the flat counter set it always was
            self.metrics.counter(
                "bucket_requests",
                labels=("kind", "backend", "dtype", "size_class"),
            ).labels(kind=plan.kind, backend=bk, dtype=_dt,
                     size_class=lpad).inc(len(reqs))
            if bsid is not None:
                trc.end(bsid, kind=plan.kind, shards=len(chunks),
                        payload_points=payload, structures=structures)

        # Phase 1 -- optimistic double-buffered dispatch (frame-buffer
        # set 0 / set 1): stage the first launch, then keep one launch
        # computing (set 0) while the next launch's host->device transfer
        # streams (set 1).  Nothing blocks until unpack -- jax's async
        # dispatch provides the overlap; this loop just orders the work so
        # it CAN overlap.  Each dispatched launch's copy back to the host
        # starts as soon as its call returns, so it runs under the later
        # launches' calls instead of one after another in unpack.  A
        # launch that raises is recorded and skipped, never aborting its
        # siblings.
        def _stage_first(L: _Launch):
            try:
                return self._stage_attempt(L.plan, L.rows, L.packed,
                                           L.reqs, 0, 0)
            except Exception as e:       # staging failure is a launch failure
                return _FailedLaunch(e)

        dsid = trc.begin("flush.dispatch", launches=len(launches)) \
            if trc.enabled else None
        outs: list = []
        prefetched = 0
        staged = _stage_first(launches[0]) if launches else None
        for k, L in enumerate(launches):
            try:
                if isinstance(staged, _FailedLaunch):
                    raise staged.err
                self._check_injected(L.reqs, 0, 0)
                self._count_launch(L.plan, L.lpad, L.reqs, L.rows,
                                   L.packed, L.report, rung=0, attempt=0,
                                   track=L.track)
                out = self._call(L.plan, staged, L.track)       # set 0
                _prefetch(out)
                outs.append(out)
                prefetched += 1
            except Exception as e:
                outs.append(_FailedLaunch(e))
            if k + 1 < len(launches):
                staged = _stage_first(launches[k + 1])          # async: set 1
        self._bump("prefetches", prefetched)
        if dsid is not None:
            trc.end(dsid, prefetched=prefetched)

        # Phase 2 -- unpack with capture: materialisation is where async
        # device errors (and injected corruption) actually surface, so
        # each launch unpacks under its own try.
        usid = trc.begin("flush.unpack") if trc.enabled else None
        failed: list[tuple[_Launch, Exception]] = []
        for L, out in zip(launches, outs):
            lsid = trc.begin("unpack", track=L.track,
                             tickets=tuple(r.ticket for r in L.reqs)) \
                if trc.enabled else None
            if isinstance(out, _FailedLaunch):
                self._bump("launch_failures")
                failed.append((L, out.err))
                if lsid is not None:
                    trc.end(lsid, outcome="failed",
                            error=type(out.err).__name__)
                continue
            try:
                self._unpack(L.plan, L.reqs, out, results, L.track)
            except Exception as e:
                self._bump("launch_failures")
                failed.append((L, e))
                if lsid is not None:
                    trc.end(lsid, outcome="failed", error=type(e).__name__)
            else:
                if lsid is not None:
                    trc.end(lsid, outcome="ok")
        if usid is not None:
            trc.end(usid, failed=len(failed))

        # Phase 3 -- sequential recovery of the failed groups (the rare
        # path; overlap no longer matters, determinism and containment do).
        if failed:
            rsid = trc.begin("flush.recover", groups=len(failed)) \
                if trc.enabled else None
            for L, err in failed:
                self._recover(L, list(L.reqs), err, results)
            if rsid is not None:
                trc.end(rsid)

        self._bump("requests", len(pending))
        if fsid is not None:
            trc.end(fsid, buckets=len(buckets), launches=len(launches))
        return [results[p.ticket] for p in pending]

    def _unpack(self, plan: BatchPlan, reqs: list, out, results: dict,
                track: str | None) -> None:
        """Unpack one launch: one device->host sync, then numpy slicing --
        per-request unpack must not become per-request dispatch again (a
        jax slice per request would re-pay the launch overhead the
        batching just removed).  Under a tracer its three parts are
        spans on the launch's track: ``unpack.wait`` (the host blocked
        on the device), ``unpack.fetch`` (the device->host transfer of
        the ready outputs, or in ``flush`` the rest of the copy that
        phase 1 started) and ``unpack.copy`` (``_resolve``)."""
        trc = obst.active()
        if not trc.enabled:
            self._resolve(plan, reqs, _fetch(plan, out), results)
            return
        with trc.span("unpack.wait", track=track):
            jax.block_until_ready(out)
        with trc.span("unpack.fetch", track=track):
            host = _fetch(plan, out)
        with trc.span("unpack.copy", track=track):
            self._resolve(plan, reqs, host, results)

    def _resolve(self, plan: BatchPlan, reqs: list, host,
                 results: dict) -> None:
        """Each request's result from one launch's host output.  Each is
        a payload-sized COPY: a view would be read-only and would pin
        the whole padded batch buffer for as long as the caller keeps
        any one result.  Projective launches return (points, mask);
        their results carry the per-point cull mask as
        ``Projected.mask``.  An instanced launch's rows are in the
        resident layout, each a flat buffer of point words with the mask
        repeated over each point's d lanes, so a request's mask takes
        every d-th word."""
        trc = obst.active()
        if plan.kind == "projective":
            host, mask = host
            words = host.reshape(len(host), -1)
            mask = mask.reshape(len(mask), -1)
            step = plan.dim if plan.instanced else 1
            for i, r in enumerate(reqs):
                results[r.ticket] = _projected(
                    np.array(words[i, :r.points.size]
                             .reshape(r.points.shape)),
                    np.array(mask[i, :r.n * step:step]
                             .reshape(r.points.shape[:-1])))
                if trc.enabled:
                    trc.instant("request.resolve", ticket=r.ticket,
                                outcome="ok")
            return
        if self.fault_config.validate_outputs and plan.qformat is None \
                and not np.isfinite(host).all():
            # inputs validated finite at submit, so a non-finite output
            # means the staged buffer (or the launch) corrupted in flight;
            # discard wholesale and let recovery re-pack from the pristine
            # host copies
            raise serrors.CorruptionError(
                f"non-finite values in {plan.kind} launch output "
                f"(B={len(reqs)})")
        fmt = quantize.as_qformat(plan.qformat) \
            if plan.qformat is not None else None
        for i, r in enumerate(reqs):
            res = np.array(host[i, :r.n].reshape(r.points.shape))
            if fmt is not None and r.dequantize:
                res = fmt.dequantize(res)
            elif r.requant is not None:
                # q->float fallback for an int16 caller: requantise so the
                # submit contract (int16 in -> int16 out) holds
                res = r.requant.quantize(res)
            results[r.ticket] = res
            if trc.enabled:
                trc.instant("request.resolve", ticket=r.ticket,
                            outcome="ok")

    def _recover(self, L: _Launch, reqs: list, err: Exception,
                 results: dict, depth: int = 0) -> None:
        """Walk the recovery ladder for one failed launch group:

          1. retry the same rung, bounded exponential backoff between
             attempts (transient faults);
          2. degrade the backend along ``dispatch.fallback_ladder``
             (substrate faults: each rung computes the same function);
          3. bisect -- split the group in half and recover each half with
             a fresh ladder (poison isolation in O(log B) launches).

        A singleton that exhausts every rung resolves to a typed
        ``LaunchError`` carrying its ticket: the request fails alone,
        with a name, and nothing is silently dropped."""
        cfg = self.fault_config
        rungs = dispatch.fallback_ladder(L.backend)
        trc = obst.active()
        rtrack = f"recovery:{L.track}" if L.track else "recovery"
        gsid = trc.begin("recover", track=rtrack,
                         tickets=tuple(r.ticket for r in reqs),
                         depth=depth, rows=len(reqs),
                         error=type(err).__name__) \
            if trc.enabled else None
        # at depth 0 the optimistic dispatch already burned attempt 0 of
        # rung 0; bisected halves start their ladder fresh
        n_failures = 1 if depth == 0 else 0
        for ri, rung in enumerate(rungs):
            plan = L.plan if ri == 0 \
                else get_batch_plan(L.ident, rung, L.qname,
                                    instanced=L.plan.instanced)
            start = n_failures if ri == 0 and depth == 0 else 0
            for attempt in range(start, cfg.max_launch_attempts):
                if n_failures:
                    time.sleep(min(cfg.backoff_cap_s, cfg.backoff_base_s *
                                   cfg.backoff_factor ** (n_failures - 1)))
                if attempt > 0:
                    self._bump("retries")
                    L.report.retries += 1
                asid = trc.begin("recover.attempt", track=rtrack,
                                 rung=rung, attempt=attempt) \
                    if trc.enabled else None
                try:
                    rows, packed = self._assemble(reqs, L.lpad, plan)
                    if plan.instanced:
                        # rebuild the buffer from the host snapshot: the
                        # device copy may be what failed
                        packed = Resident(packed.host,
                                          _place_resident(packed.host))
                    dev = self._stage_attempt(plan, rows, packed, reqs,
                                              ri, attempt)
                    self._check_injected(reqs, ri, attempt)
                    self._count_launch(plan, L.lpad, reqs, rows, packed,
                                       L.report, rung=ri, attempt=attempt,
                                       track=rtrack)
                    out = self._call(plan, dev, rtrack)
                    self._unpack(plan, reqs, out, results, rtrack)
                except Exception as e:
                    self._bump("launch_failures")
                    err = e
                    n_failures += 1
                    if asid is not None:
                        trc.end(asid, outcome="failed",
                                error=type(e).__name__)
                    continue
                if asid is not None:
                    trc.end(asid, outcome="ok")
                if ri > 0:
                    self._bump("backend_fallbacks")
                    L.report.backend_fallbacks += 1
                    L.report.final_backend = rung
                self._bump("recovered_requests", len(reqs))
                L.report.recovered_requests += len(reqs)
                if gsid is not None:
                    trc.end(gsid, outcome="recovered", rung=rung)
                return
        if len(reqs) > 1:
            self._bump("bisections")
            L.report.bisections += 1
            if trc.enabled:
                trc.instant("recover.bisect", track=rtrack,
                            tickets=tuple(r.ticket for r in reqs),
                            depth=depth, rows=len(reqs))
            if gsid is not None:
                trc.end(gsid, outcome="bisected")
            mid = len(reqs) // 2
            self._recover(L, reqs[:mid], err, results, depth + 1)
            self._recover(L, reqs[mid:], err, results, depth + 1)
            return
        r = reqs[0]
        resolution = errors.LaunchError(
            f"launch failed on every rung of {rungs} "
            f"(x{cfg.max_launch_attempts} attempts each): {err}",
            ticket=r.ticket)
        if trc.enabled and trc.recorder is not None:
            # the event window that led here rides on the resolution --
            # a chaos failure is debuggable from the error object alone
            resolution.flight = trc.recorder.snapshot()
        results[r.ticket] = resolution
        self._bump("failed_requests")
        L.report.failed_requests += 1
        if trc.enabled:
            trc.instant("request.resolve", ticket=r.ticket,
                        outcome="launch-error")
        if gsid is not None:
            trc.end(gsid, outcome="failed")
