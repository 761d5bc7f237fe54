"""Analytic per-kernel cost models: the paper's methodology as a pruner.

The source paper's loop is map -> predict analytically -> validate on the
emulator.  This module is the "predict" step for the TPU mapping: every
tunable kernel gets a closed-form cost built from the same byte accounting
``repro.kernels.opcount`` records at runtime (HBM bytes under the
memory-bound model), plus FLOPs and a per-launch / per-grid-step overhead
term.  The tuner uses these predictions to PRUNE the candidate space before
spending wall-clock on the empirical timer -- and because the byte formulas
are shared with ``opcount``, the predictions are cross-checkable against
what the runtime actually records (``tests/test_autotune.py``).

Two validation hooks tie the model back to the paper:

  * ``morphosys_cycles`` -- closed-form cycle counts for the paper's
    translation/scaling listings (Tables 1-2 structure + the fitted DMA
    wait model), exact against both the published Table 5 numbers and the
    ``core.morphosys`` emulator for the 8- and 64-element cases;
  * ``perf_rows`` -- the predictions rendered through the same
    ``core.analysis.PerfRow`` derivation the paper tables use, so
    predicted numbers print in paper-table format next to emulator rows.
"""
from __future__ import annotations

import dataclasses
import math
import typing

from repro.autotune.cache import DEFAULTS, KernelConfig, merge
from repro.core import analysis
from repro.core.morphosys.isa import dma_wait
from repro.core.morphosys.rc_array import N as RC_N

#: fixed per-launch dispatch overhead (python call + XLA arg staging +
#: result sync share), measured on the CPU ref path the tuner times; the
#: absolute value matters less than its ratio to the byte term -- it is
#: what makes "fewer launches" beat "fewer padded bytes" at small sizes.
LAUNCH_OVERHEAD_US = 30.0
#: per-grid-step overhead inside one launch (block bookkeeping); small,
#: but it is the term that rewards larger blocks until VMEM runs out.
STEP_OVERHEAD_US = 0.02
#: effective streaming bandwidth for the predicted-time denominator.  The
#: empirical timer runs wherever it runs; the model only needs candidate
#: ORDERING to be right, so one conservative CPU-class figure is used for
#: every backend (the TPU projection in benchmarks uses roofline.PEAKS).
MODEL_BW = 20e9
#: VMEM feasibility budget per core (v5e-class); candidates whose working
#: set exceeds this are rejected before timing.
VMEM_BYTES = 16 * 2 ** 20


@dataclasses.dataclass(frozen=True)
class CostEstimate:
    """One candidate's analytic cost.  ``predicted_us`` is the pruning
    score: launch overhead + grid-step overhead + streaming time."""
    kernel: str
    hbm_bytes: int
    flops: int
    launches: int
    grid_steps: int
    feasible: bool = True

    @property
    def predicted_us(self) -> float:
        if not self.feasible:
            return math.inf
        return (self.launches * LAUNCH_OVERHEAD_US
                + self.grid_steps * STEP_OVERHEAD_US
                + self.hbm_bytes / MODEL_BW * 1e6)


def _cfg(kernel: str, config: KernelConfig | None) -> KernelConfig:
    base = DEFAULTS.get(kernel, KernelConfig(kernel))
    return base if config is None else merge(base, config)


def _cdiv(a: int, b: int) -> int:
    return (a + b - 1) // b


# -- chain kernels (the paper's one-pass composite) ---------------------------

#: plan kind -> (single-chain kernel, batched kernel).  The ``_q`` kinds
#: are the int16 fixed-point lane: same staging maths, 2-byte words.
_CHAIN_KERNELS = {"diag": ("chain_diag", "chain_diag_batch"),
                  "matrix": ("chain_apply", "chain_apply_batch"),
                  "projective": ("chain_project", "chain_project_batch"),
                  "diag_q": ("chain_diag_q", "chain_diag_batch_q"),
                  "matrix_q": ("chain_apply_q", "chain_apply_batch_q")}


def _base_kind(kind: str) -> str:
    """The plan-kind lattice rung of a (possibly fixed-point) cost kind:
    byte passes and parameter-word counts come from the ONE ``opcount``
    table keyed by the base kind; the ``_q`` suffix only halves the word
    size."""
    return kind[:-2] if kind.endswith("_q") else kind


def _kind_itemsize(kind: str, itemsize: int | None) -> int:
    return itemsize if itemsize is not None else \
        (2 if kind.endswith("_q") else 4)


def chain_param_bytes(d: int, kind: str, itemsize: int = 4) -> int:
    """Composed-parameter bytes of one folded chain: (d,d)+(d,) words for a
    matrix plan, (d,)+(d,) for a diagonal plan, (d+1)^2 + 2d (homogeneous
    H plus cull bounds) for a projective plan -- delegating to the ONE
    table in ``opcount`` that ``TransformChain.apply`` and the serving
    engine also record from."""
    from repro.kernels import opcount          # late: keep imports one-way
    return opcount.chain_param_words(d, _base_kind(kind)) * itemsize


def _chain_flops_per_point(d: int, kind: str) -> int:
    """VPU work per point: one MAC for diag lanes, 2d-1 rolled MACs for
    matrix lanes, and for projective lanes a second MAC set (the
    homogeneous w), the divide, and the cull compares.  The fixed-point
    kinds run the same MAC schedule (in int32)."""
    kind = _base_kind(kind)
    if kind == "diag":
        return 2 * d
    if kind == "matrix":
        return 2 * (2 * d - 1) * d
    return (4 * (2 * d - 1) + 4) * d


def _chain_passes(kind: str) -> int:
    from repro.kernels import opcount          # late: keep imports one-way
    return opcount.chain_passes(_base_kind(kind))


def chain_cost(n_points: int, d: int, kind: str,
               config: KernelConfig | None = None, *,
               itemsize: int | None = None) -> CostEstimate:
    """One fused single-chain launch over (N, d) points: the point buffer
    moves once in, once out (plus the mask pass for projective plans),
    plus the O(1) composed parameters.  ``itemsize`` defaults by kind: 4
    bytes on the float kinds, 2 on the ``_q`` (int16 Qm.n) kinds -- the
    halved-byte prediction the fixed-point benchmark validates."""
    from repro.kernels import opcount, util  # late: keep imports one-way
    kernel = _CHAIN_KERNELS[kind][0]
    itemsize = _kind_itemsize(kind, itemsize)
    cfg = _cfg(kernel, config)
    nbytes = opcount.fused_chain_bytes(n_points, d, itemsize=itemsize,
                                       kind=_base_kind(kind))
    # lane layout: w lanes per row, block_rows rows per grid step -- the
    # same staging math the kernels run (kernels.util is the one source)
    w = util.chain_width(d, target=cfg.lane_target or 512)
    rows = _cdiv(n_points * d, w)
    steps = _cdiv(rows, cfg.block_rows or 256)
    flops = n_points * _chain_flops_per_point(d, kind)
    block_bytes = 2 * (cfg.block_rows or 256) * w * itemsize
    return CostEstimate(kernel, nbytes, flops, launches=1, grid_steps=steps,
                        feasible=block_bytes <= VMEM_BYTES)


def packed_chain_cost(bsz: int, lpad: int, d: int, kind: str,
                      config: KernelConfig | None = None, *,
                      itemsize: int | None = None) -> CostEstimate:
    """One packed-bucket launch (B requests padded to L points): the same
    byte count ``opcount.packed_chain_bytes`` records per serving launch.
    ``itemsize`` defaults by kind (2-byte words on the ``_q`` kinds)."""
    from repro.kernels import opcount, util  # late: keep imports one-way
    kernel = _CHAIN_KERNELS[kind][1]
    itemsize = _kind_itemsize(kind, itemsize)
    cfg = _cfg(kernel, config)
    nbytes = opcount.packed_chain_bytes(bsz, lpad, d, itemsize=itemsize,
                                        kind=_base_kind(kind))
    g = util.lane_group(d)
    wr = max(1, _cdiv(lpad * d, g)) * g
    bm = cfg.block_rows or util.packed_budget_rows(wr, itemsize)
    steps = _cdiv(bsz, max(1, bm))
    flops = bsz * lpad * _chain_flops_per_point(d, kind)
    block_bytes = 2 * max(1, bm) * wr * itemsize
    return CostEstimate(kernel, nbytes, flops, launches=1, grid_steps=steps,
                        feasible=block_bytes <= VMEM_BYTES)


@dataclasses.dataclass(frozen=True)
class LaunchPrediction:
    """The cost model's view of ONE dispatched serving launch, computed
    by the profiler (``obs.profile``) from the shape the launch's trace
    instant carries, so it can fold predicted-vs-observed ratios out of
    the span stream without the dispatch path running the model.

    ``hbm_bytes`` and ``flops`` come from ``packed_chain_cost``, whose
    byte formula IS ``opcount.packed_chain_bytes`` -- the same number the
    engine records as the launch's observed ``hbm_bytes`` -- so the
    byte ratio is exactly 1.0 by construction on every backend, and any
    drift between the two is a real accounting bug, not model error.
    ``m1_cycles`` is the paper-methodology projection
    (``m1_chain_cycles``): what this launch would cost on the M1 array.
    """
    kernel: str
    hbm_bytes: int
    flops: int
    m1_cycles: int


def predict_launch(kind: str, bsz: int, lpad: int, d: int, *,
                   qformat: str | None = None,
                   itemsize: int | None = None) -> LaunchPrediction:
    """Predict one packed-bucket launch (B requests padded to L points)
    of a serving plan: the per-launch prediction API the profiler calls
    when it folds a launch instant.  ``kind`` is the plan kind (``diag`` / ``matrix`` /
    ``projective``); a non-None ``qformat`` selects the int16 ``_q``
    cost kind (2-byte words), mirroring how the engine's plans carry
    the format separately from the kind."""
    cost_kind = kind if kind.endswith("_q") or qformat is None \
        else kind + "_q"
    est = packed_chain_cost(bsz, lpad, d, cost_kind, itemsize=itemsize)
    return LaunchPrediction(kernel=est.kernel, hbm_bytes=est.hbm_bytes,
                            flops=est.flops,
                            m1_cycles=m1_chain_cycles(cost_kind,
                                                      bsz * lpad, d))


# -- matmul / rmsnorm ---------------------------------------------------------

def matmul_cost(m: int, k: int, n: int, config: KernelConfig | None = None,
                *, itemsize: int = 2) -> CostEstimate:
    """Tiled matmul: operands move once (accumulation lives in VMEM
    scratch), 2mkn FLOPs, grid steps follow the (bm, bn, bk) tile; the
    working set 2*(bm*bk + bk*bn)*itemsize + bm*bn*4 must fit VMEM."""
    cfg = _cfg("matmul", config)
    bm, bn, bk = cfg.bm or 128, cfg.bn or 128, cfg.bk or 512
    nbytes = (m * k + k * n + m * n) * itemsize
    steps = _cdiv(m, bm) * _cdiv(n, bn) * _cdiv(k, bk)
    working = 2 * (bm * bk + bk * bn) * itemsize + bm * bn * 4
    return CostEstimate("matmul", nbytes, 2 * m * k * n, launches=1,
                        grid_steps=steps, feasible=working <= VMEM_BYTES)


def rmsnorm_cost(m: int, n: int, config: KernelConfig | None = None, *,
                 itemsize: int = 4) -> CostEstimate:
    """Fused rmsnorm: one read + one write of (M, N) plus the (N,) gain;
    rows blocked by ``block_rows`` (trailing dim never splits -- the mean
    needs the whole row)."""
    cfg = _cfg("rmsnorm", config)
    bm = cfg.block_rows or 256
    nbytes = 2 * m * n * itemsize + n * itemsize
    working = 2 * bm * n * itemsize
    return CostEstimate("rmsnorm", nbytes, 4 * m * n, launches=1,
                        grid_steps=_cdiv(m, bm),
                        feasible=working <= VMEM_BYTES)


# -- serving size grid --------------------------------------------------------

def grid_cost(requests: typing.Sequence[tuple[typing.Hashable, str, int, int]],
              min_len: int, waste_cap: float, *,
              itemsize: int = 4) -> CostEstimate:
    """Analytic cost of serving one workload under a candidate size grid.

    ``requests`` is ``(plan_key, kind, d, n_points)`` per request --
    the shape of the workload, no point data needed.  The model replays
    the engine's bucketing ((plan key, padded length) -> one launch) and
    charges each bucket its packed byte volume plus the per-launch
    overhead: exactly the trade the grid knobs steer (a coarser grid means
    fewer launches but more padded bytes).
    """
    from repro.kernels import opcount
    from repro.serving import bucketing
    buckets: dict[tuple, list[tuple[str, int, int]]] = {}
    for key, kind, d, n in requests:
        if n <= 0:
            continue
        lpad = bucketing.padded_length(n, min_len=min_len,
                                       waste_cap=waste_cap)
        buckets.setdefault((key, lpad), []).append((kind, d, n))
    nbytes = 0
    flops = 0
    for (_key, lpad), reqs in buckets.items():
        kind, d, _ = reqs[0]
        nbytes += opcount.packed_chain_bytes(len(reqs), lpad, d,
                                             itemsize=itemsize, kind=kind)
        flops += len(reqs) * lpad * _chain_flops_per_point(d, kind)
    return CostEstimate("serving_grid", nbytes, flops,
                        launches=len(buckets), grid_steps=len(buckets))


def workload_shape(reqs) -> list[tuple[typing.Hashable, str, int, int]]:
    """Project a ``[(chain, points), ...]`` workload to the shape tuples
    ``grid_cost`` consumes (plan key, plan kind, dim, point count); the
    plan key is the engine's ``plan_identity``, the part of its bucket
    key a chain decides."""
    from repro.serving.engine import plan_identity
    out = []
    for chain, pts in reqs:
        n = int(pts.size // chain.dim)
        out.append((plan_identity(chain), chain.plan_kind, chain.dim, n))
    return out


# -- paper cross-check: MorphoSys cycle model ---------------------------------

def morphosys_cycles(routine: str, n: int) -> int:
    """Closed-form cycle count for the paper's TinyRISC listings.

    Program structure (Tables 1-2, generalised to n a multiple of 8):
    frame-buffer loads of 2 + dma_wait(n) slots each, a 5-slot context
    load, the per-column compute/writeback instructions, and the 2-slot
    store; cycles = instructions - 1.  Reproduces the published Table 5
    numbers (96/21 translation, 55/14 scaling) and the emulator exactly.
    """
    if n % RC_N or n <= 0:
        raise ValueError(f"n must be a positive multiple of {RC_N}, got {n}")
    ncols = n // RC_N
    if routine == "translation":       # two operand loads; ldli+dbcdc+wfbi
        length = 2 * (2 + dma_wait(n)) + 5 + 3 * ncols + 2
    elif routine == "scaling":         # one operand load; sbcb+wfbi
        length = (2 + dma_wait(n)) + 5 + 2 * ncols + 2
    else:
        raise ValueError(f"no closed form for routine {routine!r}")
    return length - 1


def m1_chain_cycles(kind: str, n_points: int, d: int) -> int:
    """Projected M1 cycle count for one packed chain launch: the
    Tables 1-2 program skeleton generalised beyond the paper's two
    routines.  The element stream (``n_points * d`` words, padded to a
    multiple of the RC-array width) loads through the frame buffer in
    ``chain_passes(kind)`` operand passes of ``2 + dma_wait`` slots
    each, a 5-slot context load configures the array, each 8-element
    column spends one instruction slot per MAC-pair of the kind's
    per-point schedule plus the writeback, and the 2-slot store drains;
    cycles = instructions - 1, exactly the ``morphosys_cycles``
    accounting.  This is a PROJECTION (the paper only published the
    translation/scaling listings, which ``morphosys_cycles`` reproduces
    exactly) -- deterministic, monotone in the launch shape, and used
    for attribution, never for gating against the emulator."""
    base = _base_kind(kind)
    if base not in ("diag", "matrix", "projective"):
        raise ValueError(f"no M1 projection for plan kind {kind!r}")
    n = max(RC_N, _cdiv(max(1, n_points) * d, RC_N) * RC_N)
    ncols = n // RC_N
    per_col = _chain_flops_per_point(d, base) // (2 * d) + 1
    length = (_chain_passes(base) * (2 + dma_wait(n)) + 5
              + per_col * ncols + 2)
    return length - 1


def perf_rows() -> list[analysis.PerfRow]:
    """The analytic predictions in the paper's table format (source
    ``model``), for the 8- and 64-element cases the paper publishes --
    directly comparable against the emulator rows ``benchmarks.
    paper_tables`` derives with source ``emulator``."""
    rows = []
    for routine in ("translation", "scaling"):
        for n in (8, 64):
            rows.append(analysis.derive(routine, "m1", n,
                                        morphosys_cycles(routine, n),
                                        source="model"))
    return rows


# -- pruning ------------------------------------------------------------------

def prune(candidates: typing.Sequence[KernelConfig],
          cost_fn: typing.Callable[[KernelConfig], CostEstimate],
          keep: int) -> list[KernelConfig]:
    """Top-``keep`` candidates by predicted cost.  Deterministic: ties
    break on the candidate's persisted field repr, and infeasible
    candidates (VMEM) never survive."""
    scored = [(cost_fn(c).predicted_us, repr(sorted(c.key_fields().items())),
               c) for c in candidates]
    scored = [s for s in scored if s[0] != math.inf]
    scored.sort(key=lambda s: (s[0], s[1]))
    return [c for _, _, c in scored[:max(1, keep)]]
