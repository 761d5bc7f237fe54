"""On-device search: candidate generation -> analytic prune -> timer -> cache.

The tuner's discipline, for every tunable kernel:

  1. enumerate a small closed candidate space (block shapes, lane widths,
     matmul tiles, serving-grid knobs);
  2. prune it with the analytic cost model (``costmodel``) -- infeasible
     candidates (VMEM) die here, and only the ``keep`` cheapest survive to
     be timed;
  3. time the survivors empirically (min-of-iters after a warmup call,
     through the SAME public entry points production uses);
  4. keep the default configuration unless a candidate beats it by more
     than the noise floor, and persist the winner to the tuning cache.

Every ``tune_*`` entry accepts ``measure=`` -- a ``cfg -> seconds``
callable replacing the wall-clock timer -- which is how the determinism
tests make "same inputs -> same winners file" a hard property (and how a
cost-model-only tuning mode works: pass the prediction as the measure).
"""
from __future__ import annotations

import dataclasses
import time
import typing

from repro.autotune import costmodel
from repro.autotune.cache import DEFAULTS, KernelConfig, TuningCache

#: a candidate must beat the default by this fraction to replace it --
#: below the floor, timer noise would make winners flap run to run.
NOISE_FLOOR = 0.03


# -- candidate spaces ---------------------------------------------------------

def chain_candidates(kernel: str) -> list[KernelConfig]:
    """Single-chain kernels: grid row block x lane-packing width."""
    return [KernelConfig(kernel, block_rows=bm, lane_target=w,
                         source="candidate")
            for bm in (64, 128, 256, 512)
            for w in (256, 512, 1024)]


def chain_batch_candidates(kernel: str) -> list[KernelConfig]:
    """Batched chain kernels: batch-axis block rows (None keeps the
    stager's VMEM-budget heuristic)."""
    return [KernelConfig(kernel, source="candidate")] + \
        [KernelConfig(kernel, block_rows=bm, source="candidate")
         for bm in (8, 16, 32, 64, 128)]


def matmul_candidates() -> list[KernelConfig]:
    return [KernelConfig("matmul", bm=bm, bn=bn, bk=bk, source="candidate")
            for bm in (128, 256) for bn in (128, 256)
            for bk in (256, 512, 1024)]


def rmsnorm_candidates() -> list[KernelConfig]:
    return [KernelConfig("rmsnorm", block_rows=bm, source="candidate")
            for bm in (64, 128, 256, 512)]


def grid_candidates() -> list[KernelConfig]:
    """Serving size grid: floor x waste cap.  Coarser floors merge small
    size classes (fewer launches, more padding); tighter caps refine the
    grid (more launches, less padded traffic)."""
    return [KernelConfig("serving_grid", grid_min_len=m, grid_waste_cap=c,
                         source="candidate")
            for m in (4, 8, 16, 32, 64)
            for c in (0.125, 0.25, 0.5)]


def candidates_for(kernel: str) -> list[KernelConfig]:
    if kernel in ("chain_diag", "chain_apply", "chain_project",
                  "chain_diag_q", "chain_apply_q"):
        return chain_candidates(kernel)
    if kernel in ("chain_diag_batch", "chain_apply_batch",
                  "chain_project_batch", "chain_diag_batch_q",
                  "chain_apply_batch_q"):
        return chain_batch_candidates(kernel)
    if kernel == "matmul":
        return matmul_candidates()
    if kernel == "rmsnorm":
        return rmsnorm_candidates()
    if kernel == "serving_grid":
        return grid_candidates()
    raise ValueError(f"no candidate space for kernel {kernel!r}")


# -- the timer ----------------------------------------------------------------

def _time_best(fn: typing.Callable[[], typing.Any], iters: int) -> float:
    """Best-of-``iters`` seconds for ``fn()`` after one warmup call
    (compile + staging), blocking on every jax leaf in the result."""
    import jax
    jax.block_until_ready(fn())
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


@dataclasses.dataclass(frozen=True)
class TrialResult:
    config: KernelConfig
    seconds: float
    predicted_us: float


@dataclasses.dataclass(frozen=True)
class TuneReport:
    """One tuning decision: the winner plus every timed trial (the CLI
    prints these)."""
    kernel: str
    backend: str
    dtype: str
    n: int
    winner: KernelConfig
    trials: tuple[TrialResult, ...]

    @property
    def default_seconds(self) -> float:
        return self.trials[0].seconds      # default is always trial 0

    @property
    def winner_seconds(self) -> float:
        key = self.winner.key_fields()
        return min(t.seconds for t in self.trials
                   if t.config.key_fields() == key)


def _is_default(kernel: str, cfg: KernelConfig) -> bool:
    return cfg.key_fields() == DEFAULTS[kernel].key_fields()


def _run_trials(kernel: str, backend: str, dtype: str, n: int,
                candidates: list[KernelConfig],
                cost_fn: typing.Callable[[KernelConfig], typing.Any],
                measure: typing.Callable[[KernelConfig], float],
                *, keep: int, cache: TuningCache | None) -> TuneReport:
    """Prune -> time (default always first) -> pick -> cache."""
    survivors = costmodel.prune(candidates, cost_fn, keep)
    default = DEFAULTS[kernel]
    trials_cfgs = [default] + [c for c in survivors
                               if not _is_default(kernel, c)]
    trials = tuple(TrialResult(c, measure(c), cost_fn(c).predicted_us)
                   for c in trials_cfgs)
    # incumbent scan: a candidate must beat the current best by the noise
    # floor to take over, so the default survives timer noise and ties
    # resolve to the deterministically-first (cheapest-predicted) survivor
    best = trials[0]
    for t in trials[1:]:
        if t.seconds < best.seconds * (1.0 - NOISE_FLOOR):
            best = t
    # a default that merely kept its seat stays labelled "default" -- only
    # a candidate that actually beat it earns "tuned"
    winner = dataclasses.replace(
        best.config, source="tuned" if best is not trials[0] else "default")
    if cache is not None:
        cache.put(kernel, backend, dtype, n, winner)
    return TuneReport(kernel, backend, dtype, n, winner, trials)


# -- per-kernel tuners --------------------------------------------------------

def _ref_ignores_launch_knobs(kernel: str, backend: str, measure) -> bool:
    """True when searching would time identical code: the ``ref`` backend
    is the pure-jnp oracle and never reads the launch knobs, so on it a
    wall-clock search over kernel configs caches nothing but timer noise
    -- the winner is pinned to the default instead.  An injected
    ``measure`` (tests, cost-model-only tuning) overrides this."""
    return measure is None and backend == "ref" and kernel != "serving_grid"


def tune_chain(kernel: str, backend: str, *, n_points: int, d: int = 2,
               dtype: str = "float32", cache: TuningCache | None = None,
               measure: typing.Callable[[KernelConfig], float] | None = None,
               keep: int = 4, iters: int = 3) -> TuneReport:
    """Tune a single-chain kernel (``chain_diag`` / ``chain_apply`` /
    ``chain_project`` or their ``_q`` fixed-point twins) at one
    (points, dim) shape through the public op entry.  Fixed-point kernels
    cache under the Qm.n format name as the dtype (pass e.g.
    ``dtype="q8.7"``); their timing inputs are the float inputs quantised
    through ``repro.quantize``, so the tuner measures the lane it
    ships."""
    kind = {"chain_diag": "diag", "chain_apply": "matrix",
            "chain_project": "projective", "chain_diag_q": "diag_q",
            "chain_apply_q": "matrix_q"}[kernel]
    candidates = [] if _ref_ignores_launch_knobs(kernel, backend, measure) \
        else candidates_for(kernel)
    if measure is None:
        import numpy as np
        import jax.numpy as jnp
        from repro import kernels
        rng = np.random.default_rng(0)
        pts = jnp.asarray(rng.standard_normal((n_points, d)), jnp.float32)
        if kind in ("diag_q", "matrix_q"):
            from repro import quantize
            fmt = quantize.as_qformat(dtype)
            pq = jnp.asarray(fmt.quantize(np.asarray(pts)))
            if kind == "diag_q":
                s = jnp.asarray(fmt.quantize(rng.uniform(0.5, 2.0, d)))
                t = jnp.asarray(fmt.quantize(rng.uniform(-1, 1, d)))
                entry = lambda cfg: kernels.chain_diag_q(
                    pq, s, t, n_frac=fmt.n, backend=backend, config=cfg)
            else:
                a = jnp.asarray(fmt.quantize(rng.standard_normal((d, d))))
                t = jnp.asarray(fmt.quantize(rng.uniform(-1, 1, d)))
                entry = lambda cfg: kernels.chain_apply_q(
                    pq, a, t, n_frac=fmt.n, backend=backend, config=cfg)
        elif kind == "diag":
            s = jnp.asarray(rng.uniform(0.5, 2.0, d), jnp.float32)
            t = jnp.asarray(rng.uniform(-1, 1, d), jnp.float32)
            entry = lambda cfg: kernels.chain_diag(
                pts, s, t, backend=backend, config=cfg)
        elif kind == "matrix":
            a = jnp.asarray(rng.standard_normal((d, d)), jnp.float32)
            t = jnp.asarray(rng.uniform(-1, 1, d), jnp.float32)
            entry = lambda cfg: kernels.chain_apply(
                pts, a, t, backend=backend, config=cfg)
        else:
            from repro.serving import workload
            # time on the SAME matrix distribution the served traffic
            # draws (workload.random_projective is the one recipe)
            hj = jnp.asarray(workload.random_projective(rng, d))
            entry = lambda cfg: kernels.chain_project(
                pts, hj, -4.0, 4.0, backend=backend, config=cfg)
        measure = lambda cfg: _time_best(lambda: entry(cfg), iters)
    cost = lambda cfg: costmodel.chain_cost(n_points, d, kind, cfg)
    return _run_trials(kernel, backend, dtype, n_points, candidates, cost,
                       measure, keep=keep, cache=cache)


def tune_serving_grid(reqs, backend: str, *,
                      cache: TuningCache | None = None,
                      measure: typing.Callable[[KernelConfig], float] | None
                      = None, keep: int = 4, iters: int = 2) -> TuneReport:
    """Tune the serving size grid (floor + waste cap) on one workload:
    ``reqs`` is the ``[(chain, points), ...]`` list the GeometryServer
    serves.  The analytic prune replays the engine's bucketing per
    candidate; the timer serves the real workload under each survivor.
    The winner is cached at the workload's largest request length (the
    size-class convention grid consumers look up by), so grids tuned at
    different traffic scales coexist in one cache."""
    shape = costmodel.workload_shape(reqs)
    n = workload_size_class_n(reqs)
    if measure is None:
        from repro import serving

        def measure(cfg: KernelConfig) -> float:
            srv = serving.GeometryServer(backend=backend,
                                         min_len=cfg.grid_min_len,
                                         waste_cap=cfg.grid_waste_cap)
            return _time_best(lambda: srv.serve(reqs), iters)
    default = DEFAULTS["serving_grid"]
    cost = lambda cfg: costmodel.grid_cost(
        shape,
        cfg.grid_min_len if cfg.grid_min_len is not None
        else default.grid_min_len,
        cfg.grid_waste_cap if cfg.grid_waste_cap is not None
        else default.grid_waste_cap)
    return _run_trials("serving_grid", backend, "float32", n,
                       candidates_for("serving_grid"), cost, measure,
                       keep=keep, cache=cache)


def tune_matmul(backend: str, *, m: int, k: int, n: int,
                dtype: str = "bfloat16", cache: TuningCache | None = None,
                measure: typing.Callable[[KernelConfig], float] | None = None,
                keep: int = 4, iters: int = 3) -> TuneReport:
    candidates = [] if _ref_ignores_launch_knobs("matmul", backend, measure) \
        else candidates_for("matmul")
    if measure is None:
        import numpy as np
        import jax.numpy as jnp
        from repro import kernels
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((m, k)), dtype)
        y = jnp.asarray(rng.standard_normal((k, n)), dtype)
        measure = lambda cfg: _time_best(
            lambda: kernels.matmul(x, y, backend=backend, bm=cfg.bm,
                                   bn=cfg.bn, bk=cfg.bk), iters)
    itemsize = 2 if dtype == "bfloat16" else 4
    cost = lambda cfg: costmodel.matmul_cost(m, k, n, cfg, itemsize=itemsize)
    return _run_trials("matmul", backend, dtype, m * n, candidates, cost,
                       measure, keep=keep, cache=cache)


def tune_rmsnorm(backend: str, *, m: int, n: int, dtype: str = "float32",
                 cache: TuningCache | None = None,
                 measure: typing.Callable[[KernelConfig], float] | None = None,
                 keep: int = 3, iters: int = 3) -> TuneReport:
    candidates = [] if _ref_ignores_launch_knobs("rmsnorm", backend,
                                                 measure) \
        else candidates_for("rmsnorm")
    if measure is None:
        import numpy as np
        import jax.numpy as jnp
        from repro import kernels
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((m, n)), dtype)
        g = jnp.ones((n,), dtype)
        measure = lambda cfg: _time_best(
            lambda: kernels.rmsnorm(x, g, backend=backend, config=cfg), iters)
    cost = lambda cfg: costmodel.rmsnorm_cost(m, n, cfg)
    return _run_trials("rmsnorm", backend, dtype, m * n, candidates, cost,
                       measure, keep=keep, cache=cache)


# -- the smoke search (CI; two small shapes + the serving grid) ---------------

SMOKE_SEED = 1234             #: workload seed shared with --check re-runs
SMOKE_REQUESTS = 24
SMOKE_MAX_POINTS = 96
#: the 64-request workload (tune where you serve -- a grid tuned on small
#: traffic does not transfer to large traffic, so both scales get their
#: own cache entry)
SCALE_SEED = 1904
SCALE_REQUESTS = 64
SCALE_MAX_POINTS = 1024


def workload_size_class_n(reqs) -> int:
    """The n a workload's grid entry is cached/looked up under: the
    largest request length (point count) in the mix."""
    return max((int(p.size // c.dim) for c, p in reqs), default=0)


def smoke_workload():
    from repro.serving import workload
    return workload.random_workload(seed=SMOKE_SEED,
                                    n_requests=SMOKE_REQUESTS,
                                    max_points=SMOKE_MAX_POINTS,
                                    templates=workload.TEMPLATES[:4])


def scale_workload():
    from repro.serving import workload
    return workload.random_workload(seed=SCALE_SEED,
                                    n_requests=SCALE_REQUESTS,
                                    max_points=SCALE_MAX_POINTS)


def smoke_search(backend: str = "ref", *,
                 cache: TuningCache | None = None,
                 measure: typing.Callable[[KernelConfig], float] | None = None,
                 iters: int = 3) -> tuple[TuningCache, list[TuneReport]]:
    """The pruned search CI runs: three small chain shapes (diagonal 3D,
    general 2D, projective 3D), the fixed-point twins of the affine two
    (int16 q8.7 -- cached under the format name as the dtype), plus the
    serving grid on BOTH seeded workloads (the tiny smoke mix and the
    64-request mix -- each caches at its own size class).  Returns the
    populated cache and the per-kernel reports."""
    cache = cache if cache is not None else TuningCache()
    reports = [
        tune_chain("chain_diag", backend, n_points=2048, d=3, cache=cache,
                   measure=measure, iters=iters),
        tune_chain("chain_apply", backend, n_points=2048, d=2, cache=cache,
                   measure=measure, iters=iters),
        tune_chain("chain_project", backend, n_points=2048, d=3,
                   cache=cache, measure=measure, iters=iters),
        tune_chain("chain_diag_q", backend, n_points=2048, d=3,
                   dtype="q8.7", cache=cache, measure=measure, iters=iters),
        tune_chain("chain_apply_q", backend, n_points=2048, d=2,
                   dtype="q8.7", cache=cache, measure=measure, iters=iters),
        tune_serving_grid(smoke_workload(), backend, cache=cache,
                          measure=measure, iters=max(1, iters - 1)),
        tune_serving_grid(scale_workload(), backend, cache=cache,
                          measure=measure, iters=max(2, iters - 1)),
    ]
    return cache, reports
