"""Autotune subsystem tests: cache round-trip + winners-file determinism,
cost-model sanity against the runtime ``opcount`` byte accounting and the
MorphoSys cycle emulator, and the integration contracts -- a tuned size
grid still honours the padding-waste cap and packed-vs-per-request
equality, and every cached kernel configuration is bit-identical to the
untuned path (the knobs steer staging, never arithmetic).
"""
import os

import numpy as np
import jax.numpy as jnp
import pytest

import repro.autotune as autotune
from repro import serving
from repro.autotune import cache as tcache
from repro.autotune import costmodel, search
from repro.autotune.cache import KernelConfig, TuningCache
from repro.core import transform_chain as tc
from repro.core.morphosys import programs
from repro.kernels import opcount
from repro.serving import bucketing, workload


@pytest.fixture
def tuning_state():
    """Isolate the process-wide autotune state: every test starts disabled
    with no loaded cache and leaves no plan traced against its config."""
    autotune.set_enabled(False)
    tcache.set_cache(None)
    tcache.set_cache_path(None)
    yield
    autotune.set_enabled(None)
    tcache.set_cache(None)
    tcache.set_cache_path(None)


def _enable_with(cache: TuningCache) -> None:
    tcache.set_cache(cache)
    autotune.set_enabled(True)


#: a deterministic stand-in for the wall-clock timer: pure function of the
#: candidate's tunable fields, so search results are reproducible
def _fake_measure(cfg: KernelConfig) -> float:
    return 1.0 + sum(float(v) for v in cfg.key_fields().values()) / 1e4


# ---------------------------------------------------------------------------
# cache round-trip + determinism
# ---------------------------------------------------------------------------

def test_cache_roundtrip(tmp_path, tuning_state):
    cache = TuningCache()
    cfgs = [KernelConfig("chain_apply", block_rows=128, lane_target=1024,
                         source="tuned"),
            KernelConfig("serving_grid", grid_min_len=32,
                         grid_waste_cap=0.25, source="tuned"),
            KernelConfig("matmul", bm=256, bn=128, bk=512, source="tuned")]
    cache.put("chain_apply", "ref", "float32", 4096, cfgs[0])
    cache.put("serving_grid", "ref", "float32", 0, cfgs[1])
    cache.put("matmul", "interpret", "bfloat16", 1 << 20, cfgs[2])
    path = str(tmp_path / "winners.json")
    cache.save(path)
    loaded = TuningCache.load(path)
    assert len(loaded) == 3
    for (kernel, backend, dtype, n), cfg in (
            (("chain_apply", "ref", "float32", 4096), cfgs[0]),
            (("serving_grid", "ref", "float32", 0), cfgs[1]),
            (("matmul", "interpret", "bfloat16", 1 << 20), cfgs[2])):
        got = loaded.get(kernel, backend, dtype, n)
        assert got.key_fields() == cfg.key_fields()
        assert got.source == "cached"          # loaded winners say so
    # serialization is canonical: load -> save reproduces the same bytes
    assert loaded.to_json() == cache.to_json()


def test_cache_nearest_size_class_fallback(tuning_state):
    cache = TuningCache()
    tuned = KernelConfig("chain_apply", block_rows=64, source="tuned")
    cache.put("chain_apply", "ref", "float32", 2048, tuned)   # class p11
    # same class hits exactly; neighbouring sizes fall back to it
    assert cache.get("chain_apply", "ref", "float32", 2000) is tuned
    assert cache.get("chain_apply", "ref", "float32", 1 << 16) is tuned
    # different backend/dtype/kernel never cross-talk
    assert cache.get("chain_apply", "interpret", "float32", 2048) is None
    assert cache.get("chain_apply", "ref", "float64", 2048) is None
    assert cache.get("chain_diag", "ref", "float32", 2048) is None


def test_search_deterministic_winners_file(tmp_path, tuning_state):
    """Same inputs (workload seed, candidate spaces, measure) -> the same
    winners, serialized to byte-identical files."""
    paths = []
    for i in (0, 1):
        cache, reports = search.smoke_search("ref", measure=_fake_measure)
        # 3 float chain shapes + 2 fixed-point twins + 2 grid scales
        assert len(reports) == 7
        p = str(tmp_path / f"winners{i}.json")
        cache.save(p)
        paths.append(p)
    with open(paths[0]) as a, open(paths[1]) as b:
        assert a.read() == b.read()


def test_disabled_returns_deterministic_defaults(tuning_state):
    # even with a cache installed, disabled lookups return the defaults
    cache = TuningCache()
    cache.put("chain_apply", "ref", "float32", 0,
              KernelConfig("chain_apply", block_rows=8, source="tuned"))
    tcache.set_cache(cache)
    cfg = tcache.config_for("chain_apply", "ref", "float32", 0)
    assert cfg == tcache.DEFAULTS["chain_apply"]
    assert cfg.source == "default"
    autotune.set_enabled(True)
    assert tcache.config_for("chain_apply", "ref", "float32",
                             0).block_rows == 8


def test_committed_default_cache_loads(tuning_state):
    """The repo ships a ref-backend winners file so CI and fresh clones
    never depend on a tuning run."""
    assert os.path.exists(tcache.DEFAULT_CACHE_PATH)
    committed = TuningCache.load(tcache.DEFAULT_CACHE_PATH)
    grid = committed.get("serving_grid", "ref")
    assert grid is not None and grid.source == "cached"
    assert grid.grid_min_len >= 1
    assert 0.0 < grid.grid_waste_cap < 1.0


# ---------------------------------------------------------------------------
# cost-model sanity: bytes vs opcount, cycles vs the emulator
# ---------------------------------------------------------------------------

def test_chain_cost_matches_recorded_bytes(tuning_state):
    """The analytic byte count equals what the runtime records."""
    n, d = 500, 3
    pts = jnp.asarray(np.random.default_rng(0).standard_normal((n, d)),
                      jnp.float32)
    general = (tc.TransformChain.identity(d)
               .rotate(0.3, axis="z").translate(1.0, 2.0, 3.0))
    diag = tc.TransformChain.identity(d).scale(2.0).translate(1.0, 2.0, 3.0)
    for chain, kind in ((general, "matrix"), (diag, "diag")):
        with opcount.counting() as records:
            chain.apply(pts, backend="ref")
        (_, nbytes), = records
        assert nbytes == costmodel.chain_cost(n, d, kind).hbm_bytes


@pytest.mark.parametrize("kind", ["diag", "matrix"])
def test_packed_cost_matches_opcount(kind, tuning_state):
    for bsz, lpad, d in ((8, 64, 2), (3, 128, 3), (1, 8, 2)):
        est = costmodel.packed_chain_cost(bsz, lpad, d, kind)
        assert est.hbm_bytes == opcount.packed_chain_bytes(bsz, lpad, d,
                                                           kind=kind)


def test_grid_cost_replays_engine_bucketing(tuning_state):
    """The model's launch count equals the engine's actual schedule."""
    reqs = workload.random_workload(seed=33, n_requests=40, max_points=300)
    for min_len, cap in ((8, 0.5), (32, 0.25), (64, 0.125)):
        est = costmodel.grid_cost(costmodel.workload_shape(reqs),
                                  min_len, cap)
        srv = serving.GeometryServer(backend="ref", min_len=min_len,
                                     waste_cap=cap)
        serving.reset_stats()
        srv.serve(reqs)
        assert est.launches == serving.stats["launches"], (min_len, cap)


@pytest.mark.parametrize("grid", ["default", "cached"])
def test_grid_cost_exact_on_the_grid_a_server_resolves(grid, tuning_state):
    """The model's launch count equals the launches of a server left to
    resolve its own grid: the built-in default, or (autotune on) the
    committed cache's tuned entry for the workload's size class."""
    if grid == "cached":
        _enable_with(TuningCache.load(tcache.DEFAULT_CACHE_PATH))
    reqs = search.smoke_workload()
    srv = serving.GeometryServer(backend="ref")
    serving.reset_stats()
    srv.serve(reqs)
    assert srv.grid_source == grid
    default = tcache.DEFAULTS["serving_grid"]
    assert ((srv.min_len, srv.waste_cap)
            == (default.grid_min_len, default.grid_waste_cap)) \
        == (grid == "default")
    est = costmodel.grid_cost(costmodel.workload_shape(reqs), srv.min_len,
                              srv.waste_cap)
    assert est.launches == serving.stats["launches"]


def test_morphosys_cycles_match_emulator(tuning_state):
    """The closed-form cycle model reproduces the emulator (and through
    it the paper's published Table 5 numbers) for the 8/64-element
    cases."""
    rng = np.random.default_rng(0)
    for n in (8, 64):
        u = rng.integers(-99, 99, n)
        v = rng.integers(-99, 99, n)
        assert costmodel.morphosys_cycles("translation", n) == \
            programs.run_translation(u, v).cycles
        assert costmodel.morphosys_cycles("scaling", n) == \
            programs.run_scaling(u, 5).cycles
    # and the published constants directly
    assert costmodel.morphosys_cycles("translation", 64) == 96
    assert costmodel.morphosys_cycles("scaling", 64) == 55


def test_perf_rows_print_in_paper_format(tuning_state):
    from repro.core import analysis
    rows = costmodel.perf_rows()
    assert {(r.algorithm, r.n_elements) for r in rows} == \
        {("translation", 8), ("translation", 64),
         ("scaling", 8), ("scaling", 64)}
    assert all(r.source == "model" for r in rows)
    table = analysis.format_table(rows)
    assert "translation" in table and "model" in table


def test_prune_is_deterministic_and_drops_infeasible(tuning_state):
    cands = search.matmul_candidates()
    cost = lambda c: costmodel.matmul_cost(1024, 1024, 1024, c)
    first = costmodel.prune(cands, cost, keep=4)
    assert first == costmodel.prune(list(reversed(cands)), cost, keep=4)
    assert len(first) == 4
    # an impossible tile never survives
    huge = KernelConfig("matmul", bm=4096, bn=4096, bk=4096)
    assert huge not in costmodel.prune(cands + [huge], cost, keep=100)


# ---------------------------------------------------------------------------
# integration: tuned grid waste cap + equality, bit-identical configs
# ---------------------------------------------------------------------------

def test_tuned_grid_satisfies_waste_cap_and_equality(tuning_state):
    """A GeometryServer running a TUNED size grid still honours the
    padding-waste cap (for requests at or above the grid floor) and the
    packed-vs-per-request equality contract."""
    cache = TuningCache()
    tuned = KernelConfig("serving_grid", grid_min_len=16,
                         grid_waste_cap=0.25, source="tuned")
    cache.put("serving_grid", "ref", "float32", 0, tuned)
    _enable_with(cache)
    reqs = workload.random_workload(seed=21, n_requests=40, max_points=400,
                                    min_points=16)
    srv = serving.GeometryServer(backend="ref")     # knobs from the cache
    assert (srv.min_len, srv.waste_cap) == (16, 0.25)
    assert srv.grid_source in ("tuned", "cached")
    serving.reset_stats()
    outs = srv.serve(reqs)
    for rep in srv.last_report:
        assert rep.waste < 0.25, rep                # the tuned cap holds
    for (chain, pts), out in zip(reqs, outs):
        exp = np.asarray(chain.apply(jnp.asarray(pts), backend="ref"))
        if chain.is_diagonal:
            # XLA:CPU may fuse p*s + t into one multiply-add in one program
            # shape and not in another: they differ by one rounding of the
            # product at most (test_serving.assert_diag_within_fma)
            s, t = chain.fold()
            bound = 2 * np.finfo(np.float32).eps * (np.abs(pts * s)
                                                    + np.abs(t))
            assert (np.abs(np.asarray(out, np.float64) - exp) <= bound).all()
        else:
            np.testing.assert_allclose(np.asarray(out), exp,
                                       rtol=2e-6, atol=2e-6)


@pytest.mark.parametrize("backend", ["ref", "interpret"])
def test_apply_bit_identical_for_every_cached_config(backend, tuning_state):
    """TransformChain.apply under ANY cached kernel configuration is
    bit-identical to the untuned path: the knobs steer staging only."""
    rng = np.random.default_rng(3)
    pts = jnp.asarray(rng.standard_normal((700, 2)), jnp.float32)
    chain = (tc.TransformChain.identity(2)
             .scale(1.3, 0.8).rotate(0.4).translate(2.0, -1.0))
    diag = tc.TransformChain.identity(2).scale(1.3, 0.8).translate(2.0, -1.0)
    baseline = np.asarray(chain.apply(pts, backend=backend))
    baseline_d = np.asarray(diag.apply(pts, backend=backend))
    for cand in search.chain_candidates("chain_apply"):
        cache = TuningCache()
        cache.put("chain_apply", backend, "float32", 700,
                  KernelConfig("chain_apply", source="tuned",
                               **cand.key_fields()))
        cache.put("chain_diag", backend, "float32", 700,
                  KernelConfig("chain_diag", source="tuned",
                               **cand.key_fields()))
        _enable_with(cache)                         # clears plan caches
        np.testing.assert_array_equal(
            np.asarray(chain.apply(pts, backend=backend)), baseline)
        np.testing.assert_array_equal(
            np.asarray(diag.apply(pts, backend=backend)), baseline_d)
        autotune.set_enabled(False)


def test_server_bit_identical_under_batch_block_configs(tuning_state):
    """The GeometryServer under tuned batch-kernel block configs (same
    grid, so same bucket shapes) returns bit-identical results."""
    reqs = workload.random_workload(seed=8, n_requests=24, max_points=200)
    base = serving.GeometryServer(backend="interpret").serve(reqs)
    for bm in (8, 32, 128):
        cache = TuningCache()
        for kernel in ("chain_diag_batch", "chain_apply_batch"):
            cache.put(kernel, "interpret", "float32", 0,
                      KernelConfig(kernel, block_rows=bm, source="tuned"))
        _enable_with(cache)
        outs = serving.GeometryServer(backend="interpret").serve(reqs)
        for a, b in zip(base, outs):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        autotune.set_enabled(False)


def test_grid_for_resolution_order(tuning_state):
    # explicit knobs always win, even with a cache enabled
    cache = TuningCache()
    cache.put("serving_grid", "ref", "float32", 0,
              KernelConfig("serving_grid", grid_min_len=64,
                           grid_waste_cap=0.125, source="tuned"))
    _enable_with(cache)
    assert bucketing.grid_for("ref", min_len=4, waste_cap=0.5) == \
        (4, 0.5, "explicit")
    assert bucketing.grid_for("ref")[:2] == (64, 0.125)
    # mixed: the explicit knob wins, the other comes from the cache, and
    # the source label says so
    assert bucketing.grid_for("ref", min_len=16) == \
        (16, 0.125, "explicit+tuned")
    autotune.set_enabled(False)
    assert bucketing.grid_for("ref") == \
        (bucketing.MIN_LEN, bucketing.WASTE_CAP, "default")
    assert bucketing.grid_for("ref", waste_cap=0.25) == \
        (bucketing.MIN_LEN, 0.25, "explicit+default")


def test_set_enabled_moves_a_live_server(tuning_state):
    """Toggling the tuning cache after a server exists must move its grid
    on the next flush (the grid re-resolves per flush; plan caches are
    cleared by set_enabled itself)."""
    cache = TuningCache()
    cache.put("serving_grid", "ref", "float32", 0,
              KernelConfig("serving_grid", grid_min_len=64,
                           grid_waste_cap=0.25, source="tuned"))
    tcache.set_cache(cache)
    srv = serving.GeometryServer(backend="ref")       # built while disabled
    assert (srv.min_len, srv.grid_source) == (bucketing.MIN_LEN, "default")
    reqs = workload.random_workload(seed=4, n_requests=6, max_points=40)
    autotune.set_enabled(True)
    srv.serve(reqs)
    assert (srv.min_len, srv.waste_cap) == (64, 0.25)
    assert srv.grid_source in ("tuned", "cached")
    autotune.set_enabled(False)
    srv.serve(reqs)
    assert (srv.min_len, srv.grid_source) == (bucketing.MIN_LEN, "default")
    # explicit knobs survive every toggle
    pinned = serving.GeometryServer(backend="ref", min_len=16,
                                    waste_cap=0.5)
    autotune.set_enabled(True)
    pinned.serve(reqs)
    assert (pinned.min_len, pinned.waste_cap) == (16, 0.5)


def test_ref_backend_pins_kernel_winners_to_default(tuning_state):
    """The ref backend never reads the launch knobs, so an empirical
    search there would cache timer noise: the tuners must pin the winner
    to the default and time nothing else."""
    rep = search.tune_chain("chain_apply", "ref", n_points=256, iters=1)
    assert len(rep.trials) == 1                  # only the default ran
    assert rep.winner.key_fields() == \
        tcache.DEFAULTS["chain_apply"].key_fields()
    rep = search.tune_rmsnorm("ref", m=32, n=64, iters=1)
    assert len(rep.trials) == 1
    # an injected measure (cost-model-only tuning) still searches
    rep = search.tune_chain("chain_apply", "ref", n_points=256,
                            measure=_fake_measure)
    assert len(rep.trials) > 1


def test_workload_seed_end_to_end(tuning_state):
    """Same seed -> bit-identical request mix (chains fold identically,
    points match bitwise); different seeds -> different mixes."""
    a = workload.random_workload(seed=99, n_requests=12, max_points=64)
    b = workload.random_workload(seed=99, n_requests=12, max_points=64)
    c = workload.random_workload(seed=100, n_requests=12, max_points=64)
    for (ca, pa), (cb, pb) in zip(a, b):
        assert ca.structure == cb.structure
        np.testing.assert_array_equal(pa, pb)
        for fa, fb in zip(ca.fold(), cb.fold()):
            np.testing.assert_array_equal(fa, fb)
    assert any(pa.shape != pc.shape or not np.array_equal(pa, pc)
               for (_, pa), (_, pc) in zip(a, c))
    with pytest.raises(ValueError):
        workload.random_workload(n_requests=4)
    with pytest.raises(ValueError):
        workload.random_workload(np.random.default_rng(0), 4, seed=1)
