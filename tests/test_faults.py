"""Fault-model tests: the typed request-error taxonomy, submit/apply
boundary validation, the engine's retry / backend-degradation /
bisection recovery ladder under seeded fault injection, the q-lane
wrap-prediction policies, and the chaos soak's zero-lost invariant.
"""
import numpy as np
import pytest

from repro import errors, quantize, serving
from repro.core import transform_chain as tc
from repro.kernels import dispatch
from repro.serving import engine, faults, workload

RNG = np.random.default_rng(60)


def _fresh(**kw):
    serving.reset_stats()
    serving.clear_plan_cache()
    return serving.GeometryServer(**kw)


def _chain2():
    return tc.TransformChain.identity(2).translate(0.5, -0.25).scale(1.5, 0.5)


def _pts(n=8, dim=2):
    return RNG.uniform(-1, 1, (n, dim)).astype(np.float32)


# ---------------------------------------------------------------------------
# taxonomy
# ---------------------------------------------------------------------------

class TestTaxonomy:
    def test_codes_and_subclassing(self):
        # every member is a ValueError (legacy except-sites keep catching)
        for cls, code in [(errors.ShapeError, "shape"),
                          (errors.DtypeError, "dtype"),
                          (errors.EmptyPointsError, "empty"),
                          (errors.NonFiniteError, "nonfinite"),
                          (errors.QRangeError, "q-range"),
                          (errors.LaunchError, "launch")]:
            assert issubclass(cls, errors.RequestError)
            assert issubclass(cls, ValueError)
            assert cls.code == code
        # dtype misuse historically raised TypeError; both must keep working
        assert issubclass(errors.DtypeError, TypeError)

    def test_ticket_prefix_and_with_ticket(self):
        e = errors.ShapeError("bad", ticket=42)
        assert e.ticket == 42 and "[request 42]" in str(e)
        anon = errors.NonFiniteError("nan")
        assert anon.ticket is None and "[request" not in str(anon)
        named = anon.with_ticket(7)
        assert type(named) is errors.NonFiniteError and named.ticket == 7

    def test_fault_config_validates(self):
        with pytest.raises(ValueError):
            engine.FaultConfig(on_q_overflow="explode")
        with pytest.raises(ValueError):
            engine.FaultConfig(max_launch_attempts=0)


# ---------------------------------------------------------------------------
# boundary validation: TransformChain.apply
# ---------------------------------------------------------------------------

class TestApplyBoundary:
    def test_apply_rejects_empty_and_shape_and_float64(self):
        chain = _chain2()
        with pytest.raises(errors.EmptyPointsError):
            chain.apply(np.zeros((0, 2), np.float32))
        with pytest.raises(errors.ShapeError):
            chain.apply(np.zeros((4, 3), np.float32))
        with pytest.raises(errors.DtypeError):
            chain.apply(np.zeros((4, 2), np.float64))

    def test_apply_shape_error_is_still_a_valueerror(self):
        with pytest.raises(ValueError):
            _chain2().apply(np.zeros((4, 3), np.float32))


# ---------------------------------------------------------------------------
# boundary validation: GeometryServer.submit
# ---------------------------------------------------------------------------

class TestSubmitBoundary:
    def test_typed_rejections_carry_the_ticket(self):
        srv = _fresh(backend="ref")
        srv.submit(_chain2(), _pts())            # ticket 0
        cases = [
            (np.zeros((0, 2), np.float32), errors.EmptyPointsError),
            (np.zeros((3, 3), np.float32), errors.ShapeError),
            (np.zeros((3, 2), np.float64), errors.DtypeError),
            (np.float32(1.0), errors.ShapeError),          # bare scalar
            (np.full((3, 2), np.inf, np.float32), errors.NonFiniteError),
        ]
        for i, (bad, exc) in enumerate(cases):
            with pytest.raises(exc) as ei:
                srv.submit(_chain2(), bad)
            # rejected submissions burn their ticket id -- never reused
            assert ei.value.ticket == 1 + i
        assert serving.stats["rejected_requests"] == len(cases)
        # the queue survived every rejection
        (out,) = srv.flush()
        assert out.shape == (8, 2)

    def test_float_lane_is_strict_float32(self):
        srv = _fresh(backend="ref")
        with pytest.raises(errors.DtypeError):
            srv.submit(_chain2(), np.zeros((4, 2), np.float16))
        with pytest.raises(errors.DtypeError):
            srv.submit(_chain2(), np.zeros((4, 2), np.int32))

    def test_nonfinite_fold_rejected_at_submit(self):
        srv = _fresh(backend="ref")
        chain = tc.TransformChain.identity(2).scale(np.inf, 1.0)
        with pytest.raises(errors.NonFiniteError) as ei:
            srv.submit(chain, _pts())
        assert "fold" in str(ei.value)

    def test_malform_modes_map_to_codes(self):
        srv = _fresh(backend="ref")
        for mode, code in faults.MALFORM_MODES:
            with pytest.raises(errors.RequestError) as ei:
                srv.submit(_chain2(), faults.malform(_pts(), mode))
            assert ei.value.code == code, mode


# ---------------------------------------------------------------------------
# q-lane wrap prediction (satellite: error_bound wired into submit)
# ---------------------------------------------------------------------------

class TestQOverflowPolicy:
    def test_wrap_boundary_is_pinned(self):
        """quantize.fits flips between a x100 and a x1000 scale for q8.7
        (range [-256, 256)) -- the exact predicate submit consults."""
        fmt = quantize.as_qformat("q8.7")
        ok = tc.TransformChain.identity(2).scale(100.0).fold()
        bad = tc.TransformChain.identity(2).scale(1000.0).fold()
        assert quantize.fits(ok, "diag", fmt, 1.0)
        assert not quantize.fits(bad, "diag", fmt, 1.0)
        with pytest.raises(errors.QRangeError):
            quantize.ensure_fits(bad, "diag", fmt, 1.0, ticket=5)

    def test_reject_policy_raises_qrange(self):
        srv = _fresh(backend="ref",
                     fault_config=engine.FaultConfig(on_q_overflow="reject"))
        chain = tc.TransformChain.identity(2).scale(1000.0)
        with pytest.raises(errors.QRangeError) as ei:
            srv.submit(chain, _pts(), qformat="q8.7")
        assert ei.value.ticket == 0
        assert serving.stats["rejected_requests"] == 1

    def test_fallback_policy_serves_through_float32(self):
        srv = _fresh(backend="ref")          # default policy: fallback
        chain = tc.TransformChain.identity(2).scale(1000.0)
        pts = _pts()
        srv.submit(chain, pts, qformat="q8.7")
        (out,) = srv.flush()
        assert out.dtype == np.float32
        np.testing.assert_allclose(out, np.asarray(chain.apply(pts)),
                                   rtol=1e-5, atol=1e-5)
        assert serving.stats["q_fallbacks"] == 1
        assert srv.last_report[0].q_fallback_requests == 1

    def test_fallback_requantises_for_int16_callers(self):
        """int16 in -> int16 out even when the lane degrades to float."""
        srv = _fresh(backend="ref")
        chain = tc.TransformChain.identity(2).scale(1000.0)
        fmt = quantize.as_qformat("q8.7")
        words = fmt.quantize(_pts())
        srv.submit(chain, words, qformat="q8.7")
        (out,) = srv.flush()
        assert out.dtype == np.int16

    def test_fitting_q_requests_stay_bitwise(self):
        """The wrap check must not perturb the in-range q lane: packed
        results stay bitwise equal to apply(dtype=...)."""
        srv = _fresh(backend="ref")
        chain = _chain2()
        pts = _pts(16)
        srv.submit(chain, pts, qformat="q8.7")
        (out,) = srv.flush()
        ref = chain.apply(pts, dtype="q8.7", backend="ref")
        np.testing.assert_array_equal(out, np.asarray(ref))
        assert serving.stats["q_fallbacks"] == 0

    def test_wrap_policy_preserves_legacy_semantics(self):
        srv = _fresh(backend="ref",
                     fault_config=engine.FaultConfig(on_q_overflow="wrap"))
        chain = tc.TransformChain.identity(2).scale(1000.0)
        pts = _pts()
        srv.submit(chain, pts, qformat="q8.7")
        (out,) = srv.flush()
        ref = chain.apply(pts, dtype="q8.7", backend="ref")  # wraps too
        np.testing.assert_array_equal(out, np.asarray(ref))
        assert serving.stats["q_fallbacks"] == 0


# ---------------------------------------------------------------------------
# recovery ladder under seeded injection
# ---------------------------------------------------------------------------

def _cfg(**kw):
    kw.setdefault("backoff_base_s", 0.0)     # tests need no real sleeps
    return engine.FaultConfig(**kw)


class TestRecovery:
    def test_flaky_launch_recovers_by_retry(self):
        inj = faults.FaultInjector(flaky_tickets=frozenset({0, 1}),
                                   flaky_attempts=2)
        srv = _fresh(backend="ref", fault_config=_cfg(), injector=inj)
        chain, pts = _chain2(), _pts()
        srv.submit(chain, pts)
        srv.submit(chain, _pts())
        out = srv.flush()
        np.testing.assert_allclose(out[0], np.asarray(chain.apply(pts)),
                                   rtol=1e-6, atol=1e-6)
        # attempt 0 (phase 1) + attempt 1 fail, attempt 2 succeeds
        assert serving.stats["launch_failures"] == 2
        assert serving.stats["retries"] == 2
        assert serving.stats["recovered_requests"] == 2
        assert serving.stats["failed_requests"] == 0
        assert srv.last_report[0].retries == 2

    def test_backend_fault_degrades_down_the_ladder(self):
        assert dispatch.fallback_ladder("interpret") == ("interpret", "ref")
        # on a chip a refused kernel never lands in the interpreter
        assert dispatch.fallback_ladder("pallas") == ("pallas", "ref")
        inj = faults.FaultInjector(backend_tickets=frozenset({0}))
        srv = _fresh(backend="interpret",
                     fault_config=_cfg(max_launch_attempts=2), injector=inj)
        chain, pts = _chain2(), _pts()
        srv.submit(chain, pts)
        (out,) = srv.flush()
        np.testing.assert_allclose(
            out, np.asarray(chain.apply(pts, backend="ref")),
            rtol=1e-6, atol=1e-6)
        assert serving.stats["backend_fallbacks"] == 1
        rep = srv.last_report[0]
        assert rep.backend == "interpret" and rep.final_backend == "ref"

    def test_corruption_detected_and_retried_pristine(self):
        inj = faults.FaultInjector(corrupt_tickets=frozenset({0}))
        srv = _fresh(backend="ref", fault_config=_cfg(), injector=inj)
        chain, pts = _chain2(), _pts()
        srv.submit(chain, pts)
        (out,) = srv.flush()
        # recovered output is finite and correct: the retry re-packed
        # from the pristine host copy, not the corrupted staging buffer
        assert np.isfinite(out).all()
        np.testing.assert_allclose(out, np.asarray(chain.apply(pts)),
                                   rtol=1e-6, atol=1e-6)
        assert inj.injected_corruptions == 1
        assert serving.stats["launch_failures"] == 1
        assert serving.stats["retries"] == 1
        assert serving.stats["recovered_requests"] == 1

    def test_poison_is_bisected_to_a_named_failure(self):
        """B=8 bucket with one poison request: 3 bisections isolate it,
        the 7 siblings all recover, the poison resolves to a LaunchError
        carrying its own ticket."""
        inj = faults.FaultInjector(poison_tickets=frozenset({3}))
        srv = _fresh(backend="ref",
                     fault_config=_cfg(max_launch_attempts=2), injector=inj)
        chain = _chain2()
        ptss = [_pts(8) for _ in range(8)]    # one bucket: same structure/L
        for p in ptss:
            srv.submit(chain, p)
        out = srv.flush()
        assert len(out) == 8
        for i in range(8):
            if i == 3:
                assert isinstance(out[i], errors.LaunchError)
                assert serving.is_error(out[i]) and out[i].ticket == 3
            else:
                np.testing.assert_allclose(
                    out[i], np.asarray(chain.apply(ptss[i])),
                    rtol=1e-6, atol=1e-6)
        assert serving.stats["bisections"] == 3   # 8 -> 4 -> 2 -> 1
        assert serving.stats["failed_requests"] == 1
        assert serving.stats["recovered_requests"] == 7
        rep = srv.last_report[0]
        assert rep.bisections == 3 and rep.failed_requests == 1

    @pytest.mark.parametrize("backend,fault", [
        ("ref", "flaky"), ("interpret", "backend"), ("ref", "poison")])
    def test_resident_bucket_recovers_with_equal_results(self, backend,
                                                        fault):
        """A resident bucket whose launch the injector fails walks the
        ladder like any other: rebuilt from the handle's host snapshot,
        re-bound, and equal to a clean flush of the same requests on
        the rung it lands on."""
        from repro import graphics
        cam = graphics.Camera(eye=(0.3, 0.4, 3.0), target=(0.0, 0.0, 0.0),
                              up=(0.0, 1.0, 0.0), fov_y=0.9, aspect=1.0,
                              near=0.1, far=10.0)
        chains = [graphics.viewing_chain(
            3, model=tc.TransformChain.identity(3).rotate(0.3 * i, axis=1),
            camera=cam, viewport=graphics.Viewport(width=32.0, height=32.0))
            for i in range(4)]
        mesh = _pts(200, 3)
        # the degraded bucket lands on ref: compare with ref's own flush
        clean = _fresh(backend="ref" if fault == "backend" else backend)
        handle = clean.upload(mesh)
        want = clean.serve((c, handle) for c in chains)
        inj = faults.FaultInjector(**{
            "flaky": dict(flaky_tickets=frozenset({0, 1, 2, 3}),
                          flaky_attempts=1),
            "backend": dict(backend_tickets=frozenset({1})),
            "poison": dict(poison_tickets=frozenset({2}))}[fault])
        srv = _fresh(backend=backend, injector=inj,
                     fault_config=_cfg(max_launch_attempts=2))
        handle = srv.upload(mesh)
        got = srv.serve((c, handle) for c in chains)
        assert serving.stats["launch_failures"] >= 1
        for i, (a, b) in enumerate(zip(got, want, strict=True)):
            if fault == "poison" and i == 2:
                assert isinstance(a, errors.LaunchError) and a.ticket == 2
                continue
            assert np.array_equal(a, b) and np.array_equal(a.mask, b.mask)
        assert serving.stats["uploads"] == 1      # recovery is no upload
        if fault == "backend":
            assert srv.last_report[0].final_backend == "ref"
        if fault == "poison":
            assert serving.stats["recovered_requests"] == 3

    @pytest.mark.parametrize("fault", ["flaky", "poison"])
    def test_merged_bucket_recovers_both_structures(self, fault):
        """A bucket holding two structures of one plan identity fails its
        launch: recovery serves every request of both structures, and a
        poison request whose recovery is exhausted resolves to its own
        LaunchError."""
        inj = faults.FaultInjector(**{
            "flaky": dict(flaky_tickets=frozenset({5}), flaky_attempts=1),
            "poison": dict(poison_tickets=frozenset({5}))}[fault])
        srv = _fresh(backend="ref",
                     fault_config=_cfg(max_launch_attempts=2), injector=inj)
        other = tc.TransformChain.identity(2).scale(2.0, 0.5) \
            .translate(-1.0, 0.25).scale(0.5, 1.5)
        chains = [_chain2(), other] * 4
        assert chains[0].structure != chains[1].structure
        ptss = [_pts(8) for _ in chains]
        for chain, p in zip(chains, ptss):
            srv.submit(chain, p)
        out = srv.flush()
        (rep,) = srv.last_report
        assert rep.structure == "2D:diag" and rep.requests == 8
        assert serving.stats["bucket_structures"] == 2
        for i, (chain, p) in enumerate(zip(chains, ptss)):
            if fault == "poison" and i == 5:
                assert isinstance(out[i], errors.LaunchError)
                assert out[i].ticket == 5
                continue
            np.testing.assert_allclose(out[i], np.asarray(chain.apply(p)),
                                       rtol=1e-6, atol=1e-6)
        if fault == "flaky":
            assert serving.stats["recovered_requests"] == 8
            assert serving.stats["failed_requests"] == 0
        else:
            assert serving.stats["recovered_requests"] == 7
            assert serving.stats["failed_requests"] == 1
            assert rep.bisections == 3 and rep.failed_requests == 1

    def test_failed_bucket_never_touches_its_neighbours(self):
        """Bucket isolation: a poisoned bucket recovers/fails alone; the
        other bucket completes with exactly its one clean launch."""
        inj = faults.FaultInjector(poison_tickets=frozenset({0}))
        srv = _fresh(backend="ref",
                     fault_config=_cfg(max_launch_attempts=2), injector=inj)
        poisoned_chain, clean_chain = _chain2(), \
            tc.TransformChain.identity(3).translate(1.0, 2.0, 3.0)
        srv.submit(poisoned_chain, _pts())            # ticket 0: poison
        clean_pts = _pts(8, 3)
        srv.submit(clean_chain, clean_pts)            # different bucket
        out = srv.flush()
        assert isinstance(out[0], errors.LaunchError)
        np.testing.assert_allclose(
            out[1], np.asarray(clean_chain.apply(clean_pts)),
            rtol=1e-6, atol=1e-6)
        clean_rep = [r for r in srv.last_report
                     if r.structure.startswith("3D")][0]
        assert clean_rep.launches == 1 and clean_rep.failed_requests == 0

    def test_failed_shard_does_not_orphan_sibling_shards(self):
        """Satellite: oversized-bucket sharding under failure.  12 equal
        requests shard into 4 launches; a poison in one shard must not
        lose any other shard's results."""
        inj = faults.FaultInjector(poison_tickets=frozenset({4}))
        srv = _fresh(backend="ref",
                     fault_config=_cfg(max_launch_attempts=2), injector=inj,
                     max_points_per_launch=3 * 128)
        chain = _chain2()
        ptss = [_pts(100) for _ in range(12)]
        for p in ptss:
            srv.submit(chain, p)
        out = srv.flush()
        rep = srv.last_report[0]
        assert serving.stats["shards"] == 3   # 4 launches = 1 + 3 shards
        for i in range(12):
            if i == 4:
                assert isinstance(out[i], errors.LaunchError)
            else:
                np.testing.assert_allclose(
                    out[i], np.asarray(chain.apply(ptss[i])),
                    rtol=1e-6, atol=1e-6)
        # only the poisoned shard (3 requests) went through recovery
        assert serving.stats["recovered_requests"] == 2
        assert serving.stats["failed_requests"] == 1
        assert rep.failed_requests == 1

    def test_injected_fault_counts_as_launch_failure_not_launch(self):
        """An injector-blocked attempt never dispatched: stats['launches']
        counts only real dispatches, so clean-run launch counts are
        unchanged by the hooks existing."""
        inj = faults.FaultInjector(flaky_tickets=frozenset({0}),
                                   flaky_attempts=1)
        srv = _fresh(backend="ref", fault_config=_cfg(), injector=inj)
        srv.submit(_chain2(), _pts())
        srv.flush()
        # attempt 0 blocked (no dispatch), attempt 1 dispatched
        assert serving.stats["launches"] == 1
        assert serving.stats["launch_failures"] == 1
        assert sum(r.launches for r in srv.last_report) == \
            serving.stats["launches"]


# ---------------------------------------------------------------------------
# the copy back started at dispatch, under failure
# ---------------------------------------------------------------------------

class TestPrefetchUnderFaults:
    def test_launch_failing_at_dispatch_is_not_prefetched(self, host_copies):
        """Ticket 0's first attempt is blocked before it reaches the
        device: phase 1 prefetches only the other bucket, and recovery
        unpacks its relaunch at once, without a prefetch."""
        inj = faults.FaultInjector(flaky_tickets=frozenset({0}),
                                   flaky_attempts=1)
        srv = _fresh(backend="ref", fault_config=_cfg(), injector=inj)
        chain3 = tc.TransformChain.identity(3).translate(1.0, 2.0, 3.0)
        pts2, pts3 = _pts(), _pts(8, 3)
        srv.submit(_chain2(), pts2)
        srv.submit(chain3, pts3)
        out = srv.flush()
        np.testing.assert_allclose(out[0], np.asarray(_chain2().apply(pts2)),
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(out[1], np.asarray(chain3.apply(pts3)),
                                   rtol=1e-6, atol=1e-6)
        assert serving.stats["launches"] == 2       # bucket 1, recovery
        assert serving.stats["prefetches"] == 1
        assert host_copies == [(1, 8, 3)]           # bucket 1's (B, L, d)
        assert srv.metrics.value("prefetches") == 1

    def test_corruption_still_caught_after_prefetch(self, host_copies):
        """The corrupted launch dispatches and is prefetched; the finite
        check at unpack still sees the corrupted copy and recovery
        re-packs from the pristine host copy."""
        inj = faults.FaultInjector(corrupt_tickets=frozenset({0}))
        srv = _fresh(backend="ref", fault_config=_cfg(), injector=inj)
        chain, pts = _chain2(), _pts()
        srv.submit(chain, pts)
        (out,) = srv.flush()
        np.testing.assert_allclose(out, np.asarray(chain.apply(pts)),
                                   rtol=1e-6, atol=1e-6)
        assert inj.injected_corruptions == 1
        assert serving.stats["launch_failures"] == 1
        assert serving.stats["recovered_requests"] == 1
        assert serving.stats["launches"] == 2
        assert serving.stats["prefetches"] == len(host_copies) == 1

    def test_failing_neighbour_keeps_prefetched_results(self):
        """A poisoned bucket among three clean ones (diag, matrix,
        projective): every clean bucket's prefetched result comes back
        intact and only the dispatched launches count as prefetched."""
        inj = faults.FaultInjector(poison_tickets=frozenset({0}))
        srv = _fresh(backend="ref",
                     fault_config=_cfg(max_launch_attempts=2), injector=inj)
        rng = np.random.default_rng(61)
        clean = [(workload.chain_for(rng, d, kinds), _pts(12, d))
                 for d, kinds in ((2, "TST"), (3, "TRS"), (3, "MPC"))]
        srv.submit(_chain2(), _pts())                 # ticket 0: poison
        for chain, pts in clean:
            srv.submit(chain, pts)
        out = srv.flush()
        assert isinstance(out[0], errors.LaunchError)
        for (chain, pts), got in zip(clean, out[1:]):
            if chain.is_projective:
                exp, mask = chain.project(pts)
                np.testing.assert_array_equal(got.mask, np.asarray(mask))
            else:
                exp = chain.apply(pts)
            np.testing.assert_allclose(got, np.asarray(exp),
                                       rtol=1e-5, atol=1e-5)
        assert serving.stats["prefetches"] == 3
        assert sum(r.launches for r in srv.last_report
                   if r.failed_requests == 0) == 3

    def test_prefetch_that_raises_is_a_failed_launch(self, monkeypatch):
        """A copy that cannot start fails its launch like a failed
        dispatch: recovery serves it and the siblings are untouched."""
        real = engine._prefetch
        seen = []

        def flaky_prefetch(out):
            seen.append(out)
            if len(seen) == 1:
                raise RuntimeError("copy refused")
            real(out)
        monkeypatch.setattr(engine, "_prefetch", flaky_prefetch)
        srv = _fresh(backend="ref", fault_config=_cfg())
        chain3 = tc.TransformChain.identity(3).translate(1.0, 2.0, 3.0)
        pts2, pts3 = _pts(), _pts(8, 3)
        srv.submit(_chain2(), pts2)
        srv.submit(chain3, pts3)
        out = srv.flush()
        np.testing.assert_allclose(out[0], np.asarray(_chain2().apply(pts2)),
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(out[1], np.asarray(chain3.apply(pts3)),
                                   rtol=1e-6, atol=1e-6)
        assert serving.stats["launch_failures"] == 1
        assert serving.stats["recovered_requests"] == 1
        assert serving.stats["launches"] == 3
        assert serving.stats["prefetches"] == 1


# ---------------------------------------------------------------------------
# the chaos soak harness
# ---------------------------------------------------------------------------

class TestChaosSoak:
    def test_soak_zero_lost_and_deterministic(self):
        serving.reset_stats()
        serving.clear_plan_cache()
        a = faults.run_chaos_soak(seed=1, n_requests=32)
        b = faults.run_chaos_soak(seed=1, n_requests=32)
        assert a.lost == 0 and a.mismatches == 0
        assert a.counters() == b.counters()
        # the soak actually exercised the machinery it claims to gate
        assert a.rejected_at_submit == a.malformed > 0
        assert a.launch_failures > 0 and a.q_fallbacks == 1
        assert a.resolved + a.failed_requests == a.requests

    def test_soak_seeds_differ(self):
        serving.reset_stats()
        serving.clear_plan_cache()
        a = faults.run_chaos_soak(seed=1, n_requests=32)
        b = faults.run_chaos_soak(seed=2, n_requests=32)
        assert a.lost == b.lost == 0
        assert a.counters() != b.counters()

    def test_roles_are_pure_function_of_seed_and_ticket(self):
        i1 = faults.FaultInjector(seed=9, flaky_rate=0.2, backend_rate=0.2,
                                  corrupt_rate=0.2, poison_rate=0.2)
        i2 = faults.FaultInjector(seed=9, flaky_rate=0.2, backend_rate=0.2,
                                  corrupt_rate=0.2, poison_rate=0.2)
        roles = [i1.role(t) for t in range(200)]
        assert roles == [i2.role(t) for t in range(200)]
        assert len({r for r in roles if r}) == 4   # all roles drawn

    def test_mixed_lane_workload_shape(self):
        triples = workload.mixed_lane_workload(3, 40, q_fraction=0.5)
        assert len(triples) == 40
        q = [t for t in triples if t[2] is not None]
        assert 0 < len(q) < 40
        assert all(not c.is_projective for c, _, f in q)
