"""Fixed-point (Qm.n) lane tests: the numpy Q oracle vs the jnp ref twin
vs the Pallas kernels (bit-exact everywhere -- integer arithmetic), the
M1-emulator parity on the paper's Composite I/II programs, the per-chain
quantisation error bound (hypothesis-guarded property tests plus a
deterministic seeded sweep), and the lane end-to-end through the chain
compiler and the serving engine (where packed-vs-apply equality is
BITWISE, a stronger contract than the float lane's 1-ULP one).

``hypothesis`` is an OPTIONAL dependency (see tests/README.md): the
property tests below are skipped without it; the seeded sweeps of the
same invariants always run.
"""
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                      # optional dep -- skip, don't fail
    HAVE_HYPOTHESIS = False

    class _NoStrategies:
        def __getattr__(self, _name):
            return lambda *a, **k: None

    st = _NoStrategies()

    def settings(*_a, **_k):
        return lambda f: f

    def given(*_a, **_k):
        return lambda f: pytest.mark.skip(
            reason="hypothesis not installed (optional dep)")(f)

from repro import kernels, quantize, serving
from repro.core import transform_chain as tc
from repro.core.morphosys import programs
from repro.kernels import opcount
from repro.kernels.fixedpoint import ref as qref
from repro.quantize import Q8_7, Q15_0
from repro.serving import workload

RNG = np.random.default_rng(1904)

AFFINE_TEMPLATES = workload.AFFINE_TEMPLATES


def random_affine_chain(rng):
    dim, kinds = AFFINE_TEMPLATES[int(rng.integers(len(AFFINE_TEMPLATES)))]
    return workload.chain_for(rng, dim, kinds)


# ---------------------------------------------------------------------------
# formats + converters
# ---------------------------------------------------------------------------

class TestQFormat:
    def test_parse_names(self):
        fmt = quantize.as_qformat("q8.7")
        assert (fmt.m, fmt.n, fmt.name, fmt.scale) == (8, 7, "q8.7", 128)
        assert quantize.as_qformat(fmt) is fmt
        assert quantize.as_qformat("q15.0").n == 0

    @pytest.mark.parametrize("bad", ["q8.8", "q9.7", "float32", "q-1.16",
                                     "8.7", 87, None])
    def test_rejects_non_formats(self, bad):
        assert not quantize.is_qformat(bad)
        with pytest.raises(ValueError):
            quantize.as_qformat(bad)

    def test_quantize_roundtrip_exact_on_grid(self):
        # values on the Qm.n grid survive a quantize/dequantize round trip
        words = RNG.integers(-(1 << 15), 1 << 15, 256).astype(np.int16)
        vals = Q8_7.dequantize(words)
        assert (Q8_7.quantize(vals) == words).all()

    def test_quantize_saturates(self):
        assert Q8_7.quantize(1e6) == 32767
        assert Q8_7.quantize(-1e6) == -32768

    def test_jnp_quantizer_matches_numpy(self):
        x = RNG.uniform(-300, 300, 512).astype(np.float32)
        assert (np.asarray(Q8_7.quantize_jnp(x)) == Q8_7.quantize(x)).all()


# ---------------------------------------------------------------------------
# kernel bit-exactness vs the numpy Q oracle
# ---------------------------------------------------------------------------

def _rand_words(shape, rng=RNG):
    return rng.integers(-(1 << 15), 1 << 15, shape).astype(np.int16)


class TestKernelsBitExact:
    """Every execution path of the lane computes the SAME int16 words:
    int32 MAC + one rounding shift + wrap is exact and order-independent,
    so numpy oracle == jnp ref == Pallas (interpret) bit-for-bit --
    including full-range inputs where the arithmetic wraps."""

    @pytest.mark.parametrize("d", [2, 3])
    @pytest.mark.parametrize("n_frac", [0, 7])
    def test_diag_paths_agree(self, d, n_frac):
        p = _rand_words((137, d))
        s, t = _rand_words(d), _rand_words(d)
        want = qref.np_chain_diag_q(p, s, t, n_frac)
        for backend in ("ref", "interpret"):
            got = np.asarray(kernels.chain_diag_q(
                jnp.asarray(p), s, t, n_frac=n_frac, backend=backend))
            np.testing.assert_array_equal(got, want, err_msg=backend)

    @pytest.mark.parametrize("d", [2, 3])
    @pytest.mark.parametrize("n_frac", [0, 7])
    def test_matrix_paths_agree(self, d, n_frac):
        p = _rand_words((91, d))
        a, t = _rand_words((d, d)), _rand_words(d)
        want = qref.np_chain_matrix_q(p, a, t, n_frac)
        for backend in ("ref", "interpret"):
            got = np.asarray(kernels.chain_apply_q(
                jnp.asarray(p), a, t, n_frac=n_frac, backend=backend))
            np.testing.assert_array_equal(got, want, err_msg=backend)

    @pytest.mark.parametrize("backend", ["ref", "interpret"])
    def test_batch_equals_per_request(self, backend):
        b, lpad, d = 6, 24, 3
        pts3 = _rand_words((b, lpad, d))
        a, t = _rand_words((b, d, d)), _rand_words((b, d))
        batched = np.asarray(kernels.chain_apply_batch_q(
            jnp.asarray(pts3), a, t, n_frac=7, backend=backend))
        for i in range(b):
            np.testing.assert_array_equal(
                batched[i], qref.np_chain_matrix_q(pts3[i], a[i], t[i], 7))
        s = _rand_words((b, d))
        batched = np.asarray(kernels.chain_diag_batch_q(
            jnp.asarray(pts3), s, t, n_frac=7, backend=backend))
        for i in range(b):
            np.testing.assert_array_equal(
                batched[i], qref.np_chain_diag_q(pts3[i], s[i], t[i], 7))

    def test_rejects_unquantised_operands(self):
        with pytest.raises(TypeError, match="int16"):
            kernels.chain_diag_q(jnp.ones((4, 2), jnp.float32),
                                 jnp.ones(2), jnp.ones(2), n_frac=7)


# ---------------------------------------------------------------------------
# M1 emulator parity: the paper's Composite I/II programs
# ---------------------------------------------------------------------------

class TestEmulatorParity:
    """At n = 0 the lane IS the emulator's integer datapath (int16
    wrap-around is a ring homomorphism: accumulating in int32 and
    wrapping once equals the M1 ALU's per-step wrap), so the Composite
    I/II outputs match EXACTLY; with fraction bits the lane's single
    requantising shift relates it to the raw emulator accumulator by an
    exact integer identity, asserted below."""

    def test_composite_i_exact_q0(self):
        # Composite I: scaling then translation, q = c*u + v -- run as
        # the two chained M1 routines (Tables 1-2) on one 64-vector
        rng = np.random.default_rng(41)
        u = rng.integers(-30000, 30000, 64).astype(np.int16)
        v2 = rng.integers(-30000, 30000, 2).astype(np.int16)
        c = 5
        scaled = programs.run_scaling(u, c)
        emu = programs.run_translation(scaled.values, np.tile(v2, 32)).values
        chain = (tc.TransformChain.identity(2)
                 .scale(float(c)).translate(float(v2[0]), float(v2[1])))
        for backend in ("ref", "interpret"):
            ours = np.asarray(chain.apply(
                jnp.asarray(u.reshape(32, 2).astype(np.float32)),
                backend=backend, dtype="q15.0"))
            np.testing.assert_array_equal(ours.reshape(-1), emu,
                                          err_msg=backend)

    @pytest.mark.parametrize("theta", [0.35, -1.1, 2.4])
    def test_composite_ii_exact_q0(self, theta):
        # Composite II: 2x2 fixed-point rotation of 8 points (the
        # paper's 16-element case), integer coefficients
        c = int(np.round(np.cos(theta) * 127))
        s = int(np.round(np.sin(theta) * 127))
        rng = np.random.default_rng(int(abs(theta) * 100))
        pts = rng.integers(-90, 91, (2, 8)).astype(np.int16)
        emu = programs.run_rotation_points((c, s), pts).values
        # emulator [[c,-s],[s,c]] @ column-points == row-points @
        # [[c,s],[-s,c]] (same convention note as the Q7 cross-check)
        chain = tc.TransformChain.identity(2).matrix(
            np.array([[c, s], [-s, c]], np.float32))
        for backend in ("ref", "interpret"):
            ours = np.asarray(chain.apply(
                jnp.asarray(pts.T.astype(np.float32)),
                backend=backend, dtype="q15.0")).T
            np.testing.assert_array_equal(ours, emu, err_msg=backend)

    @pytest.mark.parametrize("theta", [0.35, -1.1, 2.4])
    def test_composite_ii_q8_7_shift_identity(self, theta):
        # with fraction bits: the lane's output is EXACTLY the emulator's
        # raw Q14 accumulator put through the one requantising shift
        # (no wrap here: |coef| <= 127, |word| <= 127 -> |acc| < 2^15)
        cq = int(np.round(np.cos(theta) * 128))
        sq = int(np.round(np.sin(theta) * 128))
        assert max(abs(cq), abs(sq)) <= 127   # 8-bit context immediates
        rng = np.random.default_rng(int(abs(theta) * 100) + 1)
        words = rng.integers(-127, 128, (2, 8)).astype(np.int16)
        emu = programs.run_rotation_points((cq, sq), words).values
        chain = tc.TransformChain.identity(2).matrix(
            np.array([[cq, sq], [-sq, cq]], np.float32) / 128.0)
        ours = np.asarray(chain.apply(jnp.asarray(words.T), backend="ref",
                                      dtype="q8.7")).T
        np.testing.assert_array_equal(
            ours.astype(np.int32), (emu.astype(np.int32) + 64) >> 7)


# ---------------------------------------------------------------------------
# the per-chain quantisation error bound
# ---------------------------------------------------------------------------

def _assert_bound_holds(chain, pts, fmt):
    """The lane's dequantised result sits within ``error_bound`` of the
    exact (float64) evaluation of the float32 fold, whenever ``fits``."""
    kind = chain.plan_kind
    folded = chain.fold()
    x_max = float(np.abs(pts).max())
    if not quantize.fits(folded, kind, fmt, x_max):
        return False
    got = np.asarray(chain.apply(jnp.asarray(pts), backend="ref",
                                 dtype=fmt.name))
    if kind == "diag":
        s, t = folded
        exact = pts.astype(np.float64) * s.astype(np.float64) \
            + t.astype(np.float64)
    else:
        a, t = folded
        exact = pts.astype(np.float64) @ a.astype(np.float64) \
            + t.astype(np.float64)
    bound = quantize.error_bound(folded, kind, fmt, x_max)
    assert (np.abs(got - exact) <= bound).all(), (
        np.abs(got - exact).max(axis=0), bound)
    return True


class TestErrorBound:
    def test_seeded_sweep_2d_3d(self):
        rng = np.random.default_rng(7)
        checked = 0
        for i in range(60):
            dim, kinds = AFFINE_TEMPLATES[i % len(AFFINE_TEMPLATES)]
            chain = workload.chain_for(rng, dim, kinds)
            pts = rng.uniform(-4, 4, (int(rng.integers(1, 80)),
                                      dim)).astype(np.float32)
            checked += _assert_bound_holds(chain, pts, Q8_7)
        assert checked >= 40          # fits() must not be vacuous

    @pytest.mark.skipif(not HAVE_HYPOTHESIS,
                        reason="hypothesis not installed (optional dep)")
    @settings(max_examples=40, deadline=None)
    @given(st.integers(0, 2 ** 31 - 1), st.sampled_from([2, 3]),
           st.integers(1, 5), st.integers(1, 48))
    def test_property_random_chains(self, seed, dim, length, n_points):
        rng = np.random.default_rng(seed)
        prims = "".join(rng.choice(list("TSRAM"), length))
        chain = workload.chain_for(rng, dim, prims)
        pts = rng.uniform(-4, 4, (n_points, dim)).astype(np.float32)
        _assert_bound_holds(chain, pts, Q8_7)

    def test_bound_generalises_q7_rotation_bound(self):
        # the historical Q7 cross-check bound (0.5*(|x|+|y|)/127) has the
        # same shape as error_bound's matrix form: half an ulp times the
        # coefficient-column mass plus the input mass
        chain = tc.TransformChain.identity(2).rotate(0.3)
        bound = quantize.error_bound(chain.fold(), "matrix", Q8_7, 90.0)
        # rows of a rotation have unit mass; d*x_max dominates
        assert (bound > 0.5 * 90.0 / 128).all()
        assert (bound < 2.0 * (90.0 + 2) / 128).all()

    def test_fits_rejects_overflow(self):
        chain = tc.TransformChain.identity(2).scale(200.0).translate(200.0)
        assert not quantize.fits(chain.fold(), "diag", Q8_7, 4.0)
        assert quantize.fits(chain.fold(), "diag", Q15_0, 4.0)


# ---------------------------------------------------------------------------
# the lane through the chain compiler
# ---------------------------------------------------------------------------

class TestChainCompilerLane:
    def test_apply_matches_oracle_bitwise(self):
        rng = np.random.default_rng(17)
        for _ in range(8):
            chain = random_affine_chain(rng)
            pts = rng.uniform(-3, 3, (50, chain.dim)).astype(np.float32)
            words = Q8_7.quantize(pts)
            got = np.asarray(chain.apply(jnp.asarray(words), backend="ref",
                                         dtype="q8.7"))
            folded_q = quantize.quantize_fold(chain.fold(), chain.plan_kind,
                                              Q8_7)
            if chain.plan_kind == "diag":
                want = qref.np_chain_diag_q(words, *folded_q, 7)
            else:
                want = qref.np_chain_matrix_q(words, *folded_q, 7)
            np.testing.assert_array_equal(got, want)

    def test_float_in_float32_out_int16_in_int16_out(self):
        chain = tc.TransformChain.identity(2).scale(1.5).translate(0.5)
        pts = RNG.uniform(-2, 2, (9, 2)).astype(np.float32)
        out_f = chain.apply(jnp.asarray(pts), backend="ref", dtype="q8.7")
        assert np.asarray(out_f).dtype == np.float32
        out_q = chain.apply(jnp.asarray(Q8_7.quantize(pts)), backend="ref",
                            dtype="q8.7")
        assert np.asarray(out_q).dtype == np.int16
        np.testing.assert_array_equal(Q8_7.quantize(np.asarray(out_f)),
                                      np.asarray(out_q))

    def test_plan_cache_no_retrace(self):
        chain = tc.TransformChain.identity(3).scale(1.1).rotate(0.4, axis=0)
        pts = RNG.uniform(-2, 2, (32, 3)).astype(np.float32)
        tc.reset_stats()
        chain.apply(jnp.asarray(pts), backend="ref", dtype="q8.7")
        assert tc.stats["compiles"] == 1
        first_traces = tc.stats["traces"]
        # same structure, fresh parameters: cache hit, no retrace
        chain2 = tc.TransformChain.identity(3).scale(0.7).rotate(1.2, axis=0)
        chain2.apply(jnp.asarray(pts), backend="ref", dtype="q8.7")
        assert tc.stats["compiles"] == 1 and tc.stats["hits"] >= 1
        assert tc.stats["traces"] == first_traces
        # the float lane compiles its OWN plan for the same structure
        chain.apply(jnp.asarray(pts), backend="ref")
        assert tc.stats["compiles"] == 2

    def test_projective_rejected_everywhere(self):
        proj = (tc.TransformChain.identity(3)
                .projective(np.eye(4, dtype=np.float32)).cull())
        pts = RNG.uniform(-1, 1, (5, 3)).astype(np.float32)
        with pytest.raises(ValueError, match="fixed-point"):
            proj.apply(jnp.asarray(pts), dtype="q8.7")
        with pytest.raises(ValueError, match="fixed-point"):
            proj.project(jnp.asarray(pts), dtype="q8.7")
        srv = serving.GeometryServer(backend="ref")
        with pytest.raises(ValueError, match="fixed-point"):
            srv.submit(proj, pts, qformat="q8.7")
        # affine chains project trivially on the q lane: mask all-True
        aff = tc.TransformChain.identity(3).scale(2.0)
        out, mask = aff.project(jnp.asarray(pts), backend="ref",
                                dtype="q8.7")
        assert mask.all() and out.shape == pts.shape

    def test_traced_params_rejected(self):
        import jax
        pts = jnp.zeros((4, 2), jnp.float32)

        def f(theta):
            c = tc.TransformChain.identity(2).rotate(theta)
            return c.apply(pts, dtype="q8.7").sum()

        with pytest.raises(NotImplementedError):
            jax.jit(f)(jnp.float32(0.3))

    def test_byte_accounting_halves(self):
        chain = tc.TransformChain.identity(2).scale(1.2).rotate(0.5)
        pts = RNG.uniform(-2, 2, (256, 2)).astype(np.float32)
        with opcount.counting() as rec_f:
            chain.apply(jnp.asarray(pts), backend="ref")
        with opcount.counting() as rec_q:
            chain.apply(jnp.asarray(pts), backend="ref", dtype="q8.7")
        (f_name, f_bytes), = rec_f
        (q_name, q_bytes), = rec_q
        assert f_name == "chain_fused_matrix"
        assert q_name == "chain_fused_matrix_q"
        assert q_bytes * 2 == f_bytes
        assert f_bytes == opcount.fused_chain_bytes(256, 2, kind="matrix")
        assert q_bytes == opcount.fused_chain_bytes(256, 2, kind="matrix",
                                                    itemsize=2)


# ---------------------------------------------------------------------------
# the lane through the serving engine
# ---------------------------------------------------------------------------

class TestServingLane:
    def test_packed_equals_apply_bitwise(self):
        # integer arithmetic: the q lane's packed-vs-apply equality is
        # EXACT on every plan kind (the float lane's 1-ULP matrix-plan
        # exception does not exist here)
        reqs = workload.random_workload(seed=23, n_requests=24,
                                        max_points=96,
                                        templates=AFFINE_TEMPLATES)
        srv = serving.GeometryServer(backend="ref")
        results = srv.serve(reqs, qformat="q8.7")
        for (chain, pts), got in zip(reqs, results):
            want = np.asarray(chain.apply(jnp.asarray(pts), backend="ref",
                                          dtype="q8.7"))
            np.testing.assert_array_equal(got, want)
            assert got.dtype == np.float32

    def test_mixed_submissions_share_bucket(self):
        chain = tc.TransformChain.identity(2).scale(1.3).translate(0.5)
        pts = RNG.uniform(-2, 2, (20, 2)).astype(np.float32)
        srv = serving.GeometryServer(backend="ref")
        serving.reset_stats()
        srv.submit(chain, pts, qformat="q8.7")
        srv.submit(chain, Q8_7.quantize(pts), qformat="q8.7")
        out_f, out_q = srv.flush()
        assert serving.stats["launches"] == 1
        assert out_f.dtype == np.float32 and out_q.dtype == np.int16
        np.testing.assert_array_equal(Q8_7.quantize(out_f), out_q)

    def test_q_and_float_lanes_bucket_separately(self):
        chain = tc.TransformChain.identity(2).scale(1.3)
        pts = RNG.uniform(-2, 2, (16, 2)).astype(np.float32)
        srv = serving.GeometryServer(backend="ref")
        serving.reset_stats()
        srv.submit(chain, pts)
        srv.submit(chain, pts, qformat="q8.7")
        srv.flush()
        assert serving.stats["launches"] == 2
        assert serving.stats["buckets"] == 2

    def test_packed_byte_accounting_uses_2byte_words(self):
        chain = tc.TransformChain.identity(2).scale(1.3).rotate(0.2)
        pts = RNG.uniform(-2, 2, (16, 2)).astype(np.float32)
        srv = serving.GeometryServer(backend="ref")
        with opcount.counting() as rec:
            srv.submit(chain, pts, qformat="q8.7")
            srv.flush()
        (name, nbytes), = [r for r in rec if r[0].startswith("serve_")]
        lpad = serving.padded_length(16, min_len=srv.min_len,
                                     waste_cap=srv.waste_cap)
        assert nbytes == opcount.packed_chain_bytes(1, lpad, 2, itemsize=2,
                                                    kind="matrix")

    def test_q8_7_moves_half_the_f32_bytes_on_the_same_requests(self):
        # one seeded affine workload on both lanes: the same launches,
        # each moving 2-byte words where the float lane moves 4-byte ones
        reqs = workload.random_workload(seed=2203, n_requests=32,
                                        max_points=512,
                                        templates=AFFINE_TEMPLATES)

        def serve(qformat):
            serving.reset_stats()
            with opcount.counting() as rec:
                serving.GeometryServer(backend="ref").serve(
                    reqs, qformat=qformat)
            return (serving.stats["launches"],
                    [b for name, b in rec if name.startswith("serve_")])

        launches_f, bytes_f = serve(None)
        launches_q, bytes_q = serve("q8.7")
        assert launches_q == launches_f == len(bytes_f) == len(bytes_q) > 1
        assert sorted(2 * b for b in bytes_q) == sorted(bytes_f)

    def test_identity_passes_and_empty_rejects(self):
        """PR 6: the q lane shares the submit boundary -- identity
        requests pass through, empty ones raise the typed error."""
        srv = serving.GeometryServer(backend="ref")
        pts = RNG.uniform(-1, 1, (4, 2)).astype(np.float32)
        t0 = srv.submit(tc.TransformChain.identity(2), pts, qformat="q8.7")
        with pytest.raises(serving.errors.EmptyPointsError):
            srv.submit(tc.TransformChain.identity(2).scale(2.0),
                       np.zeros((0, 2), np.float32), qformat="q8.7")
        res = srv.flush()
        np.testing.assert_array_equal(res[t0], pts)
        assert len(res) == 1


# ---------------------------------------------------------------------------
# graphics: affine viewing chains quantise, projective ones reject
# ---------------------------------------------------------------------------

class TestGraphicsLane:
    def test_affine_viewing_chain_quantises(self):
        from repro import graphics
        cam = graphics.Camera(eye=(0.0, 0.0, 5.0), target=(0.0, 0.0, 0.0))
        chain = graphics.viewing_chain(
            3, model=tc.TransformChain.identity(3).scale(0.5),
            camera=cam, projection=False, cull=False)
        assert not chain.is_projective and chain.plan_kind == "matrix"
        pts = RNG.uniform(-1, 1, (24, 3)).astype(np.float32)
        got = np.asarray(chain.apply(jnp.asarray(pts), backend="ref",
                                     dtype="q8.7"))
        folded = chain.fold()
        bound = quantize.error_bound(folded, "matrix", Q8_7, 1.0)
        exact = pts.astype(np.float64) @ folded[0].astype(np.float64) \
            + folded[1].astype(np.float64)
        assert (np.abs(got - exact) <= bound).all()

    def test_projective_viewing_chain_rejects(self):
        from repro import graphics
        cam = graphics.Camera(eye=(0.0, 0.0, 5.0), target=(0.0, 0.0, 0.0))
        chain = graphics.viewing_chain(3, camera=cam)
        pts = RNG.uniform(-1, 1, (8, 3)).astype(np.float32)
        with pytest.raises(ValueError, match="fixed-point"):
            chain.apply(jnp.asarray(pts), dtype="q8.7")


# ---------------------------------------------------------------------------
# autotune integration
# ---------------------------------------------------------------------------

class TestAutotuneIntegration:
    def test_defaults_exist_for_q_kernels(self):
        from repro.autotune import cache as tcache
        for k in ("chain_diag_q", "chain_apply_q", "chain_diag_batch_q",
                  "chain_apply_batch_q"):
            assert k in tcache.TUNABLE_KERNELS
            cfg = tcache.config_for(k, "ref", "q8.7", 1024)
            assert cfg.kernel == k and cfg.source == "default"

    def test_cost_model_halves_bytes(self):
        from repro.autotune import costmodel
        f32 = costmodel.chain_cost(4096, 3, "matrix")
        q = costmodel.chain_cost(4096, 3, "matrix_q")
        assert q.hbm_bytes * 2 == f32.hbm_bytes
        assert q.kernel == "chain_apply_q"
        pf = costmodel.packed_chain_cost(8, 64, 3, "diag")
        pq = costmodel.packed_chain_cost(8, 64, 3, "diag_q")
        assert pq.hbm_bytes * 2 == pf.hbm_bytes
        assert pq.kernel == "chain_diag_batch_q"

    def test_committed_cache_covers_q_lane(self):
        from repro.autotune import cache as tcache
        committed = tcache.TuningCache.load(tcache.DEFAULT_CACHE_PATH)
        assert committed.get("chain_diag_q", "ref", "q8.7", 2048) is not None
        assert committed.get("chain_apply_q", "ref", "q8.7",
                             2048) is not None
