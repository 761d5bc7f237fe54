"""Faithful-reproduction tests: M1 emulator + Intel cycle models vs paper."""
import importlib.util
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import transform_chain as tc
from repro.core.morphosys import intel, programs, rc_array


class TestContextWords:
    def test_published_add_word(self):
        # Table 1: Out = A + B  ->  0x0000F400
        assert rc_array.encode_context(rc_array.OP_ADD_AB) == 0xF400
        assert rc_array.decode_context(0x0000F400) == (rc_array.OP_ADD_AB, 0)

    def test_published_cmul_word(self):
        # Table 2: Out = 5 x A  ->  0x00009005
        assert rc_array.encode_context(rc_array.OP_CMUL, 5) == 0x9005
        assert rc_array.decode_context(0x00009005) == (rc_array.OP_CMUL, 5)

    def test_negative_immediate_roundtrip(self):
        word = rc_array.encode_context(rc_array.OP_CMAC, -4)
        assert rc_array.decode_context(word) == (rc_array.OP_CMAC, -4)


class TestCycleCounts:
    """Table 5 published cycle counts for routines with published listings."""

    @pytest.mark.parametrize("n,expected", [(8, 21), (64, 96)])
    def test_translation_cycles(self, n, expected):
        r = programs.run_translation(np.arange(n), np.arange(n))
        assert r.cycles == expected

    @pytest.mark.parametrize("n,expected", [(8, 14), (64, 55)])
    def test_scaling_cycles(self, n, expected):
        r = programs.run_scaling(np.arange(n), 5)
        assert r.cycles == expected

    def test_table1_structure(self):
        # Table 1 occupies instruction addresses 0..96 -> 97 instructions
        assert len(programs.translation_program(64)) == 97

    def test_table2_structure(self):
        # Table 2 occupies 0..55 -> 56 instructions
        assert len(programs.scaling_program(64)) == 56

    def test_matmul_reconstruction_cycles(self):
        """Paper reports 256 cycles but prints no listing; our overlapped
        reconstruction is 90 cycles (documented delta)."""
        a = np.ones((8, 8), np.int16)
        b = np.ones((8, 8), np.int16)
        assert programs.run_matmul(a, b).cycles == 90

    def test_composite_ii_reconstruction_cycles(self):
        pts = np.ones((2, 8), np.int16)
        assert programs.run_rotation_points((1, 1), pts).cycles == 25


class TestFunctionalCorrectness:
    def test_translation_values(self):
        rng = np.random.default_rng(0)
        for n in (8, 64):
            u = rng.integers(-30000, 30000, n)
            v = rng.integers(-30000, 30000, n)
            r = programs.run_translation(u, v)
            np.testing.assert_array_equal(
                r.values, programs.oracle_translation(u, v))

    def test_translation_wraps_int16(self):
        u = np.array([32767] * 8, np.int16)
        v = np.array([1] * 8, np.int16)
        r = programs.run_translation(u, v)
        assert (np.asarray(r.values) == -32768).all()

    def test_scaling_values(self):
        rng = np.random.default_rng(1)
        for n in (8, 64):
            u = rng.integers(-5000, 5000, n)
            r = programs.run_scaling(u, 5)
            np.testing.assert_array_equal(
                r.values, programs.oracle_scaling(u, 5))

    def test_matmul_values(self):
        rng = np.random.default_rng(2)
        for _ in range(5):
            a = rng.integers(-100, 100, (8, 8))
            b = rng.integers(-1000, 1000, (8, 8))
            r = programs.run_matmul(a, b)
            np.testing.assert_array_equal(r.values, programs.oracle_matmul(a, b))

    def test_rotation_points(self):
        rng = np.random.default_rng(3)
        pts = rng.integers(-100, 100, (2, 8))
        r = programs.run_rotation_points((3, 4), pts)
        rot = np.array([[3, -4], [4, 3]])
        np.testing.assert_array_equal(r.values, programs.oracle_matmul(rot, pts))

    @pytest.mark.parametrize("theta", [0.35, -1.1, 2.4])
    def test_rotation_points_match_chain_compiler_q7(self, theta):
        """Paper-fidelity cross-check: the M1 fixed-point rotation (Q7
        cos/sin, |coef| < 128 for the 8-bit context immediate) agrees
        with the chain compiler's rotation fold within quantization
        tolerance -- the emulator and the Pallas path compute the same
        transformation.

        Conventions line up exactly: the emulator's [[c,-s],[s,c]] @
        column-points equals the compiler's row-points @ [[c,s],[-s,c]].
        Integer products are exact in int16 (|x|,|y| < 91, |coef| < 128
        -> |sum| < 2*91*127 < 32767), so the ONLY error source is
        rounding cos/sin to Q7, bounded by 0.5*(|x|+|y|)/127 per
        coordinate."""
        scale = 127
        c = int(np.round(np.cos(theta) * scale))
        s = int(np.round(np.sin(theta) * scale))
        rng = np.random.default_rng(int(abs(theta) * 100))
        pts = rng.integers(-90, 91, (2, 8)).astype(np.int16)

        emu = programs.run_rotation_points((c, s), pts).values / scale

        chain = tc.TransformChain.identity(2).rotate(theta)
        ref = np.asarray(chain.apply(
            jnp.asarray(pts.T.astype(np.float32)), backend="ref")).T

        tol = 0.5 * np.abs(pts).sum(axis=0).max() / scale + 1e-3
        np.testing.assert_allclose(emu, ref, atol=tol)
        # and the interpret-mode Pallas kernel ties all three together
        pal = np.asarray(chain.apply(
            jnp.asarray(pts.T.astype(np.float32)), backend="interpret")).T
        np.testing.assert_allclose(emu, pal, atol=tol)


class TestIntelModels:
    """Tables 3-4 per-instruction clocks; n=64 translation totals are the
    paper's documented arithmetic slips."""

    @pytest.mark.parametrize("cpu,n,published,matches", [
        ("80486", 8, 90, True), ("80386", 8, 220, True),
        ("80486", 64, 769, False), ("80386", 64, 1723, False),
    ])
    def test_translation_model(self, cpu, n, published, matches):
        model = intel.translation_cycles(cpu, n)
        if matches:
            assert model == published
        else:  # slip: within 9% of published, per-instruction math exact
            assert abs(model - published) / published < 0.09

    @pytest.mark.parametrize("cpu,n,published", [
        ("80486", 8, 74), ("80386", 8, 172),
        ("80486", 64, 578), ("80386", 64, 1348),
    ])
    def test_scaling_model_exact(self, cpu, n, published):
        assert intel.scaling_cycles(cpu, n) == published

    def test_published_speedups(self):
        """Table 5 speedups = cycle ratios of its own published numbers."""
        for row in intel.PAPER_TABLE5:
            if row.speedup is None:
                continue
            m1 = intel.paper_row(row.algorithm, "m1", row.n_elements).cycles
            assert row.cycles / m1 == pytest.approx(row.speedup, rel=0.02)


def test_paper_tables_example_matches_the_paper():
    """``examples/paper_tables.py`` prints Tables 3-5: every cell is the
    paper's but the documented deltas -- Table 3's 64-element slips
    (the paper's own per-instruction clocks sum to 706 and 1732) and the
    two matrix routines the paper prints no listing for."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "examples", "paper_tables.py")
    spec = importlib.util.spec_from_file_location("paper_tables", path)
    tables = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tables)
    differs = {name: {(system, n): ours
                      for system, n, ours, paper in getattr(tables, name)()
                      if ours != paper}
               for name in ("table3", "table4", "table5_m1")}
    assert differs == {
        "table3": {("80486", 64): 706, ("80386", 64): 1732},
        "table4": {},
        "table5_m1": {("rotation_matmul", 64): 90, ("composite_ii", 16): 25},
    }
    assert len(tables.table5()) == len(intel.PAPER_TABLE5)
