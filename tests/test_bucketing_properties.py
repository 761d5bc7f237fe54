"""Property tests for the size-bucketing grid (``serving.bucketing``):
the documented guarantees -- result >= n, padding waste strictly under
the cap for any n >= min_len, power-of-two rungs at the default cap,
monotonicity in n, ``grid_for`` echoing explicit knobs -- checked over
randomised inputs with hypothesis, plus deterministic seeded sweeps of
the same invariants (and the q-lane size-class contract against the
engine's real bucket keys) that always run.

``hypothesis`` is an OPTIONAL dependency (see tests/README.md): the
property tests are skipped without it; the seeded sweeps always run.
"""
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

from repro import serving
from repro.kernels import dispatch
from repro.serving import bucketing, workload
from repro.serving.engine import GeometryServer


# ---------------------------------------------------------------------------
# hypothesis properties (skipped without the optional dep)
# ---------------------------------------------------------------------------

@settings(max_examples=200, deadline=None)
@given(n=st.integers(0, 5000),
       min_len=st.integers(1, 128),
       waste_cap=st.floats(0.05, 0.95))
def test_padded_length_bounds(n, min_len, waste_cap):
    lpad = bucketing.padded_length(n, min_len=min_len, waste_cap=waste_cap)
    assert lpad >= n
    assert lpad >= min_len
    if n >= min_len:
        # the documented contract: waste strictly under the cap
        assert bucketing.waste_fraction(n, lpad) < waste_cap
    else:
        # short requests pad to the grid floor -- the floor bounds them
        assert lpad == min_len


@settings(max_examples=200, deadline=None)
@given(n=st.integers(1, 5000))
def test_default_grid_is_pure_powers_of_two(n):
    """waste_cap=0.5 degenerates to doubling: every rung is
    min_len * 2**k (the paper's power-of-two frame-buffer banks)."""
    lpad = bucketing.padded_length(n)
    assert lpad % bucketing.MIN_LEN == 0
    rung = lpad // bucketing.MIN_LEN
    assert rung & (rung - 1) == 0        # a power of two


@settings(max_examples=100, deadline=None)
@given(n=st.integers(0, 3000),
       min_len=st.integers(1, 64),
       waste_cap=st.floats(0.05, 0.95))
def test_padded_length_monotone_in_n(n, min_len, waste_cap):
    """A longer request never gets a shorter pad (grids are ascending);
    equal-length requests always share a size class."""
    a = bucketing.padded_length(n, min_len=min_len, waste_cap=waste_cap)
    b = bucketing.padded_length(n + 1, min_len=min_len, waste_cap=waste_cap)
    assert b >= a
    assert bucketing.padded_length(n, min_len=min_len,
                                   waste_cap=waste_cap) == a


@settings(max_examples=50, deadline=None)
@given(min_len=st.integers(1, 256), waste_cap=st.floats(0.05, 0.95),
       n=st.integers(0, 4096))
def test_grid_for_echoes_explicit_knobs(min_len, waste_cap, n):
    """Explicit arguments always win over cache/defaults, and say so."""
    got = bucketing.grid_for("ref", min_len=min_len, waste_cap=waste_cap,
                             n=n)
    assert got == (min_len, waste_cap, "explicit")


# ---------------------------------------------------------------------------
# deterministic seeded sweeps of the same invariants (always run)
# ---------------------------------------------------------------------------

def test_padded_length_seeded_sweep():
    rng = np.random.default_rng(0xB0C5)
    for _ in range(500):
        n = int(rng.integers(0, 5000))
        min_len = int(rng.integers(1, 128))
        waste_cap = float(rng.uniform(0.05, 0.95))
        lpad = bucketing.padded_length(n, min_len=min_len,
                                       waste_cap=waste_cap)
        assert lpad >= max(n, min_len)
        if n >= min_len:
            assert bucketing.waste_fraction(n, lpad) < waste_cap
        nxt = bucketing.padded_length(n + 1, min_len=min_len,
                                      waste_cap=waste_cap)
        assert nxt >= lpad


def test_grid_source_labels():
    assert bucketing.grid_for("ref", min_len=8, waste_cap=0.5) \
        == (8, 0.5, "explicit")
    m, c, source = bucketing.grid_for("ref")
    assert (m, c) == (bucketing.MIN_LEN, bucketing.WASTE_CAP)
    assert source in ("default", "cached", "tuned")
    # one knob explicit, the other resolved
    m, c, source = bucketing.grid_for("ref", min_len=16)
    assert m == 16 and source.startswith("explicit+")


def test_q_lane_size_classes_match_float_lane():
    """A q8.7 and a float32 request of the same length land in the SAME
    size class (one grid for both lanes) but in DIFFERENT buckets keyed
    by the format name -- checked against the engine's real bucket keys.
    """
    serving.reset_stats()
    serving.clear_plan_cache()
    srv = GeometryServer(backend="ref")
    backend = dispatch.resolve(srv.backend)
    rng = np.random.default_rng(0xB0C6)
    chain = workload.chain_for(rng, 2, "TST")
    for n in (1, 7, 8, 9, 31, 32, 200):
        pts = rng.uniform(-1, 1, (n, 2)).astype(np.float32)
        pf = srv.validate(chain, pts)
        pq = srv.validate(chain, pts, qformat="q8.7")
        kf = srv._bucket_key(pf, backend)
        kq = srv._bucket_key(pq, backend)
        # same plan identity (dim, kind), same padded size class...
        assert kf[:2] == kq[:2] == (2, "diag") and kf[4] == kq[4]
        assert kf[4] == bucketing.padded_length(n)
        # ...different dtype lane: the format name, not the submit dtype
        assert kq[3] == "q8.7" and kf[3] != kq[3]
