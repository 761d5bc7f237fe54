"""Deterministic scheduler tests: VirtualClock semantics, the shared
nearest-rank percentile, the deadline-times-fill flush policy pinned
against hand-computed instants, per-tenant fairness under starvation,
typed backpressure rejection codes, and p50/p99 latency telemetry pinned
against hand-computed values on a fixed arrival script.

Everything here is exact (``==`` on floats): the clock is virtual, the
policy is arithmetic, and pinning the numbers is the point -- a
scheduler that can only be tested statistically is a scheduler whose
regressions ship.
"""
import math

import numpy as np
import pytest

from repro import serving
from repro.serving import workload
from repro.serving.admission import (AdmissionConfig, AdmissionController,
                                     QueueFullError, RateLimitError,
                                     TokenBucket)
from repro.serving.async_engine import AsyncGeometryServer, SLOConfig
from repro.serving.clock import MonotonicClock, VirtualClock, percentile


def _fresh_async(**kw):
    serving.reset_stats()
    serving.clear_plan_cache()
    kw.setdefault("clock", VirtualClock())
    return AsyncGeometryServer(**kw)


def _pts(rng, n, dim):
    return rng.uniform(-1, 1, (n, dim)).astype(np.float32)


# ---------------------------------------------------------------------------
# clocks
# ---------------------------------------------------------------------------

def test_virtual_clock_advances_only_on_request():
    clk = VirtualClock()
    assert clk.now() == 0.0
    assert clk.advance(1.5) == 1.5
    assert clk.now() == 1.5
    clk.sleep(0.5)
    assert clk.now() == 2.0
    clk.sleep(0.0)                      # no-op, not an error
    assert clk.now() == 2.0


def test_virtual_clock_never_rewinds():
    clk = VirtualClock(start=10.0)
    with pytest.raises(ValueError):
        clk.advance(-0.1)
    assert clk.advance_to(3.0) == 10.0   # past instants are a no-op
    assert clk.advance_to(12.5) == 12.5


def test_monotonic_clock_is_monotone():
    clk = MonotonicClock()
    a = clk.now()
    clk.sleep(0.001)
    assert clk.now() >= a


# ---------------------------------------------------------------------------
# the shared percentile definition (nearest rank)
# ---------------------------------------------------------------------------

def test_percentile_nearest_rank_pinned():
    xs = [4, 1, 3, 2]                   # order must not matter
    assert percentile(xs, 0) == 1
    assert percentile(xs, 25) == 1
    assert percentile(xs, 50) == 2
    assert percentile(xs, 75) == 3
    assert percentile(xs, 99) == 4
    assert percentile(xs, 100) == 4
    assert percentile([7.0], 50) == 7.0


def test_percentile_edge_cases():
    assert math.isnan(percentile([], 50))
    with pytest.raises(ValueError):
        percentile([1], 101)
    with pytest.raises(ValueError):
        percentile([1], -1)


# ---------------------------------------------------------------------------
# the deadline-times-fill flush policy, pinned
# ---------------------------------------------------------------------------

def test_deadline_shrinks_with_fill():
    """due_in = max_wait * (1 - fill) - age, hand-computed per submit."""
    rng = np.random.default_rng(0)
    chain = workload.chain_for(rng, 2, "TST")
    eng = _fresh_async(backend="ref",
                       slo=SLOConfig(max_wait_s=0.01, target_rows=4))
    eng.submit_async(chain, _pts(rng, 3, 2))
    assert eng.next_due_in() == pytest.approx(0.0075)   # fill 1/4
    eng.submit_async(chain, _pts(rng, 3, 2))
    assert eng.next_due_in() == pytest.approx(0.005)    # fill 2/4
    eng.submit_async(chain, _pts(rng, 3, 2))
    assert eng.next_due_in() == pytest.approx(0.0025)   # fill 3/4
    eng.submit_async(chain, _pts(rng, 3, 2))
    assert eng.next_due_in() == 0.0                     # full: due NOW
    assert eng.poll() == 4


def test_deadline_expiry_flushes_partial_bucket():
    rng = np.random.default_rng(1)
    chain = workload.chain_for(rng, 2, "TST")
    clk = VirtualClock()
    eng = _fresh_async(backend="ref", clock=clk,
                       slo=SLOConfig(max_wait_s=0.01, target_rows=4))
    t = eng.submit_async(chain, _pts(rng, 3, 2))
    clk.advance(0.0074)
    assert eng.poll() == 0              # 0.1 ms early: not due yet
    clk.advance(0.0001)
    assert eng.poll() == 1              # deadline 0.0075 reached
    assert t.latency == pytest.approx(0.0075)


def test_deadline_expiry_flush_ordering():
    """Two groups past deadline in one poll: the one whose oldest
    request has waited longest launches first (visible in the flush's
    bucket report order)."""
    rng = np.random.default_rng(2)
    late = workload.chain_for(rng, 2, "TST")     # submitted first
    fresh = workload.chain_for(rng, 3, "TRS")    # submitted second
    clk = VirtualClock()
    eng = _fresh_async(backend="ref", clock=clk,
                       slo=SLOConfig(max_wait_s=0.01, target_rows=4))
    eng.submit_async(late, _pts(rng, 3, 2))
    clk.advance(0.002)
    eng.submit_async(fresh, _pts(rng, 3, 3))
    clk.advance(0.008)                  # both deadlines have passed
    assert eng.poll() == 2
    structures = [r.structure for r in eng.server.last_report]
    assert structures == ["2D:diag", "3D:matrix"]

    # and in the mirror order when arrival order flips
    eng2 = _fresh_async(backend="ref", clock=VirtualClock(),
                        slo=SLOConfig(max_wait_s=0.01, target_rows=4))
    eng2.submit_async(fresh, _pts(rng, 3, 3))
    eng2.clock.advance(0.002)
    eng2.submit_async(late, _pts(rng, 3, 2))
    eng2.clock.advance(0.008)
    assert eng2.poll() == 2
    assert [r.structure for r in eng2.server.last_report] \
        == ["3D:matrix", "2D:diag"]


def test_poll_leaves_undue_groups_queued():
    rng = np.random.default_rng(3)
    a = workload.chain_for(rng, 2, "TST")
    b = workload.chain_for(rng, 3, "TRS")
    clk = VirtualClock()
    eng = _fresh_async(backend="ref", clock=clk,
                       slo=SLOConfig(max_wait_s=0.01, target_rows=4))
    eng.submit_async(a, _pts(rng, 3, 2))
    clk.advance(0.005)
    tb = eng.submit_async(b, _pts(rng, 3, 3))
    clk.advance(0.0025)                 # a's deadline (0.0075) fires
    assert eng.poll() == 1
    assert not tb.done()
    assert eng.stats["waiting_groups"] == 1
    assert eng.next_due_in() == pytest.approx(0.005)   # b due at 0.0125


# ---------------------------------------------------------------------------
# admission: fairness, backpressure, and typed rejection codes
# ---------------------------------------------------------------------------

def test_tenant_fair_share_prevents_starvation():
    """A flooding tenant saturates ITS share while a light tenant still
    admits -- then the global bound closes the queue for everyone."""
    clk = VirtualClock()
    ctrl = AdmissionController(
        AdmissionConfig(max_queue_depth=8, tenant_share=0.5), clk)
    admitted_heavy = 0
    for _ in range(10):                  # heavy tenant floods
        try:
            ctrl.admit("heavy")
            admitted_heavy += 1
        except QueueFullError:
            pass
    assert admitted_heavy == 4           # ceil(8 * 0.5)
    for _ in range(4):                   # light tenant is NOT starved
        ctrl.admit("light")
    with pytest.raises(QueueFullError):  # now the queue itself is full
        ctrl.admit("light")
    assert ctrl.queue_full_rejections == 7
    # releases reopen the gate (for a tenant still under its own cap)
    ctrl.release("light")
    ctrl.admit("light")
    assert ctrl.depth == 8


def test_rejection_codes_are_stable_and_typed():
    rng = np.random.default_rng(4)
    chain = workload.chain_for(rng, 2, "TST")
    eng = _fresh_async(
        backend="ref",
        admission=AdmissionConfig(max_queue_depth=2, tenant_share=1.0))
    eng.submit_async(chain, _pts(rng, 2, 2))
    eng.submit_async(chain, _pts(rng, 2, 2))
    with pytest.raises(QueueFullError) as exc:
        eng.submit_async(chain, _pts(rng, 2, 2))
    assert exc.value.code == "queue-full"
    assert isinstance(exc.value, serving.RequestError)
    assert eng.stats["queue_full_rejections"] == 1
    assert serving.stats["queue_full_rejections"] == 1
    eng.drain()                          # frees the queue
    eng.submit_async(chain, _pts(rng, 2, 2))


def test_token_bucket_refills_in_clock_time():
    b = TokenBucket(rate=100.0, burst=2.0)
    assert b.take(0.0) and b.take(0.0)
    assert not b.take(0.0)               # burst exhausted
    assert b.next_admissible_in(0.0) == pytest.approx(0.01)
    assert b.take(0.01)                  # one token refilled
    assert not b.take(0.01)


def test_rate_limited_engine_rejects_with_typed_error():
    rng = np.random.default_rng(5)
    chain = workload.chain_for(rng, 2, "TST")
    clk = VirtualClock()
    eng = _fresh_async(
        backend="ref", clock=clk,
        admission=AdmissionConfig(tenant_rate=100.0, tenant_burst=2.0))
    eng.submit_async(chain, _pts(rng, 2, 2), tenant="t0")
    eng.submit_async(chain, _pts(rng, 2, 2), tenant="t0")
    with pytest.raises(RateLimitError) as exc:
        eng.submit_async(chain, _pts(rng, 2, 2), tenant="t0")
    assert exc.value.code == "rate-limit"
    # a DIFFERENT tenant has its own bucket
    eng.submit_async(chain, _pts(rng, 2, 2), tenant="t1")
    # and clock time refills t0's
    clk.advance(0.01)
    eng.submit_async(chain, _pts(rng, 2, 2), tenant="t0")
    assert eng.stats["rate_limit_rejections"] == 1
    assert serving.stats["rate_limit_rejections"] == 1


def test_depth_rejection_spends_no_rate_token():
    clk = VirtualClock()
    ctrl = AdmissionController(
        AdmissionConfig(max_queue_depth=1, tenant_share=1.0,
                        tenant_rate=10.0, tenant_burst=2.0), clk)
    ctrl.admit("t")
    with pytest.raises(QueueFullError):
        ctrl.admit("t")                  # depth gate fires first
    ctrl.release("t")
    ctrl.admit("t")                      # the second token is still there
    assert ctrl.rate_limit_rejections == 0


# ---------------------------------------------------------------------------
# latency telemetry pinned on a fixed arrival script
# ---------------------------------------------------------------------------

def test_p50_p99_pinned_on_fixed_arrival_script():
    """Arrivals at t = 0, 1, 2, 3 ms into a 4-row bucket: the 4th fill
    triggers the flush at t = 3 ms, so latencies are exactly
    [3, 2, 1, 0] ms -- p50 = 1 ms (nearest rank), p99 = 3 ms, and the
    sustained rate is 4 requests over 3 ms."""
    rng = np.random.default_rng(6)
    chain = workload.chain_for(rng, 2, "TST")
    clk = VirtualClock()
    eng = _fresh_async(backend="ref", clock=clk,
                       slo=SLOConfig(max_wait_s=0.05, target_rows=4))
    tickets = []
    for k in range(4):
        clk.advance_to(k * 0.001)
        tickets.append(eng.submit_async(chain, _pts(rng, 3, 2)))
    assert eng.next_due_in() == 0.0
    assert eng.poll() == 4
    assert [t.latency for t in tickets] == \
        pytest.approx([0.003, 0.002, 0.001, 0.0])
    st = eng.stats
    assert st["p50_latency_s"] == pytest.approx(0.001)
    assert st["p99_latency_s"] == pytest.approx(0.003)
    assert st["max_latency_s"] == pytest.approx(0.003)
    assert st["sustained_rps"] == pytest.approx(4 / 0.003)


def test_queue_depth_telemetry():
    rng = np.random.default_rng(7)
    chain = workload.chain_for(rng, 2, "TST")
    eng = _fresh_async(backend="ref")
    for _ in range(3):
        eng.submit_async(chain, _pts(rng, 2, 2))
    st = eng.stats
    assert st["queue_depth"] == 3
    assert st["max_queue_depth_seen"] == 3
    assert st["resolved"] == 0
    eng.drain()
    st = eng.stats
    assert st["queue_depth"] == 0
    assert st["max_queue_depth_seen"] == 3   # high-water mark sticks
    assert st["resolved"] == 3
    assert serving.stats["admitted_requests"] == 3


def test_slo_config_validation():
    with pytest.raises(ValueError):
        SLOConfig(max_wait_s=-0.001)
    with pytest.raises(ValueError):
        SLOConfig(target_rows=0)
    with pytest.raises(ValueError):
        AdmissionConfig(max_queue_depth=0)
    with pytest.raises(ValueError):
        AdmissionConfig(tenant_share=0.0)
    with pytest.raises(ValueError):
        TokenBucket(rate=0.0, burst=2.0)
