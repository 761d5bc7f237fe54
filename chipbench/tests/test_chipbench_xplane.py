"""The reduction from a profiler trace to device busy time, plan time,
per-op time and labelled idle gaps, on a small trace recorded on a TPU
v5e (``record_trace.py``: two flushes of 16 mixed-stream requests).
Reading the file needs only the profiler's reader, not the chip."""
from pathlib import Path

import pytest

from chipbench import xplane

TRACE = Path(__file__).resolve().parent / "data" / "small.xplane.pb"


@pytest.fixture(scope="module")
def data():
    from jax.profiler import ProfileData
    return ProfileData.from_file(str(TRACE))


def test_interval_arithmetic():
    busy = xplane.merge([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert busy == [(0, 3), (5, 9)]
    assert xplane.total(busy) == 7
    assert xplane.clip(busy, 2, 6) == [(2, 3), (5, 6)]
    assert xplane.gaps(busy, -1, 12) == [(-1, 0), (3, 5), (9, 12)]
    assert xplane.gaps([], 0, 4) == [(0, 4)]


def test_reduction_of_a_recorded_trace(data):
    r = xplane.reduce(data)
    # the window is the benchmark's chipbench.window annotation
    assert r["window_s"] == pytest.approx(0.026843737, rel=1e-9)
    # busy is the union of the XLA ops on /device:TPU:0 inside it
    assert r["busy_s"] == pytest.approx(0.00021622, rel=1e-9)
    assert 0 < r["busy_s"] < r["window_s"]
    # every op there ran in a serving plan (modules named jit_call)
    assert r["plan_op_s"] == pytest.approx(r["busy_s"])
    ops = [s for _, s in r["device_ops"]]
    assert len(ops) == 10 and ops == sorted(ops, reverse=True)
    assert sum(ops) <= r["busy_s"] + 1e-12
    # idle gaps are labelled by the annotation the host had open
    idle = dict(r["idle_gaps"])
    assert set(idle) <= {"chipbench.submit", "chipbench.flush",
                         "host.other"}
    assert sum(idle.values()) == pytest.approx(r["window_s"] - r["busy_s"])


def test_plan_selection_is_by_module_name(data):
    r = xplane.reduce(data, plan_modules=("jit_renamed",))
    assert r["plan_op_s"] == 0.0
    assert r["busy_s"] == pytest.approx(0.00021622, rel=1e-9)


def test_no_device_no_reduction(data):
    assert xplane.reduce(data, chips=0) is None


def _fake(host_marks, ops, modules):
    """A stand-in for ``ProfileData``: one host plane with the
    benchmark's annotations, one TPU plane with ops and modules."""
    from types import SimpleNamespace as NS

    def events(items):
        return [NS(name=n, start_ns=s, end_ns=e) for s, e, n in items]
    host = NS(name="/host:CPU", lines=[NS(name="python",
                                          events=events(host_marks))])
    tpu = NS(name="/device:TPU:0",
             lines=[NS(name="XLA Ops", events=events(ops)),
                    NS(name="XLA Modules", events=events(modules))])
    return NS(planes=[host, tpu])


def test_the_window_is_the_union_of_the_timed_parts():
    w = xplane.WINDOW
    data = _fake(
        [(0, 100, w), (0, 60, "chipbench.submit"),
         (60, 100, "chipbench.flush"),
         (200, 300, w), (200, 250, "chipbench.submit"),
         (250, 300, "chipbench.flush")],
        # one op straddles the end of the first window, one runs between
        # the windows, one inside the second
        [(90, 120, "fusion"), (150, 180, "copy"), (260, 270, "kernel")],
        [(80, 130, "jit_call(3)"), (140, 190, "jit_other(1)"),
         (255, 280, "jit_call(4)")])
    r = xplane.reduce(data)
    assert r["window_s"] == pytest.approx(200e-9)
    assert r["busy_s"] == pytest.approx(20e-9)      # 90..100 and 260..270
    assert r["plan_op_s"] == pytest.approx(20e-9)
    assert dict(r["device_ops"]) == pytest.approx({"fusion": 10e-9,
                                                   "kernel": 10e-9})
    idle = dict(r["idle_gaps"])
    # each gap is labelled by the annotation open at its midpoint
    assert idle == pytest.approx({"chipbench.submit": 150e-9,
                                  "chipbench.flush": 30e-9})
