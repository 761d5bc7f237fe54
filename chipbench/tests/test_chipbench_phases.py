"""``phases.py``: idle gaps split by the program's innermost span, the
two clocks' sums of each span, the readers of the flush's new spans,
and a whole run of it on the CPU with the ``ref`` backend.  The
recorded trace is 0.02 s of timed parts (one flush of 16 mixed-stream
requests, 13 launches) profiled on a TPU v5e under
``Tracer(annotate=True)`` (``record_spans_trace.py``), with the
Tracer's own span sums beside it; reading it needs only the profiler's
reader, not the chip."""
import collections
import json
import time
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from chipbench import harness, phases, xplane
from chipbench.tests.test_chipbench_check import SEED, small

W = xplane.WINDOW
DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def recorded():
    """The recorded trace and the Tracer's span sums over it."""
    from jax.profiler import ProfileData
    return (ProfileData.from_file(str(DATA / "small_spans.xplane.pb")),
            json.loads((DATA / "small_spans.xplane.json").read_text()))


def _fake(host_marks, ops):
    """A stand-in for ``ProfileData``: one host line with the marks and
    the program's annotations, one TPU plane with ops."""
    def events(items):
        return [NS(name=n, start_ns=s, end_ns=e) for s, e, n in items]
    host = NS(name="/host:CPU", lines=[NS(name="python",
                                          events=events(host_marks))])
    tpu = NS(name="/device:TPU:0",
             lines=[NS(name="XLA Ops", events=events(ops)),
                    NS(name="XLA Modules", events=[])])
    return NS(planes=[host, tpu])


def test_innermost_labels_each_piece_by_the_deepest_open_span():
    spans = [(0, 100, "flush"), (10, 40, "flush.dispatch"),
             (12, 20, "launch.call"), (25, 30, "launch.call"),
             (50, 90, "flush.unpack"), (50, 70, "unpack"),
             (55, 60, "unpack.fetch"), (120, 130, "flush")]
    assert phases.innermost(spans) == [
        (0, 10, "flush"), (10, 12, "flush.dispatch"),
        (12, 20, "launch.call"), (20, 25, "flush.dispatch"),
        (25, 30, "launch.call"), (30, 40, "flush.dispatch"),
        (40, 50, "flush"), (50, 55, "unpack"), (55, 60, "unpack.fetch"),
        (60, 70, "unpack"), (70, 90, "flush.unpack"), (90, 100, "flush"),
        (120, 130, "flush")]
    assert phases.innermost([]) == []


def test_idle_gaps_are_cut_at_span_edges():
    names = ("flush", "flush.dispatch", "launch.call", "unpack.fetch")
    data = _fake(
        [(0, 100, W), (0, 100, "flush"), (0, 30, "chipbench.submit"),
         (30, 60, "flush.dispatch"), (40, 50, "launch.call"),
         (70, 90, "unpack.fetch"), (200, 240, W)],
        # busy 45-55 in the first window; 210-220 in the second
        [(45, 55, "op"), (210, 220, "op"), (300, 310, "outside")])
    idle = phases.idle_by_span(data, names)
    assert idle == pytest.approx({
        "flush": 50e-9, "flush.dispatch": 15e-9, "launch.call": 5e-9,
        "unpack.fetch": 20e-9, phases.CLIENT: 30e-9})
    r = xplane.reduce(data)
    assert sum(idle.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    # a mark of the benchmark's own is not a program span
    assert "chipbench.submit" not in idle
    assert phases.idle_by_span(_fake([], []), names) is None


def test_annotation_sums_read_only_the_named_spans():
    data = _fake([(0, 100, W), (0, 40, "flush"), (50, 60, "flush"),
                  (5, 9, "unpack.copy")], [])
    assert phases.annotation_sums(data, {"flush", "unpack.copy"}) == \
        pytest.approx({"flush": 50e-9, "unpack.copy": 4e-9})


def test_idle_by_span_on_a_recorded_trace(recorded):
    data, sums = recorded
    idle = phases.idle_by_span(data, sums)
    r = xplane.reduce(data)
    assert sum(idle.values()) == pytest.approx(r["window_s"] - r["busy_s"],
                                               rel=1e-9)
    # dispatch's plan calls, its own work, and the transfers back
    assert {"launch.call", "flush.dispatch", "unpack.fetch",
            phases.CLIENT} <= set(idle)
    assert set(idle) <= set(sums) | {phases.CLIENT}
    assert idle["launch.call"] + idle["unpack.fetch"] > \
        0.5 * sum(idle.values())
    # the first reduction still labels by the benchmark's marks alone
    assert {k for k, _ in r["idle_gaps"]} <= {
        "chipbench.submit", "chipbench.flush", "host.other"}


def test_the_two_clocks_agree_on_a_recorded_trace(recorded):
    data, sums = recorded
    on_profiler = phases.annotation_sums(data, sums)
    counts = collections.Counter(
        name for _, _, name in phases._host_events(data, sums.__contains__))
    assert set(on_profiler) == set(sums) == set(counts)
    # a span's two extents differ by the annotation's own opening and
    # closing, a few microseconds at most
    for name, tracer_s in sums.items():
        assert abs(on_profiler[name] - tracer_s) <= counts[name] * 5e-6, \
            name


NEW_READERS = {"launch_call_us_per_launch": ("launch.call", 0.02, 80.0),
               "unpack_wait_us_per_launch": ("unpack.wait", 0.005, 20.0),
               "unpack_fetch_us_per_launch": ("unpack.fetch", 0.01, 40.0),
               "unpack_copy_us_per_request": ("unpack.copy", 0.02, 20.0)}


@pytest.mark.parametrize("name", sorted(NEW_READERS))
def test_new_readers_on_a_record(name):
    span, spent, expected = NEW_READERS[name]
    record = {"completed": 1000, "counters": {"launches": 250},
              "spans": {"flush.dispatch": 0.05, span: spent}}
    assert harness.reader(name)(record) == pytest.approx(expected)
    # a program without the span reads nothing, as the parent does
    assert harness.reader(name)(dict(record, spans={})) is None


def test_a_run_on_the_cpu(tmp_path):
    cell, config, traffic = small("mixed_stream.flush256")
    line = phases.run(cell, config, traffic, seed=SEED, seconds=0.2,
                      t_start=time.perf_counter(), backend="ref",
                      out_dir=tmp_path / "profile")
    json.dumps(line)
    setup = line["setup"]
    # set-up traced every shape it warmed, inside the plan calls
    assert setup["traced_calls"] > 0
    assert 0 < setup["setup_plan_s"] <= setup["spans"]["launch.call"]
    traced = line["traced"]
    assert traced["launches"] > 0 and traced["flushes"] > 0
    per_launch = traced["us_per_launch"]
    for name in ("launch.call", "unpack.wait", "unpack.fetch",
                 "unpack.copy", "unpack", "flush.dispatch"):
        assert per_launch[name] > 0
    # the parts of unpack lie inside it
    parts = sum(per_launch[k] for k in ("unpack.wait", "unpack.fetch",
                                        "unpack.copy"))
    assert parts <= per_launch["unpack"]
    profiled = line["profiled"]
    # no TPU plane on the CPU: nothing to split, but the program's spans
    # are on the profiler's clock
    assert profiled["idle_by_span"] is None
    both = profiled["profiler_vs_tracer_s"]
    assert {"flush", "launch.call", "unpack.copy"} <= set(both)
    assert all(on_profiler > 0 for on_profiler, _ in both.values())
