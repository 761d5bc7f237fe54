"""The comparison that decides ``correct``: the reference's own
conventions, a sound run that passes it, and a run with the timed path
broken underneath, and the bfloat16 control, that fail it.

Runs drive ``harness.run`` -- everything of a benchmark run but the look
for a chip -- on the CPU, with the program's ``ref`` backend, at a size
a test can hold."""
import dataclasses
import json
import math
import time
from pathlib import Path

import numpy as np
import pytest

from chipbench import control, harness, reference, traffic as traffic_gen

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
HERE = harness.HERE
#: every configuration file with every traffic file of its family
CELLS = sorted(
    f"{c.stem}.{t.stem}"
    for c in (HERE / "configs").glob("*.json")
    for t in (HERE / "traffic").glob("*.json")
    if harness.load_json(c)["family"] == harness.load_json(t)["family"])
SEED = 2 ** 31 + 977          # larger than 32 signed bits hold


def small(name):
    """The cell's files, cut to a size a test run can hold by its
    family's ``SMALL``."""
    config_name, mix = name.split(".")
    cell = {"name": name, "chips": 1}
    config = harness.load_json(HERE / "configs" / f"{config_name}.json")
    traffic = harness.load_json(HERE / "traffic" / f"{mix}.json")
    cut = traffic_gen.family(config, traffic).SMALL
    return cell, dict(config, **cut["config"]), \
        dict(traffic, **cut["traffic"])


def test_every_benchmark_cell_is_among_the_files():
    assert {c["name"] for c in BENCH["workloads"]} <= set(CELLS)


def run(name, trace=False, tmp=None):
    cell, config, traffic = small(name)
    metrics = BENCH["per_layer"] if trace else BENCH["end_to_end"]
    kw = {"out_dir": tmp} if tmp is not None else {}
    return harness.run(cell, config, traffic, metrics, seed=SEED,
                       seconds=0.3, trace=trace,
                       t_start=time.perf_counter(), backend="ref", **kw)


def spec_value(spec, p):
    return reference.expect(spec, np.asarray(p, np.float64), 64)["ref"]


@pytest.mark.parametrize("spec,p,q", [
    ((("R", None, math.pi / 2),), [[1.0, 0.0]], [[0.0, 1.0]]),
    ((("R", 2, math.pi / 2),), [[1.0, 0.0, 0.0]], [[0.0, 1.0, 0.0]]),
    ((("R", 0, math.pi / 2),), [[0.0, 1.0, 0.0]], [[0.0, 0.0, 1.0]]),
    ((("R", 1, math.pi / 2),), [[0.0, 0.0, 1.0]], [[1.0, 0.0, 0.0]]),
    ((("T", (1.0, 2.0)), ("S", (2.0, 3.0))), [[1.0, 1.0]], [[4.0, 9.0]]),
    ((("A", (2.0, 2.0), (1.0, 0.0)),), [[1.0, 1.0]], [[3.0, 2.0]]),
    ((("LOOKAT", (0.0, 0.0, 5.0), (0.0, 0.0, 0.0), (0.0, 1.0, 0.0)),),
     [[0.0, 0.0, 0.0], [1.0, 0.0, 5.0]], [[0.0, 0.0, -5.0], [1.0, 0.0, 0.0]]),
    ((("PERSP", math.pi / 2, 1.0, 1.0, 3.0),),
     [[0.0, 0.0, -1.0], [0.0, 0.0, -3.0], [1.0, 1.0, -1.0]],
     [[0.0, 0.0, -1.0], [0.0, 0.0, 1.0], [1.0, 1.0, -1.0]]),
    ((("VIEWPORT", 0.0, 0.0, 1920.0, 1080.0, 0.0, 1.0),),
     [[-1.0, -1.0, -1.0], [1.0, 1.0, 1.0]],
     [[0.0, 0.0, 0.0], [1920.0, 1080.0, 1.0]]),
])
def test_reference_conventions(spec, p, q):
    np.testing.assert_allclose(spec_value(spec, p), q, atol=1e-12)


def test_reference_cull_and_points_behind_the_eye():
    spec = (("PERSP", math.pi / 2, 1.0, 1.0, 3.0), ("C", -1.0, 1.0),
            ("VIEWPORT", 0.0, 0.0, 100.0, 100.0, 0.0, 1.0))
    p = np.array([[0.0, 0.0, -2.0], [5.0, 0.0, -2.0], [0.0, 0.0, 2.0]])
    exp = reference.expect(spec, p, 64)
    assert exp["inside"].tolist() == [True, False, False]
    assert exp["mask_decided"].all()
    # behind the eye (w = -2 <= 0): the numerator, undivided
    num = np.array([0.0, 0.0, 2.0, 1.0]) @ reference.compose(spec, 3).full
    np.testing.assert_allclose(exp["ref"][2], num[:3])


def _float32_chain(spec, p):
    """The chain composed and applied in float32 from its parameters
    rounded to float32: what a sound float32 program may serve."""
    d = p.shape[1]
    h = np.eye(d + 1, dtype=np.float32)
    for prim in spec:
        kind = prim[0]
        m = np.eye(d + 1, dtype=np.float32)
        if kind == "R":
            t = np.float32(prim[2])
            c, s_ = np.cos(t), np.sin(t)
            m[:2, :2] = np.array([[c, -s_], [s_, c]], np.float32).T
        else:                                   # ("T", t)
            m[d, :d] = np.asarray(prim[1], np.float32)
        h = h @ m
    ph = np.concatenate([p, np.ones((len(p), 1), np.float32)], 1) @ h
    return ph[:, :d].astype(np.float32)


@pytest.mark.parametrize("spec", [
    (("R", None, 3.1412931096285064),),         # sine from a rounded angle
    (("T", (1000.1, -2000.3)), ("T", (-1000.0, 2000.0))),   # cancels
], ids=["rotation_by_nearly_pi", "cancelling_translations"])
def test_a_sound_float32_composition_reads_a_few_units(spec):
    rng = np.random.default_rng(SEED)
    p = rng.standard_normal((256, 2)).astype(np.float32)
    tally = reference.Tally()
    tally.add(spec, p, _float32_chain(spec, p), None, 1024)
    assert tally.failed == 0 and tally.err_ulps < 4
    # a result off by 2^-10 of itself reads many units
    served = _float32_chain(spec, p) * np.float32(1 + 2 ** -10)
    tally.add(spec, p, served, None, 1024)
    assert tally.err_ulps > 8


@pytest.mark.parametrize("name", CELLS)
def test_the_program_agrees_with_the_reference(name, tmp_path):
    line = run(name, tmp=tmp_path)
    assert line["correct"], line["check"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["checked"]["requests"] > 0
    assert line["check"]["err_ulps"]["value"] < 64
    assert list(line)[-1] == "check"
    assert set(line["metrics"]) == {"requests_per_s", "p50_latency_ms",
                                    "p95_latency_ms", "setup_s"}


def test_a_traced_run_reads_the_host_layers(tmp_path):
    line = run("mixed_stream.flush256", trace=True, tmp=tmp_path)
    assert line["correct"]
    got = line["metrics"]
    assert got["window_compiles"]["value"] == 0
    for name in ("intake_us_per_request", "pack_us_per_request",
                 "launches_per_request", "dispatch_us_per_launch",
                 "unpack_us_per_request"):
        assert got[name]["value"] > 0, name
    # a CPU trace holds no TPU: the device metrics are left out
    assert "device_idle_share" not in got and "plan_roofline" not in got
    cost = line["trace_cost"]
    assert 0 < cost["span_ms_per_flush"] < cost["window_ms_per_flush"]
    assert cost["profiled_ms_per_flush"] > 0


def _broken(engine, fault):
    """``get_batch_plan`` whose plans return a wrong answer."""
    original = engine.get_batch_plan

    def get(*args, **kw):
        plan = original(*args, **kw)

        def fn(folded, pts3):
            out = plan.fn(folded, pts3)
            pts = out[0] if isinstance(out, tuple) else out
            if fault == "answer_altered":
                pts = pts.at[0, 0, 0].multiply(1.01)
            else:                  # half of the batch left out
                pts = pts.at[pts.shape[0] // 2:].set(0.0) \
                    if pts.shape[0] > 1 else pts.at[:].set(0.0)
            return (pts, out[1]) if isinstance(out, tuple) else pts
        return dataclasses.replace(plan, fn=fn)
    return get


@pytest.mark.parametrize("fault", ["answer_altered", "half_batch_left_out"])
@pytest.mark.parametrize("name", CELLS)
def test_a_broken_timed_path_is_not_correct(name, fault, monkeypatch,
                                            tmp_path):
    from repro.serving import engine
    monkeypatch.setattr(engine, "get_batch_plan", _broken(engine, fault))
    line = run(name, tmp=tmp_path)
    assert not line["correct"]
    err = line["check"]["err_ulps"]
    assert err["value"] > err["limit"]


@pytest.mark.parametrize("name", CELLS)
def test_the_bfloat16_control_is_not_correct(name):
    cell, config, traffic = small(name)
    stream = traffic_gen.family(config, traffic).flushes(config, traffic,
                                                          SEED)
    numbers, checked = harness.check(
        control.control_sample(stream, traffic), config["limits"],
        failed=0, fallbacks=0)
    assert checked["requests"] > 0
    assert not reference.within(numbers)
    assert numbers["err_ulps"]["value"] > numbers["err_ulps"]["limit"]


def _passes(name, seed, passes):
    cell, config, traffic = small(name)
    stream = traffic_gen.family(config, traffic).flushes(config, traffic,
                                                          seed)
    return [next(stream) for _ in range(passes * traffic["pass_flushes"])]


def _shapes(flushes):
    return [[(r.chain.structure, r.points.shape) for r in f]
            for f in flushes]


@pytest.mark.parametrize("name", CELLS)
def test_every_seed_serves_the_same_shapes(name):
    a, b = _passes(name, 1, 2), _passes(name, SEED, 2)
    assert _shapes(a) == _shapes(b)
    assert not np.array_equal(a[0][0].points, b[0][0].points)


@pytest.mark.parametrize("name", CELLS)
def test_a_seed_gives_the_same_inputs(name):
    a, b = _passes(name, SEED, 1), _passes(name, SEED, 1)
    assert all(np.array_equal(x.points, y.points)
               and repr(x.spec) == repr(y.spec)
               for fa, fb in zip(a, b) for x, y in zip(fa, fb))


@pytest.mark.parametrize("name", CELLS)
def test_every_pass_repeats_the_shapes_with_fresh_values(name):
    flushes = _passes(name, SEED, 2)
    half = len(flushes) // 2
    first, second = flushes[:half], flushes[half:]
    assert _shapes(first) == _shapes(second)
    # no chain of the second pass repeats one of the first
    specs = {repr(r.spec) for f in first for r in f}
    assert not any(repr(r.spec) in specs for f in second for r in f)
