"""The resident-mesh cell: its loop uploads each mesh once, in set-up,
and sends only chains in the window; the two metrics that read what
the resident path adds, beside the accepted layers' metrics it also
reports; and a traced run of the cell at a test's size on the CPU with
the ``ref`` backend."""
import contextlib
import json
import time
from pathlib import Path

import pytest

from chipbench import discover, harness, traffic as traffic_gen
from chipbench.tests.test_chipbench_check import SEED, small

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "bunny_frames.resident8"
NEW = ("upload_bytes_per_request", "bind_us_per_launch")
# the accepted metrics whose spans and counters the resident path runs
# (a resident bucket binds, so ``bucket.pack`` has nothing to read)
HOST = ("intake_us_per_request", "launches_per_request",
        "dispatch_us_per_launch", "launch_call_us_per_launch",
        "unpack_us_per_request", "unpack_wait_us_per_launch",
        "unpack_fetch_us_per_launch", "unpack_copy_us_per_request",
        "window_compiles")
DEVICE = ("device_idle_share", "plan_roofline")


def test_the_cell_and_its_metrics_are_declared():
    cells = {c["name"]: c for c in BENCH["workloads"]}
    assert cells[CELL]["chips"] == 1
    layer = {m["name"]: m for m in BENCH["per_layer"]}
    for name in NEW:
        assert layer[name]["workloads"] == [CELL]
        assert layer[name]["moves"] == "requests_per_s"
    for name in HOST + DEVICE:
        assert CELL in layer[name]["workloads"]
    assert CELL not in layer["pack_us_per_request"]["workloads"]


def test_readers_on_a_record():
    record = {"setup_s": 1.0, "window_s": 2.0, "latencies_s": [0.003],
              "completed": 800, "spans": {"bucket.bind": 0.002},
              "counters": {"launches": 100, "requests": 800,
                           "upload_bytes": 800 * 88},
              "window_compiles": 0,
              "device": {"window_s": 1.0, "busy_s": 0.2,
                         "plan_op_s": 0.1},
              "traced_payload_bytes": 8.19e6, "peaks": {"hbm_bw": 819e9}}
    read = {name: harness.reader(name)(record)
            for name in NEW + ("plan_roofline",)}
    assert read["upload_bytes_per_request"] == 88.0
    assert read["bind_us_per_launch"] == pytest.approx(20.0)
    assert read["plan_roofline"] == pytest.approx(0.01)


def test_a_program_without_resident_buffers_reads_nothing():
    record = {"spans": {}, "counters": {"launches": 10, "requests": 80},
              "device": None, "peaks": None}
    assert all(harness.reader(name)(record) is None for name in NEW)


def test_the_loop_uploads_in_warm_and_never_in_the_window():
    from repro import serving
    cell, config, traffic = small(CELL)
    stream = traffic_gen.family(config, traffic).flushes(config, traffic,
                                                          SEED)
    loop = discover.module("loops", traffic["loop"])
    client = loop.server("ref")
    serving.reset_stats()
    for _ in range(traffic["pass_flushes"]):
        loop.warm(client, next(stream))
    assert serving.stats["uploads"] == 1          # one mesh, 8 instances
    before = dict(serving.stats)
    flushes = []
    loop.window(client, stream, 0.2, contextlib.nullcontext,
                lambda flush, outs, lat: flushes.append(outs))
    moved = {k: serving.stats[k] - before[k] for k in before}
    assert flushes and moved["uploads"] == 0
    assert moved["requests"] == moved["resident_requests"] \
        == 8 * len(flushes)
    assert moved["launches"] == len(flushes)      # one launch a frame
    assert moved["upload_bytes"] == 88 * moved["requests"]


def test_a_server_without_upload_fails_at_once():
    class Old:
        def submit(self, *a, **kw):
            raise AssertionError("submitted without an upload")

    cell, config, traffic = small(CELL)
    stream = traffic_gen.family(config, traffic).flushes(config, traffic,
                                                          SEED)
    loop = discover.module("loops", traffic["loop"])
    with pytest.raises(AttributeError, match="upload"):
        loop.warm(loop.Renderer(Old()), next(stream))


def test_a_traced_run_reads_the_resident_layers(tmp_path):
    cell, config, traffic = small(CELL)
    line = harness.run(cell, config, traffic,
                       harness.metrics_for(BENCH, CELL, True), seed=SEED,
                       seconds=0.3, trace=True, t_start=time.perf_counter(),
                       backend="ref", out_dir=tmp_path)
    assert line["correct"], line["check"]
    got = line["metrics"]
    assert got["upload_bytes_per_request"]["value"] == 88.0
    assert got["bind_us_per_launch"]["value"] > 0
    assert all(name in got for name in HOST)
    assert got["launches_per_request"]["value"] == 0.125  # 8 instances
    assert got["window_compiles"]["value"] == 0
    # a CPU trace holds no TPU: the device metrics are left out
    assert not any(name in got for name in DEVICE)
    assert "pack_us_per_request" not in got
