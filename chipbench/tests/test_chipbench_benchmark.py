"""``BENCHMARK.json`` and the files the harness finds by name: every
configuration, traffic mix and metric resolves to a file of its own,
and each metric's reader reads the record it is given."""
import json
import re
from pathlib import Path

import pytest

from chipbench import discover, harness, traffic as traffic_gen

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [c["name"] for c in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_top_level_keys_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["chipbench"]
    assert BENCH["command"] == ["python3", "chipbench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file_is_found_and_matches_its_entry(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert entry["file"] == f"chipbench/configs/{entry['name']}.json"
    config = json.loads((ROOT / entry["file"]).read_text())
    assert config["name"] == entry["name"]
    assert config["reduced"] == entry["reduced"]
    assert config["precision"] == "float32"
    assert config["limits"]["err_ulps"] > 0
    assert 0 < len(entry["why"]) <= 200 and 0 < len(entry["source"]) <= 200


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_to_its_files(name):
    cell, config, traffic = harness.cell_parts(BENCH, name)
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert name == f"{cell['config']}.{cell['traffic']}"
    assert cell["chips"] in (1, 4) and len(cell["why"]) <= 200
    loop = discover.module("loops", traffic["loop"])
    assert all(callable(getattr(loop, f)) for f in ("server", "warm",
                                                     "window"))
    family = traffic_gen.family(config, traffic)
    assert callable(family.flushes) and set(family.SMALL) == {"config",
                                                              "traffic"}
    e2e = [m["name"] for m in harness.metrics_for(BENCH, name, False)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert harness.metrics_for(BENCH, name, True)


def test_unknown_cell_is_refused():
    with pytest.raises(KeyError):
        harness.cell_parts(BENCH, "no_such.cell")


@pytest.mark.parametrize("kind,name", [("metrics", "no_such_metric"),
                                       ("families", "no_such_family"),
                                       ("loops", "no_such_loop")])
def test_a_part_that_has_no_file_is_refused(kind, name):
    with pytest.raises(KeyError, match=name):
        discover.module(kind, name)


def test_parts_are_found_by_file_name():
    for kind in ("metrics", "families", "loops"):
        files = sorted((ROOT / "chipbench" / kind).glob("*.py"))
        assert files, kind
        for f in files:
            assert discover.module(kind, f.stem).__file__ == str(f)


def test_traffic_of_another_family_is_refused():
    config = {"family": "template_stream"}
    with pytest.raises(ValueError, match="mesh_orbit"):
        traffic_gen.family(config, {"family": "mesh_orbit"})


@pytest.mark.parametrize("m", METRICS, ids=lambda m: m["name"])
def test_metric_entry_and_reader(m):
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    assert (ROOT / "chipbench" / "metrics" / f"{m['name']}.py").is_file()
    assert callable(harness.reader(m["name"]))
    if m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    else:
        assert m["moves"] == "requests_per_s"
        assert set(m["workloads"]) <= set(CELLS)


EMPTY = {"setup_s": 12.5, "window_s": 2.0, "latencies_s": [],
         "completed": 0, "spans": {}, "counters": {}, "window_compiles": 0,
         "device": None, "traced_payload_bytes": 0, "peaks": None}


@pytest.mark.parametrize("name", [m["name"] for m in BENCH["per_layer"]
                                  if m["name"] != "window_compiles"])
def test_a_reader_with_nothing_to_read_returns_nothing(name):
    assert harness.reader(name)(EMPTY) is None


def test_readers_on_a_record():
    record = dict(
        EMPTY, completed=1000, latencies_s=[0.1] * 95 + [0.3] * 5,
        spans={"request.validate": 0.02, "bucket.pack": 0.01,
               "flush.dispatch": 0.05, "flush.unpack": 0.04},
        counters={"launches": 250, "requests": 1000}, window_compiles=0,
        device={"window_s": 2.0, "busy_s": 0.5, "plan_op_s": 0.4},
        traced_payload_bytes=819e6, peaks={"hbm_bw": 819e9})
    read = {m["name"]: harness.reader(m["name"])(record) for m in METRICS}
    assert read["requests_per_s"] == 500.0
    assert read["p50_latency_ms"] == pytest.approx(100.0)
    assert read["p95_latency_ms"] == pytest.approx(100.0)
    assert read["setup_s"] == 12.5
    assert read["intake_us_per_request"] == pytest.approx(20.0)
    assert read["pack_us_per_request"] == pytest.approx(10.0)
    assert read["launches_per_request"] == 0.25
    assert read["dispatch_us_per_launch"] == pytest.approx(200.0)
    assert read["unpack_us_per_request"] == pytest.approx(40.0)
    assert read["window_compiles"] == 0
    assert read["device_idle_share"] == pytest.approx(75.0)
    assert read["plan_roofline"] == pytest.approx(0.25)


def test_plan_roofline_fails_loudly_when_no_plan_op_is_found():
    record = dict(EMPTY, traced_payload_bytes=1e6, peaks={"hbm_bw": 819e9},
                  device={"window_s": 2.0, "busy_s": 0.5, "plan_op_s": 0.0})
    with pytest.raises(ValueError, match="serving plan"):
        harness.reader("plan_roofline")(record)
