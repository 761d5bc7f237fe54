"""The metric that reads the server's ``host_operands`` counter: its
entry, its reader on a record and on a program without the counter, and
a traced run of each cell on the CPU with the ``ref`` backend, where a
launch on the array path stages its fold row and its points and a
resident launch its fold row alone."""
import json
import time
from pathlib import Path

import pytest

from chipbench import harness
from chipbench.tests.test_chipbench_check import SEED, small

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = "host_operands_per_launch"
#: the cell and the host arrays each of its launches stages
CELLS = {"mixed_stream.flush256": 2.0, "bunny_frames.resident8": 1.0}


@pytest.fixture
def cold_plans():
    """A run compiles the cell's plans; drop them after, so a later
    test in this process that counts set-up's traces sees its own."""
    from repro import serving
    yield
    serving.clear_plan_cache()


def test_the_metric_is_declared():
    (m,) = [m for m in BENCH["per_layer"] if m["name"] == NAME]
    assert m["workloads"] == list(CELLS)
    assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"]) \
        == ("arrays/launch", "lower", "program_counter", "dispatch",
            "requests_per_s")


def test_the_reader_on_a_record():
    record = {"counters": {"launches": 63, "host_operands": 126,
                           "requests": 512}}
    assert harness.reader(NAME)(record) == 2.0


@pytest.mark.parametrize("counters", [
    {"launches": 63, "requests": 512},          # a program without it
    {"launches": 0, "host_operands": 0},        # nothing launched
    {}])
def test_the_reader_reads_nothing_without_the_counter(counters):
    assert harness.reader(NAME)({"counters": counters}) is None


@pytest.mark.parametrize("name", sorted(CELLS))
def test_a_traced_run_reads_the_host_arrays_a_launch_stages(
        name, tmp_path, cold_plans):
    cell, config, traffic = small(name)
    line = harness.run(cell, config, traffic,
                       harness.metrics_for(BENCH, name, True), seed=SEED,
                       seconds=0.3, trace=True, t_start=time.perf_counter(),
                       backend="ref", out_dir=tmp_path)
    assert line["correct"], line["check"]
    assert line["metrics"][NAME]["value"] == CELLS[name]
