"""The seeded Poisson soak through the async front-end (``soak_driver``):
its traced exports are byte-identical in two fresh processes, and
tracing it moves none of its counters.
"""
import json
import os
import subprocess
import sys

import pytest

import soak_driver

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def fresh_runs(tmp_path_factory):
    """The traced soak run in two fresh processes at once; returns each
    run's output directory."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",   # CPU only: never a chip
               PYTHONPATH=os.path.join(os.path.dirname(HERE), "src"))
    dirs = [tmp_path_factory.mktemp(f"soak_{run}") for run in "ab"]
    procs = [subprocess.Popen(
        [sys.executable, os.path.join(HERE, "soak_driver.py"),
         str(d / "trace.json"), str(d / "snap.prom"),
         str(d / "counters.json")],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True) for d in dirs]
    for p in procs:
        out, _ = p.communicate(timeout=300)
        assert p.returncode == 0, out
    return dirs


@pytest.mark.parametrize("artifact",
                         ["trace.json", "snap.prom", "counters.json"])
def test_traced_soak_is_byte_identical_across_processes(fresh_runs,
                                                        artifact):
    # every span timestamp and counter is a function of the seed on the
    # soak's virtual clock: nothing of the process (hash seeds, compile
    # order, wall time) may leak into the exports
    a, b = (d / artifact for d in fresh_runs)
    assert a.read_bytes() == b.read_bytes()
    assert a.stat().st_size > 0


def test_tracing_never_steers_the_async_soak(fresh_runs):
    """The same soak untraced, in this process, gives the traced runs'
    counters exactly, and every admitted request resolved."""
    untraced = soak_driver.drive_soak(soak_driver.TRACE_REQUESTS)
    traced = json.loads((fresh_runs[0] / "counters.json").read_text())
    assert untraced == traced
    assert untraced["admitted"] == untraced["resolved"] + untraced["failed"]
    assert untraced["queue_depth"] == 0
    assert untraced["launches"] > 0
