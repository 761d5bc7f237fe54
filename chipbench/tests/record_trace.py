"""Record the small profiler trace that ``test_chipbench_xplane.py``
reads: a couple of flushes of 16 mixed-stream requests, profiled the
way a ``--trace 1`` run profiles.  Run on a TPU from the repository's
root:

    python3 chipbench/tests/record_trace.py \\
        chipbench/tests/data/small.xplane.pb
"""
import glob
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main(dest: str) -> int:
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    from chipbench import harness
    if jax.devices()[0].platform != "tpu":
        print("record_trace: no TPU", file=sys.stderr)
        return 1
    with open(ROOT / "BENCHMARK.json") as f:
        bench = json.load(f)
    cell, config, traffic = harness.cell_parts(bench, "mixed_stream.flush256")
    traffic = dict(traffic, per_flush=16, pass_flushes=2, trace_seconds=0.02)
    out = ROOT / "chipbench" / "out" / "record"
    line = harness.run(cell, dict(config, max_points=64), traffic,
                       harness.metrics_for(bench, cell["name"], True),
                       seed=1, seconds=0.02, trace=True,
                       t_start=time.perf_counter(), out_dir=out)
    print(json.dumps(line))
    (src,) = glob.glob(str(out / "profile" / "**" / "*.xplane.pb"),
                       recursive=True)
    shutil.copyfile(src, dest)
    return 0 if line["correct"] else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
