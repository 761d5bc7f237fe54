"""Record the small profiler trace that ``test_chipbench_phases.py``
reads: 0.02 s of timed parts of 16-request mixed-stream flushes,
profiled with ``obs.Tracer(annotate=True)`` installed, so the program's
spans are in the trace beside the benchmark's marks, as ``phases.py``
profiles.  Beside the trace it writes the Tracer's own span sums for
the same part, ``small_spans.xplane.json``.  Run on a TPU from the
repository's root:

    python3 chipbench/tests/record_spans_trace.py \\
        chipbench/tests/data/small_spans.xplane.pb
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
    from chipbench import harness, phases
    if jax.devices()[0].platform != "tpu":
        print("record_spans_trace: no TPU", file=sys.stderr)
        return 1
    with open(ROOT / "BENCHMARK.json") as f:
        bench = json.load(f)
    cell, config, traffic = harness.cell_parts(bench, "mixed_stream.flush256")
    traffic = dict(traffic, per_flush=16, pass_flushes=2, trace_seconds=0.02)
    out = ROOT / "chipbench" / "out" / "record_spans"
    line = phases.run(cell, dict(config, max_points=64), traffic, seed=1,
                      seconds=0.02, t_start=time.perf_counter(),
                      out_dir=out)
    print(json.dumps(line))
    (src,) = glob.glob(str(out / "**" / "*.xplane.pb"), recursive=True)
    shutil.copyfile(src, dest)
    sums = {k: tracer for k, (_, tracer)
            in line["profiled"]["profiler_vs_tracer_s"].items()}
    Path(dest).with_suffix(".json").write_text(json.dumps(sums, indent=1,
                                               sort_keys=True) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
