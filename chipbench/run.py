"""Run one cell of the chip benchmark once, on the TPU this process finds.

    python3 chipbench/run.py --workload mixed_stream.flush256 \\
        --seed 7 --seconds 30 --trace 0

Reads ``BENCHMARK.json`` from the repository's root, serves the cell
through ``GeometryServer`` with ``backend="pallas"``, and prints one
JSON result line last on standard output; the numbers compared with
the reference are the last lines of standard error.  ``--trace 0``
reports the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics from a traced run.  Exits 1, and
prints no result, where JAX finds no TPU or fewer chips than the cell
asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from chipbench import harness

    with open(ROOT / "BENCHMARK.json") as f:
        bench = json.load(f)
    cell, config, traffic = harness.cell_parts(bench, args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"run: the cell needs {cell['chips']} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 1
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    line = harness.run(
        cell, config, traffic,
        harness.metrics_for(bench, args.workload, bool(args.trace)),
        seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        t_start=T_START)
    harness.report(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
