"""The control of the comparison: the reference put in the program's
place one precision below the configuration's, on a cell's own traffic.

    python3 chipbench/control.py --workload mixed_stream.flush256 \\
        --seeds 11 12 13

For each seed it draws the cell's stream, skips the pass a run warms up
with, takes as many of the window's flushes as a run checks, computes their results with ``reference.control`` (bfloat16
inputs, float32 accumulation) on the default device, and compares them
exactly as a run compares the served results.  Every line should read
``"correct": false``; the smallest ``err_ulps`` over the seeds is the
upper reading a limit is set below.  The benchmark's runs never run it.
"""
import argparse
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]


class _Served(np.ndarray):
    """A control result with its cull mask attached, as served ones."""
    mask = None


def control_sample(stream, traffic: dict) -> list:
    """The control's results for as many window flushes as a run
    checks: those after the warm pass."""
    from chipbench import reference
    for _ in range(traffic["pass_flushes"]):
        next(stream)
    sample = []
    for _ in range(traffic["check_flushes"]):
        flush, outs = next(stream), []
        for r in flush:
            out, mask = reference.control(r.spec, r.points)
            out = out.view(_Served)
            out.mask = mask
            outs.append(out)
        sample.append((flush, outs))
    return sample


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from chipbench import harness, reference, traffic as traffic_gen

    with open(ROOT / "BENCHMARK.json") as f:
        bench = json.load(f)
    cell, config, traffic = harness.cell_parts(bench, args.workload)
    for seed in args.seeds:
        family = traffic_gen.family(config, traffic)
        numbers, checked = harness.check(
            control_sample(family.flushes(config, traffic, seed), traffic),
            config["limits"], failed=0, fallbacks=0,
            tally=getattr(family, "Tally", reference.Tally))
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": reference.within(numbers),
                          "checked": checked, "check": numbers}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
