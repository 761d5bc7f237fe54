import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape) on the production
meshes and record memory / cost / collective analysis.

The two lines above MUST stay first: jax locks the device count on first
initialisation, and the dry-run needs 512 placeholder host devices so
``jax.make_mesh`` can build the 2x16x16 production mesh.  (Smoke tests and
benches see 1 device -- this flag is set nowhere else.)

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch yi-6b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --out results.jsonl
"""
import argparse
import json
import time
import traceback

import jax  # noqa: F401 -- imported HERE so the env lines above win the race

from repro import configs, hlo_analysis, roofline
from repro.configs.shapes import SHAPES, applicability
from repro.launch import cells
from repro.launch.mesh import make_production_mesh


def run_cell(arch: str, shape: str, *, multi_pod: bool,
             verbose: bool = True, kv_int8: bool = False) -> dict:
    rec = {"arch": arch, "shape": shape,
           "mesh": "2x16x16" if multi_pod else "16x16"}
    cfg = configs.get(arch)
    ok, why = applicability(cfg, shape)
    if not ok:
        rec.update(status="SKIP", reason=why)
        return rec
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_devices = mesh.size
    cell = cells.build_cell(arch, shape, mesh, kv_int8=kv_int8)
    t_lower = time.time() - t0
    compiled = cell.lowered.compile()
    t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    raw_cost = compiled.cost_analysis()
    if isinstance(raw_cost, (list, tuple)):
        raw_cost = raw_cost[0]
    text = compiled.as_text()
    # loop-aware analysis of the partitioned module (cost_analysis counts
    # while bodies once; see repro.hlo_analysis)
    ana = hlo_analysis.analyze(text)
    chip = roofline.peaks("TPU v5 lite")          # the dry run's target
    roof = roofline.roofline_terms(
        {"flops": ana["flops"], "bytes accessed": ana["hbm_bytes"]},
        roofline.CollectiveStats(ana["collective_bytes"],
                                 ana["collective_counts"]), chip)
    mf = cells.model_flops_for_cell(cell, n_devices)
    util = roofline.model_flops_utilization(mf, roof, chip)

    rec.update(
        status="OK",
        kind=cell.spec.kind,
        n_params=cell.meta["n_params"],
        accum_steps=cell.meta.get("accum_steps"),
        lower_s=round(t_lower, 1),
        compile_s=round(t_compile, 1),
        bytes_per_device=dict(
            arguments=mem.argument_size_in_bytes,
            outputs=mem.output_size_in_bytes,
            temps=mem.temp_size_in_bytes,
            aliased=mem.alias_size_in_bytes,
            total_live=(mem.argument_size_in_bytes +
                        mem.output_size_in_bytes +
                        mem.temp_size_in_bytes -
                        mem.alias_size_in_bytes),
        ),
        hlo_flops_per_device=roof.flops,
        hlo_bytes_per_device=roof.hbm_bytes,
        collective_bytes_per_device=roof.collective_bytes,
        collective_breakdown=ana["collective_bytes"],
        collective_counts=ana["collective_counts"],
        raw_cost_analysis_flops=float((raw_cost or {}).get("flops", 0.0)),
        model_flops_per_device=mf,
        roofline=dict(t_compute=roof.t_compute, t_memory=roof.t_memory,
                      t_collective=roof.t_collective,
                      bottleneck=roof.bottleneck, **util),
    )
    if verbose:
        print(json.dumps(rec, indent=2, default=float))
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=configs.list_archs())
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--kv-int8", action="store_true",
                    help="int8-quantized KV cache for decode cells")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="run every (arch x shape) cell")
    ap.add_argument("--out", default=None, help="append JSONL records here")
    args = ap.parse_args()

    if args.all:
        todo = [(a, s) for a in configs.list_archs() for s in SHAPES]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        todo = [(args.arch, args.shape)]
    meshes = [False, True] if (args.both_meshes or args.all) else [args.multi_pod]

    failures = 0
    for arch, shape in todo:
        for mp in meshes:
            try:
                rec = run_cell(arch, shape, multi_pod=mp,
                               verbose=not args.out, kv_int8=args.kv_int8)
            except Exception as e:  # a failing cell is a bug; record it
                rec = {"arch": arch, "shape": shape,
                       "mesh": "2x16x16" if mp else "16x16",
                       "status": "FAIL", "error": f"{type(e).__name__}: {e}",
                       "traceback": traceback.format_exc()[-2000:]}
                failures += 1
                print(f"FAIL {arch} x {shape} ({rec['mesh']}): {e}")
            if args.out:
                with open(args.out, "a") as f:
                    f.write(json.dumps(rec, default=float) + "\n")
                print(f"{rec['status']:5s} {arch} x {shape} ({rec['mesh']})",
                      flush=True)
    if failures:
        raise SystemExit(f"{failures} cells failed")


if __name__ == "__main__":
    main()
