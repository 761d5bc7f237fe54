"""Autotune CLI: run the pruned search, inspect the model, guard the cache.

    PYTHONPATH=src python -m repro.autotune --smoke
        pruned search on two small chain shapes + the serving grid
        (ref backend), printing the analytic paper-format table, the
        emulator cross-check, and every timed trial.

    PYTHONPATH=src python -m repro.autotune --smoke --check
        the CI gate: additionally verifies (deterministically, by replayed
        launch counts, then by generous wall-clock bounds) that the
        committed default cache does not regress versus the built-in
        defaults or versus a fresh search.

    PYTHONPATH=src python -m repro.autotune --smoke --write-default
        persist the winners to the committed default cache
        (src/repro/autotune/default_cache.json).

``--out PATH`` writes winners to an arbitrary path instead.
"""
from __future__ import annotations

import argparse
import sys

from repro.autotune import cache as tcache
from repro.autotune import costmodel, search
from repro.core import analysis


def _print_model_table() -> None:
    print("== analytic cost model, paper-format (source=model) ==")
    print(analysis.format_table(costmodel.perf_rows()))
    print("\n== cross-check vs the MorphoSys emulator ==")
    from repro.core.morphosys import programs
    import numpy as np
    rng = np.random.default_rng(0)
    ok = True
    for routine, runner in (("translation",
                             lambda n: programs.run_translation(
                                 rng.integers(-99, 99, n),
                                 rng.integers(-99, 99, n))),
                            ("scaling",
                             lambda n: programs.run_scaling(
                                 rng.integers(-99, 99, n), 5))):
        for n in (8, 64):
            model = costmodel.morphosys_cycles(routine, n)
            emu = runner(n).cycles
            ok &= model == emu
            print(f"  {routine:<12} n={n:<3} model={model:<4} emulator={emu:<4}"
                  f" {'OK' if model == emu else 'MISMATCH'}")
    if not ok:
        sys.exit("cost model disagrees with the emulator")


def _print_reports(reports) -> None:
    for rep in reports:
        print(f"\n== {rep.kernel} ({rep.backend}, {rep.dtype}, "
              f"n={rep.n}) ==")
        for t in rep.trials:
            mark = " <- winner" if t.config.key_fields() == \
                rep.winner.key_fields() else ""
            print(f"  {t.config.describe():<52} "
                  f"{t.seconds * 1e6:9.1f} us  "
                  f"(predicted {t.predicted_us:8.1f} us){mark}")


def _check(reports) -> None:
    """CI regression gate against the committed default cache.

    Deterministic first: replay the smoke workload's bucketing under the
    committed serving-grid entry and fail if it issues more launches than
    the built-in default grid.  Then wall-clock with generous slack: the
    committed config must not be grossly slower than this run's fresh
    winner (cache gone stale), and every expected key must be present.
    """
    committed = tcache.TuningCache.load(tcache.DEFAULT_CACHE_PATH)
    failures = []
    # deterministic grid gate, per traffic scale: replay each seeded
    # workload's bucketing under the committed entry for ITS size class
    default = tcache.DEFAULTS["serving_grid"]
    for label, wl in (("smoke", search.smoke_workload()),
                      ("scale64", search.scale_workload())):
        n = search.workload_size_class_n(wl)
        entry = committed.get("serving_grid", reports[0].backend,
                              "float32", n)
        if entry is None:
            failures.append(f"missing serving_grid entry for the {label} "
                            "workload")
            continue
        shape = costmodel.workload_shape(wl)
        merged = tcache.merge(default, entry)
        com_cost = costmodel.grid_cost(shape, merged.grid_min_len,
                                       merged.grid_waste_cap)
        def_cost = costmodel.grid_cost(shape, default.grid_min_len,
                                       default.grid_waste_cap)
        print(f"[check] serving_grid[{label}] launches: committed="
              f"{com_cost.launches} default={def_cost.launches}")
        if com_cost.launches > def_cost.launches:
            failures.append(
                f"committed grid {entry.describe()} schedules "
                f"{com_cost.launches} launches vs {def_cost.launches} "
                f"for the default grid on the {label} workload")
    for rep in reports:
        entry = committed.get(rep.kernel, rep.backend, rep.dtype, rep.n)
        if entry is None:
            failures.append(f"missing cache entry: {rep.kernel}|"
                            f"{rep.backend}|{rep.dtype}")
            continue
        # wall-clock guard: committed config vs this run's fresh winner,
        # measured in the same process (2x slack absorbs eager-CPU noise;
        # the launch-count gate above is the deterministic check)
        fresh = rep.winner_seconds
        timed = {tuple(sorted(t.config.key_fields().items())): t.seconds
                 for t in rep.trials}
        com_t = timed.get(tuple(sorted(entry.key_fields().items())))
        if com_t is not None and com_t > fresh * 2.0:
            failures.append(
                f"{rep.kernel}: committed config {entry.describe()} is "
                f"{com_t * 1e6:.0f}us vs fresh winner {fresh * 1e6:.0f}us")
    if failures:
        sys.exit("autotune check FAILED:\n  " + "\n  ".join(failures))
    print("[check] committed default cache: OK")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(prog="python -m repro.autotune")
    ap.add_argument("--smoke", action="store_true",
                    help="pruned search on two small shapes + serving grid")
    ap.add_argument("--backend", default="ref",
                    choices=("ref", "interpret", "pallas"))
    ap.add_argument("--iters", type=int, default=5,
                    help="timer repetitions per candidate (best-of)")
    ap.add_argument("--out", default=None,
                    help="write winners JSON to this path")
    ap.add_argument("--write-default", action="store_true",
                    help="write winners to the committed default cache")
    ap.add_argument("--check", action="store_true",
                    help="CI gate: fail if the committed cache regresses")
    args = ap.parse_args(argv)

    _print_model_table()
    if not (args.smoke or args.check):
        print("\n(nothing to tune; pass --smoke to run the pruned search)")
        return

    cache, reports = search.smoke_search(args.backend, iters=args.iters)
    _print_reports(reports)

    if args.check:
        _check(reports)
    if args.write_default:
        cache.save(tcache.DEFAULT_CACHE_PATH)
        print(f"\nwrote {len(cache)} winners -> {tcache.DEFAULT_CACHE_PATH}")
    elif args.out:
        cache.save(args.out)
        print(f"\nwrote {len(cache)} winners -> {args.out}")


if __name__ == "__main__":
    main()
