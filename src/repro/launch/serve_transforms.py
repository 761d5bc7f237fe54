"""Driver for the batched transform-serving engine.

Generates a synthetic mixed workload (bounded structure pool, random
parameters and point counts -- the serving hot path), runs it through
``GeometryServer``, and prints the per-bucket schedule plus a comparison
against per-request dispatch:

    PYTHONPATH=src python -m repro.launch.serve_transforms --requests 64
    PYTHONPATH=src python -m repro.launch.serve_transforms --smoke

``--smoke`` shrinks the workload to a seconds-long liveness run (what CI
executes so the documented command cannot rot).  ``--autotune`` enables
the tuning cache (``repro.autotune``): the size grid and kernel launch
parameters come from the committed winners instead of the hardcoded
defaults, and the schedule header names the grid's source.  ``--trace
out.json`` serves the counted flush under a ``repro.obs`` tracer and
writes the span stream as Chrome-trace JSON -- open it in Perfetto
(one track per plan bucket, request spans on the main track).
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

from repro import obs, serving
from repro.launch.compile_cache import use_compile_cache
from repro.serving import workload
from repro.serving.workload import timed as _timed


def run_workload(requests: int, *, backend: str,
                 waste_cap: float | None = None,
                 max_points: int, max_points_per_launch: int | None,
                 seed: int, compare: bool = True,
                 trace_path: str | None = None) -> dict:
    """Serve one workload; returns the timing/schedule summary dict.
    ``waste_cap=None`` defers to the server's grid resolution (the tuning
    cache when ``repro.autotune`` is enabled, else the default grid).
    ``trace_path`` traces the counted flush and writes Chrome JSON."""
    reqs = workload.random_workload(seed=seed, n_requests=requests,
                                    max_points=max_points)

    serving.reset_stats()
    srv = serving.GeometryServer(backend=backend, waste_cap=waste_cap,
                                 max_points_per_launch=max_points_per_launch)
    warm = srv.serve(reqs)                       # compile + trace once
    jax.block_until_ready(warm)
    serving.reset_stats()
    if trace_path is not None:
        tracer = obs.Tracer()
        with obs.installed(tracer):
            srv.serve(reqs)                      # one counted, traced flush
        obs.dump_chrome_trace(tracer, trace_path)
        print(f"wrote {tracer.n_events} trace events to {trace_path}")
    else:
        srv.serve(reqs)                          # one counted flush
    stats = dict(serving.stats)
    batched_s = min(_timed(lambda: srv.serve(reqs)) for _ in range(3))

    per_request_s = None
    if compare:
        for chain, pts in reqs:                  # warm per-request plans
            chain.apply(jnp.asarray(pts), backend=backend)
        per_request_s = min(
            _timed(lambda: [chain.apply(jnp.asarray(pts), backend=backend)
                            for chain, pts in reqs])
            for _ in range(3))

    return {"requests": requests, "batched_s": batched_s,
            "per_request_s": per_request_s, "report": srv.last_report,
            "stats": stats,
            "grid": (srv.min_len, srv.waste_cap, srv.grid_source)}


def print_summary(res: dict) -> None:
    st = res["stats"]
    min_len, cap, src = res["grid"]
    print(f"size grid: min_len={min_len} waste_cap={cap} ({src})")
    print(f"{'bucket':<14} {'plan':<10} {'lpad':>5} {'reqs':>5} "
          f"{'launches':>8} {'waste':>6}")
    for rep in res["report"]:
        print(f"{rep.structure:<14} {rep.kind:<10} {rep.lpad:>5} "
              f"{rep.requests:>5} {rep.launches:>8} {rep.waste:>6.1%}")
    print(f"\n{st['requests']} requests -> {st['launches']} launches "
          f"({st['buckets']} buckets, {st['shards']} extra shards); "
          f"padding {1 - st['payload_points'] / max(1, st['padded_points']):.1%}")
    line = f"batched: {res['batched_s'] * 1e3:.1f} ms"
    if res["per_request_s"] is not None:
        line += (f"   per-request: {res['per_request_s'] * 1e3:.1f} ms   "
                 f"speedup: {res['per_request_s'] / res['batched_s']:.2f}x")
    print(line)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--backend", default=None,
                    choices=[None, "ref", "interpret", "pallas"])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--waste-cap", type=float, default=None,
                    help="explicit padding-waste cap; unset defers to the "
                         "tuning cache (with --autotune) or the default "
                         "grid")
    ap.add_argument("--autotune", action="store_true",
                    help="consult the tuning cache for the size grid and "
                         "kernel launch parameters")
    ap.add_argument("--max-points", type=int, default=4096)
    ap.add_argument("--max-points-per-launch", type=int, default=None,
                    help="shard buckets whose packed B*L exceeds this")
    ap.add_argument("--no-compare", action="store_true",
                    help="skip the per-request dispatch baseline")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny workload; CI liveness check")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="write the counted flush's span stream as "
                         "Chrome-trace JSON (open in Perfetto)")
    args = ap.parse_args(argv)

    use_compile_cache()
    if args.autotune:
        import repro.autotune
        repro.autotune.set_enabled(True)
    requests = 16 if args.smoke else args.requests
    max_points = 128 if args.smoke else args.max_points
    res = run_workload(requests, backend=args.backend,
                       waste_cap=args.waste_cap, max_points=max_points,
                       max_points_per_launch=args.max_points_per_launch,
                       seed=args.seed, compare=not args.no_compare,
                       trace_path=args.trace)
    print_summary(res)


if __name__ == "__main__":
    main()
