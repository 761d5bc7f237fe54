"""Geometry-serving demo: many composite-transform requests, few launches.

A miniature of the serving story end to end: a handful of *chain shapes*
(sprite placement, 3D pose, a custom projective touch-up) each arrive many
times with fresh parameters and differently-sized point sets.  The
GeometryServer buckets them by plan (dim, kind) + size class, so the whole
workload runs in a handful of fused kernel launches -- and every result is
checked against its own per-request ``TransformChain.apply``.

    PYTHONPATH=src python examples/serve_transforms.py
    PYTHONPATH=src python examples/serve_transforms.py --smoke   # CI

``--smoke`` shrinks the workload so CI can execute this documented command
in seconds.  ``--autotune`` turns on the tuning cache
(``repro.autotune.set_enabled``): the server's size grid and the chain
kernels' launch parameters come from the committed winners file instead
of the hardcoded defaults -- results are identical either way (the knobs
steer staging, never arithmetic), only the schedule changes.
"""
import argparse

import numpy as np
import jax.numpy as jnp

from repro import serving
from repro.core.transform_chain import TransformChain


def sprite_place(rng) -> TransformChain:
    """2D sprite placement: scale, spin, drop -- the paper's composite."""
    return (TransformChain.identity(2)
            .scale(*rng.uniform(0.5, 2.0, 2).tolist())
            .rotate(float(rng.uniform(-np.pi, np.pi)))
            .translate(*rng.uniform(-10, 10, 2).tolist()))


def pose_3d(rng) -> TransformChain:
    """3D pose: yaw about z, then scale and offset."""
    return (TransformChain.identity(3)
            .rotate(float(rng.uniform(-np.pi, np.pi)), axis="z")
            .scale(float(rng.uniform(0.5, 1.5)))
            .translate(*rng.uniform(-5, 5, 3).tolist()))


def nudge_2d(rng) -> TransformChain:
    """Diagonal-only touch-up: folds to one affine, never builds a matrix."""
    return (TransformChain.identity(2)
            .translate(*rng.uniform(-1, 1, 2).tolist())
            .scale(*rng.uniform(0.9, 1.1, 2).tolist())
            .translate(*rng.uniform(-1, 1, 2).tolist()))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=48)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny workload; what CI runs")
    ap.add_argument("--autotune", action="store_true",
                    help="serve under the tuning-cache size grid instead "
                         "of the default (results are bit-compatible; "
                         "the launch schedule changes)")
    args = ap.parse_args()
    if args.autotune:
        import repro.autotune
        repro.autotune.set_enabled(True)
    n_requests = 12 if args.smoke else args.requests
    max_pts = 64 if args.smoke else 512

    rng = np.random.default_rng(0)
    makers = [sprite_place, pose_3d, nudge_2d]
    requests = []
    for i in range(n_requests):
        chain = makers[i % len(makers)](rng)
        n = int(rng.lognormal(np.log(max_pts / 4), 0.6))
        pts = rng.standard_normal((max(1, min(n, max_pts)), chain.dim))
        requests.append((chain, pts.astype(np.float32)))

    serving.reset_stats()
    server = serving.GeometryServer(backend="ref")
    results = server.serve(requests)

    stats = serving.stats
    print(f"served {stats['requests']} requests in {stats['launches']} "
          f"launches ({stats['buckets']} plan buckets, "
          f"{stats['plan_compiles']} plans compiled)")
    for rep in server.last_report:
        print(f"  bucket {rep.structure:<13} plan={rep.kind:<6} "
              f"lpad={rep.lpad:<4} requests={rep.requests:<3} "
              f"waste={rep.waste:.0%}")

    # every packed result checked against its own per-request apply
    for (chain, pts), out in zip(requests, results):
        expect = np.asarray(chain.apply(jnp.asarray(pts), backend="ref"))
        np.testing.assert_allclose(out, expect, rtol=2e-6, atol=2e-6)
    print(f"all {n_requests} packed results match per-request apply")


if __name__ == "__main__":
    main()
