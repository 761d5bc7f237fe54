"""Chains drawn from the configuration's ``templates`` -- (dim, primitive
letters) -- with point counts lognormal around
sqrt(min_points * max_points) and spread ``sigma``, clipped to
[min_points, max_points].  Request ``i`` of a pass takes template
``i % len(templates)``; its point count and 3-D rotation axes come from
``shape_seed`` and are the same in every pass.  Parameters and
coordinates are drawn afresh for every request, as
``serving/workload.chain_for`` draws them.
"""
import math

import numpy as np

from chipbench import traffic as traffic_gen

#: what a test run overrides to hold this family at a test's size
SMALL = {"config": {"max_points": 128},
         "traffic": {"per_flush": 22, "pass_flushes": 2,
                     "check_flushes": 4, "trace_seconds": 0.1}}


def chain(rng: np.random.Generator, dim: int, kinds: str, axes: list):
    """A chain with the given structure and fresh parameters, built
    through the program's chain API, and its spec."""
    from repro.core.transform_chain import TransformChain
    chain, spec = TransformChain.identity(dim), []
    for kind, axis in zip(kinds, axes):
        if kind == "T":
            v = rng.uniform(-3, 3, dim)
            chain, prim = chain.translate(*v.tolist()), ("T", v)
        elif kind == "S":
            v = rng.uniform(0.2, 2.0, dim)
            chain, prim = chain.scale(*v.tolist()), ("S", v)
        elif kind == "R":
            theta = float(rng.uniform(-np.pi, np.pi))
            chain = chain.rotate(theta) if dim == 2 \
                else chain.rotate(theta, axis=axis)
            prim = ("R", axis, theta)
        elif kind == "A":
            s, t = rng.uniform(0.2, 2.0, dim), rng.uniform(-2, 2, dim)
            chain, prim = chain.affine(s.tolist(), t.tolist()), ("A", s, t)
        elif kind == "M":
            m = np.eye(dim + 1, dtype=np.float32)
            m[:dim, :dim] += rng.uniform(-0.4, 0.4, (dim, dim))
            m[dim, :dim] = rng.uniform(-2, 2, dim)
            chain, prim = chain.matrix(m), ("M", m.astype(np.float64))
        elif kind == "P":
            # a gentle perspective column keeps w = 1 + p.c positive for
            # most points; the rest are culled by w > 0
            m = np.eye(dim + 1, dtype=np.float32)
            m[:dim, :dim] += rng.uniform(-0.3, 0.3, (dim, dim))
            m[dim, :dim] = rng.uniform(-1, 1, dim)
            m[:dim, dim] = rng.uniform(-0.05, 0.05, dim)
            chain, prim = chain.projective(m), ("P", m.astype(np.float64))
        elif kind == "C":
            lo, hi = float(rng.uniform(-6, -3)), float(rng.uniform(3, 6))
            chain, prim = chain.cull(lo, hi), ("C", lo, hi)
        else:
            raise ValueError(f"unknown primitive letter {kind!r}")
        spec.append(prim)
    return chain, spec


def shapes(config: dict, traffic: dict) -> list:
    """One pass's shapes: (dim, letters, points, 3-D rotation axes)."""
    rng = np.random.default_rng(traffic["shape_seed"])
    lo, hi = config["min_points"], config["max_points"]
    median = max(1.0, math.sqrt(max(1, lo) * hi))
    templates = config["templates"]
    out = []
    for i in range(traffic["per_flush"] * traffic["pass_flushes"]):
        dim, kinds = templates[i % len(templates)]
        n = int(np.clip(rng.lognormal(math.log(median), config["sigma"]),
                        lo, hi))
        # a 3-D rotation's axis is part of the chain's structure: a shape
        axes = [int(rng.integers(3)) if k == "R" and dim == 3 else None
                for k in kinds]
        out.append((dim, kinds, n, axes))
    return out


def flushes(config: dict, traffic: dict, seed: int):
    plan = shapes(config, traffic)
    values = np.random.default_rng([seed, 0x57EA])
    per = traffic["per_flush"]
    while True:
        for f in range(0, len(plan), per):
            flush = []
            for dim, kinds, n, axes in plan[f:f + per]:
                pts = values.standard_normal((n, dim)).astype(np.float32)
                c, spec = chain(values, dim, kinds, axes)
                flush.append(traffic_gen.request(c, pts, spec))
            yield flush
