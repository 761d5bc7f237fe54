"""A renderer's frames: ``instances`` copies of one seeded mesh of
``vertices`` points on a ring, each under a viewing chain (model,
camera, perspective, cull, viewport).  Each flush is one frame; the
camera orbits by ``orbit_step_deg`` a frame from a seeded start, so
every frame's chains are new, and the mesh is sent again each frame,
as a renderer without resident buffers sends it.
"""
import math

import numpy as np

from chipbench import traffic as traffic_gen

#: what a test run overrides to hold this family at a test's size
SMALL = {"config": {"vertices": 600},
         "traffic": {"pass_flushes": 3, "check_flushes": 3,
                     "trace_seconds": 0.1}}


def mesh_like(rng: np.random.Generator, vertices: int,
              extent: list, centre: list) -> np.ndarray:
    """A closed, bumpy surface of ``vertices`` points with the given
    half-extents and centre, generated from ``rng``."""
    u = rng.standard_normal((vertices, 3))
    u /= np.linalg.norm(u, axis=1, keepdims=True)
    bumps = 1.0 + 0.08 * np.sin(7 * u[:, 0]) * np.cos(5 * u[:, 1])
    return (u * bumps[:, None] * extent + centre).astype(np.float32)


def flushes(config: dict, traffic: dict, seed: int):
    from repro import graphics
    from repro.core.transform_chain import TransformChain
    values = np.random.default_rng([seed, 0x0B17])
    k = config["instances"]
    if traffic["per_flush"] != k:
        raise ValueError(f"a frame submits the scene's {k} instances, "
                         f"not {traffic['per_flush']}")
    mesh = mesh_like(values, config["vertices"], config["extent"],
                     config["centre"])
    yaws = values.uniform(-np.pi, np.pi, k)
    phase = float(values.uniform(0, 2 * np.pi))
    cam, vp = config["camera"], config["viewport"]
    fov = math.radians(cam["fov_y_deg"])
    aspect = vp["width"] / vp["height"]
    ring = config["ring_radius"]
    step = math.radians(traffic["orbit_step_deg"])
    viewport = graphics.Viewport(width=vp["width"], height=vp["height"])
    frame = 0
    while True:
        phi = phase + frame * step
        eye = (cam["radius"] * math.cos(phi), cam["height"],
               cam["radius"] * math.sin(phi))
        target, up = (0.0, 0.0, 0.0), (0.0, 1.0, 0.0)
        camera = graphics.Camera(eye=eye, target=target, up=up, fov_y=fov,
                                 aspect=aspect, near=cam["near"],
                                 far=cam["far"])
        flush = []
        for i in range(k):
            at = 2 * math.pi * i / k
            pos = (ring * math.cos(at), 0.0, ring * math.sin(at))
            model = TransformChain.identity(3) \
                .rotate(float(yaws[i]), axis=1).translate(*pos)
            chain = graphics.viewing_chain(3, model=model, camera=camera,
                                           viewport=viewport)
            spec = [("R", 1, float(yaws[i])), ("T", pos),
                    ("LOOKAT", eye, target, up),
                    ("PERSP", fov, aspect, cam["near"], cam["far"]),
                    ("C", -1.0, 1.0),
                    ("VIEWPORT", 0.0, 0.0, vp["width"], vp["height"],
                     0.0, 1.0)]
            flush.append(traffic_gen.request(chain, mesh, spec))
        yield flush
        frame += 1
