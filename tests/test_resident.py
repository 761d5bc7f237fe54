"""Resident meshes: ``GeometryServer.upload`` and requests on its handle.

A handle's projective requests run the host-array path's folds through
the same kernel body (the instanced kernel shares the batch kernel's
chunk schedule), and its diag and matrix requests take the host-array
path on the handle's snapshot, so on ``ref`` and ``interpret`` every
request on a handle equals the same request submitted as an array
through the same server bit for bit.
Against float64 the projective results are held to the engine's
float32-epsilon scale: a few float32 units of the first-order bound of
``[p, 1] @ H`` and its divide, applied to the SAME float32 fold (the
fold is the host path's and is checked per primitive in
``test_graphics.py``), and the cull mask is exact wherever float64 puts
the point farther than that bound from a cull plane.
"""
import json
import math
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro import errors, graphics, scene, serving
from repro.core import transform_chain as tc

BACKENDS = ["ref", "interpret"]
EPS32 = float(np.finfo(np.float32).eps)


def _fresh(**kw):
    serving.reset_stats()
    return serving.GeometryServer(**kw)


def _mesh(rng, n, d=3):
    return (rng.standard_normal((n, d)) * 0.1).astype(np.float32)


def _view(i, d=3):
    """Instance ``i`` of a ring under an orbiting camera: model,
    camera, perspective, cull, viewport."""
    if d == 2:
        return tc.TransformChain.identity(2).rotate(0.3 * i) \
            .translate(0.2 * i, -0.1).projective(
                np.array([[1.0, 0.0, 0.2], [0.0, 1.0, 0.1],
                          [0.0, 0.0, 1.0]], np.float32)) \
            .cull(-1.0, 1.0)
    at = 2 * math.pi * i / 20
    cam = graphics.Camera(eye=(0.9 * math.cos(i), 0.2, 0.9 * math.sin(i)),
                          target=(0.0, 0.0, 0.0), up=(0.0, 1.0, 0.0),
                          fov_y=math.radians(20.0), aspect=16 / 9,
                          near=0.05, far=5.0)
    model = tc.TransformChain.identity(3).rotate(0.3 * i, axis=1) \
        .translate(0.3 * math.cos(at), 0.0, 0.3 * math.sin(at))
    return graphics.viewing_chain(
        3, model=model, camera=cam,
        viewport=graphics.Viewport(width=1920.0, height=1080.0))


def _same(a, b):
    if not np.array_equal(a, b) or a.shape != b.shape:
        return False
    return np.array_equal(getattr(a, "mask", None), getattr(b, "mask", None))


def _host_path(backend, reqs):
    """The same requests, submitted as arrays."""
    return _fresh(backend=backend).serve(reqs)


@pytest.mark.parametrize("d", [2, 3])
@pytest.mark.parametrize("backend", BACKENDS)
def test_instances_equal_the_host_array_path(backend, d):
    """20 instances, more than one batch block of the host path, beside
    the same 20 requests as arrays in the same flush."""
    pts = _mesh(np.random.default_rng(d), 1000, d)
    chains = [_view(i, d) for i in range(20)]
    srv = _fresh(backend=backend)
    handle = srv.upload(pts)
    for c in chains:
        srv.submit(c, handle)
    for c in chains:
        srv.submit(c, pts)
    out = srv.flush()
    assert all(_same(a, b) for a, b in zip(out[:20], out[20:], strict=True))
    assert all(isinstance(r, serving.Projected) and r.shape == pts.shape
               for r in out)
    assert serving.stats["launches"] == 2
    assert serving.stats["resident_requests"] == 20
    assert [r.kind for r in srv.last_report] == ["projective"] * 2


def _bound(h, lo, hi, p):
    """float64 projection of ``p`` by the float32 fold ``(h, lo, hi)``,
    its first-order float32 error bound per coordinate, and whether
    float64 decides each point's cull test beyond that bound."""
    h = np.asarray(h, np.float64)
    p = np.asarray(p, np.float64)
    d = p.shape[-1]
    ph = np.concatenate([p, np.ones(p.shape[:-1] + (1,))], -1)
    num, w = ph @ h[:, :d], ph @ h[:, d]
    mag_num = np.abs(ph) @ np.abs(h[:, :d])
    mag_w = np.abs(ph) @ np.abs(h[:, d])
    v = num / np.where(w > 0, w, 1.0)[:, None]
    err = 8 * EPS32 * (mag_num + np.abs(v) * mag_w[:, None]) \
        / np.where(w > 0, np.abs(w), 1.0)[:, None]
    inside = (w > 0) & np.all((v >= lo) & (v <= hi), axis=-1)
    decided = (np.abs(w) > 8 * EPS32 * mag_w) & np.all(
        (np.abs(v - lo) > err) & (np.abs(v - hi) > err), axis=-1)
    return v, err, inside, decided


@pytest.mark.parametrize("backend", BACKENDS)
def test_within_the_float64_bound_and_masks_exact(backend):
    pts = _mesh(np.random.default_rng(5), 3000)
    chains = [_view(i) for i in range(8)]
    srv = _fresh(backend=backend)
    handle = srv.upload(pts)
    out = srv.serve((c, handle) for c in chains)
    decided = checked = 0
    for c, r in zip(chains, out, strict=True):
        v, err, inside, ok = _bound(*c.fold(), pts)
        front = inside | ~ok                       # w > 0 where decided
        assert (np.abs(r - v) <= err)[front].all()
        assert np.array_equal(r.mask[ok], inside[ok])
        decided += ok.sum()
        checked += len(pts)
    assert decided > 0.99 * checked and 0 < sum(r.mask.sum() for r in out)


@pytest.mark.parametrize("backend", BACKENDS)
def test_two_meshes_beside_array_requests_in_one_flush(backend):
    """Two handles, diag and matrix chains on a handle, and array
    requests: each handle's projective requests make one bucket, its
    diag and matrix requests bucket with array requests, results keep
    submission order, each equal to the host-array path."""
    rng = np.random.default_rng(9)
    big, small = _mesh(rng, 2500), _mesh(rng, 300)
    diag = tc.TransformChain.identity(3).scale(2.0, 3.0, 4.0) \
        .translate(1.0, 2.0, 3.0)
    mat = tc.TransformChain.identity(3).rotate(0.4, axis=0) \
        .translate(1.0, -2.0, 0.5)
    srv = _fresh(backend=backend)
    hb, hs = srv.upload(big), srv.upload(small)
    reqs = [(_view(0), big), (_view(1), small), (diag, big), (mat, small),
            (_view(2), big), (mat, big), (_view(3), small)]
    for chain, p in reqs:
        srv.submit(chain, hb if p is big else hs)
    for chain, p in reqs[:3]:
        srv.submit(chain, p)
    out = srv.flush()
    # one instanced bucket per handle; diag at the big size class (with
    # the array request), matrix at both size classes, and the two array
    # projective requests' own
    assert len(srv.last_report) == 2 + 1 + 2 + 2
    assert sum(r.kind == "projective" for r in srv.last_report) == 4
    assert serving.stats["resident_requests"] == 4
    ref = _host_path(backend, reqs)
    assert all(_same(a, b) for a, b in zip(out[:7], ref, strict=True))
    assert all(_same(a, b) for a, b in zip(out[7:], ref[:3], strict=True))
    assert srv.metrics.value("uploads") == 2


@pytest.mark.parametrize("backend", BACKENDS)
def test_one_handle_is_one_bucket_whatever_the_structures(backend):
    """Instances of two projective structures on one handle make one
    bucket and one launch (a bucket keys on the plan identity and the
    handle), a second handle its own: one launch per 8 instances, as a
    frame of one structure makes, each equal to the host-array path."""
    rng = np.random.default_rng(12)
    meshes = [_mesh(rng, 900), _mesh(rng, 400)]
    bare = np.eye(4, dtype=np.float32)
    bare[3, 2] = 0.5                        # perspective, no cull
    srv = _fresh(backend=backend)
    handles = [srv.upload(m) for m in meshes]
    reqs = []
    for m in meshes:
        for i in range(8):
            reqs.append((_view(i) if i % 2 else tc.TransformChain.identity(3)
                         .rotate(0.2 * i, axis=2).translate(0.0, 0.0, 1.0)
                         .projective(bare), m))
    assert len({c.structure for c, _ in reqs}) == 2
    out = srv.serve((c, handles[0] if m is meshes[0] else handles[1])
                    for c, m in reqs)
    assert serving.stats["buckets"] == serving.stats["launches"] == 2
    assert serving.stats["launches"] / serving.stats["requests"] == 0.125
    assert serving.stats["bucket_structures"] == 4
    assert serving.stats["resident_requests"] == 16
    assert all(_same(a, b) for a, b in
               zip(out, _host_path(backend, reqs), strict=True))


@pytest.mark.parametrize("kind", ["diag", "matrix"])
@pytest.mark.parametrize("backend", BACKENDS)
def test_diag_and_matrix_on_a_handle_take_the_array_path(backend, kind):
    """A diag or matrix chain on a handle is no instance: it joins the
    array requests' bucket at its size class and equals them bit for
    bit."""
    pts = _mesh(np.random.default_rng(11), 600)
    chain = tc.TransformChain.identity(3).scale(2.0, 3.0, 4.0) \
        .translate(1.0, 2.0, 3.0) if kind == "diag" else \
        tc.TransformChain.identity(3).rotate(0.4, axis=0).translate(1, 2, 3)
    srv = _fresh(backend=backend)
    handle = srv.upload(pts)
    srv.submit(chain, handle)
    srv.submit(chain, pts)
    a, b = srv.flush()
    assert np.array_equal(a, b)
    assert [r.kind for r in srv.last_report] == [kind]
    assert serving.stats["resident_requests"] == 0
    assert srv.last_report[0].requests == 2


def test_one_handle_serves_many_flushes():
    pts = _mesh(np.random.default_rng(4), 700)
    srv = _fresh(backend="ref")
    handle = srv.upload(pts)
    for frame in range(5):
        chains = [_view(frame * 8 + i) for i in range(8)]
        out = srv.serve((c, handle) for c in chains)
        assert all(_same(a, b) for a, b in
                   zip(out, _host_path("ref", [(c, pts) for c in chains]),
                       strict=True))
    assert srv.metrics.value("uploads") == 1
    assert srv.metrics.value("resident_requests") == 40
    assert srv.metrics.value("launches") == 5


def test_mutating_the_array_after_upload_changes_no_result():
    pts = _mesh(np.random.default_rng(6), 400)
    kept = pts.copy()
    srv = _fresh(backend="ref")
    handle = srv.upload(pts)
    pts *= 3.0
    assert not handle.host.flags.writeable
    with pytest.raises(ValueError):
        handle.host[0, 0] = 1.0
    out = srv.serve([(_view(1), handle), (tc.TransformChain.identity(3),
                                          handle)])
    assert _same(out[0], _host_path("ref", [(_view(1), kept)])[0])
    assert np.array_equal(out[1], kept) and out[1].flags.writeable


def test_typed_errors_for_handles_and_uploads():
    srv = _fresh(backend="ref")
    handle = srv.upload(_mesh(np.random.default_rng(7), 50))
    chain2 = tc.TransformChain.identity(2).translate(1.0, 2.0)
    with pytest.raises(errors.DtypeError) as e:
        srv.submit(tc.TransformChain.identity(3).scale(2.0), handle,
                   qformat="q8.7")
    assert e.value.ticket == 0
    with pytest.raises(errors.ShapeError) as e:
        srv.submit(chain2, handle)
    assert e.value.ticket == 1
    assert serving.stats["rejected_requests"] == 2 and srv.pending == 0
    for bad, err in [(np.ones((4, 3)), errors.DtypeError),
                     (np.zeros((0, 3), np.float32), errors.EmptyPointsError),
                     (np.float32(1.0), errors.ShapeError),
                     (np.array([[np.nan, 0.0, 0.0]], np.float32),
                      errors.NonFiniteError)]:
        with pytest.raises(err):
            srv.upload(bad)
    assert serving.stats["uploads"] == 1


def test_rows_split_by_the_launch_cap():
    pts = _mesh(np.random.default_rng(8), 1000)
    chains = [_view(i) for i in range(6)]
    srv = _fresh(backend="ref", max_points_per_launch=2 * 1024)
    handle = srv.upload(pts)
    out = srv.serve((c, handle) for c in chains)
    assert serving.stats["launches"] == 3 and serving.stats["shards"] == 2
    assert all(_same(a, b) for a, b in
               zip(out, _host_path("ref", [(c, pts) for c in chains]),
                   strict=True))


def test_submit_scene_and_the_async_engine_take_a_handle():
    rng = np.random.default_rng(10)
    pts = _mesh(rng, 500)
    g = scene.SceneGraph(3, cache=scene.FoldCache())
    g.add("world", tc.TransformChain.identity(3).translate(0.0, 0.0, 1.0))
    for i in range(3):
        g.add(f"n{i}", tc.TransformChain.identity(3).rotate(0.2 * i, axis=1)
              .translate(0.1 * i, 0.0, 0.0), parent="world")
    srv = _fresh(backend="ref")
    handle = srv.upload(pts)
    tickets = [srv.submit_scene(g, f"n{i}", handle) for i in range(3)]
    out = srv.flush()
    ref = _host_path("ref", [(g.world_chain(f"n{i}"), pts)
                             for i in range(3)])
    assert all(_same(out[t], r) for t, r in zip(tickets, ref, strict=True))

    eng = serving.AsyncGeometryServer(backend="ref",
                                      clock=serving.VirtualClock())
    handle = eng.server.upload(pts)
    chains = [_view(i) for i in range(4)]
    got = eng.gather([eng.submit_async(c, handle) for c in chains])
    assert all(_same(a, b) for a, b in
               zip(got, _host_path("ref", [(c, pts) for c in chains]),
                   strict=True))


_MESH_RESIDENT = """
    import json, sys
    import numpy as np, jax
    from repro import graphics, serving
    from repro.core import transform_chain as tc
    from repro.launch.mesh import make_mesh
    backend = sys.argv[1]
    rng = np.random.default_rng(12)
    pts = (rng.standard_normal((900, 3)) * 0.1).astype(np.float32)
    cam = graphics.Camera(eye=(0.9, 0.2, 0.1), target=(0.0, 0.0, 0.0),
                          up=(0.0, 1.0, 0.0), fov_y=0.35, aspect=1.5,
                          near=0.05, far=5.0)
    chains = [graphics.viewing_chain(
        3, model=tc.TransformChain.identity(3).rotate(0.3 * i, axis=1),
        camera=cam, viewport=graphics.Viewport(width=640.0, height=480.0))
        for i in range(6)]
    mat = tc.TransformChain.identity(3).rotate(0.5, axis=2).translate(1, 2, 3)

    def serve():
        srv = serving.GeometryServer(backend=backend)
        h = srv.upload(pts)
        devices = len(h.device.sharding.device_set)
        return srv.serve([(c, h) for c in chains] + [(mat, h)]), devices
    one, one_dev = serve()
    with jax.set_mesh(make_mesh((4,), ("data",))):
        mesh, mesh_dev = serve()
    same = sum(np.array_equal(a, b) and np.array_equal(
        getattr(a, "mask", None), getattr(b, "mask", None))
        for a, b in zip(one, mesh, strict=True))
    print(json.dumps({"same": same, "n": len(one), "one": one_dev,
                      "mesh": mesh_dev}))
"""


@pytest.mark.parametrize("backend", BACKENDS)
def test_mesh_replicates_the_handle_and_equals_one_device(backend):
    """Under ``jax.set_mesh`` on 4 virtual CPU devices the handle is
    replicated at upload, the instances split over the mesh, and the
    results are one device's bit for bit (a child process, so the
    device-count flag stays out of this one)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(repo, "src"))
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(_MESH_RESIDENT), backend],
        capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["one"] == 1 and rec["mesh"] == 4
    assert rec["same"] == rec["n"] == 7
