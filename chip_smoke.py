"""Serve the geometry stream once on a TPU, through compiled Pallas kernels.

    python chip_smoke.py              # one chip: phases a-d
    python chip_smoke.py --chips 4    # stream a over a 4-device mesh only

Phases on one chip, each through the server's normal entry points with
``backend="pallas"`` named explicitly:

  a  1,024 requests of ``workload.random_workload`` (every template:
     diagonal, matrix and projective chains, 2-D and 3-D, up to 4,096
     points each), submitted and flushed 256 at a time;
  b  the same stream shape with a q8.7 fixed-point share
     (``workload.mixed_lane_workload``);
  c  256 requests through ``AsyncGeometryServer`` on a ``MonotonicClock``,
     drained;
  d  one bucket of 8 meshes of 35,947 3-D vertices (the Stanford Bunny's
     published vertex count; the geometry is generated from the seed),
     each through a ``graphics.viewing_chain`` projective chain and
     through a diagonal chain.

With ``--chips 4`` the script runs stream a with a 4-device mesh set, and
the same requests on one device, and nothing else.

Every result is checked against a float64 numpy reference computed from
the request's host fold, within a tolerance derived in ``_expect``; q8.7
results are checked bitwise against the integer oracle.  A launch
failure, a backend fallback, a failed request or a bucket that did not
finish on ``pallas`` fails the run, as does a missing TPU.  Timings are
host-clock smoke timings of one warm flush, not benchmarks.  The last
line of standard output is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import numpy as np

SEED = 0
N_REQUESTS = 1024                 # phases a and b
N_ASYNC = 256                     # phase c
FLUSH = 256                       # requests per flush in phases a and b
MAX_POINTS = 4096                 # repro.launch.serve_transforms default
BUNNY_VERTICES = 35_947           # Stanford 3D Scanning Repository
EPS = float(np.finfo(np.float32).eps)
BACKEND = "pallas"


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


# -- references --------------------------------------------------------------

def _expect(chain, pts: np.ndarray, qname: str | None) -> dict:
    """The numpy reference for one request, from its host fold.

    Tolerances, per plan kind (``u = EPS / 2`` is float32's unit
    roundoff; a kernel result is float32 arithmetic on float32 inputs):

      * diag ``p*s + t``: one product and one sum, each rounded once
        (or fused), so the error is at most ``EPS * (|p*s| + |t|)``;
        the tolerance is twice that.
      * matrix ``p @ A + t``: ``d`` products and ``d`` sums, so the
        standard summation bound ``(d + 1) * EPS * (|p| @ |A| + |t|)``.
      * projective: the same bound on the homogeneous numerator ``acc``
        and on ``w``, carried through the divide, plus four ulps for
        the divide itself (the chip's float32 divide is not required to
        round correctly).  Points whose ``w`` lies within its bound of
        zero, or whose coordinate lies within its bound of a cull
        plane, have no decidable mask bit; they are counted, and their
        mask is not compared.
      * q8.7: integer arithmetic, so the served words equal the oracle's
        bit for bit (``kernels/fixedpoint/ref.py``).  A request the
        error bound says would wrap is served on the float lane
        (``FaultConfig.on_q_overflow="fallback"``) and checked as float.
    """
    from repro import quantize
    from repro.core import transform_chain as tc
    from repro.kernels.fixedpoint import ref as qref

    d = chain.dim
    kind = tc.plan_kind_of(chain.structure)
    fold = chain.fold()
    flat = np.asarray(pts, np.float32).reshape(-1, d)
    if qname is not None:
        fmt = quantize.as_qformat(qname)
        if quantize.fits(fold, kind, fmt, float(np.abs(flat).max())):
            words = fmt.quantize(flat)
            qf = quantize.quantize_fold(fold, kind, fmt)
            oracle = qref.np_chain_diag_q if kind == "diag" \
                else qref.np_chain_matrix_q
            out = fmt.dequantize(oracle(words, *qf, fmt.n))
            return {"kind": "q", "ref": out.reshape(pts.shape)}
    p = flat.astype(np.float64)
    if kind == "diag":
        s, t = (np.asarray(f, np.float64) for f in fold)
        ref = p * s + t
        tol = 2 * EPS * (np.abs(p * s) + np.abs(t))
        return {"kind": kind, "ref": ref.reshape(pts.shape),
                "tol": tol.reshape(pts.shape)}
    if kind == "matrix":
        a, t = (np.asarray(f, np.float64) for f in fold)
        ref = p @ a + t
        tol = (d + 1) * EPS * (np.abs(p) @ np.abs(a) + np.abs(t))
        return {"kind": kind, "ref": ref.reshape(pts.shape),
                "tol": tol.reshape(pts.shape)}
    h, lo, hi = (np.asarray(f, np.float64) for f in fold)
    ph = p @ h[:d] + h[d]
    acc, w = ph[:, :d], ph[:, d:]
    acc_err = (d + 1) * EPS * (np.abs(p) @ np.abs(h[:d, :d])
                               + np.abs(h[d, :d]))
    w_err = (d + 1) * EPS * (np.abs(p) @ np.abs(h[:d, d:]) + np.abs(h[d, d:]))
    decided = (np.abs(w) > 2 * w_err)[:, 0]
    pos = w > 0
    v = np.where(pos, acc / np.where(pos, w, 1.0), acc)
    tol = np.where(pos, (acc_err + np.abs(v) * w_err)
                   / np.maximum(np.abs(w) - w_err, 1e-300), acc_err) \
        + 4 * EPS * np.abs(v)
    with np.errstate(invalid="ignore"):        # inf cull bounds
        inside = pos[:, 0] & ((v >= lo) & (v <= hi)).all(axis=1)
        clear = ((np.abs(v - lo) > tol) & (np.abs(v - hi) > tol)).all(axis=1)
    lead = pts.shape[:-1]
    return {"kind": kind, "ref": v.reshape(pts.shape),
            "tol": tol.reshape(pts.shape), "decided": decided.reshape(lead),
            "mask": inside.reshape(lead),
            "mask_decided": (decided & clear).reshape(lead)}


class Tally:
    """Agreement of one phase's results with their references."""

    def __init__(self):
        self.max_abs = 0.0          # largest |served - reference|
        self.max_ratio = 0.0        # largest error / tolerance (<= 1)
        self.q_bitwise = 0          # q8.7 requests equal bit for bit
        self.undecided_mask = 0     # points with no decidable mask bit
        self.projected = 0          # points through a projective chain
        self.inside = 0             # ... that the cull mask kept

    def add(self, chain, pts, qname, out) -> None:
        from repro import serving
        check(not serving.is_error(out), f"request resolved to {out!r}")
        mask = getattr(out, "mask", None)
        out = np.asarray(out)
        check(out.shape == pts.shape and np.isfinite(out).all(),
              f"result shape {out.shape} for {pts.shape}, or non-finite")
        exp = _expect(chain, pts, qname)
        if exp["kind"] == "q":
            check(np.array_equal(out, exp["ref"]), "q8.7 result differs "
                  "from the integer oracle")
            self.q_bitwise += 1
            return
        keep = np.ones(pts.shape[:-1], bool)
        if exp["kind"] == "projective":
            md = exp["mask_decided"]
            wrong = int((np.asarray(mask)[md] != exp["mask"][md]).sum())
            check(wrong == 0, f"cull mask differs on {wrong} decidable "
                  "points")
            self.undecided_mask += int((~md).sum())
            self.projected += md.size
            self.inside += int(np.asarray(mask).sum())
            keep = exp["decided"]
        err = np.abs(out.astype(np.float64) - exp["ref"])[keep]
        tol = exp["tol"][keep]
        if err.size:
            ratio = float((err / np.maximum(tol, 1e-300)).max())
            check(ratio <= 1.0, f"{exp['kind']} error {float(err.max())} "
                  f"exceeds its tolerance (ratio {ratio})")
            self.max_abs = max(self.max_abs, float(err.max()))
            self.max_ratio = max(self.max_ratio, ratio)


# -- serving phases ----------------------------------------------------------

class CompileClock:
    """Seconds JAX spent in backend compiles (a persistent-cache hit
    counts only its retrieval), and how many compiles hit the cache."""

    def __init__(self):
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def check_clean(reports) -> None:
    """No launch failed, nothing fell back, every bucket ran on pallas."""
    from repro import serving
    for key in ("launch_failures", "backend_fallbacks", "failed_requests"):
        check(serving.stats[key] == 0, f"{key}={serving.stats[key]}")
    finals = {r.final_backend for r in reports}
    check(finals == {BACKEND}, f"buckets finished on {finals}")


def serve_batches(srv, batches) -> list:
    """Submit and flush each batch of (chain, points, qformat) triples."""
    outs = []
    for batch in batches:
        for chain, pts, q in batch:
            srv.submit(chain, pts, qformat=q)
        outs.extend(srv.flush())
    return outs


def run_phase(name: str, clock: CompileClock, serve, items):
    """Serve ``items`` cold, check every result, then time one warm
    flush of the same shapes.  ``serve()`` returns (results, server,
    warm_flush) where ``warm_flush()`` re-serves one flush.  Returns
    the printed row and the results."""
    from repro import serving
    serving.reset_stats()
    c0, h0 = clock.seconds, clock.cache_hits
    outs, server, warm_flush = serve()
    compile_s, hits = clock.seconds - c0, clock.cache_hits - h0
    tally = Tally()
    for (chain, pts, q), out in zip(items, outs, strict=True):
        tally.add(chain, pts, q, out)
    launches = serving.stats["launches"]
    t0 = time.perf_counter()
    jax.block_until_ready(warm_flush())
    warm_s = time.perf_counter() - t0
    check_clean(server.reports)
    buckets = len(server.reports) - len(server.last_report)
    row = {"phase": name, "requests": len(items), "buckets": buckets,
           "launches": launches, "compile_s": compile_s,
           "compile_cache_hits": hits, "smoke_warm_flush_s": warm_s,
           "max_abs_err": tally.max_abs, "max_err_over_tol": tally.max_ratio,
           "q8_7_bitwise": tally.q_bitwise,
           "projected_points": tally.projected,
           "inside_frustum": tally.inside,
           "undecided_mask_points": tally.undecided_mask}
    print(json.dumps(row), flush=True)
    return row, outs


def batched(items, size):
    return [items[i:i + size] for i in range(0, len(items), size)]


def phase_stream(name, clock, items, *, flush=FLUSH):
    from repro import serving
    srv = serving.GeometryServer(backend=BACKEND)
    batches = batched(items, flush)

    def serve():
        outs = serve_batches(srv, batches)
        return outs, srv, lambda: serve_batches(srv, batches[:1])
    return run_phase(name, clock, serve, items)


def phase_async(clock, items):
    from repro import serving
    srv = serving.AsyncGeometryServer(backend=BACKEND,
                                      clock=serving.MonotonicClock())

    def run():
        tickets = [srv.submit_async(c, p, qformat=q) for c, p, q in items]
        srv.drain()
        return [t.result() for t in tickets]

    def serve():
        return run(), srv.server, run
    return run_phase("c_async", clock, serve, items)


def bunny_like(rng: np.random.Generator) -> np.ndarray:
    """A closed, bumpy 35,947-vertex surface about the Bunny's size
    (~0.15 m across), generated from ``rng``."""
    u = rng.standard_normal((BUNNY_VERTICES, 3))
    u /= np.linalg.norm(u, axis=1, keepdims=True)
    bumps = 1.0 + 0.08 * np.sin(7 * u[:, 0]) * np.cos(5 * u[:, 1])
    return (u * bumps[:, None] * (0.078, 0.076, 0.061)
            + (-0.017, 0.11, -0.002)).astype(np.float32)


def mesh_items(rng: np.random.Generator) -> list:
    """8 meshes, each under a viewing chain (one projective bucket) and
    under a diagonal chain (one diagonal bucket)."""
    from repro import graphics
    from repro.core.transform_chain import TransformChain
    items = []
    for i in range(8):
        mesh = bunny_like(rng)
        phi = 2 * np.pi * i / 8
        model = TransformChain.identity(3).rotate(float(phi), axis=1) \
            .translate(0.0, -0.1, 0.0)
        camera = graphics.Camera(
            eye=(0.4 * np.cos(phi), 0.1, 0.4 * np.sin(phi) + 0.1),
            target=(0.0, 0.0, 0.0), fov_y=np.pi / 9, near=0.05, far=5.0)
        view = graphics.viewing_chain(
            3, model=model, camera=camera,
            viewport=graphics.Viewport(width=1920.0, height=1080.0))
        items.append((view, mesh, None))
        diag = TransformChain.identity(3) \
            .scale(*rng.uniform(0.5, 2.0, 3).tolist()) \
            .translate(*rng.uniform(-1.0, 1.0, 3).tolist())
        items.append((diag, mesh, None))
    return items


def one_chip(clock) -> None:
    from repro.serving import workload
    stream = [(c, p, None) for c, p in workload.random_workload(
        seed=SEED, n_requests=N_REQUESTS, max_points=MAX_POINTS)]
    phase_stream("a_stream", clock, stream)
    mixed = workload.mixed_lane_workload(SEED, N_REQUESTS,
                                         max_points=MAX_POINTS)
    row, _ = phase_stream("b_q8_7_share", clock, mixed)
    check(row["q8_7_bitwise"] > 0, "no request ran on the q8.7 lane")
    async_items = [(c, p, None) for c, p in workload.random_workload(
        seed=SEED + 2, n_requests=N_ASYNC, max_points=MAX_POINTS)]
    phase_async(clock, async_items)
    meshes = mesh_items(np.random.default_rng([SEED, BUNNY_VERTICES]))
    row, _ = phase_stream("d_bunny_meshes", clock, meshes, flush=len(meshes))
    check(row["buckets"] == 2, f"{row['buckets']} buckets for the meshes")


def four_chips(clock) -> None:
    """Stream a with a 4-device mesh set, against one device."""
    from repro import serving
    from repro.launch.mesh import make_mesh
    from repro.serving import workload
    check(len(jax.devices()) >= 4, f"{len(jax.devices())} devices, need 4")
    stream = [(c, p, None) for c, p in workload.random_workload(
        seed=SEED, n_requests=N_REQUESTS, max_points=MAX_POINTS)]
    one, single = phase_stream("a_stream_one_device", clock, stream)
    mesh = make_mesh((4,), ("data",))
    with jax.set_mesh(mesh):
        four, sharded = phase_stream("a_stream_4_device_mesh", clock, stream)
        # the staging every launch above went through, on a bucket of 8
        # requests of 4,096 3-D points
        pts = np.zeros((8, MAX_POINTS, 3), np.float32)
        _, packed = serving.GeometryServer._stage((pts[:, :1],), pts)
    rows = {s.data.shape[0] for s in packed.addressable_shards}
    check(len(packed.sharding.device_set) == 4 and rows == {2},
          f"packed operand on {len(packed.sharding.device_set)} devices, "
          f"{rows} rows each")
    same = sum(np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(single, sharded, strict=True))
    check(same == len(stream), f"{len(stream) - same} mesh results differ "
          "from one device")
    print(json.dumps({"mesh": dict(mesh.shape), "packed_sharding":
                      str(packed.sharding.spec), "equal_to_one_device": same,
                      "launches_one_device": one["launches"],
                      "launches_mesh": four["launches"]}), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform})",
              file=sys.stderr)
        return 1
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro import roofline
    from repro.launch.compile_cache import use_compile_cache

    print(f"compile cache: {use_compile_cache()}")
    print(f"devices: {len(devices)} "
          f"{[d.device_kind for d in devices]}")
    kind = devices[0].device_kind
    chip = roofline.peaks(kind)
    print(f"peaks ({kind}): bf16 {chip.flops:.3g} FLOP/s, int8 "
          f"{chip.int8_ops:.3g} OP/s, HBM {chip.hbm_bw:.3g} B/s, "
          f"{chip.hbm_bytes:.3g} B; {chip.source}")
    clock = CompileClock()
    t0 = time.perf_counter()
    (four_chips if args.chips == 4 else one_chip)(clock)
    print(f"total: {time.perf_counter() - t0:.1f} s, compile "
          f"{clock.seconds:.1f} s, {clock.cache_hits} compile-cache hits")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
