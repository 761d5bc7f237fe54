"""Batched transform-serving engine tests: packed-batch equality against
per-request ``apply``, the size-bucketing waste cap, the one-compile-per-
structure (no-retrace) guarantee under load, oversized-bucket sharding,
the packed-batch launch/byte accounting, and the device-to-host copy
each dispatched launch starts at dispatch.
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import jax.numpy as jnp
import pytest

from repro import obs, serving
from repro.core import transform_chain as tc
from repro.kernels import opcount
from repro.serving import bucketing, engine, workload


def _fresh_server(**kw):
    serving.reset_stats()
    serving.clear_plan_cache()
    return serving.GeometryServer(**kw)


def assert_diag_within_fma(out, exp, chain, pts):
    """Diagonal plans compute ``p*s + t`` per coordinate.  XLA:CPU may
    contract that into one fused multiply-add in one program shape and not
    in another, and the two differ by at most the rounding of the product:
    half an ulp of ``|p*s|``, plus half an ulp of the result.  Two float32
    epsilons of ``|p*s| + |t|`` bound both."""
    s, t = chain.fold()
    bound = 2 * np.finfo(np.float32).eps * (np.abs(pts * s) + np.abs(t))
    diff = np.abs(np.asarray(out, np.float64) - np.asarray(exp, np.float64))
    assert (diff <= bound).all(), float((diff - bound).max())


def _serve_and_compare(backend, reqs, **server_kw):
    """Serve ``reqs`` packed and compare each result to per-request apply.

    The fold is bit-identical by construction (one shared host code path),
    so the only permitted daylight is the fused application's rounding
    freedom (XLA:CPU contracts float multiply-adds per program shape):
    diagonal plans to the one-rounding bound of ``assert_diag_within_fma``;
    matrix plans to float32-epsilon scale -- far inside the 2e-4 the
    compiler's own oracle tests allow; projective plans to a slightly
    wider relative tolerance (the perspective divide amplifies the
    last-ULP freedom), with the cull mask carried on ``Projected.mask``
    matching ``chain.project``.
    """
    srv = _fresh_server(backend=backend, **server_kw)
    outs = srv.serve(reqs)
    assert len(outs) == len(reqs)
    for chain, pts in reqs:
        assert pts.dtype == np.float32
    for (chain, pts), out in zip(reqs, outs):
        assert out.shape == pts.shape
        if chain.is_projective:
            exp, mexp = chain.project(jnp.asarray(pts), backend=backend)
            assert isinstance(out, serving.Projected)
            np.testing.assert_array_equal(np.asarray(out.mask),
                                          np.asarray(mexp))
            np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                                       rtol=1e-5, atol=1e-5)
            continue
        exp = chain.apply(jnp.asarray(pts), backend=backend)
        if chain.is_diagonal:
            assert_diag_within_fma(out, exp, chain, pts)
        else:
            np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                                       rtol=2e-6, atol=2e-6)
    return srv


# ---------------------------------------------------------------------------
# packed == per-request across random mixed workloads
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["ref", "interpret"])
def test_packed_matches_per_request_mixed_workload(backend):
    rng = np.random.default_rng(11)
    reqs = workload.random_workload(rng, 48, max_points=300)
    # the default template pool now includes projective viewing chains
    assert any(c.is_projective for c, _ in reqs)
    srv = _serve_and_compare(backend, reqs)
    # structures x sizes bucket; every bucket saved launches vs per-request
    assert serving.stats["requests"] == 48
    assert serving.stats["launches"] < 48
    assert serving.stats["launches"] == sum(r.launches
                                            for r in srv.last_report)


def test_packed_results_deterministic_across_flushes():
    """Same workload, same bucket shapes -> bitwise identical results."""
    rng = np.random.default_rng(5)
    reqs = workload.random_workload(rng, 24, max_points=200)
    out1 = _fresh_server(backend="ref").serve(reqs)
    out2 = serving.GeometryServer(backend="ref").serve(reqs)
    for a, b in zip(out1, out2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_padding_never_contaminates_payload():
    """A request's bits must not depend on WHICH requests share its bucket
    (same bucket shape, different neighbours)."""
    rng = np.random.default_rng(9)
    dim, kinds = 2, "TSRT"
    probe = workload.chain_for(rng, dim, kinds)
    pts = rng.standard_normal((50, dim)).astype(np.float32)
    outs = []
    for neighbour_seed in (1, 2):
        nrng = np.random.default_rng(neighbour_seed)
        reqs = [(probe, pts)] + [
            (workload.chain_for(nrng, dim, kinds),
             nrng.standard_normal((int(nrng.integers(1, 64)), dim))
             .astype(np.float32))
            for _ in range(5)]
        outs.append(np.asarray(_fresh_server(backend="ref").serve(reqs)[0]))
    np.testing.assert_array_equal(outs[0], outs[1])


def test_identity_passes_through_and_empty_rejects():
    """Identity chains skip the launch path entirely; empty point sets
    are rejected AT SUBMIT with a typed, ticket-carrying error (an empty
    result is indistinguishable from a lost one) -- PR 6 tightened what
    used to be a silent pass-through."""
    srv = _fresh_server(backend="ref")
    pts = np.ones((4, 2), np.float32)
    srv.submit(tc.TransformChain.identity(2), pts)
    with pytest.raises(serving.errors.EmptyPointsError) as ei:
        srv.submit(workload.chain_for(np.random.default_rng(0), 2, "TS"),
                   np.zeros((0, 2), np.float32))
    assert ei.value.ticket == 1 and ei.value.code == "empty"
    (out_id,) = srv.flush()
    np.testing.assert_array_equal(np.asarray(out_id), pts)
    assert serving.stats["launches"] == 0
    assert serving.stats["rejected_requests"] == 1


def test_leading_batch_shapes_roundtrip():
    """(B, N, d)-shaped requests come back with their original shape."""
    rng = np.random.default_rng(3)
    chain = workload.chain_for(rng, 3, "TRS")
    pts = rng.standard_normal((4, 13, 3)).astype(np.float32)
    out = _fresh_server(backend="ref").serve([(chain, pts)])[0]
    assert out.shape == pts.shape
    exp = chain.apply(jnp.asarray(pts), backend="ref")
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=2e-6, atol=2e-6)


def test_submitted_points_are_copied():
    """Mutating the caller's buffer between submit and flush must not
    change the queued request (and identity results must not alias it)."""
    rng = np.random.default_rng(1)
    chain = workload.chain_for(rng, 2, "TS")
    pts = rng.standard_normal((20, 2)).astype(np.float32)
    snapshot = pts.copy()
    srv = _fresh_server(backend="ref")
    srv.submit(chain, pts)
    srv.submit(tc.TransformChain.identity(2), pts)
    pts[:] = 0.0
    out, out_id = srv.flush()
    np.testing.assert_array_equal(
        np.asarray(out),
        np.asarray(chain.apply(jnp.asarray(snapshot), backend="ref")))
    np.testing.assert_array_equal(np.asarray(out_id), snapshot)


def test_dim_mismatch_rejected():
    srv = _fresh_server(backend="ref")
    with pytest.raises(ValueError):
        srv.submit(tc.TransformChain.identity(2).translate(1.0),
                   np.zeros((5, 3), np.float32))


# ---------------------------------------------------------------------------
# size-bucketing policy: the waste cap
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("waste_cap", [0.5, 0.25, 0.125])
def test_padded_length_respects_waste_cap(waste_cap):
    min_len = 8
    prev = 0
    for n in range(1, 3000):
        lpad = bucketing.padded_length(n, min_len=min_len,
                                       waste_cap=waste_cap)
        assert lpad >= n and lpad >= min_len
        if n >= min_len:
            assert bucketing.waste_fraction(n, lpad) < waste_cap, \
                f"n={n} lpad={lpad}"
        assert lpad >= prev          # monotone grid
        prev = lpad


def test_pow2_grid_at_default_cap():
    """waste_cap=0.5 degenerates to power-of-two padding."""
    for n in (1, 8, 9, 17, 100, 1000):
        lpad = bucketing.padded_length(n)
        assert lpad & (lpad - 1) == 0


def test_engine_waste_stays_under_cap():
    rng = np.random.default_rng(17)
    reqs = workload.random_workload(rng, 40, max_points=400, min_points=8)
    for cap in (0.5, 0.25):
        srv = _fresh_server(backend="ref", waste_cap=cap)
        srv.serve(reqs)
        for rep in srv.last_report:
            assert rep.waste < cap, rep


# ---------------------------------------------------------------------------
# plan economy: one compile per structure under load, few launches
# ---------------------------------------------------------------------------

def test_one_plan_compile_per_structure_under_load():
    rng = np.random.default_rng(23)
    templates = ((2, "TSRT"), (3, "SAT"), (2, "TST"))
    reqs = workload.random_workload(rng, 60, templates=templates,
                                    max_points=250)
    srv = _fresh_server(backend="ref")
    srv.serve(reqs)
    assert serving.stats["plan_compiles"] == len(templates)
    assert serving.stats["plan_hits"] == len(srv.last_report) - len(templates)
    # a second wave: same request sizes (same bucket shapes) but fresh
    # parameter values -- the serving hot path.  No new compiles, no new
    # traces.
    traces = serving.stats["traces"]
    prng = np.random.default_rng(99)
    wave2 = [(workload.chain_for(prng, ch.dim,
                                 "".join(k for k, _ in ch.kinds)), pts)
             for ch, pts in reqs]
    srv.serve(wave2)
    assert serving.stats["plan_compiles"] == len(templates)
    assert serving.stats["traces"] == traces, \
        "seen bucket shapes must not retrace"


def test_bucketing_groups_by_structure_and_size():
    rng = np.random.default_rng(31)
    # 16 requests, one structure, sizes split across two pow2 classes
    chain_rng = np.random.default_rng(7)
    reqs = []
    for i in range(16):
        n = 30 if i % 2 else 120          # -> lpad 32 and 128
        reqs.append((workload.chain_for(chain_rng, 2, "TSRT"),
                     rng.standard_normal((n, 2)).astype(np.float32)))
    srv = _fresh_server(backend="ref")
    srv.serve(reqs)
    assert serving.stats["buckets"] == 2
    assert serving.stats["launches"] == 2
    assert {r.lpad for r in srv.last_report} == {32, 128}
    assert all(r.requests == 8 for r in srv.last_report)


# ---------------------------------------------------------------------------
# the bucket key is the plan identity (dim, kind), not the chain structure
# ---------------------------------------------------------------------------

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: two requests to each of three size classes, per structure
_SIZES = (7, 30, 120, 7, 30, 120)


def _every_template(seed):
    """(chain, points, qformat) requests: each ``workload.TEMPLATES``
    entry (a 3-D template with a rotation under three rotation-axis
    variants, the axis being part of a chain's structure), then two
    diagonal structures on the q8.7 lane; each structure sends
    ``_SIZES`` points, with fresh parameters per request."""
    rng = np.random.default_rng(seed)
    reqs = []
    for dim, kinds in workload.TEMPLATES:
        variants = 3 if dim == 3 and "R" in kinds else 1
        by_structure: dict = {}
        while len(by_structure) < variants or \
                min(map(len, by_structure.values())) < len(_SIZES):
            c = workload.chain_for(rng, dim, kinds)
            if c.structure in by_structure or len(by_structure) < variants:
                by_structure.setdefault(c.structure, []).append(c)
        for chains in by_structure.values():
            reqs += [(c, rng.standard_normal((n, dim)).astype(np.float32),
                      None) for c, n in zip(chains, _SIZES)]
    for kinds in ("TST", "TTSS"):
        reqs += [(workload.chain_for(rng, 2, kinds),
                  rng.uniform(-1, 1, (n, 2)).astype(np.float32), "q8.7")
                 for n in _SIZES]
    return reqs


@pytest.mark.parametrize("backend", ["ref", "interpret"])
def test_merged_buckets_are_bitwise_per_structure_buckets(backend):
    """One flush of every template, rotation-axis variants and a q8.7
    lane included, returns bit for bit what serving each structure
    alone in its own flush returns: a bucket now holds every structure
    of one plan identity, and each request still runs the same body on
    operands of the same shapes.  Each structure sends two requests to
    each size class, because on the CPU a one-row batch compiles to a
    program of its own whose float contraction may differ by one
    rounding (the module's equality contract), under either key."""
    reqs = _every_template(16)
    trc = obs.Tracer()
    srv = _fresh_server(backend=backend)
    with obs.installed(trc):
        for chain, pts, qname in reqs:
            srv.submit(chain, pts, qformat=qname)
        merged = srv.flush()
    groups: dict = {}
    for i, (chain, _, qname) in enumerate(reqs):
        groups.setdefault((chain.structure, qname), []).append(i)
    alone = [None] * len(reqs)
    for (_, qname), idx in groups.items():
        one = serving.GeometryServer(backend=backend)
        outs = one.serve([reqs[i][:2] for i in idx], qformat=qname)
        for i, out in zip(idx, outs, strict=True):
            alone[i] = out
    for a, b in zip(merged, alone, strict=True):
        assert type(a) is type(b) and a.dtype == b.dtype
        assert np.array_equal(a, b)
        if isinstance(a, serving.Projected):
            assert np.array_equal(a.mask, b.mask)
    # 19 structures (17 float, 2 q8.7) x 3 size classes, in 7 plan
    # identities and lanes x 3 size classes
    assert len(groups) == 19
    assert srv.metrics.value("bucket_structures") == 57
    assert srv.metrics.value("buckets") == 21
    assert srv.metrics.value("q_fallbacks") == 0
    assert {r.structure for r in srv.last_report} == {
        "2D:diag", "2D:matrix", "2D:projective",
        "3D:diag", "3D:matrix", "3D:projective"}
    # a projective chain without a cull and one with it share a launch
    letters = {i: "".join(k for k, _ in c.kinds)
               for i, (c, _, _) in enumerate(reqs)}
    shared = [s for s in trc.spans if s.name == "launch"
              and {"TSRP", "MPC"} <= {letters[t] for t in s.tickets}]
    assert len(shared) == 3


def test_the_mixed_stream_pass_makes_126_launches(monkeypatch):
    """The chip benchmark's ``mixed_stream.flush256`` pass (4 flushes of
    256 requests over 11 templates): the server's own ``_bucket_key``
    on the pass's shapes makes 126 buckets, where a bucket per chain
    structure made 304.  Nothing is served."""
    monkeypatch.syspath_prepend(REPO)
    from chipbench import harness
    from chipbench.families import template_stream
    bench = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    _, config, traffic = harness.cell_parts(bench, "mixed_stream.flush256")
    shapes = template_stream.shapes(config, traffic)
    srv = serving.GeometryServer(backend="ref")
    rng = np.random.default_rng(0)
    per = traffic["per_flush"]
    by_plan, by_structure = [], []
    for f in range(0, len(shapes), per):
        keys, structures = set(), set()
        for dim, kinds, n, axes in shapes[f:f + per]:
            chain, _ = template_stream.chain(rng, dim, kinds, axes)
            key = srv._bucket_key(
                srv.validate(chain, np.zeros((n, dim), np.float32)),
                "pallas")
            keys.add(key)
            structures.add((chain.structure,) + key[2:])
        by_plan.append(len(keys))
        by_structure.append(len(structures))
    assert by_structure == [76, 69, 80, 79] and sum(by_structure) == 304
    assert by_plan == [33, 30, 31, 32]
    assert sum(by_plan) / len(shapes) == 126 / 1024 == 0.123046875


def test_two_structures_of_one_plan_identity_share_one_plan_compile():
    rng = np.random.default_rng(61)
    a = workload.chain_for(rng, 2, "TSRT")
    b = workload.chain_for(rng, 2, "ASM")
    assert a.structure != b.structure
    assert engine.plan_identity(a) == engine.plan_identity(b) \
        == (2, "matrix")
    srv = _fresh_server(backend="ref")
    srv.serve([(a, rng.standard_normal((30, 2)).astype(np.float32)),
               (b, rng.standard_normal((100, 2)).astype(np.float32))])
    # two size classes: two buckets, one compiled plan
    assert serving.stats["buckets"] == 2
    assert serving.stats["plan_compiles"] == 1
    assert serving.stats["plan_hits"] == 1


def test_two_structures_of_one_plan_identity_share_one_bucket():
    rng = np.random.default_rng(62)
    reqs = [(workload.chain_for(rng, 2, kinds),
             rng.standard_normal((20, 2)).astype(np.float32))
            for kinds in ("TST", "TTSS") * 4]
    trc = obs.Tracer()
    srv = _fresh_server(backend="ref")
    with obs.installed(trc):
        outs = srv.serve(reqs)
    for (chain, pts), out in zip(reqs, outs, strict=True):
        assert_diag_within_fma(
            out, chain.apply(jnp.asarray(pts), backend="ref"), chain, pts)
    assert serving.stats["buckets"] == serving.stats["launches"] == 1
    assert serving.stats["bucket_structures"] == 2
    assert srv.metrics.value("bucket_structures") == 2
    (rep,) = srv.last_report
    assert rep.structure == "2D:diag" and rep.requests == 8
    (span,) = [s for s in trc.spans if s.name == "bucket.assemble"]
    assert span.attrs["structures"] == 2
    assert span.track == "2D:diag|ref|<f4|32"


# ---------------------------------------------------------------------------
# one fold row a launch: the folds travel as one (B, words) array
# ---------------------------------------------------------------------------

#: (dim, template) of each plan kind, and the lanes it has
_ROW_CASES = [(dim, kind, lane)
              for dim in (2, 3)
              for kind in ("diag", "matrix", "projective")
              for lane in (None, "q8.7")
              if not (lane and kind == "projective")]
_TEMPLATE = {(2, "diag"): "TST", (3, "diag"): "SAT",
             (2, "matrix"): "TSRT", (3, "matrix"): "TRS",
             (2, "projective"): "TSP", (3, "projective"): "TSRP"}


def _row_requests(dim, kind, seed, sizes=(7, 30, 30, 120)):
    """Requests of one plan identity at three size classes: buckets of
    one row and of two."""
    rng = np.random.default_rng(seed)
    return [(workload.chain_for(rng, dim, _TEMPLATE[dim, kind]),
             rng.uniform(-1, 1, (n, dim)).astype(np.float32))
            for n in sizes]


def _case_id(case):
    dim, kind, lane = case
    return f"{dim}D-{kind}-{lane or 'f32'}"


@pytest.mark.parametrize("case", _ROW_CASES, ids=_case_id)
def test_a_fold_row_splits_back_into_each_part_bit_for_bit(case):
    """``_fold_row`` lays each request's fold parts side by side in one
    row of the lane's dtype, and the plan's ``_fold_parts`` takes them
    apart on the device into exactly the arrays the parts stacked one by
    one make: every bit, infinite cull bounds included."""
    import jax
    from repro import quantize
    dim, kind, lane = case
    folds = [c.fold() for c, _ in _row_requests(dim, kind, 71)]
    dtype = np.float32
    if lane is not None:
        folds = [quantize.quantize_fold(f, kind, lane) for f in folds]
        dtype = np.int16
    rows = engine._fold_row(folds, dtype)
    assert rows.dtype == dtype and rows.shape == (
        len(folds), sum(part.size for part in folds[0]))
    parts = jax.jit(lambda r: engine._fold_parts(r, kind, dim))(rows)
    for got, want in zip(parts, zip(*folds), strict=True):
        want = np.stack(want)
        assert got.dtype == want.dtype and got.shape == want.shape
        assert np.asarray(got).tobytes() == want.tobytes()


def _assert_served_is_apply(chain, pts, lane, out):
    """``out`` is ``chain``'s own ``apply`` (``project``, with its cull
    mask) of ``pts`` bit for bit; a float diagonal plan to the
    one-rounding bound of ``assert_diag_within_fma``, since XLA:CPU
    contracts ``p*s + t`` into a fused multiply-add in one program shape
    and not in another, with folds staged either way."""
    if chain.is_projective:
        exp, mask = chain.project(jnp.asarray(pts), backend="ref")
        assert np.array_equal(out.mask, np.asarray(mask))
    else:
        exp = chain.apply(jnp.asarray(pts), backend="ref", dtype=lane)
    exp = np.asarray(exp)
    assert out.dtype == exp.dtype and out.shape == exp.shape
    if chain.is_diagonal and lane is None:
        assert_diag_within_fma(out, exp, chain, pts)
    else:
        assert out.tobytes() == exp.tobytes()


@pytest.mark.parametrize("case", _ROW_CASES, ids=_case_id)
def test_a_flush_of_fold_rows_is_bitwise_per_request_apply(case):
    """Each request served through a flush, whose launches stage one
    fold row and the points, equals its chain's own ``apply`` (or
    ``project``, with its cull mask) bit for bit, on one-row and
    two-row buckets, on the float and the q8.7 lane."""
    dim, kind, lane = case
    reqs = _row_requests(dim, kind, 72)
    srv = _fresh_server(backend="ref")
    outs = srv.serve(reqs, qformat=lane)
    assert serving.stats["launches"] == 3
    assert serving.stats["host_operands"] == 2 * serving.stats["launches"]
    for (chain, pts), out in zip(reqs, outs, strict=True):
        _assert_served_is_apply(chain, pts, lane, out)


_MESH_ROWS = """
    import json
    import jax, numpy as np
    from repro import serving
    from repro.launch.mesh import make_mesh
    from repro.serving import workload
    cases = json.loads(sys.argv[1])
    rng = np.random.default_rng(73)
    reqs = [(workload.chain_for(rng, dim, letters),
             rng.uniform(-1, 1, (n, dim)).astype(np.float32), lane)
            for dim, letters, lane in cases for n in (7, 30, 30, 120, 30)]

    def serve():
        srv = serving.GeometryServer(backend="ref")
        for chain, pts, lane in reqs:
            srv.submit(chain, pts, qformat=lane)
        return srv.flush()
    one = serve()
    serving.reset_stats()
    with jax.set_mesh(make_mesh((4,), ("data",))):
        mesh = serve()
    saved = {}
    for i, (a, b) in enumerate(zip(one, mesh, strict=True)):
        saved.update({f"one{i}": a, f"mesh{i}": b})
        if getattr(a, "mask", None) is not None:
            saved.update({f"one{i}.mask": a.mask, f"mesh{i}.mask": b.mask})
    np.savez(sys.argv[2], **saved)
    print(json.dumps({"launches": serving.stats["launches"],
                      "host_operands": serving.stats["host_operands"]}))
"""


def test_a_mesh_flush_of_fold_rows_is_bitwise_per_request_apply(tmp_path):
    """Under ``jax.set_mesh`` on 4 virtual CPU devices ``_stage`` shards
    each launch's one fold row on the batch axis with its points: every
    plan kind, dimension and lane returns one device's results bit for
    bit, and each equals per-request ``apply`` as on one device (a
    child process, so the device-count flag stays out of this one)."""
    cases = [(dim, _TEMPLATE[dim, kind], lane)
             for dim, kind, lane in _ROW_CASES]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(REPO, "src"))
    script = "import sys\n" + textwrap.dedent(_MESH_ROWS)
    saved = tmp_path / "outs.npz"
    out = subprocess.run(
        [sys.executable, "-c", script, json.dumps(cases), str(saved)],
        capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    # three size classes for each of the 10 plan identities and lanes
    assert rec["launches"] == 3 * len(_ROW_CASES)
    assert rec["host_operands"] == 2 * rec["launches"]
    arrays = np.load(saved)
    rng = np.random.default_rng(73)
    reqs = [(workload.chain_for(rng, dim, letters),
             rng.uniform(-1, 1, (n, dim)).astype(np.float32), lane)
            for dim, letters, lane in cases for n in (7, 30, 30, 120, 30)]
    for i, (chain, pts, lane) in enumerate(reqs):
        one, mesh = arrays[f"one{i}"], arrays[f"mesh{i}"]
        assert one.dtype == mesh.dtype and one.tobytes() == mesh.tobytes()
        if chain.is_projective:
            mask = arrays[f"mesh{i}.mask"]
            assert np.array_equal(arrays[f"one{i}.mask"], mask)
            mesh = engine._projected(mesh, mask)
        _assert_served_is_apply(chain, pts, lane, mesh)


# ---------------------------------------------------------------------------
# sharding oversized buckets
# ---------------------------------------------------------------------------

def test_oversized_bucket_shards_and_matches():
    rng = np.random.default_rng(41)
    chain_rng = np.random.default_rng(2)
    reqs = [(workload.chain_for(chain_rng, 2, "TSRT"),
             rng.standard_normal((100, 2)).astype(np.float32))
            for _ in range(12)]                   # one bucket, lpad=128
    srv = _serve_and_compare("ref", reqs, max_points_per_launch=3 * 128)
    assert serving.stats["buckets"] == 1
    assert serving.stats["launches"] == 4        # 12 reqs / 3 rows per shard
    assert serving.stats["shards"] == 3
    assert srv.last_report[0].launches == 4


# ---------------------------------------------------------------------------
# packed-batch byte accounting
# ---------------------------------------------------------------------------

def test_serving_records_packed_bytes_per_launch():
    rng = np.random.default_rng(43)
    chain_rng = np.random.default_rng(4)
    reqs = [(workload.chain_for(chain_rng, 2, "TSRT"),
             rng.standard_normal((60, 2)).astype(np.float32))
            for _ in range(8)]                    # one matrix bucket, lpad=64
    srv = _fresh_server(backend="ref")
    with opcount.counting() as records:
        srv.serve(reqs)
    serve_records = [r for r in records if r[0].startswith("serve_bucket_")]
    assert len(serve_records) == serving.stats["launches"] == 1
    (_, nbytes), = serve_records
    assert nbytes == opcount.packed_chain_bytes(8, 64, 2, kind="matrix")
    # the batched launch moves padded bytes, but still strictly fewer than
    # 8 requests x k=4 primitives of sequential per-primitive dispatch
    sequential = 8 * 4 * 2 * (60 * 2 * 4)
    assert nbytes < sequential


# ---------------------------------------------------------------------------
# stats reset semantics: the launch invariant across flush cycles
# ---------------------------------------------------------------------------

def test_stats_launch_invariant_across_flush_cycles():
    """``stats["launches"] == sum(r.launches for r in srv.reports)`` must
    hold across MULTIPLE flushes (reports accumulate; last_report is only
    the latest flush's slice) and survive a per-server reset."""
    rng = np.random.default_rng(51)
    srv = _fresh_server(backend="ref")
    for cycle in range(3):
        for chain, pts in workload.random_workload(rng, 12, max_points=80):
            srv.submit(chain, pts)
        srv.flush()
        assert serving.stats["launches"] == \
            sum(r.launches for r in srv.reports)
    assert len(srv.reports) > len(srv.last_report)  # accumulated, not sliced
    # per-server reset zeroes BOTH sides of the invariant in one step
    srv.reset_stats()
    assert serving.stats["launches"] == 0 and srv.reports == []
    for chain, pts in workload.random_workload(rng, 8, max_points=80):
        srv.submit(chain, pts)
    srv.flush()
    assert serving.stats["launches"] == sum(r.launches for r in srv.reports)


def test_stats_launch_invariant_holds_through_recovery():
    """Recovery launches (retries, ladder rungs, bisection probes) count
    into the SAME per-bucket reports the module counter sums over, so the
    invariant survives fault injection too."""
    reqs = workload.mixed_lane_workload(33, 32)
    inj = serving.FaultInjector(seed=33, flaky_rate=0.12, backend_rate=0.08,
                                corrupt_rate=0.08, poison_rate=0.05)
    serving.reset_stats()
    serving.clear_plan_cache()
    srv = serving.GeometryServer(backend="interpret", injector=inj,
                                 fault_config=serving.FaultConfig(
                                     backoff_base_s=0.0))
    for cycle in range(2):
        for chain, pts, qname in reqs:
            srv.submit(chain, pts, qformat=qname)
        srv.flush()
        assert serving.stats["launches"] == \
            sum(r.launches for r in srv.reports)
    assert serving.stats["launch_failures"] > 0     # the ladder really ran


# ---------------------------------------------------------------------------
# the device->host copy starts at dispatch
# ---------------------------------------------------------------------------

def _mixed_requests(seed):
    return workload.random_workload(np.random.default_rng(seed), 24,
                                    max_points=100)


@pytest.mark.parametrize("backend", ["ref", "interpret"])
def test_flush_prefetches_every_output_leaf(backend, host_copies):
    srv = _serve_and_compare(backend, _mixed_requests(11))
    kinds = {r.kind for r in srv.last_report}
    assert kinds == {"diag", "matrix", "projective"}
    # one copy per output leaf: a projective launch has points and mask
    assert len(host_copies) == sum(
        r.launches * (2 if r.kind == "projective" else 1)
        for r in srv.last_report)
    assert serving.stats["prefetches"] == serving.stats["launches"] > 1
    assert srv.metrics.value("prefetches") == serving.stats["launches"]


@pytest.mark.parametrize("backend", ["ref", "interpret"])
def test_prefetched_results_are_bitwise_unprefetched(backend, monkeypatch):
    """Only the moment of the copy moves: the same flush served without
    the prefetch gives the same bits, cull masks included."""
    reqs = _mixed_requests(12)
    with_copy = _fresh_server(backend=backend).serve(reqs)
    assert serving.stats["prefetches"] == serving.stats["launches"] > 1
    assert any(isinstance(r, serving.Projected) for r in with_copy)
    monkeypatch.setattr(engine, "_prefetch", lambda out: None)
    without = serving.GeometryServer(backend=backend).serve(reqs)
    for a, b in zip(with_copy, without):
        assert np.array_equal(np.asarray(a), np.asarray(b))
        if isinstance(a, serving.Projected):
            assert np.array_equal(a.mask, b.mask)


def test_one_launch_flush_prefetches_once(host_copies):
    srv = _serve_and_compare(
        "ref", [(workload.chain_for(np.random.default_rng(3), 3, "MPC"),
                 np.random.default_rng(4).standard_normal((40, 3))
                 .astype(np.float32))])
    assert serving.stats["launches"] == serving.stats["prefetches"] == 1
    assert len(host_copies) == 2               # projected points, mask
    assert srv.metrics.value("prefetches") == 1


@pytest.mark.parametrize("pending", ["none", "identity"])
def test_flush_without_launches_prefetches_nothing(pending, host_copies):
    srv = _fresh_server(backend="ref")
    if pending == "identity":
        srv.submit(tc.TransformChain.identity(2),
                   np.ones((4, 2), np.float32))
    assert len(srv.flush()) == (pending == "identity")
    assert serving.stats["launches"] == serving.stats["prefetches"] == 0
    assert host_copies == [] and srv.metrics.value("prefetches") == 0


_MESH_PREFETCH = """
    import json, sys
    import jax, numpy as np
    from repro import serving
    from repro.launch.mesh import make_mesh
    from repro.serving import workload
    backend = sys.argv[1]
    cls = type(jax.numpy.zeros(()))
    real = cls.copy_to_host_async
    spans = []                      # devices each started copy spans

    def spy(self):
        spans.append(len(self.sharding.device_set))
        return real(self)
    cls.copy_to_host_async = spy
    reqs = workload.random_workload(np.random.default_rng(21), 24,
                                    max_points=100)
    one = serving.GeometryServer(backend=backend).serve(reqs)
    one_spans = list(spans)
    del spans[:]
    serving.reset_stats()
    with jax.set_mesh(make_mesh((4,), ("data",))):
        mesh = serving.GeometryServer(backend=backend).serve(reqs)
    same = sum(np.array_equal(np.asarray(a), np.asarray(b))
               and np.array_equal(getattr(a, "mask", None),
                                  getattr(b, "mask", None))
               for a, b in zip(one, mesh, strict=True))
    print(json.dumps({"one_spans": one_spans, "mesh_spans": spans,
                      "launches": serving.stats["launches"],
                      "prefetches": serving.stats["prefetches"],
                      "same": same, "n": len(reqs)}))
"""


@pytest.mark.parametrize("backend", ["ref", "interpret"])
def test_mesh_flush_prefetches_sharded_outputs(backend):
    """Under a 4-device mesh every launch's output is sharded over the
    mesh, its copy back still starts at dispatch, and the results are
    the single device's bit for bit.  Runs in a child process on four
    virtual CPU devices, so the device-count flag stays out of this
    one."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(repo, "src"))
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(_MESH_PREFETCH), backend],
        capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["prefetches"] == rec["launches"] > 1
    # one copy per output leaf on either side, each over the whole mesh
    assert len(rec["mesh_spans"]) == len(rec["one_spans"]) >= rec["launches"]
    assert set(rec["one_spans"]) == {1} and set(rec["mesh_spans"]) == {4}
    assert rec["same"] == rec["n"]
