"""Scene-graph fold-CSE correctness: the bitwise + counting contracts.

What is pinned here (see ``docs/scene_graph.md``):

  * **bitwise**: any interleaving of node edits and world-fold queries
    yields folds bit-identical to folding every world chain from
    scratch with ``fold_structure`` (the carry fold re-runs the same
    loop, so equality is exact, not approximate) -- seeded sweeps plus
    a hypothesis property over random trees and edit/query schedules;
  * **counting**: fold executions per "frame" equal the dirty-subtree
    size (O(changed nodes), the benchmark's gated claim), reverting a
    node to previously-folded content costs ZERO folds (content-hash
    cache), and a second scene sharing the ``FoldCache`` serves its
    common subchains from the first scene's entries;
  * **stability**: content digests are pure functions of chain content
    -- equal across processes (no ``PYTHONHASHSEED`` dependence) and
    across graphs built in different orders, and the cached fold bytes
    are identical to the scratch fold bytes;
  * **serving**: ``submit_scene`` / ``submit_scene_async`` results are
    bitwise equal to submitting the node's world chain, bitwise equal
    to per-request ``apply`` on diagonal float32 plans and on the q8.7
    lane for every plan kind, and within the engine's documented
    last-ULP envelope on float matrix plans.

``hypothesis`` is an OPTIONAL dependency (see tests/README.md): the
property tests are skipped without it; the seeded sweeps always run.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                      # optional dep -- skip, don't fail
    HAVE_HYPOTHESIS = False

    class _NoStrategies:
        def __getattr__(self, _name):
            return lambda *a, **k: None

    st = _NoStrategies()

    def settings(*_a, **_k):
        return lambda f: f

    def given(*_a, **_k):
        return lambda f: pytest.mark.skip(
            reason="hypothesis not installed (optional dep)")(f)

from repro import scene, serving
from repro.core import transform_chain as tc
from repro.obs import trace as obst
from repro.serving.async_engine import AsyncGeometryServer
from repro.serving.clock import VirtualClock


def _bytes_eq(a, b) -> bool:
    a, b = np.asarray(a), np.asarray(b)
    return a.dtype == b.dtype and a.shape == b.shape \
        and a.tobytes() == b.tobytes()


def _fold_eq(fa, fb) -> bool:
    return len(fa) == len(fb) and all(_bytes_eq(x, y)
                                      for x, y in zip(fa, fb))


def _scratch_fold(graph, name):
    c = graph.world_chain(name)
    return tc.fold_structure(c.structure, c.params)


def _rand_local(rng, dim, *, kinds="TSAR", max_len=3):
    """A random local chain (possibly empty) over the given kind set."""
    c = tc.TransformChain.identity(dim)
    for _ in range(int(rng.integers(0, max_len + 1))):
        k = kinds[int(rng.integers(len(kinds)))]
        if k == "T":
            c = c.translate(*rng.standard_normal(dim).astype(np.float32))
        elif k == "S":
            c = c.scale(*(rng.uniform(0.5, 2.0, dim).astype(np.float32)))
        elif k == "A":
            c = c.affine(rng.uniform(0.5, 2.0, dim).astype(np.float32),
                         rng.standard_normal(dim).astype(np.float32))
        else:
            axis = int(rng.integers(3)) if dim == 3 else None
            c = c.rotate(float(rng.uniform(-3, 3)), axis=axis)
    return c


def _rand_tree(rng, dim, n_nodes, **local_kw):
    """Random forest: each node parents under a uniformly random earlier
    node (or is a root); returns (graph, names)."""
    g = scene.SceneGraph(dim, cache=scene.FoldCache())
    names = []
    for i in range(n_nodes):
        parent = None
        if names and rng.uniform() < 0.8:
            parent = names[int(rng.integers(len(names)))]
        names.append(g.add(f"n{i}", _rand_local(rng, dim, **local_kw),
                           parent=parent))
    return g, names


# ---------------------------------------------------------------------------
# carry folds: piecewise == one-pass, bit for bit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dim", [2, 3])
def test_fold_carry_piecewise_bitwise(dim):
    rng = np.random.default_rng(101 + dim)
    for _ in range(20):
        c = _rand_local(rng, dim, max_len=6)
        if not len(c):
            continue
        kind = tc.plan_kind_of(c.structure)
        one = tc.fold_structure(c.structure, c.params)
        for cut in range(len(c.kinds) + 1):
            carry = tc.fold_carry_identity(kind, dim)
            carry = tc.fold_carry_extend(kind, dim, carry,
                                         c.kinds[:cut], c.params[:cut])
            carry = tc.fold_carry_extend(kind, dim, carry,
                                         c.kinds[cut:], c.params[cut:])
            assert _fold_eq(one, tc.fold_carry_finish(kind, carry))


def test_fold_carry_projective_bitwise():
    c = (tc.TransformChain.identity(3)
         .translate(1.0, 2.0, 3.0).rotate(0.3, axis=1)
         .projective(np.eye(4, dtype=np.float32)
                     + np.float32(0.01) * np.ones((4, 4), np.float32))
         .cull((-1, -1, -1), (1, 1, 1)).scale(2.0).translate(1.0, 1.0, 1.0))
    kind = tc.plan_kind_of(c.structure)
    assert kind == "projective"
    one = tc.fold_structure(c.structure, c.params)
    carry = tc.fold_carry_identity(kind, 3)
    for i in range(len(c.kinds)):
        carry = tc.fold_carry_extend(kind, 3, carry, c.kinds[i:i + 1],
                                     c.params[i:i + 1])
    assert _fold_eq(one, tc.fold_carry_finish(kind, carry))


def test_fold_carry_kind_restrictions():
    c = tc.TransformChain.identity(2).rotate(0.5)
    with pytest.raises(ValueError):
        tc.fold_carry_extend("diag", 2, tc.fold_carry_identity("diag", 2),
                             c.kinds, c.params)
    p = tc.TransformChain.identity(2).cull((-1, -1), (1, 1))
    with pytest.raises(ValueError):
        tc.fold_carry_extend("matrix", 2,
                             tc.fold_carry_identity("matrix", 2),
                             p.kinds, p.params)
    with pytest.raises(ValueError):
        tc.fold_carry_identity("banded", 2)


def test_fold_carry_after_cull_restriction_survives_resume():
    # a cull in the carried prefix must still reject a following rotation
    pre = tc.TransformChain.identity(2).cull((-1, -1), (1, 1))
    carry = tc.fold_carry_extend(
        "projective", 2, tc.fold_carry_identity("projective", 2),
        pre.kinds, pre.params)
    rot = tc.TransformChain.identity(2).rotate(0.3)
    with pytest.raises(ValueError):
        tc.fold_carry_extend("projective", 2, carry, rot.kinds, rot.params)


# ---------------------------------------------------------------------------
# graph structure + dirty bits
# ---------------------------------------------------------------------------

def test_graph_structure_errors():
    g = scene.SceneGraph(2, cache=scene.FoldCache())
    g.add("a")
    with pytest.raises(ValueError):
        g.add("a")                                  # duplicate
    with pytest.raises(KeyError):
        g.add("b", parent="nope")                   # unknown parent
    with pytest.raises(KeyError):
        g.world_fold("nope")                        # unknown node
    with pytest.raises(ValueError):
        g.add("c", tc.TransformChain.identity(3))   # dim mismatch
    with pytest.raises(ValueError):
        g.add("")                                   # empty name
    with pytest.raises(TypeError):
        g.add("d", local="not a chain")


def test_subtree_and_dirty_propagation():
    g = scene.SceneGraph(2, cache=scene.FoldCache())
    g.add("r", tc.TransformChain.identity(2).translate(1.0))
    g.add("a", tc.TransformChain.identity(2).scale(2.0), parent="r")
    g.add("b", tc.TransformChain.identity(2).scale(3.0), parent="r")
    g.add("a1", tc.TransformChain.identity(2).translate(5.0), parent="a")
    assert g.subtree("a") == ["a", "a1"]
    assert sorted(g.leaves()) == ["a1", "b"]
    for n in g.names():
        g.world_fold(n)
        assert not g.dirty(n)
    assert g.set_local("a", tc.TransformChain.identity(2).scale(4.0)) == 2
    assert g.dirty("a") and g.dirty("a1")
    assert not g.dirty("r") and not g.dirty("b")
    # editing while already dirty does not recount
    assert g.set_local("a", tc.TransformChain.identity(2).scale(5.0)) == 0


def test_identity_world_chain():
    g = scene.SceneGraph(2, cache=scene.FoldCache())
    g.add("r")
    g.add("c", parent="r")
    assert len(g.world_chain("c")) == 0
    assert g.world_kind("c") == "diag"
    s, t = g.world_fold("c")
    assert _bytes_eq(s, np.ones(2, np.float32))
    assert _bytes_eq(t, np.zeros(2, np.float32))


# ---------------------------------------------------------------------------
# (a) edits + queries interleaved == scratch folds, bitwise
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dim,kinds", [(2, "TSA"), (3, "TSAR"), (2, "TSAR")])
def test_world_folds_bitwise_vs_scratch_seeded(dim, kinds):
    rng = np.random.default_rng(2026)
    for trial in range(8):
        g, names = _rand_tree(rng, dim, 12, kinds=kinds)
        for step in range(12):
            if rng.uniform() < 0.4:
                g.set_local(names[int(rng.integers(len(names)))],
                            _rand_local(rng, dim, kinds=kinds))
            q = names[int(rng.integers(len(names)))]
            assert _fold_eq(g.world_fold(q), _scratch_fold(g, q))
        for n in names:                     # full sweep at the end
            assert _fold_eq(g.world_fold(n), _scratch_fold(g, n))


def test_world_folds_bitwise_projective_scene():
    g = scene.SceneGraph(3, cache=scene.FoldCache())
    g.add("model", tc.TransformChain.identity(3).rotate(0.3, axis=2))
    g.add("camera",
          tc.TransformChain.identity(3).translate(0.0, 0.0, -5.0),
          parent="model")
    proj = np.eye(4, dtype=np.float32)
    proj[2, 3] = np.float32(-1.0)
    proj[3, 3] = np.float32(0.0)
    g.add("clip", tc.TransformChain.identity(3).projective(proj),
          parent="camera")
    g.add("vp", tc.TransformChain.identity(3)
          .cull((-1, -1, -1), (1, 1, 1)).scale(100.0, 100.0, 1.0),
          parent="clip")
    for n in g.names():
        assert _fold_eq(g.world_fold(n), _scratch_fold(g, n))
    assert g.world_kind("vp") == "projective"
    g.set_local("camera",
                tc.TransformChain.identity(3).translate(0.0, 1.0, -7.0))
    for n in g.names():
        assert _fold_eq(g.world_fold(n), _scratch_fold(g, n))


if HAVE_HYPOTHESIS:
    _ops = st.lists(st.tuples(st.sampled_from(["edit", "query"]),
                              st.integers(0, 9),
                              st.integers(0, 2 ** 16)),
                    min_size=1, max_size=25)

    @settings(max_examples=30, deadline=None)
    @given(tree_seed=st.integers(0, 2 ** 16), ops=_ops)
    def test_world_folds_bitwise_vs_scratch_property(tree_seed, ops):
        rng = np.random.default_rng(tree_seed)
        g, names = _rand_tree(rng, 3, 10)
        for op, idx, seed in ops:
            name = names[idx % len(names)]
            if op == "edit":
                g.set_local(name, _rand_local(
                    np.random.default_rng(seed), 3))
            else:
                assert _fold_eq(g.world_fold(name), _scratch_fold(g, name))
        for n in names:
            assert _fold_eq(g.world_fold(n), _scratch_fold(g, n))


# ---------------------------------------------------------------------------
# (b) fold counts == dirty-subtree size per frame
# ---------------------------------------------------------------------------

def _resolve_all_leaves(g):
    for n in g.leaves():
        g.world_fold(n)


def test_fold_count_equals_dirty_subtree():
    # locals get content-unique parameters on purpose: two siblings with
    # EQUAL content share one digest and fold once (that CSE is tested
    # separately); here every node must be its own fold unit so the
    # folds == nodes / folds == dirtied arithmetic is exact
    g = scene.SceneGraph(3, cache=scene.FoldCache())
    g.add("root", tc.TransformChain.identity(3).translate(0.5, 0.0, 0.0))
    g.add("cam", tc.TransformChain.identity(3).rotate(0.2, axis=0),
          parent="root")
    for b in range(4):
        g.add(f"b{b}", tc.TransformChain.identity(3)
              .scale(np.float32(1.0 + b)), parent="cam")
        for leaf in range(3):
            g.add(f"b{b}/l{leaf}", tc.TransformChain.identity(3)
                  .translate(np.float32(leaf), np.float32(b), 0.0),
                  parent=f"b{b}")
    scene.reset_stats()
    _resolve_all_leaves(g)
    # cold frame: every node folds exactly once (in the leaves' kind)
    assert scene.stats["folds"] == len(g)
    assert scene.stats["cache_misses"] == scene.stats["folds"]
    assert scene.stats["refolds"] == 0
    # animated frames: folds == dirtied, exactly, frame after frame
    for frame in range(5):
        before = dict(scene.stats)
        edit = f"b{frame % 4}"
        dirtied = g.set_local(
            edit, tc.TransformChain.identity(3)
            .scale(np.float32(1.0 + 0.1 * frame))
            .translate(np.float32(frame), 0.0, 0.0))
        assert dirtied == len(g.subtree(edit)) == 4
        _resolve_all_leaves(g)
        assert scene.stats["folds"] - before["folds"] == dirtied
        assert scene.stats["refolds"] - before["refolds"] == dirtied
        assert scene.stats["dirtied"] - before["dirtied"] == dirtied
    # a clean re-query costs nothing
    before = dict(scene.stats)
    _resolve_all_leaves(g)
    assert scene.stats["folds"] == before["folds"]


def test_revert_to_cached_content_costs_zero_folds():
    g = scene.SceneGraph(2, cache=scene.FoldCache())
    old = tc.TransformChain.identity(2).scale(2.0)
    g.add("r", tc.TransformChain.identity(2).translate(1.0, 0.0))
    g.add("c", old, parent="r")
    g.world_fold("c")
    g.set_local("c", tc.TransformChain.identity(2).scale(3.0))
    g.world_fold("c")
    scene.reset_stats()
    # revert: same CONTENT as the first local -> digest matches -> hit
    assert g.set_local("c", tc.TransformChain.identity(2).scale(2.0)) == 1
    f = g.world_fold("c")
    assert scene.stats["folds"] == 0
    assert scene.stats["cse_hits"] == 1
    assert _fold_eq(f, _scratch_fold(g, "c"))


# ---------------------------------------------------------------------------
# (c) content keys: cross-process / cross-graph stability, shared-cache CSE
# ---------------------------------------------------------------------------

_DIGEST_SNIPPET = """
import numpy as np
from repro import scene
from repro.core import transform_chain as tc
g = scene.SceneGraph(3, cache=scene.FoldCache())
g.add("w", tc.TransformChain.identity(3).translate(1.0, 2.0, 3.0))
g.add("c", tc.TransformChain.identity(3).rotate(0.25, axis=1), parent="w")
f = g.world_fold("c")
print(g.world_digest("c"))
print(np.asarray(f[0]).tobytes().hex())
print(np.asarray(f[1]).tobytes().hex())
"""


def test_content_keys_and_folds_stable_across_processes():
    out = subprocess.run(                 # CPU only: never take a chip
        [sys.executable, "-c", _DIGEST_SNIPPET],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, check=True).stdout.split()
    g = scene.SceneGraph(3, cache=scene.FoldCache())
    g.add("w", tc.TransformChain.identity(3).translate(1.0, 2.0, 3.0))
    g.add("c", tc.TransformChain.identity(3).rotate(0.25, axis=1),
          parent="w")
    f = g.world_fold("c")
    assert out[0] == g.world_digest("c")
    assert out[1] == np.asarray(f[0]).tobytes().hex()
    assert out[2] == np.asarray(f[1]).tobytes().hex()


def test_digest_is_content_not_construction_order():
    a = scene.SceneGraph(2, cache=scene.FoldCache())
    a.add("r", tc.TransformChain.identity(2).scale(2.0))
    a.add("x", tc.TransformChain.identity(2).translate(1.0, 0.0),
          parent="r")
    a.add("y", tc.TransformChain.identity(2).translate(0.0, 1.0),
          parent="r")
    b = scene.SceneGraph(2, cache=scene.FoldCache())
    b.add("r2", tc.TransformChain.identity(2).scale(2.0))
    b.add("y2", tc.TransformChain.identity(2).translate(0.0, 1.0),
          parent="r2")
    b.add("x2", tc.TransformChain.identity(2).translate(1.0, 0.0),
          parent="r2")
    assert a.world_digest("x") == b.world_digest("x2")
    assert a.world_digest("y") == b.world_digest("y2")
    assert a.world_digest("x") != a.world_digest("y")
    # shape framing: scalar-broadcast 1.0 and explicit (1.0, 1.0) params
    # are different content even though they fold to equal values
    c1 = tc.TransformChain.identity(2).translate(1.0)
    c2 = tc.TransformChain.identity(2).translate(1.0, 1.0)
    assert scene.chain_digest(2, c1.kinds, c1.params) \
        != scene.chain_digest(2, c2.kinds, c2.params)


def test_cse_across_scenes_sharing_a_cache():
    shared = scene.FoldCache()
    prefix = tc.TransformChain.identity(3).rotate(0.4, axis=1) \
        .translate(0.0, 0.0, -5.0)
    leafc = tc.TransformChain.identity(3).scale(2.0)
    a = scene.SceneGraph(3, cache=shared)
    a.add("cam", prefix)
    a.add("obj", leafc, parent="cam")
    b = scene.SceneGraph(3, cache=shared)
    b.add("cam", prefix)
    b.add("obj", leafc, parent="cam")
    scene.reset_stats()
    fa = a.world_fold("obj")
    folds_a = scene.stats["folds"]
    assert folds_a == 2
    fb = b.world_fold("obj")
    # scene b resolves entirely from scene a's entries: zero new folds
    assert scene.stats["folds"] == folds_a
    assert scene.stats["cse_hits"] >= 1
    assert _fold_eq(fa, fb)


# ---------------------------------------------------------------------------
# serving integration: submit_scene / submit_scene_async equality
# ---------------------------------------------------------------------------

def _diag_scene(rng):
    g = scene.SceneGraph(2, cache=scene.FoldCache())
    g.add("view", tc.TransformChain.identity(2).scale(0.5)
          .translate(1.0, 2.0))
    leaves = [g.add(f"n{i}", tc.TransformChain.identity(2)
                    .affine(np.float32(1.0 + i), (np.float32(i), 0.0)),
                    parent="view")
              for i in range(5)]
    return g, leaves


def _matrix_scene(rng):
    g = scene.SceneGraph(3, cache=scene.FoldCache())
    g.add("world", tc.TransformChain.identity(3).translate(0.0, 0.0, 1.0))
    g.add("camera", tc.TransformChain.identity(3).rotate(0.4, axis=1)
          .translate(0.0, 0.0, -5.0), parent="world")
    leaves = []
    for b in range(4):
        g.add(f"b{b}", tc.TransformChain.identity(3)
              .scale(np.float32(1.0 + b)), parent="camera")
        leaves.append(g.add(f"b{b}/leaf", tc.TransformChain.identity(3)
                            .affine(0.5, (np.float32(b), 0.0, 0.0)),
                            parent=f"b{b}"))
    return g, leaves


def test_submit_scene_float32_bitwise_on_diag_plans():
    rng = np.random.default_rng(11)
    g, leaves = _diag_scene(rng)
    serving.reset_stats()
    srv = serving.GeometryServer(backend="ref")
    pts = {n: rng.standard_normal((8, 2)).astype(np.float32)
           for n in leaves}
    tickets = {n: srv.submit_scene(g, n, pts[n]) for n in leaves}
    res = srv.flush()
    for n in leaves:
        oracle = g.world_chain(n).apply(pts[n], backend="ref")
        assert _bytes_eq(res[tickets[n]], oracle)


def test_submit_scene_equals_submit_chain_bitwise():
    # scene-cached fold vs per-request fold, same server, same buckets:
    # identical requests land in one packed batch -> results are bitwise
    # equal on EVERY plan kind (the fold itself is bitwise by the carry
    # construction; identical batch rows cannot diverge)
    rng = np.random.default_rng(12)
    g, leaves = _matrix_scene(rng)
    srv = serving.GeometryServer(backend="ref")
    pts = {n: rng.standard_normal((16, 3)).astype(np.float32)
           for n in leaves}
    via_scene = {n: srv.submit_scene(g, n, pts[n]) for n in leaves}
    via_chain = {n: srv.submit(g.world_chain(n), pts[n]) for n in leaves}
    res = srv.flush()
    for n in leaves:
        assert _bytes_eq(res[via_scene[n]], res[via_chain[n]])
        # and within the engine's documented last-ULP envelope of apply
        np.testing.assert_allclose(
            np.asarray(res[via_scene[n]]),
            np.asarray(g.world_chain(n).apply(pts[n], backend="ref")),
            rtol=2e-6, atol=2e-6)


def test_submit_scene_q8_7_bitwise_every_plan_kind():
    rng = np.random.default_rng(13)
    for build in (_diag_scene, _matrix_scene):
        g, leaves = build(rng)
        dim = g.dim
        srv = serving.GeometryServer(backend="ref")
        pts = {n: rng.uniform(-2, 2, (12, dim)).astype(np.float32)
               for n in leaves}
        tickets = {n: srv.submit_scene(g, n, pts[n], qformat="q8.7")
                   for n in leaves}
        res = srv.flush()
        for n in leaves:
            oracle = g.world_chain(n).apply(pts[n], backend="ref",
                                            dtype="q8.7")
            assert _bytes_eq(res[tickets[n]], oracle)


def test_submit_scene_projective_equals_chain():
    g = scene.SceneGraph(3, cache=scene.FoldCache())
    g.add("cam", tc.TransformChain.identity(3).translate(0.0, 0.0, -4.0))
    proj = np.eye(4, dtype=np.float32)
    proj[2, 3] = np.float32(-1.0)
    proj[3, 3] = np.float32(0.0)
    g.add("clip", tc.TransformChain.identity(3).projective(proj),
          parent="cam")
    g.add("vp", tc.TransformChain.identity(3)
          .cull((-1, -1, -1), (1, 1, 1)).scale(50.0, 50.0, 1.0),
          parent="clip")
    rng = np.random.default_rng(14)
    pts = rng.uniform(-1, 1, (32, 3)).astype(np.float32)
    srv = serving.GeometryServer(backend="ref")
    t_scene = srv.submit_scene(g, "vp", pts)
    t_chain = srv.submit(g.world_chain("vp"), pts)
    res = srv.flush()
    assert _bytes_eq(res[t_scene], res[t_chain])
    assert _bytes_eq(res[t_scene].mask, res[t_chain].mask)


def test_submit_scene_identity_node_passthrough():
    g = scene.SceneGraph(2, cache=scene.FoldCache())
    g.add("r")
    pts = np.arange(8, dtype=np.float32).reshape(4, 2)
    srv = serving.GeometryServer(backend="ref")
    t = srv.submit_scene(g, "r", pts)
    res = srv.flush()
    assert _bytes_eq(res[t], pts)


def test_submit_scene_async_bitwise():
    rng = np.random.default_rng(15)
    g, leaves = _matrix_scene(rng)
    srv = AsyncGeometryServer(backend="ref", clock=VirtualClock())
    pts = {n: rng.uniform(-2, 2, (8, 3)).astype(np.float32)
           for n in leaves}
    tickets = {n: srv.submit_scene_async(g, n, pts[n], qformat="q8.7")
               for n in leaves}
    srv.drain()
    for n in leaves:
        oracle = g.world_chain(n).apply(pts[n], backend="ref",
                                        dtype="q8.7")
        assert _bytes_eq(tickets[n].result(), oracle)


def test_submit_scene_cse_counters_move_not_refolds():
    rng = np.random.default_rng(16)
    g, leaves = _matrix_scene(rng)
    for n in leaves:
        g.world_fold(n)                 # warm the cache
    scene.reset_stats()
    srv = serving.GeometryServer(backend="ref")
    for n in leaves:
        srv.submit_scene(g, n, rng.standard_normal((4, 3))
                         .astype(np.float32))
    srv.flush()
    assert scene.stats["folds"] == 0
    assert scene.stats["cse_hits"] == len(leaves)


# ---------------------------------------------------------------------------
# obs integration: instants mirror the counters
# ---------------------------------------------------------------------------

def test_scene_trace_instants_match_counters():
    clock = VirtualClock()
    trc = obst.Tracer(clock=clock)
    obst.install(trc)
    try:
        g = scene.SceneGraph(2, cache=scene.FoldCache())
        scene.reset_stats()
        g.add("r", tc.TransformChain.identity(2).scale(2.0))
        g.add("c", tc.TransformChain.identity(2).translate(1.0, 0.0),
              parent="r")
        g.world_fold("c")               # 2 cold folds
        g.world_fold("c")               # 1 cse hit
        g.set_local("c", tc.TransformChain.identity(2).translate(2.0, 0.0))
        g.world_fold("c")               # 1 refold (+1 cse hit at "r")
        assert trc.count("scene.fold") == scene.stats["folds"] \
            - scene.stats["refolds"] == 2
        assert trc.count("scene.refold") == scene.stats["refolds"] == 1
        assert trc.count("scene.cse_hit") == scene.stats["cse_hits"] == 2
    finally:
        obst.install(None)
