"""Compile rehearsal: the served Pallas kernels compile for a TPU v5e.

Interpret mode runs a kernel body on the CPU; it cannot show whether
Mosaic, the chip's kernel compiler, accepts the body.  These cases
compile each batch kernel the server launches, and each 1-D twin that
``TransformChain.apply`` launches, for one chip of a described
``v5e:2x2`` topology at serving widths (64 requests of 4,096 points;
the 1-D twins over the same 4,096 points).  Nothing runs.  The topology
is described inside a fixture, so only the worker that runs this file
loads the TPU compiler, and a host that cannot describe it skips.
"""
import functools
import importlib
import math
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

B, L = 64, 4096
N_FRAC = 7                                       # q8.7
F32, I16 = jnp.float32, jnp.int16

_affine = importlib.import_module("repro.kernels.affine.affine")
_matmul = importlib.import_module("repro.kernels.matmul.matmul")
_project = importlib.import_module("repro.kernels.projective.projective")
_fixed = importlib.import_module("repro.kernels.fixedpoint.fixedpoint")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # the TPU compiler otherwise writes its logs under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip: keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


#: kernel -> (module, plan kind, dtype, static kwargs); a ``_1d`` twin
#: also takes ``d``
KERNELS = {
    "chain_diag_batch_2d": (_affine, "diag", F32, {}),
    "chain_matrix_batch_2d": (_matmul, "matrix", F32, {}),
    "chain_project_batch_2d": (_project, "projective", F32, {}),
    "chain_diag_batch_2d_q": (_fixed, "diag", I16, {"n_frac": N_FRAC}),
    "chain_matrix_batch_2d_q": (_fixed, "matrix", I16, {"n_frac": N_FRAC}),
    "chain_diag_1d": (_affine, "diag", F32, {}),
    "chain_matrix_1d": (_matmul, "matrix", F32, {}),
    "chain_project_1d": (_project, "projective", F32, {}),
    "chain_diag_1d_q": (_fixed, "diag", I16, {"n_frac": N_FRAC}),
    "chain_matrix_1d_q": (_fixed, "matrix", I16, {"n_frac": N_FRAC}),
}


def _shapes(name, kind, d):
    """Operand shapes: one packed bucket of B requests of L points, or
    one 1-D launch over L points, and the folded parameters."""
    params = {"diag": [(d,), (d,)], "matrix": [(d, d), (d,)],
              "projective": [(d + 1, d + 1), (d,), (d,)]}[kind]
    if "_1d" in name:
        return [(L * d,)] + params
    return [(B, L, d)] + [(B,) + p for p in params]


@pytest.mark.parametrize("d", [2, 3])
@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, d, one_chip):
    module, kind, dt, kw = KERNELS[name]
    if "_1d" in name:
        kw = dict(kw, d=d)
    fn = functools.partial(getattr(module, name), **kw)
    specs = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
             for s in _shapes(name, kind, d)]
    compiled = jax.jit(fn).lower(*specs).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("vertices,d", [(35_947, 3), (437_645, 3),
                                        (4_096, 2)])
def test_instanced_kernel_compiles_for_v5e(vertices, d, one_chip):
    """The instanced projective kernel over a resident buffer of the
    Bunny's and the Dragon's vertex counts, 8 instances: the block is
    one tile of rows however long the mesh, so both compile."""
    from repro.kernels import util
    rows = util.resident_rows(vertices * d, d)
    shapes = [(rows, util.lane_group(d)), (8, d + 1, d + 1), (8, d), (8, d)]
    specs = [jax.ShapeDtypeStruct(s, F32, sharding=one_chip) for s in shapes]
    compiled = jax.jit(_project.chain_project_instanced_2d).lower(
        *specs).compile()
    assert "tpu_custom_call" in compiled.as_text()


#: each batch plan the server builds: (dim, plan kind, q-format)
PLANS = [(d, kind, q) for d in (2, 3)
         for kind in ("diag", "matrix", "projective")
         for q in (None, "q8.7") if not (q and kind == "projective")]


@pytest.mark.parametrize("d,kind,qname", PLANS)
def test_served_plan_compiles_for_v5e(d, kind, qname, one_chip):
    """A served plan as a launch calls it: one fold row and the packed
    points, the row split into the kernel's operands on the device."""
    from repro.serving import engine
    plan = engine._compile_batch_q((d, kind), "pallas", qname) \
        if qname else engine._compile_batch((d, kind), "pallas")
    dt = I16 if qname else F32
    words = sum(math.prod(s) for s in engine._fold_shapes(kind, d))
    rows = jax.ShapeDtypeStruct((B, words), dt, sharding=one_chip)
    pts = jax.ShapeDtypeStruct((B, L, d), dt, sharding=one_chip)
    compiled = plan.fn.lower(rows, pts).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_served_instanced_plan_compiles_for_v5e(one_chip):
    """The resident Bunny's plan: 8 fold rows over its buffer."""
    from repro.kernels import util
    from repro.serving import engine
    plan = engine._compile_batch((3, "projective"), "pallas",
                                 instanced=True)
    buffer = (util.resident_rows(35_947 * 3, 3), util.lane_group(3))
    rows = jax.ShapeDtypeStruct((8, 22), F32, sharding=one_chip)
    x = jax.ShapeDtypeStruct(buffer, F32, sharding=one_chip)
    compiled = plan.fn.lower(rows, x).compile()
    assert "tpu_custom_call" in compiled.as_text()
