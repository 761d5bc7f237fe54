"""Kernel microbenchmarks: the paper's metrics applied to the TPU mapping.

The paper reports elements/cycle for its vector routines on the M1 at
100 MHz.  We benchmark the same primitive classes through the public kernel
API (ref backend -- the XLA path that the dry-run lowers; the Pallas bodies
are validated separately in interpret mode, which is a correctness
interpreter, not a performance path) and report us/call plus the derived
elements/us.  On-CPU numbers calibrate nothing about the TPU -- the TPU
projection column divides the memory-bound byte volume by v5e HBM bandwidth
(these ops are all memory-bound; see EXPERIMENTS.md section Perf).

The ``chain_*`` rows benchmark the paper's headline claim -- composite
transforms as ONE pass instead of one pass per primitive -- through the
fused transform-chain compiler; see ``benchmarks/PERF.md`` for what each
row means and the byte accounting behind the speedup.

``run(smoke=True)`` shrinks every shape and the iteration count so the
whole sweep finishes in seconds (the CI liveness pass); row names gain a
``_smoke`` suffix so small-shape numbers are never mistaken for the real
sweep.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import kernels
from repro.autotune import cache as tuning
from repro.core import transform_chain as tc
from repro.core import transform_engine as te
from repro.kernels import dispatch
from repro.roofline import peaks


def _cfg_tag(kernel: str, dtype: str, n: int) -> str:
    """Which launch config this row used: the same tuning-cache lookup the
    kernel itself performs (``default(...)`` when autotuning is off,
    ``cached(...)``/``tuned(...)`` winners otherwise)."""
    return tuning.config_for(kernel, dispatch.resolve(None), dtype,
                             n).describe()


def _time(fn, *args, iters: int = 20) -> float:
    out = fn(*args)               # one warmup call: compile + stage buffers
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6   # us


def _chain_rows(rng, *, n_points: int, iters: int, tag: str) -> list[str]:
    """Fused one-pass chains vs sequential per-primitive dispatch (CPU ref)."""
    rows = []
    pts = jnp.asarray(rng.standard_normal((n_points, 2)), jnp.float32)
    sv = jnp.asarray([1.3, 0.8], jnp.float32)
    t1 = jnp.asarray([3.0, 2.0], jnp.float32)
    t2 = jnp.asarray([-1.0, 5.0], jnp.float32)
    theta = 0.3

    # length-4 general chain: translate . scale . rotate . translate
    def sequential(p):
        return te.translate(te.rotate(te.scale(te.translate(p, t2), sv),
                                      theta), t1)

    us_seq = _time(sequential, pts, iters=iters)
    rows.append(f"chain_sequential_len4{tag},{us_seq:.1f},"
                f"elems_per_us={pts.size / us_seq:.0f};hbm_passes=4")

    chain = (tc.TransformChain.identity(2)
             .translate(-1.0, 5.0).scale(1.3, 0.8).rotate(theta)
             .translate(3.0, 2.0))
    tc.clear_plan_cache()
    t0 = time.perf_counter()
    jax.block_until_ready(chain.apply(pts))
    cold_us = (time.perf_counter() - t0) * 1e6        # fold + trace + run
    us_fused = _time(chain.apply, pts, iters=iters)   # plan-cache hits
    rows.append(f"chain_fused_len4{tag},{us_fused:.1f},"
                f"elems_per_us={pts.size / us_fused:.0f};hbm_passes=1;"
                f"speedup_vs_sequential={us_seq / us_fused:.2f}x;"
                f"config={_cfg_tag('chain_apply', 'float32', n_points)}")
    rows.append(f"chain_plan_cache{tag},{us_fused:.1f},"
                f"cold_us={cold_us:.1f};"
                f"cachehit_speedup={cold_us / us_fused:.1f}x")

    # length-3 diagonal chain: folds to one affine, never touches the MXU
    def seq_diag(p):
        return te.translate(te.scale(te.translate(p, t2), sv), t1)

    us_seq_d = _time(seq_diag, pts, iters=iters)
    diag = (tc.TransformChain.identity(2)
            .translate(-1.0, 5.0).scale(1.3, 0.8).translate(3.0, 2.0))
    jax.block_until_ready(diag.apply(pts))
    us_diag = _time(diag.apply, pts, iters=iters)
    rows.append(f"chain_fused_diag_len3{tag},{us_diag:.1f},"
                f"elems_per_us={pts.size / us_diag:.0f};plan=diag_no_mxu;"
                f"sequential_us={us_seq_d:.1f};"
                f"speedup_vs_sequential={us_seq_d / us_diag:.2f}x;"
                f"config={_cfg_tag('chain_diag', 'float32', n_points)}")
    return rows


def run(smoke: bool = False) -> list[str]:
    rows = []
    rng = np.random.default_rng(0)
    tag = "_smoke" if smoke else ""
    iters = 3 if smoke else 20

    # vector-vector (translation) and vector-scalar (scaling)
    m, n = (256, 256) if smoke else (1024, 1024)
    x = jnp.asarray(rng.standard_normal((m, n)), jnp.float32)
    z = jnp.asarray(rng.standard_normal((m, n)), jnp.float32)
    s = jnp.asarray(rng.standard_normal((n,)), jnp.float32)
    t = jnp.asarray(rng.standard_normal((n,)), jnp.float32)

    vecadd = jax.jit(lambda a, b: kernels.vecadd(a, b))
    us = _time(vecadd, x, z, iters=iters)
    tpu_us = 3 * x.size * 4 / peaks("TPU v5 lite").hbm_bw * 1e6
    rows.append(f"kernel_vecadd_translation{tag},{us:.1f},"
                f"elems_per_us={x.size/us:.0f};tpu_projection_us={tpu_us:.1f}")

    scale = jax.jit(lambda a, b: kernels.scale(a, b))
    us = _time(scale, x, s, iters=iters)
    rows.append(f"kernel_scale_scaling{tag},{us:.1f},"
                f"elems_per_us={x.size/us:.0f};tpu_projection_us={tpu_us:.1f}")

    affine = jax.jit(lambda a, b, c: kernels.affine(a, b, c))
    us = _time(affine, x, s, t, iters=iters)
    rows.append(f"kernel_affine_fused{tag},{us:.1f},"
                f"elems_per_us={x.size/us:.0f};fusion_saves=1x_hbm_pass")

    # composite transform chains (the paper's General Composite Algorithm)
    rows += _chain_rows(rng, n_points=1 << 12 if smoke else 1 << 19,
                        iters=iters, tag=tag)

    # rotation (rope) on a head block
    rope_shape = (2, 256, 128) if smoke else (8, 4096, 128)
    xr = jnp.asarray(rng.standard_normal(rope_shape), jnp.bfloat16)
    cos, sin = kernels.rope_tables(jnp.arange(rope_shape[1]), 128)
    rope = jax.jit(lambda a: kernels.rope(a, cos, sin))
    us = _time(rope, xr, iters=iters)
    rows.append(f"kernel_rope_rotation{tag},{us:.1f},elems_per_us={xr.size/us:.0f}")

    # matmul (rotation/composite)
    mm_n = 256 if smoke else 1024
    a = jnp.asarray(rng.standard_normal((mm_n, mm_n)), jnp.bfloat16)
    b = jnp.asarray(rng.standard_normal((mm_n, mm_n)), jnp.bfloat16)
    mm = jax.jit(lambda p, q: kernels.matmul(p, q))
    us = _time(mm, a, b, iters=iters)
    fl = 2 * mm_n ** 3
    rows.append(f"kernel_matmul{tag},{us:.1f},"
                f"gflops_cpu={fl/us/1e3:.1f};tpu_projection_us={fl/197e12*1e6:.1f};"
                f"config={_cfg_tag('matmul', 'bfloat16', mm_n * mm_n)}")

    # rmsnorm fused (derived-scalar scaling)
    g = jnp.ones((n,), jnp.float32)
    rn = jax.jit(lambda p: kernels.rmsnorm(p, g))
    us = _time(rn, x, iters=iters)
    rows.append(f"kernel_rmsnorm{tag},{us:.1f},elems_per_us={x.size/us:.0f};"
                f"config={_cfg_tag('rmsnorm', 'float32', x.size)}")

    # blockwise attention (composite), causal
    seq = 256 if smoke else 4096
    q = jnp.asarray(rng.standard_normal((1, 8, seq, 64)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((1, 2, seq, 64)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((1, 2, seq, 64)), jnp.bfloat16)
    att = jax.jit(lambda a_, b_, c_: kernels.attention(a_, b_, c_))
    us = _time(att, q, k, v, iters=3)
    fl = 4 * 8 * seq * seq * 64 / 2
    rows.append(f"kernel_attention{tag},{us:.1f},gflops_cpu={fl/us/1e3:.1f}")
    return rows
