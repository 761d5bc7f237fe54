"""Pallas TPU flash attention (forward) -- beyond-paper composite transform.

The paper's section-5.3 "composite algorithms" chain its three primitives
(matmul, vector-scalar, vector-vector).  Attention is exactly such a chain --
S = QK^T (matmul), online softmax (vector-scalar with a data-derived scalar,
like RMSNorm), O = PV (matmul) -- and the MorphoSys frame-buffer discipline
maps directly: KV blocks stream through VMEM (bank 0/1 double-buffering by
the Pallas pipeline) while the accumulator lives in the cell output
registers (fp32 VMEM scratch).

Grid: (batch*heads, q_blocks, kv_blocks), kv innermost with "arbitrary"
semantics so the m/l/acc scratch carries across kv steps.  GQA is expressed
in the K/V index maps (q head h reads kv head h // group) -- no KV
materialisation.  Causal and sliding-window masks skip dead kv blocks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.util import SUBLANES, pad_axis, pick_block

_NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  bq: int, bk: int, nkv: int, scale: float, causal: bool,
                  window: int | None, q_offset: int, s_actual: int,
                  t_actual: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # block-level liveness: any (q, k) pair in this tile unmasked?
    q_lo = q_offset + qi * bq
    q_hi = q_lo + bq - 1
    k_lo = ki * bk
    k_hi = k_lo + bk - 1
    live = k_lo < t_actual
    if causal:
        live &= k_lo <= q_hi
    if window is not None:
        live &= k_hi > q_lo - window

    @pl.when(live)
    def _step():
        q = q_ref[0].astype(jnp.float32) * scale        # (bq, d)
        k = k_ref[0].astype(jnp.float32)                # (bk, d)
        v = v_ref[0].astype(jnp.float32)                # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (bq, bk)

        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = kpos < t_actual
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[:, :1]                           # (bq, 1)
        l_prev = l_ref[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)      # (bq, 1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                          # (bq, bk)
        corr = jnp.exp(m_prev - m_new)                  # (bq, 1)
        l_new = corr * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == nkv - 1)
    def _flush():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "scale", "causal", "window", "q_offset", "bq", "bk", "interpret"))
def flash_attention_3d(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                       scale: float, causal: bool = True,
                       window: int | None = None, q_offset: int = 0,
                       bq: int = 128, bk: int = 128,
                       interpret: bool = False) -> jnp.ndarray:
    """q (BHq, S, D), k/v (BHkv, T, D) -> (BHq, S, D); GQA via index maps."""
    bhq, s, d = q.shape
    bhkv, t, _ = k.shape
    assert bhq % bhkv == 0, (bhq, bhkv)
    group = bhq // bhkv
    bq = pick_block(s, bq, SUBLANES)
    bk = pick_block(t, bk, SUBLANES)
    qp = pad_axis(q, 1, bq)
    kp = pad_axis(k, 1, bk)
    vp = pad_axis(v, 1, bk)
    nq, nkv = qp.shape[1] // bq, kp.shape[1] // bk
    kernel = functools.partial(
        _flash_kernel, bq=bq, bk=bk, nkv=nkv, scale=scale, causal=causal,
        window=window, q_offset=q_offset, s_actual=s, t_actual=t)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(qp.shape, q.dtype),
        grid=(bhq, nq, nkv),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda h, i, kk: (h, i, 0)),
            pl.BlockSpec((1, bk, d), lambda h, i, kk, g=group: (h // g, kk, 0)),
            pl.BlockSpec((1, bk, d), lambda h, i, kk, g=group: (h // g, kk, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda h, i, kk: (h, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),   # m (running max)
            pltpu.VMEM((bq, 128), jnp.float32),   # l (running denominator)
            pltpu.VMEM((bq, d), jnp.float32),     # acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qp, kp, vp)
    return out[:, :s, :]
