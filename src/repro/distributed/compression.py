"""Gradient compression for cross-pod (DCN) synchronisation.

Two mechanisms, both with error feedback so compression noise does not
accumulate:

  * implicit bf16: backward reduces gradients in the params' bf16 dtype
    (half the collective bytes of fp32) while the accumulation across
    microbatches and the optimizer run in fp32 -- on by default;
  * explicit int8: per-tensor-scaled int8 quantisation applied around the
    pod-axis psum (4x fewer DCN bytes), used via shard_map when
    ``--grad-compression int8`` is set on the launcher.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def quantize_int8(g: jnp.ndarray, err: jnp.ndarray):
    """(g + err) -> (int8 q, fp32 scale, new_err)."""
    target = g.astype(jnp.float32) + err
    scale = jnp.maximum(jnp.max(jnp.abs(target)), 1e-12) / 127.0
    q = jnp.clip(jnp.round(target / scale), -127, 127).astype(jnp.int8)
    deq = q.astype(jnp.float32) * scale
    return q, scale, target - deq


def dequantize_int8(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    return q.astype(jnp.float32) * scale


def init_error_state(params):
    return jax.tree.map(
        lambda a: jnp.zeros(a.shape, jnp.float32), params)


def pod_sync_int8(grads, err_state, mesh, pspecs):
    """All-reduce grads over the 'pod' axis with int8 + error feedback.

    Call with grads already reduced over the in-pod 'data' axis (which SPMD
    does during backward); only the slow DCN hop is compressed."""
    if "pod" not in mesh.axis_names:
        return grads, err_state

    def sync_leaf(g, err, spec):
        def inner(g_blk, err_blk):
            q, scale, new_err = quantize_int8(g_blk, err_blk)
            total = jax.lax.psum(q.astype(jnp.int32), "pod")
            scale_max = jax.lax.pmax(scale, "pod")
            g_out = (total.astype(jnp.float32) * scale_max /
                     mesh.shape["pod"]).astype(g_blk.dtype)
            return g_out, new_err

        inner_spec = P(*(s if s != "pod" else None for s in
                         (spec or P(*(None,) * g.ndim))))
        fn = jax.shard_map(inner, mesh=mesh,
                           in_specs=(inner_spec, inner_spec),
                           out_specs=(inner_spec, inner_spec))
        return fn(g, err)

    flat_g, treedef = jax.tree_util.tree_flatten(grads)
    flat_e = treedef.flatten_up_to(err_state)
    flat_s = treedef.flatten_up_to(pspecs)
    out = [sync_leaf(g, e, s) for g, e, s in zip(flat_g, flat_e, flat_s)]
    return (treedef.unflatten([o[0] for o in out]),
            treedef.unflatten([o[1] for o in out]))
