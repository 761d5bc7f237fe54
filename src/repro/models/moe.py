"""Top-k mixture-of-experts FFN -- explicit shard_map distribution.

Distribution history (EXPERIMENTS.md section Perf, dbrx cell): two
global-view (pjit-propagated) dispatch layouts measured 6.1-7.2 TB/device
of collectives on dbrx train_4k -- the SPMD partitioner conservatively
replicates + all-reduces the dispatch scatters.  The production layout is
therefore EXPLICIT:

  * ``moe_ffn`` shard_maps over the whole mesh: tokens local to their data
    shard (one group = one sequence), expert weights' d_ff dim local to
    the "model" shard (expert tensor parallelism -- fine-grained MoE never
    needs an all-to-all);
  * inside, dispatch is plain local jnp: sort-based (argsort by expert id
    + running starts), capacity C = ceil(cf*S*k/E) per sequence, dropped
    tokens write to a sentinel row;
  * the ONE collective is an explicit bf16 psum of the combined (B,S,d)
    output over "model" (combine is linear, so reducing after combine
    moves S rows instead of E*C capacity slots -- 5x fewer bytes at
    top-4 x 1.25 capacity);
  * router fp32; Switch aux loss pmean'd over the data axes.

Without a mesh (single-device tests) the same local function runs
directly.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.distributed.sharding import ambient_mesh
from repro.models.config import ModelConfig


def init(key, cfg: ModelConfig, dtype):
    d, e, ff = cfg.d_model, cfg.n_experts, cfg.d_ff
    ks = jax.random.split(key, 4)
    s_in, s_out = d ** -0.5, ff ** -0.5
    return {
        "router": (jax.random.normal(ks[0], (d, e), jnp.float32) * s_in).astype(jnp.float32),
        "w_gate": (jax.random.normal(ks[1], (e, d, ff), jnp.float32) * s_in).astype(dtype),
        "w_up": (jax.random.normal(ks[2], (e, d, ff), jnp.float32) * s_in).astype(dtype),
        "w_down": (jax.random.normal(ks[3], (e, ff, d), jnp.float32) * s_out).astype(dtype),
    }


def capacity(cfg: ModelConfig, group_tokens: int) -> int:
    c = int(cfg.capacity_factor * group_tokens * cfg.experts_per_token
            / cfg.n_experts)
    return max(8, -(-c // 8) * 8)  # round up to sublane multiple


def _moe_local(router, w_gate, w_up, w_down, x, cfg: ModelConfig,
               tp_axis: str | None):
    """Per-shard MoE; x (B_local, S, d); w_* carry a LOCAL d_ff slice."""
    orig_b = x.shape[0]
    if x.shape[1] == 1 and orig_b > 1:
        # decode: one token per sequence -- dispatch the local batch as a
        # single group, or per-sequence capacity pads every token to 8
        # expert slots (measured 20x useful-flops loss on dbrx decode)
        x = x.reshape(1, orig_b, -1)
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.experts_per_token
    c = capacity(cfg, s)
    sk = s * k

    # routing (fp32, replicated across the model axis)
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32), router)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, k)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(axis=-1, keepdims=True), 1e-9)
    frac = jnp.mean(jax.nn.one_hot(expert_ids, e, dtype=jnp.float32),
                    axis=(0, 1, 2))
    aux = e * jnp.sum(frac * probs.mean(axis=(0, 1)))

    # group-local sort-based dispatch (one group per sequence)
    ids = expert_ids.reshape(b, sk)
    gates = gate_vals.reshape(b, sk)
    order = jnp.argsort(ids, axis=-1, stable=True)
    sid = jnp.take_along_axis(ids, order, -1)
    stok = order // k
    sgate = jnp.take_along_axis(gates, order, -1)
    counts = jax.nn.one_hot(ids, e, dtype=jnp.int32).sum(axis=1)
    starts = jnp.cumsum(counts, axis=-1) - counts
    pos = jnp.arange(sk)[None] - jnp.take_along_axis(starts, sid, -1)
    keep = pos < c
    slot = jnp.where(keep, sid * c + pos, e * c)

    brow = jnp.arange(b)[:, None]
    rows = e * c + 1
    flat_slot = (brow * rows + slot).reshape(-1)
    flat_tok = (brow * s + stok).reshape(-1)
    xg = jnp.take(x.reshape(b * s, d), flat_tok, axis=0)
    buf = jnp.zeros((b * rows, d), x.dtype).at[flat_slot].set(xg)
    xe = buf.reshape(b, rows, d)[:, :e * c].reshape(b, e, c, d)

    # expert SwiGLU on the local d_ff slice (bf16 in, fp32 accumulate)
    h = jax.nn.silu(jnp.einsum("becd,edf->becf", xe, w_gate,
                               preferred_element_type=jnp.float32)) * \
        jnp.einsum("becd,edf->becf", xe, w_up,
                   preferred_element_type=jnp.float32)
    h = h.astype(x.dtype)
    ye = jnp.einsum("becf,efd->becd", h, w_down).astype(x.dtype)

    # combine locally (linear in ye), then ONE bf16 psum over the TP axis
    yflat = jnp.concatenate(
        [ye.reshape(b, e * c, d), jnp.zeros((b, 1, d), ye.dtype)],
        axis=1).reshape(b * rows, d)
    contrib = jnp.take(yflat, flat_slot, axis=0).reshape(b, sk, d) * \
        (sgate * keep).astype(ye.dtype)[..., None]
    y = jnp.zeros((b * s, d), x.dtype).at[flat_tok].add(
        contrib.reshape(-1, d).astype(x.dtype)).reshape(b, s, d)
    if tp_axis is not None:
        y = jax.lax.psum(y, tp_axis)
    if orig_b != b:
        y = y.reshape(orig_b, 1, d)
    return y, aux


def moe_ffn(params, x: jnp.ndarray, cfg: ModelConfig):
    """x (B, S, d) -> (y (B, S, d), aux scalar); shard_mapped under a mesh."""
    mesh = ambient_mesh()
    if mesh is None or not mesh.axis_names:
        return _moe_local(params["router"], params["w_gate"], params["w_up"],
                          params["w_down"], x, cfg, tp_axis=None)

    from jax.sharding import PartitionSpec as P
    names = set(mesh.axis_names)
    fsdp = tuple(a for a in ("pod", "data") if a in names)
    tp = "model" if "model" in names else None
    ff_spec = P(None, None, tp) if tp and cfg.d_ff % mesh.shape[tp] == 0 \
        else P(None, None, None)
    ff_spec_down = P(None, ff_spec[2], None)
    batch_spec = P(fsdp if x.shape[0] % _width(mesh, fsdp) == 0 else None,
                   None, None)

    def local_fn(router, w_gate, w_up, w_down, xl):
        y, aux = _moe_local(router, w_gate, w_up, w_down, xl, cfg,
                            tp_axis=ff_spec[2])
        if fsdp:
            aux = jax.lax.pmean(aux, fsdp)
        if tp:
            aux = jax.lax.pmean(aux, tp)  # identical, but align replication
        return y, aux

    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(None, None), ff_spec, ff_spec, ff_spec_down, batch_spec),
        out_specs=(batch_spec, P()), check_vma=False)
    return fn(params["router"], params["w_gate"], params["w_up"],
              params["w_down"], x)


def _width(mesh, axes) -> int:
    w = 1
    for a in axes:
        w *= mesh.shape[a]
    return max(w, 1)
