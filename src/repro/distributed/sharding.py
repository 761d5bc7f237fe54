"""Sharding rules: parameter/optimizer/activation/cache PartitionSpecs.

Layout (DESIGN.md section 4): mesh axes (pod, data, model) or (data, model).

  * ``fsdp``  = ("pod", "data")  -- ZeRO-3 weight shard + batch shard,
  * ``tp``    = "model"          -- Megatron-style tensor parallel.

Every rank>=2 weight shards its TP-natural dim over ``model`` and its other
major dim over the fsdp axes, so params AND optimizer state are fully
sharded; XLA SPMD inserts the per-layer all-gathers which, under the layer
scan, overlap with the previous layer's compute (the paper's frame-buffer
set-0/set-1 discipline, one level up).

KV caches: heads shard over ``model`` when divisible; otherwise the cache
*length* dim shards over ``model`` (sequence-sharded decode: scores stay
sharded over T and only the small PV partial-sums all-reduce).
"""
from __future__ import annotations

import re
from typing import TYPE_CHECKING

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

if TYPE_CHECKING:  # avoid repro.models import cycle (models use constrain())
    from repro.models.config import ModelConfig


def ambient_mesh():
    """The abstract mesh of the enclosing ``jax.set_mesh`` scope (usable
    inside ``jit``), or None when no mesh is set.  Placing arrays needs
    the concrete mesh: ``jax.sharding.get_mesh()``, outside ``jit``."""
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m


def axis_names(mesh: Mesh) -> tuple[tuple[str, ...], str]:
    names = mesh.axis_names
    tp = "model"
    fsdp = tuple(n for n in names if n != tp)
    return fsdp, tp


# rule: path-regex -> (spec for last two dims);  extra leading dims (layer
# stack, expert dim) are replicated.
_COL = "col"   # (.., d_in, d_out_tp):  P(fsdp, tp)
_ROW = "row"   # (.., d_in_tp, d_out):  P(tp, fsdp)
_PARAM_RULES: list[tuple[str, str]] = [
    (r"\['(embed|unembed)'\]$", "embed"),          # (V, d): P(tp, fsdp)
    (r"\['(wq|wk|wv)'\]$", _COL),
    (r"\['(w_gate|w_up)'\]$", _COL),
    (r"\['in_proj'\]$", _COL),
    (r"\['router'\]$", "router"),                  # (d, E): P(fsdp, None)
    (r"\['(wo|w_down|out_proj)'\]$", _ROW),
    # conv_w stays replicated: its channel layout is (heads x headdim)
    # interleaved, which a model-axis shard cannot re-express after the
    # (B,S,di)->(B,S,h,p) reshape (forces mesh-transpose permutes).
    (r"\['conv_w'\]$", "replicate"),
]


def param_spec(path_str: str, ndim: int, fsdp, tp) -> P:
    if ndim <= 1:
        return P()
    lead = (None,) * (ndim - 2)
    for pattern, kind in _PARAM_RULES:
        if re.search(pattern, path_str):
            if kind == "embed":
                return P(*lead, tp, fsdp)
            if kind == _COL:
                return P(*lead, fsdp, tp)
            if kind == _ROW:
                return P(*lead, tp, fsdp)
            if kind == "router":
                return P(*lead, fsdp, None)
            if kind == "replicate":
                return P(*lead, None, None)
    return P(*lead, None, None)                    # unknown 2D+: replicate


def params_specs(params_shape, mesh: Mesh):
    """PartitionSpec pytree for a params (or shapes) pytree."""
    fsdp, tp = axis_names(mesh)
    flat, treedef = jax.tree_util.tree_flatten_with_path(params_shape)
    specs = [param_spec(jax.tree_util.keystr(path), leaf.ndim, fsdp, tp)
             for path, leaf in flat]
    return jax.tree_util.tree_unflatten(treedef, specs)


def opt_specs(opt_shape, pspecs):
    """Optimizer state mirrors the params' specs (fully sharded fp32)."""
    return {
        "step": P(),
        "master": pspecs,
        "m": pspecs,
        "v": pspecs,
    }


def batch_specs(batch_shape, mesh: Mesh, *, accum_dim: bool):
    """Training batch (accum, micro, ...) or serving batch (B, ...):
    the batch dim shards over all fsdp axes."""
    fsdp, _ = axis_names(mesh)

    def spec(leaf):
        if accum_dim:
            return P(None, fsdp, *(None,) * (leaf.ndim - 2))
        return P(fsdp, *(None,) * (leaf.ndim - 1))

    return jax.tree.map(spec, batch_shape)


def _attn_cache_spec(shape_tree, cfg: "ModelConfig", mesh: Mesh):
    fsdp, tp = axis_names(mesh)
    tp_size = mesh.shape[tp]
    heads_shardable = cfg.n_kv_heads % tp_size == 0 if cfg.n_kv_heads else False

    def spec(path, leaf):
        name = jax.tree_util.keystr(path)
        if name.endswith("['kpos']"):
            return P(*(None,) * leaf.ndim)
        # (L, B, Hkv, T, D)
        if heads_shardable:
            return P(None, fsdp, tp, None, None)
        return P(None, fsdp, None, tp, None)       # sequence-sharded cache

    flat, treedef = jax.tree_util.tree_flatten_with_path(shape_tree)
    return jax.tree_util.tree_unflatten(
        treedef, [spec(p, l) for p, l in flat])


def cache_specs(cache_shape, cfg: "ModelConfig", mesh: Mesh):
    """Specs for the serve cache pytree (attention / ssm / hybrid / encdec)."""
    fsdp, tp = axis_names(mesh)

    def spec(path, leaf):
        name = jax.tree_util.keystr(path)
        if "kpos" in name:
            return P(*(None,) * leaf.ndim)
        if "'state'" in name:                      # (L, B, h, p, n)
            return P(None, fsdp, None, tp, None)
        if "'conv'" in name:                       # (L, B, w-1, ch)
            return P(None, fsdp, None, tp)
        # attention k/v (self or cross): (L, B, Hkv, T, D)
        tp_size = mesh.shape[tp]
        if cfg.n_kv_heads and cfg.n_kv_heads % tp_size == 0:
            return P(None, fsdp, tp, None, None)
        return P(None, fsdp, None, tp, None)

    flat, treedef = jax.tree_util.tree_flatten_with_path(cache_shape)
    return jax.tree_util.tree_unflatten(
        treedef, [spec(p, l) for p, l in flat])


def constrain(x, *axes):
    """Best-effort activation sharding constraint under the ambient mesh.

    ``axes`` name mesh axes per dim ("batch" expands to all fsdp axes);
    axes missing from the mesh or not dividing the dim are dropped, and the
    call is a no-op outside jit/mesh contexts -- so model code can pin its
    activation layouts without caring whether it runs on 1 CPU device or
    the 512-chip production mesh."""
    mesh = ambient_mesh()
    if mesh is None or not mesh.axis_names:
        return x
    names = set(mesh.axis_names)
    fsdp = tuple(n for n in ("pod", "data") if n in names)
    spec = []
    for i, ax in enumerate(axes):
        if ax is None:
            spec.append(None)
            continue
        group = fsdp if ax == "batch" else (ax,) if isinstance(ax, str) else ax
        group = tuple(a for a in group if a in names)
        size = 1
        for a in group:
            size *= mesh.shape[a]
        if not group or size == 0 or x.shape[i] % size:
            spec.append(None)
        else:
            spec.append(group if len(group) > 1 else group[0])
    if all(s is None for s in spec):
        return x
    return jax.lax.with_sharding_constraint(x, P(*spec))


def sanitize_specs(shape_tree, spec_tree, mesh: Mesh):
    """Drop spec axes whose mesh size does not divide the tensor dim.

    pjit *arguments* require exact divisibility; odd vocab sizes (50280,
    49155, 32001, 51865) or batch=1 long-context cells fall back to
    replication on that dim.  The downgrades are deliberate production
    behaviour and are surfaced in the dry-run record."""
    def fix(shape_leaf, spec):
        dims = shape_leaf.shape
        new = []
        for i, axis in enumerate(spec):
            if axis is None or i >= len(dims):
                new.append(axis)
                continue
            axes = axis if isinstance(axis, tuple) else (axis,)
            size = 1
            for a in axes:
                size *= mesh.shape[a]
            new.append(axis if dims[i] % size == 0 else None)
        return P(*new)

    return jax.tree.map(fix, shape_tree, spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def to_shardings(spec_tree, mesh: Mesh, shape_tree=None):
    if shape_tree is not None:
        spec_tree = sanitize_specs(shape_tree, spec_tree, mesh)
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))
