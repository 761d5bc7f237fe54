"""Cell assembly: (arch x input-shape x mesh) -> lowered/compiled artifact.

A "cell" is one entry of the assignment's 40-cell grid.  ``build_cell``
returns the jitted step lowered with ShapeDtypeStruct stand-ins (no device
allocation), plus enough metadata for the roofline report.

Importable without the 512-device XLA flag; launch/dryrun.py sets that up.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.configs.shapes import (
    MICROBATCH_PER_SHARD, SHAPES, ShapeSpec, applicability,
)
from repro.distributed import sharding
from repro.distributed.steps import (
    make_decode_step, make_prefill_step, make_train_step,
)
from repro.models import attention_flops, build, flops_per_token
from repro.models.config import ModelConfig, ssd_flops
from repro.optim import AdamWConfig, adamw_init


@dataclasses.dataclass
class Cell:
    arch: str
    shape: str
    cfg: ModelConfig
    spec: ShapeSpec
    lowered: Any
    meta: dict


def _data_width(mesh) -> int:
    fsdp, _ = sharding.axis_names(mesh)
    w = 1
    for a in fsdp:
        w *= mesh.shape[a]
    return w


def _train_batch_struct(cfg: ModelConfig, spec: ShapeSpec, accum: int,
                        micro: int):
    s = spec.seq_len
    b: dict = {
        "tokens": jax.ShapeDtypeStruct((accum, micro, s), jnp.int32),
        "labels": jax.ShapeDtypeStruct((accum, micro, s), jnp.int32),
    }
    if cfg.frontend == "vision":
        b["patches"] = jax.ShapeDtypeStruct(
            (accum, micro, cfg.n_frontend_tokens, cfg.d_model), jnp.float32)
    elif cfg.frontend == "audio":
        b["frames"] = jax.ShapeDtypeStruct(
            (accum, micro, s, cfg.d_model), jnp.float32)
    return b


def _serve_batch_struct(cfg: ModelConfig, batch: int, seq: int):
    dec_len = 1 if cfg.is_encdec else seq
    b: dict = {"tokens": jax.ShapeDtypeStruct((batch, dec_len), jnp.int32)}
    if cfg.frontend == "vision":
        b["patches"] = jax.ShapeDtypeStruct(
            (batch, cfg.n_frontend_tokens, cfg.d_model), jnp.float32)
    elif cfg.frontend == "audio":
        b["frames"] = jax.ShapeDtypeStruct((batch, seq, cfg.d_model),
                                           jnp.float32)
    return b


def input_specs(arch: str, shape: str, mesh, cfg=None) -> dict:
    """ShapeDtypeStruct stand-ins for every input of the cell's step."""
    cfg = cfg or configs.get(arch)
    spec = SHAPES[shape]
    model = build(cfg)
    params_shape = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    out = {"params": params_shape}
    if spec.kind == "train":
        micro = MICROBATCH_PER_SHARD[arch] * _data_width(mesh)
        accum = max(1, spec.global_batch // micro)
        micro = spec.global_batch // accum
        out["opt_state"] = jax.eval_shape(adamw_init, params_shape)
        out["batch"] = _train_batch_struct(cfg, spec, accum, micro)
        out["accum"] = accum
    else:
        b = spec.global_batch
        enc_len = spec.seq_len if cfg.is_encdec else 0
        out["batch"] = _serve_batch_struct(cfg, b, spec.seq_len)
        out["cache"] = model.cache_struct(b, spec.seq_len, enc_len)
        out["tokens"] = jax.ShapeDtypeStruct((b,), jnp.int32)
        out["pos"] = jax.ShapeDtypeStruct((), jnp.int32)
    return out


def build_cell(arch: str, shape: str, mesh, *,
               opt_cfg: AdamWConfig | None = None,
               kv_int8: bool = False) -> Cell:
    cfg = configs.get(arch)
    if kv_int8:
        cfg = dataclasses.replace(cfg, kv_cache_dtype="int8")
    spec = SHAPES[shape]
    ok, why = applicability(cfg, shape)
    if not ok:
        raise ValueError(f"cell ({arch}, {shape}) skipped: {why}")
    model = build(cfg)
    specs_in = input_specs(arch, shape, mesh, cfg)
    params_shape = specs_in["params"]
    pspecs = sharding.params_specs(params_shape, mesh)
    psh = sharding.to_shardings(pspecs, mesh, params_shape)
    meta: dict = {"arch": arch, "shape": shape, "kind": spec.kind}

    with jax.set_mesh(mesh):
        if spec.kind == "train":
            accum = specs_in["accum"]
            ospecs = sharding.opt_specs(specs_in["opt_state"], pspecs)
            osh = sharding.to_shardings(ospecs, mesh, specs_in["opt_state"])
            bspecs = sharding.batch_specs(specs_in["batch"], mesh,
                                          accum_dim=True)
            bsh = sharding.to_shardings(bspecs, mesh, specs_in["batch"])
            step = make_train_step(model, opt_cfg or AdamWConfig(), accum)
            jitted = jax.jit(step, in_shardings=(psh, osh, bsh),
                             out_shardings=(psh, osh, None),
                             donate_argnums=(0, 1))
            lowered = jitted.lower(params_shape, specs_in["opt_state"],
                                   specs_in["batch"])
            meta["accum_steps"] = accum
            meta["tokens_per_step"] = spec.global_batch * spec.seq_len
        elif spec.kind == "prefill":
            bspecs = sharding.batch_specs(specs_in["batch"], mesh,
                                          accum_dim=False)
            bsh = sharding.to_shardings(bspecs, mesh, specs_in["batch"])
            cspecs = sharding.cache_specs(specs_in["cache"], cfg, mesh)
            csh = sharding.to_shardings(cspecs, mesh, specs_in["cache"])
            step = make_prefill_step(model)
            jitted = jax.jit(step, in_shardings=(psh, bsh, csh),
                             out_shardings=(None, csh), donate_argnums=(2,))
            lowered = jitted.lower(params_shape, specs_in["batch"],
                                   specs_in["cache"])
            meta["tokens_per_step"] = spec.global_batch * spec.seq_len
        else:  # decode
            fsdp, _ = sharding.axis_names(mesh)
            tsh = sharding.to_shardings(P(fsdp), mesh, specs_in["tokens"])
            cspecs = sharding.cache_specs(specs_in["cache"], cfg, mesh)
            csh = sharding.to_shardings(cspecs, mesh, specs_in["cache"])
            step = make_decode_step(model)
            jitted = jax.jit(step, in_shardings=(psh, tsh, None, csh),
                             out_shardings=(None, csh), donate_argnums=(3,))
            lowered = jitted.lower(params_shape, specs_in["tokens"],
                                   specs_in["pos"], specs_in["cache"])
            meta["tokens_per_step"] = spec.global_batch
        meta["n_params"] = cfg.param_count()
        meta["n_active_params"] = cfg.active_param_count()
        return Cell(arch, shape, cfg, spec, lowered, meta)


def model_flops_for_cell(cell: Cell, n_devices: int) -> float:
    """Analytic MODEL_FLOPS per device per step (6*N_active*D + attention)."""
    cfg, spec = cell.cfg, cell.spec
    if spec.kind == "train":
        tokens = spec.global_batch * spec.seq_len
        f = flops_per_token(cfg) * tokens
        f += attention_flops(cfg, spec.global_batch, spec.seq_len)
        f += ssd_flops(cfg, spec.global_batch, spec.seq_len)
    elif spec.kind == "prefill":
        tokens = spec.global_batch * spec.seq_len
        f = flops_per_token(cfg) / 3 * tokens          # fwd only = 2N
        f += attention_flops(cfg, spec.global_batch, spec.seq_len) / 3
        f += ssd_flops(cfg, spec.global_batch, spec.seq_len) / 3
    else:
        f = flops_per_token(cfg) / 3 * spec.global_batch
        f += attention_flops(cfg, spec.global_batch, 1,
                             kv_len=spec.seq_len, causal=False) / 3
        # decode SSD: recurrent step only (no chunked quadratic term)
        if cfg.family in ("ssm", "hybrid"):
            f += (4.0 * cfg.ssm_heads * cfg.ssm_headdim * cfg.ssm_state *
                  spec.global_batch * cfg.n_layers) / 3
    return f / n_devices
