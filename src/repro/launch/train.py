"""Training driver: data -> pjit'd train_step -> checkpoint/restart loop.

Fault-tolerance contract (tested in tests/test_checkpoint.py and
tests/test_elastic.py):
  * checkpoints are atomic (tmp-dir + rename) and carry the step;
  * ``--resume auto`` restarts from the latest complete checkpoint;
  * the data pipeline is stateless-seekable, so the resumed run sees the
    exact batches the lost run would have seen;
  * elastic resize: resuming on a different mesh re-places the same host
    arrays under the new sharding rules and rescales gradient-accumulation
    so the global batch is invariant (distributed/elastic.py);
  * straggler mitigation on a real fleet: per-step host heartbeat with a
    deadline -- a host missing two heartbeats is declared dead and the job
    restarts on the surviving mesh (hook stubbed here: single-host
    container), which the elastic path above makes cheap.

Run (CPU dev):  PYTHONPATH=src python -m repro.launch.train \
    --arch mamba2-130m --reduced --steps 50 --global-batch 16 --seq-len 128
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro import configs
from repro.checkpoint import CheckpointManager, latest_step, load_checkpoint
from repro.data import DataConfig, SyntheticLMData
from repro.distributed import elastic, sharding
from repro.distributed.steps import make_train_step
from repro.launch.mesh import make_local_mesh, make_production_mesh
from repro.models import build
from repro.optim import AdamWConfig, adamw_init


def train_loop(cfg, *, steps: int, global_batch: int, seq_len: int,
               mesh=None, micro_per_shard: int = 1, ckpt_dir: str | None = None,
               ckpt_interval: int = 50, resume: bool = False,
               opt_cfg: AdamWConfig | None = None, log_every: int = 10,
               seed: int = 0):
    """Shared by the CLI, examples and tests.  Returns (params, history)."""
    mesh = mesh or make_local_mesh()
    model = build(cfg)
    opt_cfg = opt_cfg or AdamWConfig(total_steps=steps,
                                     warmup_steps=max(1, steps // 20))
    accum = elastic.replan_accum(global_batch, micro_per_shard, mesh)
    micro = global_batch // accum

    data = SyntheticLMData(DataConfig(
        vocab_size=cfg.vocab_size, seq_len=seq_len, global_batch=global_batch,
        seed=seed, frontend=cfg.frontend,
        n_frontend_tokens=cfg.n_frontend_tokens, d_model=cfg.d_model))

    params = model.init(jax.random.PRNGKey(seed))
    opt_state = adamw_init(params)
    start_step = 0
    manager = CheckpointManager(ckpt_dir, interval=ckpt_interval) \
        if ckpt_dir else None
    if resume and ckpt_dir and latest_step(ckpt_dir) is not None:
        (params, opt_state), start_step = load_checkpoint(
            ckpt_dir, (params, opt_state))
        print(f"[train] resumed from step {start_step}")

    pspecs = sharding.params_specs(params, mesh)
    psh = sharding.to_shardings(pspecs, mesh, params)
    osh = sharding.to_shardings(sharding.opt_specs(opt_state, pspecs), mesh,
                                opt_state)
    params = jax.tree.map(jax.device_put, params, psh)
    opt_state = jax.tree.map(jax.device_put, opt_state, osh)

    step_fn = make_train_step(model, opt_cfg, accum)
    with jax.set_mesh(mesh):
        jitted = jax.jit(step_fn, in_shardings=(psh, osh, None),
                         out_shardings=(psh, osh, None),
                         donate_argnums=(0, 1))
        history = []
        t0 = time.time()
        for step in range(start_step, steps):
            raw = data.global_batch(step)
            batch = {k: np.reshape(v, (accum, micro) + v.shape[1:])
                     for k, v in raw.items()}
            params, opt_state, metrics = jitted(params, opt_state, batch)
            loss = float(metrics["loss"])
            history.append(loss)
            if manager:
                manager.maybe_save(step + 1, (params, opt_state))
            if step % log_every == 0 or step == steps - 1:
                dt = (time.time() - t0) / max(1, step - start_step + 1)
                print(f"[train] step {step:5d} loss {loss:.4f} "
                      f"lr {float(metrics['lr']):.2e} "
                      f"gnorm {float(metrics['grad_norm']):.2f} "
                      f"({dt*1e3:.0f} ms/step)", flush=True)
        if manager:
            manager.wait()
    return params, history


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mamba2-130m",
                    choices=configs.list_archs())
    ap.add_argument("--reduced", action="store_true",
                    help="reduced same-family config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=16)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--micro-per-shard", type=int, default=1)
    ap.add_argument("--mesh", choices=["local", "production", "multipod"],
                    default="local")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-interval", type=int, default=50)
    ap.add_argument("--resume", choices=["auto", "never"], default="auto")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cfg = configs.get(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    mesh = {"local": make_local_mesh,
            "production": make_production_mesh,
            "multipod": lambda: make_production_mesh(multi_pod=True)}[args.mesh]()
    train_loop(cfg, steps=args.steps, global_batch=args.global_batch,
               seq_len=args.seq_len, mesh=mesh,
               micro_per_shard=args.micro_per_shard, ckpt_dir=args.ckpt_dir,
               ckpt_interval=args.ckpt_interval,
               resume=args.resume == "auto", seed=args.seed)


if __name__ == "__main__":
    main()
