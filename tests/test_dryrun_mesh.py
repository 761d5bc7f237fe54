"""Dry-run / elastic tests that need >1 host device: run in subprocesses so
the 8-device XLA flag never leaks into this process (smoke tests must see 1
device, per the assignment)."""
import json
import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str) -> str:
    # the child runs on virtual CPU devices and never tries to take a chip
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_cell_lowers_and_compiles_on_small_mesh():
    """The dry-run machinery end-to-end on a 4x2 mesh with a reduced arch."""
    out = _run("""
        import jax, json
        from repro.launch import cells
        from repro.launch.mesh import make_mesh
        from repro import hlo_analysis
        mesh = make_mesh((4, 2), ("data", "model"))
        # full-size configs are exercised by the real dry-run; here a small
        # arch proves the machinery under pytest time budgets.
        cell = cells.build_cell("mamba2-130m", "decode_32k", mesh)
        comp = cell.lowered.compile()
        mem = comp.memory_analysis()
        ana = hlo_analysis.analyze(comp.as_text())
        print(json.dumps({
            "temps": mem.temp_size_in_bytes,
            "flops": ana["flops"],
            "collective": sum(ana["collective_bytes"].values()),
        }))
    """)
    rec = json.loads(out.strip().splitlines()[-1])
    assert rec["flops"] > 0
    assert rec["collective"] > 0          # sharded decode must communicate


def test_train_step_lowers_multipod_axes():
    """(pod, data, model) mesh on 8 devices: the pod axis must shard."""
    out = _run("""
        import jax, json
        from repro.launch import cells
        from repro.launch.mesh import make_mesh
        from repro import hlo_analysis
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        cell = cells.build_cell("hymba-1.5b", "decode_32k", mesh)
        comp = cell.lowered.compile()
        ana = hlo_analysis.analyze(comp.as_text())
        print(json.dumps({"collective": sum(ana["collective_bytes"].values())}))
    """)
    rec = json.loads(out.strip().splitlines()[-1])
    assert rec["collective"] > 0


def test_elastic_shrink_resume():
    """Checkpoint on an 8-device mesh, resume on 4 devices: loss continues
    from the same value and the global batch is preserved."""
    out = _run("""
        import json, tempfile, jax
        import numpy as np
        from repro import configs
        from repro.launch.train import train_loop
        from repro.launch.mesh import make_mesh

        cfg = configs.get("mamba2-130m").reduced()
        d = tempfile.mkdtemp()
        mesh8 = make_mesh((8, 1), ("data", "model"))
        _, h1 = train_loop(cfg, steps=6, global_batch=8, seq_len=64,
                           mesh=mesh8, ckpt_dir=d, ckpt_interval=3,
                           log_every=100, seed=5)
        mesh4 = make_mesh((4, 1), ("data", "model"),
                          devices=jax.devices()[:4])
        _, h2 = train_loop(cfg, steps=10, global_batch=8, seq_len=64,
                           mesh=mesh4, ckpt_dir=d, resume=True,
                           ckpt_interval=3, log_every=100, seed=5)
        print(json.dumps({"h1": h1, "h2": h2}))
    """)
    rec = json.loads(out.strip().splitlines()[-1])
    # resumed first-step loss must continue the trajectory, not restart at init
    assert rec["h2"][0] < rec["h1"][0] - 0.2
    assert len(rec["h2"]) == 4   # steps 6..9
