"""Elastic rescale end-to-end: train on mesh A, checkpoint, restore on a
*different* mesh shape, and continue with an identical loss trajectory.

This is the DESIGN.md §6 contract: checkpoints are stored logically
unsharded, so a restarted job may come back with a different device
count/topology (lost pod) and resume exactly.  Runs in a subprocess so
the 8 virtual host devices don't leak into the rest of the suite.
"""
import json
import os
import subprocess
import sys

import numpy as np

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses, json, sys
import jax, jax.numpy as jnp
from repro.checkpoint import ckpt
from repro.configs import get_config
from repro.data.pipeline import DataConfig, SyntheticTokenSource
from repro.models import model as M
from repro.optim import adamw
from repro.sharding import Policy
from repro.train import trainer as T
from jax.sharding import AxisType, Mesh

mode, ckpt_dir = sys.argv[1], sys.argv[2]

cfg = dataclasses.replace(
    get_config("llama3.2-1b"), name="elastic", n_layers=2, d_model=64,
    n_heads=4, n_kv_heads=2, d_head=16, d_ff=128, vocab=128,
    dtype="float32", remat=False, q_chunk=32, kv_chunk=32)
src = SyntheticTokenSource(DataConfig(global_batch=8, seq_len=16,
                                      vocab=cfg.vocab),
                           process_index=0, process_count=1)
tc = T.TrainConfig(opt=adamw.AdamWConfig(lr=1e-3, warmup_steps=2,
                                         total_steps=10))

def make_mesh(shape):
    return jax.make_mesh(shape, ("data", "model"),
                         axis_types=(AxisType.Auto, AxisType.Auto))

def run_steps(params, opt, policy, mesh, start, n):
    step = T.jit_train_step(cfg, tc, policy,
                            jax.eval_shape(lambda: params),
                            jax.eval_shape(lambda: src(0)))
    losses = []
    for i in range(start, start + n):
        b = jax.tree.map(jnp.asarray, src(i))
        with mesh:
            params, opt, met = step(params, opt, b)
        losses.append(float(met["loss"]))
    return params, opt, losses

if mode == "full":
    # uninterrupted 6 steps on mesh (4, 2)
    mesh = make_mesh((4, 2))
    policy = Policy(mesh=mesh, fsdp=True)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    opt = adamw.init_state(tc.opt, params)
    _, _, losses = run_steps(params, opt, policy, mesh, 0, 6)
    print(json.dumps(losses))
elif mode == "phase1":
    # 3 steps on mesh (4, 2), then checkpoint
    mesh = make_mesh((4, 2))
    policy = Policy(mesh=mesh, fsdp=True)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    opt = adamw.init_state(tc.opt, params)
    params, opt, losses = run_steps(params, opt, policy, mesh, 0, 3)
    ckpt.save(ckpt_dir, 3, {"params": params, "opt": opt},
              extra={"data": src.checkpoint_state(3)})
    print(json.dumps(losses))
else:
    # restore on a DIFFERENT mesh (2, 4) and continue 3 steps
    mesh = make_mesh((2, 4))
    policy = Policy(mesh=mesh, fsdp=True)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    opt = adamw.init_state(tc.opt, params)
    state, extra = ckpt.restore(ckpt_dir, {"params": params, "opt": opt})
    start = SyntheticTokenSource.resume_step(extra["data"])
    _, _, losses = run_steps(state["params"], state["opt"], policy, mesh,
                             start, 3)
    print(json.dumps(losses))
"""


def _run(mode: str, ckpt_dir: str) -> list[float]:
    env = dict(os.environ)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.path.join(root, "src")
    out = subprocess.run(
        [sys.executable, "-c", _SCRIPT, mode, ckpt_dir],
        capture_output=True, text=True, env=env, timeout=420)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_elastic_rescale_exact_resume(tmp_path):
    ckpt_dir = str(tmp_path / "ck")
    full = _run("full", ckpt_dir)
    first = _run("phase1", ckpt_dir)
    resumed = _run("phase2", ckpt_dir)
    np.testing.assert_allclose(first, full[:3], rtol=1e-5)
    # resumed on the (2,4) mesh must continue the (4,2) trajectory
    np.testing.assert_allclose(resumed, full[3:], rtol=1e-4, atol=1e-5)
