"""Distributed paths on 8 host-platform devices.

These run in SUBPROCESSES because the device-count flag must be set before
jax initializes, and the rest of the suite must keep seeing 1 device."""
import os
import subprocess
import sys


ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def run_sub(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={devices} "
                        + env.get("XLA_FLAGS", ""))
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    return out.stdout


def test_sharded_train_step_matches_single_device():
    """Same math: 2x4 mesh train step == single-device train step."""
    print(run_sub("""
import dataclasses
import jax, numpy as np, jax.numpy as jnp
from repro.configs import get_config
from repro.models.model import init_params
from repro.optim import make_optimizer, constant_lr
from repro.launch.mesh import make_mesh
from repro.launch.steps import TrainState, make_train_step
from repro.launch import shardings as shd

cfg = get_config("llama3.2-1b", reduced=True)
cfg = dataclasses.replace(cfg, d_model=64, n_heads=4, kv_heads=2)
opt = make_optimizer("adamw", constant_lr(1e-2))
params = init_params(jax.random.PRNGKey(0), cfg)
tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab)

# single device
step1 = jax.jit(make_train_step(cfg, opt))
s1, m1 = step1(TrainState(params, opt.init(params)), {"tokens": tokens})

# 2x4 mesh
mesh = make_mesh((2, 4), ("data", "model"))
cfg2 = dataclasses.replace(cfg, batch_axes=("data",))
with mesh:
    p2 = jax.device_put(params, shd.param_shardings(params, mesh))
    st2 = TrainState(p2, opt.init(p2))
    step2 = jax.jit(make_train_step(cfg2, opt))
    s2, m2 = step2(st2, {"tokens": jax.device_put(
        tokens, shd.batch_sharding(mesh, 8))})
np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                           rtol=2e-3)
w1 = np.asarray(jax.tree_util.tree_leaves(s1.params)[0], np.float32)
w2 = np.asarray(jax.tree_util.tree_leaves(s2.params)[0], np.float32)
np.testing.assert_allclose(w1, w2, rtol=2e-2, atol=2e-4)
print("sharded == single-device train step OK")
"""))


def test_elastic_reshard_and_shrink():
    print(run_sub("""
import jax, numpy as np, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.runtime.elastic import (build_mesh, largest_feasible_mesh,
                                   reshard)
assert largest_feasible_mesh(8, 4) == (2, 4)
assert largest_feasible_mesh(6, 2) == (3, 2)
mesh8 = build_mesh((2, 4), ("data", "model"))
mesh4 = build_mesh((1, 4), ("data", "model"), devices=jax.devices()[:4])
tree = {"w": jnp.arange(64.0).reshape(8, 8)}
spec_fn = lambda key, leaf: P("data", "model")
t8 = reshard(tree, mesh8, spec_fn)
t4 = reshard(t8, mesh4, spec_fn)
np.testing.assert_array_equal(np.asarray(t4["w"]), np.asarray(tree["w"]))
print("elastic reshard OK")
"""))


def test_dryrun_entry_small_mesh():
    """The dryrun module itself (flag handling + lower + compile) on a tiny
    mesh via direct function use."""
    print(run_sub("""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8 " + \
    os.environ.get("XLA_FLAGS", "")
import jax
from repro.launch.mesh import make_mesh
from repro.launch.steps import lower_cell, cell_config
import dataclasses
mesh = make_mesh((2, 4), ("data", "model"))
import repro.configs.llama3_2_1b as mod
cfg = dataclasses.replace(mod.REDUCED, batch_axes=("data",))
# reuse the real lower_cell machinery on the reduced config
from repro.launch import steps
from repro.configs.base import SHAPES
spec = SHAPES["train_4k"]
lowered = lower_cell("llama3.2-1b", "train_4k", mesh, cfg=dataclasses.replace(
    cfg, loss_chunk=64))
compiled = lowered.compile()
assert compiled.memory_analysis() is not None
print("mini dryrun OK")
""", devices=8))
