"""Distributed-machinery tests on an 8-fake-device mesh (subprocess: the
device-count flag must precede jax init, and the main test process keeps the
single real CPU device)."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, os.path.join(%(repo)r, "src"))
import functools
import jax, jax.numpy as jnp
import numpy as np
from repro import configs
from repro.configs import shapes as shapes_lib
from repro.core.analog import AnalogConfig
from repro.launch import sharding as shd
from repro.launch.steps import make_train_step, make_serve_step
from repro.models.common import set_logical_rules
from repro.models import lm
from repro.training import optim as optim_lib

from repro.launch import mesh as mesh_lib
mesh = mesh_lib.make_host_mesh(model=2)  # (4, 2) over 8 devices
cfg = configs.get_smoke("tinyllama-1.1b")
set_logical_rules(shd.logical_rules(mesh, cfg))
key = jax.random.PRNGKey(0)
params = lm.lm_init(key, cfg)
params_shape = jax.eval_shape(lambda: params)
param_shards = shd.param_shardings(params_shape, mesh, cfg)
opt_cfg = optim_lib.OptimizerConfig(lr=1e-2, total_steps=50, warmup=0)
opt_state = optim_lib.init(opt_cfg, params)

B, S = 8, 32
batch = {
    "tokens": jax.random.randint(key, (B, S), 0, cfg.vocab),
    "labels": jax.random.randint(key, (B, S), 0, cfg.vocab),
}
batch_specs = jax.eval_shape(lambda: batch)
batch_shards = shd.batch_shardings(batch_specs, mesh)
rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
opt_shape = jax.eval_shape(lambda: opt_state)

# optimizer state shardings mirror params
from repro.launch.sharding import build_opt_shardings
opt_shards = build_opt_shardings(opt_shape, params_shape, param_shards, mesh)

acfg = AnalogConfig().train(eta=0.05)
step = make_train_step(cfg, acfg, opt_cfg)
jstep = jax.jit(step, in_shardings=(param_shards, opt_shards, batch_shards, rep),
                out_shardings=(param_shards, opt_shards, rep))
with jax.set_mesh(mesh):
    params_s = jax.device_put(params, param_shards)
    opt_s = jax.device_put(opt_state, opt_shards)
    batch_s = jax.device_put(batch, batch_shards)
    losses = []
    for i in range(6):
        params_s, opt_s, metrics = jstep(params_s, opt_s, batch_s, jax.random.fold_in(key, i))
        losses.append(float(metrics["loss"]))

# loss decreases over a few steps on repeated batch
assert min(losses[1:]) < losses[0], losses
# parameters are actually sharded: a TP weight uses >1 device
w = params_s.blocks[0]["attn"]["wq"]["w"]
assert len(w.sharding.device_set) > 1
# numerical equivalence vs single-logical-device run
params_1 = lm.lm_init(key, cfg)
opt_1 = optim_lib.init(opt_cfg, params_1)
l0 = None
for i in range(6):
    params_1, opt_1, m1 = jax.jit(step)(params_1, opt_1, batch, jax.random.fold_in(key, i))
    l0 = float(m1["loss"])
assert abs(l0 - losses[-1]) < 1e-1, (l0, losses[-1])
print(json.dumps({"ok": True, "losses": losses, "unsharded_final": l0}))
""".replace("json.dumps", "__import__('json').dumps")


@pytest.mark.slow
def test_sharded_train_step_matches_unsharded():
    script = SCRIPT % {"repo": REPO}
    out = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert '"ok": true' in out.stdout.lower()


def test_production_mesh_shapes():
    """Mesh axes/shape contract (no device init: read the function source)."""
    import inspect

    from repro.launch import mesh as mesh_mod

    src = inspect.getsource(mesh_mod.make_production_mesh)
    assert "(2, 16, 16)" in src and "(16, 16)" in src
    assert '"pod", "data", "model"' in src


def test_dryrun_sets_device_flag_first():
    path = os.path.join(REPO, "src", "repro", "launch", "dryrun.py")
    with open(path) as f:
        head = f.read(300)
    assert head.startswith("import os")
    assert "xla_force_host_platform_device_count=512" in head
