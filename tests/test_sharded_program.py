"""Sharded CiMPrograms: programming under pjit inherits the weight
shardings and is bit-identical to the host-programmed chip; drift_to is a
jitted, sharding-preserving update; programmed chips serialize to a
versioned artifact that round-trips exactly (same logits, same mapping).

The mesh tests need 8 (virtual) devices: the multi-device CI job provides
them via XLA_FLAGS=--xla_force_host_platform_device_count=8; under the
plain single-device tier-1 run they skip. The fresh-process round-trip
test (slow) spawns its own 8-device subprocesses and runs everywhere.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import store
from repro.core import engine
from repro.core.analog import AnalogConfig, AnalogCtx
from repro.launch import mesh as mesh_lib
from repro.models import ModelConfig, lm_forward, lm_init

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

INFER = AnalogConfig().infer(b_adc=8, t_seconds=86400.0)

needs8 = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs 8 (virtual) devices: run under "
    "XLA_FLAGS=--xla_force_host_platform_device_count=8 "
    "(the multi-device CI job does)",
)


def _moe_cfg(**kw):
    base = dict(
        name="t", family="moe", n_layers=2, n_experts=8, top_k=2,
    )
    base.update(kw)
    return ModelConfig(**base).smoke()


def _trees_bit_equal(a, b) -> bool:
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(la, lb)
    )


# ------------------------------------------------- sharded program + drift


@needs8
def test_sharded_program_bit_identical_to_host():
    """The tentpole contract: a chip programmed under pjit on an 8-device
    mesh is the SAME chip a single host would program -- conductances, Q
    factors, GDC numerators, effective weights, everything bitwise."""
    from repro.launch import mesh as mesh_lib
    from repro.launch import steps

    cfg = _moe_cfg()
    params = lm_init(jax.random.PRNGKey(0), cfg)
    prog_h = engine.compile_program(params, INFER, jax.random.PRNGKey(1))
    mesh = mesh_lib.make_serving_mesh(8)
    prog_s = steps.program_for_serving(
        params, INFER, jax.random.PRNGKey(1), mesh=mesh, model_cfg=cfg
    )
    assert _trees_bit_equal(prog_h.state, prog_s.state)
    assert _trees_bit_equal(prog_h.params, prog_s.params)
    assert prog_h.plans == prog_s.plans


@needs8
def test_pcm_state_inherits_weight_shardings():
    """g_pos/g_neg/q_* are created under jit with the spec of the weight
    they were programmed from (no host-side tree walk)."""
    from jax.sharding import NamedSharding
    from repro.launch import mesh as mesh_lib
    from repro.launch import steps

    cfg = _moe_cfg()
    params = lm_init(jax.random.PRNGKey(0), cfg)
    mesh = mesh_lib.make_serving_mesh(8)
    prog = steps.program_for_serving(
        params, INFER, jax.random.PRNGKey(1), mesh=mesh, model_cfg=cfg
    )
    w_sh = prog.params.blocks[0]["attn"]["wq"]["w"].sharding
    st = prog.state["blocks/0/attn/wq"]
    assert isinstance(w_sh, NamedSharding)
    assert any(ax is not None for ax in w_sh.spec)  # actually TP-sharded
    for leaf in ("g_pos", "g_neg", "q_pos", "q_neg"):
        assert st[leaf].sharding == w_sh, leaf
    # per-member scalars carry the stack part of the spec (here: replicated)
    assert st["w_scale"].sharding.is_fully_replicated


@needs8
def test_sharded_drift_matches_host_walk_bit_exact():
    """drift_to on the sharded program == drift_to on the host program,
    bitwise, with the serving shardings preserved (no gather to host)."""
    from repro.launch import mesh as mesh_lib
    from repro.launch import steps

    cfg = _moe_cfg()
    params = lm_init(jax.random.PRNGKey(0), cfg)
    prog_h = engine.compile_program(params, INFER, jax.random.PRNGKey(1))
    mesh = mesh_lib.make_serving_mesh(8)
    prog_s = steps.program_for_serving(
        params, INFER, jax.random.PRNGKey(1), mesh=mesh, model_cfg=cfg
    )
    aged_h = prog_h.drift_to(30 * 86400.0)
    aged_s = prog_s.drift_to(30 * 86400.0)
    assert _trees_bit_equal(aged_h.params, aged_s.params)
    # shardings preserved through the jitted update
    w_before = prog_s.params.blocks[0]["attn"]["wq"]["w"].sharding
    w_after = aged_s.params.blocks[0]["attn"]["wq"]["w"].sharding
    assert w_before == w_after
    assert not w_after.is_fully_replicated


@needs8
def test_moe_shardmap_programmed_parity_on_mesh():
    """ROADMAP gap: moe_dispatch="shard_map" programmed-mode parity on a
    real (2, 4) mesh -- manual all_to_all dispatch of a programmed expert
    bank (incl. the shared expert and per-expert GDC scales) matches the
    GShard einsum dispatch."""
    from repro.models import moe as moe_lib
    from repro.models.moe_shardmap import moe_apply_shardmap

    mesh = mesh_lib.make_serving_mesh(4)
    cfg = ModelConfig(
        family="moe", n_experts=8, top_k=2, d_model=32, d_ff=64,
        capacity_factor=8.0, moe_groups=2, shared_expert=True,
    )
    bank = moe_lib.moe_init(jax.random.PRNGKey(0), cfg)
    prog = engine.compile_program({"moe": bank}, INFER, jax.random.PRNGKey(5))
    node = prog.params["moe"]
    assert node["out_scale_buf"].shape == (3, 8)
    ctx = AnalogCtx(cfg=prog.cfg, gain_s=jnp.float32(1.0))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 32))

    y_einsum = moe_lib.moe_apply(node, x, ctx, cfg)
    with jax.set_mesh(mesh):
        y_shardmap = moe_apply_shardmap(node, x, ctx, cfg)
    np.testing.assert_allclose(
        np.asarray(y_einsum), np.asarray(y_shardmap), rtol=1e-4, atol=1e-5
    )
    # and the shard_map path really dispatched (it must not have fallen
    # back to the einsum path: outside the mesh they are the same function)
    assert not np.allclose(np.asarray(y_shardmap), 0.0)


def test_shared_expert_included_by_shardmap_fallback():
    """Single-device guard for the shared-expert term: the shard_map entry
    point must produce the einsum result including the shared expert."""
    from repro.models import moe as moe_lib
    from repro.models.moe_shardmap import moe_apply_shardmap

    cfg = ModelConfig(
        family="moe", n_experts=4, top_k=2, d_model=32, d_ff=64,
        capacity_factor=8.0, moe_groups=2, shared_expert=True,
    )
    bank = moe_lib.moe_init(jax.random.PRNGKey(0), cfg)
    ctx = AnalogCtx(cfg=AnalogConfig(), gain_s=jnp.float32(1.0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 32))
    y_e = moe_lib.moe_apply(bank, x, ctx, cfg)
    y_s = moe_apply_shardmap(bank, x, ctx, cfg)
    np.testing.assert_allclose(np.asarray(y_e), np.asarray(y_s),
                               rtol=1e-5, atol=1e-6)


# ------------------------------------------------------- program artifacts


def test_program_artifact_roundtrip_lm():
    """save -> load -> execute: same logits; drift_to on the loaded program
    is the same chip aging (bit-identical to drifting the original)."""
    cfg = _moe_cfg()
    params = lm_init(jax.random.PRNGKey(0), cfg)
    prog = engine.compile_program(params, INFER, jax.random.PRNGKey(1))
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 8), 0, cfg.vocab)
    logits0, _ = lm_forward(prog.params, {"tokens": toks}, prog.cfg, cfg)

    path = store.save_program("/tmp/cim_prog_test_lm", prog)
    prog2 = store.load_program(path, params_like=params)
    assert prog2.cfg == prog.cfg
    assert prog2.plans == prog.plans
    assert prog2.t_seconds == prog.t_seconds
    logits1, _ = lm_forward(prog2.params, {"tokens": toks}, prog2.cfg, cfg)
    np.testing.assert_array_equal(np.asarray(logits0), np.asarray(logits1))

    aged0, _ = lm_forward(
        prog.drift_to(30 * 86400.0).params, {"tokens": toks}, prog.cfg, cfg
    )
    aged1, _ = lm_forward(
        prog2.drift_to(30 * 86400.0).params, {"tokens": toks}, prog2.cfg, cfg
    )
    np.testing.assert_array_equal(np.asarray(aged0), np.asarray(aged1))


def test_program_artifact_roundtrip_cnn_mapping():
    """CNN program artifact keeps the 2D crossbar blocks AND the physical
    array mapping: the reloaded occupancy_grid is identical."""
    from benchmarks.common import KWS_BENCH_DW
    from repro.core.crossbar import occupancy_grid
    from repro.models.analognet import cnn_apply, cnn_init, crossbar_transforms

    cfg = KWS_BENCH_DW
    params = cnn_init(jax.random.PRNGKey(0), cfg)
    prog = engine.compile_program(
        params, INFER, jax.random.PRNGKey(1),
        transforms=crossbar_transforms(cfg), with_mapping=True,
    )
    path = store.save_program("/tmp/cim_prog_test_cnn", prog)
    prog2 = store.load_program(path)  # plain-dict params: no template needed
    x = jax.random.normal(
        jax.random.PRNGKey(2), (2,) + cfg.input_hw + (cfg.in_channels,)
    )
    y0 = cnn_apply(prog.params, x, prog.cfg, cfg)
    y1 = cnn_apply(prog2.params, x, prog2.cfg, cfg)
    np.testing.assert_array_equal(np.asarray(y0), np.asarray(y1))
    assert prog2.mapping is not None
    assert prog2.mapping.n_arrays == prog.mapping.n_arrays
    for a in range(prog.mapping.n_arrays):
        np.testing.assert_array_equal(
            occupancy_grid(prog.mapping, a), occupancy_grid(prog2.mapping, a)
        )
    assert prog2.mapping.utilization == prog.mapping.utilization


def test_program_artifact_versioning():
    cfg = _moe_cfg()
    params = lm_init(jax.random.PRNGKey(0), cfg)
    prog = engine.compile_program(params, INFER, jax.random.PRNGKey(1))
    path = store.save_program("/tmp/cim_prog_test_ver", prog)
    meta_path = os.path.join(path, "meta.json")
    with open(meta_path) as f:
        meta = json.load(f)
    assert meta["format"] == store.PROGRAM_FORMAT
    assert meta["version"] == store.PROGRAM_VERSION

    # a future (newer) artifact version must be refused, not misread
    with open(meta_path, "w") as f:
        json.dump({**meta, "version": store.PROGRAM_VERSION + 1}, f)
    with pytest.raises(ValueError, match="version"):
        store.load_program(path, params_like=params)

    # a foreign directory with a COMMIT file is not a program artifact
    with open(meta_path, "w") as f:
        json.dump({"step": 3}, f)
    with pytest.raises(ValueError, match="cim-program"):
        store.load_program(path, params_like=params)


def test_program_artifact_rejects_mismatched_model():
    """Loading an artifact with a template from a different architecture
    must fail loudly, not silently mix stored and freshly-initialized
    weights."""
    import dataclasses

    cfg = _moe_cfg()
    prog = engine.compile_program(
        lm_init(jax.random.PRNGKey(0), cfg), INFER, jax.random.PRNGKey(1)
    )
    path = store.save_program("/tmp/cim_prog_test_mismatch", prog)
    wrong_cfg = dataclasses.replace(cfg, d_model=cfg.d_model * 2)
    wrong_template = lm_init(jax.random.PRNGKey(0), wrong_cfg)
    with pytest.raises(ValueError, match="does not match"):
        store.load_program(path, params_like=wrong_template)


def test_make_serving_mesh_contract():
    from repro.launch import mesh as mesh_lib

    mesh = mesh_lib.make_serving_mesh()
    n = len(jax.devices())
    assert mesh.axis_names == ("data", "model")
    assert mesh.shape["model"] == n and mesh.shape["data"] == 1
    mesh3 = mesh_lib.make_serving_mesh(3)  # non-divisor degrees round down
    assert n % mesh3.shape["model"] == 0


# ------------------------------------ fresh-process artifact (acceptance)

_PROGRAM_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
import sys
sys.path.insert(0, os.path.join(%(repo)r, "src"))
import json
import jax, jax.numpy as jnp
import numpy as np
from repro.checkpoint import store
from repro.core import engine
from repro.core.analog import AnalogConfig
from repro.launch import mesh as mesh_lib, steps
from repro.models import ModelConfig, lm_forward, lm_init

INFER = AnalogConfig().infer(b_adc=8, t_seconds=86400.0)
cfg = ModelConfig(name="t", family="moe", n_layers=2, n_experts=8, top_k=2).smoke()
params = lm_init(jax.random.PRNGKey(0), cfg)
toks = jax.random.randint(jax.random.PRNGKey(2), (2, 8), 0, cfg.vocab)

# program on the 8-virtual-device mesh and persist the chip
mesh = mesh_lib.make_serving_mesh(8)
assert mesh.devices.size == 8
prog_s = steps.program_for_serving(
    params, INFER, jax.random.PRNGKey(1), mesh=mesh, model_cfg=cfg)
store.save_program(%(art)r, prog_s)

# single-process host-walk reference: program on one device, drift, forward
prog_h = engine.compile_program(params, INFER, jax.random.PRNGKey(1))
aged_h = prog_h.drift_to(24 * 3600.0)
logits_h, _ = lm_forward(aged_h.params, {"tokens": toks}, aged_h.cfg, cfg)
np.savez(%(ref)r, logits=np.asarray(logits_h), tokens=np.asarray(toks))
print(json.dumps({"ok": True}))
"""

_RELOAD_SCRIPT = r"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"  # fresh single-device process
import sys
sys.path.insert(0, os.path.join(%(repo)r, "src"))
import json
import jax, jax.numpy as jnp
import numpy as np
from repro.checkpoint import store
from repro.models import ModelConfig, lm_forward, lm_init

cfg = ModelConfig(name="t", family="moe", n_layers=2, n_experts=8, top_k=2).smoke()
params = lm_init(jax.random.PRNGKey(0), cfg)
ref = np.load(%(ref)r)

program = store.load_program(%(art)r, params_like=params)
program = program.drift_to(24 * 3600.0)  # jitted drift on the loaded chip
logits, _ = lm_forward(
    program.params, {"tokens": jnp.asarray(ref["tokens"])}, program.cfg, cfg)
identical = bool(np.array_equal(np.asarray(logits), ref["logits"]))
print(json.dumps({"ok": True, "bit_identical": identical}))
assert identical, "mesh-programmed+saved+reloaded chip diverged from host walk"
"""


@pytest.mark.slow
def test_mesh_programmed_artifact_fresh_process_bit_identical(tmp_path):
    """The acceptance scenario end to end: program on an 8-virtual-device
    mesh -> save -> reload in a FRESH process -> jitted drift_to(24h) ->
    logits bit-identical to the single-process host-walk path."""
    art = str(tmp_path / "chip")
    ref = str(tmp_path / "ref.npz")
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)
    a = subprocess.run(
        [sys.executable, "-c",
         _PROGRAM_SCRIPT % {"repo": REPO, "art": art, "ref": ref}],
        capture_output=True, text=True, timeout=900, env=env,
    )
    assert a.returncode == 0, a.stderr[-3000:]
    b = subprocess.run(
        [sys.executable, "-c",
         _RELOAD_SCRIPT % {"repo": REPO, "art": art, "ref": ref}],
        capture_output=True, text=True, timeout=900, env=env,
    )
    assert b.returncode == 0, b.stderr[-3000:]
    assert '"bit_identical": true' in b.stdout.lower()
