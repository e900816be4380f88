"""Compile the main path's kernels for a described TPU v5e, with no chip.

The TPU compiler is installed here and compiles for a chip that is described
rather than attached (``jax.experimental.topologies``): it refuses what a
chip run would refuse -- unaligned blocks, VMEM overflow, unsupported ops --
at no chip time. The topology is described inside a module-scoped fixture,
never at import, so every test worker collects the same tests and only the
one given this file loads the TPU library. JAX's persistent compilation
cache is off around these compiles (an executable for an absent chip cannot
be read back).
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.core import engine
from repro.core.analog import AnalogConfig
from repro.kernels import decode_fused, ops
from repro.models import lm

#: olmo-1b's projection shapes (K, N): wq/wk/wv/wo, w1/w3, w2, lm_head
OLMO_SHAPES = [(2048, 2048), (2048, 8192), (8192, 2048), (2048, 50304)]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    was_on = jax.config.jax_enable_compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            try:
                topo = topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2"
                )
            except Exception as e:  # no TPU compiler in this install
                pytest.skip(f"no v5e:2x2 topology can be described: {e}")
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was_on)
            compilation_cache.reset_cache()


@pytest.mark.parametrize("bits", [4, 8])
@pytest.mark.parametrize("k,n", OLMO_SHAPES)
def test_analog_mvm_compiles_for_v5e(one_chip, k, n, bits):
    """The per-MVM kernel at every olmo-1b projection, 4 decode slots."""
    spec = lambda shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.float32, sharding=one_chip
    )
    fn = jax.jit(
        lambda x, w, r, s: ops.analog_mvm(
            x, w, r_adc=r, out_scale=s, bits=bits, interpret=False
        )
    )
    compiled = fn.lower(spec((4, k)), spec((k, n)), spec(()), spec(())).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_grid_refused_by_mosaic(one_chip):
    """The fused decode grid at the smallest olmo-shaped width (one 128-wide
    head, 4 layers) still lowers the GQA attention einsum, with two batch
    dims, inside the kernel -- which Mosaic refuses at every width. This
    pins the refusal; once the grid's attention lowers (ROADMAP 1.3),
    compile here instead and run the grid in chip_smoke.py."""
    import dataclasses

    cfg = dataclasses.replace(
        configs.get("olmo-1b"), n_layers=4, d_model=128, n_heads=1,
        n_kv_heads=1, head_dim=128, d_ff=512, vocab=1024,
    )
    acfg = AnalogConfig().infer(b_adc=8)
    params = jax.eval_shape(
        lambda k: engine.compile_program(lm.lm_init(k, cfg), acfg, k).params,
        jax.random.PRNGKey(0),
    )
    pcfg = dataclasses.replace(acfg, mode=engine.PCM_PROGRAMMED)
    d, f = cfg.d_model, cfg.d_ff
    plan = lambda k, n: engine.plan_for(pcfg, k, n)  # noqa: E731
    fplan = engine.FusedDecodePlan(
        n_groups=cfg.n_layers,
        proj_plans=(plan(d, d),) * 4 + (plan(d, f),) * 2 + (plan(f, d),),
        head_plan=plan(d, cfg.vocab),
        interpret=False,
    )
    cache = jax.eval_shape(
        lambda: decode_fused.init_fused_cache(cfg, cfg.n_layers, 8, 64, cfg.dtype)
    )
    place = lambda t: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), t
    )
    tok = jax.ShapeDtypeStruct((8, 1), jnp.int32, sharding=one_chip)
    step = jax.jit(
        lambda p, t, c: decode_fused.fused_decode_step(p, t, c, fplan, cfg, pcfg)
    )
    with pytest.raises(Exception, match="Up to 1 batch dim supported"):
        step.lower(place(params), tok, place(cache)).compile()
