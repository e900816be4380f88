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
import numpy as np
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


def test_paged_decode_attention_is_the_kernel_for_v5e(one_chip):
    """The paged branch of ``attn_apply`` at the conv cell's shapes (64
    slots of 2048 tokens, 8192 pages of 16, 16 x 128 heads, bf16) compiles
    to the Pallas paged-attention kernel, and no f32 copy of the gathered
    (64, 2048) view remains: that copy was most of the decode step. With
    the cache donated, as the engine donates it, the token write leaves
    the pools in place: no copy of a whole pool to another layout."""
    import re

    from repro.core.analog import AnalogCtx
    from repro.models import attention as attn_lib

    cfg = configs.get("olmo-1b")
    n_slots, s_max, bf16 = 64, 2048, jnp.bfloat16
    params = jax.eval_shape(
        lambda k: attn_lib.attn_init(k, cfg), jax.random.PRNGKey(0)
    )
    cache = jax.eval_shape(lambda: attn_lib.init_paged_cache(
        cfg, n_slots, s_max, bf16, page_size=16, n_pages=8192))
    place = lambda t: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), t
    )
    x = jax.ShapeDtypeStruct((n_slots, 1, cfg.d_model), bf16, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((n_slots, 1), jnp.int32, sharding=one_chip)

    def step(p, x, pos, c):
        ctx = AnalogCtx(cfg=AnalogConfig(), gain_s=jnp.ones((), jnp.float32))
        return attn_lib.attn_apply(p, x, ctx, cfg, positions=pos, cache=c)

    text = jax.jit(step, donate_argnums=(3,)).lower(
        place(params), x, pos, place(cache)).compile().as_text()
    assert "tpu_custom_call" in text
    view = n_slots * s_max * cfg.n_kv_heads * cfg.hd
    f32 = {tuple(map(int, d.split(","))) for d in
           re.findall(r"f32\[(\d+(?:,\d+)*)\]", text)}
    assert not [d for d in f32 if np.prod(d) == view], sorted(f32)
    pool = "bf16[{}]".format(",".join(map(str, cache.k.shape)))
    copies = [ln for ln in text.splitlines() if f"= {pool}" in ln and " copy(" in ln]
    assert not copies, copies


def test_lfm2_cell_decode_and_4096_prefill_compile_for_v5e(one_chip):
    """The engine's programs at the lfm2moe-b8-conv cell's shapes (LFM2's
    first six layers at published widths, 8 of 32 experts held, a 16384-row
    head; 64 slots of 4096 tokens in 16-token pages, one conv state per
    slot and conv layer): the decode step, with the paged-attention kernel
    over GQA 32/8 and the MoE counts in its token vector, and the prefill
    at the 4096 bucket both compile for a described v5e, and neither needs
    more device memory than the chip has left beside the programmed chip
    and the cache."""
    import json
    import pathlib

    from benchmarks.chip.drivers import lfm2moe_serve
    from repro.serving import ServingConfig, ServingEngine

    root = pathlib.Path(__file__).resolve().parents[1]
    c = json.loads((root / "benchmarks/chip/configs/lfm2moe-b8.json").read_text())
    mc = lfm2moe_serve.model_config(c)
    acfg = AnalogConfig().infer(b_adc=8)
    def programmed(k):
        prog = engine.compile_program(lm.lm_init(k, mc), acfg, k)
        return prog.params, prog.state

    params, state = jax.eval_shape(programmed, jax.random.PRNGKey(0))
    import dataclasses

    pcfg = dataclasses.replace(acfg, mode=engine.PCM_PROGRAMMED)
    n_slots, s_max = c["n_slots"], c["s_max"]
    eng = ServingEngine(mc, pcfg, params, ServingConfig(
        n_slots=n_slots, s_max=s_max, paged=True, page_size=c["page_size"],
        ref_check=False))
    # pools made for a TPU: the head axis is padded to the kernel's lanes
    cache = jax.eval_shape(lambda: lm.init_lm_cache(
        mc, n_slots, s_max, mc.dtype, stacked=False, paged=True,
        page_size=c["page_size"], platform="tpu"))
    assert cache[0] == [] and cache[1][2].k.shape[-1] == 128
    place = lambda t: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), t
    )
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)  # noqa: E731
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    decode = eng._decode.lower(
        place(params), i32(n_slots, 1), place(cache), key).compile()
    assert "tpu_custom_call" in decode.as_text()
    out = decode.out_info[0]
    assert out.shape == (n_slots + 4,)  # the tokens, then the MoE counts
    prefill = eng._prefill_bucket.lower(
        place(params), i32(1, s_max), i32(1), key).compile()
    # held on the chip: the effective weights, the PCM state they were
    # programmed from (kept for ageing) and the paged cache; 16e9 leaves
    # room below the v5e's 16.9e9 for the allocator and the embedding copy
    resident = sum(a.size * a.dtype.itemsize
                   for a in jax.tree.leaves((params, state, cache)))
    for prog in (decode, prefill):
        temp = prog.memory_analysis().temp_size_in_bytes
        assert resident + temp < 16e9, (resident, temp)
