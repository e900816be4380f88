"""Kernel micro-benchmarks: fused analog MVM (interpret mode on CPU; the
derived column reports the HBM-roofline time the fused kernel would take on
TPU v5e vs the unfused jnp composition's extra partial-sum traffic)."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from benchmarks.common import csv_row, launch_count, time_call
from repro.core import engine as engine_lib
from repro.core.analog import AnalogConfig
from repro.kernels import decode_fused as df
from repro.kernels.ops import analog_mvm
from repro.kernels.ref import analog_mvm_ref
from repro.models import lm
from repro.models.common import ModelConfig

HBM_BW = 819e9


def _execute_mvm_rows(fast: bool) -> list[str]:
    """Fused GDC-epilogue kernel vs the jnp ``execute_mvm`` oracle.

    Times the engine's unified execute hot path (the ``pcm_programmed``
    serving MVM: pre-quantized inputs x effective weights, per-row-tile ADC,
    fused GDC ``out_scale``) through both backends of the SAME
    ExecutionPlan machinery: the Pallas kernel and the tile-serial jnp
    reference. Off-TPU the kernel runs in interpret mode (functional
    parity, no perf claim); on a TPU host (``jax.devices()[0].platform ==
    "tpu"``) it is the real lowering and the row pair is the
    kernel-vs-oracle speedup the ROADMAP asks for. The derived column
    carries the backend and the max |kernel - oracle| deviation on the
    probe batch (ADC codes are asserted identical in tests/test_lowbit.py;
    FMA fusion may move the digital sum 1-2 ulp).
    """
    on_tpu = jax.devices()[0].platform == "tpu"
    shapes = [(128, 2048, 256)] if fast else [(128, 2048, 256),
                                              (256, 4096, 512)]
    acfg = AnalogConfig().infer(b_adc=8)
    rows = []
    for m, k, n in shapes:
        kx, kw = jax.random.split(jax.random.PRNGKey(0))
        x_q = jax.random.normal(kx, (m, k), jnp.float32)
        w = jax.random.normal(kw, (k, n), jnp.float32) * k**-0.5
        ra, gdc = jnp.float32(2.0), jnp.float32(1.3)
        plan_o = engine_lib.plan_for(acfg, k, n)
        plan_k = engine_lib.plan_for(
            dataclasses.replace(
                acfg, use_kernel=True, interpret=not on_tpu
            ),
            k, n,
        )

        def oracle(x, w, _p=plan_o):
            return engine_lib.execute_mvm(x, w, ra, _p, out_scale=gdc)

        def kernel(x, w, _p=plan_k):
            return engine_lib.execute_mvm(x, w, ra, _p, out_scale=gdc)

        iters = 2 if fast else 5
        # repro-lint: disable=RL003 -- one jit per benchmarked shape is the sweep design; time_call warms up first
        us_o = time_call(jax.jit(oracle), x_q, w, iters=iters)
        # repro-lint: disable=RL003 -- one jit per benchmarked shape is the sweep design; time_call warms up first
        us_k = time_call(jax.jit(kernel), x_q, w, iters=iters)
        dev = float(jnp.max(jnp.abs(kernel(x_q, w) - oracle(x_q, w))))
        backend = "tpu" if on_tpu else "interpret"
        # dispatch accounting: the oracle is pure XLA (0 Pallas launches),
        # the kernel backend is exactly one launch per MVM
        l_o = launch_count(oracle, x_q, w)
        l_k = launch_count(kernel, x_q, w)
        rows.append(csv_row(
            f"execute_mvm_oracle_gdc_{m}x{k}x{n}", us_o,
            f"backend=jnp_tiles={plan_o.n_row_tiles}_launches={l_o}"))
        rows.append(csv_row(
            f"execute_mvm_kernel_gdc_{m}x{k}x{n}", us_k,
            f"backend={backend}_speedup_vs_oracle={us_o / max(us_k, 1e-9):.2f}x"
            f"_max_abs_dev={dev:.2e}_launches={l_k}"))
    return rows


def _decode_step_rows(fast: bool) -> list[str]:
    """Whole-step megakernel vs the per-layer XLA decode walk.

    ``decode_step_xla`` is the serving default: ``lm_forward`` threads
    ``7 * n_layers + 1`` separate ``execute_mvm`` dispatches (plus
    norms/attention glue) through XLA per decode step. ``decode_step_fused``
    executes the SAME step as ONE ``pallas_call`` over a layer-walk grid
    (``kernels/decode_fused.py``). Both rows carry a launch column from
    :func:`benchmarks.common.launch_count`; the fused row asserts exactly
    one launch and bitwise logit/token parity with the unfused path before
    timing anything. Off-TPU the fused kernel runs in interpret mode --
    the row is a parity/launch-count check only. On a TPU host the floor
    below (>= 1.3x tokens/s) would be asserted, but Mosaic does not lower
    the grid yet (ROADMAP 1.3), so it has never been measured.
    """
    on_tpu = jax.devices()[0].platform == "tpu"
    cfg = ModelConfig(name="bench", family="dense", n_kv_heads=2).smoke()
    acfg = AnalogConfig().infer(b_adc=8)
    params = lm.lm_init(jax.random.PRNGKey(0), cfg)
    program = engine_lib.compile_program(
        params, acfg, jax.random.PRNGKey(42)
    )
    fplan = engine_lib.build_fused_plan(program)
    pparams, pacfg = program.params, program.cfg

    b, s_max = 4, 32
    ucache = lm.init_lm_cache(cfg, b, s_max, cfg.dtype, stacked=False,
                              per_slot=True)
    fcache = df.init_fused_cache(cfg, fplan.n_groups, b, s_max, cfg.dtype)
    for slot in range(b):
        prompt = (jnp.arange(6 + slot)[None] * 5 % cfg.vocab).astype(
            jnp.int32
        )
        c = lm.init_lm_cache(cfg, 1, s_max, cfg.dtype)
        _, c = lm.lm_forward(pparams, {"tokens": prompt}, pacfg, cfg,
                             cache=c, last_token_only=True)
        pc = lm.unstack_cache(c)
        ucache = lm.write_cache_slot(ucache, pc, slot)
        fcache = df.write_fused_slot(fcache, pc, slot)
    tok = jnp.full((b, 1), 7, jnp.int32)

    def decode_xla(tok, cache):
        return lm.lm_forward(pparams, {"tokens": tok}, pacfg, cfg,
                             cache=cache)

    def decode_fused(tok, cache):
        return df.fused_decode_step(pparams, tok, cache, fplan, cfg, pacfg)

    l_x = launch_count(decode_xla, tok, ucache)
    l_f = launch_count(decode_fused, tok, fcache)
    assert l_f == 1, f"fused decode must be ONE kernel launch, got {l_f}"
    n_mvm = len(engine_lib.FUSED_PROJS) * fplan.n_groups + 1

    lx, _ = decode_xla(tok, ucache)
    lf, _ = decode_fused(tok, fcache)
    assert jnp.array_equal(lx, lf), (
        "fused decode diverged bitwise from the per-layer path"
    )
    assert jnp.array_equal(
        jnp.argmax(lx[:, -1], -1), jnp.argmax(lf[:, -1], -1)
    ), "fused decode emitted different tokens than the per-layer path"

    iters = 2 if fast else 5
    # repro-lint: disable=RL003 -- one jit per benchmarked path is the sweep design; time_call warms up first
    us_x = time_call(jax.jit(decode_xla), tok, ucache, iters=iters)
    # repro-lint: disable=RL003 -- one jit per benchmarked path is the sweep design; time_call warms up first
    us_f = time_call(jax.jit(decode_fused), tok, fcache, iters=iters)
    speedup = us_x / max(us_f, 1e-9)
    if on_tpu:
        assert speedup >= 1.3, (
            f"fused decode must clear 1.3x over the XLA walk on a native-"
            f"lowering host, got {speedup:.2f}x"
        )
    backend = "tpu" if on_tpu else "interpret"
    return [
        csv_row(
            "decode_step_xla", us_x,
            f"backend=xla_launches={l_x}_mvm_dispatches={n_mvm}"
            f"_tokens_per_s={b / (us_x / 1e6):.0f}"),
        csv_row(
            "decode_step_fused", us_f,
            f"backend={backend}_launches={l_f}"
            f"_speedup_vs_xla={speedup:.2f}x"
            f"_tokens_per_s={b / (us_f / 1e6):.0f}_parity=bitwise"),
    ]


def run(fast: bool = False) -> list[str]:
    rows = []
    shapes = [(256, 4096, 512)] if fast else [
        (256, 2048, 512), (256, 4096, 512), (512, 8192, 1024)]
    for m, k, n in shapes:
        kx, kw = jax.random.split(jax.random.PRNGKey(0))
        x = jax.random.normal(kx, (m, k), jnp.float32)
        w = jax.random.normal(kw, (k, n), jnp.float32) * k**-0.5
        rd, ra = jnp.float32(4.0), jnp.float32(2.0)

        us_ref = time_call(
            jax.jit(lambda x, w: analog_mvm_ref(x, w, rd, ra)),  # repro-lint: disable=RL003 -- one jit per benchmarked shape is the sweep design
            x, w, iters=2)
        us_ker = time_call(
            lambda x, w: analog_mvm(x, w, r_adc=ra, r_dac=rd, interpret=True),
            x, w, iters=2)
        # TPU roofline estimate: fused kernel moves x + w + out once; the jnp
        # composition additionally writes+reads the (M, T, N) partials
        tiles = -(-k // 1024)
        fused_bytes = (m * k + k * n + m * n) * 4
        unfused_bytes = fused_bytes + 2 * m * n * tiles * 4
        rows.append(csv_row(
            f"analog_mvm_ref_{m}x{k}x{n}", us_ref,
            f"tpu_roofline_us={unfused_bytes/HBM_BW*1e6:.1f}"))
        rows.append(csv_row(
            f"analog_mvm_kernel_{m}x{k}x{n}", us_ker,
            f"tpu_roofline_us={fused_bytes/HBM_BW*1e6:.1f}"
            f"_traffic_saving={unfused_bytes/fused_bytes:.2f}x"))

        # pcm_infer serving shape: pre-quantized inputs (no DAC stage) with
        # the GDC out_scale epilogue fused into the kernel flush -- the
        # execute phase of a compiled CiMProgram.
        gdc = jnp.float32(1.3)
        us_serve = time_call(
            lambda x, w: analog_mvm(
                x, w, r_adc=ra, r_dac=None, out_scale=gdc, interpret=True),
            x, w, iters=2)
        rows.append(csv_row(
            f"analog_mvm_gdc_epilogue_{m}x{k}x{n}", us_serve,
            f"tpu_roofline_us={fused_bytes/HBM_BW*1e6:.1f}_fused_gdc"))
    rows.extend(_execute_mvm_rows(fast))
    rows.extend(_decode_step_rows(fast))
    return rows


if __name__ == "__main__":
    for r in run(fast=True):
        print(r)
