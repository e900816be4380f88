"""Step functions: train / prefill / serve(decode), pjit-ready.

Factories close over static config (ModelConfig, AnalogConfig, optimizer) and
return pure functions of (params, opt_state, batch, rng) suitable for
jax.jit with in/out shardings. The same functions back the real launcher
(train.py / serve.py) and the dry-run (dryrun.py).

Analog serving follows the hardware's program-once / execute-many lifecycle:
call ``engine.compile_program`` ONCE before the decode loop -- it compiles
the param tree into a CiMProgram (PCM chain applied a single time) -- and
feed the returned (program.params, program.cfg) to the prefill/serve steps.
The per-call ``pcm_infer`` mode re-simulates programming on every forward
and exists for statistical accuracy sweeps, not serving.

Request-level serving (slot scheduling, continuous batching, drift-policy
hooks) lives one layer up in :mod:`repro.serving`: ``ServingEngine`` owns
one compiled program and drives the prefill/decode lifecycle itself;
:func:`refresh_program` below is what its refresh policy calls.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro import clock as clock_lib
from repro import obs
from repro.core.analog import AnalogConfig
from repro.models.common import ModelConfig
from repro.models.lm import lm_forward, lm_loss
from repro.training import optim as optim_lib

Array = jax.Array


def program_for_serving(
    params: Any,
    analog_cfg: AnalogConfig,
    key: Array,
    *,
    mesh: Any = None,
    model_cfg: Optional[ModelConfig] = None,
    transforms: Optional[dict] = None,
    with_mapping: bool = False,
    b_adc_overrides: Optional[dict] = None,
    t_seconds: Optional[float] = None,
    chip_id: Optional[int] = None,
):
    """Program phase of an analog serving deployment -> CiMProgram.

    With ``mesh``, params are placed in the inference layout (TP over
    ``model``) first and the PCM state is created under jit with the same
    shardings -- the chip a fleet would program collectively, bit-identical
    to the single-host program. The returned program's (params, cfg) feed
    the prefill/serve steps directly.

    ``b_adc_overrides``: per-layer {path-pattern: bits in {4, 6, 8}} for
    mixed-precision programs (e.g. keep the lm_head at 8 bits while the
    block projections serve at 4) -- see ``engine.compile_program``.

    ``t_seconds`` overrides the config's chip age for the first evaluation
    (drift-lifecycle serving compiles at the schedule's first age).

    Recorded as the ``program`` span, which ends when the programmed params
    are ready on the device.
    """
    from repro.core import engine
    from repro.launch import sharding as shd

    with obs.span("program", clock_lib.SYSTEM.now):
        shardings = None
        if mesh is not None:
            shardings = shd.program_shardings(params, mesh, model_cfg)
            params = jax.device_put(params, shardings)
        program = engine.compile_program(
            params,
            analog_cfg,
            key,
            t_seconds=t_seconds,
            transforms=transforms,
            with_mapping=with_mapping,
            shardings=shardings,
            b_adc_overrides=b_adc_overrides,
            chip_id=chip_id,
        )
        jax.block_until_ready(program.params)
    return program


def refresh_program(
    program: Any,
    src_params: Any,
    key: Array,
    *,
    mesh: Any = None,
    model_cfg: Optional[ModelConfig] = None,
    transforms: Optional[dict] = None,
):
    """Refresh policy: rewrite a drifted chip from the stored source weights.

    When serving accuracy degrades past the deployment's threshold (GDC only
    compensates the *mean* conductance decay, not the spread), the chip is
    reprogrammed in place: fresh write noise is drawn, the drift clock resets
    to the programming reference age t_c, and the refreshed chip serves the
    same configuration -- per-layer bitwidth overrides are recovered from the
    old program's quant plans, so refresh works for loaded artifacts too.
    """
    from repro.core import engine
    from repro.core import pcm as pcm_lib

    return program_for_serving(
        src_params,
        program.cfg,
        key,
        mesh=mesh,
        model_cfg=model_cfg,
        transforms=transforms,
        b_adc_overrides=engine.plan_bit_overrides(program) or None,
        t_seconds=pcm_lib.T_C,
        # a rewrite changes the devices' contents, not which chip they are
        chip_id=program.chip_id,
    )


def make_train_step(
    cfg: ModelConfig,
    analog_cfg: AnalogConfig,
    opt_cfg: optim_lib.OptimizerConfig,
    accum_steps: int = 1,
):
    """(params, opt_state, batch, rng) -> (params, opt_state, metrics).

    ``accum_steps > 1``: microbatch gradient accumulation via lax.scan --
    activation memory scales with batch/accum_steps while arithmetic and
    gradient traffic are unchanged. The standard fit-the-giant-model knob
    (llama4-maverick train_4k: 33 GiB -> HBM-feasible at accum 4).
    """

    def loss_for(p, batch, noise_rng):
        return lm_loss(p, batch, analog_cfg, cfg, rng=noise_rng)

    def train_step(params, opt_state, batch, rng):
        step_rng = jax.random.fold_in(rng, opt_state.step)
        noise_rng = step_rng if analog_cfg.needs_rng else None

        if accum_steps <= 1:
            (loss, metrics), grads = jax.value_and_grad(
                loss_for, has_aux=True
            )(params, batch, noise_rng)
        else:
            micro = jax.tree.map(
                lambda x: x.reshape(
                    (accum_steps, x.shape[0] // accum_steps) + x.shape[1:]
                ),
                batch,
            )

            def acc_body(carry, mb):
                g_acc, l_acc = carry
                (l, _), g = jax.value_and_grad(loss_for, has_aux=True)(
                    params, mb, noise_rng
                )
                g_acc = jax.tree.map(jnp.add, g_acc, g)
                return (g_acc, l_acc + l), None

            g0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params
            )
            (grads, loss_sum), _ = jax.lax.scan(
                acc_body, (g0, jnp.zeros((), jnp.float32)), micro
            )
            grads = jax.tree.map(lambda g: g / accum_steps, grads)
            loss = loss_sum / accum_steps
            metrics = {"loss": loss}

        params, opt_state, opt_metrics = optim_lib.update(
            opt_cfg, params, grads, opt_state
        )
        metrics = {**metrics, **opt_metrics}
        return params, opt_state, metrics

    return train_step


def make_prefill_step(cfg: ModelConfig, analog_cfg: AnalogConfig):
    """(params, batch, cache, rng) -> (next_token_logits, cache)."""

    def prefill_step(params, batch, cache, rng):
        noise_rng = rng if analog_cfg.needs_rng else None
        logits, cache = lm_forward(
            params,
            batch,
            analog_cfg,
            cfg,
            rng=noise_rng,
            cache=cache,
            last_token_only=True,
        )
        return logits, cache

    return prefill_step


def make_serve_step(cfg: ModelConfig, analog_cfg: AnalogConfig):
    """One decode step: (params, batch, cache, rng) -> (next_tokens, cache).

    ``batch`` holds the freshly sampled token(s) from the previous step
    (tokens: (B, 1); frames for the audio family). Greedy argmax sampling.
    """

    def serve_step(params, batch, cache, rng):
        noise_rng = rng if analog_cfg.needs_rng else None
        logits, cache = lm_forward(
            params, batch, analog_cfg, cfg, rng=noise_rng, cache=cache
        )
        next_tokens = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return next_tokens, cache

    return serve_step
