"""Driver: back-to-back inference batches through a programmed CNN.

The system under test is the program's programmed-CNN path, as
``benchmarks/pipeline_bench.py`` drives it: ``engine.compile_program``
with the model's ``crossbar_transforms`` (each conv kernel programmed as
its im2col crossbar block), aged to ``t_hours``, then a jitted
``cnn_apply`` on ``program.params``. In the window, batch after batch
from a pool of host batches (made from the seed before the window) is
sent to the device and its logits come back to the host.

``correct``: after the window, a sample drawn from the seed of the
answers returned in the window (at least the last batch's) is recomputed
by the plain reference (``reference/<reference>.py``: the chip rebuilt
from the seed); the largest distance between a returned logit and the
reference's must stay within the configuration's limit.
"""

from __future__ import annotations

import gc
import importlib
import time

import numpy as np

from benchmarks.chip import flops, gen, harness, weights

#: answers kept per batch for the check, drawn from the seed
KEEP_PER_BATCH = 16


def model_config(c: dict):
    from repro.models.analognet import CNNConfig, ConvSpec

    return CNNConfig(
        name=c["name"], input_hw=tuple(c["input_hw"]),
        in_channels=int(c["in_channels"]),
        convs=tuple(ConvSpec(v["name"], v["kh"], v["kw"], v["c_in"],
                             v["c_out"], v["stride"]) for v in c["convs"]),
        n_classes=int(c["n_classes"]), fc_width=int(c["fc_width"]),
    )


def run(ctx: harness.Context) -> dict:
    import jax

    from repro.core import engine
    from repro.core.analog import AnalogConfig
    from repro.models.analognet import cnn_apply, cnn_init, crossbar_transforms

    c = ctx.config
    mc = model_config(c)
    shapes = jax.eval_shape(lambda k: cnn_init(k, mc), jax.random.PRNGKey(0))
    params = weights.fill(shapes, ctx.seed)
    ctx.mark("weights")
    acfg = AnalogConfig().infer(
        b_adc=int(ctx.setting("b_adc")), t_seconds=float(c["t_hours"]) * 3600.0)
    t0 = time.perf_counter()
    program = engine.compile_program(
        params, acfg, weights.seed_key(ctx.seed, "program"),
        transforms=crossbar_transforms(mc))
    jax.block_until_ready(program.params)
    program_s = time.perf_counter() - t0
    del params
    served = program.params
    if "mvm_dtype" in ctx.overrides:
        served = weights.with_mvm_dtype(served, ctx.overrides["mvm_dtype"])
    ctx.mark("program")
    fwd = jax.jit(lambda p, x: cnn_apply(p, x, program.cfg, mc))
    pool = gen.batches(ctx.traffic, ctx.seed,
                       tuple(c["input_hw"]) + (int(c["in_channels"]),))
    batch = pool[0].shape[0]
    ctx.mark("inputs")
    np.asarray(fwd(served, jax.device_put(pool[0])))  # warm-up
    ctx.mark("warm-up")
    keep_rng = gen.rng_for(ctx.seed, "keep")

    kept = []  # (pool index, rows, logits of those rows)
    t_open = time.perf_counter()
    ctx.open_window(t_open)
    n_done, t_done = 0, t_open
    while True:
        now = time.perf_counter()
        ctx.tick(now)
        if now >= ctx.t_close:
            break
        i = n_done % len(pool)
        with ctx.span("kws_batch"):
            logits = np.asarray(fwd(served, jax.device_put(pool[i])))
        rows = keep_rng.choice(batch, KEEP_PER_BATCH, replace=False)
        kept.append((i, rows, logits[rows]))
        n_done += 1
        t_done = time.perf_counter()
    ctx.close_window()
    peak = harness.memory_peak_bytes()
    trace = ctx.read_trace()
    del program, served, fwd
    gc.collect()

    err = max_logit_err(ctx, kept, pool)
    limit = float(c["limits"]["max_logit_err"])
    n_inf = n_done * batch
    ctx.log(f"window: {n_done} batches of {batch}, {n_inf} inferences in "
            f"{t_done - t_open:.3f} s; checked {int(c['check_answers'])} answers")
    return {
        "correct": err <= limit,
        "attempted": n_inf,
        "failed": 0,
        "metrics": {"setup_s": ctx.setup_s,
                    "inferences_per_s": n_inf / (t_done - t_open)},
        "counters": {"program_s": program_s, "inferences": n_inf,
                     "flops": n_inf * flops.cnn_flops(c),
                     "window_s": t_done - t_open},
        "checks": {"max_logit_err": (err, limit)},
        "memory_peak_bytes": peak,
        "trace": trace,
    }


def max_logit_err(ctx: harness.Context, kept: list, pool: list) -> float:
    """Largest |returned logit - reference logit| over a sample of kept
    answers drawn from the seed (the last batch's always among them)."""
    import jax.numpy as jnp

    c = ctx.config
    ref = importlib.import_module(f"benchmarks.chip.reference.{c['reference']}")
    flat = [(b, j) for b in range(len(kept)) for j in range(len(kept[b][1]))]
    n = min(int(c["check_answers"]), len(flat))
    pick = gen.rng_for(ctx.seed, "sample").choice(len(flat), n, replace=False)
    chosen = sorted({flat[k] for k in pick} | {(len(kept) - 1, 0)})
    x = np.stack([pool[kept[b][0]][kept[b][1][j]] for b, j in chosen])
    got = np.stack([kept[b][2][j] for b, j in chosen])
    state = ref.program(c, ctx.seed, float(c["t_hours"]) * 3600.0)
    want = np.concatenate([
        np.asarray(ref.forward(state, jnp.asarray(x[k:k + 256]),
                               convs=ref.conv_dims(c), bits=int(c["b_adc"])))
        for k in range(0, len(x), 256)
    ])
    err = np.abs(got - want)
    ctx.log(f"errors: p50 {float(np.median(err))!r}, p99 "
            f"{float(np.quantile(err, 0.99))!r}, mean {float(err.mean())!r}")
    return float(err.max())
