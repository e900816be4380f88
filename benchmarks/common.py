"""Shared benchmark helpers: the scaled-down accuracy substrate.

The paper's accuracy experiments (Table 1, Fig. 7, Fig. 9) train full KWS/VWW
models for 100-200 epochs on Speech Commands / VWW. Offline on CPU we
reproduce the *protocol* on scaled models + the synthetic learnable tasks
(repro.data.pipeline), which preserves every mechanism under test: two-stage
training, noise injection, DAC/ADC ranges with shared S, PCM drift chain.
Absolute accuracies differ from the paper's; the *deltas and orderings* are
the reproduced quantities.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.analog import AnalogConfig
from repro.data.pipeline import PipelineConfig, batch_at, iterate
from repro.models.analognet import (
    CNNConfig,
    ConvSpec,
    cnn_apply,
    cnn_init,
    cnn_loss,
)
from repro.training.loop import TrainConfig, run_two_stage

# scaled AnalogNet-KWS-like model (dense 3x3 convs) and its depthwise twin
KWS_BENCH = CNNConfig(
    name="bench_kws_dense",
    input_hw=(16, 8),
    in_channels=1,
    convs=(
        ConvSpec("c1", 3, 3, 1, 16, 2),
        ConvSpec("c2", 3, 3, 16, 24, 2),
        ConvSpec("c3", 3, 3, 24, 24, 1),
    ),
    n_classes=8,
    fc_width=24,
)

KWS_BENCH_DW = CNNConfig(
    name="bench_kws_depthwise",
    input_hw=(16, 8),
    in_channels=1,
    convs=(
        ConvSpec("c1", 3, 3, 1, 16, 2),
        ConvSpec("dw2", 3, 3, 16, 16, 2, depthwise=True),
        ConvSpec("pw2", 1, 1, 16, 24, 1),
        ConvSpec("dw3", 3, 3, 24, 24, 1, depthwise=True),
        ConvSpec("pw3", 1, 1, 24, 24, 1),
    ),
    n_classes=8,
    fc_width=24,
)

VWW_BENCH = CNNConfig(
    name="bench_vww_dense",
    input_hw=(24, 24),
    in_channels=3,
    convs=(
        ConvSpec("stem", 3, 3, 3, 12, 2),
        ConvSpec("b1e", 3, 3, 12, 32, 2),
        ConvSpec("b1p", 1, 1, 32, 16, 1),
        ConvSpec("b2e", 3, 3, 16, 48, 2),
        ConvSpec("b2p", 1, 1, 48, 24, 1),
    ),
    n_classes=2,
    fc_width=24,
)

VWW_BENCH_BNECK = CNNConfig(
    name="bench_vww_bottleneck",
    input_hw=(24, 24),
    in_channels=3,
    convs=(
        ConvSpec("stem", 3, 3, 3, 12, 2),
        ConvSpec("bneck1", 1, 1, 12, 3, 1),  # the narrow layers the paper
        ConvSpec("bneck2", 3, 3, 3, 12, 1),  # removes (Fig. 3 right)
        ConvSpec("b1e", 3, 3, 12, 32, 2),
        ConvSpec("b1p", 1, 1, 32, 16, 1),
        ConvSpec("b2e", 3, 3, 16, 48, 2),
        ConvSpec("b2p", 1, 1, 48, 24, 1),
    ),
    n_classes=2,
    fc_width=24,
)


def pipe_for(cfg: CNNConfig, batch: int = 64) -> PipelineConfig:
    return PipelineConfig(
        kind="kws",
        global_batch=batch,
        n_classes=cfg.n_classes,
        input_hw=cfg.input_hw,
        channels=cfg.in_channels,
    )


def train_model(
    cfg: CNNConfig,
    *,
    stage1: int = 60,
    stage2: int = 60,
    eta: float = 0.1,
    b_adc: int = 8,
    quant_noise_p: float = 0.5,
    lr: float = 5e-3,
    seed: int = 0,
):
    pipe = pipe_for(cfg)

    def loss_fn(p, b, acfg, rng):
        return cnn_loss(p, b, acfg, cfg, rng=rng)

    params0 = cnn_init(jax.random.PRNGKey(seed), cfg)
    tcfg = TrainConfig(
        stage1_steps=stage1, stage2_steps=stage2, eta=eta, b_adc=b_adc,
        quant_noise_p=quant_noise_p, lr=lr, log_every=1_000_000,
    )
    params, _ = run_two_stage(loss_fn, params0, iterate(pipe), tcfg)
    return params


def _protocol_accuracy(params, cfg: CNNConfig, analog_cfg, rng, n_batches: int) -> float:
    """Mean accuracy over the shared eval protocol (fixed batches 50k+i)."""
    pipe = pipe_for(cfg)
    accs = []
    for i in range(n_batches):
        b = jax.tree.map(jnp.asarray, batch_at(pipe, 50_000 + i))
        logits = cnn_apply(
            params, b["x"], analog_cfg, cfg,
            rng=jax.random.fold_in(rng, i) if analog_cfg.needs_rng else None,
        )
        accs.append(float((logits.argmax(-1) == b["y"]).mean()))
    return float(np.mean(accs))


def eval_program_accuracy(program, cfg: CNNConfig, *, n_batches: int = 4) -> float:
    """Accuracy of one compiled chip (frozen conductances, no per-call RNG)."""
    return _protocol_accuracy(
        program.params, cfg, program.cfg, jax.random.PRNGKey(0), n_batches
    )


def eval_accuracy(
    params,
    cfg: CNNConfig,
    analog_cfg: AnalogConfig,
    *,
    n_batches: int = 4,
    n_draws: int = 3,
    seed: int = 123,
) -> tuple[float, float]:
    """(mean, std) accuracy over PCM noise draws (paper uses 25 runs).

    Each PCM draw programs one simulated chip via ``engine.compile_program``
    and evaluates every batch against those frozen conductances -- the
    paper's N-chips protocol and the deployment lifecycle. The 1/f read
    noise is frozen with them (one realization per chip, bit-exact
    executes); per-MVM read-noise resampling is the programmed engine's
    ``AnalogConfig(resample_read_noise=True)`` -- the legacy path that
    re-simulated the whole PCM chain inside every forward call is gone.
    Non-PCM configs (digital / analog_train) evaluate directly.
    """
    from repro.core import engine
    from repro.models.analognet import crossbar_transforms

    accs = []
    for d in range(n_draws):
        rng = jax.random.PRNGKey(seed + d)
        if analog_cfg.mode == "pcm_infer":
            program = engine.compile_program(
                params, analog_cfg, rng, transforms=crossbar_transforms(cfg)
            )
            accs.append(eval_program_accuracy(program, cfg, n_batches=n_batches))
        else:
            accs.append(
                _protocol_accuracy(params, cfg, analog_cfg, rng, n_batches)
            )
    return float(np.mean(accs)), float(np.std(accs))


def csv_row(name: str, us_per_call: float, derived: str) -> str:
    return f"{name},{us_per_call:.2f},{derived}"


def time_call(fn, *args, iters: int = 3) -> float:
    fn(*args)  # compile
    t0 = time.time()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.time() - t0) / iters * 1e6


def pallas_calls(fn, *args) -> list[dict]:
    """The params of every ``pallas_call`` one call of ``fn`` dispatches
    (``interpret``, ``grid_mapping``, ...), in jaxpr order.

    Walks the jaxpr, recursing into nested jaxprs (jit/scan/cond/...
    bodies). Backend-independent by design: it works in interpret mode
    too, where ``.lower().compile().cost_analysis()`` carries no
    kernel-launch stats -- the jaxpr is the dispatch plan either way.
    """

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn.params
            for v in eqn.params.values():
                for sub in v if isinstance(v, (list, tuple)) else (v,):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        yield from walk(inner)

    return list(walk(jax.make_jaxpr(fn)(*args).jaxpr))


def launch_count(fn, *args) -> int:
    """Number of Pallas kernel launches one call of ``fn`` dispatches: on a
    TPU one ``pallas_call`` equation is one device kernel launch per grid."""
    return len(pallas_calls(fn, *args))
