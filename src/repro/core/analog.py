"""AnalogLinear / AnalogConv: analog-CiM-deployable layers (paper Sec. 3-4).

Every stationary-weight matmul in the framework goes through
:func:`analog_matmul`, a thin *plan dispatcher* over the program/execute
engine (:mod:`repro.core.engine`). Execution modes (``AnalogConfig.mode``):

  * ``digital``        -- plain matmul (FP baseline / fastest training).
  * ``analog_train``   -- the paper's HW-aware training graph (Fig. 4):
                           STE weight clip -> Gaussian noise injection (Eq. 1)
                           -> DAC fake-quant on inputs -> MVM -> per-crossbar-
                           tile ADC fake-quant on partial sums -> digital sum.
  * ``pcm_infer``      -- per-call deployment simulation: weights pass through
                           the calibrated PCM chain (program/drift/read noise,
                           pcm.py) on *every* forward call. Use this for
                           statistical accuracy sweeps where each call should
                           be an independent chip/noise draw.
  * ``pcm_programmed`` -- execute phase of a compiled
                           :class:`~repro.core.engine.CiMProgram`: weights in
                           the param tree are already PCM effective weights
                           (programmed ONCE by ``engine.compile_program``)
                           and each layer carries its GDC ``out_scale_buf``.
                           This is the serving path: no weight-domain work
                           per call, kernel-fusable GDC epilogue.

Program-once / execute-many lifecycle (matches the hardware, Sec. 5):

    program = engine.compile_program(params, AnalogConfig().infer(), key)
    logits = model_forward(program.params, batch, program.cfg, ...)  # many x
    aged = program.drift_to(30 * 86400.0)  # same chip, one month later

All modes share one execute hot path (``engine.execute_mvm``), which
dispatches between the fused Pallas kernel and the jnp reference according
to the layer's static :class:`~repro.core.engine.ExecutionPlan`.

Faithfulness note: when a layer's fan-in exceeds the physical array rows
(1024), the layer is split across row tiles and the hardware ADC-converts each
tile's bitline charge *before* digital accumulation. We reproduce that with
per-tile quantization -- it is the dominant quantization effect for LM-scale
layers (K = 4096..8192 spans 4..8 tiles).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import engine as engine_lib
from repro.core import noise as noise_lib
from repro.core import pcm as pcm_lib
from repro.core import quant as quant_lib
from repro.core.engine import PCM_PROGRAMMED
from repro.core.quant import QuantSpec

Array = jax.Array

DIGITAL = "digital"
ANALOG_TRAIN = "analog_train"
PCM_INFER = "pcm_infer"


@dataclasses.dataclass(frozen=True)
class AnalogConfig:
    """Static configuration of the analog execution environment."""

    mode: str = DIGITAL
    eta: float = 0.1  # training-noise level (Eq. 1); paper sweeps 2%..20%
    b_adc: int = 8  # ADC ENOB; DAC = b_adc + 1 (Eq. 3)
    quant_noise_p: float = 1.0  # Fan et al. stochastic-quant prob (0.5 in paper)
    per_tile_adc: bool = True
    tile_rows: int = 1024  # physical crossbar source lines
    tile_cols: int = 512  # physical crossbar bitlines (differential columns)
    t_seconds: float = 86400.0  # PCM evaluation time (24 h default, Table 1)
    pcm: pcm_lib.PCMConfig = dataclasses.field(default_factory=pcm_lib.PCMConfig)
    use_kernel: bool = False  # route the fused MVM through the Pallas kernel
    interpret: bool = False  # Pallas interpret mode (CPU validation)
    # pcm_programmed only: resample 1/f read noise per MVM call from stored
    # pre-read conductance buffers (pcm.read's "at MVM time" contract). The
    # program then carries per-layer read_bufs and forward calls take an RNG;
    # calls WITHOUT an RNG still execute the frozen (bit-exact) read draw.
    resample_read_noise: bool = False

    @property
    def spec(self) -> QuantSpec:
        return QuantSpec(b_adc=self.b_adc, quant_noise_p=self.quant_noise_p)

    @property
    def needs_rng(self) -> bool:
        """True for modes that draw fresh noise on every forward call.

        ``digital`` draws nothing; ``pcm_programmed`` executes a compiled
        CiMProgram whose noise is frozen in the programmed weights -- unless
        ``resample_read_noise`` asks for a fresh read draw per MVM.
        """
        if self.mode == PCM_PROGRAMMED:
            return self.resample_read_noise
        return self.mode in (ANALOG_TRAIN, PCM_INFER)

    def train(self, **kw) -> "AnalogConfig":
        return dataclasses.replace(self, mode=ANALOG_TRAIN, **kw)

    def infer(self, **kw) -> "AnalogConfig":
        return dataclasses.replace(self, mode=PCM_INFER, quant_noise_p=1.0, **kw)


@dataclasses.dataclass
class AnalogCtx:
    """Per-call (traced) context threaded through the model."""

    cfg: AnalogConfig
    gain_s: Array  # the single network-wide ADC gain S (Eq. 5)
    key: Optional[Array] = None  # base RNG for noise draws (None = no noise)
    layer_counter: int = 0  # folded into noise keys for uniqueness

    def next_key(self) -> Optional[Array]:
        if self.key is None:
            return None
        self.layer_counter += 1
        return jax.random.fold_in(self.key, self.layer_counter)


def analog_matmul(
    x: Array,
    w: Array,
    *,
    r_adc: Array,
    w_min: Array,
    w_max: Array,
    ctx: AnalogCtx,
    out_scale: Optional[Array] = None,
    b_adc: Optional[int] = None,
    read_buf: Optional[dict] = None,
) -> Array:
    """The framework-wide analog-aware matmul. x: (..., K), w: (K, N).

    A plan dispatcher: derives the layer's static ExecutionPlan (cached per
    (config, K, N, bits)) and routes every mode through the engine's unified
    execute phase. ``out_scale`` is the layer's GDC scalar in
    ``pcm_programmed`` mode (``None`` elsewhere, or for layers that were
    not part of the compiled program). ``b_adc`` overrides the config's ADC
    bitwidth for this layer (mixed-precision programs; the DAC keeps one
    extra bit via the plan's QuantSpec). ``read_buf`` is the layer's
    pre-read conductance buffer for per-MVM read-noise resampling
    (``pcm_programmed`` with ``cfg.resample_read_noise``; ignored without
    an RNG in the ctx so the default execute stays bit-exact).
    """
    cfg = ctx.cfg
    if cfg.mode == DIGITAL:
        return engine_lib.execute_digital(x, w)

    plan = engine_lib.plan_for(
        cfg, int(w.shape[-2]), int(w.shape[-1]), b_adc=b_adc
    )

    # fake-quant promotes to f32 (range params are f32); keep the analog
    # chain in f32 internally and restore the caller's dtype at the end
    out_dtype = x.dtype
    spec = plan.spec
    if cfg.mode == ANALOG_TRAIN:
        w_key = ctx.next_key()
        w_eff = noise_lib.inject(w_key, w, cfg.eta, w_min, w_max)
        qn_key_in = ctx.next_key() if spec.quant_noise_p < 1.0 else None
        qn_key_out = (
            ctx.next_key()
            if spec.quant_noise_p < 1.0 and not cfg.use_kernel
            else None
        )
        x_q = quant_lib.dac_quantize(
            x, r_adc, ctx.gain_s, w_max, spec, qn_key_in
        )
        # quantized activations/weights live on a <=2^b_dac-level grid:
        # exactly representable in bf16 -- keeping the inter-quantizer chain
        # in f32 doubles both HBM traffic and the FSDP weight-gather volume
        x_q = x_q.astype(out_dtype)
        return engine_lib.execute_mvm(
            x_q,
            w_eff.astype(x_q.dtype),
            r_adc,
            plan,
            qn_key=qn_key_out,
        ).astype(out_dtype)

    if cfg.mode == PCM_PROGRAMMED:
        # Execute phase: ``w`` already holds PCM effective weights from a
        # compiled CiMProgram; no per-call weight work. With a read_buf AND
        # an RNG, the frozen read draw is replaced by a fresh per-MVM draw
        # from the stored pre-read conductances (pcm.read semantics);
        # without an RNG the frozen weights execute bit-exactly as before.
        w_exec = w
        if read_buf is not None and cfg.resample_read_noise:
            r_key = ctx.next_key()
            if r_key is not None:
                w_exec = engine_lib.resample_read(r_key, read_buf).astype(
                    w.dtype
                )
        return engine_lib.execute_programmed(
            x, w_exec, r_adc, ctx.gain_s, w_max, plan,
            out_scale=1.0 if out_scale is None else out_scale,
        )

    if cfg.mode == PCM_INFER:
        w_key = ctx.next_key()
        if w_key is None:
            raise ValueError("pcm_infer requires an RNG key in the AnalogCtx")
        engine_lib.record_program_event()  # per-call reprogramming (legacy)
        w_c = jnp.clip(w, w_min, w_max)
        w_eff, gdc = pcm_lib.simulate_weights(
            w_key, w_c.astype(jnp.float32), cfg.t_seconds, cfg.pcm
        )
        return engine_lib.execute_programmed(
            x, w_eff, r_adc, ctx.gain_s, w_max, plan, out_scale=gdc
        )

    raise ValueError(f"unknown analog mode: {cfg.mode}")


# ---------------------------------------------------------------------------
# Layer wrappers (parameter containers). The framework's module system is
# functional: ``init`` returns a param pytree, ``apply`` consumes it.
# Buffers (non-trainable) use the ``_buf`` suffix; the optimizer masks them.
# ---------------------------------------------------------------------------


def linear_init(
    key: Array,
    d_in: int,
    d_out: int,
    *,
    use_bias: bool = False,
    dtype=jnp.float32,
    scale: float | None = None,
) -> dict:
    w_key, _ = jax.random.split(key)
    s = scale if scale is not None else d_in**-0.5
    params = {
        "w": (jax.random.normal(w_key, (d_in, d_out), jnp.float32) * s).astype(dtype),
        "r_adc": jnp.ones((), jnp.float32),
        "w_clip_buf": jnp.array([-1.0, 1.0], jnp.float32),  # set by stage-1
    }
    if use_bias:
        params["b"] = jnp.zeros((d_out,), dtype)
    return params


def linear_apply(params: dict, x: Array, ctx: AnalogCtx) -> Array:
    w_min = params["w_clip_buf"][..., 0]
    w_max = params["w_clip_buf"][..., 1]
    y = analog_matmul(
        x,
        params["w"],
        r_adc=params["r_adc"],
        w_min=w_min,
        w_max=w_max,
        ctx=ctx,
        out_scale=params.get("out_scale_buf"),
        b_adc=engine_lib.bits_of(params.get("b_adc_buf")),
        read_buf=params.get("read_buf"),
    )
    if "b" in params:
        # Bias is applied in the digital domain, after the ADC (paper Sec. 3.1).
        y = y + params["b"].astype(y.dtype)
    return y


def proj(params: dict, name: str, x: Array, ctx: AnalogCtx) -> Array:
    """:func:`linear_apply` of the layer ``params[name]`` under the named
    scope ``name``, so its device ops carry the projection's name."""
    with jax.named_scope(name):
        return linear_apply(params[name], x, ctx)


def refresh_clip_ranges(params: dict, n_std: float = 2.0) -> dict:
    """Stage-1 helper: recompute every layer's static clip range from std(W).

    Walks an arbitrary param pytree and updates each ``w_clip_buf`` from its
    sibling ``w``. Called every 10 steps in stage 1, then frozen for stage 2.
    """

    def walk(tree):
        if isinstance(tree, dict):
            new = {k: walk(v) for k, v in tree.items()}
            if "w" in new and "w_clip_buf" in new:
                w = new["w"]
                # Per-layer scalar ranges; for stacked (scanned) layers keep
                # one range per layer: reduce over all but the leading stack
                # axis if the buffer is stacked.
                buf = new["w_clip_buf"]
                if buf.ndim == 1:  # unstacked: shape (2,)
                    std = jnp.std(w)
                    new["w_clip_buf"] = jnp.stack([-n_std * std, n_std * std])
                else:  # stacked: shape (L, 2)
                    axes = tuple(range(1, w.ndim))
                    std = jnp.std(w, axis=axes)
                    new["w_clip_buf"] = jnp.stack(
                        [-n_std * std, n_std * std], axis=-1
                    )
            return new
        return tree

    return walk(params)
