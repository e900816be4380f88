"""Program-once / execute-many engine for analog CiM inference.

On the real AON-CiM accelerator (paper Sec. 5) deployment is a two-phase
lifecycle:

  1. **Program phase** -- every layer's weights are written into the PCM
     crossbar exactly once. Programming (write) noise is drawn at that
     moment and is thereafter *frozen in the devices*; what changes over a
     deployment's lifetime is conductance drift and instantaneous read
     noise. The layer-serial mapper statically places every layer on the
     physical array before any inference runs.

  2. **Execute phase** -- inferences run against the programmed
     conductances: DAC -> crossbar MVM -> per-row-tile ADC -> digital
     accumulation -> GDC scaling. No weight-domain work happens per call.

:func:`compile_program` reproduces that lifecycle for an arbitrary param
pytree: it walks the tree once, applies the PCM programming chain to every
analog layer, derives a static :class:`ExecutionPlan` per layer (row-tile
split, column strips, kernel-vs-jnp selection, quant spec) from the crossbar
geometry, and returns a :class:`CiMProgram` whose ``params`` drop into the
model's normal ``apply`` functions. :meth:`CiMProgram.drift_to` re-evaluates
the *same* programmed conductances at a later wall-clock time -- drift and
read noise change, programming noise does not.

The execute phase is the single hot-path MVM entry (:func:`execute_mvm`)
shared by all ``AnalogConfig`` modes: ``analog_train`` feeds it
noise-injected weights, ``pcm_infer``/programmed inference feed it PCM
effective weights plus the GDC ``out_scale`` epilogue. With
``use_kernel=True`` it runs the fused Pallas kernel, which keeps per-tile
partial sums in VMEM instead of materializing the (..., T, N) tensor in HBM.
"""

from __future__ import annotations

import dataclasses
import fnmatch
import functools
import math
from typing import Any, Callable, Mapping as MappingT, Optional, Union

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from repro import clock as clock_lib
from repro import obs
from repro.core import pcm as pcm_lib
from repro.core import quant as quant_lib
from repro.core.crossbar import LayerShape, Mapping, map_layers
from repro.core.quant import QuantSpec

Array = jax.Array

#: AnalogConfig.mode for inference against a compiled CiMProgram: weights in
#: the params tree are already PCM effective weights and each layer carries
#: its ``out_scale_buf`` GDC scalar -- the execute phase does no weight work.
PCM_PROGRAMMED = "pcm_programmed"

# Trace-time programming counter. Incremented by every per-layer programming
# event (both compile_program and the legacy per-call pcm_infer path run it
# under Python control flow, so jit traces count once per layer per trace).
# Lets tests assert the program-once contract: after compile_program, an
# entire serving loop -- including its first traced step -- adds zero.
_PROGRAM_EVENTS = {"layers": 0}


def program_event_count() -> int:
    """Number of per-layer PCM programming events since process start."""
    return _PROGRAM_EVENTS["layers"]


def record_program_event() -> None:
    """Count one per-layer programming event (trace-time bookkeeping)."""
    _PROGRAM_EVENTS["layers"] += 1


# ---------------------------------------------------------------------------
# Execution plans (static, derived from crossbar geometry + AnalogConfig)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ExecutionPlan:
    """Static per-layer execution plan for the unified MVM hot path."""

    k: int  # fan-in (crossbar source lines spanned)
    n: int  # fan-out (bitlines spanned)
    tile_rows: int  # physical array rows -> row-tile split granularity
    tile_cols: int  # physical array cols -> column strips
    per_tile_adc: bool
    spec: QuantSpec
    use_kernel: bool
    interpret: bool

    @property
    def n_row_tiles(self) -> int:
        return max(1, math.ceil(self.k / self.tile_rows))

    @property
    def n_col_strips(self) -> int:
        return max(1, math.ceil(self.n / self.tile_cols))


@functools.lru_cache(maxsize=4096)
def plan_for(cfg, k: int, n: int, b_adc: Optional[int] = None) -> ExecutionPlan:
    """Derive (and cache) the static execution plan for a (K, N) layer.

    ``cfg`` is a (hashable, frozen) AnalogConfig; the plan is pure geometry
    + mode flags, so one cache entry serves every call of the same shape.

    ``b_adc`` overrides the config's ADC bitwidth for this layer (the DAC
    keeps ``b_adc + 1`` bits per Eq. 3 -- that relation lives in QuantSpec).
    Per-layer overrides are how mixed-precision programs execute: the layer
    carries its bitwidth (see :func:`b_adc_buf` / :func:`bits_of`) and every
    downstream consumer -- the jnp oracle, the fused kernel epilogue -- reads
    the bits from the plan's spec. Overrides are validated against the
    serving-supported set {4, 6, 8}; the default (``None``) keeps whatever
    the config says, including training-only widths like 16.
    """
    spec = cfg.spec
    if b_adc is not None and b_adc != spec.b_adc:
        quant_lib.validate_b_adc(b_adc, "per-layer b_adc override")
        spec = dataclasses.replace(spec, b_adc=int(b_adc))
    return ExecutionPlan(
        k=k,
        n=n,
        tile_rows=cfg.tile_rows,
        tile_cols=cfg.tile_cols,
        per_tile_adc=cfg.per_tile_adc,
        spec=spec,
        use_kernel=cfg.use_kernel,
        interpret=cfg.interpret,
    )


# ---------------------------------------------------------------------------
# Per-layer ADC bitwidths (mixed-precision serving)
#
# The execute phase runs under jit, where params leaves are tracers -- a
# bitwidth stored as an array *value* could not feed the kernel's static
# ``bits`` argument. The bitwidth is therefore encoded in a buffer's trailing
# SHAPE (shapes are static under tracing): a layer programmed at b_adc=4
# carries ``b_adc_buf`` with trailing dimension 4. Stack dims (scanned LM
# groups, MoE expert banks) are prepended so the buffer slices/scans in
# lockstep with the weights; every member of one stack shares one bitwidth.
# ---------------------------------------------------------------------------

#: dict/sequence of (layer-path pattern -> b_adc) accepted by
#: :func:`compile_program`; patterns use fnmatch syntax over '/'-joined
#: walk paths ("blocks/*/ffn/w1", "lm_head", ...).
BitOverrides = Union[MappingT[str, int], tuple]


def normalize_b_adc_overrides(overrides: Optional[BitOverrides]) -> tuple:
    """Normalize overrides to a ((pattern, bits), ...) tuple; validate bits."""
    if not overrides:
        return ()
    items = (
        tuple(overrides.items())
        if isinstance(overrides, MappingT)
        else tuple(tuple(it) for it in overrides)
    )
    for pat, bits in items:
        quant_lib.validate_b_adc(int(bits), f"b_adc override for {pat!r}")
    return tuple((str(p), int(b)) for p, b in items)


def resolve_b_adc(
    overrides: tuple, path: str, default: int
) -> int:
    """Bitwidth for ``path``: last matching override pattern wins."""
    bits = default
    for pat, b in overrides:
        if path == pat or fnmatch.fnmatchcase(path, pat):
            bits = b
    return bits


def b_adc_buf(stack: tuple, bits: int) -> Array:
    """Shape-encoded per-layer bitwidth buffer (values double as a record)."""
    return jnp.full(tuple(stack) + (int(bits),), int(bits), jnp.int8)


def bits_of(buf: Optional[Array]) -> Optional[int]:
    """Static bitwidth of a ``b_adc_buf`` leaf (or None when absent)."""
    return None if buf is None else int(buf.shape[-1])


# ---------------------------------------------------------------------------
# Execute phase: the one hot-path MVM used by all modes
# ---------------------------------------------------------------------------


def execute_digital(x: Array, w: Array) -> Array:
    """Digital baseline MVM (mode == "digital")."""
    return jnp.matmul(x, w.astype(x.dtype))


def tile_matmul_quant(
    x: Array,
    w: Array,
    r_adc: Array,
    spec: QuantSpec,
    tile_rows: int,
    per_tile_adc: bool,
    qn_key: Optional[Array],
    out_scale: Array | float = 1.0,
) -> Array:
    """jnp reference execute: per-row-tile ADC quant + digital accumulation.

    x: (..., K)  w: (K, N). Partial sums over each K-tile of ``tile_rows``
    rows are ADC-quantized independently (each physical tile has its own
    bitline ADCs sharing the same fixed gain), then summed digitally and
    scaled by ``out_scale`` (the GDC factor; 1.0 during training). This is
    the autodiff-able oracle; the fused Pallas kernel (kernels/ops) computes
    the same function without materializing the (..., T, N) partials in HBM.
    """
    k = w.shape[0]
    acc_dtype = jnp.float32
    # f32 operands mean f32 products: a TPU's default precision would round
    # them to bf16 on the MXU, which moves ADC codes (``HIGHEST`` is a no-op
    # for bf16 operands and on the CPU)
    hi = jax.lax.Precision.HIGHEST
    if not per_tile_adc or k <= tile_rows:
        with jax.named_scope("crossbar"):
            y = jnp.matmul(
                x, w, preferred_element_type=acc_dtype, precision=hi
            )
        with jax.named_scope("adc"):
            y = quant_lib.adc_quantize(y, r_adc, spec, qn_key)
        with jax.named_scope("gdc"):
            return (y * out_scale).astype(x.dtype)

    n_tiles = -(-k // tile_rows)
    pad = n_tiles * tile_rows - k
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
        w = jnp.pad(w, [(0, pad), (0, 0)])
    xt = x.reshape(x.shape[:-1] + (n_tiles, tile_rows))
    wt = w.reshape(n_tiles, tile_rows, w.shape[-1])
    # (..., T, rows) x (T, rows, N) -> (..., T, N): one MVM per physical tile.
    with jax.named_scope("crossbar"):
        y_tiles = jnp.einsum(
            "...tk,tkn->...tn", xt, wt, preferred_element_type=acc_dtype,
            precision=hi,
        )
    with jax.named_scope("adc"):
        y_tiles = quant_lib.adc_quantize(y_tiles, r_adc, spec, qn_key)
        # per-tile quantized partials are grid values: store at compute
        # dtype. Digital accumulation runs tile-serially (t=0..T-1),
        # matching both the hardware's layer-serial ADC readout order and
        # the fused kernel's VMEM accumulator -- float addition is
        # non-associative, so a tree-reduce here would put the oracle one
        # ulp off the kernel and break the kernel-vs-oracle bit-identity
        # the low-bit parity tests pin down.
        y_tiles = y_tiles.astype(x.dtype).astype(acc_dtype)
        y = y_tiles[..., 0, :]
        for t in range(1, n_tiles):
            y = y + y_tiles[..., t, :]
    with jax.named_scope("gdc"):
        return (y * out_scale).astype(x.dtype)


def execute_mvm(
    x_q: Array,
    w_eff: Array,
    r_adc: Array,
    plan: ExecutionPlan,
    *,
    out_scale: Array | float = 1.0,
    qn_key: Optional[Array] = None,
) -> Array:
    """Unified execute-phase MVM: pre-quantized inputs x effective weights.

    Dispatches to the fused Pallas kernel when the plan selects it (the
    kernel keeps per-tile partials in VMEM and fuses the GDC epilogue;
    quant-noise masking is a training-only jnp feature, so a qn_key forces
    the reference path), otherwise to the jnp reference.
    """
    if plan.use_kernel and qn_key is None:
        from repro.kernels import ops as kernel_ops

        # one kernel: the crossbar MVM with its ADC and GDC fused in
        with jax.named_scope("crossbar"):
            return kernel_ops.analog_mvm(
                x_q,
                w_eff,
                r_adc=jnp.abs(r_adc),
                out_scale=out_scale,
                bits=plan.spec.b_adc,
                tile_rows=plan.tile_rows,
                per_tile_adc=plan.per_tile_adc,
                interpret=plan.interpret,
            )
    return tile_matmul_quant(
        x_q,
        w_eff,
        r_adc,
        plan.spec,
        plan.tile_rows,
        plan.per_tile_adc,
        qn_key,
        out_scale,
    )


def execute_programmed(
    x: Array,
    w: Array,
    r_adc: Array,
    gain_s: Array,
    w_max: Array,
    plan: ExecutionPlan,
    out_scale: Array | float = 1.0,
) -> Array:
    """Execute phase of a deployed layer: DAC -> crossbar -> ADC -> GDC.

    The crossbar MVM runs in the dtype of the effective weights ``w`` --
    f32 in every program, as analog weights are f32 masters -- whatever the
    model's activation dtype: the DAC levels are cast to it, and only the
    result is cast back to ``x.dtype``. Per-call (``pcm_infer``) and
    programmed layers (``analog.analog_matmul``) and the fused decode grid
    all execute through here.
    """
    with jax.named_scope("dac"):
        x_q = quant_lib.dac_quantize(x, r_adc, gain_s, w_max, plan.spec, None)
    return execute_mvm(
        x_q.astype(w.dtype),
        w,
        r_adc,
        plan,
        out_scale=out_scale,
    ).astype(x.dtype)


# ---------------------------------------------------------------------------
# Program phase: PCM chain applied once, drift re-evaluable
# ---------------------------------------------------------------------------


def _program_2d(key: Array, w: Array, w_min, w_max, cfg: pcm_lib.PCMConfig):
    """Program one weight block into PCM state (write noise drawn HERE).

    Returns the per-block programming state: programmed differential
    conductance fractions, the read-noise Q factors (functions of the
    programming *targets*), the GDC numerator, the weight scale, and the
    layer key from which drift/read draws are deterministically derived.
    """
    w_c = jnp.clip(w, w_min, w_max).astype(jnp.float32)
    g_pos_t, g_neg_t, w_scale = pcm_lib.weights_to_conductances(w_c)
    k_pp, k_pn = jax.random.split(key)
    return {
        "g_pos": pcm_lib.program(k_pp, g_pos_t, cfg),
        "g_neg": pcm_lib.program(k_pn, g_neg_t, cfg),
        "q_pos": pcm_lib.read_noise_q(g_pos_t),
        "q_neg": pcm_lib.read_noise_q(g_neg_t),
        # det_sum: bit-identical under any sharding -- a chip programmed
        # under pjit is the same chip a single host would have programmed.
        "gt_sum": pcm_lib.det_sum(g_pos_t + g_neg_t),
        "w_scale": w_scale,
        "key": key,
    }


def _drift_read_2d(state: dict, t: Array, cfg: pcm_lib.PCMConfig):
    """Re-evaluate programmed conductances at time ``t`` -> (w_eff, gdc).

    Per-device drift exponents and read-noise draws derive deterministically
    from the stored layer key: two evaluations of the same program at the
    same ``t`` are bit-identical, and moving ``t`` changes only the drift /
    read-noise processes -- never the programming noise.
    """
    k_dp, k_dn, k_rp, k_rn = jax.random.split(state["key"], 4)
    g_pos, g_neg = state["g_pos"], state["g_neg"]
    if cfg.drift:
        nu_p = pcm_lib.sample_drift_nu(k_dp, g_pos.shape, cfg)
        nu_n = pcm_lib.sample_drift_nu(k_dn, g_neg.shape, cfg)
        g_pos = g_pos * pcm_lib.drift_factor(nu_p, t)
        g_neg = g_neg * pcm_lib.drift_factor(nu_n, t)
    if cfg.gdc:
        # det_sum keeps the GDC scalar bit-identical across mesh shapes, so
        # every replica of a serving fleet applies the same digital factor.
        gdc = state["gt_sum"] / (pcm_lib.det_sum(g_pos + g_neg) + 1e-12)
    else:
        gdc = jnp.ones((), jnp.float32)
    if cfg.read_noise:
        scale_t = pcm_lib.read_noise_scale(t)
        g_pos = jnp.maximum(
            g_pos
            + g_pos * state["q_pos"] * scale_t
            * jax.random.normal(k_rp, g_pos.shape, jnp.float32),
            0.0,
        )
        g_neg = jnp.maximum(
            g_neg
            + g_neg * state["q_neg"] * scale_t
            * jax.random.normal(k_rn, g_neg.shape, jnp.float32),
            0.0,
        )
    w_eff = (g_pos - g_neg) * state["w_scale"]
    return w_eff, gdc


def _read_buffers_2d(state: dict, t: Array, cfg: pcm_lib.PCMConfig) -> dict:
    """Pre-read execute-time buffers for per-MVM read-noise resampling.

    ``pcm.read``'s contract is "read noise is sampled at MVM time", but the
    frozen ``w_eff`` of a compiled program necessarily bakes ONE read draw in
    (required for bit-exact executes). This returns what the execute phase
    needs to honour the per-MVM contract instead: the drifted conductances
    *before* any read draw, plus the per-device read-noise sigmas at time
    ``t`` (sigma = G_D * Q * sqrt(log((t+t_r)/t_r))), and the weight scale.
    Drift exponents derive from the stored layer key exactly as in
    :func:`_drift_read_2d`, so these buffers describe the same chip.
    """
    k_dp, k_dn, _, _ = jax.random.split(state["key"], 4)
    g_pos, g_neg = state["g_pos"], state["g_neg"]
    if cfg.drift:
        nu_p = pcm_lib.sample_drift_nu(k_dp, g_pos.shape, cfg)
        nu_n = pcm_lib.sample_drift_nu(k_dn, g_neg.shape, cfg)
        g_pos = g_pos * pcm_lib.drift_factor(nu_p, t)
        g_neg = g_neg * pcm_lib.drift_factor(nu_n, t)
    if cfg.read_noise:
        scale_t = pcm_lib.read_noise_scale(t)
        sigma_pos = g_pos * state["q_pos"] * scale_t
        sigma_neg = g_neg * state["q_neg"] * scale_t
    else:
        sigma_pos = jnp.zeros_like(g_pos)
        sigma_neg = jnp.zeros_like(g_neg)
    return {
        "g_pos": g_pos,
        "g_neg": g_neg,
        "sigma_pos": sigma_pos,
        "sigma_neg": sigma_neg,
        "w_scale": state["w_scale"],
    }


def resample_read(key: Array, buf: dict) -> Array:
    """One fresh per-MVM read-noise draw -> effective weights.

    ``buf`` is the per-layer ``read_buf`` built by :func:`read_buffers`
    (possibly with leading stack dims). Matches ``pcm.read``: G ~ N(G_D,
    sigma), clipped at zero, mapped back to weight units.
    """
    k_p, k_n = jax.random.split(key)
    g_pos = jnp.maximum(
        buf["g_pos"]
        + buf["sigma_pos"]
        * jax.random.normal(k_p, buf["g_pos"].shape, jnp.float32),
        0.0,
    )
    g_neg = jnp.maximum(
        buf["g_neg"]
        + buf["sigma_neg"]
        * jax.random.normal(k_n, buf["g_neg"].shape, jnp.float32),
        0.0,
    )
    w_scale = buf["w_scale"]
    w_scale = w_scale.reshape(w_scale.shape + (1, 1))
    return (g_pos - g_neg) * w_scale


def _stacked(fn: Callable, n_stack_dims: int) -> Callable:
    """vmap ``fn`` over ``n_stack_dims`` leading axes of every argument."""
    for _ in range(n_stack_dims):
        fn = jax.vmap(fn)
    return fn


# ---------------------------------------------------------------------------
# Jitted program/drift cores (sharding-aware, bit-stable)
#
# Both phases run through cached jit wrappers so the numerics are pinned to
# ONE compiled computation per (pcm config, stack depth, sharding): the
# program path and every later drift_to of the same chip hit the same code,
# which together with det_sum and the sharding-invariant RNG makes a chip
# programmed on an N-device mesh bit-identical to the host-programmed chip.
# ---------------------------------------------------------------------------


def _full_spec(sharding: NamedSharding, ndim: int) -> tuple:
    """Pad a (possibly prefix) PartitionSpec to full rank."""
    spec = tuple(sharding.spec) + (None,) * (ndim - len(sharding.spec))
    return spec


def state_shardings(
    w_sharding: NamedSharding, n_stack_dims: int
) -> dict[str, NamedSharding]:
    """Shardings for a programmed-layer state, inherited from the weight.

    The conductance pairs and Q factors are elementwise images of the weight
    block, so they carry the weight's spec verbatim; the per-stack-member
    scalars (``gt_sum``, ``w_scale``) keep only the stack part of the spec,
    and the per-member RNG keys get a trailing unsharded key axis.
    """
    mesh = w_sharding.mesh
    spec = _full_spec(w_sharding, n_stack_dims + 2)
    full = NamedSharding(mesh, PartitionSpec(*spec))
    stack = NamedSharding(mesh, PartitionSpec(*spec[:n_stack_dims]))
    key_sh = NamedSharding(
        mesh, PartitionSpec(*spec[:n_stack_dims], None)
    )
    return {
        "g_pos": full,
        "g_neg": full,
        "q_pos": full,
        "q_neg": full,
        "gt_sum": stack,
        "w_scale": stack,
        "key": key_sh,
    }


@functools.lru_cache(maxsize=512)
def _jitted_program(
    cfg: pcm_lib.PCMConfig,
    n_stack_dims: int,
    w_sharding: Optional[NamedSharding],
):
    fn = _stacked(
        lambda k_, w_, lo, hi: _program_2d(k_, w_, lo, hi, cfg),
        n_stack_dims,
    )
    if w_sharding is None:
        return jax.jit(fn)
    return jax.jit(
        fn, out_shardings=state_shardings(w_sharding, n_stack_dims)
    )


@functools.lru_cache(maxsize=512)
def _jitted_drift(
    cfg: pcm_lib.PCMConfig,
    n_stack_dims: int,
    w_sharding: Optional[NamedSharding],
):
    def fn(state, t):
        return _stacked(lambda s: _drift_read_2d(s, t, cfg), n_stack_dims)(
            state
        )

    if w_sharding is None:
        return jax.jit(fn)
    mesh = w_sharding.mesh
    spec = _full_spec(w_sharding, n_stack_dims + 2)
    return jax.jit(
        fn,
        out_shardings=(
            NamedSharding(mesh, PartitionSpec(*spec)),
            NamedSharding(mesh, PartitionSpec(*spec[:n_stack_dims])),
        ),
    )


@functools.lru_cache(maxsize=512)
def _jitted_read_buffers(
    cfg: pcm_lib.PCMConfig,
    n_stack_dims: int,
    w_sharding: Optional[NamedSharding],
):
    def fn(state, t):
        return _stacked(lambda s: _read_buffers_2d(s, t, cfg), n_stack_dims)(
            state
        )

    if w_sharding is None:
        return jax.jit(fn)
    mesh = w_sharding.mesh
    spec = _full_spec(w_sharding, n_stack_dims + 2)
    full = NamedSharding(mesh, PartitionSpec(*spec))
    stack = NamedSharding(mesh, PartitionSpec(*spec[:n_stack_dims]))
    return jax.jit(
        fn,
        out_shardings={
            "g_pos": full,
            "g_neg": full,
            "sigma_pos": full,
            "sigma_neg": full,
            "w_scale": stack,
        },
    )


def read_buffers(
    state: dict,
    t_seconds,
    cfg: pcm_lib.PCMConfig,
    *,
    n_stack_dims: int,
    sharding: Optional[NamedSharding] = None,
) -> dict:
    """Per-MVM read-noise buffers of a programmed state at ``t_seconds``.

    Jitted and sharding-preserving like :func:`drift_state`; see
    :func:`_read_buffers_2d` for contents and :func:`resample_read` for use.
    """
    t = jnp.asarray(t_seconds, jnp.float32)
    return _jitted_read_buffers(cfg, n_stack_dims, sharding)(state, t)


def program_weight(
    key: Array,
    w: Array,
    w_min: Array,
    w_max: Array,
    t_seconds,
    cfg: pcm_lib.PCMConfig,
    *,
    sharding: Optional[NamedSharding] = None,
):
    """Program a (stack..., K, N) weight tensor once; evaluate at t_seconds.

    Leading axes beyond the trailing (K, N) matrix are treated as stacked
    independent layers (scanned LM groups, MoE expert banks): each stack
    member gets its own write-noise draw, weight scale, and GDC scalar.
    Returns (w_eff, out_scale, state).

    With ``sharding`` (the weight's NamedSharding) the PCM state is created
    under jit with shardings inherited from the weight -- no host-side
    materialization -- and is bit-identical to the host-programmed state.
    """
    record_program_event()
    stack = w.shape[:-2]
    w_min_b = jnp.broadcast_to(jnp.asarray(w_min, jnp.float32), stack)
    w_max_b = jnp.broadcast_to(jnp.asarray(w_max, jnp.float32), stack)
    n_members = math.prod(stack) if stack else 1
    keys = jax.random.split(key, n_members).reshape(stack + (-1,))

    state = _jitted_program(cfg, len(stack), sharding)(
        keys, w, w_min_b, w_max_b
    )
    with obs.span("program.age", clock_lib.SYSTEM.now):
        w_eff, out_scale = drift_state(
            state, t_seconds, cfg, n_stack_dims=len(stack), sharding=sharding
        )
    return w_eff, out_scale, state


def drift_state(
    state: dict,
    t_seconds,
    cfg: pcm_lib.PCMConfig,
    *,
    n_stack_dims: int,
    sharding: Optional[NamedSharding] = None,
):
    """(w_eff, out_scale) of a programmed state re-evaluated at t_seconds.

    Runs as a jitted, sharding-preserving update: the conductances stay
    sharded on whatever mesh holds them (``sharding`` pins the effective
    weights back to the serving layout) and never gather to host.
    """
    t = jnp.asarray(t_seconds, jnp.float32)
    return _jitted_drift(cfg, n_stack_dims, sharding)(state, t)


def _layer_sharding(leaf) -> Optional[NamedSharding]:
    """The NamedSharding committed on an array, if any."""
    sh = getattr(leaf, "sharding", None)
    return sh if isinstance(sh, NamedSharding) else None


# ---------------------------------------------------------------------------
# Param-tree walk: find analog layers, program them, rebuild the tree
# ---------------------------------------------------------------------------


def _is_linear_layer(node: dict) -> bool:
    return (
        isinstance(node.get("w"), (jax.Array, jnp.ndarray))
        and "r_adc" in node
        and "w_clip_buf" in node
    )


def _is_expert_bank(node: dict) -> bool:
    """MoE expert banks: raw (E, K, N) arrays w1/w3/w2 sharing per-family
    r_adc (..., 3) and w_clip_buf (..., 3, 2) -- see models/moe.py."""
    return (
        all(
            isinstance(node.get(k), (jax.Array, jnp.ndarray))
            for k in ("w1", "w3", "w2")
        )
        and "r_adc" in node
        and "w_clip_buf" in node
        and "w" not in node
    )


_MOE_FAMILIES = ("w1", "w3", "w2")  # row order of r_adc / w_clip_buf


#: expert-bank keys consumed by the bank programming itself; sibling entries
#: (e.g. the MoE dict's "shared" expert linear layers, the digital router)
#: must still be walked.
_BANK_KEYS = frozenset(_MOE_FAMILIES) | {
    "r_adc", "w_clip_buf", "out_scale_buf", "b_adc_buf", "read_buf"
}


def _walk(tree: Any, fn: Callable[[str, dict], dict], path: str = "") -> Any:
    """Rebuild ``tree``, applying ``fn(path, node)`` to analog-layer dicts."""
    if isinstance(tree, dict):
        if _is_linear_layer(tree):
            return fn(path, tree)
        if _is_expert_bank(tree):
            new = fn(path, tree)
            for k, v in tree.items():
                if k not in _BANK_KEYS:
                    new[k] = _walk(v, fn, f"{path}/{k}" if path else k)
            return new
        return {
            k: _walk(v, fn, f"{path}/{k}" if path else k)
            for k, v in tree.items()
        }
    if hasattr(tree, "_fields"):  # NamedTuple (LMParams)
        return type(tree)(
            *(
                _walk(getattr(tree, f), fn, f"{path}/{f}" if path else f)
                for f in tree._fields
            )
        )
    if isinstance(tree, (tuple, list)):
        out = [
            _walk(v, fn, f"{path}/{i}" if path else str(i))
            for i, v in enumerate(tree)
        ]
        return type(tree)(out) if isinstance(tree, tuple) else out
    return tree


# ---------------------------------------------------------------------------
# Drift lifecycle: schedules of chip ages + the aging entry point
#
# A deployed chip is programmed once and then *ages in place*: drift and read
# noise evolve on a log-time scale while the programmed state stays frozen.
# DriftSchedule captures the sequence of wall-clock ages a serving deployment
# re-evaluates the chip at (paper Fig. 7: 25s -> 1h -> 1d -> 1mo -> 1y);
# age_program advances ONE CiMProgram along it without any reprogramming,
# recording the trajectory in the program's age_history.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class DriftSchedule:
    """A monotone sequence of chip ages (seconds) to serve a program at."""

    times: tuple[float, ...]

    def __post_init__(self):
        ts = tuple(float(t) for t in self.times)
        if not ts:
            raise ValueError("DriftSchedule needs at least one age")
        if not all(math.isfinite(t) for t in ts):
            # NaN compares False everywhere, so it would sail through the
            # ordering and t_c checks and poison the whole PCM chain
            raise ValueError(f"DriftSchedule ages must be finite: {ts}")
        if any(b <= a for a, b in zip(ts, ts[1:])):
            raise ValueError(
                f"DriftSchedule ages must be strictly increasing: {ts}"
            )
        if ts[0] < pcm_lib.T_C:
            # the drift law (t/t_c)^-nu is defined from the programming
            # reference age onward; ages below it would be silently clamped
            # (identical chips under different labels) or, for t <= 0, feed
            # NaNs into the read-noise scale
            raise ValueError(
                f"DriftSchedule ages must be >= t_c = {pcm_lib.T_C}s (the "
                f"drift law's programming reference age): {ts}"
            )
        object.__setattr__(self, "times", ts)

    def __iter__(self):
        return iter(self.times)

    def __len__(self) -> int:
        return len(self.times)

    @property
    def labels(self) -> tuple[str, ...]:
        return tuple(pcm_lib.format_age(t) for t in self.times)

    @classmethod
    def fig7(cls) -> "DriftSchedule":
        """The paper's Fig. 7 ages: 25s, 1h, 1d, 1mo, 1y."""
        return cls(tuple(pcm_lib.FIG7_TIMES.values()))

    @classmethod
    def log_spaced(cls, t_start: float, t_end: float, n: int) -> "DriftSchedule":
        """``n`` log-spaced ages in [max(t_start, t_c), t_end]."""
        return cls(pcm_lib.log_spaced_times(t_start, t_end, n))

    @classmethod
    def parse(cls, text: str) -> "DriftSchedule":
        """Parse a CLI schedule: 'fig7' or a comma list of seconds.

        ``'25,3600,86400'`` -> ages 25s, 1h, 1d.
        """
        text = text.strip()
        if text.lower() == "fig7":
            return cls.fig7()
        try:
            times = tuple(float(x) for x in text.split(",") if x.strip())
        except ValueError as e:
            raise ValueError(
                f"bad drift schedule {text!r}: want 'fig7' or a comma "
                "list of seconds, e.g. '25,3600,86400'"
            ) from e
        return cls(times)


def plan_bit_overrides(program: "CiMProgram") -> dict[str, int]:
    """Recover the per-layer ``b_adc_overrides`` a program was compiled with.

    Reprogramming a chip (the serve-time refresh policy) must reproduce the
    same mixed-precision configuration, but a loaded artifact only carries
    the resulting per-layer plans. Bitwidths are read back from the plans:
    exact layer paths for linear layers, plus the parent (bank) path for MoE
    expert-bank families -- bank nodes match overrides by the *bank* path
    while their plans are stored per family (``.../w1`` etc.). The extra
    parent patterns are harmless for non-bank parents: plain dict parents
    are never themselves walked as analog nodes.
    """
    default = program.cfg.b_adc
    out = {
        p: plan.spec.b_adc
        for p, plan in program.plans.items()
        if plan.spec.b_adc != default
    }
    for p, bits in list(out.items()):
        head, _, fam = p.rpartition("/")
        if head and fam in _MOE_FAMILIES and head not in program.plans:
            if all(out.get(f"{head}/{f}") == bits for f in _MOE_FAMILIES):
                out[head] = bits
    return out


def device_age(t_wall: float, refresh_wall: Optional[float]) -> float:
    """Device age of a chip at wall (deployment) age ``t_wall``.

    ``refresh_wall`` is the wall age the chip was last rewritten at (None =
    never refreshed). A rewritten chip is YOUNGER than the deployment: its
    drift clock restarted at the refresh, so its device age is ``t_wall -
    refresh_wall``, floored at the programming reference age t_c (below
    which the drift law is undefined). Shared by every refresh-policy
    consumer (serve.py's drift loop, serving.DriftPolicy) so the wall-vs-
    device arithmetic cannot diverge between paths.
    """
    if refresh_wall is None:
        return float(t_wall)
    return max(float(t_wall) - float(refresh_wall), pcm_lib.T_C)


def age_program(program: "CiMProgram", t_seconds: float) -> "CiMProgram":
    """Advance a programmed chip to age ``t_seconds`` -- never reprograms.

    The drift-lifecycle entry point: re-evaluates the same programmed
    conductances via the jitted, sharding-preserving :meth:`CiMProgram.
    drift_to` (programming noise, per-layer ``b_adc_buf`` bitwidths, and --
    when compiled with ``resample_read_noise`` -- the ``read_buf`` contract
    all stay coherent) and appends the new age to the program's
    ``age_history`` so a saved artifact remembers its drift trajectory.
    Guarded by the trace-time programming counter: aging a chip must add
    zero programming events.
    """
    before = program_event_count()
    aged = program.drift_to(t_seconds)
    after = program_event_count()
    if after != before:
        raise RuntimeError(
            f"age_program reprogrammed the chip ({after - before} "
            "programming events during drift_to) -- drift must only "
            "re-evaluate the frozen devices"
        )
    return dataclasses.replace(
        aged, age_history=program.age_history + (float(t_seconds),)
    )


# ---------------------------------------------------------------------------
# CiMProgram
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class CiMProgram:
    """A compiled analog deployment: programmed params + static plans.

    ``params`` is structurally identical to the source param tree, with
    every analog layer's weights replaced by PCM effective weights and an
    ``out_scale_buf`` GDC scalar added -- it drops straight into the model's
    ``apply``/``forward`` functions together with ``cfg`` (whose mode is
    :data:`PCM_PROGRAMMED`). ``state`` holds the frozen programming state so
    :meth:`drift_to` can re-evaluate the same devices at a later time.
    """

    params: Any
    cfg: Any  # AnalogConfig with mode == PCM_PROGRAMMED
    t_seconds: float
    state: dict[str, Any]
    plans: dict[str, ExecutionPlan]
    mapping: Optional[Mapping] = None
    #: drift trajectory: every age this chip has been evaluated at, starting
    #: with the programming-time evaluation. :func:`age_program` appends;
    #: the artifact stores it (optional ``age_history`` meta, v1-compatible)
    #: so a reloaded chip knows how it was aged. ``drift_to`` itself is a
    #: stateless primitive and does not record.
    age_history: tuple[float, ...] = ()
    #: fleet identity: which physical chip this program is (None for a
    #: solo chip). A fleet compiles N draws with ``chip_id=0..N-1`` so
    #: routing, refresh events, and the artifact can name the chip; the
    #: id rides through :func:`age_program`/``drift_to`` (dataclasses.
    #: replace) and the v1 artifact (optional meta, like ``age_history``).
    chip_id: Optional[int] = None

    @property
    def n_layers(self) -> int:
        return len(self.plans)

    def drift_to(self, t_seconds: float) -> "CiMProgram":
        """Same programmed conductances, re-evaluated at ``t_seconds``.

        Only drift and read noise change; programming noise (and therefore
        the underlying device state) is identical to the original program.
        The per-layer update runs jitted and sharding-preserving: a sharded
        program advances chip time without gathering conductances to host
        (effective weights land back on each weight's serving sharding).
        """
        pcm_cfg = self.cfg.pcm

        def reprogram(path: str, node: dict) -> dict:
            st = self.state[path]
            new = dict(node)
            if "w" in node:
                sharding = _layer_sharding(node["w"])
                n_stack = st["g_pos"].ndim - 2
                w_eff, gdc = drift_state(
                    st, t_seconds, pcm_cfg,
                    n_stack_dims=n_stack, sharding=sharding,
                )
                new["w"] = w_eff.astype(node["w"].dtype)
                new["out_scale_buf"] = gdc
                if "read_buf" in node:
                    new["read_buf"] = read_buffers(
                        st, t_seconds, pcm_cfg,
                        n_stack_dims=n_stack, sharding=sharding,
                    )
            else:
                scales, read_bufs = [], {}
                for fam in _MOE_FAMILIES:
                    sharding = _layer_sharding(node[fam])
                    n_stack = st[fam]["g_pos"].ndim - 2
                    w_eff, gdc = drift_state(
                        st[fam], t_seconds, pcm_cfg,
                        n_stack_dims=n_stack, sharding=sharding,
                    )
                    new[fam] = w_eff.astype(node[fam].dtype)
                    scales.append(gdc)
                    if "read_buf" in node:
                        read_bufs[fam] = read_buffers(
                            st[fam], t_seconds, pcm_cfg,
                            n_stack_dims=n_stack, sharding=sharding,
                        )
                if read_bufs:
                    new["read_buf"] = read_bufs
                new["out_scale_buf"] = jnp.stack(scales, axis=-2)
            return new

        return dataclasses.replace(
            self,
            params=_walk(self.params, reprogram),
            t_seconds=float(t_seconds),
        )


# ---------------------------------------------------------------------------
# Fused decode plan (layer-serial megakernel lowering)
# ---------------------------------------------------------------------------

#: Projection walk-path order of one attention period group, matching the
#: execution (and AnalogCtx key-counter) order of ``lm._block_apply``:
#: wq/wk/wv are issued by attn_apply, wo closes it, then the FFN triple.
FUSED_PROJS = (
    "attn/wq", "attn/wk", "attn/wv", "attn/wo",
    "ffn/w1", "ffn/w3", "ffn/w2",
)


@dataclasses.dataclass(frozen=True)
class FusedDecodePlan:
    """Static lowering of a whole programmed decode step to ONE grid.

    The paper's AON-CiM accelerator is layer-SERIAL: the entire network
    walks one physical datapath. This plan mirrors that on the digital
    side -- the per-layer :class:`ExecutionPlan` table is collapsed into
    per-projection plans (every stacked group shares one plan per
    projection, so per-layer ``b_adc`` overrides resolve *statically* per
    grid step) plus the lm_head plan. ``kernels/decode_fused.py`` executes
    it as a single Pallas grid of ``n_groups + 1`` steps.
    """

    n_groups: int
    #: one ExecutionPlan per projection, in :data:`FUSED_PROJS` order
    proj_plans: tuple
    head_plan: ExecutionPlan
    interpret: bool


def build_fused_plan(program: "CiMProgram") -> FusedDecodePlan:
    """Lower a compiled program's per-layer plans into one FusedDecodePlan.

    Raises ``ValueError`` when the program cannot be statically fused:
    anything beyond stacked attention+FFN period groups and an lm_head
    (tail layers, MoE expert banks, recurrent state, biased projections)
    has no place in the layer-serial grid walk.
    """
    cfg = program.cfg
    if cfg.use_kernel:
        raise ValueError(
            "fused decode replaces the per-layer kernel dispatch; serve "
            "the program with use_kernel=False"
        )
    required = tuple(f"blocks/0/{p}" for p in FUSED_PROJS) + ("lm_head",)
    have = set(program.plans)
    extras = {p for p in have if p.startswith("extras/")}
    missing = sorted(set(required) - have)
    unfusable = sorted(have - set(required) - extras)
    if missing or unfusable:
        raise ValueError(
            "program's per-layer plans cannot be statically fused into "
            f"one decode grid: missing={missing} unfusable={unfusable} "
            "(fused decode supports stacked attention+FFN blocks plus an "
            "lm_head -- no tail layers, MoE banks, or recurrent state)"
        )
    blocks = getattr(program.params, "blocks", None)
    head = getattr(program.params, "lm_head", None)
    if not blocks or head is None:
        raise ValueError(
            "fused decode needs LM params with stacked period blocks and "
            "an lm_head"
        )
    block = blocks[0]
    for path in FUSED_PROJS:
        kind, name = path.split("/")
        pp = block[kind][name]
        if "b" in pp:
            raise ValueError(
                f"blocks/0/{path} carries a bias; the fused decode grid "
                "executes bias-free projections only (qkv_bias "
                "architectures are unsupported)"
            )
        if "out_scale_buf" not in pp:
            raise ValueError(
                f"blocks/0/{path} has no GDC out_scale_buf -- not a "
                "compiled program?"
            )
    if "out_scale_buf" not in head:
        raise ValueError("lm_head has no GDC out_scale_buf -- not a "
                         "compiled program?")

    def _plan(path: str) -> ExecutionPlan:
        # re-derive from the program's cfg so post-load flag flips
        # (interpret, ...) never leak in; the stored per-layer bitwidth is
        # what resolves statically per grid step
        p = program.plans[path]
        return plan_for(cfg, p.k, p.n, b_adc=p.spec.b_adc)

    return FusedDecodePlan(
        n_groups=int(block["attn"]["wq"]["w"].shape[0]),
        proj_plans=tuple(_plan(f"blocks/0/{p}") for p in FUSED_PROJS),
        head_plan=_plan("lm_head"),
        interpret=jax.default_backend() != "tpu",
    )


def sharding_lookup(shardings: Any) -> dict[str, NamedSharding]:
    """Flatten a shardings pytree into a path -> NamedSharding dict.

    Paths use the same '/'-joined syntax as the :func:`_walk` param walk
    (dict keys, NamedTuple field names, sequence indices), so a tree built
    by ``launch.sharding.param_shardings`` lines up with the program walk.
    """
    if shardings is None:
        return {}
    flat, _ = jax.tree_util.tree_flatten_with_path(shardings)
    out: dict[str, NamedSharding] = {}
    for path, leaf in flat:
        if not isinstance(leaf, NamedSharding):
            continue
        parts = []
        for p in path:
            if hasattr(p, "key"):
                parts.append(str(p.key))
            elif hasattr(p, "name"):
                parts.append(str(p.name))
            elif hasattr(p, "idx"):
                parts.append(str(p.idx))
        out["/".join(parts)] = leaf
    return out


def compile_program(
    params: Any,
    cfg: Any,
    key: Array,
    *,
    t_seconds: Optional[float] = None,
    transforms: Optional[dict[str, Callable[[Array], Array]]] = None,
    with_mapping: bool = False,
    shardings: Any = None,
    b_adc_overrides: Optional[BitOverrides] = None,
    chip_id: Optional[int] = None,
) -> CiMProgram:
    """Program phase: walk ``params`` once and build a :class:`CiMProgram`.

    ``cfg`` is an AnalogConfig supplying the PCM model, quant spec, and
    crossbar geometry; its mode is ignored (the returned program's cfg is
    the same config with mode set to :data:`PCM_PROGRAMMED`).

    ``transforms`` maps a layer path to a weight-to-crossbar-block function
    (e.g. im2col flattening / depthwise densification for conv layers) run
    *before* programming, so write noise lands on the physical cells --
    including the zero cells of densified depthwise diagonals. Programmed
    conv weights therefore come back 2D; the layer ``apply`` functions
    detect that and skip their own flattening.

    ``with_mapping=True`` additionally shelf-packs every programmed block
    through the layer-serial tiler, attaching the physical array Mapping
    (placements + utilization) to the program.

    ``shardings``: a pytree of NamedShardings matching ``params`` (e.g.
    from ``launch.sharding.param_shardings(..., inference=True)``). Each
    layer's PCM state is then created under jit with shardings inherited
    from its weight, instead of a host-side materialization. When omitted,
    weights already committed with a NamedSharding (params placed on a mesh
    by the caller) inherit their own shardings automatically. The chip is
    bit-identical either way (det_sum + sharding-invariant RNG); layers
    with a ``transforms`` entry change shape and are programmed host-side.

    ``b_adc_overrides``: per-layer ADC bitwidths for mixed-precision serving
    -- a {path-pattern: bits} dict (fnmatch over walk paths; MoE expert
    banks match the *bank* path, all three weight families share the bank's
    ADCs). Matched layers get a plan quantizing at ``bits`` (DAC at
    ``bits + 1``) and carry a shape-encoded ``b_adc_buf`` so the execute
    phase recovers the bitwidth statically under jit; bits must be in
    {4, 6, 8}. Unmatched layers use ``cfg.b_adc``.

    ``chip_id``: optional fleet identity tag carried on the program (and
    into the v1 artifact) -- a fleet compiles N independent draws of the
    same weights under distinct keys with ``chip_id=0..N-1``.
    """
    t = float(cfg.t_seconds if t_seconds is None else t_seconds)
    transforms = transforms or {}
    overrides = normalize_b_adc_overrides(b_adc_overrides)
    if overrides:
        quant_lib.validate_b_adc(cfg.b_adc, "cfg.b_adc (with overrides)")
    want_read_buf = bool(getattr(cfg, "resample_read_noise", False))
    shard_of = sharding_lookup(shardings)
    state: dict[str, Any] = {}
    plans: dict[str, ExecutionPlan] = {}
    shapes: list[LayerShape] = []
    counter = {"n": 0}

    def next_key() -> Array:
        counter["n"] += 1
        return jax.random.fold_in(key, counter["n"])

    def add_plan(
        path: str, w2d: Array, count: int = 1, bits: Optional[int] = None
    ) -> None:
        k_dim, n_dim = int(w2d.shape[-2]), int(w2d.shape[-1])
        plans[path] = plan_for(cfg, k_dim, n_dim, b_adc=bits)
        for i in range(count):
            shapes.append(
                LayerShape(f"{path}[{i}]" if count > 1 else path,
                           k_dim, n_dim, n_patches=1)
            )

    def layer_sharding(
        layer_path: str, leaf_path: str, leaf: Array
    ) -> Optional[NamedSharding]:
        if layer_path in transforms:
            return None  # shape changed by the transform; program host-side
        return shard_of.get(leaf_path) or _layer_sharding(leaf)

    def program_node(path: str, node: dict) -> dict:
        new = dict(node)
        bits = resolve_b_adc(overrides, path, cfg.b_adc)
        if "w" in node:
            w2d = transforms.get(path, lambda w: w)(node["w"])
            if w2d.ndim > 3:
                # Only 2D blocks or one stack level (scanned LM groups) are
                # meaningful crossbar programs; a 4D tensor here is almost
                # certainly a conv kernel missing its im2col/densify
                # transform -- programming its spatial dims as independent
                # layers would be silently wrong.
                raise ValueError(
                    f"layer '{path}': weight shape {tuple(w2d.shape)} has "
                    "more than one stack dim; pass a transforms= entry "
                    "(e.g. analognet.crossbar_transforms) to flatten conv "
                    "kernels to their 2D crossbar blocks before programming"
                )
            buf = node["w_clip_buf"]
            w_min, w_max = buf[..., 0], buf[..., 1]
            sharding = layer_sharding(path, f"{path}/w", node["w"])
            w_eff, gdc, st = program_weight(
                next_key(), w2d, w_min, w_max, t, cfg.pcm,
                sharding=sharding,
            )
            new["w"] = w_eff.astype(node["w"].dtype)
            new["out_scale_buf"] = gdc
            stack = w2d.shape[:-2]
            if bits != cfg.b_adc:
                new["b_adc_buf"] = b_adc_buf(stack, bits)
            if want_read_buf:
                new["read_buf"] = read_buffers(
                    st, t, cfg.pcm,
                    n_stack_dims=len(stack), sharding=sharding,
                )
            state[path] = st
            n_members = math.prod(stack) if w2d.ndim > 2 else 1
            add_plan(path, w2d, n_members, bits=bits)
        else:  # MoE expert bank
            st_fams, scales, read_bufs = {}, [], {}
            for f, fam in enumerate(_MOE_FAMILIES):
                w = node[fam]
                buf = node["w_clip_buf"]  # (..., 3, 2)
                stack = w.shape[:-2]
                w_min = jnp.broadcast_to(
                    buf[..., f, 0][..., None] if stack else buf[..., f, 0],
                    stack,
                )
                w_max = jnp.broadcast_to(
                    buf[..., f, 1][..., None] if stack else buf[..., f, 1],
                    stack,
                )
                sharding = layer_sharding(path, f"{path}/{fam}", w)
                w_eff, gdc, st = program_weight(
                    next_key(), w, w_min, w_max, t, cfg.pcm,
                    sharding=sharding,
                )
                new[fam] = w_eff.astype(w.dtype)
                st_fams[fam] = st
                scales.append(gdc)
                if want_read_buf:
                    read_bufs[fam] = read_buffers(
                        st, t, cfg.pcm,
                        n_stack_dims=len(stack), sharding=sharding,
                    )
                add_plan(
                    f"{path}/{fam}", w,
                    math.prod(stack) if stack else 1,
                    bits=bits,
                )
            new["out_scale_buf"] = jnp.stack(scales, axis=-2)
            if bits != cfg.b_adc:
                # one bitwidth per bank: all three families share the
                # physical per-layer ADC configuration (fixed-gain Eq. 5)
                new["b_adc_buf"] = b_adc_buf(stack, bits)
            if want_read_buf:
                new["read_buf"] = read_bufs
            state[path] = st_fams
        return new

    programmed = _walk(params, program_node)
    mapping = None
    if with_mapping and shapes:
        mapping = map_layers(shapes, cfg.tile_rows, cfg.tile_cols)
    return CiMProgram(
        params=programmed,
        cfg=dataclasses.replace(cfg, mode=PCM_PROGRAMMED, quant_noise_p=1.0),
        t_seconds=t,
        state=state,
        plans=plans,
        mapping=mapping,
        age_history=(t,),
        chip_id=chip_id,
    )
