"""AnalogNet-KWS and AnalogNet-VWW (paper Sec. 4.1, Appendix B).

The exact Fig.-10 layer tables are an image unavailable in the provided text;
both architectures are reconstructed from the paper's hard constraints (see
DESIGN.md Sec. 6):

  AnalogNet-KWS  -- MicroNet-KWS-S backbone with every depthwise-separable
    block replaced by a dense 3x3 conv and the final 196-channel layer
    removed. Reconstruction: 4x conv3x3 at 106 channels; 305.7k weights =
    58.3% of the 1024x512 array (paper: 57.3%), 76.8 MOP/inf (paper-implied:
    77.3), tall im2col blocks (954 rows <= 1024).

  AnalogNet-VWW  -- MobileNetV2-style backbone at 100x100x3 with MBConv ->
    fused-MBConv (dense 3x3 expand + 1x1 project) and the two early narrow
    bottleneck layers removed. Reconstruction: 347k weights = 66.2% (paper:
    67.5%), 75 MOP/inf (paper-implied: 70.6).

Convolutions execute as IM2COL + analog_matmul -- the same dataflow as the
AON-CiM hardware IM2COL unit -> DAC -> crossbar -> ADC chain, so the analog
noise/quant path sees exactly the tensors the hardware would.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.analog import AnalogConfig, AnalogCtx, analog_matmul
from repro.core.crossbar import LayerShape, conv_weight_as_matrix, im2col

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class ConvSpec:
    name: str
    kh: int
    kw: int
    c_in: int
    c_out: int
    stride: int = 1
    depthwise: bool = False


@dataclasses.dataclass(frozen=True)
class CNNConfig:
    name: str
    input_hw: tuple
    in_channels: int
    convs: tuple  # of ConvSpec
    n_classes: int
    fc_width: int  # channels entering the final FC


def analognet_kws_config() -> CNNConfig:
    c = 106
    return CNNConfig(
        name="analognet_kws",
        input_hw=(49, 10),
        in_channels=1,
        convs=(
            ConvSpec("conv1", 3, 3, 1, c, 1),
            ConvSpec("conv2", 3, 3, c, c, 2),
            ConvSpec("conv3", 3, 3, c, c, 1),
            ConvSpec("conv4", 3, 3, c, c, 1),
        ),
        n_classes=12,  # full 12-keyword Speech Commands task
        fc_width=c,
    )


def analognet_vww_config(with_bottlenecks: bool = False) -> CNNConfig:
    convs = [ConvSpec("stem", 3, 3, 3, 24, 2)]
    if with_bottlenecks:
        # Table 1 ablation (last row): the two early narrow layers the paper
        # removes -- noise-robustness bottlenecks (Fig. 3 right).
        convs += [
            ConvSpec("bneck1", 1, 1, 24, 8, 1),
            ConvSpec("bneck2", 3, 3, 8, 24, 1),
        ]
    convs += [
        ConvSpec("b1_expand", 3, 3, 24, 96, 2),
        ConvSpec("b1_proj", 1, 1, 96, 32, 1),
        ConvSpec("b2_expand", 3, 3, 32, 128, 2),
        ConvSpec("b2_proj", 1, 1, 128, 48, 1),
        ConvSpec("b3_expand", 3, 3, 48, 192, 2),
        ConvSpec("b3_proj", 1, 1, 192, 64, 1),
        ConvSpec("b4_expand", 3, 3, 64, 256, 1),
        ConvSpec("b4_proj", 1, 1, 256, 96, 1),
        ConvSpec("head", 1, 1, 96, 128, 1),
    ]
    return CNNConfig(
        name="analognet_vww" + ("_bneck" if with_bottlenecks else ""),
        input_hw=(100, 100),
        in_channels=3,
        convs=tuple(convs),
        n_classes=2,
        fc_width=128,
    )


# ---------------------------------------------------------------------------
# init / apply
# ---------------------------------------------------------------------------


def cnn_init(key: Array, cfg: CNNConfig) -> dict:
    params: dict = {"gain_s": jnp.ones((), jnp.float32)}
    keys = jax.random.split(key, len(cfg.convs) + 1)
    for k, spec in zip(keys, cfg.convs):
        c_mult = 1 if spec.depthwise else spec.c_in
        fan_in = spec.kh * spec.kw * c_mult
        shape = (
            (spec.kh, spec.kw, spec.c_in, 1)
            if spec.depthwise
            else (spec.kh, spec.kw, spec.c_in, spec.c_out)
        )
        params[spec.name] = {
            "w": jax.random.normal(k, shape, jnp.float32) * (2.0 / fan_in) ** 0.5,
            "r_adc": jnp.ones((), jnp.float32),
            "w_clip_buf": jnp.array([-1.0, 1.0], jnp.float32),
            "bn_scale": jnp.ones((spec.c_out,), jnp.float32),
            "bn_bias": jnp.zeros((spec.c_out,), jnp.float32),
        }
    params["fc"] = {
        "w": jax.random.normal(keys[-1], (cfg.fc_width, cfg.n_classes), jnp.float32)
        * cfg.fc_width**-0.5,
        "b": jnp.zeros((cfg.n_classes,), jnp.float32),
        "r_adc": jnp.ones((), jnp.float32),
        "w_clip_buf": jnp.array([-1.0, 1.0], jnp.float32),
    }
    return params


def conv_apply(
    p: dict, x: Array, spec: ConvSpec, ctx: AnalogCtx, relu: bool = True
) -> Array:
    """IM2COL + analog matmul + digital BN/ReLU (the hardware dataflow)."""
    if p["w"].ndim == 2:
        # Compiled CiMProgram path: the program phase already flattened /
        # densified the kernel into its physical crossbar block and applied
        # the PCM chain, so ``w`` arrives as the programmed 2D matrix.
        w2d = p["w"]
    elif spec.depthwise:
        # Depthwise runs as a grouped conv digitally; its *mapping* to the
        # crossbar (densified) is what the baseline analysis quantifies.
        # For analog simulation we densify -- faithfully including the noise
        # contribution of the zero cells on shared bitlines.
        from repro.core.crossbar import depthwise_densify

        w2d = depthwise_densify(p["w"])
    else:
        w2d = conv_weight_as_matrix(p["w"])
    with jax.named_scope("im2col"):
        patches = im2col(x, spec.kh, spec.kw, spec.stride, "SAME")
    with jax.named_scope("mvm"):
        y = analog_matmul(
            patches,
            w2d.astype(x.dtype),
            r_adc=p["r_adc"],
            w_min=p["w_clip_buf"][0],
            w_max=p["w_clip_buf"][1],
            ctx=ctx,
            out_scale=p.get("out_scale_buf"),
        )
    # BN folded to scale/bias; applied in the digital datapath (Sec. 5.2).
    y = y * p["bn_scale"].astype(y.dtype) + p["bn_bias"].astype(y.dtype)
    return jax.nn.relu(y) if relu else y


def cnn_apply(
    params: dict, x: Array, analog_cfg: AnalogConfig, cfg: CNNConfig, rng=None
) -> Array:
    """x: (B, H, W, C) -> logits (B, n_classes)."""
    ctx = AnalogCtx(cfg=analog_cfg, gain_s=params["gain_s"], key=rng)
    for spec in cfg.convs:
        with jax.named_scope(spec.name):
            x = conv_apply(params[spec.name], x, spec, ctx)
    with jax.named_scope("pool"):
        x = x.mean(axis=(1, 2))  # global average pool (digital)
    fc = params["fc"]
    with jax.named_scope("fc"):
        y = analog_matmul(
            x,
            fc["w"].astype(x.dtype),
            r_adc=fc["r_adc"],
            w_min=fc["w_clip_buf"][0],
            w_max=fc["w_clip_buf"][1],
            ctx=ctx,
            out_scale=fc.get("out_scale_buf"),
        )
        return y + fc["b"].astype(y.dtype)


def cnn_loss(params, batch, analog_cfg, cfg, rng=None):
    logits = cnn_apply(params, batch["x"], analog_cfg, cfg, rng).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, batch["y"][:, None], axis=-1).mean()
    acc = (logits.argmax(-1) == batch["y"]).mean()
    return nll, {"loss": nll, "acc": acc}


def crossbar_transforms(cfg: CNNConfig) -> dict:
    """Weight-to-crossbar-block transforms for ``engine.compile_program``.

    Maps each conv layer's param path to the function that flattens its 4D
    kernel into the physical 2D block (im2col layout; depthwise kernels are
    densified to their block-diagonal form) so PCM programming noise lands
    on the actual crossbar cells -- including zero cells of the depthwise
    diagonals, exactly as per-call pcm_infer simulates them.
    """
    from repro.core.crossbar import depthwise_densify

    return {
        spec.name: depthwise_densify if spec.depthwise else conv_weight_as_matrix
        for spec in cfg.convs
    }


# ---------------------------------------------------------------------------
# Crossbar layer shapes (for the AON-CiM model)
# ---------------------------------------------------------------------------


def _spatial_sizes(cfg: CNNConfig) -> list[tuple]:
    h, w = cfg.input_hw
    sizes = []
    for spec in cfg.convs:
        h = -(-h // spec.stride)
        w = -(-w // spec.stride)
        sizes.append((h, w))
    return sizes


def layer_shapes(cfg: CNNConfig) -> list[LayerShape]:
    """Crossbar-mapped LayerShapes for every layer (Fig. 6 / Fig. 8 input)."""
    shapes = []
    for spec, (h, w) in zip(cfg.convs, _spatial_sizes(cfg)):
        if spec.depthwise:
            rows = spec.kh * spec.kw * spec.c_in
            shapes.append(
                LayerShape(
                    spec.name,
                    rows,
                    spec.c_in,
                    n_patches=h * w,
                    nnz_rows=spec.kh * spec.kw,
                )
            )
        else:
            rows = spec.kh * spec.kw * spec.c_in
            shapes.append(LayerShape(spec.name, rows, spec.c_out, n_patches=h * w))
    shapes.append(LayerShape("fc", cfg.fc_width, cfg.n_classes, n_patches=1))
    return shapes
