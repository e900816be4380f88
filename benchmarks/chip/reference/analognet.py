"""Plain reference of an AnalogNet CNN on a programmed PCM chip.

Each convolution is im2col ('SAME' padding, patch features ordered
kernel row, kernel column, channel) times its programmed (kh*kw*c_in,
c_out) crossbar block, then the digital folded batch norm and ReLU;
global average pooling, then the programmed FC layer plus its digital
bias. Everything is f32, as the deployment runs it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.chip import weights
from benchmarks.chip.reference import analog


def program(c: dict, seed: int, t_seconds: float) -> dict:
    """The chip's programmed state, rebuilt from the seed (the chip
    programs its layers in key order: the convolutions, then the FC)."""
    base = weights.seed_key(seed, "weights")
    pkey = weights.seed_key(seed, "program")
    t = jnp.float32(t_seconds)
    state = {"gain_s": weights.leaf("gain_s", (), base)}
    layers = [(v["name"], (v["kh"], v["kw"], v["c_in"], v["c_out"])) for v in c["convs"]]
    layers.append(("fc", (c["fc_width"], c["n_classes"])))
    for i, (name, shape) in enumerate(sorted(layers)):
        w = weights.leaf(f"{name}/w", shape, base)
        w = w.reshape(-1, shape[-1])  # a conv kernel as its crossbar block
        clip = weights.leaf(f"{name}/w_clip_buf", (2,), base)
        w_eff, gdc = analog.program_layer(jax.random.fold_in(pkey, i + 1), w, clip, t)
        layer = {"w": w_eff, "gdc": gdc, "w_max": clip[1],
                 "r_adc": weights.leaf(f"{name}/r_adc", (), base)}
        if name == "fc":
            layer["b"] = weights.leaf("fc/b", (shape[-1],), base)
        else:
            layer["bn_scale"] = weights.leaf(f"{name}/bn_scale", (shape[-1],), base)
            layer["bn_bias"] = weights.leaf(f"{name}/bn_bias", (shape[-1],), base)
        state[name] = layer
    return state


def im2col(x, kh: int, kw: int, stride: int):
    """(B, H, W, C) -> (B, Ho, Wo, kh*kw*C) with 'SAME' padding."""
    _, h, w, _ = x.shape
    ho, wo = -(-h // stride), -(-w // stride)
    ph = max((ho - 1) * stride + kh - h, 0)
    pw = max((wo - 1) * stride + kw - w, 0)
    x = jnp.pad(x, ((0, 0), (ph // 2, ph - ph // 2), (pw // 2, pw - pw // 2), (0, 0)))
    cols = [
        x[:, i:i + (ho - 1) * stride + 1:stride, j:j + (wo - 1) * stride + 1:stride, :]
        for i in range(kh) for j in range(kw)
    ]
    return jnp.concatenate(cols, axis=-1)


@functools.partial(jax.jit, static_argnames=("convs", "bits"))
def forward(state: dict, x, *, convs: tuple, bits: int):
    """Logits (B, n_classes) of inputs (B, H, W, C)."""
    gain = state["gain_s"]

    def lin(layer, v):
        return analog.linear(v, layer["w"], layer["gdc"], layer["r_adc"],
                             layer["w_max"], gain, bits)

    for name, kh, kw, stride in convs:
        layer = state[name]
        y = lin(layer, im2col(x, kh, kw, stride))
        x = jax.nn.relu(y * layer["bn_scale"] + layer["bn_bias"])
    x = x.mean(axis=(1, 2))
    return lin(state["fc"], x) + state["fc"]["b"]


def conv_dims(c: dict) -> tuple:
    return tuple((v["name"], v["kh"], v["kw"], v["stride"]) for v in c["convs"])
