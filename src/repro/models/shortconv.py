"""LFM2's gated short convolution: the sequence mixer of its conv layers.

    B, C, x = split3(h W_in)
    y = C * causal_depthwise_conv_k(B * x)
    out = y W_out

``W_in`` (d x 3d) and ``W_out`` (d x d) are programmed layers
(AnalogLinear). The depthwise conv (``cfg.conv_width`` taps per channel,
no bias) stays digital, like the MoE router: its taps sum in f32, in tap
order, and the result is cast back to the activation dtype. Its state is
the last ``k - 1`` conv inputs ``B * x``: a sequence starts from zeros, a
decode step shifts one input in.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.analog import AnalogCtx, linear_init, proj
from repro.models.common import ModelConfig

Array = jax.Array


class ConvState(NamedTuple):
    x: Array  # (B, k - 1, d): the conv's last k - 1 inputs, oldest first


def conv_init(key: Array, cfg: ModelConfig) -> dict:
    k_in, k_conv, k_out = jax.random.split(key, 3)
    d, k = cfg.d_model, cfg.conv_width
    return {
        "in_proj": linear_init(k_in, d, 3 * d),
        "kernel": jax.random.normal(k_conv, (k, d), jnp.float32) * k**-0.5,
        "out_proj": linear_init(k_out, d, d),
    }


def init_conv_state(cfg: ModelConfig, batch: int, dtype) -> ConvState:
    return ConvState(jnp.zeros((batch, cfg.conv_width - 1, cfg.d_model), dtype))


def conv_apply(
    params: dict,
    h: Array,
    ctx: AnalogCtx,
    state: Optional[ConvState] = None,
    last_index: Optional[Array] = None,
) -> tuple[Array, Optional[ConvState]]:
    """h: (B, S, d) -> (out, new state, or None without one).

    ``state`` holds the inputs before ``h[:, 0]``. ``last_index`` (B,)
    is each row's last real position in a right-padded prefill: the new
    state then holds the inputs up to it, so pad tokens never enter it
    (without it, the inputs up to the end).
    """
    b, s, d = h.shape
    k = params["kernel"].shape[0]
    gb, gc, xx = jnp.split(proj(params, "in_proj", h, ctx), 3, axis=-1)
    bx = gb * xx
    with jax.named_scope("conv1d"):
        hist = (
            jnp.zeros((b, k - 1, d), bx.dtype) if state is None
            else state.x.astype(bx.dtype)
        )
        win = jnp.concatenate([hist, bx], axis=1)  # (B, S + k - 1, d)
        taps = params["kernel"].astype(jnp.float32)
        y = win[:, :s].astype(jnp.float32) * taps[0]
        for j in range(1, k):
            y = y + win[:, j : j + s].astype(jnp.float32) * taps[j]
        y = y.astype(bx.dtype)
    out = proj(params, "out_proj", gc * y, ctx)
    if state is None:
        return out, None
    # window index i holds position i - (k - 1): positions L - k + 2 .. L
    # start at index L + 1
    end = (
        jnp.full((b,), s, jnp.int32) if last_index is None
        else jnp.asarray(last_index, jnp.int32) + 1
    )
    tail = jax.vmap(
        lambda w, e: jax.lax.dynamic_slice_in_dim(w, e, k - 1, axis=0)
    )(win, end)
    return out, ConvState(tail.astype(state.x.dtype))
