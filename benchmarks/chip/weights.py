"""Weights from the seed: one rule per leaf, keyed by the leaf's path.

The driver fills the program's parameter tree with these leaves; the
reference calls :func:`leaf` with the same paths and shapes and gets the
same values, without taking anything from the program. The rules are the
deployment conventions the program's own initializers use: weights
N(0, gain / fan_in) (gain 2 for convolutions), embeddings N(0, 0.02^2),
ADC ranges and the network gain at 1, clip ranges at [-1, 1], batch-norm
folded to the identity, biases at 0.
"""

from __future__ import annotations

import math
import zlib

import numpy as np


def seed_key(seed: int, stream: str):
    """A JAX key for one named stream of a seed (seeds past 32 bits too)."""
    import jax

    k = jax.random.PRNGKey(np.uint32(seed & 0xFFFFFFFF))
    k = jax.random.fold_in(k, np.uint32((seed >> 32) & 0xFFFFFFFF))
    return jax.random.fold_in(k, np.uint32(zlib.crc32(stream.encode())))


def leaf(path: str, shape: tuple, base):
    """The value of the leaf at ``path`` ('/'-joined) for key ``base``."""
    import jax
    import jax.numpy as jnp

    name = path.rsplit("/", 1)[-1]
    key = jax.random.fold_in(base, np.uint32(zlib.crc32(path.encode())))
    f32 = jnp.float32
    if name == "w":
        conv = len(shape) == 4
        fan_in = math.prod(shape[:-1]) if conv else shape[-2]
        gain = 2.0 if conv else 1.0
        return jax.random.normal(key, shape, f32) * (gain / fan_in) ** 0.5
    if name == "table":
        return jax.random.normal(key, shape, f32) * 0.02
    if name == "w_clip_buf":
        return jnp.broadcast_to(jnp.array([-1.0, 1.0], f32), shape)
    if name in ("r_adc", "gain_s", "bn_scale"):
        return jnp.ones(shape, f32)
    if name in ("b", "bn_bias"):
        return jnp.zeros(shape, f32)
    raise KeyError(f"no weight rule for leaf {path!r}")


def path_str(key_path) -> str:
    """'/'-joined names of a ``jax.tree_util`` key path."""
    parts = []
    for k in key_path:
        for attr in ("key", "name", "idx"):
            if hasattr(k, attr):
                parts.append(str(getattr(k, attr)))
                break
    return "/".join(parts)


def fill(shapes, seed: int):
    """A tree shaped like ``shapes`` (``jax.eval_shape`` output), every
    leaf from :func:`leaf`, made on the device in one jitted call."""
    import jax

    base = seed_key(seed, "weights")

    def make(base):
        return jax.tree_util.tree_map_with_path(
            lambda p, s: leaf(path_str(p), s.shape, base).astype(s.dtype),
            shapes,
        )

    return jax.jit(make)(base)


def with_mvm_dtype(params, dtype):
    """``params`` of a programmed chip with every programmed layer's
    effective weights (``w`` beside an ``out_scale_buf``) cast to
    ``dtype``. The program runs each crossbar MVM in its weights' dtype,
    so this is the chip computed at that precision: the control's."""
    import jax.numpy as jnp

    if isinstance(params, dict):
        out = {k: with_mvm_dtype(v, dtype) for k, v in params.items()}
        if "out_scale_buf" in params and "w" in params:
            out["w"] = params["w"].astype(jnp.dtype(dtype))
        return out
    if isinstance(params, (list, tuple)):
        items = [with_mvm_dtype(v, dtype) for v in params]
        return type(params)(*items) if hasattr(params, "_fields") else type(params)(items)
    return params
