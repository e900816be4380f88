"""Plain reference of a served decoder LM on a programmed PCM chip.

OLMo's block (arXiv:2402.00838): pre-norm, multi-head attention with
rotary embeddings (the rotate-half form, theta 10^4), SwiGLU feed-forward
(silu(x W1) * (x W3)) W2, untied head. Departure kept from the system: the
norm is a scale-free RMS norm, where OLMo's is a non-parametric
LayerNorm. Activations are in the configuration's activation dtype (bf16
for olmo-1b) at the same boundaries as the deployment:
the embedding, each norm's output, each programmed layer's output, the
rotated heads and the attention output; sums and softmax run in f32.

Every projection and the head are programmed layers
(:mod:`reference.analog`); the embedding is a digital lookup. No cache,
no batching, no paging: one causal pass over the whole sequence.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.chip import weights
from benchmarks.chip.reference import analog

#: the programmed layers in the order the chip programs them (its walk
#: visits the parameter tree in key order), each stacked over the layers
LAYERS = ("attn/wk", "attn/wo", "attn/wq", "attn/wv",
          "ffn/w1", "ffn/w2", "ffn/w3")


def shapes(c: dict) -> dict:
    """path -> (K, N) of every programmed layer (stacked ones without L)."""
    d, hd = c["hidden_size"], c["head_dim"]
    q, kv, ff = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd, c["intermediate_size"]
    return {
        "attn/wk": (d, kv), "attn/wo": (q, d), "attn/wq": (d, q),
        "attn/wv": (d, kv), "ffn/w1": (d, ff), "ffn/w2": (ff, d),
        "ffn/w3": (d, ff), "lm_head": (d, c["vocab_size"]),
    }


def program(c: dict, seed: int, t_seconds: float) -> dict:
    """The chip's programmed state, rebuilt from the seed.

    Returns {"<layer>": (w_eff, gdc, r_adc, w_max)} and the embedding."""
    base = weights.seed_key(seed, "weights")
    pkey = weights.seed_key(seed, "program")
    n_l = c["num_hidden_layers"]
    t = jnp.float32(t_seconds)
    out = {}
    order = [f"blocks/0/{p}" for p in LAYERS] + ["lm_head"]
    for i, path in enumerate(order):
        kn = shapes(c)[path.removeprefix("blocks/0/")]
        stack = (n_l,) if path.startswith("blocks") else ()
        w = weights.leaf(f"{path}/w", stack + kn, base)
        clip = weights.leaf(f"{path}/w_clip_buf", stack + (2,), base)
        r_adc = weights.leaf(f"{path}/r_adc", stack, base)
        w_eff, gdc = analog.program_layer(jax.random.fold_in(pkey, i + 1), w, clip, t)
        out[path] = (w_eff, gdc, r_adc, clip[..., 1])
        del w
    out["embed"] = weights.leaf("embed/table", (c["vocab_size"], c["hidden_size"]), base)
    out["gain_s"] = weights.leaf("gain_s", (), base)
    return out


def _rmsnorm(x, eps: float):
    xf = x.astype(jnp.float32)
    return (xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)).astype(x.dtype)


def _rope(x, theta: float):
    """x: (S, H, D), positions 0..S-1."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).astype(x.dtype)


def _attention(q, k, v):
    """Causal attention of one sequence; q, k, v: (S, H, D) bf16."""
    s_len, _, d = q.shape
    s = jnp.einsum("qhd,khd->hqk", q, k, preferred_element_type=jnp.float32) * d**-0.5
    causal = jnp.arange(s_len)[:, None] >= jnp.arange(s_len)[None, :]
    s = jnp.where(causal[None], s, -jnp.inf)
    p = jnp.exp(s - s.max(-1, keepdims=True))
    o = jnp.einsum("hqk,khd->qhd", p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    return (o / p.sum(-1).T[..., None]).astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("dims", "bits"))
def forward(state: dict, tokens, *, dims: tuple, bits: int):
    """Logits (S, vocab) of one token sequence through the chip."""
    n_l, n_h, hd, theta, dtype, eps = dims
    gain = state["gain_s"]

    def lin(path, x, layer=None):
        w, gdc, r_adc, w_max = state[path]
        if layer is not None:
            w, gdc, r_adc, w_max = w[layer], gdc[layer], r_adc[layer], w_max[layer]
        return analog.linear(x, w, gdc, r_adc, w_max, gain, bits)

    h = state["embed"].astype(dtype)[tokens]
    s_len = tokens.shape[0]
    for li in range(n_l):
        a = _rmsnorm(h, eps)
        q = lin("blocks/0/attn/wq", a, li).reshape(s_len, n_h, hd)
        k = lin("blocks/0/attn/wk", a, li).reshape(s_len, -1, hd)
        v = lin("blocks/0/attn/wv", a, li).reshape(s_len, -1, hd)
        group = n_h // k.shape[1]  # query head h reads key/value head h // group
        k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
        o = _attention(_rope(q, theta), _rope(k, theta), v)
        h = h + lin("blocks/0/attn/wo", o.reshape(s_len, n_h * hd), li)
        a = _rmsnorm(h, eps)
        f = jax.nn.silu(lin("blocks/0/ffn/w1", a, li)) * lin("blocks/0/ffn/w3", a, li)
        h = h + lin("blocks/0/ffn/w2", f, li)
    return lin("lm_head", _rmsnorm(h, eps)).astype(jnp.float32)


def dims(c: dict) -> tuple:
    """The static sizes :func:`forward` takes."""
    return (c["num_hidden_layers"], c["num_attention_heads"], c["head_dim"],
            float(c["rope_theta"]), c["activation_dtype"], float(c["norm_eps"]))
