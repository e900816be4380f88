"""Plain reference of LFM2-MoE's share served from a programmed PCM chip.

The block (LiquidAI's LFM2 modelling code, LFM2-8B-A1B's config.json):
``h += mixer(norm(h))``, then ``h += ffn(norm(h))``; a final norm before
the head. RMSNorm is ``x / sqrt(mean(x^2) + eps) * g``.

* Conv mixer: ``B, C, x = split3(x W_in)``; ``y = C * conv(B * x)``, with
  ``conv`` a causal depthwise convolution of ``conv_L_cache`` taps and no
  bias; ``out = y W_out``.
* Attention mixer: GQA, a per-head RMSNorm of q and of k before RoPE (the
  rotate-half form), softmax over the causal scores, ``W_o``.
* FFN: ``(silu(x W1) * (x W3)) W2``, of ``intermediate_size`` in the first
  ``num_dense_layers`` layers.
* MoE in every later layer: ``s = sigmoid(x W_router)`` over all
  ``num_experts_published`` experts, in f32; the top ``num_experts_per_tok``
  by ``s + expert_bias`` (ties to the lowest index); weights the chosen
  ``s`` normalised to sum 1, times ``routed_scaling_factor``; the output
  ``sum_e weight_e FFN_e(x)`` of expert FFNs of ``moe_intermediate_size``.

Departures, each shared with the program: only the chip's share of the
experts is computed (``num_experts`` of them from ``expert_share_first``,
every one densely over every token with a routing mask), and what the
other chips' experts add is left out; LFM2's code divides the routing
weights by their sum plus 1e-6, here by the sum; the head is its own
programmed layer (untied); weights and norm gains come from the seed
(``benchmarks/chip/weights.py`` conventions, gains 1, expert bias 0).

Activations are in the configuration's activation dtype (bf16) at the
same boundaries as the deployment: the embedding, each norm's output,
each programmed layer's output, ``B * x``, the conv's output, ``C * y``,
the rotated heads, the attention output and each layer's MoE output.
Sums, the softmax, the router and the conv taps run in f32, matrix
products at ``Precision.HIGHEST``. Every projection, expert bank and the
head are programmed layers (:mod:`reference.analog`); the embedding, the
router and the conv are digital. No cache, no batching, no paging: one
causal pass over the whole sequence, attention computed in blocks of
queries so that it fits.
"""

from __future__ import annotations

import functools
import zlib

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import weights
from benchmarks.chip.reference import analog

HIGHEST = jax.lax.Precision.HIGHEST
#: query rows of one attention block
Q_BLOCK = 512
#: an expert bank's families in the order the chip programs them
BANK = ("w1", "w3", "w2")


def leaf(path: str, shape: tuple, base):
    """:func:`weights.leaf`, plus the leaves LFM2 adds: expert banks and
    the conv's taps N(0, 1 / fan_in), norm gains 1, expert bias 0."""
    name = path.rsplit("/", 1)[-1]
    if name in ("w1", "w2", "w3", "kernel"):
        key = jax.random.fold_in(base, np.uint32(zlib.crc32(path.encode())))
        return jax.random.normal(key, shape, jnp.float32) * (1.0 / shape[-2]) ** 0.5
    if name == "scale":
        return jnp.ones(shape, jnp.float32)
    if name == "expert_bias":
        return jnp.zeros(shape, jnp.float32)
    return weights.leaf(path, shape, base)


def kinds(c: dict) -> tuple:
    """(mixer, ffn) of each layer: "conv" or "attn", "dense" or "moe"."""
    return tuple(
        ("attn" if t == "full_attention" else "conv",
         "dense" if i < c["num_dense_layers"] else "moe")
        for i, t in enumerate(c["layer_types"][: c["num_hidden_layers"]])
    )


def layers(c: dict) -> list:
    """(path, (K, N), bank) of every programmed layer in the order the chip
    programs it: layer by layer, each layer's parts in key order
    (attn < conv < ffn < moe), a bank's families in :data:`BANK` order."""
    d, ff, mf = c["hidden_size"], c["intermediate_size"], c["moe_intermediate_size"]
    nh, nkv = c["num_attention_heads"], c["num_key_value_heads"]
    hd = d // nh
    out = []
    for i, (mixer, ffn) in enumerate(kinds(c)):
        p = f"tail/{i}"
        if mixer == "attn":
            out += [(f"{p}/attn/wk", (d, nkv * hd), False),
                    (f"{p}/attn/wo", (nh * hd, d), False),
                    (f"{p}/attn/wq", (d, nh * hd), False),
                    (f"{p}/attn/wv", (d, nkv * hd), False)]
        else:
            out += [(f"{p}/conv/in_proj", (d, 3 * d), False),
                    (f"{p}/conv/out_proj", (d, d), False)]
        if ffn == "dense":
            out += [(f"{p}/ffn/w1", (d, ff), False), (f"{p}/ffn/w2", (ff, d), False),
                    (f"{p}/ffn/w3", (d, ff), False)]
        else:
            out += [(f"{p}/moe/w1", (d, mf), True), (f"{p}/moe/w3", (d, mf), True),
                    (f"{p}/moe/w2", (mf, d), True)]
    return out + [("lm_head", (d, c["vocab_size"]), False)]


def program(c: dict, seed: int, t_seconds: float) -> dict:
    """The chip's programmed state, rebuilt from the seed.

    Returns {"<layer>": (w_eff, gdc, r_adc, w_max)} (a bank's stacked over
    its experts), the digital leaves and the embedding."""
    base = weights.seed_key(seed, "weights")
    pkey = weights.seed_key(seed, "program")
    t = jnp.float32(t_seconds)
    n_e = c["num_experts"]
    out = {}
    for i, (path, kn, bank) in enumerate(layers(c)):
        if bank:
            node, fam = path.rsplit("/", 1)
            f = BANK.index(fam)
            w = leaf(path, (n_e,) + kn, base)
            clip = jnp.broadcast_to(leaf(f"{node}/w_clip_buf", (3, 2), base)[f], (n_e, 2))
            r_adc = leaf(f"{node}/r_adc", (3,), base)[f]
        else:
            w = leaf(f"{path}/w", kn, base)
            clip = leaf(f"{path}/w_clip_buf", (2,), base)
            r_adc = leaf(f"{path}/r_adc", (), base)
        w_eff, gdc = analog.program_layer(jax.random.fold_in(pkey, i + 1), w, clip, t)
        out[path] = (w_eff, gdc, r_adc, clip[..., 1])
        del w
    d, hd = c["hidden_size"], c["hidden_size"] // c["num_attention_heads"]
    for i, (mixer, ffn) in enumerate(kinds(c)):
        p = f"tail/{i}"
        for norm in ("norm1", "norm2"):
            out[f"{p}/{norm}"] = leaf(f"{p}/{norm}/scale", (d,), base)
        if mixer == "attn":
            out[f"{p}/q_norm"] = leaf(f"{p}/attn/q_norm/scale", (hd,), base)
            out[f"{p}/k_norm"] = leaf(f"{p}/attn/k_norm/scale", (hd,), base)
        else:
            out[f"{p}/kernel"] = leaf(f"{p}/conv/kernel", (c["conv_L_cache"], d), base)
        if ffn == "moe":
            n_r = c["num_experts_published"]
            out[f"{p}/router"] = leaf(f"{p}/moe/router/w", (d, n_r), base)
            out[f"{p}/expert_bias"] = leaf(f"{p}/moe/expert_bias", (n_r,), base)
    out["final_norm"] = leaf("final_norm/scale", (d,), base)
    out["embed"] = leaf("embed/table", (c["vocab_size"], d), base)
    out["gain_s"] = leaf("gain_s", (), base)
    return out


def _rmsnorm(x, g, eps: float):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps) * g
    return y.astype(x.dtype)


def _rope(x, theta: float):
    """x: (S, H, D), positions 0..S-1."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).astype(x.dtype)


def _attention(q, k, v):
    """Causal attention of one sequence, in blocks of :data:`Q_BLOCK`
    queries; q: (S, H, D), k, v: (S, H, D) bf16."""
    s_len, _, d = q.shape
    outs = []
    for q0 in range(0, s_len, Q_BLOCK):
        qb = q[q0: q0 + Q_BLOCK]
        s = jnp.einsum("qhd,khd->hqk", qb, k, preferred_element_type=jnp.float32) * d**-0.5
        causal = (q0 + jnp.arange(qb.shape[0]))[:, None] >= jnp.arange(s_len)[None, :]
        s = jnp.where(causal[None], s, -jnp.inf)
        p = jnp.exp(s - s.max(-1, keepdims=True))
        o = jnp.einsum("hqk,khd->qhd", p.astype(v.dtype), v,
                       preferred_element_type=jnp.float32)
        outs.append((o / p.sum(-1).T[..., None]).astype(q.dtype))
    return jnp.concatenate(outs)


def route(x, router, bias, k: int):
    """(ids (T, k), weights (T, k)) of LFM2's routing, in f32."""
    logits = jnp.matmul(x.astype(jnp.float32), router, precision=HIGHEST)
    s = jax.nn.sigmoid(logits)
    sel = s + bias
    ids = []
    for _ in range(k):
        i = jnp.argmax(sel, axis=-1)  # the first maximum: the lowest index
        ids.append(i)
        sel = jnp.where(jnp.arange(sel.shape[-1]) == i[:, None], -jnp.inf, sel)
    ids = jnp.stack(ids, -1)
    w = jnp.take_along_axis(s, ids, -1)
    return ids, w / w.sum(-1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("dims", "bits"))
def forward(state: dict, tokens, *, dims: tuple, bits: int):
    """Logits (S, vocab) of one token sequence through the chip."""
    layer_kinds, n_h, hd, theta, dtype, eps, top_k, first, n_held, scale = dims
    gain = state["gain_s"]

    def lin(path, x, e=None):
        w, gdc, r_adc, w_max = state[path]
        if e is not None:
            w, gdc, w_max = w[e], gdc[e], w_max[e]
        return analog.linear(x, w, gdc, r_adc, w_max, gain, bits)

    h = state["embed"].astype(dtype)[tokens]
    s_len = tokens.shape[0]
    for li, (mixer, ffn) in enumerate(layer_kinds):
        p = f"tail/{li}"
        a = _rmsnorm(h, state[f"{p}/norm1"], eps)
        if mixer == "conv":
            bx_c_x = lin(f"{p}/conv/in_proj", a)
            gb, gc, xx = jnp.split(bx_c_x, 3, axis=-1)
            bx = gb * xx
            taps = state[f"{p}/kernel"]
            k = taps.shape[0]
            win = jnp.concatenate([jnp.zeros((k - 1, bx.shape[-1]), bx.dtype), bx])
            y = win[:s_len].astype(jnp.float32) * taps[0]
            for j in range(1, k):
                y = y + win[j: j + s_len].astype(jnp.float32) * taps[j]
            out = lin(f"{p}/conv/out_proj", gc * y.astype(dtype))
        else:
            q = lin(f"{p}/attn/wq", a).reshape(s_len, n_h, hd)
            kk = lin(f"{p}/attn/wk", a).reshape(s_len, -1, hd)
            v = lin(f"{p}/attn/wv", a).reshape(s_len, -1, hd)
            q = _rmsnorm(q, state[f"{p}/q_norm"], eps)
            kk = _rmsnorm(kk, state[f"{p}/k_norm"], eps)
            group = n_h // kk.shape[1]  # query head h reads kv head h // group
            kk, v = jnp.repeat(kk, group, axis=1), jnp.repeat(v, group, axis=1)
            o = _attention(_rope(q, theta), _rope(kk, theta), v)
            out = lin(f"{p}/attn/wo", o.reshape(s_len, n_h * hd))
        h = h + out
        a = _rmsnorm(h, state[f"{p}/norm2"], eps)
        if ffn == "dense":
            f = jax.nn.silu(lin(f"{p}/ffn/w1", a)) * lin(f"{p}/ffn/w3", a)
            h = h + lin(f"{p}/ffn/w2", f)
        else:
            ids, wts = route(a, state[f"{p}/router"], state[f"{p}/expert_bias"], top_k)
            y = None
            for e in range(n_held):
                gate = jnp.sum(jnp.where(ids == first + e, wts, 0.0), -1) * scale
                f = jax.nn.silu(lin(f"{p}/moe/w1", a, e)) * lin(f"{p}/moe/w3", a, e)
                part = gate[:, None] * lin(f"{p}/moe/w2", f, e).astype(jnp.float32)
                y = part if y is None else y + part
            h = h + y.astype(dtype)
    return lin("lm_head", _rmsnorm(h, state["final_norm"], eps)).astype(jnp.float32)


def dims(c: dict) -> tuple:
    """The static sizes :func:`forward` takes."""
    nh = c["num_attention_heads"]
    return (kinds(c), nh, c["hidden_size"] // nh, float(c["rope_theta"]),
            c["activation_dtype"], float(c["norm_eps"]), c["num_experts_per_tok"],
            c["expert_share_first"], c["num_experts"],
            float(c["routed_scaling_factor"]))

