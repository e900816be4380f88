"""Model operations of an LFM2-MoE configuration, counted from shapes.

The rules of :mod:`benchmarks.chip.flops`: only the mathematics counts, a
multiply-add is two operations. Per token, a conv layer runs its two
projections and its depthwise taps, an attention layer its four
projections, a dense layer its FFN, and an MoE layer its router over
every published expert plus, in expectation, ``num_experts_per_tok x
num_experts / num_experts_published`` of this chip's expert FFNs (the
tokens' picks that land on the experts held here). The head runs once a
prefill (the last position) and once a decoded token.
"""

from __future__ import annotations


def _kinds(c: dict) -> list:
    return [("attn" if t == "full_attention" else "conv",
             "dense" if i < c["num_dense_layers"] else "moe")
            for i, t in enumerate(c["layer_types"][: c["num_hidden_layers"]])]


def layer_macs(c: dict) -> float:
    """Multiply-adds one token makes through the layers (no head)."""
    d = c["hidden_size"]
    nh, nkv = c["num_attention_heads"], c["num_key_value_heads"]
    hd = d // nh
    held = c["num_experts_per_tok"] * c["num_experts"] / c["num_experts_published"]
    total = 0.0
    for mixer, ffn in _kinds(c):
        if mixer == "attn":
            total += d * nh * hd * 2 + d * nkv * hd * 2
        else:
            total += d * 3 * d + d * d + c["conv_L_cache"] * d
        if ffn == "dense":
            total += 3 * d * c["intermediate_size"]
        else:
            total += d * c["num_experts_published"]
            total += held * 3 * d * c["moe_intermediate_size"]
    return total


def attention_flops(c: dict, q_positions: int, context: int) -> int:
    """QK^T and PV of the attention layers for ``q_positions`` queries
    over ``context`` keys each."""
    n_attn = sum(m == "attn" for m, _ in _kinds(c))
    return 4 * n_attn * c["hidden_size"] * q_positions * context


def prefill_flops(c: dict, prompt: int) -> float:
    """A prompt's prefill: every position through the layers, causal
    attention over the positions before it, and the head once."""
    causal = attention_flops(c, 1, 1) * prompt * (prompt + 1) // 2
    head = 2 * c["hidden_size"] * c["vocab_size"]
    return 2 * layer_macs(c) * prompt + causal + head


def decode_flops(c: dict, context: int) -> float:
    """One decoded token at ``context`` cached positions (itself included)."""
    head = 2 * c["hidden_size"] * c["vocab_size"]
    return 2 * layer_macs(c) + head + attention_flops(c, 1, context)
