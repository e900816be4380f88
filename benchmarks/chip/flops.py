"""Model operations, counted from shapes: the work the model needs.

Only the mathematics counts -- never how the program emulates it (an f32
matmul at ``Precision.HIGHEST`` runs as six bf16 passes on a TPU; it
counts once). A multiply-add is two operations.
"""

from __future__ import annotations

import json
import pathlib


def peak(device_kind: str) -> dict:
    """The chip's published peaks; an unknown device is an error."""
    table = json.loads((pathlib.Path(__file__).parent / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add a row "
            f"with its source to peaks.json"
        )
    return table[device_kind]


def lm_matmul_params(c: dict) -> int:
    """Weights a token multiplies through: the decoder layers and the head
    (the embedding is a lookup)."""
    d, hd = c["hidden_size"], c["head_dim"]
    attn = d * c["num_attention_heads"] * hd * 2 + d * c["num_key_value_heads"] * hd * 2
    ffn = 3 * d * c["intermediate_size"]
    return c["num_hidden_layers"] * (attn + ffn) + d * c["vocab_size"]


def lm_attention_flops(c: dict, q_positions: int, context: int) -> int:
    """QK^T and PV for ``q_positions`` queries over ``context`` keys each."""
    width = c["num_attention_heads"] * c["head_dim"]
    return 4 * c["num_hidden_layers"] * width * q_positions * context


def lm_prefill_flops(c: dict, prompt: int) -> int:
    """A prompt's prefill: every position through the layers, causal
    attention over the positions before it, and the head once (only the
    last position's logits are computed)."""
    layers = lm_matmul_params(c) - c["hidden_size"] * c["vocab_size"]
    causal = lm_attention_flops(c, 1, 1) * prompt * (prompt + 1) // 2
    return 2 * layers * prompt + causal + 2 * c["hidden_size"] * c["vocab_size"]


def lm_decode_flops(c: dict, context: int) -> int:
    """One decoded token at ``context`` cached positions (itself included)."""
    return 2 * lm_matmul_params(c) + lm_attention_flops(c, 1, context)


def cnn_flops(c: dict) -> int:
    """One inference of a CNN config: im2col convs and the FC layer."""
    h, w = c["input_hw"]
    total = 0
    for conv in c["convs"]:
        s = conv["stride"]
        h, w = -(-h // s), -(-w // s)
        total += 2 * h * w * conv["kh"] * conv["kw"] * conv["c_in"] * conv["c_out"]
    return total + 2 * c["fc_width"] * c["n_classes"]
