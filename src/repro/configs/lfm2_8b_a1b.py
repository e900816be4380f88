"""lfm2-8b-a1b [moe]: 24L d_model=2048, 18 gated short-conv + 6 GQA layers.

GQA 32 query / 8 KV heads of 64 with a per-head QK RMSNorm, RoPE theta 1e6,
RMS eps 1e-5, vocab 65536. The first 2 layers have a dense SwiGLU FFN of
7168; every later layer 32 routed experts of 1792, top-4, routed by
sigmoid scores plus an expert bias, no shared expert (dropless dispatch).
Conv layers: in_proj d x 3d, a causal depthwise conv of 3 taps, out_proj.
Source: hf:LiquidAI/LFM2-8B-A1B config.json (``layer_types``,
``num_dense_layers`` 2).
"""

from repro.models.common import ModelConfig

#: ``layer_types`` of the published config
LAYER_TYPES = (
    "conv", "conv", "full_attention", "conv", "conv", "conv",
    "full_attention", "conv", "conv", "conv", "full_attention", "conv",
    "conv", "conv", "full_attention", "conv", "conv", "conv",
    "full_attention", "conv", "conv", "full_attention", "conv", "conv",
)
NUM_DENSE_LAYERS = 2


def layer_kinds(layer_types=LAYER_TYPES, num_dense_layers=NUM_DENSE_LAYERS):
    """Block kinds (models.lm) of LFM2's ``layer_types``: the first
    ``num_dense_layers`` layers' FFN is dense, every later one MoE."""
    kinds = []
    for i, t in enumerate(layer_types):
        attn = t == "full_attention"
        if i < num_dense_layers:
            kinds.append("attn" if attn else "conv")
        else:
            kinds.append("moe" if attn else "conv_moe")
    return tuple(kinds)


def config() -> ModelConfig:
    return ModelConfig(
        name="lfm2-8b-a1b",
        family="moe",
        n_layers=24,
        d_model=2048,
        n_heads=32,
        n_kv_heads=8,
        head_dim=64,
        d_ff=7168,
        moe_d_ff=1792,
        vocab=65536,
        n_experts=32,
        top_k=4,
        moe_dispatch="dropless",
        layer_kinds=layer_kinds(),
        conv_width=3,
        qk_norm=True,
        rope_theta=1_000_000.0,
        norm_eps=1e-5,
    )
