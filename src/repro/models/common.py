"""Shared model components: config, norms, RoPE, embeddings, sharding hooks.

The module system is purely functional: every block is an ``init(key, cfg)``
returning a param pytree and an ``apply(params, x, ...)``. Non-trainable
buffers carry the ``_buf`` suffix (masked by the optimizer); every weight
matmul routes through :func:`repro.core.analog.linear_apply`, so the paper's
noise/quant technique is available framework-wide via the AnalogCtx.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.analog import AnalogCtx, linear_apply, linear_init

Array = jax.Array


# ---------------------------------------------------------------------------
# Logical-axis sharding hook. Model code annotates activations with *logical*
# axis names; the launcher maps them onto whatever mesh is active. With no
# mesh (unit tests, CPU smoke runs) the annotation is a no-op.
# ---------------------------------------------------------------------------

# logical name -> mesh axes (None = replicated / not sharded)
_LOGICAL_RULES: dict[str, Any] = {}


def set_logical_rules(rules: dict[str, Any]) -> None:
    _LOGICAL_RULES.clear()
    _LOGICAL_RULES.update(rules)


def logical_rules() -> dict[str, Any]:
    return dict(_LOGICAL_RULES)


def shard(x: Array, *names: Optional[str]) -> Array:
    """Annotate ``x`` with a sharding built from logical axis names."""
    if not _LOGICAL_RULES:
        return x
    # the mesh entered with ``jax.set_mesh`` (empty outside one)
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return x
    spec = P(*[_LOGICAL_RULES.get(n) if n else None for n in names])
    return jax.lax.with_sharding_constraint(x, jax.sharding.NamedSharding(mesh, spec))


# ---------------------------------------------------------------------------
# LM-family configuration (covers all 10 assigned architectures)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str = "model"
    family: str = "dense"  # dense | moe | ssm | hybrid | audio | vlm
    n_layers: int = 2
    d_model: int = 128
    n_heads: int = 2
    n_kv_heads: int = 2
    d_ff: int = 256
    vocab: int = 256
    head_dim: int = 0  # 0 -> d_model // n_heads
    # MoE
    n_experts: int = 0
    top_k: int = 0
    moe_every: int = 1  # an MoE FFN every N layers (llama4 interleaves: 2)
    shared_expert: bool = False
    capacity_factor: float = 1.25
    moe_groups: int = 16  # dispatch groups (GShard-style); >= data shards
    moe_dispatch: str = "einsum"  # einsum (GShard one-hot) | scatter (indexed)
    # SSM (mamba2 / SSD)
    ssm_state: int = 0
    ssm_headdim: int = 64
    ssm_expand: int = 2
    ssm_chunk: int = 256
    conv_width: int = 4
    # hybrid (recurrentgemma / griffin)
    block_pattern: tuple = ()  # e.g. ("rec", "rec", "attn")
    local_window: int = 2048
    lru_width: int = 0  # 0 -> d_model
    # flavor flags
    qkv_bias: bool = False  # qwen2
    nonparametric_ln: bool = False  # olmo
    n_codebooks: int = 0  # musicgen parallel heads
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    # modality stub
    frontend: str = "none"  # none | audio_frames | vision_patches
    num_patches: int = 0  # paligemma: SigLIP tokens prepended
    # attention compute strategy
    attn_chunk_q: int = 512
    attn_chunk_kv: int = 1024
    # precision
    dtype: Any = jnp.bfloat16
    remat: bool = True

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def d_inner(self) -> int:  # mamba2
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_headdim

    def smoke(self) -> "ModelConfig":
        """Reduced config of the same family for CPU smoke tests."""
        return dataclasses.replace(
            self,
            n_layers=max(2, len(self.block_pattern) or 2),
            d_model=64,
            n_heads=4,
            n_kv_heads=max(1, min(self.n_kv_heads, 2)),
            d_ff=128,
            vocab=256,
            head_dim=16,
            n_experts=min(self.n_experts, 4) if self.n_experts else 0,
            top_k=min(self.top_k, 2) if self.top_k else 0,
            moe_groups=2,
            ssm_state=min(self.ssm_state, 16) if self.ssm_state else 0,
            ssm_headdim=16,
            ssm_chunk=16,
            local_window=32,
            lru_width=0,
            num_patches=8 if self.frontend == "vision_patches" else 0,
            attn_chunk_q=16,
            attn_chunk_kv=32,
            dtype=jnp.float32,
            remat=False,
        )


# ---------------------------------------------------------------------------
# Norms / embeddings / rope
# ---------------------------------------------------------------------------


def rmsnorm_init(cfg: ModelConfig, width: int | None = None) -> dict:
    if cfg.nonparametric_ln:
        return {}
    return {"scale": jnp.ones((width or cfg.d_model,), jnp.float32)}


def rmsnorm_apply(params: dict, x: Array, eps: float = 1e-6) -> Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    if "scale" in params:
        x = x * params["scale"]
    return x.astype(dtype)


def embedding_init(key: Array, vocab: int, d_model: int) -> dict:
    return {"table": jax.random.normal(key, (vocab, d_model), jnp.float32) * 0.02}


def embedding_apply(params: dict, tokens: Array, dtype) -> Array:
    return params["table"].astype(dtype)[tokens]


def rope(x: Array, positions: Array, theta: float) -> Array:
    """Rotary embeddings. x: (..., S, H, hd), positions: (..., S)."""
    hd = x.shape[-1]
    half = hd // 2
    # integer iota, then convert: Mosaic (the fused decode grid) has no
    # float iota; the values are identical
    freqs = theta ** (-jnp.arange(half).astype(jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, half)
    cos = jnp.cos(angles)[..., None, :]  # broadcast over heads
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


# Re-exports used across model files
__all__ = [
    "ModelConfig",
    "AnalogCtx",
    "linear_init",
    "linear_apply",
    "rmsnorm_init",
    "rmsnorm_apply",
    "embedding_init",
    "embedding_apply",
    "rope",
    "shard",
    "set_logical_rules",
    "logical_rules",
]
