"""One-launch programmed decode: the layer walk as a Pallas grid dimension.

The paper's AON-CiM accelerator is layer-SERIAL precisely to eliminate
inter-layer interconnect cost -- the whole network walks one physical
datapath with weights resident in PCM. The digital twin previously paid the
opposite cost: every decode step threaded ``7 * n_layers + 1`` separate
``execute_mvm`` dispatches (plus norms/attention glue) through XLA, so
launch overhead and HBM weight re-streaming dominated small-batch decode --
the always-on, latency-bound regime AnalogNets targets.

This module executes the ENTIRE programmed decode step as ONE
``pl.pallas_call``:

* grid = ``(n_groups + 1,)`` -- grid step ``g < n_groups`` runs period
  group ``g`` (attention + FFN, all seven projections with their fused
  DAC -> tiled-MVM -> ADC -> GDC ``out_scale`` epilogues); the final step
  runs final-norm + lm_head;
* the per-layer weight stacks, norm scales, and KV blocks are BlockSpec'd
  ``(1, ...)`` slices indexed by ``g``, so Pallas's automatic pipelining
  double-buffers layer ``g+1``'s weights into VMEM while layer ``g``
  computes -- the hardware's "weights stream while the tile computes"
  schedule, for free;
* per-layer GDC/requant scalars (``r_adc``, ``w_max``, ``out_scale``,
  ``gain_s``) live in a scalar-prefetch table (SMEM), indexed by the grid
  step; per-layer ADC bitwidths (mixed-precision ``b_adc_overrides``)
  resolve STATICALLY through :class:`repro.core.engine.FusedDecodePlan` --
  one shared plan per projection across the stacked group, checked at
  ``build_fused_plan`` time;
* the hidden state rides a VMEM scratch buffer across grid steps (the
  layer-serial "one datapath" residual), never touching HBM between
  layers.

Bit-exactness contract: the kernel body calls the SAME library ops as the
per-layer path (``engine.execute_programmed``, ``common.rmsnorm_apply``/
``rope``, ``attention.decode_attention``) at the
same shapes and in the same order, and the KV write is a positional select
of identical values -- so in interpret mode (every non-TPU host) the ADC
codes are bit-identical to ``lm_forward``'s unfused decode, which the
tests pin down exactly. On a TPU host (``jax.default_backend() == "tpu"``)
the plan flips ``interpret=False``, and Mosaic refuses the grid: the
in-kernel ``attention.decode_attention`` einsum has two batch dims ("Up to
1 batch dim supported"), and at published widths the whole-layer weight
blocks would not fit VMEM either (``tests/test_tpu_compile.py`` pins the
refusal). The >= 1.3x tokens/s claim of the ``decode_step_fused`` bench
row has therefore never been measured.

Per-MVM read-noise resampling (``resample_read_noise`` programs executed
with an RNG) re-draws the effective weight stacks OUTSIDE the kernel with
exactly the per-layer fold-in keys ``AnalogCtx.next_key`` would produce
(wq=1, wk=2, wv=3, wo=4, w1=5, w3=6, w2=7 under ``fold_in(rng, layer)``;
lm_head = counter 1 under the unfolded ``rng``), so the streamed weights
match the per-layer path draw for draw.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import engine as engine_lib
from repro.models import attention as attn_lib
from repro.models.common import (
    ModelConfig,
    embedding_apply,
    rmsnorm_apply,
    rope,
)

Array = jax.Array


# ---------------------------------------------------------------------------
# Fused slot cache: one stacked (L, B, S, kv, hd) KV buffer
#
# The serving engine's unfused decode keeps an UNSTACKED per-slot cache (a
# list of per-group KVCaches) so each layer's dynamic-update-slice stays
# local to its own buffer. The fused grid wants the opposite layout: one
# stacked buffer whose leading axis is the grid dimension, so layer g's KV
# block is a BlockSpec slice. Same values, different shape.
# ---------------------------------------------------------------------------


def init_fused_cache(
    cfg: ModelConfig, n_groups: int, batch: int, s_max: int, dtype
) -> attn_lib.KVCache:
    """Stacked per-slot decode cache for the fused grid.

    ``k``/``v``: (n_groups, B, s_max, kv_heads, hd); ``length``: (B,) --
    one shared per-slot length vector (every attention layer of a decode
    step advances together, so one vector serves all layers).
    """
    shape = (n_groups, batch, s_max, cfg.n_kv_heads, cfg.hd)
    return attn_lib.KVCache(
        k=jnp.zeros(shape, dtype),
        v=jnp.zeros(shape, dtype),
        length=jnp.zeros((batch,), jnp.int32),
    )


def write_fused_slot(
    fused: attn_lib.KVCache, src: tuple, slot
) -> attn_lib.KVCache:
    """Write a prefilled request cache into batch row ``slot``.

    ``src`` is the standard unstacked batch=1 prefill cache
    (``lm.unstack_cache`` output): a list of per-group ``(KVCache,)``
    tuples with k/v (1, S, kv, hd) and scalar lengths. Rows are restacked
    along the fused leading axis -- a pure layout change, value for value
    identical to ``lm.write_cache_slot`` on the unstacked cache.
    """
    groups, _tails = src
    k_new = jnp.stack([g[0].k[0] for g in groups]).astype(fused.k.dtype)
    v_new = jnp.stack([g[0].v[0] for g in groups]).astype(fused.v.dtype)
    return attn_lib.KVCache(
        k=jax.lax.dynamic_update_index_in_dim(fused.k, k_new, slot, 1),
        v=jax.lax.dynamic_update_index_in_dim(fused.v, v_new, slot, 1),
        length=fused.length.at[slot].set(
            groups[0][0].length.astype(jnp.int32)
        ),
    )


def reset_fused_slot(fused: attn_lib.KVCache, slot) -> attn_lib.KVCache:
    """Zero batch row ``slot`` across every layer (retired-slot hygiene)."""
    return attn_lib.KVCache(
        k=fused.k.at[:, slot].set(jnp.zeros(fused.k.shape[2:], fused.k.dtype)),
        v=fused.v.at[:, slot].set(jnp.zeros(fused.v.shape[2:], fused.v.dtype)),
        length=fused.length.at[slot].set(0),
    )


# ---------------------------------------------------------------------------
# The megakernel body
# ---------------------------------------------------------------------------


def _decode_kernel(
    tab_ref,  # (L+1, 7, 3) f32 scalar-prefetch: [r_adc, w_max, out_scale]
    h0_ref,  # (B, 1, D) embedded token (grid-constant)
    lens_ref,  # (B, 1) int32 per-slot positions (grid-constant)
    n1_ref,  # (1, 1, D) layer g's norm1 scale
    n2_ref,  # (1, 1, D) layer g's norm2 scale
    wq_ref,  # (1, D, nh*hd) layer g's projection weights ...
    wk_ref,
    wv_ref,
    wo_ref,
    w1_ref,
    w3_ref,
    w2_ref,
    kc_ref,  # (1, B, S, kv, hd) layer g's KV block (read side)
    vc_ref,
    fin_ref,  # (1, D) final-norm scale (grid-constant)
    wh_ref,  # (D, V) lm_head weights (grid-constant)
    logits_ref,  # (B, 1, V) out, written at the head step
    ko_ref,  # (1, B, S, kv, hd) layer g's KV block (write side)
    vo_ref,
    h_ref,  # (B, 1, D) VMEM scratch: the layer-serial residual stream
    *,
    plan: "engine_lib.FusedDecodePlan",
    cfg: ModelConfig,
):
    n_groups = plan.n_groups
    g = pl.program_id(0)
    # step 0 seeds the residual stream from the embedded token; every later
    # step continues from the scratch carry (VMEM-resident across the walk)
    x = jnp.where(g == 0, h0_ref[...], h_ref[...])
    gain_s = tab_ref[n_groups, 1, 0]

    def proj(h, w, row, p_idx, pplan):
        # one programmed MVM: DAC quant -> tiled crossbar MVM with per-tile
        # ADC requant at the plan's static bitwidth -> GDC out_scale. Same
        # library calls as analog.analog_matmul's pcm_programmed execute,
        # so the codes are bit-identical to the per-layer path.
        return engine_lib.execute_programmed(
            h, w, tab_ref[row, p_idx, 0], gain_s, tab_ref[row, p_idx, 1],
            pplan, out_scale=tab_ref[row, p_idx, 2],
        )

    @pl.when(g < n_groups)
    def _layer():
        pp = plan.proj_plans
        nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
        b = x.shape[0]
        s_max = kc_ref.shape[2]
        lens = lens_ref[...]  # (B, 1): each slot's own position

        h1 = rmsnorm_apply({"scale": n1_ref[0]}, x, cfg.norm_eps)
        q = attn_lib._split_heads(proj(h1, wq_ref[0], g, 0, pp[0]), nh, hd)
        k = attn_lib._split_heads(proj(h1, wk_ref[0], g, 1, pp[1]), nkv, hd)
        v = attn_lib._split_heads(proj(h1, wv_ref[0], g, 2, pp[2]), nkv, hd)
        q = rope(q, lens, cfg.rope_theta)
        k = rope(k, lens, cfg.rope_theta)

        # positional select == the unfused path's per-slot
        # dynamic_update_slice: identical values copied at identical rows
        # (serving guarantees lens < s_max), expressed as a dense mask so
        # the whole (B, S) block writes in one shot
        ln = lens[:, 0]
        pos = jax.lax.broadcasted_iota(jnp.int32, (b, s_max), 1)
        write = (pos == ln[:, None])[:, :, None, None]
        ck = jnp.where(write, k.astype(kc_ref.dtype), kc_ref[0])
        cv = jnp.where(write, v.astype(vc_ref.dtype), vc_ref[0])
        out = attn_lib.decode_attention(q, attn_lib.KVCache(ck, cv, ln + 1))
        out = out.reshape(b, 1, nh * hd)

        x1 = x + proj(out, wo_ref[0], g, 3, pp[3])
        h2 = rmsnorm_apply({"scale": n2_ref[0]}, x1, cfg.norm_eps)
        ff = proj(
            jax.nn.silu(proj(h2, w1_ref[0], g, 4, pp[4]))
            * proj(h2, w3_ref[0], g, 5, pp[5]),
            w2_ref[0],
            g,
            6,
            pp[6],
        )
        h_ref[...] = x1 + ff
        ko_ref[0] = ck
        vo_ref[0] = cv

    @pl.when(g == n_groups)
    def _head():
        hn = rmsnorm_apply({"scale": fin_ref[0]}, x, cfg.norm_eps)
        logits_ref[...] = proj(hn, wh_ref[...], n_groups, 0, plan.head_plan)


# ---------------------------------------------------------------------------
# Host-side wrapper
# ---------------------------------------------------------------------------


def _resampled_stacks(params, analog_cfg, rng):
    """Effective weight stacks, re-drawing read noise when asked.

    Mirrors ``AnalogCtx.next_key`` exactly: the counter advances once per
    projection that carries a ``read_buf`` (call order wq, wk, wv, wo, w1,
    w3, w2 under the per-layer ``fold_in(rng, g)`` key; lm_head is counter
    1 under the engine rng itself), so each layer's fresh draw is the one
    the per-layer path would make.
    """
    block = params.blocks[0]
    head = params.lm_head
    resample = analog_cfg.resample_read_noise and rng is not None
    n_groups = int(block["attn"]["wq"]["w"].shape[0])

    stacks = []
    counter = 0
    for path in engine_lib.FUSED_PROJS:
        kind, name = path.split("/")
        pp = block[kind][name]
        if analog_cfg.resample_read_noise and "read_buf" in pp:
            counter += 1
        if resample and "read_buf" in pp:
            c = counter
            stacks.append(
                jnp.stack([
                    engine_lib.resample_read(
                        jax.random.fold_in(jax.random.fold_in(rng, gi), c),
                        jax.tree.map(lambda a, _gi=gi: a[_gi], pp["read_buf"]),
                    )
                    for gi in range(n_groups)
                ]).astype(pp["w"].dtype)
            )
        else:
            stacks.append(pp["w"])

    if resample and "read_buf" in head:
        w_head = engine_lib.resample_read(
            jax.random.fold_in(rng, 1), head["read_buf"]
        ).astype(head["w"].dtype)
    else:
        w_head = head["w"]
    return stacks, w_head


def _scalar_table(params, n_groups: int) -> Array:
    """(L+1, 7, 3) f32 SMEM table: [r_adc, w_max, gdc out_scale] per
    (grid step, projection); row L col 0 is the lm_head, row L col 1
    carries the network-wide ADC gain S."""
    block = params.blocks[0]
    head = params.lm_head
    tab = jnp.zeros((n_groups + 1, len(engine_lib.FUSED_PROJS), 3), jnp.float32)
    for p, path in enumerate(engine_lib.FUSED_PROJS):
        kind, name = path.split("/")
        pp = block[kind][name]
        tab = tab.at[:n_groups, p, 0].set(pp["r_adc"].astype(jnp.float32))
        tab = tab.at[:n_groups, p, 1].set(
            pp["w_clip_buf"][..., 1].astype(jnp.float32)
        )
        tab = tab.at[:n_groups, p, 2].set(
            pp["out_scale_buf"].astype(jnp.float32)
        )
    tab = tab.at[n_groups, 0, 0].set(head["r_adc"].astype(jnp.float32))
    tab = tab.at[n_groups, 0, 1].set(
        head["w_clip_buf"][..., 1].astype(jnp.float32)
    )
    tab = tab.at[n_groups, 0, 2].set(head["out_scale_buf"].astype(jnp.float32))
    tab = tab.at[n_groups, 1, 0].set(params.gain_s.astype(jnp.float32))
    return tab


def fused_decode_step(
    params,
    tok: Array,
    cache: attn_lib.KVCache,
    plan: "engine_lib.FusedDecodePlan",
    model_cfg: ModelConfig,
    analog_cfg,
    *,
    rng: Array | None = None,
):
    """One decode step for the whole programmed model in ONE kernel launch.

    ``tok``: (B, 1) int32; ``cache``: the :func:`init_fused_cache` layout.
    Returns ``(logits (B, 1, V), new_cache)`` with every slot's position
    advanced by one -- the exact values ``lm_forward``'s unfused decode
    produces on the unstacked per-slot cache.
    """
    cfg = model_cfg
    n_groups = plan.n_groups
    h0 = embedding_apply(params.embed, tok, cfg.dtype)
    b, _, d = h0.shape
    s_max = int(cache.k.shape[2])
    kv, hd = cfg.n_kv_heads, cfg.hd
    lens = cache.length[:, None]  # (B, 1)

    stacks, w_head = _resampled_stacks(params, analog_cfg, rng)
    tab = _scalar_table(params, n_groups)
    block = params.blocks[0]
    # (L, 1, D): the block's last two dims then equal the array's, which
    # Mosaic requires of a (1, D) slice (second-minor 1 is not 8-aligned)
    ones_ld = jnp.ones((n_groups, d), jnp.float32)
    n1 = block["norm1"].get("scale", ones_ld)[:, None, :]
    n2 = block["norm2"].get("scale", ones_ld)[:, None, :]
    fin = params.final_norm.get(
        "scale", jnp.ones((d,), jnp.float32)
    )[None, :]
    vocab = int(w_head.shape[-1])

    def _const(*zeros):
        return lambda g, _tab, _z=zeros: _z

    def _at_layer(n_extra_zeros):
        # layer-indexed blocks; the head step (g == L) revisits block L-1,
        # which Pallas's pipeline recognizes as "already resident" -- no
        # extra fetch, no extra writeback
        zeros = (0,) * n_extra_zeros
        return lambda g, _tab: (jnp.minimum(g, n_groups - 1),) + zeros

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_groups + 1,),
        in_specs=[
            pl.BlockSpec((b, 1, d), _const(0, 0, 0)),  # h0
            pl.BlockSpec((b, 1), _const(0, 0)),  # lens
            pl.BlockSpec((1, 1, d), _at_layer(2)),  # norm1 scale
            pl.BlockSpec((1, 1, d), _at_layer(2)),  # norm2 scale
        ]
        + [
            pl.BlockSpec((1,) + s.shape[1:], _at_layer(len(s.shape) - 1))
            for s in stacks  # per-layer weight stacks: the VMEM prefetch
        ]
        + [
            pl.BlockSpec((1, b, s_max, kv, hd), _at_layer(4)),  # kc
            pl.BlockSpec((1, b, s_max, kv, hd), _at_layer(4)),  # vc
            pl.BlockSpec((1, d), _const(0, 0)),  # final-norm scale
            pl.BlockSpec((d, vocab), _const(0, 0)),  # lm_head
        ],
        out_specs=[
            pl.BlockSpec((b, 1, vocab), _const(0, 0, 0)),  # logits
            pl.BlockSpec((1, b, s_max, kv, hd), _at_layer(4)),  # ko
            pl.BlockSpec((1, b, s_max, kv, hd), _at_layer(4)),  # vo
        ],
        scratch_shapes=[pltpu.VMEM((b, 1, d), h0.dtype)],
    )
    logits, ko, vo = pl.pallas_call(
        functools.partial(_decode_kernel, plan=plan, cfg=cfg),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, 1, vocab), h0.dtype),
            jax.ShapeDtypeStruct(cache.k.shape, cache.k.dtype),
            jax.ShapeDtypeStruct(cache.v.shape, cache.v.dtype),
        ],
        interpret=plan.interpret,
    )(tab, h0, lens, n1, n2, *stacks, cache.k, cache.v, fin, w_head)
    return logits, attn_lib.KVCache(ko, vo, cache.length + 1)
