"""The LM family: one model definition covering all 10 assigned architectures.

Families (ModelConfig.family):
  dense   -- llama3.2-3b, tinyllama-1.1b, olmo-1b, qwen2-72b
  moe     -- llama4-maverick (128e top-1, interleaved, shared expert),
             phi3.5-moe (16e top-2)
  ssm     -- mamba2-2.7b (attention-free SSD)
  hybrid  -- recurrentgemma-9b (2x RG-LRU : 1x local attention)
  audio   -- musicgen-large (decoder over EnCodec frames; frontend stubbed)
  vlm     -- paligemma-3b (SigLIP patches stubbed, gemma decoder)

Structure: layers are grouped into the architecture's repeating *period*
(dense: [attn]; llama4: [attn, moe]; recurrentgemma: [rec, rec, attn]) and
the period-group stack is evaluated with lax.scan -- essential to keep HLO
size and compile time bounded at 80-layer/512-device scale. Layers left over
when n_layers % period != 0 run unscanned (recurrentgemma: 38 = 12*3 + 2).

Every projection is an AnalogLinear: the paper's noise-injection + DAC/ADC
training and PCM inference apply to the full LM family through the same
AnalogCtx used by the TinyML models.

Analog deployment is program-once / execute-many: ``engine.compile_program``
walks LMParams (NamedTuple + stacked block pytrees are handled generically),
applies the PCM chain to every projection a single time, and returns
programmed params that drop straight into :func:`lm_forward` with the
program's ``pcm_programmed`` config -- no per-step RNG, no weight-domain
work inside the decode loop. See launch/serve.py.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.analog import (
    AnalogConfig, AnalogCtx, linear_apply, linear_init, proj,
)
from repro.models import attention as attn_lib
from repro.models import griffin as griffin_lib
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models.common import (
    ModelConfig,
    embedding_apply,
    embedding_init,
    rmsnorm_apply,
    rmsnorm_init,
    shard,
)

Array = jax.Array


# ---------------------------------------------------------------------------
# Block types
# ---------------------------------------------------------------------------


def block_period(cfg: ModelConfig) -> list[str]:
    if cfg.family == "ssm":
        return ["ssm"]
    if cfg.family == "hybrid":
        return list(cfg.block_pattern) or ["rec", "rec", "attn"]
    if cfg.family == "moe":
        if cfg.moe_every <= 1:
            return ["moe"]
        return ["attn"] * (cfg.moe_every - 1) + ["moe"]
    return ["attn"]  # dense / audio / vlm


def mlp_init(key: Array, cfg: ModelConfig) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w1": linear_init(k1, cfg.d_model, cfg.d_ff),
        "w3": linear_init(k3, cfg.d_model, cfg.d_ff),
        "w2": linear_init(k2, cfg.d_ff, cfg.d_model),
    }


def mlp_apply(params: dict, x: Array, ctx: AnalogCtx) -> Array:
    h = jax.nn.silu(proj(params, "w1", x, ctx)) * proj(params, "w3", x, ctx)
    h = shard(h, "batch", None, "ffn")
    return proj(params, "w2", h, ctx)


def _block_init(key: Array, kind: str, cfg: ModelConfig) -> dict:
    km, kf, kn1, kn2 = jax.random.split(key, 4)
    params: dict[str, Any] = {"norm1": rmsnorm_init(cfg)}
    if kind == "ssm":
        params["ssm"] = ssm_lib.ssm_init(km, cfg)
        return params
    params["norm2"] = rmsnorm_init(cfg)
    if kind == "attn":
        params["attn"] = attn_lib.attn_init(km, cfg)
        params["ffn"] = mlp_init(kf, cfg)
    elif kind == "moe":
        params["attn"] = attn_lib.attn_init(km, cfg)
        params["moe"] = moe_lib.moe_init(kf, cfg)
    elif kind == "rec":
        params["rec"] = griffin_lib.griffin_init(km, cfg)
        params["ffn"] = mlp_init(kf, cfg)
    elif kind == "lattn":  # local-window attention (hybrid family)
        params["attn"] = attn_lib.attn_init(km, cfg)
        params["ffn"] = mlp_init(kf, cfg)
    else:
        raise ValueError(kind)
    return params


def _slice_cache(cache, layer_idx):
    if cache is None or layer_idx is None:
        return cache
    return jax.tree.map(lambda x: x[layer_idx], cache)


def _writeback_cache(full, new, layer_idx):
    if full is None or layer_idx is None:
        return new
    return jax.tree.map(
        lambda f, n: jax.lax.dynamic_update_index_in_dim(f, n.astype(f.dtype), layer_idx, 0),
        full,
        new,
    )


def _block_apply(
    params: dict,
    kind: str,
    x: Array,
    ctx: AnalogCtx,
    cfg: ModelConfig,
    positions: Array,
    cache,
    layer_idx=None,
):
    """One block: norm -> mixer -> residual [-> norm -> ffn -> residual].

    ``layer_idx``: when set, ``cache`` is layer-stacked (decode unrolled
    path); attention writes the new token into the stacked buffer in place,
    while the small SSM/RG-LRU states use slice + write-back.
    """
    h = rmsnorm_apply(params["norm1"], x, cfg.norm_eps)
    if kind == "ssm":
        out, nc = ssm_lib.ssm_apply(
            params["ssm"], h, ctx, cfg, _slice_cache(cache, layer_idx)
        )
        return x + out, _writeback_cache(cache, nc, layer_idx)
    if kind == "rec":
        out, nc = griffin_lib.griffin_apply(
            params["rec"], h, ctx, cfg, _slice_cache(cache, layer_idx)
        )
        new_cache = _writeback_cache(cache, nc, layer_idx)
    else:
        window = cfg.local_window if cfg.family == "hybrid" else None
        with jax.named_scope("attn"):
            out, new_cache = attn_lib.attn_apply(
                params["attn"], h, ctx, cfg, positions=positions, cache=cache,
                window=window, layer_idx=layer_idx,
            )
    x = x + out
    h = rmsnorm_apply(params["norm2"], x, cfg.norm_eps)
    if kind == "moe":
        if cfg.moe_dispatch == "shard_map":
            from repro.models.moe_shardmap import moe_apply_shardmap

            x = x + moe_apply_shardmap(params["moe"], h, ctx, cfg)
        else:
            x = x + moe_lib.moe_apply(params["moe"], h, ctx, cfg)
    else:
        with jax.named_scope("mlp"):
            x = x + mlp_apply(params["ffn"], h, ctx)
    return x, new_cache


def _block_cache(
    kind: str, cfg: ModelConfig, batch: int, s_max: int, dtype,
    per_slot: bool = False,
    paged: bool = False,
    page_size: int = 16,
    n_pages: int = 0,
):
    if kind == "ssm":
        return ssm_lib.init_ssm_cache(cfg, batch, dtype)
    if kind == "rec":
        return griffin_lib.init_rglru_cache(cfg, batch, dtype)
    # local attention needs only a window-sized cache; decode_32k/long_500k
    # feasibility for the hybrid family rests on this bound.
    if cfg.family == "hybrid":
        s_max = min(s_max, cfg.local_window)
    if paged:
        return attn_lib.init_paged_cache(
            cfg, batch, s_max, dtype, page_size=page_size, n_pages=n_pages
        )
    return attn_lib.init_cache(cfg, batch, s_max, dtype, per_slot=per_slot)


# ---------------------------------------------------------------------------
# Model init / apply
# ---------------------------------------------------------------------------


class LMParams(NamedTuple):
    embed: dict
    blocks: Any  # stacked (n_groups, ...) pytree of period params
    tail: tuple  # leftover (unscanned) block params
    final_norm: dict
    lm_head: dict
    extras: dict  # frontend projections etc.
    gain_s: Array  # network-wide ADC gain S (Eq. 5)


def lm_init(key: Array, cfg: ModelConfig) -> LMParams:
    period = block_period(cfg)
    n_groups = cfg.n_layers // len(period)
    n_tail = cfg.n_layers - n_groups * len(period)
    k_embed, k_blocks, k_tail, k_head, k_extra = jax.random.split(key, 5)

    def init_group(gk: Array) -> tuple:
        keys = jax.random.split(gk, len(period))
        return tuple(_block_init(keys[i], kind, cfg) for i, kind in enumerate(period))

    group_keys = jax.random.split(k_blocks, n_groups)
    blocks = jax.vmap(init_group)(group_keys)

    tail = tuple(
        _block_init(jax.random.fold_in(k_tail, i), period[i % len(period)], cfg)
        for i in range(n_tail)
    )

    extras: dict[str, Any] = {}
    if cfg.frontend == "vision_patches":
        extras["patch_proj"] = linear_init(k_extra, cfg.d_model, cfg.d_model)

    head_out = cfg.vocab * max(cfg.n_codebooks, 1)
    return LMParams(
        embed=embedding_init(k_embed, cfg.vocab, cfg.d_model),
        blocks=blocks,
        tail=tail,
        final_norm=rmsnorm_init(cfg),
        lm_head=linear_init(k_head, cfg.d_model, head_out),
        extras=extras,
        gain_s=jnp.ones((), jnp.float32),
    )


def _embed_inputs(params: LMParams, batch: dict, cfg: ModelConfig, ctx: AnalogCtx):
    """Token / frame / patch embedding with modality stubs."""
    if cfg.frontend == "audio_frames":
        # musicgen: precomputed EnCodec frame embeddings (assignment stub)
        h = batch["frames"].astype(cfg.dtype)
    elif cfg.frontend == "vision_patches" and "patches" in batch:
        tok = embedding_apply(params.embed, batch["tokens"], cfg.dtype)
        patches = linear_apply(
            params.extras["patch_proj"], batch["patches"].astype(cfg.dtype), ctx
        )
        h = jnp.concatenate([patches, tok], axis=1)
    else:
        h = embedding_apply(params.embed, batch["tokens"], cfg.dtype)
    return shard(h, "batch", None, None)


def lm_forward(
    params: LMParams,
    batch: dict,
    analog_cfg: AnalogConfig,
    cfg: ModelConfig,
    *,
    rng: Optional[Array] = None,
    cache: Optional[tuple] = None,
    last_token_only: bool = False,
    last_index: Optional[Array] = None,
):
    """Forward pass. Returns (logits, new_cache).

    ``cache`` is (stacked_group_caches, tail_caches) or None. When
    ``last_token_only`` (prefill serving), only the final position's logits
    are computed -- at 32k x 152k vocab the full logits tensor would be
    hundreds of GB. ``last_index`` (a (B,) int vector, requires
    ``last_token_only``) picks each row's logit position explicitly --
    bucketed prefill right-pads prompts to a shared length, so row ``i``'s
    real last token sits at ``len_i - 1``, not at ``-1``.
    """
    period = block_period(cfg)
    ctx0 = AnalogCtx(cfg=analog_cfg, gain_s=params.gain_s, key=rng)
    with jax.named_scope("embed"):
        h = _embed_inputs(params, batch, cfg, ctx0)
    b, s, _ = h.shape

    if cache is not None:
        group_caches, tail_caches = cache
        # all block caches agree on length; attention caches carry it
        start = _cache_length(group_caches, tail_caches)
    else:
        group_caches, tail_caches = None, None
        start = 0
    if getattr(start, "ndim", 0):
        # per-slot cache (continuous-batching serving): every batch row is
        # an independent request at its own position -> (B, S) positions
        positions = start[:, None] + jnp.arange(s)[None, :]
    else:
        positions = start + jnp.arange(s)[None, :]  # (1, S) broadcasts

    def group_fn(h, group_params, group_cache, group_idx):
        ctx = AnalogCtx(
            cfg=analog_cfg,
            gain_s=params.gain_s,
            key=None if rng is None else jax.random.fold_in(rng, group_idx),
        )
        new_caches = []
        for i, kind in enumerate(period):
            blk_cache = None if group_cache is None else group_cache[i]
            h, nc = _block_apply(
                group_params[i], kind, h, ctx, cfg, positions, blk_cache
            )
            new_caches.append(nc)
        # Megatron-SP-style: the scan carry (== the per-layer residual saved
        # for the rematerialised backward) lives sequence-sharded over the
        # model axis; GSPMD inserts the gather at the next block's first use.
        h = shard(h, "batch", "seq", None)
        return h, tuple(new_caches)

    if cfg.remat:
        group_fn = jax.checkpoint(group_fn, static_argnums=())

    n_groups = cfg.n_layers // len(period)
    if n_groups > 0:
        idxs = jnp.arange(n_groups)

        def scan_body(h, xs):
            gp, gc, gi = xs
            h, nc = group_fn(h, gp, gc, gi)
            return h, nc

        if group_caches is None:
            # dummy per-group cache slot so the scan signature is static
            h, _ = jax.lax.scan(
                lambda hh, xs: group_fn(hh, xs[0], None, xs[1])[:1] + ((),),
                h,
                (params.blocks, idxs),
            )
            new_group_caches = None
        elif isinstance(group_caches, list) or s == 1:
            # Decode: an unrolled layer loop where each layer updates only
            # its OWN cache buffer in place (donated). Under lax.scan the
            # cache must flow xs -> ys, which copies the entire multi-GiB KV
            # cache every step -- measured 2x cache bytes per decode step.
            # The list (unstacked) layout additionally keeps every
            # dynamic-update-slice local to one layer's buffer.
            unstacked = isinstance(group_caches, list)
            new_gcs = []
            gc_cur = group_caches  # stacked path: evolving shared buffers
            for gi in range(n_groups):
                gp = jax.tree.map(lambda x, _gi=gi: x[_gi], params.blocks)
                ctx_g = AnalogCtx(
                    cfg=analog_cfg,
                    gain_s=params.gain_s,
                    key=None if rng is None else jax.random.fold_in(rng, gi),
                )
                new_gc = []
                for i, kind in enumerate(period):
                    if unstacked:
                        h, nc = _block_apply(
                            gp[i], kind, h, ctx_g, cfg, positions,
                            group_caches[gi][i],
                        )
                    else:
                        h, nc = _block_apply(
                            gp[i], kind, h, ctx_g, cfg, positions,
                            gc_cur[i], layer_idx=gi,
                        )
                    new_gc.append(nc)
                if unstacked:
                    new_gcs.append(tuple(new_gc))
                else:
                    gc_cur = tuple(new_gc)
            new_group_caches = new_gcs if unstacked else gc_cur
        else:
            h, new_group_caches = jax.lax.scan(
                scan_body, h, (params.blocks, group_caches, idxs)
            )
    else:
        new_group_caches = group_caches

    new_tail_caches = []
    for i, tp in enumerate(params.tail):
        kind = period[i % len(period)]
        ctx = AnalogCtx(
            cfg=analog_cfg,
            gain_s=params.gain_s,
            key=None if rng is None else jax.random.fold_in(rng, 10_000 + i),
        )
        tc = None if tail_caches is None else tail_caches[i]
        h, nc = _block_apply(tp, kind, h, ctx, cfg, positions, tc)
        new_tail_caches.append(nc)

    with jax.named_scope("head"):
        h = rmsnorm_apply(params.final_norm, h, cfg.norm_eps)
        if last_token_only:
            if last_index is not None:
                h = jnp.take_along_axis(h, last_index[:, None, None], axis=1)
            else:
                h = h[:, -1:, :]
        with jax.named_scope("lm_head"):
            logits = linear_apply(params.lm_head, h, ctx0)
        logits = shard(logits, "batch", None, "vocab")
    if cfg.n_codebooks:
        logits = logits.reshape(*logits.shape[:-1], cfg.n_codebooks, cfg.vocab)

    new_cache = None
    if cache is not None:
        new_cache = (new_group_caches, tuple(new_tail_caches))
    return logits, new_cache


def _cache_length(group_caches, tail_caches) -> Array:
    """Recover the current sequence position from any attention cache.

    Returns a scalar for rectangle-batch caches. For a *slot* cache
    (unstacked layout with per-slot ``KVCache.length`` of shape (B,), see
    :func:`init_lm_cache`), returns the (B,) vector so positions are
    computed per request. Stacked caches prepend a layer axis to the
    length, which is stripped (every layer agrees on the position).
    """
    stacked_groups = not isinstance(group_caches, list)

    def find(c, stacked):
        if isinstance(c, attn_lib.PagedKVCache):
            return c.length  # always (B,): paged caches are per-slot
        if isinstance(c, attn_lib.KVCache):
            ln = c.length
            if stacked and ln.ndim:
                ln = ln[0]  # strip the layer-stack axis
            return ln
        return None

    is_cache = lambda x: isinstance(
        x,
        (
            attn_lib.KVCache,
            attn_lib.PagedKVCache,
            ssm_lib.SSMCache,
            griffin_lib.RGLRUCache,
        ),
    )
    for container, stacked in (
        (group_caches, stacked_groups),
        (tail_caches, False),
    ):
        for leaf in jax.tree.leaves(container, is_leaf=is_cache):
            ln = find(leaf, stacked)
            if ln is not None:
                return ln
    return jnp.zeros((), jnp.int32)  # pure-SSM models are position-free


def init_lm_cache(
    cfg: ModelConfig,
    batch: int,
    s_max: int,
    dtype,
    stacked: bool = True,
    per_slot: bool = False,
    paged: bool = False,
    page_size: int = 16,
    n_pages: Optional[int] = None,
) -> tuple:
    """Build the (group caches, tail caches) pytree.

    ``stacked=True``: one (n_groups, ...) buffer per cache leaf -- required by
    the prefill scan. ``stacked=False``: a *list* of per-group caches --
    the decode layout, where each layer's in-place token write touches only
    its own buffer (a whole-stack dynamic-update-slice costs full-buffer
    traffic in the XLA cost model and defeats donation analysis).

    ``per_slot=True`` (requires ``stacked=False``): the continuous-batching
    *slot* layout (repro.serving) -- attention lengths become (B,) vectors
    so every batch row is an independent request at its own position, and
    :func:`write_cache_slot` / :func:`reset_cache_slot` admit/retire one
    request without touching the other slots.

    ``paged=True`` (requires ``stacked=False``): the block/paged slot layout
    (repro.serving paged mode) -- every attention leaf becomes a
    :class:`repro.models.attention.PagedKVCache` sharing one page-id space
    of ``n_pages`` pages (default: enough to hold ``batch`` max-length
    slots plus the reserved scratch page 0), with ``s_max`` the per-slot
    *virtual* capacity. Slot admission/retirement goes through
    :func:`write_cache_slot_paged` / :func:`free_cache_slot_paged` with
    page ids handed out by the serving engine's allocator.
    """
    if per_slot and stacked:
        raise ValueError(
            "per_slot caches use the unstacked decode layout "
            "(pass stacked=False)"
        )
    if paged:
        if stacked:
            raise ValueError(
                "paged caches use the unstacked decode layout "
                "(pass stacked=False)"
            )
        kinds = set(block_period(cfg))
        if not kinds <= {"attn", "moe"}:
            raise ValueError(
                "paged serving supports attention-cache families only "
                f"(family={cfg.family!r} has blocks {sorted(kinds)}): "
                "SSM/RG-LRU recurrent state is position-free, so the "
                "right-padded bucketed prefill that paging relies on would "
                "fold pad tokens into it"
            )
        if n_pages is None:
            n_pages = batch * (-(-s_max // page_size)) + 1
    period = block_period(cfg)
    n_groups = cfg.n_layers // len(period)
    n_tail = cfg.n_layers - n_groups * len(period)

    def one_group():
        return tuple(
            _block_cache(
                kind, cfg, batch, s_max, dtype, per_slot=per_slot,
                paged=paged, page_size=page_size, n_pages=n_pages or 0,
            )
            for kind in period
        )

    if stacked:
        group = one_group()
        groups = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (n_groups,) + x.shape), group
        )
    else:
        groups = [one_group() for _ in range(n_groups)]
    tail = tuple(
        _block_cache(
            period[i % len(period)], cfg, batch, s_max, dtype,
            per_slot=per_slot,
            paged=paged, page_size=page_size, n_pages=n_pages or 0,
        )
        for i in range(n_tail)
    )
    return groups, tail


def unstack_cache(cache: tuple) -> tuple:
    """Convert a stacked cache (post-prefill) to the decode list layout."""
    groups, tail = cache
    if isinstance(groups, list):
        return cache
    n_groups = jax.tree.leaves(groups)[0].shape[0] if jax.tree.leaves(groups) else 0
    out = [
        jax.tree.map(lambda x, _i=i: x[_i], groups) for i in range(n_groups)
    ]
    return out, tail


# ---------------------------------------------------------------------------
# Cache-slot helpers (continuous-batching serving, repro.serving)
#
# The serving engine owns ONE per-slot decode cache (init_lm_cache with
# stacked=False, per_slot=True) whose batch rows are independent request
# slots. Admitting a request = prefill it alone (batch=1, standard stacked
# cache), unstack, and write every leaf's row into the slot; retiring =
# zero the slot. Both are whole-row, static-shape updates, so one jitted
# computation serves every (slot, request) combination.
# ---------------------------------------------------------------------------


def write_cache_slot(cache: tuple, src: tuple, slot) -> tuple:
    """Write a single-request cache into batch row ``slot`` of a slot cache.

    ``cache``: the shared per-slot decode cache (B slots, unstacked layout,
    per-slot lengths). ``src``: the request's own batch=1 cache in the same
    unstacked layout (prefill + :func:`unstack_cache`), built with the SAME
    ``s_max`` so rows line up. The request's scalar cache length lands in
    the slot's entry of the (B,) length vector; everything else (KV rows,
    SSM/RG-LRU states) is a full-row copy.
    """

    def write(dst, s):
        if dst.ndim == s.ndim:  # (B, ...) <- (1, ...) row copy
            return jax.lax.dynamic_update_index_in_dim(
                dst, s[0].astype(dst.dtype), slot, 0
            )
        # per-slot length vector (B,) <- the request's scalar length
        return dst.at[slot].set(s.astype(dst.dtype))

    return jax.tree.map(write, cache, src)


def reset_cache_slot(cache: tuple, slot) -> tuple:
    """Zero batch row ``slot`` of a per-slot cache (retired-slot hygiene).

    A retired slot keeps stepping with the live batch (its output is
    discarded), so its buffers hold garbage; resetting before re-admission
    keeps the invariant that a freshly admitted request sees exactly the
    state a solo run would.
    """

    def reset(leaf):
        return leaf.at[slot].set(jnp.zeros(leaf.shape[1:], leaf.dtype))

    return jax.tree.map(reset, cache)


# ---------------------------------------------------------------------------
# Paged-cache slot helpers (repro.serving paged mode)
#
# The engine owns ONE paged decode cache (init_lm_cache with stacked=False,
# paged=True): per attention layer a page pool + per-slot page tables, one
# shared page-id space (the allocator hands out ids valid in every layer).
# Admission scatters a request's rectangular prefill cache into its pages
# (the pools are head-major, (n_kv, n_pages, page_size, hd)); growth
# appends a page id to the slot's table; retirement zeroes the slot's
# pages/table/length so the ids can be reissued.
# ---------------------------------------------------------------------------

_is_paged = lambda x: isinstance(x, attn_lib.PagedKVCache)


def write_cache_slot_paged(
    cache: tuple, src: tuple, slot, row, pages, length
) -> tuple:
    """Scatter one request's prefill cache into slot ``slot``'s pages.

    ``src`` is a *rectangular* prefill cache in the unstacked layout
    (bucketed prefill + :func:`unstack_cache`) with ``S_bucket`` rows per
    attention leaf; ``row`` picks the request's batch row (bucketed
    prefill batches several same-bucket requests). ``pages`` is a
    (ceil(S_bucket/page_size),) int32 vector of page ids for this slot --
    entries past the request's real ``ceil(length/page_size)`` pages are
    0, so the pad-region rows of a short prompt land in the scratch page
    instead of costing real pages. ``length`` is the request's true token
    count; decode masks everything past it, so pad-position K/V inside
    the slot's last real page is inert.
    """
    pages = jnp.asarray(pages, jnp.int32)
    nbp = pages.shape[0]
    length = jnp.asarray(length, jnp.int32)

    def write(dst: attn_lib.PagedKVCache, s_leaf: attn_lib.KVCache):
        ps = dst.page_size

        def scatter(pool, rows):
            rows = rows[row].astype(pool.dtype)  # (S_bucket, kv, hd)
            pad = nbp * ps - rows.shape[0]
            if pad:
                rows = jnp.pad(rows, ((0, pad), (0, 0), (0, 0)))
            rows = rows.reshape(nbp, ps, *rows.shape[1:])
            return pool.at[:, pages].set(rows.transpose(2, 0, 1, 3))

        table_row = (
            jnp.zeros((dst.table.shape[1],), jnp.int32).at[:nbp].set(pages)
        )
        return dst._replace(
            k=scatter(dst.k, s_leaf.k),
            v=scatter(dst.v, s_leaf.v),
            table=dst.table.at[slot].set(table_row),
            length=dst.length.at[slot].set(length),
        )

    return jax.tree.map(write, cache, src, is_leaf=_is_paged)


def append_cache_page(cache: tuple, slot, entry, page) -> tuple:
    """Grow slot ``slot`` by one page: table[slot, entry] = page, all layers.

    Called by the engine when a slot's decode position crosses a page
    boundary; the page's stale content is never read (positions past the
    slot's length are masked), so no zeroing is needed on append.
    """

    def app(dst: attn_lib.PagedKVCache):
        return dst._replace(table=dst.table.at[slot, entry].set(page))

    return jax.tree.map(app, cache, is_leaf=_is_paged)


def free_cache_slot_paged(cache: tuple, slot, pages) -> tuple:
    """Retire slot ``slot``: zero its pages, table row, and length.

    ``pages`` is a fixed-width (pages_per_slot,) int32 vector -- the slot's
    real page ids padded with 0s (re-zeroing the scratch page is harmless).
    Zeroing the pool rows keeps the invariant that a freshly admitted
    request sees exactly the state a solo run would, and pins the
    "free leaves other slots' pages bitwise untouched" property.
    """
    pages = jnp.asarray(pages, jnp.int32)

    def free(dst: attn_lib.PagedKVCache):
        n_kv, _, ps, hd = dst.k.shape
        z = jnp.zeros((n_kv, pages.shape[0], ps, hd), dst.k.dtype)
        return dst._replace(
            k=dst.k.at[:, pages].set(z),
            v=dst.v.at[:, pages].set(z),
            table=dst.table.at[slot].set(0),
            length=dst.length.at[slot].set(0),
        )

    return jax.tree.map(free, cache, is_leaf=_is_paged)


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------


def lm_loss(
    params: LMParams,
    batch: dict,
    analog_cfg: AnalogConfig,
    cfg: ModelConfig,
    rng: Optional[Array] = None,
) -> tuple[Array, dict]:
    logits, _ = lm_forward(params, batch, analog_cfg, cfg, rng=rng)
    labels = batch["labels"]
    if cfg.frontend == "vision_patches" and "patches" in batch:
        # image-prefix positions carry no LM loss
        logits = logits[:, batch["patches"].shape[1] :]
    logits = logits.astype(jnp.float32)
    # Sharding-friendly CE: take_along_axis over a vocab-sharded logits
    # tensor forces GSPMD to replicate it (tens of GB at 4k x 152k vocab);
    # the one-hot contraction partitions cleanly with a partial-sum reduce.
    lse = jax.nn.logsumexp(logits, axis=-1)
    onehot = jax.nn.one_hot(labels, cfg.vocab, dtype=logits.dtype)
    onehot = shard(onehot, "batch", None, *([None] * (onehot.ndim - 3) + ["vocab"]))
    ll = jnp.sum(logits * onehot, axis=-1)
    nll = lse - ll
    mask = batch.get("mask")
    if mask is None:
        loss = nll.mean()
    else:
        while mask.ndim < nll.ndim:
            mask = mask[..., None]
        loss = (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    metrics = {"loss": loss, "ppl_proxy": jnp.exp(jnp.minimum(loss, 20.0))}
    return loss, metrics
