"""Attention: GQA projections (analog-mapped) + digital score/value compute.

Three execution paths, selected by input shape/cache:
  * training / short prefill  -- chunked online-softmax ("flash"-style) scan,
    O(chunk^2) live memory instead of O(S^2): mandatory at 32k context;
  * decode                    -- one query token against a KV cache (over a
    paged cache on a TPU: the Pallas paged-attention kernel, see
    PagedKVCache);
  * local (sliding-window)    -- banded variant used by recurrentgemma.

Per the paper's hardware model, Q/K/V/O *projections* are stationary-weight
matmuls (analog-CiM-mapped via AnalogLinear); the QK^T and AV products have
two dynamic operands and cannot live in NVM crossbars -- they execute on the
digital datapath (DESIGN.md SecArch-applicability). On TPU both are MXU
matmuls; the distinction matters for the AON-CiM energy model only.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.extend import core as jex_core
from jax.interpreters import mlir

from repro.core.analog import AnalogCtx, linear_init, proj
from repro.models.common import (
    ModelConfig, rmsnorm_apply, rmsnorm_init, rope, shard,
)

Array = jax.Array

NEG_INF = -1e30


class KVCache(NamedTuple):
    k: Array  # (B, S_max, n_kv, hd)
    v: Array  # (B, S_max, n_kv, hd)
    #: tokens already written. () int32 for a rectangle batch (every row
    #: advances in lockstep); (B,) int32 for a *slot* cache (continuous-
    #: batching serving, repro.serving), where each batch row is an
    #: independent request at its own position.
    length: Array


def attn_init(key: Array, cfg: ModelConfig) -> dict:
    kq, kk, kv, ko = jax.random.split(key, 4)
    hd, nh, nkv = cfg.hd, cfg.n_heads, cfg.n_kv_heads
    params = {
        "wq": linear_init(kq, cfg.d_model, nh * hd, use_bias=cfg.qkv_bias),
        "wk": linear_init(kk, cfg.d_model, nkv * hd, use_bias=cfg.qkv_bias),
        "wv": linear_init(kv, cfg.d_model, nkv * hd, use_bias=cfg.qkv_bias),
        "wo": linear_init(ko, nh * hd, cfg.d_model),
    }
    if cfg.qk_norm:
        params["q_norm"] = rmsnorm_init(cfg, hd)
        params["k_norm"] = rmsnorm_init(cfg, hd)
    return params


def _split_heads(x: Array, n: int, hd: int) -> Array:
    return x.reshape(*x.shape[:-1], n, hd)


def _gqa_scores(q: Array, k: Array) -> Array:
    """q: (B, Sq, H, D), k: (B, Sk, Kv, D) -> (B, Kv, G, Sq, Sk)."""
    b, sq, h, d = q.shape
    kv = k.shape[2]
    g = h // kv
    qg = q.reshape(b, sq, kv, g, d)
    return jnp.einsum(
        "bqkgd,bskd->bkgqs", qg, k, preferred_element_type=jnp.float32
    )


def _gqa_values(p: Array, v: Array) -> Array:
    """p: (B, Kv, G, Sq, Sk), v: (B, Sk, Kv, D) -> (B, Sq, H, D).

    p is cast down to v's dtype (not v up to f32 -- that would materialise an
    f32 copy of the entire KV cache); accumulation stays f32 on the MXU.
    """
    b, kv, g, sq, sk = p.shape
    out = jnp.einsum(
        "bkgqs,bskd->bqkgd",
        p.astype(v.dtype),
        v,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(b, sq, kv * g, v.shape[-1])


def chunked_attention(
    q: Array,
    k: Array,
    v: Array,
    *,
    q_chunk: int,
    kv_chunk: int,
    causal: bool = True,
    window: Optional[int] = None,
    q_offset: int | Array = 0,
) -> Array:
    """Online-softmax attention, O(q_chunk * kv_chunk) live score memory.

    q: (B, Sq, H, D); k, v: (B, Sk, Kv, D). GQA by head grouping. ``window``
    bounds attention to the last ``window`` positions (local attention).
    ``q_offset`` is the absolute position of q[0] (prefill continuation).

    The kv reduction is *shape-stable*: ``kv_chunk`` is never clamped to the
    sequence length, so a short sequence pads up to one full chunk instead
    of shrinking the chunk. Padded/masked positions contribute exact zeros
    to an identically-shaped per-chunk reduction, which makes the outputs at
    real positions bitwise independent of right-padding -- the property
    bucketed prefill (repro.serving paged mode) relies on for its
    generations to be bit-identical to exact-length prefill.
    """
    b, sq, h, d = q.shape
    sk = k.shape[1]
    scale = d**-0.5
    q_chunk = min(q_chunk, sq)
    sq_p = -(-sq // q_chunk) * q_chunk
    sk_p = -(-sk // kv_chunk) * kv_chunk
    if sq_p != sq:
        q = jnp.pad(q, ((0, 0), (0, sq_p - sq), (0, 0), (0, 0)))
    if sk_p != sk:
        k = jnp.pad(k, ((0, 0), (0, sk_p - sk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, sk_p - sk), (0, 0), (0, 0)))
    sk_valid, sq_orig = sk, sq
    sq, sk = sq_p, sk_p
    nq, nk = sq // q_chunk, sk // kv_chunk
    kvh = k.shape[2]
    g = h // kvh

    qs = q.reshape(b, nq, q_chunk, h, d).swapaxes(0, 1)  # (nq, B, qc, H, D)
    ks = k.reshape(b, nk, kv_chunk, kvh, d).swapaxes(0, 1)
    vs = v.reshape(b, nk, kv_chunk, kvh, d).swapaxes(0, 1)

    q_pos_base = jnp.arange(q_chunk)
    k_pos_base = jnp.arange(kv_chunk)

    @jax.checkpoint
    def q_step(_, qi_qc):
        qi, qc = qi_qc
        q_pos = q_offset + qi * q_chunk + q_pos_base  # (qc,)

        # Flash-style backward: without rematerialisation lax.scan saves the
        # (B, Kv, G, qc, kc) probability tensor of EVERY kv step for the VJP
        # -- O(S^2) residant memory, exactly what chunking is meant to avoid.
        # Checkpointing the body recomputes p in the backward pass.
        @jax.checkpoint
        def kv_step(carry, ki_kc):
            m, l, acc = carry
            ki, kc, vc = ki_kc
            s = _gqa_scores(qc, kc) * scale  # (B, Kv, G, qc, kc) f32
            k_pos = ki * kv_chunk + k_pos_base
            mask = k_pos[None, :] < sk_valid  # padded kv positions
            if causal:
                mask &= q_pos[:, None] >= k_pos[None, :]
            if window is not None:
                mask &= q_pos[:, None] - k_pos[None, :] < window
            mask = jnp.broadcast_to(mask, (q_chunk, kv_chunk))
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = l * alpha + p.sum(axis=-1)
            # PV product with bf16 operands + f32 MXU accumulation: keeping
            # p (B,Kv,G,qc,kc) in f32 and upcasting v doubles the dominant
            # HBM stream of the whole training step (measured 0.8 TB/dev on
            # tinyllama train_4k); max/exp/l stay f32 elementwise.
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bkgqs,bskd->bkgqd",
                p.astype(vc.dtype),
                vc,
                preferred_element_type=jnp.float32,
            )
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, kvh, g, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kvh, g, q_chunk), jnp.float32)
        a0 = jnp.zeros((b, kvh, g, q_chunk, d), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0), (jnp.arange(nk), ks, vs)
        )
        out = acc / jnp.maximum(l[..., None], 1e-30)  # (B, Kv, G, qc, D)
        out = out.transpose(0, 3, 1, 2, 4).reshape(b, q_chunk, h, d)
        return None, out.astype(q.dtype)

    _, outs = jax.lax.scan(q_step, None, (jnp.arange(nq), qs))
    out = outs.swapaxes(0, 1).reshape(b, sq, h, d)
    return out[:, :sq_orig]


def decode_attention(
    q: Array, cache: KVCache, *, rolling: bool = False
) -> Array:
    """One-token attention against the cache. q: (B, 1, H, D).

    ``rolling``: the cache is a circular window buffer (local attention);
    every written slot is by construction within the window, so validity is
    simply "slot has been written".
    """
    b, _, h, d = q.shape
    s_max = cache.k.shape[1]
    scale = d**-0.5
    s = _gqa_scores(q, cache.k) * scale  # (B, Kv, G, 1, S_max)
    pos = jnp.arange(s_max)
    limit = jnp.minimum(cache.length, s_max) if rolling else cache.length
    if cache.length.ndim:
        # per-slot lengths: each batch row is an independent request
        valid = pos[None, :] < limit[:, None]  # (B, S_max)
        s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
    else:
        valid = pos[None, :] < limit
        s = jnp.where(valid[None, None, None], s, NEG_INF)
    # normalize AFTER the PV product, as chunked_attention does: the bf16
    # cast then rounds the same unnormalized probabilities in both paths,
    # so a cached decode step reproduces the full forward's attention
    # (normalizing first differs from it by ~2^-9 per probability in bf16)
    p = jnp.exp(s - s.max(axis=-1, keepdims=True))
    l = p.sum(axis=-1)  # (B, Kv, G, 1)
    out = _gqa_values(p, cache.v)  # (B, 1, H, D) f32
    b, kv, g, sq = l.shape
    l = l.transpose(0, 3, 1, 2).reshape(b, sq, kv * g, 1)
    return (out / jnp.maximum(l, 1e-30)).astype(q.dtype)


class PagedKVCache(NamedTuple):
    """Block/paged KV cache (the vLLM idiom): a pool of fixed-size pages
    shared by every request slot, plus a per-slot page table.

    Slots no longer own a worst-case (B, S_max) rectangle -- each holds
    ``ceil(length / page_size)`` pages, so resident KV memory tracks actual
    usage, not provisioning. Page id 0 is a reserved *scratch* page: it is
    never allocated, unused page-table entries point at it, and retired
    slots (whose pages have been returned to the free list) write their
    dead decode tokens into it instead of corrupting reassigned pages. A
    slot whose table row starts with page 0 holds no request: every live
    slot's first page is real.

    The pools are head-major, ``(n_kv, n_pages, page_size, hd_pool)``: one
    kv head's page is one contiguous ``(page_size, hd_pool)`` block, the
    unit the TPU's paged-attention kernel copies. For a pool made for a TPU
    ``hd_pool`` is the head size rounded up to a multiple of 128
    (:func:`pool_head_dim`), the lane width the kernel blocks its outputs
    by; the padding holds zeros and no path reads it into a result.
    Decode attention over the cache
    (:func:`paged_decode_attention`) takes one of two paths, chosen by the
    backend the program is compiled for and the cache's sharding:

    * on a TPU with the cache on one device, the Pallas kernel reads each
      live slot's pages straight from the pool, in bf16, up to the slot's
      length, and skips idle slots;
    * elsewhere (the CPU, a cache sharded over several devices) the pool is
      gathered into the rectangle a slot cache would hold
      (:func:`paged_view`) and attended by :func:`decode_attention`. Only
      this path is bitwise identical to the rectangular slot cache.
    """

    k: Array  # (n_kv, n_pages, page_size, hd_pool) -- shared by all slots
    v: Array  # (n_kv, n_pages, page_size, hd_pool)
    table: Array  # (B, pages_per_slot) int32 page ids; 0 = scratch page
    length: Array  # (B,) int32 tokens written per slot
    #: zero-element (s_max, 0) buffer: shape-encodes the slot's virtual
    #: capacity (the b_adc_buf idiom), so the gathered decode view can be
    #: sliced to EXACTLY the rectangle an equivalent slot cache would have
    #: -- reduction shapes match and decode stays bitwise identical.
    cap_buf: Array

    @property
    def page_size(self) -> int:
        return self.k.shape[2]

    @property
    def s_max(self) -> int:
        return self.cap_buf.shape[0]


def pool_head_dim(hd: int, platform: Optional[str] = None) -> int:
    """A page pool's head size on ``platform`` (default: this process's
    backend). Decode attention lowers to the Pallas paged-attention kernel
    for a TPU and to the view path elsewhere (:func:`paged_decode_attention`),
    so the rule follows the same target: for a TPU, ``hd`` rounded up to a
    multiple of 128, since the kernel blocks its per-head softmax
    statistics by the head size, which Mosaic takes only as a multiple of
    128 (LFM2's 64-wide heads are padded, OLMo's 128 are not); elsewhere
    ``hd``, which keeps a narrow-headed pool as small as its tokens (the
    view path also reads a padded pool, bitwise as an unpadded one)."""
    if (platform or jax.default_backend()) != "tpu":
        return hd
    return -(-hd // 128) * 128


def pad_head(x: Array, width: int) -> Array:
    """``x`` with its last (head) axis zero-padded to ``width``."""
    pad = width - x.shape[-1]
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)]) if pad else x


def init_paged_cache(
    cfg: ModelConfig,
    batch: int,
    s_max: int,
    dtype,
    *,
    page_size: int,
    n_pages: int,
    platform: Optional[str] = None,
) -> PagedKVCache:
    """One layer's page pool + per-slot tables (pool id space is shared
    across layers: the serving allocator hands out one page id that is
    valid in every layer's pool). ``platform``: :func:`pool_head_dim`."""
    if page_size < 1:
        raise ValueError(f"page_size must be >= 1, got {page_size}")
    pages_per_slot = -(-s_max // page_size)
    if n_pages < 2:
        raise ValueError(
            f"n_pages={n_pages}: need the scratch page plus at least one "
            "usable page"
        )
    # NOTE: n_pages may be much smaller than batch * pages_per_slot (that
    # is the point: s_max is VIRTUAL capacity); the serving engine's
    # admission reservations keep actual usage within the pool.
    shape = (cfg.n_kv_heads, n_pages, page_size,
             pool_head_dim(cfg.hd, platform))
    return PagedKVCache(
        k=jnp.zeros(shape, dtype),
        v=jnp.zeros(shape, dtype),
        table=jnp.zeros((batch, pages_per_slot), jnp.int32),
        length=jnp.zeros((batch,), jnp.int32),
        cap_buf=jnp.zeros((s_max, 0), jnp.int32),
    )


def paged_view(cache: PagedKVCache) -> KVCache:
    """Gather the head-major pool through the page tables into a
    rectangular (B, s_max, n_kv, hd) slot-cache view.

    Pure data movement (gather + transpose + reshape + slice, no
    arithmetic), sliced to the shape-encoded virtual capacity (the head
    axis keeps the pool's padding, :func:`pool_head_dim`): attention
    over the view is bitwise identical to attention over a rectangular slot
    cache holding the same tokens. Positions past a slot's length read
    scratch/garbage rows and are masked to exact-zero probability by
    :func:`decode_attention`. The decode path off the TPU (see
    :class:`PagedKVCache`).
    """
    b, pages_per_slot = cache.table.shape
    n_kv, _, ps, hd = cache.k.shape

    def gather(pool):
        rows = pool[:, cache.table]  # (n_kv, B, pages_per_slot, ps, hd)
        rows = rows.transpose(1, 2, 3, 0, 4)
        return rows.reshape(b, pages_per_slot * ps, n_kv, hd)[:, : cache.s_max]

    return KVCache(gather(cache.k), gather(cache.v), cache.length)


#: tokens of one slot's pages the paged-attention kernel reads per step of
#: its loop (its compute block), as near as a divisor of the slot's pages
#: allows: 256 read the pages fastest of 64, 128 and 256 on a TPU v5e
#: (PERF.md, section 6)
KERNEL_BLOCK_TOKENS = 256


def kernel_block_pages(page_size: int, pages_per_slot: int) -> int:
    """Pages per compute block of the paged-attention kernel: the largest
    divisor of ``pages_per_slot`` that holds at most
    :data:`KERNEL_BLOCK_TOKENS` tokens (one page at the least)."""
    most = max(1, min(KERNEL_BLOCK_TOKENS // page_size, pages_per_slot))
    return max(d for d in range(1, most + 1) if pages_per_slot % d == 0)


def paged_attention_kernel(q: Array, cache: PagedKVCache) -> Array:
    """Decode attention over a paged cache through the TPU's Pallas
    paged-attention kernel. q: (B, 1, H, D) -> (B, 1, H, D).

    A live slot's pages are read from the pool in bf16, a compute block at
    a time, up to its length; an idle slot (table row starting with the
    scratch page) is given length 0, which the kernel skips, and its row is
    exact zeros. Table entries past a live slot's pages point at its first
    page, so a live slot never reads the scratch page the idle slots write
    into. q is scaled by ``D**-0.5`` in f32, as :func:`decode_attention`
    scales the scores.
    """
    from jax.experimental.pallas.ops.tpu.paged_attention import (
        paged_attention,
    )

    d = q.shape[-1]
    pages_per_slot = cache.table.shape[1]
    ps = cache.page_size
    live = cache.table[:, 0] != 0
    n = jnp.where(live, jnp.minimum(cache.length, cache.s_max), 0)
    used = jnp.arange(pages_per_slot)[None, :] < -(-n // ps)[:, None]
    table = jnp.where(used, cache.table, cache.table[:, :1])
    qs = pad_head(q[:, 0].astype(jnp.float32) * d**-0.5, cache.k.shape[-1])
    out = paged_attention(
        qs, cache.k, cache.v, n, table,
        pages_per_compute_block=kernel_block_pages(ps, pages_per_slot),
    )[..., :d]
    out = jnp.where(live[:, None, None], out, 0.0)
    return out[:, None].astype(q.dtype)


def _lowering(path):
    """``path(q, cache)`` as a lowering of :data:`_paged_decode_p`."""

    def attend(q, k, v, table, length, *, s_max):
        cap = jnp.zeros((s_max, 0), jnp.int32)
        return path(q, PagedKVCache(k, v, table, length, cap))

    return mlir.lower_fun(attend, multiple_results=False)


def _view_path(q: Array, cache: PagedKVCache) -> Array:
    with jax.named_scope("paged_view"):
        view = paged_view(cache)
        hd = q.shape[-1]
        if view.k.shape[-1] != hd:  # a padded pool (pool_head_dim)
            view = KVCache(view.k[..., :hd], view.v[..., :hd], view.length)
    with jax.named_scope("scores"):
        return decode_attention(q, view)


def _kernel_path(q: Array, cache: PagedKVCache) -> Array:
    with jax.named_scope("scores"):
        return paged_attention_kernel(q, cache)


#: paged decode attention, lowered per platform: the kernel for a TPU, the
#: gathered view for every other backend (the choice is the compiler's
#: target, so a program compiled for a described TPU takes the kernel too)
_paged_decode_p = jex_core.Primitive("paged_decode_attention")
_paged_decode_p.def_abstract_eval(lambda q, *_, **__: q)
_paged_decode_p.def_impl(
    lambda *args, **params: jax.jit(
        functools.partial(_paged_decode_p.bind, **params)
    )(*args)
)
mlir.register_lowering(_paged_decode_p, _lowering(_view_path))
mlir.register_lowering(_paged_decode_p, _lowering(_kernel_path), platform="tpu")


def _on_one_device(x) -> bool:
    return jax.typeof(x).sharding.mesh.size <= 1


def paged_decode_attention(q: Array, cache: PagedKVCache) -> Array:
    """One-token attention over a paged cache (after this step's token is
    written). q: (B, 1, H, D). The kernel on a TPU with the cache on one
    device, else the gathered view (see :class:`PagedKVCache`): the kernel
    lowers only through Mosaic and is not partitioned across devices."""
    if not (
        jax.sharding.get_abstract_mesh().size <= 1
        and all(_on_one_device(x) for x in (q, cache.k, cache.v))
    ):
        return _view_path(q, cache)
    return _paged_decode_p.bind(
        q, cache.k, cache.v, cache.table, cache.length, s_max=cache.s_max
    )


def kernel_engages(cache: PagedKVCache) -> bool:
    """Whether decode over this (concrete) cache takes the kernel: its pool
    lies on one TPU device. The host-side twin of the choice
    :func:`paged_decode_attention` makes while tracing."""
    devices = cache.k.sharding.device_set
    return len(devices) == 1 and next(iter(devices)).platform == "tpu"


def attn_apply(
    params: dict,
    x: Array,
    ctx: AnalogCtx,
    cfg: ModelConfig,
    *,
    positions: Array,
    cache: Optional[KVCache] = None,
    window: Optional[int] = None,
    layer_idx: Optional[int] = None,
) -> tuple[Array, Optional[KVCache]]:
    """Full attention block. x: (B, S, M). Returns (out, updated_cache).

    ``layer_idx`` (static int): ``cache`` is layer-stacked (L, B, S, kv, hd);
    the new token is written in place into the stacked buffer and attention
    reads a fused view -- no per-step cache copy (decode fast path).
    """
    b, s, _ = x.shape
    hd, nh, nkv = cfg.hd, cfg.n_heads, cfg.n_kv_heads
    q = _split_heads(proj(params, "wq", x, ctx), nh, hd)
    k = _split_heads(proj(params, "wk", x, ctx), nkv, hd)
    v = _split_heads(proj(params, "wv", x, ctx), nkv, hd)
    if cfg.qk_norm:  # per-head RMSNorm of q and k, before RoPE
        q = rmsnorm_apply(params["q_norm"], q, cfg.norm_eps)
        k = rmsnorm_apply(params["k_norm"], k, cfg.norm_eps)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    q = shard(q, "batch", None, "heads", None)
    k = shard(k, "batch", None, "kv_heads", None)
    v = shard(v, "batch", None, "kv_heads", None)

    if isinstance(cache, PagedKVCache):
        if s != 1:
            raise NotImplementedError(
                "paged caches are decode-only: prefill a request alone into "
                "a rectangular cache and scatter it into pages "
                "(models.lm.write_cache_slot_paged)"
            )
        if window is not None:
            raise NotImplementedError(
                "local-window attention keeps its bounded rolling buffer; "
                "paging applies to global-attention caches only"
            )
        # decode: write this token's K/V row at (page, offset) of each
        # slot's current position, then attend over the slots' pages
        ps = cache.page_size
        page = jnp.take_along_axis(
            cache.table, (cache.length // ps)[:, None], axis=1, mode="clip"
        )[:, 0]  # (B,) -- OOB entries of retired slots clip to scratch
        at_off = jnp.arange(ps)[None, :] == (cache.length % ps)[:, None]

        def put(pool, x):
            # whole pages in and out: a scatter of single rows would make
            # the TPU compiler lay the pool out page-major and copy all of
            # it to and from the kernel's head-major layout every step
            rows = x[:, 0].swapaxes(0, 1)[:, :, None].astype(pool.dtype)
            rows = pad_head(rows, pool.shape[-1])
            pages = jnp.where(at_off[None, :, :, None], rows, pool[:, page])
            return pool.at[:, page].set(pages)

        new_cache = PagedKVCache(
            put(cache.k, k), put(cache.v, v), cache.table, cache.length + 1,
            cache.cap_buf,
        )
        out = paged_decode_attention(q, new_cache)
        out = out.reshape(b, s, nh * hd)
        return proj(params, "wo", out, ctx), new_cache

    new_cache = None
    s_cache = (
        cache.k.shape[2] if (cache is not None and layer_idx is not None)
        else (cache.k.shape[1] if cache is not None else 0)
    )
    rolling = window is not None and s_cache <= window
    if cache is not None and s == 1 and layer_idx is not None:
        # stacked decode fast path: in-place write into (L, B, S, kv, hd)
        ln = cache.length[layer_idx]
        idx = ln % s_cache if rolling else ln
        ck = jax.lax.dynamic_update_slice(
            cache.k, k[None].astype(cache.k.dtype), (layer_idx, 0, idx, 0, 0)
        )
        cv = jax.lax.dynamic_update_slice(
            cache.v, v[None].astype(cache.v.dtype), (layer_idx, 0, idx, 0, 0)
        )
        new_len = cache.length.at[layer_idx].add(1)
        layer_cache = KVCache(ck[layer_idx], cv[layer_idx], ln + 1)
        new_cache = KVCache(ck, cv, new_len)
        with jax.named_scope("scores"):
            out = decode_attention(q, layer_cache, rolling=rolling)
    elif cache is not None and s == 1:
        # decode: append to cache (circular slot for window buffers)
        idx = cache.length % s_cache if rolling else cache.length
        if cache.length.ndim:
            # per-slot lengths: each row writes at its own position
            def _put(c, u, i):
                return jax.lax.dynamic_update_slice(c, u, (i, 0, 0))

            ck = jax.vmap(_put)(cache.k, k.astype(cache.k.dtype), idx)
            cv = jax.vmap(_put)(cache.v, v.astype(cache.v.dtype), idx)
        else:
            ck = jax.lax.dynamic_update_slice(cache.k, k, (0, idx, 0, 0))
            cv = jax.lax.dynamic_update_slice(cache.v, v, (0, idx, 0, 0))
        new_cache = KVCache(ck, cv, cache.length + 1)
        with jax.named_scope("scores"):
            out = decode_attention(q, new_cache, rolling=rolling)
    elif cache is not None:
        # prefill: write the prefix (for window buffers, only the last
        # ``s_cache`` keys, placed at their position-mod-window slots so
        # subsequent decode writes keep the circular invariant)
        if rolling and s >= s_cache:
            k_t, v_t = k[:, -s_cache:], v[:, -s_cache:]
            ck = jnp.roll(k_t, s % s_cache, axis=1)
            cv = jnp.roll(v_t, s % s_cache, axis=1)
        elif rolling:
            ck = jax.lax.dynamic_update_slice(cache.k, k, (0, 0, 0, 0))
            cv = jax.lax.dynamic_update_slice(cache.v, v, (0, 0, 0, 0))
        else:
            ck = jax.lax.dynamic_update_slice(cache.k, k, (0, cache.length, 0, 0))
            cv = jax.lax.dynamic_update_slice(cache.v, v, (0, cache.length, 0, 0))
        new_cache = KVCache(ck, cv, cache.length + s)
        with jax.named_scope("scores"):
            out = chunked_attention(
                q,
                k,
                v,
                q_chunk=cfg.attn_chunk_q,
                kv_chunk=cfg.attn_chunk_kv,
                causal=True,
                window=window,
                q_offset=0,
            )
    else:
        with jax.named_scope("scores"):
            out = chunked_attention(
                q,
                k,
                v,
                q_chunk=cfg.attn_chunk_q,
                kv_chunk=cfg.attn_chunk_kv,
                causal=True,
                window=window,
            )
    out = out.reshape(b, s, nh * hd)
    return proj(params, "wo", out, ctx), new_cache


def init_cache(
    cfg: ModelConfig, batch: int, s_max: int, dtype, per_slot: bool = False
) -> KVCache:
    shape = (batch, s_max, cfg.n_kv_heads, cfg.hd)
    return KVCache(
        k=jnp.zeros(shape, dtype),
        v=jnp.zeros(shape, dtype),
        length=jnp.zeros((batch,) if per_slot else (), jnp.int32),
    )
