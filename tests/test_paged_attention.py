"""Decode attention over a paged KV cache: the TPU's Pallas paged-attention
kernel (run here in Mosaic's interpret mode) against the gathered-view path
the CPU takes, and the kernel's compute-block rule.

Layout of every case: 2 kv heads of 128, 16-token pages, 8 pages a slot
(s_max 128), three slots. Slot 0 holds the case's length; slot 1 a live
request of 40 tokens; slot 2 is idle (table row all scratch page, length
grown past s_max, as a retired slot's keeps growing).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from repro.models import attention as attn_lib

N_KV, HD, PS, PPS, B = 2, 128, 16, 8, 3
S_MAX = PS * PPS
IDLE_LEN = S_MAX + 72
#: slot 0's length per case; None is an idle slot
CASES = {"len1": 1, "len16": 16, "len17": 17, "full": S_MAX, "idle": None}


def _cache(len0):
    kk, kv = jax.random.split(jax.random.PRNGKey(0))
    n_pages = 1 + 2 * PPS
    shape = (N_KV, n_pages, PS, HD)
    table = np.zeros((B, PPS), np.int32)
    lengths = [IDLE_LEN if len0 is None else len0, 40, IDLE_LEN]
    for slot, n in enumerate(lengths[:2]):
        if slot == 0 and len0 is None:
            continue
        used = -(-n // PS)
        table[slot, :used] = 1 + slot * PPS + np.arange(used)
    return attn_lib.PagedKVCache(
        k=jax.random.normal(kk, shape, jnp.float32).astype(jnp.bfloat16),
        v=jax.random.normal(kv, shape, jnp.float32).astype(jnp.bfloat16),
        table=jnp.asarray(table),
        length=jnp.asarray(lengths, jnp.int32),
        cap_buf=jnp.zeros((S_MAX, 0), jnp.int32),
    )


def _q():
    q = jax.random.normal(jax.random.PRNGKey(1), (B, 1, N_KV, HD))
    return q.astype(jnp.bfloat16)


def _kernel(q, cache):
    with pltpu.force_tpu_interpret_mode():
        return np.asarray(
            jax.jit(attn_lib.paged_attention_kernel)(q, cache), np.float32
        )


def _view(q, cache):
    out = jax.jit(
        lambda q, c: attn_lib.decode_attention(q, attn_lib.paged_view(c))
    )(q, cache)
    return np.asarray(out, np.float32)


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_matches_the_view_and_skips_idle_slots(case):
    cache, q = _cache(CASES[case]), _q()
    got, want = _kernel(q, cache), _view(q, cache)
    live = [0, 1] if CASES[case] is not None else [1]
    idle = sorted({0, 1, 2} - set(live))
    # the view path rounds the probabilities to bf16 before the value
    # product and both round the output to bf16: a few bf16 ulps apart
    np.testing.assert_allclose(got[live], want[live], rtol=0, atol=2**-6)
    assert not np.any(got[idle]), "an idle slot's row must be exact zeros"
    # the idle slots' table rows name only the scratch page: filling it
    # with NaN must not reach a live row, nor an idle one
    nan = cache._replace(k=cache.k.at[:, 0].set(jnp.nan),
                         v=cache.v.at[:, 0].set(jnp.nan))
    again = _kernel(q, nan)
    np.testing.assert_array_equal(again[live], got[live])
    assert not np.any(again[idle])


@pytest.mark.parametrize(
    "page_size,pages_per_slot,want",
    [(16, 128, 16), (16, 8, 8), (16, 24, 12), (64, 6, 3), (5, 7, 7), (512, 4, 1)],
)
def test_kernel_block_is_a_divisor_of_the_slots_pages(
    page_size, pages_per_slot, want
):
    got = attn_lib.kernel_block_pages(page_size, pages_per_slot)
    assert got == want
    assert pages_per_slot % got == 0
    assert got == 1 or got * page_size <= attn_lib.KERNEL_BLOCK_TOKENS


def test_the_cpu_lowers_the_view_path():
    """Off the TPU the paged branch is the gathered view: bitwise the
    view path's output, with no kernel in the program."""
    cache, q = _cache(17), _q()
    fn = jax.jit(attn_lib.paged_decode_attention)
    assert "tpu_custom_call" not in fn.lower(q, cache).as_text()
    np.testing.assert_array_equal(np.asarray(fn(q, cache), np.float32),
                                  _view(q, cache))


def test_padded_pool_of_64_wide_gqa_heads():
    """LFM2's heads: 64 wide, 4 query heads a KV head. A pool made for a
    TPU has its head axis padded to the kernel's 128 lanes
    (``pool_head_dim``), elsewhere not: the
    kernel on the padded pool matches the view of the unpadded one, and the
    view path of the padded pool is bitwise the unpadded one's."""
    assert attn_lib.pool_head_dim(64, "tpu") == 128
    assert attn_lib.pool_head_dim(128, "tpu") == 128
    assert attn_lib.pool_head_dim(64, "cpu") == 64
    base = _cache(17)
    small = base._replace(k=base.k[..., :64], v=base.v[..., :64])
    padded = small._replace(k=attn_lib.pad_head(small.k, 128),
                            v=attn_lib.pad_head(small.v, 128))
    q = jax.random.normal(jax.random.PRNGKey(1), (B, 1, 4 * N_KV, 64))
    q = q.astype(jnp.bfloat16)
    want = _view(q, small)
    got = _kernel(q, padded)
    assert got.shape == want.shape
    np.testing.assert_allclose(got[:2], want[:2], rtol=0, atol=2**-6)
    assert not np.any(got[2])
    view = jax.jit(attn_lib._view_path)
    np.testing.assert_array_equal(np.asarray(view(q, padded), np.float32), want)
