"""Pallas TPU kernel: fused DAC-quant -> crossbar-tiled MVM -> ADC-quant.

TPU adaptation of the paper's analog MVM (Sec. 3.1): on real AON-CiM hardware
the DAC/MVM/ADC chain is free-running analog; in the training/simulation
framework it is the hot spot, executed for *every* weight matmul of every
step. The fusion matters because the naive jnp composition materializes the
(M, T, N) per-tile partial-sum tensor in HBM; the kernel keeps partial sums in
a VMEM accumulator and only writes the final (M, N) block.

Tiling (see DESIGN.md "hardware adaptation"):
  * K-block == ``tile_rows`` (1024) == the physical crossbar source lines, so
    per-K-block ADC quantization is *exactly* the per-row-tile conversion the
    layer-serial hardware performs;
  * N-block 512 == the physical bitline count (MXU-aligned: 4 x 128 lanes);
  * M-block 256 batch rows, fp32 accumulation in VMEM scratch.

VMEM footprint at defaults (bf16 in, f32 acc):
  x (256x1024x2) + w (1024x512x2) + acc (256x512x4) + out ~= 2.6 MB << 16 MB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array


def _quant(v: Array, r: Array, bits: int) -> Array:
    """Hard symmetric fake-quant (forward only; STE lives in the custom VJP)."""
    n_levels = 2 ** (bits - 1) - 1
    r = jnp.abs(r) + 1e-9
    step = r / n_levels
    return jnp.round(jnp.clip(v, -r, r) / step) * step


def _kernel(
    r_ref,  # (3,) f32 in SMEM: [r_dac, r_adc, out_scale]
    x_ref,  # (block_m, tile_rows) VMEM
    w_ref,  # (tile_rows, block_n) VMEM
    out_ref,  # (block_m, block_n) VMEM
    acc_ref,  # (block_m, block_n) f32 VMEM scratch
    *,
    b_dac: int,
    b_adc: int,
    per_tile_adc: bool,
    apply_dac: bool,
    n_k_tiles: int,
):
    kt = pl.program_id(2)

    @pl.when(kt == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    r_dac = r_ref[0]
    r_adc = r_ref[1]
    # DAC: quantize the input slab feeding this crossbar row-tile (skipped
    # when the caller pre-quantized the activations, e.g. with quant-noise).
    x_q = x_ref[...].astype(jnp.float32)
    if apply_dac:
        x_q = _quant(x_q, r_dac, b_dac)
    partial = jax.lax.dot_general(
        x_q,
        w_ref[...].astype(jnp.float32),
        (((1,), (0,)), ((), ())),
        # f32 products, as the jnp oracle computes them
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    if per_tile_adc:
        # ADC converts each physical row-tile's bitline charge independently;
        # accumulation across tiles happens in the digital domain.
        partial = _quant(partial, r_adc, b_adc)
    acc_ref[...] += partial

    @pl.when(kt == n_k_tiles - 1)
    def _flush():
        acc = acc_ref[...]
        if not per_tile_adc:
            acc = _quant(acc, r_adc, b_adc)
        # Digital epilogue: the GDC scalar multiplies the ADC outputs after
        # accumulation (pcm_infer deployment; 1.0 during training). Fused
        # here so the programmed-inference path needs no extra HBM pass.
        out_ref[...] = (acc * r_ref[2]).astype(out_ref.dtype)


def _round_up(v: int, mult: int) -> int:
    return -(-v // mult) * mult


@functools.partial(
    jax.jit,
    static_argnames=(
        "b_dac",
        "b_adc",
        "tile_rows",
        "per_tile_adc",
        "apply_dac",
        "block_m",
        "block_n",
        "interpret",
    ),
)
def analog_mvm_fwd(
    x: Array,
    w: Array,
    r_dac: Array,
    r_adc: Array,
    out_scale: Array | float = 1.0,
    *,
    b_dac: int = 9,
    b_adc: int = 8,
    tile_rows: int = 1024,
    per_tile_adc: bool = True,
    apply_dac: bool = True,
    block_m: int = 256,
    block_n: int = 512,
    interpret: bool = False,
) -> Array:
    """Forward fused analog MVM. x: (M, K), w: (K, N) -> (M, N)."""
    m, k = x.shape
    _, n = w.shape

    block_m = min(block_m, _round_up(m, 8))
    block_n = min(block_n, _round_up(n, 128))
    mp = _round_up(m, block_m)
    np_ = _round_up(n, block_n)
    kp = _round_up(k, tile_rows)
    if (mp, kp) != (m, k):
        x = jnp.pad(x, ((0, mp - m), (0, kp - k)))
    if (kp, np_) != (k, n):
        w = jnp.pad(w, ((0, kp - k), (0, np_ - n)))

    n_k_tiles = kp // tile_rows
    grid = (mp // block_m, np_ // block_n, n_k_tiles)
    ranges = jnp.stack(
        [
            jnp.asarray(r_dac, jnp.float32).reshape(()),
            jnp.asarray(r_adc, jnp.float32).reshape(()),
            jnp.asarray(out_scale, jnp.float32).reshape(()),
        ]
    )

    out = pl.pallas_call(
        functools.partial(
            _kernel,
            b_dac=b_dac,
            b_adc=b_adc,
            per_tile_adc=per_tile_adc,
            apply_dac=apply_dac,
            n_k_tiles=n_k_tiles,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_m, tile_rows), lambda i, j, kt, _r: (i, kt)),
                pl.BlockSpec((tile_rows, block_n), lambda i, j, kt, _r: (kt, j)),
            ],
            out_specs=pl.BlockSpec(
                (block_m, block_n), lambda i, j, kt, _r: (i, j)
            ),
            scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((mp, np_), x.dtype),
        interpret=interpret,
    )(ranges, x, w)
    return out[:m, :n]
