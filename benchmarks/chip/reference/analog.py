"""Plain analog semantics, written from the paper's equations.

* PCM programming (Nandakumar et al. 2019, as the paper simulates it):
  clip the weights, scale by max|W| into a differential pair of
  conductance fractions, add write noise sigma_P(g) = max(-1.1731 g^2 +
  1.9650 g + 0.2635, 0) uS of G_max = 25 uS, drift each device by
  (t / 25 s)^-nu with nu ~ N(0.06, 0.02) cut at 0, take the global drift
  compensation (GDC) as sum(G_target) / sum(G_drifted), then one 1/f read
  draw G ~ N(G_D, G_D Q sqrt(log((t + 250 ns) / 250 ns))) with
  Q = min(0.0088 / g^0.65, 0.2).
* Execution of a programmed layer: a (b_adc + 1)-bit DAC over
  r_dac = r_adc |S| / w_max, the f32 crossbar MVM of each 1024-row tile,
  a b_adc-bit ADC over r_adc on each tile's partial sum, the digital sum
  of the tiles, and the GDC factor.

The noise draws are the chip's: each programmed layer's key is the
deployment's programming key folded with the layer's place in the order
the chip is programmed, split over its stacked members, then into the
write (2), drift (first 2 of 4) and read (last 2 of 4) draws.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

G_MAX_US = 25.0
T_C = 25.0
T_READ = 250e-9
NU_MEAN, NU_STD = 0.06, 0.02
HIGHEST = jax.lax.Precision.HIGHEST


def fake_quant(x, r, bits: int):
    """Symmetric uniform quantizer over [-r, r] with 2^(bits-1)-1 levels a
    side, returned in the value domain."""
    r = jnp.abs(r) + 1e-9
    step = r / (2 ** (bits - 1) - 1)
    return jnp.round(jnp.clip(x, -r, r) / step) * step


def _program_block(key, w, w_min, w_max, t):
    """One 2-D block -> (effective weights, GDC factor)."""
    f32 = jnp.float32
    w = jnp.clip(w, w_min, w_max).astype(f32)
    scale = jnp.max(jnp.abs(w)) + 1e-12
    gp_t = jnp.maximum(w / scale, 0.0)
    gn_t = jnp.maximum(-w / scale, 0.0)

    def write(k, g):
        sigma = jnp.maximum(-1.1731 * g**2 + 1.9650 * g + 0.2635, 0.0)
        return jnp.clip(
            g + sigma / G_MAX_US * jax.random.normal(k, g.shape, f32), 0.0, 1.2
        )

    def drift(k, g):
        nu = jnp.maximum(NU_MEAN + NU_STD * jax.random.normal(k, g.shape, f32), 0.0)
        return g * (jnp.maximum(t, T_C) / T_C) ** (-nu)

    def read(k, g, g_t):
        q = jnp.minimum(0.0088 / jnp.maximum(g_t, 1e-9) ** 0.65, 0.2)
        s = jnp.sqrt(jnp.log((t + T_READ) / T_READ))
        return jnp.maximum(g + g * q * s * jax.random.normal(k, g.shape, f32), 0.0)

    k_wp, k_wn = jax.random.split(key)
    k_dp, k_dn, k_rp, k_rn = jax.random.split(key, 4)
    gp, gn = write(k_wp, gp_t), write(k_wn, gn_t)
    gp, gn = drift(k_dp, gp), drift(k_dn, gn)
    gdc = jnp.sum(gp_t + gn_t) / (jnp.sum(gp + gn) + 1e-12)
    gp, gn = read(k_rp, gp, gp_t), read(k_rn, gn, gn_t)
    return (gp - gn) * scale, gdc


@jax.jit
def program_layer(key, w, clip, t):
    """Program a (stack..., K, N) weight; ``clip`` is (stack..., 2).

    Returns (effective weights, GDC factors of shape stack)."""
    stack = w.shape[:-2]
    keys = jax.random.split(key, math.prod(stack) if stack else 1)
    keys = keys.reshape(stack + (-1,))
    fn = lambda k, w_, c: _program_block(k, w_, c[0], c[1], t)  # noqa: E731
    for _ in stack:
        fn = jax.vmap(fn)
    return fn(keys, w, clip)


def linear(x, w_eff, gdc, r_adc, w_max, gain_s, bits: int,
           tile_rows: int = 1024):
    """A programmed layer: DAC -> tiled MVM -> per-tile ADC -> sum -> GDC.

    The result is in ``x``'s dtype, as the deployment returns it."""
    r_dac = jnp.abs(r_adc) * jnp.abs(gain_s) / (jnp.abs(w_max) + 1e-9)
    xq = fake_quant(x.astype(jnp.float32), r_dac, bits + 1)
    k = w_eff.shape[0]
    y = None
    for t0 in range(0, k, tile_rows):
        part = jnp.matmul(
            xq[..., t0:t0 + tile_rows], w_eff[t0:t0 + tile_rows],
            precision=HIGHEST, preferred_element_type=jnp.float32,
        )
        part = fake_quant(part, r_adc, bits)
        y = part if y is None else y + part
    return (y * gdc).astype(x.dtype)
