"""LFM2-MoE at a smoke size on the CPU: the gated short conv, the dropless
held-expert MoE, per-slot conv state in the paged cache, and the served
chip against the plain reference (``benchmarks/chip/reference/lfm2_moe``).
Weights are seeded random draws."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip.drivers import lfm2moe_serve
from benchmarks.chip.reference import lfm2_moe as ref
from repro import configs, obs
from repro.configs import lfm2_8b_a1b
from repro.core.analog import AnalogConfig, AnalogCtx
from repro.launch import steps
from repro.models import lm
from repro.models import moe as moe_lib
from repro.models import shortconv
from repro.models.common import ModelConfig
from repro.serving import BucketedScheduler, Request, ServingConfig, ServingEngine

SEED = 2**31 + 17
#: a benchmark-style configuration at a smoke size: LFM2's first five
#: layers (conv+dense x2, attn+MoE, conv+MoE x2), 2 of 8 experts held
TOY = {
    "arch": "lfm2-8b-a1b", "conv_L_cache": 3, "hidden_size": 64,
    "intermediate_size": 128, "moe_intermediate_size": 96,
    "layer_types": list(lfm2_8b_a1b.LAYER_TYPES),
    "norm_eps": 1e-5, "num_attention_heads": 4, "num_dense_layers": 2,
    "num_experts": 2, "num_experts_published": 8, "expert_share_first": 2,
    "num_experts_per_tok": 4, "num_hidden_layers": 5,
    "num_key_value_heads": 2, "rope_theta": 1e6, "routed_scaling_factor": 1,
    "vocab_size": 256, "activation_dtype": "bfloat16", "b_adc": 8,
    "t_hours": 24.0,
}
S_MAX = 64
#: served logits against the reference's (see the test that uses it)
TOL = 1 / 64


def _program(c=TOY, bits=8, seed=SEED):
    mc = lfm2moe_serve.model_config(c)
    shapes = jax.eval_shape(lambda k: lm.lm_init(k, mc), jax.random.PRNGKey(0))
    params = lfm2moe_serve.fill(shapes, seed)
    acfg = AnalogConfig().infer(b_adc=bits, t_seconds=c["t_hours"] * 3600.0)
    from benchmarks.chip import weights

    return mc, steps.program_for_serving(
        params, acfg, weights.seed_key(seed, "program"))


def _requests(vocab, lens, new=5):
    rng = np.random.default_rng(3)
    return [Request(rid=i, prompt=rng.integers(0, vocab, n), max_new_tokens=new)
            for i, n in enumerate(lens)]


def _serve(mc, program, reqs, **kw):
    eng = ServingEngine(mc, program.cfg, program.params, ServingConfig(
        n_slots=4, s_max=S_MAX, paged=True, page_size=8, record_logits=True,
        ref_check=False, **kw), program=program)
    rep = eng.run(reqs, scheduler=BucketedScheduler())
    return eng, {r.rid: r for r in rep.records}


def _ref_logits(state, c, prompt, tokens):
    seq = np.zeros(S_MAX, np.int32)
    full = np.concatenate([prompt, tokens[:-1]])
    seq[: full.size] = full
    lg = np.asarray(ref.forward(state, jnp.asarray(seq), dims=ref.dims(c),
                                bits=c["b_adc"]))
    return lg[prompt.size - 1: prompt.size - 1 + tokens.size]


def test_paged_prefill_then_decode_matches_reference():
    """Every logit the engine served (bucketed prefill, then decode through
    the paged cache and conv state) against the reference's one causal pass
    over the same tokens, on the same chip rebuilt from the seed.

    Tolerance 1/64, two bf16 ulps of these logits (|logit| < 2): both
    paths round bf16 activations at the same boundaries and run the same
    f32 MVMs and ADCs, so they agree bitwise here; what is left is for a
    DAC or ADC code that a different summation order (online vs whole
    softmax) moves. A chip programmed at 6-bit ADCs, against the same
    8-bit reference, is off by more than 30x the tolerance."""
    mc, program = _program()
    reqs = _requests(TOY["vocab_size"], [5, 13, 30, 9])
    _, recs = _serve(mc, program, reqs)
    state = ref.program(TOY, SEED, TOY["t_hours"] * 3600.0)
    worst = 0.0
    for r in reqs:
        rec = recs[r.rid]
        want = _ref_logits(state, TOY, r.prompt, rec.tokens)
        worst = max(worst, float(np.abs(rec.logits - want).max()))
    assert worst <= TOL

    mc6, program6 = _program(bits=6)
    _, recs6 = _serve(mc6, program6, reqs)
    off = max(float(np.abs(recs6[r.rid].logits - _ref_logits(
        state, TOY, r.prompt, recs6[r.rid].tokens)).max()) for r in reqs)
    assert off > 30 * TOL


def _moe_cfg(**kw):
    base = dict(name="t", family="moe", n_layers=1, d_model=32, n_heads=2,
                n_kv_heads=2, d_ff=48, vocab=64, n_experts=8, top_k=4,
                moe_dispatch="dropless", dtype=jnp.float32, remat=False)
    base.update(kw)
    return ModelConfig(**base)


def _share(params, cfg, held):
    """The layer's parameters on the chip that holds ``held``."""
    idx = jnp.asarray(held)
    out = dict(params)
    for f in ("w1", "w3", "w2"):
        out[f] = params[f][idx]
    return out, dataclasses.replace(cfg, moe_held=tuple(held))


def test_expert_shares_add_up_to_the_uncut_layer():
    """Four chips' parts of a dropless layer (2 of 8 experts each; router
    and expert bias on every chip) sum to the layer with all 8 experts.
    f32 activations; the parts' sum reorders the f32 combine, so 1e-6."""
    cfg = _moe_cfg()
    params = moe_lib.moe_init(jax.random.PRNGKey(0), cfg)
    params["expert_bias"] = jax.random.normal(jax.random.PRNGKey(2), (8,)) * 0.1
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 7, cfg.d_model))
    ctx = AnalogCtx(cfg=AnalogConfig(), gain_s=jnp.ones(()))
    whole, st = moe_lib.moe_apply_dropless(params, x, ctx, cfg)
    parts, held_rows = 0.0, 0
    for c0 in range(0, 8, 2):
        p, c = _share(params, cfg, (c0, c0 + 1))
        y, s = moe_lib.moe_apply_dropless(p, x, ctx, c)
        parts = parts + y
        held_rows += int(s[0])
    np.testing.assert_allclose(parts, whole, rtol=1e-5, atol=1e-6)
    assert held_rows == int(st[0]) == 21 * cfg.top_k


def test_routing_every_token_to_one_held_expert_drops_nothing():
    """An expert bias that sends every token to expert 3 (top-1): the chip
    holding it returns that expert's FFN for every row, the others zeros."""
    cfg = _moe_cfg(top_k=1)
    params = moe_lib.moe_init(jax.random.PRNGKey(0), cfg)
    params["expert_bias"] = jnp.zeros((8,)).at[3].set(10.0)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
    ctx = AnalogCtx(cfg=AnalogConfig(), gain_s=jnp.ones(()))
    p, c = _share(params, cfg, (2, 3))
    y, st = moe_lib.moe_apply_dropless(p, x, ctx, c)
    w1, w3, w2 = (params[f][3] for f in ("w1", "w3", "w2"))
    want = (jax.nn.silu(x @ w1) * (x @ w3)) @ w2
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-6)
    assert int(st[0]) == int(st[2]) == 32  # every row, all on one expert
    p, c = _share(params, cfg, (0, 1))
    y, st = moe_lib.moe_apply_dropless(p, x, ctx, c)
    assert float(jnp.abs(y).max()) == 0.0 and int(st[0]) == 0


def test_bucketed_prefill_conv_state_matches_exact_length():
    """Right-padded rows with their last positions leave the conv state
    (and the logits at those positions) bitwise as exact-length prefill."""
    mc, program = _program()
    cfg, params = mc, program.params
    lens = [5, 11, 16]
    rng = np.random.default_rng(0)
    toks = np.zeros((3, 16), np.int32)
    for i, n in enumerate(lens):
        toks[i, :n] = rng.integers(0, cfg.vocab, n)
    toks[0, 5:] = 7  # pad ids that are not zeros
    cache = lm.init_lm_cache(cfg, 3, 16, cfg.dtype)
    lg, pc = lm.lm_forward(params, {"tokens": jnp.asarray(toks)}, program.cfg,
                           cfg, cache=cache, last_token_only=True,
                           last_index=jnp.asarray(lens) - 1)
    for i, n in enumerate(lens):
        c1 = lm.init_lm_cache(cfg, 1, n, cfg.dtype)
        lg1, pc1 = lm.lm_forward(params, {"tokens": jnp.asarray(toks[i:i + 1, :n])},
                                 program.cfg, cfg, cache=c1, last_token_only=True)
        np.testing.assert_array_equal(lg[i], lg1[0])
        for got, want in zip(pc[1], pc1[1]):
            if isinstance(got, shortconv.ConvState):
                np.testing.assert_array_equal(got.x[i], want.x[0])


def test_batched_prefill_bit_identical_to_solo():
    """Dropless MoE lets the paged engine batch prefill rows (4 at the
    smallest bucket), and what it serves is bitwise what solo prefill
    serves. The routing counts come back with the tokens."""
    mc, program = _program()
    reqs = _requests(TOY["vocab_size"], [4, 6, 7, 5, 20, 3])
    obs.reset()
    eng4, batched = _serve(mc, program, reqs, prefill_batch=4)
    counts = obs.counters()
    eng1, solo = _serve(mc, program, reqs, prefill_batch=1)
    assert eng4.prefill_batch == 4 and eng1.prefill_batch == 1
    for r in reqs:
        np.testing.assert_array_equal(batched[r.rid].tokens, solo[r.rid].tokens)
        np.testing.assert_array_equal(batched[r.rid].logits, solo[r.rid].logits)
    n_moe = 3  # MoE layers of the toy's five
    # a token is a prompt position or a decode step's live slot (each
    # request's 4 tokens after its first); the 2 held experts' MVMs run
    # over every row, pad positions, dummy rows and idle slots included
    real = sum(r.prompt.size + r.max_new_tokens - 1 for r in reqs)
    assert counts["moe.tokens"] == n_moe * real
    assert counts["moe.rows_computed"] > 2 * counts["moe.tokens"]
    assert 0 < counts["moe.rows_held"] <= 2 * counts["moe.tokens"]
    assert 0 < counts["moe.rows_max_expert"]


def test_moe_counts_only_real_rows():
    """Prompts of 20, 9 and 30 tokens share one prefill of 4 rows of 32
    (a dummy row, pad positions), then one decode step of 4 slots (one
    idle): the three MoE layers count 59 + 3 tokens each, while the 2 held
    experts' MVMs ran (4 x 32 + 4) rows each."""
    mc, program = _program()
    reqs = _requests(TOY["vocab_size"], [20, 9, 30], new=2)
    obs.reset()
    _serve(mc, program, reqs)
    counts = obs.counters()
    assert counts["moe.tokens"] == 3 * (59 + 3)
    assert counts["moe.rows_computed"] == 3 * 2 * (4 * 32 + 4)
    assert counts["moe.rows_held"] <= 2 * counts["moe.tokens"]


def test_slot_free_resets_conv_state():
    mc, program = _program()
    cfg = mc
    cache = lm.init_lm_cache(cfg, 3, S_MAX, cfg.dtype, stacked=False,
                             paged=True, page_size=8)
    toks = jnp.asarray(np.arange(1, 9, dtype=np.int32)[None])
    pc = lm.init_lm_cache(cfg, 1, 8, cfg.dtype)
    _, pc = lm.lm_forward(program.params, {"tokens": toks}, program.cfg, cfg,
                          cache=pc, last_token_only=True)
    pc = lm.unstack_cache(pc)
    pages = jnp.asarray([1], jnp.int32)
    for slot in (0, 2):
        cache = lm.write_cache_slot_paged(cache, pc, slot, 0, pages + slot, 8)
    states = [c for c in cache[1] if isinstance(c, shortconv.ConvState)]
    assert states and all(float(jnp.abs(s.x[2]).max()) > 0 for s in states)
    freed = lm.free_cache_slot_paged(cache, 2, jnp.asarray([3, 0, 0, 0, 0, 0, 0, 0]))
    for before, after in zip(cache[1], freed[1]):
        if isinstance(after, shortconv.ConvState):
            assert float(jnp.abs(after.x[2]).max()) == 0.0
            np.testing.assert_array_equal(after.x[0], before.x[0])


@pytest.mark.parametrize("arch", ["lfm2-8b-a1b"])
def test_full_config_has_published_widths(arch):
    cfg = configs.get(arch)
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd) == (2048, 32, 8, 64)
    assert (cfg.d_ff, cfg.moe_d_ff, cfg.n_experts, cfg.top_k) == (7168, 1792, 32, 4)
    kinds = lm.layer_kinds(cfg)
    assert [i for i, k in enumerate(kinds) if k in ("attn", "moe")] == [
        2, 6, 10, 14, 18, 21]
    assert kinds[:2] == ["conv", "conv"] and kinds.count("conv_moe") == 16
