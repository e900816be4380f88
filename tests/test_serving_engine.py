"""Continuous-batching serving engine invariants (repro.serving).

The load-bearing claim: continuous batching is *semantically inert* --
per-request generations are bit-identical to serving the request alone on a
fresh engine with a frozen chip draw; scheduling only changes when work
happens. Plus the scheduler invariants (no double-booked slots, reset
before re-admission, FIFO waves) and the drift-lifecycle composition
(DriftPolicy ages the chip between decode steps with zero programming
events; refresh accounts for its own)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine as engine_mod
from repro.core.analog import AnalogConfig
from repro.core.engine import DriftSchedule
from repro.models import ModelConfig, init_lm_cache, lm_forward, lm_init
from repro.models import attention as attn_lib
from repro.models.lm import (
    free_cache_slot_paged,
    reset_cache_slot,
    unstack_cache,
    write_cache_slot,
    write_cache_slot_paged,
)
from repro.serving import (
    BucketedScheduler,
    ContinuousScheduler,
    DriftPolicy,
    Request,
    ServingConfig,
    ServingEngine,
    StaticBatchScheduler,
    poisson_trace,
)
from repro.serving.engine import _kv_cache_bytes

DIGITAL = AnalogConfig()
S_MAX = 48


def _cfg(**kw):
    return ModelConfig(name="t", family=kw.pop("family", "dense"), **kw).smoke()


@pytest.fixture(scope="module")
def dense_cfg():
    return _cfg(n_kv_heads=2)


@pytest.fixture(scope="module")
def dense_params(dense_cfg):
    return lm_init(jax.random.PRNGKey(0), dense_cfg)


@pytest.fixture(scope="module")
def program(dense_cfg, dense_params):
    """ONE frozen chip draw shared by every test in the module."""
    return engine_mod.compile_program(
        dense_params,
        AnalogConfig().infer(b_adc=8, t_seconds=86400.0),
        jax.random.PRNGKey(42),
    )


def _trace(cfg, n=5, key=1, new_tokens=(3, 10)):
    return poisson_trace(
        jax.random.PRNGKey(key), n, vocab=cfg.vocab,
        prompt_lens=(4, 8, 12), new_tokens=new_tokens,
    )


# ------------------------------------------------------------ bit-identity


def test_continuous_bit_identical_to_solo_on_frozen_chip(dense_cfg, program):
    """Acceptance criterion: each request's generation under continuous
    batching equals serving it ALONE on a fresh single-slot engine."""
    trace = _trace(dense_cfg)
    served = ServingEngine.for_program(
        program, dense_cfg, ServingConfig(n_slots=3, s_max=S_MAX)
    )
    rep = served.run(trace)
    solo = ServingEngine.for_program(
        program, dense_cfg, ServingConfig(n_slots=1, s_max=S_MAX)
    )
    for r in trace:
        alone = solo.run([r]).tokens_of(r.rid)
        together = rep.tokens_of(r.rid)
        assert np.array_equal(alone, together), (r.rid, alone, together)


def test_static_and_continuous_schedulers_same_outputs(dense_cfg, program):
    """Scheduling changes throughput, never tokens."""
    trace = [
        r if i % 3 else dataclasses.replace(r, max_new_tokens=12)
        for i, r in enumerate(_trace(dense_cfg, n=6, new_tokens=(3, 4)))
    ]
    served = ServingEngine.for_program(
        program, dense_cfg, ServingConfig(n_slots=3, s_max=S_MAX)
    )
    rep_c = served.run(trace, scheduler=ContinuousScheduler())
    rep_s = served.run(trace, scheduler=StaticBatchScheduler())
    for r in trace:
        assert np.array_equal(rep_c.tokens_of(r.rid), rep_s.tokens_of(r.rid))
    # the long-request mix makes wave padding visible: continuous batching
    # serves the same tokens in strictly fewer decode steps
    assert rep_c.n_steps < rep_s.n_steps
    assert rep_c.n_generated == rep_s.n_generated
    assert rep_c.occupancy > rep_s.occupancy


def test_digital_engine_matches_full_forward_oracle():
    """Per-slot prefill+decode == re-running the growing sequence through
    the plain forward pass, for every cache family."""
    for kw in (
        dict(family="dense", n_kv_heads=2),
        dict(family="hybrid", block_pattern=("rec", "rec", "attn")),
        dict(family="ssm", ssm_state=16),
    ):
        cfg = _cfg(**kw)
        params = lm_init(jax.random.PRNGKey(0), cfg)
        served = ServingEngine(
            cfg, DIGITAL, params, ServingConfig(n_slots=3, s_max=S_MAX)
        )
        # two staggered-length requests share the batch
        reqs = [
            Request(rid=0, prompt=np.arange(9) % cfg.vocab, max_new_tokens=5),
            Request(rid=1, prompt=np.arange(4) % cfg.vocab, max_new_tokens=6),
        ]
        rep = served.run(reqs)
        for req in reqs:
            toks = list(req.prompt)
            want = []
            for _ in range(req.max_new_tokens):
                lg, _ = lm_forward(
                    params,
                    {"tokens": jnp.asarray(toks, jnp.int32)[None]},
                    DIGITAL, cfg,
                )
                t = int(jnp.argmax(lg[0, -1]))
                want.append(t)
                toks.append(t)
            got = rep.tokens_of(req.rid).tolist()
            assert got == want, (kw["family"], req.rid, got, want)


def test_ref_counters_perfect_agreement_for_digital_engine(
    dense_cfg, dense_params
):
    """Digital engine vs digital reference: the teacher-forced counters
    must read exactly top1=1.0, mse=0 -- pins the counter plumbing."""
    served = ServingEngine(
        dense_cfg, DIGITAL, dense_params, ServingConfig(n_slots=2, s_max=S_MAX),
        ref_params=dense_params,
    )
    rep = served.run(_trace(dense_cfg, n=3))
    assert rep.counters["top1"] == 1.0
    assert rep.counters["logit_mse"] == 0.0
    assert rep.counters["decisions"] == rep.n_generated


# ------------------------------------------------------ scheduler invariants


def test_slots_never_serve_two_live_requests(dense_cfg, dense_params):
    served = ServingEngine(
        dense_cfg, DIGITAL, dense_params, ServingConfig(n_slots=2, s_max=S_MAX)
    )
    rep = served.run(_trace(dense_cfg, n=7, key=3))
    assert rep.n_requests == 7
    by_slot: dict = {}
    for r in rep.records:
        by_slot.setdefault(r.slot, []).append(r)
    for recs in by_slot.values():
        recs.sort(key=lambda r: r.admit_step)
        for a, b in zip(recs, recs[1:]):
            # a slot is re-admitted only at/after its previous retirement
            assert b.admit_step >= a.finish_step, (a, b)


def test_static_scheduler_admits_in_waves(dense_cfg, dense_params):
    served = ServingEngine(
        dense_cfg, DIGITAL, dense_params, ServingConfig(n_slots=3, s_max=S_MAX)
    )
    reqs = [
        Request(rid=i, prompt=np.arange(4), max_new_tokens=4)
        for i in range(5)
    ]
    rep = served.run(reqs, scheduler=StaticBatchScheduler())
    admits = sorted(r.admit_step for r in rep.records)
    finishes = {r.rid: r.finish_step for r in rep.records}
    # wave 1: three requests at step 0; wave 2 starts only when ALL of
    # wave 1 has drained
    assert admits[:3] == [0, 0, 0]
    wave1_end = max(finishes[i] for i in range(3))
    assert admits[3] >= wave1_end
    assert admits[3] == admits[4]


def test_retired_slot_is_reset_before_readmission(dense_cfg, dense_params):
    """More requests than slots forces re-admission into retired slots; a
    stale (non-reset) cache row would corrupt the follow-on request, which
    the solo comparison would catch."""
    served = ServingEngine(
        dense_cfg, DIGITAL, dense_params, ServingConfig(n_slots=1, s_max=S_MAX)
    )
    reqs = [
        Request(rid=0, prompt=np.arange(12) % dense_cfg.vocab,
                max_new_tokens=6),
        Request(rid=1, prompt=np.arange(5) % dense_cfg.vocab,
                max_new_tokens=6),
    ]
    rep = served.run(reqs)
    reused = [r for r in rep.records if r.rid == 1][0]
    assert reused.slot == 0  # same slot, re-admitted
    fresh = ServingEngine(
        dense_cfg, DIGITAL, dense_params, ServingConfig(n_slots=1, s_max=S_MAX)
    )
    alone = fresh.run([reqs[1]])
    assert np.array_equal(alone.tokens_of(1), rep.tokens_of(1))


def test_eos_retires_a_request_early(dense_cfg, dense_params):
    served = ServingEngine(
        dense_cfg, DIGITAL, dense_params, ServingConfig(n_slots=1, s_max=S_MAX)
    )
    req = Request(rid=0, prompt=np.arange(6), max_new_tokens=8)
    full = served.run([req]).tokens_of(0)
    eos = int(full[2])
    rep = served.run(
        [dataclasses.replace(req, eos_id=eos)]
    )
    rec = rep.records[0]
    assert rec.finished_by == "eos"
    got = rep.tokens_of(0)
    assert got[-1] == eos
    assert got.size == int(np.argmax(full == eos)) + 1
    assert np.array_equal(got, full[: got.size])


def test_occupancy_and_latency_metrics(dense_cfg, dense_params):
    served = ServingEngine(
        dense_cfg, DIGITAL, dense_params, ServingConfig(n_slots=2, s_max=S_MAX)
    )
    rep = served.run(_trace(dense_cfg, n=4))
    assert 0.0 < rep.occupancy <= 1.0
    assert rep.slot_steps <= rep.n_steps * rep.n_slots
    assert rep.latency_s(95) >= rep.latency_s(50) >= 0.0
    assert rep.tokens_per_s > 0 and rep.requests_per_s > 0
    assert "mode=continuous" in rep.summary()


# ------------------------------------------------------------ cache helpers


def test_write_and_reset_cache_slot(dense_cfg, dense_params):
    """lm-level slot helpers: write lands the request's row (and scalar
    length) in exactly one slot; reset zeroes exactly that slot."""
    shared = init_lm_cache(
        dense_cfg, 3, 16, jnp.float32, stacked=False, per_slot=True
    )
    single = init_lm_cache(dense_cfg, 1, 16, jnp.float32)
    toks = jnp.arange(6, dtype=jnp.int32)[None, :]
    _, single = lm_forward(
        dense_params, {"tokens": toks}, DIGITAL, dense_cfg, cache=single,
        last_token_only=True,
    )
    single = unstack_cache(single)
    shared = write_cache_slot(shared, single, 1)
    for dst, src in zip(jax.tree.leaves(shared), jax.tree.leaves(single)):
        if dst.ndim == src.ndim:
            assert np.array_equal(np.asarray(dst[1]), np.asarray(src[0]))
            assert not np.any(np.asarray(dst[0]))  # other slots untouched
            assert not np.any(np.asarray(dst[2]))
        else:  # per-slot length vector <- scalar
            assert dst.shape == (3,)
            assert int(dst[1]) == int(src) == 6
            assert int(dst[0]) == int(dst[2]) == 0
    shared = reset_cache_slot(shared, 1)
    for leaf in jax.tree.leaves(shared):
        assert not np.any(np.asarray(leaf))


def test_per_slot_cache_requires_unstacked_layout(dense_cfg):
    with pytest.raises(ValueError, match="unstacked"):
        init_lm_cache(
            dense_cfg, 2, 16, jnp.float32, stacked=True, per_slot=True
        )


# -------------------------------------------------------------- validation


def test_request_validation():
    with pytest.raises(ValueError, match="empty prompt"):
        Request(rid=0, prompt=np.zeros((0,)), max_new_tokens=4)
    with pytest.raises(ValueError, match="max_new_tokens"):
        Request(rid=0, prompt=np.arange(4), max_new_tokens=0)


def test_run_rejects_requests_that_overflow_s_max(dense_cfg, dense_params):
    served = ServingEngine(
        dense_cfg, DIGITAL, dense_params, ServingConfig(n_slots=1, s_max=8)
    )
    with pytest.raises(ValueError, match="s_max"):
        served.run([Request(rid=0, prompt=np.arange(6), max_new_tokens=6)])


def test_engine_rejects_codebook_decoders(dense_cfg, dense_params):
    cb_cfg = dataclasses.replace(dense_cfg, n_codebooks=2)
    with pytest.raises(NotImplementedError, match="token stream"):
        ServingEngine(cb_cfg, DIGITAL, dense_params, ServingConfig(n_slots=1, s_max=8))


def test_poisson_trace_shapes_and_arrivals(dense_cfg):
    trace = poisson_trace(
        jax.random.PRNGKey(0), 8, vocab=dense_cfg.vocab, rate=100.0,
        prompt_lens=(4, 8), new_tokens=(2, 5),
    )
    arr = [r.arrival_t for r in trace]
    assert arr[0] == 0.0
    assert all(b >= a for a, b in zip(arr, arr[1:]))
    assert any(t > 0 for t in arr[1:])
    for r in trace:
        assert r.prompt.size in (4, 8)
        assert 2 <= r.max_new_tokens <= 5
        assert r.prompt.dtype == np.int32
    saturated = poisson_trace(
        jax.random.PRNGKey(0), 4, vocab=dense_cfg.vocab
    )
    assert all(r.arrival_t == 0.0 for r in saturated)


def test_poisson_arrivals_gate_admission(dense_cfg, dense_params):
    """With a virtual clock, a request that has not arrived must not be
    admitted even when slots are free."""
    clock = {"t": 0.0}

    def now():
        return clock["t"]

    def sleep(dt):
        clock["t"] += max(dt, 1e-3)

    served = ServingEngine(
        dense_cfg, DIGITAL, dense_params, ServingConfig(n_slots=2, s_max=S_MAX)
    )
    reqs = [
        Request(rid=0, prompt=np.arange(4), max_new_tokens=2),
        Request(rid=1, prompt=np.arange(4), max_new_tokens=2,
                arrival_t=0.5),  # arrives later on the virtual clock
    ]
    rep = served.run(reqs, now_fn=now, sleep_fn=sleep)
    recs = {r.rid: r for r in rep.records}
    assert recs[0].admit_t < 0.5 <= recs[1].admit_t
    assert recs[1].admit_step >= recs[0].finish_step


# ----------------------------------------------------------- paged KV cache


def test_paged_bit_identical_to_rect_across_page_sizes(dense_cfg, program):
    """The tentpole invariant: paged serving (bucketed padded prefill,
    page-table gather decode, lazy page growth) is bit-identical to the
    rectangular slot cache on the same frozen chip draw -- including page
    sizes that do NOT divide the prompt lengths (ps=5 vs prompts 9/23)."""
    trace = poisson_trace(
        jax.random.PRNGKey(1), 7, vocab=dense_cfg.vocab,
        prompt_lens=(4, 9, 16, 23, 33), new_tokens=(3, 10),
    )
    rect = ServingEngine.for_program(
        program, dense_cfg, ServingConfig(n_slots=3, s_max=S_MAX)
    )
    rep_r = rect.run(list(trace))
    for ps in (4, 5, 16):
        paged = ServingEngine.for_program(
            program, dense_cfg, ServingConfig(n_slots=3, s_max=S_MAX,
            paged=True, page_size=ps, prefill_batch=2),
        )
        rep_p = paged.run(list(trace), scheduler=BucketedScheduler())
        for r in trace:
            assert np.array_equal(
                rep_p.tokens_of(r.rid), rep_r.tokens_of(r.rid)
            ), (ps, r.rid)
        assert rep_p.n_prefill_traces <= len(paged.prefill_buckets)
        assert rep_p.peak_pages_in_use > 0
        assert rep_p.program_events_delta == 0


def test_paged_long_prompts_flat_memory(dense_cfg, program):
    """Virtual capacity: prompts the rectangle could not afford, served at
    a page pool SMALLER than the rectangular cache -- and still bitwise
    equal to one-at-a-time rectangular serving."""
    s_virt = 384
    n_pages = 26  # 25 usable pages * 16 = 400 rows vs 2*384 = 768 rect rows
    long_reqs = poisson_trace(
        jax.random.PRNGKey(2), 4, vocab=dense_cfg.vocab,
        prompt_lens=(16, 150, 300), new_tokens=(3, 6),
    )
    paged = ServingEngine.for_program(
        program, dense_cfg, ServingConfig(n_slots=2, s_max=s_virt,
        paged=True, page_size=16, n_pages=n_pages, prefill_batch=2),
    )
    rep = paged.run(list(long_reqs), scheduler=BucketedScheduler())
    solo = ServingEngine.for_program(
        program, dense_cfg, ServingConfig(n_slots=1, s_max=s_virt)
    )
    rep_s = solo.run(list(long_reqs))
    for r in long_reqs:
        assert np.array_equal(rep.tokens_of(r.rid), rep_s.tokens_of(r.rid))
    rect_bytes = _kv_cache_bytes(
        init_lm_cache(
            dense_cfg, 2, s_virt, dense_cfg.dtype,
            stacked=False, per_slot=True,
        )
    )
    assert rep.peak_kv_bytes < rect_bytes
    assert 0 < rep.peak_pages_in_use <= n_pages - 1


def test_paged_drift_lifecycle_composition(dense_cfg, dense_params):
    """Paged serving composes with the drift lifecycle: the same
    DriftPolicy ages the chip at the same decode steps, and the paged
    generations stay bit-identical to the rectangular engine's."""
    program = engine_mod.compile_program(
        dense_params, AnalogConfig().infer(b_adc=8, t_seconds=25.0),
        jax.random.PRNGKey(5),
    )
    policy = DriftPolicy(
        DriftSchedule((25.0, 3600.0, 86400.0)), every_steps=2
    )
    trace = _trace(dense_cfg, n=4, new_tokens=(6, 10))
    rect = ServingEngine.for_program(
        program, dense_cfg, ServingConfig(n_slots=2, s_max=S_MAX)
    )
    rep_r = rect.run(trace, drift_policy=policy)
    # prefill_batch=1 + FIFO admission: decode steps align with the
    # rectangular engine's, so the age ticks land at the same steps
    paged = ServingEngine.for_program(
        program, dense_cfg, ServingConfig(n_slots=2, s_max=S_MAX,
        paged=True, page_size=8, prefill_batch=1),
    )
    rep_p = paged.run(trace, drift_policy=policy)
    for r in trace:
        assert np.array_equal(rep_p.tokens_of(r.rid), rep_r.tokens_of(r.rid))
    assert rep_p.program_events_delta == 0
    assert (
        [e["step"] for e in rep_p.age_events]
        == [e["step"] for e in rep_r.age_events]
    )
    assert paged.program.t_seconds == 86400.0


def test_paged_prefill_traces_bounded_by_buckets(dense_cfg, dense_params,
                                                 assert_max_retraces):
    """Satellite: many distinct prompt lengths compile one prefill trace
    per BUCKET in paged mode, but one per LENGTH in exact-length mode."""
    lens = tuple(range(5, 17))  # 12 distinct lengths
    reqs = [
        Request(rid=i, prompt=(np.arange(n) % dense_cfg.vocab).astype(np.int32),
                max_new_tokens=2)
        for i, n in enumerate(lens)
    ]
    paged = ServingEngine(
        dense_cfg, DIGITAL, dense_params, ServingConfig(n_slots=2, s_max=S_MAX,
        paged=True, page_size=8),
    )
    rep_p = paged.run(list(reqs), scheduler=BucketedScheduler())
    assert rep_p.n_prefill_traces <= len(paged.prefill_buckets)
    # dynamic pin of the RL003 invariant: a second identical run over the
    # warmed buckets must not compile anything new
    with assert_max_retraces(0):
        paged.run(list(reqs), scheduler=BucketedScheduler())
    rect = ServingEngine(
        dense_cfg, DIGITAL, dense_params, ServingConfig(n_slots=2, s_max=S_MAX)
    )
    rep_r = rect.run(list(reqs))
    assert rep_r.n_prefill_traces == len(lens)
    for r in reqs:
        assert np.array_equal(rep_p.tokens_of(r.rid), rep_r.tokens_of(r.rid))


def test_serve_report_empty_run(dense_cfg, dense_params):
    """Edge case: an empty trace is a valid run -- zero everything, no
    division blowups, summary still renders."""
    served = ServingEngine(
        dense_cfg, DIGITAL, dense_params, ServingConfig(n_slots=2, s_max=S_MAX)
    )
    rep = served.run([])
    assert rep.n_requests == 0 and rep.n_generated == 0 and rep.n_steps == 0
    assert rep.occupancy == 0.0
    assert rep.latency_s(95) == 0.0 and rep.ttft_s(95) == 0.0
    assert rep.tokens_per_s == 0.0 and rep.requests_per_s == 0.0
    assert rep.program_events_delta == 0
    assert "requests=0" in rep.summary()
    with pytest.raises(KeyError):
        rep.tokens_of(0)


def test_serve_report_single_request_no_decode_steps(dense_cfg, dense_params):
    """Edge case: max_new_tokens=1 retires at prefill -- the run has zero
    decode steps yet one generated token, and the metrics stay sane."""
    served = ServingEngine(
        dense_cfg, DIGITAL, dense_params, ServingConfig(n_slots=2, s_max=S_MAX)
    )
    rep = served.run(
        [Request(rid=7, prompt=np.arange(6), max_new_tokens=1)]
    )
    assert rep.n_requests == 1 and rep.n_generated == 1
    assert rep.n_steps == 0 and rep.slot_steps == 0
    assert rep.occupancy == 0.0
    assert rep.tokens_of(7).size == 1
    rec = rep.records[0]
    assert rec.finished_by == "max_tokens"
    assert 0.0 <= rec.ttft_s <= rec.latency_s
    assert rep.ttft_s(95) == rec.ttft_s
    assert "requests=1" in rep.summary()


def test_paged_engine_validation(dense_cfg, dense_params):
    mk = lambda **kw: ServingEngine(
        dense_cfg, DIGITAL, dense_params,
        ServingConfig(n_slots=1, s_max=16, paged=True, **kw),
    )
    with pytest.raises(ValueError, match="page_size"):
        mk(page_size=0)
    with pytest.raises(ValueError, match="prefill_batch"):
        mk(prefill_batch=0)
    # recurrent families carry position-free state that right-padded
    # bucketed prefill would corrupt -- rejected at construction
    for kw in (
        dict(family="ssm", ssm_state=16),
        dict(family="hybrid", block_pattern=("rec", "rec", "attn")),
    ):
        cfg = _cfg(**kw)
        with pytest.raises(ValueError, match="position-free"):
            ServingEngine(
                cfg, DIGITAL, lm_init(jax.random.PRNGKey(0), cfg),
                ServingConfig(n_slots=1, s_max=16, paged=True),
            )
    audio_cfg = dataclasses.replace(dense_cfg, frontend="audio_frames")
    with pytest.raises(NotImplementedError, match="feature-fed"):
        ServingEngine(
            audio_cfg, DIGITAL, dense_params, ServingConfig(n_slots=1, s_max=16,
            paged=True),
        )


def test_paged_run_rejects_infeasible_and_feature_requests(
    dense_cfg, dense_params
):
    tight = ServingEngine(
        dense_cfg, DIGITAL, dense_params, ServingConfig(n_slots=1, s_max=48,
        paged=True, page_size=8, n_pages=3),  # 2 usable pages = 16 rows
    )
    with pytest.raises(ValueError, match="never be admitted"):
        tight.run(
            [Request(rid=0, prompt=np.arange(20), max_new_tokens=10)]
        )
    roomy = ServingEngine(
        dense_cfg, DIGITAL, dense_params, ServingConfig(n_slots=1, s_max=48,
        paged=True, page_size=8),
    )
    with pytest.raises(NotImplementedError, match="paged mode"):
        roomy.run(
            [Request(rid=0, prompt=np.arange(4), max_new_tokens=2,
                     features={"audio_frames": np.zeros((1, 2, 4))})]
        )


def test_paged_free_leaves_other_slots_pages_untouched(
    dense_cfg, dense_params
):
    """Satellite: freeing one slot's pages zeroes exactly those pool rows;
    every page owned by another slot stays bitwise untouched."""
    ps = 4
    paged = init_lm_cache(
        dense_cfg, 2, 16, jnp.float32, stacked=False,
        paged=True, page_size=ps, n_pages=8,
    )

    def prefill_src(shift):
        single = init_lm_cache(dense_cfg, 1, 8, jnp.float32)
        toks = ((jnp.arange(8) + shift) % dense_cfg.vocab).astype(jnp.int32)
        _, c = lm_forward(
            dense_params, {"tokens": toks[None]}, DIGITAL, dense_cfg,
            cache=single, last_token_only=True,
        )
        return unstack_cache(c)

    paged = write_cache_slot_paged(
        paged, prefill_src(0), 0, 0, np.array([1, 2], np.int32), 8
    )
    paged = write_cache_slot_paged(
        paged, prefill_src(3), 1, 0, np.array([3, 4], np.int32), 8
    )

    def paged_leaves(tree):
        return [
            leaf
            for leaf in jax.tree.leaves(
                tree, is_leaf=lambda x: isinstance(x, attn_lib.PagedKVCache)
            )
            if isinstance(leaf, attn_lib.PagedKVCache)
        ]

    before = [
        (np.asarray(c.k), np.asarray(c.v), np.asarray(c.table),
         np.asarray(c.length))
        for c in paged_leaves(paged)
    ]
    pvec = np.zeros((4,), np.int32)
    pvec[:2] = (1, 2)
    freed = free_cache_slot_paged(paged, 0, pvec)
    for (k0, v0, tab0, len0), c in zip(before, paged_leaves(freed)):
        assert not np.any(np.asarray(c.k)[:, 1:3])  # slot 0's pages zeroed
        assert not np.any(np.asarray(c.v)[:, 1:3])
        np.testing.assert_array_equal(np.asarray(c.k)[:, 3:5], k0[:, 3:5])
        np.testing.assert_array_equal(np.asarray(c.v)[:, 3:5], v0[:, 3:5])
        np.testing.assert_array_equal(np.asarray(c.table)[1], tab0[1])
        assert int(np.asarray(c.length)[1]) == int(len0[1]) == 8
        assert not np.any(np.asarray(c.table)[0])
        assert int(np.asarray(c.length)[0]) == 0


# ---------------------------------------------------------- drift lifecycle


def test_drift_policy_ages_chip_between_steps(dense_cfg, dense_params):
    program = engine_mod.compile_program(
        dense_params, AnalogConfig().infer(b_adc=8, t_seconds=25.0),
        jax.random.PRNGKey(5),
    )
    served = ServingEngine.for_program(
        program, dense_cfg, ServingConfig(n_slots=2, s_max=S_MAX),
    )
    policy = DriftPolicy(
        DriftSchedule((25.0, 3600.0, 86400.0)), every_steps=2
    )
    rep = served.run(
        _trace(dense_cfg, n=4, new_tokens=(6, 10)), drift_policy=policy
    )
    assert rep.program_events_delta == 0
    assert rep.reprograms == 0
    ages = [ev for ev in rep.age_events if ev["kind"] == "age"]
    assert [ev["t_wall"] for ev in ages] == [3600.0, 86400.0]
    assert served.program.t_seconds == 86400.0
    assert served.program.age_history == (25.0, 3600.0, 86400.0)


def test_drift_policy_refresh_on_degraded_agreement(dense_cfg, dense_params):
    program = engine_mod.compile_program(
        dense_params, AnalogConfig().infer(b_adc=8, t_seconds=25.0),
        jax.random.PRNGKey(6),
    )
    served = ServingEngine.for_program(
        program, dense_cfg, ServingConfig(n_slots=2, s_max=S_MAX),
        ref_params=dense_params, src_params=dense_params,
    )
    policy = DriftPolicy(
        DriftSchedule((25.0, 3600.0)), every_steps=3,
        refresh_below=1.1,  # untrained net: always degraded -> always fires
    )
    rep = served.run(
        _trace(dense_cfg, n=4, new_tokens=(6, 10)), drift_policy=policy
    )
    assert rep.reprograms >= 1
    assert any(ev["kind"] == "reprogram" for ev in rep.age_events)
    # the zero-delta contract still holds: every programming event is
    # accounted to a refresh
    assert rep.program_events_delta == 0


def test_drift_policy_validation():
    with pytest.raises(ValueError, match="every_steps"):
        DriftPolicy(DriftSchedule((25.0,)), every_steps=0)


def test_age_to_requires_a_program(dense_cfg, dense_params):
    served = ServingEngine(
        dense_cfg, DIGITAL, dense_params, ServingConfig(n_slots=1, s_max=8)
    )
    with pytest.raises(RuntimeError, match="digital"):
        served.age_to(3600.0)
    with pytest.raises(RuntimeError, match="src_params"):
        served.refresh(jax.random.PRNGKey(0))
