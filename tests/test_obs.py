"""The program's recorder (repro.obs) and what the engine records in it.

Serving runs here step a paged engine on a VirtualClock, so every span the
engine records is exact; the recorder's hooks (compiles, collections) and
the named scopes the model step carries are checked at smoke size.
"""

import gc
import glob
import os
import re
import queue
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.clock import VirtualClock
from repro.core import engine as engine_mod
from repro.core.analog import AnalogConfig
from repro.models import ModelConfig, lm_init
from repro.models.analognet import (
    CNNConfig,
    ConvSpec,
    cnn_apply,
    cnn_init,
    crossbar_transforms,
)
from repro.serving import (
    BucketedScheduler,
    Request,
    ServingConfig,
    ServingEngine,
    poisson_trace,
)

#: the benchmark harness's own span names, which the program's must avoid
HARNESS_SPANS = {"bench_window", "admit", "decode_step", "kws_batch"}
ENGINE_SPANS = {
    "engine.admit", "engine.prefill", "engine.first_token",
    "engine.slot_write", "engine.decode", "engine.page_append",
    "engine.decode_launch", "engine.decode_sync", "engine.tokens",
}


@pytest.fixture(scope="module")
def cfg():
    return ModelConfig(name="t", family="dense", n_kv_heads=2).smoke()


@pytest.fixture(scope="module")
def program(cfg):
    return engine_mod.compile_program(
        lm_init(jax.random.PRNGKey(0), cfg),
        AnalogConfig().infer(b_adc=8, t_seconds=86400.0),
        jax.random.PRNGKey(42),
    )


def _engine(cfg, program, **kw):
    scfg = ServingConfig(n_slots=4, s_max=48, paged=True, page_size=8, **kw)
    return ServingEngine.for_program(program, cfg, scfg)


def _spans(recs):
    return [r for r in recs if r[0] == obs.SPAN]


@pytest.fixture(scope="module")
def paged_run(cfg, program):
    """(records, report, requests) of one paged run on a virtual clock."""
    eng = _engine(cfg, program)
    reqs = poisson_trace(
        jax.random.PRNGKey(1), 6, vocab=cfg.vocab, prompt_lens=(4, 8, 12),
        new_tokens=(3, 10), rate=200.0,
    )
    eng.run(reqs, scheduler=BucketedScheduler(), clock=VirtualClock())  # warm
    c0 = obs.cursor()
    rep = eng.run(reqs, scheduler=BucketedScheduler(), clock=VirtualClock())
    return obs.records(c0), rep, reqs


# ------------------------------------------------------------- the engine


def test_every_decode_has_exactly_one_sync_child(paged_run):
    recs, rep, _ = paged_run
    spans = _spans(recs)
    decodes = [r for r in spans if r[1] == "engine.decode"]
    assert len(decodes) == rep.n_steps > 0
    for d in decodes:
        kids = [r[1] for r in spans if r[5] == d[4]]
        assert kids.count("engine.decode_sync") == 1
        assert set(kids) == {"engine.page_append", "engine.decode_launch",
                             "engine.decode_sync", "engine.tokens"}


def test_children_fit_inside_their_parent(paged_run):
    spans = _spans(paged_run[0])
    by_id = {r[4]: r for r in spans}
    kids: dict = {}
    for r in spans:
        if r[5] in by_id:
            kids.setdefault(r[5], []).append(r)
    assert kids
    for pid, rs in kids.items():
        p = by_id[pid]
        assert sum(r[3] - r[2] for r in rs) <= p[3] - p[2]
        assert all(p[2] <= r[2] and r[3] <= p[3] for r in rs)


def test_queue_wait_is_admission_minus_arrival(cfg, program):
    clk = VirtualClock(tick=1e-3)
    eng = _engine(cfg, program)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab, 6),
                    max_new_tokens=3, arrival_t=t)
            for i, t in enumerate((0.0, 0.0, 0.004, 0.02))]
    eng.run(reqs, scheduler=BucketedScheduler(), clock=clk)  # warm
    c0 = obs.cursor()
    run = eng.start_run(scheduler=BucketedScheduler(), clock=clk)
    run.submit(reqs)
    while run.has_work:
        run.admit_arrived()
        if run.n_active == 0:
            run.idle_wait()
            continue
        run.decode_step()
    recs = obs.records(c0)
    admits = {r[4]: r for r in _spans(recs) if r[1] == "engine.admit"}
    waits = {r[6]: r for r in recs
             if r[0] == obs.SAMPLE and r[1] == "engine.queue_wait"}
    assert sorted(waits) == [0, 1, 2, 3]
    arrival = {r.rid: r.arrival_t for r in reqs}
    for rid, w in waits.items():
        admit = admits[w[5]]  # the admission the sample was made in
        assert w[2] == admit[2]
        assert w[7] == pytest.approx(admit[2] - run.t_start - arrival[rid])
        assert w[7] >= 0.0
    assert sum(a[7] for a in admits.values()) == 4  # requests admitted


def test_prefill_token_counters_count_rows_times_bucket(cfg, program):
    # buckets 16, 32 and 48 (s_max) at prefill_batch 2: the budget is 32
    # tokens, so bucket 16 takes 2 rows a call and buckets 32 and 48 one
    eng = _engine(cfg, program, prefill_buckets=(16, 32), prefill_batch=2)
    rng = np.random.default_rng(1)
    lens = (5, 7, 20, 3)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab, n),
                    max_new_tokens=2) for i, n in enumerate(lens)]
    eng.run(reqs, scheduler=BucketedScheduler())  # warm
    c0 = obs.cursor()
    before = obs.counters()
    eng.run(reqs, scheduler=BucketedScheduler())
    recs = obs.records(c0)
    # the bucketed scheduler admits by bucket: [5, 7] at 16 (2 rows), [3]
    # at 16 (2 rows, one a dummy), [20] at 32 (1 row)
    computed = 2 * 16 + 2 * 16 + 1 * 32
    prefills = [(r[7], r[8]) for r in _spans(recs) if r[1] == "engine.prefill"]
    assert prefills == [(16, 2), (16, 2), (32, 1)]

    def added(name):
        return obs.counters()[name] - before.get(name, 0)

    assert added("engine.prefill_tokens_computed") == computed
    assert added("engine.prefill_tokens_real") == sum(lens)
    counts = [r for r in recs if r[0] == obs.COUNT
              and r[1] == "engine.prefill_tokens_computed"]
    assert sum(r[7] for r in counts) == computed
    # each count is timed by the prefill span it was made in
    ids = {r[4]: r for r in _spans(recs) if r[1] == "engine.prefill"}
    assert all(r[5] in ids and r[2] == ids[r[5]][2] for r in counts)


def _kv_counts(recs, name):
    return [r for r in recs if r[0] == obs.COUNT and r[1] == name]


def test_kv_read_counters_count_the_view_off_the_tpu(paged_run):
    """On the CPU the decode step attends over the whole gathered view:
    each step reads n_slots x s_max tokens, counted in its decode span."""
    recs, rep, _ = paged_run
    decodes = {r[4]: r for r in _spans(recs) if r[1] == "engine.decode"}
    for name in ("engine.kv_tokens_read", "engine.kv_tokens_view"):
        counts = _kv_counts(recs, name)
        assert len(counts) == rep.n_steps
        assert all(r[7] == 4 * 48 for r in counts)
        assert all(r[5] in decodes and r[2] == decodes[r[5]][2]
                   for r in counts)


def test_kv_read_counter_rounds_live_lengths_to_the_kernel_block(
    cfg, program
):
    """On the kernel path a step reads each live slot's length rounded up
    to the compute block, from the host's bookkeeping."""
    eng = _engine(cfg, program)
    rng = np.random.default_rng(2)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab, n),
                    max_new_tokens=4) for i, n in enumerate((5, 17))]
    run = eng.start_run(scheduler=BucketedScheduler(), clock=VirtualClock())
    assert run.kv_block is None  # the CPU takes the gathered view
    run.kv_block = 16  # as a TPU's kernel would read
    run.submit(reqs)
    run.admit_arrived()
    c0 = obs.cursor()
    run.decode_step()  # lengths 6 and 18: one block and two
    (read,) = _kv_counts(obs.records(c0), "engine.kv_tokens_read")
    assert read[7] == 16 + 32


def test_report_timings_are_the_runs_spans(paged_run):
    recs, rep, _ = paged_run
    spans = _spans(recs)
    key = next(r[6] for r in spans if r[1] == "engine.decode")
    assert all(r[6] == key for r in spans
               if r[1] in ("engine.admit", "engine.decode"))
    assert rep.t_decode == pytest.approx(
        sum(r[3] - r[2] for r in spans if r[1] == "engine.decode"))
    assert rep.t_prefill == pytest.approx(
        sum(r[3] - r[2] for r in spans if r[1] == "engine.admit"))
    assert rep.t_decode > 0 and rep.t_prefill > 0


def test_engine_span_names_differ_from_the_harness(paged_run):
    names = {r[1] for r in _spans(paged_run[0])}
    assert names == ENGINE_SPANS
    assert not names & HARNESS_SPANS
    assert "program" not in HARNESS_SPANS


def test_an_admission_round_that_admits_nothing_records_nothing(
    cfg, program
):
    eng = _engine(cfg, program)
    run = eng.start_run(clock=VirtualClock())
    run.submit([Request(rid=0, prompt=np.arange(4), max_new_tokens=2,
                        arrival_t=5.0)])
    c0 = obs.cursor()
    run.admit_arrived()  # nothing has arrived yet
    assert not [r for r in obs.records(c0) if r[0] != obs.GC]


# ----------------------------------------------------------- the recorder


def test_spans_nest_and_carry_their_numbers():
    clk = VirtualClock(tick=1.0)
    c0 = obs.cursor()
    with obs.span("t.outer", clk.now, key=7, a=1) as outer:
        outer.b = 2
        with obs.span("t.inner", clk.now, a=3):
            obs.sample("t.sample", 0.5, 10.0, rid=4)
        with obs.span("t.inner", clk.now):
            pass
    recs = obs.records(c0)
    spans = {r[1]: r for r in _spans(recs)}
    o = spans["t.outer"]
    assert (o[1], o[2], o[3], o[5], o[6], o[7], o[8]) == (
        "t.outer", 1.0, 6.0, -1, 7, 1, 2)
    inner = [r for r in _spans(recs) if r[1] == "t.inner"]
    assert [(r[2], r[3], r[5], r[7]) for r in inner] == [
        (2.0, 3.0, o[4], 3), (4.0, 5.0, o[4], 0)]
    (s,) = [r for r in recs if r[0] == obs.SAMPLE]
    assert (s[2], s[5], s[6], s[7]) == (10.0, inner[0][4], 4, 0.5)


def test_threads_keep_their_own_parents():
    clk = VirtualClock(tick=1.0)
    c0 = obs.cursor()
    boxes = (queue.Queue(), queue.Queue())

    def work(i):
        def meet():  # both threads hold a span open here at once
            boxes[1 - i].put(None)
            boxes[i].get(timeout=10)

        with obs.span(f"t.thread{i}", clk.now):
            meet()
            with obs.span(f"t.thread{i}.child", clk.now):
                meet()

    with ThreadPoolExecutor(2) as pool:
        list(pool.map(work, range(2)))
    spans = {r[1]: r for r in _spans(obs.records(c0))}
    for i in range(2):
        assert spans[f"t.thread{i}.child"][5] == spans[f"t.thread{i}"][4]


def test_threads_lose_no_record_or_count():
    n_threads, n_each = 16, 300
    c0 = obs.cursor()
    before = obs.counters().get("t.stress", 0)
    was = sys.getswitchinterval()

    def work(i):
        for _ in range(n_each):
            with obs.span("t.stress", time.monotonic, key=i):
                obs.count("t.stress")

    sys.setswitchinterval(1e-6)
    try:
        with ThreadPoolExecutor(n_threads) as pool:
            list(pool.map(work, range(n_threads), timeout=60))
    finally:
        sys.setswitchinterval(was)
    spans = [r for r in _spans(obs.records(c0)) if r[1] == "t.stress"]
    assert len(spans) == n_threads * n_each
    assert len({r[4] for r in spans}) == len(spans)  # distinct span ids
    assert obs.counters()["t.stress"] - before == n_threads * n_each


def test_a_full_ring_counts_what_it_dropped():
    clk = VirtualClock(tick=1.0)
    d0 = obs.dropped()
    for _ in range(obs.RING + 10):
        with obs.span("t.fill", clk.now):
            pass
    assert obs.dropped() >= d0 + 10
    assert len(obs.records()) == obs.RING
    assert obs.lost_until() >= 2.0  # the first overwritten span's end


def test_a_forced_collection_is_recorded():
    before = obs.counters().get("gc.gen2.n", 0)
    c0 = obs.cursor()
    with obs.span("t.collect", time.monotonic):
        gc.collect()
    recs = obs.records(c0)
    (sp,) = [r for r in _spans(recs) if r[1] == "t.collect"]
    full = [r for r in recs if r[0] == obs.GC and r[7] == 2]
    assert full and all(sp[2] <= r[2] <= r[3] <= sp[3] for r in full)
    assert all(r[5] == sp[4] for r in full)
    assert obs.counters()["gc.gen2.n"] >= before + 1
    assert obs.counters()["gc.gen2.s"] > 0.0


def test_a_compile_inside_a_span_is_attributed_to_it():
    c0 = obs.cursor()

    def fresh_recorder_probe(v):
        return jnp.cos(v) * 5.0 - 2.0

    x = jnp.arange(5.0).block_until_ready()  # compiles outside the span
    with obs.span("t.step", time.monotonic):
        jax.jit(fresh_recorder_probe)(x).block_until_ready()
    recs = obs.records(c0)
    (sp,) = [r for r in _spans(recs) if r[1] == "t.step"]
    inside = [r for r in recs if r[0] == obs.COMPILE and r[5] == sp[4]]
    assert [r[1] for r in inside] == ["jit(fresh_recorder_probe)"]
    assert sp[2] <= inside[0][2] <= inside[0][3] <= sp[3]


def test_assert_max_retraces_reads_the_recorder(assert_max_retraces):
    f = jax.jit(lambda v: v * 3 + 1)
    x = jnp.arange(4.0)
    f(x).block_until_ready()
    with assert_max_retraces(0):
        f(x).block_until_ready()
    with pytest.raises(AssertionError, match="new jit compilation"):
        with assert_max_retraces(0):
            f(jnp.arange(5.0)).block_until_ready()


@pytest.mark.skipif(sys.platform != "linux", reason="Linux clocks")
def test_perf_counter_and_monotonic_read_one_clock():
    for name in ("perf_counter", "monotonic"):
        assert time.get_clock_info(name).implementation == (
            "clock_gettime(CLOCK_MONOTONIC)")


def test_spans_reach_the_profiler_trace(tmp_path):
    clk = VirtualClock(tick=1.0)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("t.traced", clk.now):
            with obs.span("t.traced_child", clk.now):
                jnp.arange(3.0).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    with obs.span("t.untraced", clk.now):
        pass
    (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                        recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    names = {e.name for p in data.planes if p.name.startswith("/host:")
             for line in p.lines for e in line.events}
    assert {"t.traced", "t.traced_child"} <= names
    assert "t.untraced" not in names


# --------------------------------------------------------- named scopes


def _scopes(lowered) -> set:
    """Every scope path in the lowered program's op names, without the
    outer ``jit(...)``."""
    out = set()
    for loc in re.findall(r'loc\("jit\([^)]*\)/([^"]*)"',
                          lowered.as_text(debug_info=True)):
        parts = loc.split("/")
        out |= {"/".join(parts[:i]) for i in range(1, len(parts) + 1)}
    return out


def test_the_decode_step_carries_the_scope_contract(cfg, program):
    eng = _engine(cfg, program)
    run = eng.start_run()
    scopes = _scopes(eng._decode.lower(
        eng.params, run.cur, run.cache, jax.random.PRNGKey(0)))
    want = {"embed", "attn", "attn/paged_view", "attn/scores", "mlp", "head",
            "head/lm_head"}
    want |= {f"attn/{w}" for w in ("wq", "wk", "wv", "wo")}
    want |= {f"mlp/{w}" for w in ("w1", "w3", "w2")}
    for proj in ("attn/wq", "mlp/w2", "head/lm_head"):
        want |= {f"{proj}/{s}" for s in ("dac", "crossbar", "adc", "gdc")}
    assert want <= scopes, sorted(want - scopes)


def test_cnn_apply_carries_the_scope_contract():
    mc = CNNConfig(
        name="tiny", input_hw=(8, 6), in_channels=1,
        convs=(ConvSpec("conv1", 3, 3, 1, 4, 2),
               ConvSpec("conv2", 3, 3, 4, 4, 1)),
        n_classes=3, fc_width=4,
    )
    prog = engine_mod.compile_program(
        cnn_init(jax.random.PRNGKey(0), mc),
        AnalogConfig().infer(b_adc=8, t_seconds=86400.0),
        jax.random.PRNGKey(1), transforms=crossbar_transforms(mc),
    )
    fwd = jax.jit(lambda p, x: cnn_apply(p, x, prog.cfg, mc))
    scopes = _scopes(fwd.lower(prog.params, jnp.zeros((2, 8, 6, 1))))
    want = {"pool", "fc", "fc/dac", "fc/crossbar", "fc/adc", "fc/gdc"}
    for conv in ("conv1", "conv2"):
        want |= {f"{conv}/im2col", f"{conv}/mvm", f"{conv}/mvm/crossbar"}
    assert want <= scopes, sorted(want - scopes)
