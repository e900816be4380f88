"""Driver: a decoder LM served from a programmed PCM chip.

The system under test is the program's serving path: the model
(``repro.configs.get(arch)`` at the configuration's sizes) is programmed
once into a simulated PCM chip (``launch.steps.program_for_serving``: the
write noise drawn, the chip aged to ``t_hours``, each layer's GDC
factor), then served by ``serving.ServingEngine`` with the paged KV cache
and bucketed prefill. The harness steps the engine's own run loop
(``EngineRun.admit_arrived`` / ``decode_step``, as ``ServingEngine.run``
does) against an open-loop arrival schedule, and times every token as
the engine hands it to the host (``on_token``).

Set-up: weights from the seed, programming, one warm-up run that
compiles every prefill bucket, the decode step, page appends and slot
writes and frees, then the lead-in (requests at the cell's rate that
fill the slots before the window opens).

``correct``: once the window has closed and the chip is freed, a sample
of finished requests drawn from the seed (the longest, and at least
``MIN_OTHERS`` others, so that many slots' answers are read) is run
through the plain reference (``reference/<reference>.py``: the same
programmed chip rebuilt from the seed, one causal pass, no cache); at
every served token the gap by which the reference's logit of the served
token lies below its best must stay within the configuration's limit.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib
import time

import numpy as np

from benchmarks.chip import flops, gen, harness, stats, weights

#: a warm-up request at each prefill bucket, this many per bucket (at
#: least the rows one prefill call of the smallest bucket takes)
WARM_PER_BUCKET = 4
#: how long past the window's close the run waits for a first token
DRAIN_S = 60.0
#: finished requests checked besides the longest, at the least
MIN_OTHERS = 8


def model_config(c: dict):
    """The program's config for the arch, at this configuration's sizes."""
    import jax.numpy as jnp

    from repro import configs

    base = configs.get(c["arch"])
    return dataclasses.replace(
        base,
        n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
        head_dim=c["head_dim"], d_ff=c["intermediate_size"],
        vocab=c["vocab_size"], rope_theta=float(c["rope_theta"]),
        norm_eps=float(c["norm_eps"]),
        dtype=jnp.dtype(c["activation_dtype"]),
    )


def build(ctx: harness.Context):
    """Weights, the programmed chip and the engine. Returns (engine,
    program seconds)."""
    import jax

    from repro.core.analog import AnalogConfig
    from repro.launch import steps
    from repro.models import lm
    from repro.serving import ServingConfig, ServingEngine

    c = ctx.config
    mc = model_config(c)
    shapes = jax.eval_shape(lambda k: lm.lm_init(k, mc), jax.random.PRNGKey(0))
    params = weights.fill(shapes, ctx.seed)
    ctx.mark("weights")
    acfg = AnalogConfig().infer(
        b_adc=int(ctx.setting("b_adc")), t_seconds=float(c["t_hours"]) * 3600.0)
    t0 = time.perf_counter()
    program = steps.program_for_serving(
        params, acfg, weights.seed_key(ctx.seed, "program"))
    jax.block_until_ready(program.params)
    program_s = time.perf_counter() - t0
    del params
    served = program.params
    if "mvm_dtype" in ctx.overrides:
        served = weights.with_mvm_dtype(served, ctx.overrides["mvm_dtype"])
        program = dataclasses.replace(program, params=served)
    ctx.mark("program")
    scfg = ServingConfig(
        n_slots=int(c["n_slots"]), s_max=int(c["max_position_embeddings"]),
        paged=True, page_size=int(c["page_size"]), n_pages=c.get("n_pages"),
        ref_check=False,
    )
    eng = ServingEngine(mc, program.cfg, served, scfg, program=program)
    return eng, program_s


def warm_up(eng, vocab: int) -> None:
    """Serve a few requests at every prefill bucket to completion: every
    program the window can call is compiled (or loaded) here."""
    from repro.serving import BucketedScheduler, Request

    rng = np.random.default_rng(0)
    reqs, rid = [], 0
    for b in eng.prefill_buckets:
        n_prompt = min(b, eng.s_max - 2)
        for _ in range(WARM_PER_BUCKET):
            reqs.append(Request(rid=rid, prompt=rng.integers(0, vocab, n_prompt),
                                max_new_tokens=2))
            rid += 1
    eng.run(reqs, scheduler=BucketedScheduler())


def serve(ctx: harness.Context, eng, reqs: list) -> dict:
    """Lead-in and window: step the engine against the arrival schedule."""
    from repro.serving import BucketedScheduler, Request

    c = ctx.config
    lead = float(ctx.traffic["lead_in_s"])
    prompt_len = {r.rid: r.prompt.size for r in reqs}
    max_new = {r.rid: r.max_new for r in reqs}
    tokens: dict = {}
    active: set = set()
    admitted: list = []

    def on_token(rid, _tok):
        ts = tokens.setdefault(rid, [])
        ts.append(time.perf_counter())
        if len(ts) == 1:
            admitted.append(rid)
        if len(ts) < max_new[rid]:
            active.add(rid)
        else:
            active.discard(rid)

    run = eng.start_run(scheduler=BucketedScheduler(), on_token=on_token,
                        now_fn=time.perf_counter, sleep_fn=time.sleep)
    run.submit([Request(rid=r.rid, prompt=r.prompt, max_new_tokens=r.max_new,
                        arrival_t=r.arrival_s) for r in reqs])
    t_open = run.t_start + lead
    due = {r.rid: run.t_start + r.arrival_s for r in reqs if r.in_window}
    opened = closed = False
    while True:
        now = time.perf_counter()
        if not opened and now >= t_open:
            ctx.open_window(t_open)
            opened = True
        ctx.tick(now)
        if opened and not closed and now >= ctx.t_close:
            ctx.close_window()
            closed = True
        if closed and (all(rid in tokens for rid in due)
                       or now >= ctx.t_close + DRAIN_S):
            break
        admitted.clear()
        with ctx.span("admit") as attrs:
            run.admit_arrived()
        attrs["flops"] = sum(
            flops.lm_prefill_flops(c, prompt_len[rid]) for rid in admitted)
        if run.n_active == 0:
            if run.queue:
                run.idle_wait()
            else:
                time.sleep(0.001)
            continue
        step_flops = sum(
            flops.lm_decode_flops(c, prompt_len[rid] + len(tokens[rid]))
            for rid in active)
        with ctx.span("decode_step", flops=step_flops, n_active=len(active)):
            run.decode_step()
    return {"run": run, "tokens": tokens, "due": due, "t_stop": time.perf_counter()}


def sample(records: list, seed: int, min_tokens: int) -> list:
    """Finished requests to check: the longest, then others drawn from the
    seed, at least ``MIN_OTHERS`` of them and until all hold
    ``min_tokens`` served tokens."""
    recs = sorted(records, key=lambda r: r.rid)
    if not recs:
        return []
    longest = max(recs, key=lambda r: (r.tokens.size, -r.rid))
    rest = [r for r in recs if r is not longest]
    order = gen.rng_for(seed, "sample").permutation(len(rest))
    out, n = [longest], longest.tokens.size
    for i in order:
        if n >= min_tokens and len(out) > MIN_OTHERS:
            break
        out.append(rest[i])
        n += rest[i].tokens.size
    return out


def logit_gaps(ctx: harness.Context, checked: list, prompts: dict) -> np.ndarray:
    """At every served token of ``checked``: the reference's best logit
    minus its logit of the served token."""
    import jax.numpy as jnp

    c = ctx.config
    ref = importlib.import_module(f"benchmarks.chip.reference.{c['reference']}")
    state = ref.program(c, ctx.seed, float(c["t_hours"]) * 3600.0)
    s_pad = int(c["max_position_embeddings"])
    gaps = []
    for rec in checked:
        prompt = prompts[rec.rid]
        seq = np.zeros(s_pad, np.int32)
        full = np.concatenate([prompt, rec.tokens[:-1]])
        seq[: full.size] = full
        lg = np.asarray(ref.forward(state, jnp.asarray(seq), dims=ref.dims(c),
                                    bits=int(c["b_adc"])))
        lo = prompt.size - 1
        lg = lg[lo: lo + rec.tokens.size]
        gaps.append(lg.max(-1) - lg[np.arange(rec.tokens.size), rec.tokens])
    return np.concatenate(gaps) if gaps else np.zeros(0)


def run(ctx: harness.Context) -> dict:
    c = ctx.config
    eng, program_s = build(ctx)
    warm_up(eng, int(c["vocab_size"]))
    ctx.mark("warm-up")
    reqs = gen.requests(ctx.traffic, ctx.seed, ctx.seconds, int(c["vocab_size"]))
    served = serve(ctx, eng, reqs)
    peak = harness.memory_peak_bytes()
    trace = ctx.read_trace()
    m = stats.serve_metrics(served["due"], served["tokens"], ctx.t_open,
                            ctx.t_close, served["t_stop"])
    checked = sample(served["run"].records, ctx.seed, int(c["check_tokens"]))
    prompts = {r.rid: r.prompt for r in reqs}
    del served["run"], eng
    gc.collect()
    gaps = logit_gaps(ctx, checked, prompts)
    gap = float(gaps.max()) if gaps.size else float("inf")
    limit = float(c["limits"]["max_logit_gap"])
    ctx.log(f"window: {m['attempted']} requests due, {m['failed']} without a "
            f"first token, {m['n_gaps']} token gaps; checked "
            f"{len(checked)} requests, {gaps.size} served tokens")
    if gaps.size:
        ctx.log(f"gaps: p50 {np.median(gaps)!r}, p99 {np.quantile(gaps, 0.99)!r}, "
                f"share off the reference's best {float((gaps > 0).mean())!r}")
    return {
        "correct": bool(gaps.size) and gap <= limit,
        "attempted": m["attempted"],
        "failed": m["failed"],
        "metrics": {"setup_s": ctx.setup_s, "ttft_p90_ms": m["ttft_p90_ms"],
                    "tpot_ms": m["tpot_ms"]},
        "counters": {"program_s": program_s, "itl_p99_ms": m["itl_p99_ms"]},
        "checks": {"max_logit_gap": (gap, limit)},
        "memory_peak_bytes": peak,
        "trace": trace,
    }
