"""Driver: an LFM2-MoE share served from a programmed PCM chip.

The system under test is the program's serving path, as in
:mod:`lm_serve`: the model (``repro.configs.get(arch)`` at the
configuration's sizes: its layer order, the experts this chip holds, the
vocabulary slice) is programmed once (``launch.steps.program_for_serving``)
and served by ``serving.ServingEngine`` with the paged KV cache, per-slot
conv state and bucketed prefill; the harness steps the engine's run loop
against an open-loop arrival schedule (:func:`serve`).

What differs from :mod:`lm_serve`, and is here: the configuration's keys
(LFM2's ``layer_types``, two FFN widths, the expert share, a serving
context ``s_max`` below ``max_position_embeddings``), the weights' leaves
(:func:`reference.lfm2_moe.leaf`), and the model FLOPs
(:mod:`benchmarks.chip.flops_moe`). The warm-up and the sample of checked
requests are :mod:`lm_serve`'s.

``correct``: at every served token of :mod:`lm_serve`'s sample of finished
requests, the gap by which the plain reference's (``reference/lfm2_moe.py``)
logit of the served token lies below its best; their 99th percentile must
stay within the configuration's limit ``p99_logit_gap``. The percentile,
not :mod:`lm_serve`'s widest gap: a token whose routing the program and
the reference decide differently, where two experts' scores nearly tie,
moves its logits by far more than the arithmetic does, so the widest gap
of a sound run lies too close to a control's to tell them apart
(PERF.md). The widest gap and the mean are logged.
"""

from __future__ import annotations

import dataclasses
import gc
import time

import numpy as np

from benchmarks.chip import flops_moe, gen, harness, stats, weights
from benchmarks.chip.drivers import lm_serve
from benchmarks.chip.reference import lfm2_moe as ref

warm_up = lm_serve.warm_up
sample = lm_serve.sample
DRAIN_S = lm_serve.DRAIN_S


def model_config(c: dict):
    """The program's config for the arch, at this configuration's sizes.
    An unknown arch raises here, before anything is built."""
    import jax.numpy as jnp

    from repro import configs

    base = configs.get(c["arch"])
    from repro.configs import lfm2_8b_a1b

    first, n_held = int(c["expert_share_first"]), int(c["num_experts"])
    return dataclasses.replace(
        base,
        n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
        head_dim=c["hidden_size"] // c["num_attention_heads"],
        d_ff=c["intermediate_size"], moe_d_ff=c["moe_intermediate_size"],
        vocab=c["vocab_size"], n_experts=c["num_experts_published"],
        moe_held=tuple(range(first, first + n_held)),
        top_k=c["num_experts_per_tok"], conv_width=c["conv_L_cache"],
        layer_kinds=lfm2_8b_a1b.layer_kinds(
            tuple(c["layer_types"]), c["num_dense_layers"]),
        rope_theta=float(c["rope_theta"]), norm_eps=float(c["norm_eps"]),
        dtype=jnp.dtype(c["activation_dtype"]),
    )


def fill(shapes, seed: int):
    """The parameter tree from the seed, every leaf from
    :func:`reference.lfm2_moe.leaf`, made on the device in one call."""
    import jax

    base = weights.seed_key(seed, "weights")

    def make(base):
        return jax.tree_util.tree_map_with_path(
            lambda p, s: ref.leaf(weights.path_str(p), s.shape, base).astype(s.dtype),
            shapes,
        )

    return jax.jit(make)(base)


def build(ctx: harness.Context):
    """Weights, the programmed chip and the engine. Returns (engine,
    program seconds)."""
    c = ctx.config
    mc = model_config(c)

    import jax

    from repro.core.analog import AnalogConfig
    from repro.launch import steps
    from repro.models import lm
    from repro.serving import ServingConfig, ServingEngine

    shapes = jax.eval_shape(lambda k: lm.lm_init(k, mc), jax.random.PRNGKey(0))
    params = fill(shapes, ctx.seed)
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
        n_slots=int(c["n_slots"]), s_max=int(c["s_max"]), paged=True,
        page_size=int(c["page_size"]), n_pages=c.get("n_pages"),
        ref_check=False,
    )
    eng = ServingEngine(mc, program.cfg, served, scfg, program=program)
    return eng, program_s


def serve(ctx: harness.Context, eng, reqs: list) -> dict:
    """Lead-in and window: step the engine against the arrival schedule
    (:func:`lm_serve.serve`'s loop, with this model's FLOPs)."""
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
            flops_moe.prefill_flops(c, prompt_len[rid]) for rid in admitted)
        if run.n_active == 0:
            if run.queue:
                run.idle_wait()
            else:
                time.sleep(0.001)
            continue
        step_flops = sum(
            flops_moe.decode_flops(c, prompt_len[rid] + len(tokens[rid]))
            for rid in active)
        with ctx.span("decode_step", flops=step_flops, n_active=len(active)):
            run.decode_step()
    return {"run": run, "tokens": tokens, "due": due, "t_stop": time.perf_counter()}


def logit_gaps(ctx: harness.Context, checked: list, prompts: dict) -> np.ndarray:
    """At every served token of ``checked``: the reference's best logit
    minus its logit of the served token (sequences padded to ``s_max``,
    so the reference compiles once)."""
    import jax.numpy as jnp

    c = ctx.config
    state = ref.program(c, ctx.seed, float(c["t_hours"]) * 3600.0)
    s_pad = int(c["s_max"])
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
    gap = float(np.quantile(gaps, 0.99)) if gaps.size else float("inf")
    limit = float(c["limits"]["p99_logit_gap"])
    ctx.log(f"window: {m['attempted']} requests due, {m['failed']} without a "
            f"first token, {m['n_gaps']} token gaps; checked "
            f"{len(checked)} requests, {gaps.size} served tokens")
    if gaps.size:
        ctx.log(f"gaps: max {float(gaps.max())!r}, mean {float(gaps.mean())!r}, "
                f"p50 {np.median(gaps)!r}, p99 {gap!r}, share off the "
                f"reference's best {float((gaps > 0).mean())!r}")
    return {
        "correct": bool(gaps.size) and gap <= limit,
        "attempted": m["attempted"],
        "failed": m["failed"],
        "metrics": {"setup_s": ctx.setup_s, "ttft_p90_ms": m["ttft_p90_ms"],
                    "tpot_ms": m["tpot_ms"]},
        "counters": {"program_s": program_s, "itl_p99_ms": m["itl_p99_ms"]},
        "checks": {"p99_logit_gap": (gap, limit)},
        "memory_peak_bytes": peak,
        "trace": trace,
    }
