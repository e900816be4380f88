"""Serving launcher: request-level serving over the repro.serving engine.

``python -m repro.launch.serve --arch tinyllama-1.1b --tokens 32 --batch 4``

Runs a model through the production serving flow: the arch's CPU smoke
preset by default, or with ``--published`` its published config (every
width as published, bf16 activations) cut to ``--n-layers`` layers. All
serving goes through ``repro.serving.ServingEngine`` -- one jitted decode
over a slot-based KV cache -- in one of two shapes:

* default: a rectangle batch of ``--batch`` identical-length requests
  (the classic fixed-batch pass, now expressed as requests);
* ``--request-trace N``: N variable-length requests served by the
  continuous-batching scheduler -- retired slots are refilled mid-flight
  so the decode batch stays full. ``--arrival-rate R`` spaces the trace
  over Poisson arrivals at R requests/second (default: all queued at t=0).

With ``--analog`` the PCM weights are programmed exactly ONCE before the
decode loop (engine.compile_program: the hardware's program-once /
execute-many lifecycle); every prefill/decode step then executes against the
programmed conductances with the GDC epilogue and needs no per-step RNG.
``--per-call`` restores the legacy behaviour that re-simulates programming
inside every forward -- useful only to measure what program-once saves.

The programmed chip is a deployable artifact: ``--save-program DIR``
persists it (versioned layout, checkpoint/store.py) and ``--load-program
DIR`` serves an existing chip draw instead of programming a new one --
every replica of a fleet loads the SAME chip. ``--mesh-model N`` programs
and serves sharded (TP degree N over the local devices); the saved artifact
is layout-free and bit-identical to the host-programmed chip.

Low-precision serving: ``--b-adc {4,6,8}`` compiles every layer's quant plan
(and the fused kernel's epilogue) at that ADC bitwidth -- the paper's
efficiency headline comes from exactly this knob (8.58 -> 57.39 TOPS/W for
KWS at 8 -> 4 bits, Sec. 7). ``--b-adc-overrides 'lm_head=8,blocks/*=4'``
compiles a mixed-precision program (fnmatch patterns over layer walk paths;
the bitwidth is recorded per layer in the saved artifact). Analog serving
also reports accuracy counters -- greedy top-1 agreement and logit MSE
against the digital full-precision reference, teacher-forced on the analog
token stream -- so the throughput/accuracy trade is a printed number
(``--no-ref-check`` skips the reference pass).

Drift-lifecycle serving: ``--drift-schedule 25,3600,86400`` (or ``fig7``,
the paper's 25s/1h/1d/1mo/1y grid) serves ONE programmed chip at every age
of the schedule -- the chip ages in place via ``engine.age_program``
(jitted, sharding-preserving drift re-evaluation; zero reprogramming,
asserted through the program-event counter) and the accuracy counters are
re-emitted per age, reproducing the paper's headline accuracy-after-24h
claim on the exact serving artifact. ``--refresh-below 0.9`` arms the
refresh policy: when top-1 agreement at some age degrades past the
threshold, the chip is reprogrammed from the stored source weights
(``steps.refresh_program``: fresh write noise, drift clock reset to t_c, a
logged ``reprogram`` event) and the remaining schedule serves the fresh
chip. ``--save-program`` after a schedule persists the final aged chip with
its full ``age_history``, so a reloaded artifact serves bit-exactly at the
last age. Combined with ``--request-trace``, the schedule becomes a
``serving.DriftPolicy``: the chip ages (and refreshes) BETWEEN decode
steps of one continuous run -- the paper's always-on deployment.

Fleet serving: ``--fleet N`` spreads the ``--request-trace`` across N
independently-programmed chips behind a ``serving.FleetRouter`` (each chip
its own write-noise draw under a distinct key; with ``--load-program`` the
fleet is N replicas of the saved draw instead). ``--agreement-slo X`` arms
SLO-aware dispatch: arrived requests go to the least-loaded chip whose
recent top-1 agreement clears X, and the report records the worst
aggregate-agreement window. ``--fleet 1`` is byte-identical to not passing
``--fleet`` at all (it routes through the single-engine path). ``--async``
serves the same fleet through the threaded front end (one worker thread
per chip, overlapped jitted decode, bounded admission via ``--queue-cap``)
and prints a greppable ``async fleet:`` throughput line.
"""

from __future__ import annotations

import argparse
import dataclasses
import sys
import time

import jax
import numpy as np

from repro import configs
from repro.checkpoint import store
from repro.core import engine
from repro.core import pcm as pcm_lib
from repro.core.analog import AnalogConfig
from repro.core.engine import DriftSchedule
from repro.core.quant import SUPPORTED_B_ADC
from repro.launch import compile_cache
from repro.launch import mesh as mesh_lib
from repro.launch import sharding as shd
from repro.launch import steps
from repro.models import lm
from repro.models.common import ModelConfig
from repro.serving import (
    AsyncConfig,
    AsyncFleetRouter,
    BucketedScheduler,
    DriftPolicy,
    FleetConfig,
    FleetRouter,
    Request,
    ServingConfig,
    ServingEngine,
    poisson_trace,
)


def parse_b_adc_overrides(text: str) -> dict:
    """Parse 'pattern=bits,pattern=bits' into an overrides dict."""
    out = {}
    for item in text.split(","):
        item = item.strip()
        if not item:
            continue
        pat, sep, bits = item.partition("=")
        if not sep or not bits.strip().isdigit():
            raise ValueError(
                f"bad --b-adc-overrides entry {item!r} "
                "(want pattern=bits with integer bits)"
            )
        out[pat.strip()] = int(bits)
    return out


def trace_prompt_buckets(prompt_len: int) -> tuple[int, ...]:
    """Variable prompt-length buckets for --request-trace.

    A small bucket set bounds the number of prefill traces (one jit trace
    per distinct prompt length) while keeping the workload variable.
    """
    return tuple(sorted({max(1, (prompt_len * k) // 4) for k in (1, 2, 3, 4)}))


def request_trace(args, cfg: ModelConfig) -> list[Request]:
    """The ``--request-trace`` workload (seeded: the same args give the
    same prompts, budgets and arrivals)."""
    return poisson_trace(
        jax.random.PRNGKey(7), args.request_trace,
        vocab=cfg.vocab, rate=args.arrival_rate,
        prompt_lens=trace_prompt_buckets(args.prompt_len),
        new_tokens=(max(1, min(8, args.tokens)), args.tokens),
    )


def build_parser() -> argparse.ArgumentParser:
    """Serving CLI, grouped by subsystem (the groups mirror the config
    surfaces: serving -> ServingConfig, paging -> its paged fields,
    fleet -> FleetConfig; drift/analog stay launcher-level)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b",
                    choices=sorted(configs.LM_ARCHS))
    ap.add_argument("--published", action="store_true",
                    help="serve the arch's published config (every width "
                         "as published) instead of its CPU smoke preset")
    ap.add_argument("--n-layers", type=int, default=None, metavar="N",
                    help="cut a --published config to its first N layers "
                         "(one pipeline stage of a deeper deployment)")

    g = ap.add_argument_group(
        "serving", "workload shape and the request-level engine")
    g.add_argument("--batch", type=int, default=4)
    g.add_argument("--prompt-len", type=int, default=32)
    g.add_argument("--tokens", type=int, default=32)
    g.add_argument("--request-trace", type=int, default=None, metavar="N",
                   help="continuous batching: serve N variable-length "
                        "requests (prompts bucketed up to --prompt-len, "
                        "budgets up to --tokens) through the request-level "
                        "scheduler over --batch decode slots")
    g.add_argument("--arrival-rate", type=float, default=None, metavar="R",
                   help="Poisson arrivals at R requests/s for "
                        "--request-trace (default: all queued at t=0)")
    g.add_argument("--no-ref-check", action="store_true",
                   help="skip the digital-reference accuracy counters")
    g.add_argument("--record-logits", action="store_true",
                   help="keep every emitted position's logits on its "
                        "request record (host memory: vocab floats per "
                        "token; for checks against a reference)")

    g = ap.add_argument_group(
        "paging", "paged KV cache + bucketed prefill (over --request-trace)")
    g.add_argument("--kv-page-size", type=int, default=None, metavar="P",
                   help="paged KV cache: serve --request-trace over a "
                        "shared pool of P-token pages per layer instead "
                        "of per-slot s_max rectangles; prompts prefill "
                        "right-padded to a bucket grid (one jit trace per "
                        "bucket) and admission is length-sorted")
    g.add_argument("--kv-pages", type=int, default=None, metavar="N",
                   help="page-pool size for --kv-page-size (default: the "
                        "rectangle-equivalent slots*ceil(s_max/P)+1; pass "
                        "less to serve long prompts at flat memory)")
    g.add_argument("--prefill-buckets", default=None, metavar="SPEC",
                   help="comma list of prefill pad lengths for "
                        "--kv-page-size (default: geometric 32*2^k grid "
                        "up to s_max)")

    g = ap.add_argument_group(
        "analog program", "program-once PCM deployment and its artifact")
    g.add_argument("--analog", action="store_true",
                   help="serve through the PCM deployment (program-once)")
    g.add_argument("--per-call", action="store_true",
                   help="legacy: re-simulate PCM programming every forward")
    g.add_argument("--t-hours", type=float, default=24.0,
                   help="PCM drift time for --analog")
    g.add_argument("--b-adc", type=int, default=None,
                   choices=list(SUPPORTED_B_ADC),
                   help="ADC bitwidth for analog serving (default 8); with "
                        "--load-program it must match the artifact")
    g.add_argument("--b-adc-overrides", default=None, metavar="SPEC",
                   help="mixed-precision: comma list of pattern=bits over "
                        "layer paths, e.g. 'lm_head=8,blocks/*=4'")
    g.add_argument("--resample-read-noise", action="store_true",
                   help="resample PCM 1/f read noise per MVM from stored "
                        "pre-read conductances (default: frozen draw, "
                        "bit-exact executes)")
    g.add_argument("--use-kernel", action="store_true",
                   help="execute through the fused Pallas MVM kernel "
                        "(interpret mode off-TPU); bit-identical to the "
                        "jnp oracle for single-row-tile layers")
    g.add_argument("--fused-decode", action="store_true",
                   help="execute the whole programmed decode step as ONE "
                        "Pallas grid (layer walk = grid dimension, weights "
                        "double-buffered through VMEM; interpret mode "
                        "off-TPU); bit-identical to the per-layer path")
    g.add_argument("--mesh-model", type=int, default=0,
                   help="shard programming+serving with this TP degree")
    g.add_argument("--save-program", default=None, metavar="DIR",
                   help="persist the programmed chip artifact")
    g.add_argument("--load-program", default=None, metavar="DIR",
                   help="serve a saved chip draw (implies --analog)")

    g = ap.add_argument_group(
        "drift", "drift-lifecycle serving over one chip")
    g.add_argument("--drift-schedule", default=None, metavar="SPEC",
                   help="drift-lifecycle serving: age ONE programmed chip "
                        "across these ages (comma list of seconds, or "
                        "'fig7' for the paper's 25s/1h/1d/1mo/1y grid) and "
                        "re-emit the accuracy counters at each age; "
                        "overrides --t-hours")
    g.add_argument("--refresh-below", type=float, default=None, metavar="X",
                   help="refresh policy: reprogram the chip from the "
                        "stored source weights (fresh write noise, age "
                        "resets to t_c) when top-1 agreement at an age of "
                        "the --drift-schedule drops below X; logs a "
                        "'reprogram' event")

    g = ap.add_argument_group(
        "fleet", "N programmed chips behind one router")
    g.add_argument("--fleet", type=int, default=None, metavar="N",
                   help="serve the --request-trace across N independent "
                        "chip draws (or N replicas of a --load-program "
                        "artifact) behind serving.FleetRouter; --fleet 1 "
                        "is byte-identical to the single-engine path")
    g.add_argument("--agreement-slo", type=float, default=None, metavar="X",
                   help="fleet SLO: dispatch to the least-loaded chip "
                        "whose recent top-1 agreement clears X, and record "
                        "the worst aggregate-agreement window")
    g.add_argument("--async", dest="use_async", action="store_true",
                   help="serve the fleet through the threaded front end "
                        "(one worker thread per chip; jitted decode steps "
                        "release the GIL, so per-chip decode overlaps in "
                        "wall clock) instead of the synchronous tick loop")
    g.add_argument("--queue-cap", type=int, default=None, metavar="N",
                   help="async backpressure: cap on fleet-wide queued "
                        "work; submissions block at the cap (default 64)")
    return ap


def model_config(args) -> ModelConfig:
    """The served model: the smoke preset, or the published config cut to
    ``--n-layers``. Validation and serving both build it here."""
    if not args.published:
        return configs.get_smoke(args.arch)
    cfg = configs.get(args.arch)
    if args.n_layers is not None:
        cfg = dataclasses.replace(cfg, n_layers=args.n_layers)
    return cfg


def validate_args(ap: argparse.ArgumentParser, args) -> None:
    """Reject mutually-inconsistent flag combinations with clear errors."""
    if args.n_layers is not None:
        if not args.published:
            ap.error("--n-layers cuts a --published config (the smoke "
                     "preset fixes its own depth)")
        full = configs.get(args.arch).n_layers
        if not 1 <= args.n_layers <= full:
            ap.error(f"--n-layers must be in [1, {full}] for {args.arch}")
    cfg = model_config(args)
    if args.per_call and not args.analog:
        ap.error("--per-call only qualifies --analog (pass both)")
    if args.load_program and args.per_call:
        ap.error("--load-program serves a compiled program (no --per-call)")
    if args.save_program and not (args.analog or args.load_program):
        ap.error("--save-program needs a compiled program (add --analog)")
    if args.save_program and args.per_call:
        ap.error("--per-call compiles no program; nothing to --save-program")
    if args.b_adc_overrides and (args.per_call or args.load_program):
        ap.error("--b-adc-overrides applies at program-compile time "
                 "(use with --analog, not --per-call/--load-program)")
    if args.b_adc_overrides and not args.analog:
        ap.error("--b-adc-overrides needs --analog")
    if args.resample_read_noise and (
        args.per_call or not (args.analog or args.load_program)
    ):
        ap.error("--resample-read-noise needs a compiled program "
                 "(--analog or --load-program, without --per-call)")
    if args.drift_schedule and args.per_call:
        ap.error("--drift-schedule ages a compiled program in place "
                 "(no --per-call)")
    if args.drift_schedule and not (args.analog or args.load_program):
        ap.error("--drift-schedule needs a compiled program "
                 "(--analog or --load-program)")
    if args.refresh_below is not None and not args.drift_schedule:
        ap.error("--refresh-below is the --drift-schedule refresh policy "
                 "(pass both)")
    if args.refresh_below is not None and args.no_ref_check:
        ap.error("--refresh-below triggers on the top-1 agreement counter "
                 "(drop --no-ref-check)")
    if args.request_trace is not None and args.per_call:
        ap.error("--request-trace serves through the compiled-program "
                 "engine; --per-call is the legacy rectangle path")
    if args.request_trace is not None and args.request_trace < 1:
        ap.error("--request-trace needs at least one request")
    if args.request_trace is not None:
        frontend = cfg.frontend
        if frontend in ("audio_frames", "vision_patches"):
            ap.error(f"--request-trace serves token prompts; the "
                     f"{frontend} frontend ({args.arch}) needs the "
                     "rectangle path")
    if args.arrival_rate is not None and args.request_trace is None:
        ap.error("--arrival-rate paces a --request-trace (pass both)")
    if args.kv_page_size is not None and args.request_trace is None:
        ap.error("--kv-page-size is the paged request-level path "
                 "(pass --request-trace)")
    if args.kv_page_size is not None and args.kv_page_size < 1:
        ap.error("--kv-page-size must be >= 1")
    if args.kv_page_size is not None:
        family = cfg.family
        if family in ("ssm", "hybrid"):
            ap.error(f"--kv-page-size pages attention KV caches; the "
                     f"{family} family ({args.arch}) carries position-free "
                     "recurrent state that right-padded bucketed prefill "
                     "would corrupt")
    if args.fused_decode:
        if not (args.analog or args.load_program):
            ap.error("--fused-decode executes a compiled chip's per-layer "
                     "plans as one grid (add --analog or --load-program)")
        if args.per_call:
            ap.error("--fused-decode needs the program-once path; "
                     "--per-call re-simulates programming every forward")
        if args.use_kernel:
            ap.error("--fused-decode subsumes the per-MVM kernel "
                     "(--use-kernel) -- the whole decode step is already "
                     "one launch")
        if args.kv_page_size is not None:
            ap.error("--fused-decode owns one stacked slot cache; it does "
                     "not compose with the paged KV cache "
                     "(--kv-page-size)")
        if args.fleet is not None and args.fleet > 1:
            ap.error("--fused-decode is not threaded through the fleet "
                     "path (serve one chip)")
        if args.mesh_model:
            ap.error("--fused-decode runs the decode step in one single-"
                     "device kernel; sharded serving keeps the per-layer "
                     "path")
        if cfg.family in ("ssm", "hybrid", "moe"):
            ap.error(f"--fused-decode fuses the dense attention+FFN layer "
                     f"walk; the {cfg.family} family ({args.arch}) "
                     "has recurrent or MoE blocks with no grid-step "
                     "lowering")
        if cfg.qkv_bias:
            ap.error(f"--fused-decode executes bias-free projections; "
                     f"{args.arch} programs qkv biases the fused grid "
                     "cannot apply")
    if args.kv_pages is not None and args.kv_page_size is None:
        ap.error("--kv-pages sizes the --kv-page-size pool (pass both)")
    if args.prefill_buckets is not None and args.kv_page_size is None:
        ap.error("--prefill-buckets shapes --kv-page-size prefill "
                 "(pass both)")
    if args.prefill_buckets is not None:
        try:
            buckets = [int(x) for x in args.prefill_buckets.split(",") if x]
        except ValueError:
            ap.error(f"bad --prefill-buckets {args.prefill_buckets!r} "
                     "(want a comma list of integers)")
        if not buckets or min(buckets) < 1:
            ap.error("--prefill-buckets needs positive lengths")
    if args.fleet is not None and args.fleet < 1:
        ap.error("--fleet needs at least one chip")
    if args.fleet is not None and args.request_trace is None:
        ap.error("--fleet spreads a request trace across chips "
                 "(pass --request-trace)")
    if args.fleet is not None and args.fleet > 1:
        if not (args.analog or args.load_program):
            ap.error("--fleet programs N independent chip draws "
                     "(add --analog, or --load-program for replicas)")
        if args.drift_schedule:
            ap.error("--drift-schedule is the single-chip lifecycle path; "
                     "fleet chips age on their own clocks")
        if args.save_program:
            ap.error("--save-program persists ONE chip; a fleet is N "
                     "draws (save a single-chip run, then --fleet with "
                     "--load-program for replicas)")
        if args.use_kernel:
            ap.error("--use-kernel is not threaded through the fleet path "
                     "(serve chips through the single-engine path)")
    if args.use_async and (args.fleet is None or args.fleet < 2):
        ap.error("--async drives the fleet front end (pass --fleet >= 2)")
    if args.queue_cap is not None:
        if not args.use_async:
            ap.error("--queue-cap configures the --async admission queue "
                     "(pass --async)")
        if args.queue_cap < 1:
            ap.error("--queue-cap needs at least one slot")
    if args.agreement_slo is not None:
        if args.fleet is None or args.fleet < 2:
            ap.error("--agreement-slo gates fleet dispatch "
                     "(pass --fleet >= 2)")
        if args.no_ref_check:
            ap.error("--agreement-slo compares against the digital "
                     "reference (drop --no-ref-check)")
        if not (0.0 <= args.agreement_slo <= 1.0):
            ap.error("--agreement-slo is a top-1-agreement fraction "
                     "in [0, 1]")
    if args.refresh_below is not None and args.load_program:
        # the artifact deliberately stores no pre-programming weights (the
        # chip is the artifact); refresh rewrites from THIS process's
        # source weights, which is only correct if the artifact was
        # programmed from the same ones (serve's own deterministic init is
        # -- but a chip programmed via the API may not be)
        print("warning: --refresh-below with --load-program reprograms "
              "from this process's deterministic source weights; if the "
              "artifact was programmed from different weights, a refresh "
              "will rewrite a different model", file=sys.stderr)


def main(argv=None):
    """Parse ``argv`` (default: ``sys.argv``), serve, print the summary and
    return the serving report (a fleet's report under ``--fleet``)."""
    return run(argv)[0]


def run(argv=None):
    """:func:`main`, returning ``(report, engine)``: the
    :class:`ServingEngine` that served (``None`` under ``--fleet``) holds
    the programmed chip as the run left it."""
    ap = build_parser()
    args = ap.parse_args(argv)
    validate_args(ap, args)
    compile_cache.enable()
    schedule = None
    if args.drift_schedule:
        try:
            schedule = DriftSchedule.parse(args.drift_schedule)
        except ValueError as e:
            ap.error(str(e))
    b_adc = 8 if args.b_adc is None else args.b_adc
    overrides = None
    if args.b_adc_overrides:
        try:
            overrides = parse_b_adc_overrides(args.b_adc_overrides)
        except ValueError as e:
            ap.error(str(e))

    cfg = model_config(args)
    if cfg.n_codebooks:
        # musicgen-style decoders emit one token per codebook per step; the
        # request-level engine drives a single token stream
        ap.error(f"--arch {args.arch}: multi-codebook decoders are not "
                 "servable through the token-stream engine")
    analog = args.analog or args.load_program is not None
    # --fleet 1 deliberately routes through the single-engine path below:
    # one chip needs no router, and the byte-identical output is pinned
    fleet_n = args.fleet if args.fleet is not None and args.fleet > 1 else None
    t0_seconds = (schedule.times[0] if schedule is not None
                  else args.t_hours * 3600.0)
    acfg = AnalogConfig()
    if analog:
        acfg = AnalogConfig().infer(
            b_adc=b_adc, t_seconds=t0_seconds,
            resample_read_noise=args.resample_read_noise,
        )

    key = jax.random.PRNGKey(0)
    # one consumer per subkey: weight init, patch/token data, engine rng
    k_init, k_data, k_rng = jax.random.split(key, 3)
    params = lm.lm_init(k_init, cfg)
    mesh = (mesh_lib.make_serving_mesh(args.mesh_model)
            if args.mesh_model else None)
    if mesh is not None:
        # the chip's layout: left where lm_init put them, the source weights
        # would hold a whole unsharded model on device 0 for the run
        params = jax.device_put(
            params, shd.program_shardings(params, mesh, cfg)
        )
    # pre-programming weights: the digital reference for the accuracy
    # counters AND the source the refresh policy reprograms the chip from
    src_params = ref_params = params

    program = None
    if args.load_program is not None:
        t0 = time.time()
        program = store.load_program(
            args.load_program, params_like=params,
            shardings=shd.program_shardings(params, mesh, cfg)
            if mesh is not None else None,
        )
        if args.b_adc is not None and program.cfg.b_adc != args.b_adc:
            ap.error(
                f"--b-adc {args.b_adc} does not match the loaded artifact "
                f"(compiled at b_adc={program.cfg.b_adc}); bitwidths are "
                "baked into a program's quant plans at compile time"
            )
        if args.resample_read_noise and not program.cfg.resample_read_noise:
            ap.error(
                "--resample-read-noise: the loaded artifact carries no "
                "read buffers (compile it with --analog "
                "--resample-read-noise --save-program)"
            )
        if program.t_seconds != t0_seconds:
            # same chip, advanced to the requested deployment age -- through
            # age_program so the trajectory stays recorded (a later
            # --save-program must not write a stale age_history)
            program = engine.age_program(program, t0_seconds)
        where = f" onto {mesh.devices.size}-device mesh" if mesh else ""
        print(f"loaded programmed chip ({program.n_layers} layers, "
              f"b_adc={program.cfg.b_adc}, "
              f"t={pcm_lib.format_age(program.t_seconds)}, "
              f"age_history={len(program.age_history)} entries) "
              f"in {time.time()-t0:.2f}s from {args.load_program}{where}")
    elif analog and not args.per_call and fleet_n is None:
        # Program phase: one pass over the param tree, before any serving.
        # (A fleet without --load-program compiles its N draws itself.)
        t0 = time.time()
        program = steps.program_for_serving(
            params, acfg, jax.random.PRNGKey(42), mesh=mesh, model_cfg=cfg,
            b_adc_overrides=overrides,
        )
        # dispatch is asynchronous: time the programming, not its enqueue
        jax.block_until_ready(program.params)
        where = f"on {mesh.devices.size}-device mesh " if mesh else ""
        mixed = f" with {len(overrides)} bitwidth overrides" if overrides else ""
        print(f"programmed {program.n_layers} analog layers once {where}"
              f"in {time.time()-t0:.2f}s (b_adc={b_adc}{mixed}, "
              f"t={pcm_lib.format_age(t0_seconds)})")
    if program is not None:
        params, acfg = program.params, program.cfg
        # schedule/trace runs save AFTER serving (the chip may age en
        # route); everything else saves the freshly compiled/loaded chip
        if (args.save_program and schedule is None
                and args.request_trace is None):
            path = store.save_program(args.save_program, program)
            print(f"saved programmed chip artifact to {path}")
    if args.use_kernel:
        # per-layer bits travel in the params (shape-encoded b_adc_buf), so
        # flipping the backend needs no recompile of the program itself
        acfg = dataclasses.replace(
            acfg, use_kernel=True,
            interpret=jax.default_backend() != "tpu",
        )

    b, s = args.batch, args.prompt_len
    s_max = s + args.tokens
    patches = None
    if cfg.frontend == "vision_patches":
        # independent per-request images (sliced per rid below)
        patches = jax.random.normal(
            k_data, (b, cfg.num_patches, cfg.d_model), cfg.dtype
        )
        s_max += cfg.num_patches

    # Digital full-precision reference, teacher-forced on the analog token
    # stream: at every emitted position the two models see identical inputs,
    # so top-1 agreement / logit MSE isolate the analog (quantization + PCM)
    # error -- the accuracy axis of the paper's bitwidth trade (Sec. 7).
    ref_check = analog and not args.no_ref_check
    serving_cfg = ServingConfig(
        n_slots=b,
        s_max=s_max,
        paged=args.kv_page_size is not None,
        page_size=args.kv_page_size if args.kv_page_size is not None else 16,
        n_pages=args.kv_pages,
        prefill_buckets=(
            tuple(int(x) for x in args.prefill_buckets.split(",") if x)
            if args.prefill_buckets else None
        ),
        ref_check=not args.no_ref_check,
        fused_decode=args.fused_decode,
        record_logits=args.record_logits,
    )
    served = None
    if fleet_n is None:
        served = ServingEngine(
            cfg, acfg, params, serving_cfg, program=program,
            ref_params=ref_params if ref_check else None,
            src_params=src_params, mesh=mesh, rng=k_rng,
        )

    def fmt_timing(m):
        per_tok = m.t_decode / max(m.n_steps, 1) * 1e3
        return (f"prefill={m.t_prefill*1e3:.1f}ms "
                f"decode={per_tok:.2f}ms/token")

    def fmt_counters(m):
        c = m.counters
        return (f"top1_agreement={c['top1']:.4f} "
                f"logit_mse={c['logit_mse']:.6e} "
                f"decisions={c['decisions']}")

    def print_pass(m):
        print(f"arch={cfg.name} analog={analog} mode={acfg.mode} "
              f"b_adc={acfg.b_adc} {fmt_timing(m)}")
        if ref_check:
            print(f"accuracy_vs_digital_ref: {fmt_counters(m)}")

    if args.request_trace is not None:
        # Continuous batching: variable-length requests through the slot
        # scheduler; with a --drift-schedule the chip ages (and refreshes)
        # BETWEEN decode steps of this single run via the DriftPolicy.
        trace = request_trace(args, cfg)
        if cfg.family == "moe":
            print("warning: MoE capacity routing pools tokens across the "
                  "decode batch, so continuous-batching generations are "
                  "not bit-identical to solo serving for this family",
                  file=sys.stderr)
        if fleet_n is not None:
            # Fleet serving: the same trace spread across N chips behind
            # the router (see serving/fleet.py for the dispatch/refresh
            # semantics). --fleet 1 never reaches here by construction.
            fleet_cfg = FleetConfig(
                n_chips=fleet_n, agreement_slo=args.agreement_slo
            )
            router_cls = AsyncFleetRouter if args.use_async else FleetRouter
            t0 = time.time()
            if program is not None:
                router = router_cls.from_program(
                    program, cfg, serving_cfg, fleet_cfg,
                    ref_params=ref_params if ref_check else None,
                    src_params=src_params, mesh=mesh,
                    rng=jax.random.PRNGKey(42),
                )
                print(f"fleet: {fleet_n} replicas of the loaded chip draw "
                      f"in {time.time()-t0:.2f}s")
            else:
                router = router_cls.build(
                    params, acfg, cfg, serving_cfg, fleet_cfg,
                    key=jax.random.PRNGKey(42),
                    ref_params=ref_params if ref_check else None,
                    src_params=src_params, mesh=mesh,
                    b_adc_overrides=overrides,
                )
                print(f"programmed {fleet_n} independent chip draws in "
                      f"{time.time()-t0:.2f}s (b_adc={b_adc}, "
                      f"t={pcm_lib.format_age(t0_seconds)})")
            sched = BucketedScheduler() if args.kv_page_size else None
            if args.use_async:
                # the classmethods construct with the default AsyncConfig;
                # the queue cap is the only knob the CLI exposes
                router.async_cfg = AsyncConfig(
                    queue_cap=args.queue_cap or 64
                )
                t1 = time.time()
                freport = router.serve(trace, scheduler=sched)
                print(f"async fleet: workers={fleet_n} "
                      f"queue_cap={router.async_cfg.queue_cap} "
                      f"wall={time.time()-t1:.2f}s "
                      f"tokens_per_s={freport.tokens_per_s:.1f}")
            else:
                freport = router.run(trace, scheduler=sched)
            print(freport.summary())
            if ref_check:
                c = freport.counters
                print(f"accuracy_vs_digital_ref: "
                      f"top1_agreement={c['top1']:.4f} "
                      f"decisions={c['decisions']}")
            longest = max(freport.records, key=lambda r: r.n_new)
            print("generated token ids (longest request):",
                  longest.tokens[: min(16, longest.n_new)].tolist())
            return freport, None
        policy = None
        if schedule is not None:
            est_steps = sum(r.max_new_tokens for r in trace) // max(b, 1)
            policy = DriftPolicy(
                schedule,
                every_steps=max(1, est_steps // max(len(schedule), 1)),
                refresh_below=args.refresh_below,
            )
        report = served.run(
            trace,
            scheduler=BucketedScheduler() if args.kv_page_size else None,
            drift_policy=policy,
        )
        for ev in report.age_events:
            if ev["kind"] == "age":
                print(f"drift_age step={ev['step']} t={ev['t_wall']:.0f}s "
                      f"({pcm_lib.format_age(ev['t_device'])} device age)")
            else:
                print(f"drift_event step={ev['step']} reprogram: "
                      f"top1_agreement={ev['top1']:.4f} < "
                      f"refresh_below={args.refresh_below}")
        print(report.summary())
        if ref_check:
            print(f"accuracy_vs_digital_ref: {fmt_counters(report)}")
        if args.save_program and program is not None:
            path = store.save_program(args.save_program, served.program)
            print(f"saved programmed chip artifact to {path}")
        longest = max(report.records, key=lambda r: r.n_new)
        print("generated token ids (longest request):",
              longest.tokens[: min(16, longest.n_new)].tolist())
        return report, served

    def rectangle_requests():
        toks = jax.random.randint(k_data, (b, s), 0, cfg.vocab)
        return [
            Request(
                rid=i, prompt=np.asarray(toks[i]),
                max_new_tokens=args.tokens,
                features=(None if patches is None
                          else {"patches": patches[i : i + 1]}),
            )
            for i in range(b)
        ]

    if schedule is None:
        m = served.run(rectangle_requests())
        print_pass(m)
    else:
        # Drift-lifecycle serving: ONE chip ages in place across the
        # schedule; the program-event counter proves no reprogramming
        # happens unless the refresh policy fires.
        print(f"drift_schedule: ages={','.join(schedule.labels)}"
              + (f" refresh_below={args.refresh_below}"
                 if args.refresh_below is not None else ""))
        events0 = engine.program_event_count()
        reprograms = 0
        refresh_wall = None  # schedule (wall) age of the last refresh
        m = None
        for i, t_age in enumerate(schedule):
            if i > 0:
                # schedule ages are wall-clock deployment times; a refresh
                # genuinely resets the drift clock instead of being erased
                # by the next absolute-age evaluation (engine.device_age)
                served.age_to(engine.device_age(t_age, refresh_wall))
            line = (f"drift_age t={t_age:.0f}s "
                    f"({pcm_lib.format_age(t_age)})")
            if refresh_wall is not None:
                line += f" chip_age={pcm_lib.format_age(served.program.t_seconds)}"
            m = served.run(rectangle_requests())
            line += f": {fmt_timing(m)}"
            if ref_check:
                line += " " + fmt_counters(m)
            print(line)
            if (args.refresh_below is not None
                    and m.counters["top1"] < args.refresh_below):
                reprograms += 1
                refresh_wall = t_age
                print(f"drift_event t={t_age:.0f}s reprogram: "
                      f"top1_agreement={m.counters['top1']:.4f} < "
                      f"refresh_below={args.refresh_below}; rewriting chip "
                      f"from stored weights (chip age resets to "
                      f"{pcm_lib.format_age(pcm_lib.T_C)})")
                served.refresh(
                    jax.random.fold_in(jax.random.PRNGKey(43), reprograms)
                )
        delta = engine.program_event_count() - events0
        print(f"drift_lifecycle: ages={len(schedule)} "
              f"reprograms={reprograms} program_events_delta={delta} "
              f"final_age={pcm_lib.format_age(served.program.t_seconds)}")
        if args.save_program:
            path = store.save_program(args.save_program, served.program)
            hist = ",".join(pcm_lib.format_age(t)
                            for t in served.program.age_history)
            print(f"saved programmed chip artifact at final age "
                  f"(age_history={hist}) to {path}")
        print_pass(m)
    seq0 = m.tokens_of(0)
    print("generated token ids (first sequence):",
          seq0[: min(16, seq0.size)].tolist())
    return m, served


if __name__ == "__main__":
    main()
