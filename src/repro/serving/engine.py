"""Continuous-batching serving engine over ONE programmed CiM chip.

The always-on deployment of the paper (Secs. 5-7) programs a PCM chip once
and then answers an unbounded request stream while the devices drift. The
:class:`ServingEngine` is that deployment as code: it owns one compiled
:class:`~repro.core.engine.CiMProgram` (or plain digital params), a
slot-based KV cache (``models.lm.init_lm_cache(..., per_slot=True)``: B
independent request slots with per-slot lengths), and a decode loop in
which ONE jitted step advances every active slot together.

Construction takes a frozen :class:`~repro.serving.config.ServingConfig`
(slots, capacity, the paged-cache geometry, prefill bucketing, ref-check)
plus the live objects -- program, reference/source params, mesh, rng -- as
keywords::

    engine = ServingEngine.for_program(
        program, model_cfg, ServingConfig(n_slots=8, s_max=160),
        ref_params=params,
    )

The pre-config loose kwargs (``n_slots=...``, ``paged=...``, ...) still
work for one release through a deprecation shim that emits exactly one
:class:`DeprecationWarning` per construction.

Lifecycle of a request (see ``serving/scheduler.py`` for admission):

  1. *admit*  -- the request is prefilled ALONE (batch=1, its exact prompt
     length) and the resulting cache is written into a free slot
     (``models.lm.write_cache_slot``); the prefill's greedy token seeds the
     slot's decode stream.
  2. *decode* -- every engine step runs one jitted forward over all slots;
     per-slot cache lengths keep each request at its own position, so a
     freshly admitted 8-token request and a 100-tokens-deep one share the
     same batch.
  3. *retire* -- on EOS or the request's token budget the slot is recorded,
     reset (``models.lm.reset_cache_slot``), and immediately re-admittable.

Because slots are independent (no cross-batch coupling outside MoE
capacity routing), a request's generation is bit-identical to serving it
alone on a fresh engine -- continuous batching is semantically inert; it
only changes *when* work happens, never *what* is computed. Tests pin this.

A serving run is an :class:`EngineRun`: the per-run state (queue, caches,
slots, counters, drift bookkeeping) plus the stepping surface
(:meth:`EngineRun.admit_arrived` / :meth:`EngineRun.decode_step` /
:meth:`EngineRun.finish`). :meth:`ServingEngine.run` drives one run to
completion; the fleet router (``serving/fleet.py``) interleaves many runs
-- one per chip -- stepping each engine in turn and migrating live slots
between them (:meth:`EngineRun.live` / :meth:`EngineRun.evict`) when a
chip drains for a refresh.

The engine composes with the drift lifecycle: :meth:`age_to` advances the
chip between decode steps via ``engine.age_program`` (zero programming
events, asserted), and a :class:`DriftPolicy` does it on a step cadence
inside :meth:`run`, optionally triggering ``steps.refresh_program`` when
the running top-1 agreement vs the digital reference degrades -- a
long-running server reproducing the paper's programmed-chip lifetime
while it serves.
"""

from __future__ import annotations

import dataclasses
import itertools
import warnings
from collections import deque
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import clock as clock_lib
from repro import obs
from repro.core import engine as engine_mod
from repro.core.analog import AnalogConfig
from repro.core.engine import CiMProgram, DriftSchedule
from repro.kernels import decode_fused
from repro.models import attention as attn_lib
from repro.models.common import ModelConfig
from repro.models.lm import (
    append_cache_page,
    block_period,
    free_cache_slot_paged,
    init_lm_cache,
    lm_forward,
    reset_cache_slot,
    unstack_cache,
    write_cache_slot,
    write_cache_slot_paged,
)
from repro.serving.config import ServingConfig
from repro.serving.paging import PageAllocator, bucket_for, default_buckets
from repro.serving.requests import Request, RequestRecord
from repro.serving.scheduler import ContinuousScheduler

Array = jax.Array

#: ids of serving runs, on their spans in the recorder
_run_ids = itertools.count()

#: constructor keywords the pre-ServingConfig API accepted loosely; they
#: now route through the deprecation shim into a ServingConfig
_LEGACY_CONFIG_KEYS = frozenset(
    {"n_slots", "s_max", "paged", "page_size", "n_pages",
     "prefill_buckets", "prefill_batch"}
)


def _kv_cache_bytes(cache) -> int:
    """Resident K/V bytes of a decode cache (rectangular or paged)."""
    kinds = (attn_lib.KVCache, attn_lib.PagedKVCache)
    total = 0
    for leaf in jax.tree.leaves(cache, is_leaf=lambda x: isinstance(x, kinds)):
        if isinstance(leaf, kinds):
            total += leaf.k.nbytes + leaf.v.nbytes
    return total


@dataclasses.dataclass(frozen=True)
class DriftPolicy:
    """Age the served chip on a decode-step cadence inside :meth:`run`.

    Every ``every_steps`` decode steps the engine advances the chip to the
    next age of ``schedule`` (the program is assumed compiled at the
    schedule's first age, exactly like ``serve.py --drift-schedule``).
    Ages are *wall* deployment times: after a refresh the device age is
    ``max(t_wall - t_refresh_wall, t_c)``, so a rewritten chip is genuinely
    younger than the deployment.

    ``refresh_below``: when the top-1 agreement vs the digital reference
    over the segment since the last tick drops below this threshold, the
    chip is reprogrammed from the engine's stored source weights
    (``steps.refresh_program``) before the next age applies. Requires the
    engine to run with ``ref_params`` and ``src_params``.
    """

    schedule: DriftSchedule
    every_steps: int
    refresh_below: Optional[float] = None

    def __post_init__(self):
        if self.every_steps < 1:
            raise ValueError("DriftPolicy.every_steps must be >= 1")


@dataclasses.dataclass
class _Slot:
    req: Request
    tokens: list[int]
    admit_step: int
    admit_t: float
    # paged mode: page ids this slot currently owns, and how many more
    # pages of the pool are reserved (but not yet allocated) for its
    # worst-case growth -- see EngineRun.decode_step
    pages: Optional[list] = None
    reserve_left: int = 0
    #: per-position logits rows (``ServingConfig.record_logits``)
    logits: Optional[list] = None


@dataclasses.dataclass
class ServeReport:
    """Everything a serving run produced: outputs, counters, and metrics."""

    records: list[RequestRecord]
    scheduler: str
    n_slots: int
    n_steps: int  # decode steps
    slot_steps: int  # sum over steps of active slots
    #: the run's ``engine.admit`` and ``engine.decode`` spans summed (those
    #: the recorder still holds, ``obs.RING`` records back at most)
    t_prefill: float
    t_decode: float
    wall: float
    counters: Optional[dict]  # {"top1", "logit_mse", "decisions"} or None
    age_events: list[dict]
    reprograms: int
    program_events_delta: int  # beyond what refreshes account for: always 0
    #: distinct prefill shapes this ENGINE has jit-compiled so far (one
    #: trace per shape). Bucketed prefill bounds this by the bucket count;
    #: exact-length prefill grows it with every distinct prompt length.
    n_prefill_traces: int = 0
    #: resident K/V bytes of the decode cache -- the slot rectangles, or
    #: the page pools in paged mode (buffers are statically allocated, so
    #: resident == peak)
    peak_kv_bytes: int = 0
    #: paged mode: allocator high-water mark (pages), else 0
    peak_pages_in_use: int = 0

    @property
    def n_requests(self) -> int:
        return len(self.records)

    @property
    def n_generated(self) -> int:
        return sum(r.n_new for r in self.records)

    @property
    def tokens_per_s(self) -> float:
        return self.n_generated / max(self.wall, 1e-9)

    @property
    def requests_per_s(self) -> float:
        return self.n_requests / max(self.wall, 1e-9)

    @property
    def occupancy(self) -> float:
        """Mean fraction of decode slots holding a live request."""
        return self.slot_steps / max(self.n_steps * self.n_slots, 1)

    def latency_s(self, pct: float) -> float:
        """Arrival-to-retirement latency percentile (seconds)."""
        if not self.records:
            return 0.0
        return float(np.percentile([r.latency_s for r in self.records], pct))

    def ttft_s(self, pct: float) -> float:
        """Time-to-first-token percentile (seconds)."""
        if not self.records:
            return 0.0
        return float(np.percentile([r.ttft_s for r in self.records], pct))

    def tokens_of(self, rid: int) -> np.ndarray:
        for r in self.records:
            if r.rid == rid:
                return r.tokens
        raise KeyError(rid)

    def summary(self) -> str:
        line = (
            f"serving: mode={self.scheduler} requests={self.n_requests} "
            f"tokens={self.n_generated} steps={self.n_steps} "
            f"tokens_per_s={self.tokens_per_s:.1f} "
            f"requests_per_s={self.requests_per_s:.2f} "
            f"occupancy={self.occupancy:.3f} "
            f"p50_ms={self.latency_s(50) * 1e3:.0f} "
            f"p95_ms={self.latency_s(95) * 1e3:.0f} "
            f"p95_ttft_ms={self.ttft_s(95) * 1e3:.0f} "
            f"prefill_traces={self.n_prefill_traces} "
            f"kv_mib={self.peak_kv_bytes / 2**20:.1f} "
            f"reprograms={self.reprograms} "
            f"program_events_delta={self.program_events_delta}"
        )
        if self.counters is not None:
            line += (
                f" top1_agreement={self.counters['top1']:.4f}"
                f" logit_mse={self.counters['logit_mse']:.6e}"
            )
        return line


class ServingEngine:
    """Request-level serving over one model (programmed chip or digital).

    ``config`` is a :class:`~repro.serving.config.ServingConfig` -- the
    documented constructor is ``ServingEngine(model_cfg, analog_cfg,
    params, ServingConfig(...))`` (legacy loose kwargs route through a
    one-warning deprecation shim). ``analog_cfg``/``params`` are what the
    forward pass executes -- for a compiled chip use :meth:`for_program`
    (or pass ``program=``), which also enables
    :meth:`age_to`/:class:`DriftPolicy`. ``ref_params`` switches on the
    accuracy counters (unless ``config.ref_check`` is False): a digital
    full-precision reference decoded in lockstep, teacher-forced on the
    served token stream (the same counters ``serve.py`` always printed).
    ``src_params`` is the refresh policy's reprogramming source.

    ``config.paged`` switches the slot cache to the block/paged layout:
    ``s_max`` becomes the per-slot VIRTUAL capacity while resident KV
    memory is ``n_pages * page_size`` rows per layer (default: the same
    footprint as the rectangle, ``n_slots * ceil(s_max/page_size) + 1``
    pages -- pass a smaller pool to serve long-prompt traffic at flat
    memory). Prefill is *bucketed*: prompts are right-padded to
    ``prefill_buckets`` (default: a geometric 32*2^k grid up to
    ``s_max``) and same-bucket admissions share one padded prefill call.
    ``prefill_batch`` sets the row count at the SMALLEST bucket; larger
    buckets batch proportionally fewer rows (a constant prefill token
    budget, so a lone long prompt never pays for dummy rows), and each
    bucket has exactly one ``(rows, bucket)`` shape -- the engine
    compiles at most one prefill trace per bucket. ``prefill_batch`` is
    forced to 1 when
    the analog config draws per-request noise (per-rid rng keys) or the
    period contains MoE blocks (capacity routing couples batch rows);
    both keep paged serving bit-identical to the rectangular engine.
    """

    def __init__(
        self,
        model_cfg: ModelConfig,
        analog_cfg: AnalogConfig,
        params: Any,
        config: Optional[ServingConfig] = None,
        *,
        program: Optional[CiMProgram] = None,
        ref_params: Any = None,
        src_params: Any = None,
        mesh: Any = None,
        rng: Optional[Array] = None,
        **legacy,
    ):
        if legacy:
            unknown = sorted(set(legacy) - _LEGACY_CONFIG_KEYS)
            if unknown:
                raise TypeError(
                    f"ServingEngine got unexpected keyword arguments "
                    f"{unknown}; serving settings live on ServingConfig"
                )
            if config is not None:
                raise TypeError(
                    "pass serving settings through ServingConfig OR the "
                    "legacy loose kwargs, not both"
                )
            # exactly ONE warning per construction however many legacy
            # kwargs were passed (pinned by tests)
            warnings.warn(
                "ServingEngine's loose serving kwargs (n_slots=..., "
                "s_max=..., paged=..., ...) are deprecated; pass a "
                "ServingConfig instead: ServingEngine(model_cfg, "
                "analog_cfg, params, ServingConfig(n_slots=..., "
                "s_max=..., ...)). The legacy kwargs will be removed "
                "in the next release.",
                DeprecationWarning,
                stacklevel=2,
            )
            config = ServingConfig(**legacy)
        if config is None:
            raise TypeError(
                "ServingEngine needs a ServingConfig, e.g. "
                "ServingEngine(model_cfg, analog_cfg, params, "
                "ServingConfig(n_slots=4, s_max=64))"
            )
        if model_cfg.n_codebooks:
            raise NotImplementedError(
                "request-level serving drives a single token stream; "
                "multi-codebook decoders are not supported"
            )
        self.cfg = model_cfg
        self.acfg = analog_cfg
        self.params = params
        self.program = program
        self.config = config
        self.n_slots = int(config.n_slots)
        self.s_max = int(config.s_max)
        self.ref_params = ref_params
        self.src_params = src_params
        self.mesh = mesh
        self.rng = jax.random.PRNGKey(0) if rng is None else rng
        self.reprograms = 0
        #: distinct prefill shapes jitted by this engine (one trace each)
        self._prefill_shapes: set = set()

        self.paged = bool(config.paged)
        if self.paged:
            if model_cfg.frontend in ("audio_frames", "vision_patches"):
                raise NotImplementedError(
                    "bucketed prefill pads token prompts; feature-fed "
                    f"frontends ({model_cfg.frontend!r}) are not supported "
                    "in paged mode"
                )
            self.page_size = int(config.page_size)
            self.pages_per_slot = -(-self.s_max // self.page_size)
            self.n_pages = int(
                config.n_pages
                if config.n_pages is not None
                else self.n_slots * self.pages_per_slot + 1
            )
            buckets = (
                tuple(config.prefill_buckets)
                if config.prefill_buckets
                else default_buckets(self.s_max)
            )
            self.prefill_buckets = tuple(
                sorted({min(int(b), self.s_max) for b in buckets} | {self.s_max})
            )
            if min(self.prefill_buckets) < 1:
                raise ValueError(
                    f"prefill buckets must be >= 1: {self.prefill_buckets}"
                )
            # per-request rng keys and MoE capacity routing both couple a
            # prefill batch's rows to its composition; solo prefill keeps
            # paged serving bit-identical to the rectangular engine
            prefill_batch = config.prefill_batch
            if analog_cfg.needs_rng or "moe" in block_period(model_cfg):
                prefill_batch = 1
            self.prefill_batch = int(prefill_batch)
            # constant prefill TOKEN budget: ``prefill_batch`` rows at the
            # smallest bucket, fewer rows as buckets grow (a lone long
            # prompt padded to a fixed row count would pay row_count times
            # its prefill FLOPs in dummy rows -- measured as a 2.4x p95
            # TTFT regression). One (rows, bucket) shape per bucket keeps
            # the trace bound at len(prefill_buckets).
            budget = self.prefill_batch * min(self.prefill_buckets)
            self._pb_of = {
                b: max(1, min(self.prefill_batch, budget // b))
                for b in self.prefill_buckets
            }
            # early family validation (same check init_lm_cache applies)
            init_lm_cache(
                model_cfg, 1, self.page_size, model_cfg.dtype,
                stacked=False, paged=True,
                page_size=self.page_size, n_pages=2,
            )

        self.fused = bool(getattr(config, "fused_decode", False))
        self._fused_plan = None
        if self.fused:
            if program is None:
                raise ValueError(
                    "fused_decode executes a compiled CiMProgram's per-"
                    "layer plans as one grid; pass program= (or use "
                    "ServingEngine.for_program)"
                )
            if mesh is not None:
                raise NotImplementedError(
                    "fused decode runs the whole step in one single-"
                    "device kernel; sharded serving keeps the per-layer "
                    "path"
                )
            if block_period(model_cfg) != ["attn"]:
                raise NotImplementedError(
                    "fused decode supports the dense attention+FFN layer "
                    f"walk; family {model_cfg.family!r} has recurrent or "
                    "MoE blocks with no grid-step lowering"
                )
            # raises ValueError when the artifact's plans can't be
            # statically fused (tail layers, biases, missing GDC scalars)
            self._fused_plan = engine_mod.build_fused_plan(program)

        cfg, acfg, s_full = self.cfg, self.acfg, self.s_max

        def prefill(params, batch, rng):
            cache = init_lm_cache(cfg, 1, s_full, cfg.dtype)
            logits, cache = lm_forward(
                params, batch, acfg, cfg, cache=cache, last_token_only=True,
                rng=rng if acfg.needs_rng else None,
            )
            last = logits[:, -1]
            tok = jnp.argmax(last, axis=-1).astype(jnp.int32)
            return tok, last, unstack_cache(cache)

        def decode(params, tok, cache, rng):
            logits, cache = lm_forward(
                params, {"tokens": tok}, acfg, cfg, cache=cache,
                rng=rng if acfg.needs_rng else None,
            )
            last = logits[:, -1]
            return jnp.argmax(last, axis=-1).astype(jnp.int32), last, cache

        self._prefill = jax.jit(prefill)
        self._decode = jax.jit(decode, donate_argnums=(2,))
        # donate the shared cache: admission/retirement touch one slot row
        # but without donation XLA copies the whole multi-layer buffer
        self._write_slot = jax.jit(write_cache_slot, donate_argnums=(0,))
        self._reset_slot = jax.jit(reset_cache_slot, donate_argnums=(0,))

        # the MAIN cache's slot writers: the fused path swaps in the
        # stacked-layout versions while the reference cache (always the
        # rectangular per-slot layout) keeps using _write/_reset_slot
        self._write_main = self._write_slot
        self._reset_main = self._reset_slot
        if self.fused:
            fplan = self._fused_plan

            def fused_step(params, tok, cache, rng):
                logits, cache = decode_fused.fused_decode_step(
                    params, tok, cache, fplan, cfg, acfg,
                    rng=rng if acfg.needs_rng else None,
                )
                last = logits[:, -1]
                return (
                    jnp.argmax(last, axis=-1).astype(jnp.int32), last, cache
                )

            self._decode = jax.jit(fused_step, donate_argnums=(2,))
            self._write_main = jax.jit(
                decode_fused.write_fused_slot, donate_argnums=(0,)
            )
            self._reset_main = jax.jit(
                decode_fused.reset_fused_slot, donate_argnums=(0,)
            )

        if self.paged:

            def prefill_bucket(params, toks, last_idx, rng):
                # (PB, S_bucket) right-padded prompts; one jit trace per
                # bucket length. last_idx picks each row's true final
                # position (padding makes row ends differ).
                pb, sb = toks.shape
                cache = init_lm_cache(cfg, pb, sb, cfg.dtype)
                logits, cache = lm_forward(
                    params, {"tokens": toks}, acfg, cfg, cache=cache,
                    last_token_only=True, last_index=last_idx,
                    rng=rng if acfg.needs_rng else None,
                )
                last = logits[:, -1]
                tok = jnp.argmax(last, axis=-1).astype(jnp.int32)
                return tok, last, unstack_cache(cache)

            self._prefill_bucket = jax.jit(prefill_bucket)
            self._write_slot_paged = jax.jit(
                write_cache_slot_paged, donate_argnums=(0,)
            )
            self._append_page = jax.jit(
                append_cache_page, donate_argnums=(0,)
            )
            self._free_slot_paged = jax.jit(
                free_cache_slot_paged, donate_argnums=(0,)
            )

        self._ref = ref_params is not None and config.ref_check
        if self._ref:
            dig = AnalogConfig()  # digital full-precision reference

            def ref_prefill(params, batch):
                cache = init_lm_cache(cfg, 1, s_full, cfg.dtype)
                logits, cache = lm_forward(
                    params, batch, dig, cfg, cache=cache,
                    last_token_only=True,
                )
                return logits[:, -1], unstack_cache(cache)

            def ref_decode(params, tok, cache):
                logits, cache = lm_forward(
                    params, {"tokens": tok}, dig, cfg, cache=cache
                )
                return logits[:, -1], cache

            def count(a, r):
                a, r = a.astype(jnp.float32), r.astype(jnp.float32)
                agree = (
                    jnp.argmax(a, axis=-1) == jnp.argmax(r, axis=-1)
                ).astype(jnp.float32)
                return agree, jnp.sum((a - r) ** 2, axis=-1)

            self._ref_prefill = jax.jit(ref_prefill)
            self._ref_decode = jax.jit(ref_decode, donate_argnums=(2,))
            self._count = jax.jit(count)

    # -- chip lifecycle ----------------------------------------------------

    @classmethod
    def for_program(
        cls,
        program: CiMProgram,
        model_cfg: ModelConfig,
        config: Optional[ServingConfig] = None,
        **kw,
    ) -> "ServingEngine":
        """Engine over a compiled chip: executes (program.params, .cfg)."""
        return cls(
            model_cfg, program.cfg, program.params, config,
            program=program, **kw
        )

    def set_program(self, program: CiMProgram) -> None:
        """Swap in a new evaluation of the chip (values change, shapes
        don't -- the jitted closures never re-trace)."""
        self.program = program
        self.params = program.params

    def age_to(self, t_seconds: float) -> None:
        """Age the served chip in place (zero programming events,
        asserted by ``engine.age_program``)."""
        if self.program is None:
            raise RuntimeError("no compiled program to age (digital engine)")
        if float(t_seconds) != self.program.t_seconds:
            self.set_program(engine_mod.age_program(self.program, t_seconds))

    def refresh(self, key: Array) -> int:
        """Reprogram the chip from the stored source weights.

        Returns the number of per-layer programming events consumed, which
        the run's accounting adds to its allowance so the zero-delta
        assertion still holds across a refresh.
        """
        from repro.launch import steps

        if self.program is None or self.src_params is None:
            raise RuntimeError(
                "refresh needs a compiled program and src_params"
            )
        before = engine_mod.program_event_count()
        self.set_program(
            steps.refresh_program(
                self.program, self.src_params, key,
                mesh=self.mesh, model_cfg=self.cfg,
            )
        )
        self.reprograms += 1
        return engine_mod.program_event_count() - before

    # -- serving -----------------------------------------------------------

    def _prefill_inputs(self, req: Request) -> dict:
        batch = {"tokens": jnp.asarray(req.prompt, jnp.int32)[None, :]}
        if req.features:
            batch.update(req.features)
        return batch

    def start_run(
        self,
        *,
        scheduler: Any = None,
        drift_policy: Optional[DriftPolicy] = None,
        clock: Optional[clock_lib.Clock] = None,
        now_fn=None,
        sleep_fn=None,
        max_steps: Optional[int] = None,
        track_events: bool = True,
        on_token=None,
        on_retire=None,
    ) -> "EngineRun":
        """Open a fresh :class:`EngineRun` over this engine's (already
        compiled) closures.

        Each run re-initializes the slot caches, so runs are independent.
        Time enters only through ``clock`` (default: the system clock;
        tests inject a :class:`repro.clock.VirtualClock`); ``now_fn``/
        ``sleep_fn`` override individual methods of it. ``track_events=False`` delegates the
        program-event accounting to an outer owner (the fleet router owns
        it fleet-wide: with several engines sharing the global counter,
        per-run deltas would see sibling chips' refreshes).

        ``on_token(rid, token)`` fires for every token as it reaches the
        host -- the first token at admission, then one per decode step --
        and ``on_retire(record)`` fires when a request retires. Both run
        inline on whatever thread is stepping the run (the async fleet's
        streaming path); they must be cheap and must not call back into
        the run.
        """
        return EngineRun(
            self,
            scheduler=scheduler or ContinuousScheduler(),
            drift_policy=drift_policy,
            now_fn=now_fn or (clock or clock_lib.SYSTEM).now,
            sleep_fn=sleep_fn or (clock or clock_lib.SYSTEM).sleep,
            max_steps=max_steps,
            track_events=track_events,
            on_token=on_token,
            on_retire=on_retire,
        )

    def run(
        self,
        requests: list[Request],
        *,
        scheduler: Any = None,
        drift_policy: Optional[DriftPolicy] = None,
        clock: Optional[clock_lib.Clock] = None,
        now_fn=None,
        sleep_fn=None,
        max_steps: Optional[int] = None,
    ) -> ServeReport:
        """Serve ``requests`` to completion and return the run's report."""
        run = self.start_run(
            scheduler=scheduler, drift_policy=drift_policy, clock=clock,
            now_fn=now_fn, sleep_fn=sleep_fn, max_steps=max_steps,
        )
        run.submit(requests)
        while run.has_work:
            run.admit_arrived()
            if run.n_active == 0:
                if not run.queue:
                    break
                # idle: every queued request is still in flight to us
                run.idle_wait()
                continue
            run.decode_step()
        return run.finish()


class EngineRun:
    """One serving run's state plus its stepping surface.

    Created by :meth:`ServingEngine.start_run`. :meth:`ServingEngine.run`
    drives a run to completion; the fleet router steps several runs (one
    per chip) in lockstep and uses :meth:`live`/:meth:`evict` to migrate
    in-flight requests off a chip that is draining for a refresh, and
    :meth:`refresh_chip` to account the rewrite. The stepping order per
    tick is *admit then decode* -- exactly the order the single-engine
    loop uses, so a router-driven run is bit-identical to a solo one.

    Thread-safety: an ``EngineRun`` is **not** internally synchronized.
    Every mutating method (``submit``/``admit_arrived``/``decode_step``/
    ``evict``/``refresh_chip``/``retire``/``finish``) assumes a single
    caller; the slot list, the jax cache handles, and the counters are
    plain shared state. The concurrency contract (the async fleet's actor
    discipline, linted as RL006) is *exclusive ownership*: exactly one
    worker thread drives a given run, and other threads interact with it
    only by enqueuing commands to that owner. Bare counter/len reads
    (``n_active``, ``agree_sum``, ``decisions``, ``len(run.queue)``) are
    GIL-atomic snapshots and are safe cross-thread for monitoring; acting
    on the run from a non-owner thread is not.
    """

    def __init__(
        self,
        engine: ServingEngine,
        *,
        scheduler: Any,
        drift_policy: Optional[DriftPolicy],
        now_fn,
        sleep_fn,
        max_steps: Optional[int],
        track_events: bool,
        on_token=None,
        on_retire=None,
    ):
        self.eng = engine
        self.scheduler = scheduler
        self.drift_policy = drift_policy
        self.now_fn = now_fn
        self.sleep_fn = sleep_fn
        self.max_steps = max_steps
        self.track_events = track_events
        self.on_token = on_token
        self.on_retire = on_retire

        self.queue: deque[Request] = deque()
        if engine.paged:
            self.cache = init_lm_cache(
                engine.cfg, engine.n_slots, engine.s_max, engine.cfg.dtype,
                stacked=False, paged=True,
                page_size=engine.page_size, n_pages=engine.n_pages,
            )
            # engine-side page bookkeeping, fresh per run: the free list
            # plus a reservation counter. Admission reserves a request's
            # WORST-CASE page count (prompt + full budget), so a request
            # that got in can always append its growth pages -- mid-flight
            # pool exhaustion cannot deadlock the decode loop.
            self.allocator = PageAllocator(engine.n_pages)
            self.reserved = 0
            # KV tokens the paged kernel reads per slot and compute block
            # (None: the decode step attends over the gathered view)
            leaf = jax.tree.leaves(
                self.cache, is_leaf=lambda x: isinstance(x, attn_lib.PagedKVCache)
            )[0]
            self.kv_block = (
                attn_lib.kernel_block_pages(
                    engine.page_size, engine.pages_per_slot
                ) * engine.page_size
                if engine.mesh is None and attn_lib.kernel_engages(leaf)
                else None
            )
        elif engine.fused:
            # one stacked (L, B, S, kv, hd) buffer: the fused grid's layer
            # axis doubles as its BlockSpec index
            self.cache = decode_fused.init_fused_cache(
                engine.cfg, engine._fused_plan.n_groups, engine.n_slots,
                engine.s_max, engine.cfg.dtype,
            )
            self.allocator = None
            self.reserved = 0
        else:
            self.cache = init_lm_cache(
                engine.cfg, engine.n_slots, engine.s_max, engine.cfg.dtype,
                stacked=False, per_slot=True,
            )
            self.allocator = None
            self.reserved = 0
        self.peak_kv_bytes = _kv_cache_bytes(self.cache)
        self.ref_cache = (
            init_lm_cache(
                engine.cfg, engine.n_slots, engine.s_max, engine.cfg.dtype,
                stacked=False, per_slot=True,
            )
            if engine._ref
            else None
        )
        self.cur = jnp.zeros((engine.n_slots, 1), jnp.int32)
        self.slots: list[Optional[_Slot]] = [None] * engine.n_slots
        self.records: list[RequestRecord] = []
        self.steps = 0
        self.slot_steps = 0
        self.agree_sum = 0.0
        self.err_sum = 0.0
        self.decisions = 0
        #: the run's id on its ``engine.admit`` and ``engine.decode``
        #: spans, and the recorder's cursor at its start
        self.id = next(_run_ids)
        self.obs0 = obs.cursor()
        self.events0 = engine_mod.program_event_count()
        self.allowed_events = 0
        self.reprograms0 = engine.reprograms
        self.age_events: list[dict] = []
        # drift-policy runtime state
        self.pol_idx = 1  # the program is compiled at the schedule's first age
        self.last_wall = (
            drift_policy.schedule.times[0] if drift_policy else None
        )
        self.refresh_wall: Optional[float] = None
        self.seg_agree = 0.0
        self.seg_dec = 0
        self.t_start = now_fn()

    # -- queries -----------------------------------------------------------

    @property
    def n_active(self) -> int:
        return sum(s is not None for s in self.slots)

    @property
    def has_work(self) -> bool:
        return bool(self.queue) or any(s is not None for s in self.slots)

    @property
    def elapsed(self) -> float:
        """Seconds since the run started (on the run's clock)."""
        return self.now_fn() - self.t_start

    def live(self) -> list[tuple[int, Request, list[int]]]:
        """Snapshot of live slots: ``(slot, request, tokens so far)``."""
        return [
            (i, st.req, list(st.tokens))
            for i, st in enumerate(self.slots)
            if st is not None
        ]

    # -- request intake ----------------------------------------------------

    def submit(self, requests: list[Request]) -> None:
        """Validate and enqueue requests (mid-run submission is fine --
        the fleet router feeds migrated continuations this way)."""
        eng = self.eng
        for r in requests:
            if r.prompt.size + r.max_new_tokens > eng.s_max:
                raise ValueError(
                    f"request {r.rid}: prompt ({r.prompt.size}) + budget "
                    f"({r.max_new_tokens}) exceeds the engine's s_max="
                    f"{eng.s_max}"
                )
            if eng.paged and r.features:
                raise NotImplementedError(
                    f"request {r.rid}: feature-fed prefill is not "
                    "supported in paged mode (bucketed prefill pads "
                    "token prompts)"
                )
            if eng.paged:
                need = -(
                    -(r.prompt.size + r.max_new_tokens) // eng.page_size
                )
                if need > eng.n_pages - 1:
                    raise ValueError(
                        f"request {r.rid}: worst case needs {need} pages "
                        f"of {eng.page_size} but the pool has only "
                        f"{eng.n_pages - 1} usable -- it could never be "
                        "admitted"
                    )
        merged = list(self.queue) + list(requests)
        merged.sort(key=lambda r: r.arrival_t)  # stable: FIFO within ties
        self.queue = deque(merged)

    # -- stepping ----------------------------------------------------------

    def idle_wait(self) -> None:
        """Sleep toward the next queued arrival (nothing is decodable)."""
        wait = self.queue[0].arrival_t - (self.now_fn() - self.t_start)
        self.sleep_fn(max(min(wait, 0.01), 1e-4))

    def admit_arrived(self) -> None:
        """Admission phase: move arrived requests into free decode slots
        (scheduler-gated), prefilling each and seeding its slot."""
        eng = self.eng
        t_admit = self.now_fn()
        now = t_admit - self.t_start
        n_arrived = sum(1 for r in self.queue if r.arrival_t <= now)
        free = [i for i, s in enumerate(self.slots) if s is None]
        n_admit = self.scheduler.admit(
            n_arrived, len(free), eng.n_slots - len(free)
        )
        # a scheduler cannot over-admit: a slot never serves two live
        # requests, and only arrived requests are admissible
        n_admit = min(n_admit, n_arrived, len(free))
        # the queue is arrival-sorted, so the arrived requests are its
        # prefix; a scheduler's ``order`` hook picks WHICH of them
        # enter (default: FIFO)
        arrived = [self.queue[j] for j in range(n_arrived)]
        order_fn = getattr(self.scheduler, "order", None)
        perm = (
            list(order_fn(arrived)) if order_fn else list(range(n_arrived))
        )
        admitted: list[tuple[Request, int]] = []  # (request, queue idx)
        pending = 0  # pages claimed by this round's earlier admissions
        for j in perm[:n_admit]:
            req = arrived[j]
            if eng.paged:
                # reserve the worst case up front (head-of-line
                # blocking: stop rather than starve a long request)
                need = -(
                    -(req.prompt.size + req.max_new_tokens) // eng.page_size
                )
                if self.allocator.n_free - self.reserved - pending < need:
                    break
                pending += need
            admitted.append((req, j))
        if not admitted:
            return
        for j in sorted((j for _, j in admitted), reverse=True):
            del self.queue[j]

        reqs = [r for r, _ in admitted]
        with obs.span("engine.admit", self.now_fn, key=self.id,
                      a=len(reqs), t0=t_admit):
            for req in reqs:
                obs.sample("engine.queue_wait", now - req.arrival_t, t_admit,
                           req.rid)
            if eng.paged:
                self._admit_paged(reqs, free)
            else:
                self._admit_rect(reqs, free)

    def _admit_rect(self, reqs: list[Request], free: list[int]) -> None:
        eng = self.eng
        for req in reqs:
            slot = free.pop(0)
            n_prompt = int(req.prompt.size)
            eng._prefill_shapes.add((1, n_prompt))
            with obs.span("engine.prefill", self.now_fn, a=n_prompt, b=1):
                obs.count("engine.prefill_tokens_real", n_prompt)
                obs.count("engine.prefill_tokens_computed", n_prompt)
                tok0, logits0, pcache = eng._prefill(
                    eng.params,
                    eng._prefill_inputs(req),
                    jax.random.fold_in(eng.rng, 1_000_000 + req.rid),
                )
            with obs.span("engine.slot_write", self.now_fn):
                self.cache = eng._write_main(
                    self.cache, pcache, jnp.int32(slot)
                )
                self.cur = self.cur.at[slot, 0].set(tok0[0])
            if eng._ref:
                r_logits, r_pcache = eng._ref_prefill(
                    eng.ref_params, eng._prefill_inputs(req)
                )
                self.ref_cache = eng._write_slot(
                    self.ref_cache, r_pcache, jnp.int32(slot)
                )
                self._count_decision(logits0, r_logits, 0)
            with obs.span("engine.first_token", self.now_fn):
                # repro-lint: disable=RL004 -- one sync per ADMISSION (not per decode tick): the first token must reach the host record
                first = int(tok0[0])
            self.slots[slot] = _Slot(
                req, [first], self.steps, self.now_fn() - self.t_start,
                logits=self._logits_rows(logits0, 0),
            )
            if self.on_token is not None:
                self.on_token(req.rid, self.slots[slot].tokens[0])
            self.maybe_retire(slot)

    def _admit_paged(self, reqs: list[Request], free: list[int]) -> None:
        eng = self.eng
        ps = eng.page_size
        # group consecutive same-bucket admissions into one padded
        # prefill call of up to prefill_batch rows
        k0 = 0
        while k0 < len(reqs):
            sb = bucket_for(
                int(reqs[k0].prompt.size), eng.prefill_buckets
            )
            pb = eng._pb_of[sb]
            chunk = [reqs[k0]]
            while (
                len(chunk) < pb
                and k0 + len(chunk) < len(reqs)
                and bucket_for(
                    int(reqs[k0 + len(chunk)].prompt.size),
                    eng.prefill_buckets,
                )
                == sb
            ):
                chunk.append(reqs[k0 + len(chunk)])
            k0 += len(chunk)
            toks = np.zeros((pb, sb), np.int32)
            lens = np.ones((pb,), np.int32)
            for j, req in enumerate(chunk):
                toks[j, : req.prompt.size] = req.prompt
                lens[j] = req.prompt.size
            for j in range(len(chunk), pb):
                toks[j] = toks[0]  # dummy rows repeat row 0
                lens[j] = lens[0]
            eng._prefill_shapes.add((pb, sb))
            with obs.span("engine.prefill", self.now_fn, a=sb, b=pb):
                obs.count("engine.prefill_tokens_real",
                          sum(int(r.prompt.size) for r in chunk))
                obs.count("engine.prefill_tokens_computed", pb * sb)
                tokv, logitsv, pcache = eng._prefill_bucket(
                    eng.params,
                    jnp.asarray(toks),
                    jnp.asarray(lens - 1),
                    jax.random.fold_in(
                        eng.rng, 1_000_000 + chunk[0].rid
                    ),
                )
            for j, req in enumerate(chunk):
                slot = free.pop(0)
                n_prompt = int(req.prompt.size)
                nbp_real = -(-n_prompt // ps)
                need = -(-(n_prompt + req.max_new_tokens) // ps)
                pages = self.allocator.alloc(nbp_real)
                self.reserved += need - nbp_real
                pvec = np.zeros((-(-sb // ps),), np.int32)
                pvec[:nbp_real] = pages
                with obs.span("engine.slot_write", self.now_fn):
                    self.cache = eng._write_slot_paged(
                        self.cache, pcache, jnp.int32(slot), jnp.int32(j),
                        jnp.asarray(pvec), jnp.int32(n_prompt),
                    )
                    self.cur = self.cur.at[slot, 0].set(tokv[j])
                if eng._ref:
                    r_logits, r_pcache = eng._ref_prefill(
                        eng.ref_params, eng._prefill_inputs(req)
                    )
                    self.ref_cache = eng._write_slot(
                        self.ref_cache, r_pcache, jnp.int32(slot)
                    )
                    self._count_decision(logitsv[j : j + 1], r_logits, 0)
                with obs.span("engine.first_token", self.now_fn):
                    # repro-lint: disable=RL004 -- one sync per ADMISSION (bucketed prefill), amortized over the request's whole decode
                    first = int(tokv[j])
                self.slots[slot] = _Slot(
                    req, [first], self.steps,
                    self.now_fn() - self.t_start,
                    pages=pages, reserve_left=need - nbp_real,
                    logits=self._logits_rows(logitsv, j),
                )
                if self.on_token is not None:
                    self.on_token(req.rid, self.slots[slot].tokens[0])
                self.maybe_retire(slot)

    def decode_step(self) -> None:
        """One jitted decode step over all live slots, plus retirement,
        the drift-policy tick, and the runaway guard."""
        eng = self.eng
        now_fn = self.now_fn
        with obs.span("engine.decode", now_fn, key=self.id):
            if eng.paged:
                with obs.span("engine.page_append", now_fn):
                    self._append_pages()
                self._count_kv_reads()
            with obs.span("engine.decode_launch", now_fn):
                nxt, logits, self.cache = eng._decode(
                    eng.params, self.cur, self.cache,
                    jax.random.fold_in(eng.rng, self.steps),
                )
                if eng._ref:
                    r_logits, self.ref_cache = eng._ref_decode(
                        eng.ref_params, self.cur, self.ref_cache
                    )
                    a_v, e_v = eng._count(logits, r_logits)
            with obs.span("engine.decode_sync", now_fn):
                if eng._ref:
                    a_np, e_np = np.asarray(a_v), np.asarray(e_v)
                nxt_np = np.asarray(nxt)
            with obs.span("engine.tokens", now_fn):
                self.steps += 1
                active = [i for i, s in enumerate(self.slots) if s is not None]
                self.slot_steps += len(active)
                rows = (
                    np.asarray(logits, np.float32)
                    if eng.config.record_logits else None
                )
                for i in active:
                    self.slots[i].tokens.append(int(nxt_np[i]))
                    if rows is not None:
                        self.slots[i].logits.append(rows[i])
                    if self.on_token is not None:
                        self.on_token(
                            self.slots[i].req.rid, self.slots[i].tokens[-1]
                        )
                    if eng._ref:
                        self.agree_sum += float(a_np[i])
                        self.err_sum += float(e_np[i])
                        self.decisions += 1
                        self.seg_agree += float(a_np[i])
                        self.seg_dec += 1
                self.cur = nxt[:, None]
                for i in active:
                    self.maybe_retire(i)

                self._drift_tick()

        if self.max_steps is not None and self.steps >= self.max_steps:
            raise RuntimeError(
                f"serving run exceeded max_steps={self.max_steps} with "
                f"{self.n_active} live slots and "
                f"{len(self.queue)} queued requests"
            )

    def _append_pages(self) -> None:
        """Lazy growth: a slot whose next decode write crosses a page
        boundary gets one page off the free list (always available -- it
        was reserved at admission)."""
        eng = self.eng
        for i, st in enumerate(self.slots):
            if st is None:
                continue
            pos = int(st.req.prompt.size) + len(st.tokens) - 1
            entry = pos // eng.page_size
            if entry >= len(st.pages):
                (page,) = self.allocator.alloc(1)
                self.reserved -= 1
                st.reserve_left -= 1
                st.pages.append(page)
                self.cache = eng._append_page(
                    self.cache, jnp.int32(i), jnp.int32(entry),
                    jnp.int32(page),
                )

    def _count_kv_reads(self) -> None:
        """Count the KV tokens this step's paged attention reads, from the
        slots' bookkeeping: on the kernel path each live slot's length
        rounded up to the compute block, else the whole gathered view
        (``engine.kv_tokens_view``, n_slots x s_max)."""
        eng = self.eng
        view = eng.n_slots * eng.s_max
        bk = self.kv_block
        read = view if bk is None else sum(
            -(-min(st.req.prompt.size + len(st.tokens), eng.s_max) // bk) * bk
            for st in self.slots if st is not None
        )
        obs.count("engine.kv_tokens_read", read)
        obs.count("engine.kv_tokens_view", view)

    def _logits_rows(self, logits, row: int) -> Optional[list]:
        """A new slot's recorded logits: its first token's row, if kept."""
        if not self.eng.config.record_logits:
            return None
        return [np.asarray(logits[row], np.float32)]

    def _count_decision(self, a_logits, r_logits, row: int) -> None:
        a, e = self.eng._count(a_logits, r_logits)
        self.agree_sum += float(a[row])
        self.err_sum += float(e[row])
        self.decisions += 1
        self.seg_agree += float(a[row])
        self.seg_dec += 1

    def _drift_tick(self) -> None:
        policy = self.drift_policy
        if policy is None or self.steps % policy.every_steps != 0:
            return
        # refresh check on the segment served since the last tick
        if (
            policy.refresh_below is not None
            and self.eng._ref
            and self.seg_dec > 0
            and self.seg_agree / self.seg_dec < policy.refresh_below
        ):
            top1 = self.seg_agree / self.seg_dec
            self.refresh_chip(
                jax.random.fold_in(self.eng.rng, 7_000_000 + self.steps),
                top1=top1,
            )
        self.seg_agree, self.seg_dec = 0.0, 0
        if self.pol_idx < len(policy.schedule.times):
            t_wall = policy.schedule.times[self.pol_idx]
            self.pol_idx += 1
            self.last_wall = t_wall
            dev = engine_mod.device_age(t_wall, self.refresh_wall)
            self.eng.age_to(dev)
            self.age_events.append(
                {
                    "kind": "age",
                    "step": self.steps,
                    "t_wall": t_wall,
                    "t_device": dev,
                }
            )

    # -- retirement / migration -------------------------------------------

    def retire(self, i: int, st: _Slot, by: str) -> None:
        # a migration continuation carries the FIRST chip's first-token
        # time; recording it as admit_t keeps ttft_s spanning every chip
        # the request touched instead of restarting at re-admission
        first_t = st.req.first_token_t
        rec = RequestRecord(
            rid=st.req.rid,
            slot=i,
            tokens=np.asarray(st.tokens, np.int32),
            n_prompt=int(st.req.prompt.size),
            admit_step=st.admit_step,
            finish_step=self.steps,
            arrival_t=st.req.arrival_t,
            admit_t=st.admit_t if first_t is None else first_t,
            finish_t=self.now_fn() - self.t_start,
            finished_by=by,
            logits=None if st.logits is None else np.stack(st.logits),
        )
        self.records.append(rec)
        self._release_slot(i, st)
        if self.on_retire is not None:
            self.on_retire(rec)

    def maybe_retire(self, i: int) -> None:
        st = self.slots[i]
        if st.req.eos_id is not None and st.tokens[-1] == st.req.eos_id:
            self.retire(i, st, "eos")
        elif len(st.tokens) >= st.req.max_new_tokens:
            self.retire(i, st, "max_tokens")

    def evict(self, i: int) -> tuple[Request, list[int]]:
        """Remove a LIVE slot without recording a retirement.

        The fleet router's drain path: the request and its tokens so far
        come back so the router can re-enqueue a continuation on a sibling
        chip; this run's conservation (slot freed, pages returned) is kept
        intact.
        """
        st = self.slots[i]
        if st is None:
            raise ValueError(f"slot {i} holds no live request")
        self._release_slot(i, st)
        return st.req, list(st.tokens)

    def _release_slot(self, i: int, st: _Slot) -> None:
        eng = self.eng
        if eng.paged:
            # zero the slot's pages/table/length, then return the ids
            # (and the unused tail of its reservation) to the pool
            pvec = np.zeros((eng.pages_per_slot,), np.int32)
            pvec[: len(st.pages)] = st.pages
            self.cache = eng._free_slot_paged(
                self.cache, jnp.int32(i), jnp.asarray(pvec)
            )
            self.allocator.free(st.pages)
            self.reserved -= st.reserve_left
        else:
            self.cache = eng._reset_main(self.cache, jnp.int32(i))
        if eng._ref:
            self.ref_cache = eng._reset_slot(self.ref_cache, jnp.int32(i))
        self.slots[i] = None

    def refresh_chip(self, key: Array, top1: Optional[float] = None) -> int:
        """Reprogram this run's chip and account the programming events
        against the run's allowance (kept zero-delta)."""
        consumed = self.eng.refresh(key)
        self.allowed_events += consumed
        self.refresh_wall = self.last_wall
        self.age_events.append(
            {
                "kind": "reprogram",
                "step": self.steps,
                "top1": top1,
                "t_device": self.eng.program.t_seconds,
            }
        )
        return consumed

    # -- completion --------------------------------------------------------

    def finish(self) -> ServeReport:
        """Close the run: conservation checks + the final report."""
        eng = self.eng
        wall = self.now_fn() - self.t_start
        delta = engine_mod.program_event_count() - self.events0
        if (
            self.track_events
            and eng.program is not None
            and delta != self.allowed_events
        ):
            raise RuntimeError(
                f"serving run recorded {delta} programming events but "
                f"refreshes account for {self.allowed_events} -- the "
                "programmed chip must never be rewritten by serving itself"
            )
        if eng.paged and (self.allocator.n_in_use or self.reserved):
            raise RuntimeError(
                f"page leak: {self.allocator.n_in_use} pages still "
                f"allocated and {self.reserved} still reserved after every "
                "request retired -- admit/retire must conserve the free list"
            )
        spent = {"engine.admit": 0.0, "engine.decode": 0.0}
        for kind, name, t0, t1, _, _, key, _, _ in obs.records(self.obs0):
            if kind == obs.SPAN and key == self.id and name in spent:
                spent[name] += t1 - t0
        counters = None
        if eng._ref:
            counters = {
                "top1": self.agree_sum / max(self.decisions, 1),
                "logit_mse": self.err_sum / max(
                    self.decisions * eng.cfg.vocab, 1
                ),
                "decisions": self.decisions,
            }
        return ServeReport(
            records=self.records,
            scheduler=getattr(
                self.scheduler, "name", type(self.scheduler).__name__
            ),
            n_slots=eng.n_slots,
            n_steps=self.steps,
            slot_steps=self.slot_steps,
            t_prefill=spent["engine.admit"],
            t_decode=spent["engine.decode"],
            wall=wall,
            counters=counters,
            age_events=self.age_events,
            reprograms=eng.reprograms - self.reprograms0,
            program_events_delta=(
                delta - self.allowed_events if self.track_events else 0
            ),
            n_prefill_traces=len(eng._prefill_shapes),
            peak_kv_bytes=self.peak_kv_bytes,
            peak_pages_in_use=(
                self.allocator.peak_in_use if eng.paged else 0
            ),
        )
