"""Serving configuration surfaces: :class:`ServingConfig` / :class:`FleetConfig`.

The engine grew one keyword at a time across PRs 5-6 until its constructor
carried ~10 loose kwargs (slots, paged/page/bucket settings, ...) that every
caller -- serve.py, benchmarks, examples -- had to thread positionally.
A fleet dimension on top (N chips, SLO, refresh staggering) does not fit
that shape, so the surface is two frozen dataclasses:

* :class:`ServingConfig` -- everything that shapes ONE engine's serving
  behaviour and is a plain value (slot count, virtual capacity, the paged
  KV-cache geometry, prefill bucketing, whether the digital-reference
  counters run). Live objects (the compiled program, reference / source
  params, mesh, rng) stay constructor keywords on
  :class:`~repro.serving.engine.ServingEngine` -- they are state, not
  configuration, and are not comparable/hashable the way a config must be.
* :class:`FleetConfig` -- the fleet dimension: how many chips, the
  aggregate-agreement SLO the router admits against, the per-chip refresh
  trigger, and the stagger discipline (how many chips may be down at once,
  and for how many router ticks a rewrite takes).
* :class:`AsyncConfig` -- the async front end over the fleet
  (``serving/async_fleet.py``): the fleet-wide queued-work cap, what
  ``submit`` does when the cap is hit (block vs shed), how many worker
  threads drive the chips, and the idle poll cadence.

All validate eagerly in ``__post_init__`` so a bad value dies at config
construction, not deep inside a serving run. Legacy
``ServingEngine(n_slots=..., ...)`` kwargs still work for one release via a
deprecation shim (exactly one :class:`DeprecationWarning` per construction).
"""

from __future__ import annotations

import dataclasses
from typing import Optional


@dataclasses.dataclass(frozen=True)
class ServingConfig:
    """Plain-value configuration of one :class:`ServingEngine`.

    ``n_slots``
        Decode slots -- the continuous-batching width. Every engine step
        advances all live slots with one jitted forward.
    ``s_max``
        Per-slot capacity in tokens (prompt + generation budget). With
        ``paged=True`` this is *virtual* capacity: resident memory is the
        page pool, not ``n_slots * s_max``.
    ``paged`` / ``page_size`` / ``n_pages``
        Switch the slot rectangles to the shared paged KV cache: per-layer
        pools of ``page_size``-token pages, ``n_pages`` total (page 0 is
        the reserved scratch page). ``n_pages=None`` sizes the pool to the
        rectangle-equivalent ``n_slots * ceil(s_max/page_size) + 1``.
    ``prefill_buckets`` / ``prefill_batch``
        Bucketed prefill (paged mode): prompts are right-padded to the
        bucket grid (default: geometric ``32*2^k`` up to ``s_max``) so the
        engine compiles one prefill trace per bucket; ``prefill_batch``
        rows batch at the smallest bucket (constant prefill token budget,
        proportionally fewer rows at larger buckets).
    ``ref_check``
        Whether the digital-reference accuracy counters (greedy top-1
        agreement, logit MSE) run when the engine is given ``ref_params``.
        ``False`` skips the lockstep reference decode even if reference
        params are available (the ``serve.py --no-ref-check`` knob).
    ``fused_decode``
        Execute the whole programmed decode step as ONE Pallas grid
        (``kernels/decode_fused.py``): the layer walk becomes a grid
        dimension and every layer's DAC/MVM/ADC/GDC chain runs inside a
        single kernel launch. Requires a compiled :class:`CiMProgram`
        whose plans pass ``engine.build_fused_plan``; bit-identical to
        the per-layer decode. Does not compose with ``paged`` (the fused
        grid owns one stacked slot cache, not a page pool).
    ``record_logits``
        Keep the logits of every emitted position on the request's
        :class:`~repro.serving.requests.RequestRecord` (``logits``, f32
        ``(n_new, vocab)`` on the host): the served path's own output, for
        checks against a reference forward. Costs one (slots, vocab) copy
        to the host per decode step.
    """

    n_slots: int
    s_max: int
    paged: bool = False
    page_size: int = 16
    n_pages: Optional[int] = None
    prefill_buckets: Optional[tuple] = None
    prefill_batch: int = 4
    ref_check: bool = True
    fused_decode: bool = False
    record_logits: bool = False

    def __post_init__(self):
        if self.n_slots < 1:
            raise ValueError("need at least one decode slot")
        if self.fused_decode and self.paged:
            raise ValueError(
                "fused_decode writes the stacked per-slot KV cache inside "
                "one decode grid; it does not compose with the paged KV "
                "cache -- pick one"
            )
        if self.s_max < 1:
            raise ValueError(f"s_max must be >= 1, got {self.s_max}")
        if self.prefill_buckets is not None:
            object.__setattr__(
                self, "prefill_buckets",
                tuple(int(b) for b in self.prefill_buckets),
            )
        if self.paged:
            if self.page_size < 1:
                raise ValueError(
                    f"page_size must be >= 1, got {self.page_size}"
                )
            if self.prefill_batch < 1:
                raise ValueError(
                    f"prefill_batch must be >= 1, got {self.prefill_batch}"
                )
            if self.n_pages is not None and self.n_pages < 2:
                raise ValueError(
                    f"need at least 2 pages (scratch + 1 usable), got "
                    f"{self.n_pages}"
                )


@dataclasses.dataclass(frozen=True)
class FleetConfig:
    """Configuration of a :class:`~repro.serving.fleet.FleetRouter`.

    ``n_chips``
        Independently-programmed chips behind the router. Each chip is its
        own write-noise draw with its own drift clock -- chips are
        non-interchangeable replicas, which is exactly why the router
        tracks per-chip age/agreement state.
    ``agreement_slo``
        Aggregate top-1-agreement floor for the fleet (vs the digital
        reference). Admission prefers chips whose recent agreement clears
        the SLO, and the router records the worst aggregate window so a
        refresh storm can be *asserted* to never dip below it
        (``FleetReport.min_window_agreement``). ``None`` disables both.
    ``refresh_below``
        Per-chip refresh trigger: when one chip's agreement over the last
        health-check window drops below this, the router drains the chip
        (in-flight requests migrate losslessly to siblings), reprograms it
        from the stored source weights, and rejoins it with a reset drift
        clock. Requires the engines to run with reference counters.
    ``check_every``
        Router ticks between health checks (agreement windows, refresh
        triggers, SLO tracking).
    ``max_refreshing``
        Stagger width: at most this many chips may be down (draining /
        rewriting) at any moment, so the fleet never loses more than a
        known fraction of its capacity to refreshes. When refreshes are
        armed (``refresh_below`` set) this must leave at least one chip
        serving (``max_refreshing < n_chips``) -- otherwise a drain of
        the last healthy chip has nowhere to migrate its in-flight
        requests and dispatch dies mid-run.
    ``refresh_steps``
        Router ticks a chip stays out of rotation while its rewrite is in
        flight -- the modelled PCM write latency. Siblings carry the
        migrated load for the whole window; at the end the chip is
        reprogrammed (fresh write noise, age reset to t_c) and rejoins.
    """

    n_chips: int
    agreement_slo: Optional[float] = None
    refresh_below: Optional[float] = None
    check_every: int = 8
    max_refreshing: int = 1
    refresh_steps: int = 4

    def __post_init__(self):
        if self.n_chips < 1:
            raise ValueError(f"need at least one chip, got {self.n_chips}")
        if self.check_every < 1:
            raise ValueError(
                f"check_every must be >= 1, got {self.check_every}"
            )
        if self.max_refreshing < 1:
            raise ValueError(
                f"max_refreshing must be >= 1, got {self.max_refreshing}"
            )
        if self.refresh_steps < 0:
            raise ValueError(
                f"refresh_steps must be >= 0, got {self.refresh_steps}"
            )
        for name in ("agreement_slo", "refresh_below"):
            v = getattr(self, name)
            if v is not None and not (0.0 <= v <= 1.0):
                raise ValueError(
                    f"{name} is a top-1-agreement fraction in [0, 1], "
                    f"got {v}"
                )
        if self.refresh_below is not None and self.max_refreshing >= self.n_chips:
            raise ValueError(
                f"max_refreshing={self.max_refreshing} with "
                f"n_chips={self.n_chips} would allow every chip to drain at "
                f"once, leaving migrated requests nowhere to go -- "
                f"max_refreshing must be < n_chips when refreshes are armed"
            )


@dataclasses.dataclass(frozen=True)
class AsyncConfig:
    """Configuration of the async fleet front end.

    (:class:`~repro.serving.async_fleet.AsyncFleetRouter` -- the threaded
    serving layer over a fleet of chips.)

    ``queue_cap``
        Fleet-wide queued-work cap: the number of accepted-but-not-yet-
        admitted requests (admission queue + per-chip engine queues +
        dispatched-but-unprocessed submissions) at which ``submit`` /
        ``submit_stream`` applies backpressure.
    ``shed_policy``
        What backpressure does: ``"block"`` makes submit wait until work
        drains below the cap (bounded by ``submit_timeout_s`` when set);
        ``"shed"`` raises :class:`~repro.serving.async_fleet.QueueFull`
        immediately.
    ``workers``
        Decode worker threads. ``None`` (default) gives every chip its
        own worker -- maximum decode overlap, since jitted decode steps
        release the GIL inside XLA. Fewer workers than chips round-robins
        chips across workers (chip ``c`` is owned by worker
        ``c % workers``); each chip is still owned by exactly one worker,
        which is the fleet's whole thread-safety story.
    ``submit_timeout_s``
        With ``shed_policy="block"``: how long a blocked submit waits for
        capacity before raising ``QueueFull``. ``None`` waits forever.
    ``poll_s``
        Idle poll cadence for workers with no admissible work and for the
        coordinator between bookkeeping ticks. Real-clock threads only;
        the deterministic driver paces itself off the injected clock.
    """

    queue_cap: int = 64
    shed_policy: str = "block"
    workers: Optional[int] = None
    submit_timeout_s: Optional[float] = None
    poll_s: float = 1e-3

    def __post_init__(self):
        if self.queue_cap < 1:
            raise ValueError(f"queue_cap must be >= 1, got {self.queue_cap}")
        if self.shed_policy not in ("block", "shed"):
            raise ValueError(
                f"shed_policy must be 'block' or 'shed', got "
                f"{self.shed_policy!r}"
            )
        if self.workers is not None and self.workers < 1:
            raise ValueError(f"workers must be >= 1, got {self.workers}")
        if self.submit_timeout_s is not None and self.submit_timeout_s < 0:
            raise ValueError(
                f"submit_timeout_s must be >= 0, got {self.submit_timeout_s}"
            )
        if self.poll_s <= 0:
            raise ValueError(f"poll_s must be > 0, got {self.poll_s}")
