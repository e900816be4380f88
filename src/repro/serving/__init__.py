"""repro.serving -- request-level serving over programmed CiM chips.

Architecture (one PR-4-era ``serve_pass`` rectangle, refactored into
layers):

* ``config.py``    -- the configuration surface: frozen
  :class:`ServingConfig` (slots, capacity, paged-KV geometry, prefill
  bucketing, ref-check) and :class:`FleetConfig` (chip count, aggregate
  agreement SLO, refresh trigger + stagger discipline). ``ServingEngine``
  takes a ``ServingConfig``; the pre-config loose kwargs still work for
  one release behind a single-warning deprecation shim.
* ``requests.py``  -- the client surface: :class:`Request` (variable-length
  prompt, token budget, EOS, arrival time), :class:`RequestRecord` (what a
  retired request hands back), and :func:`poisson_trace` (the synthetic
  variable-length workload with optional Poisson arrivals).
* ``scheduler.py`` -- admission policy only: :class:`ContinuousScheduler`
  (refill any free slot immediately -- the decode batch stays full under
  variable-length traffic) vs :class:`StaticBatchScheduler` (classic wave
  batching, the padded baseline the benchmarks compare against) vs
  :class:`BucketedScheduler` (continuous admission in prompt-length order,
  so the paged engine's bucketed prefill batches same-bucket requests).
* ``paging.py``    -- :class:`PageAllocator`, the engine-side free list
  over one page-id space shared by every attention layer's pool (page 0
  is the reserved scratch page), plus the geometric prefill-bucket grid
  (:func:`default_buckets`/:func:`bucket_for`).
* ``engine.py``    -- :class:`ServingEngine`: owns ONE compiled
  ``CiMProgram`` (or digital params), a slot-based KV cache with per-slot
  lengths (``models.lm``: ``init_lm_cache(per_slot=True)`` +
  ``write_cache_slot``/``reset_cache_slot``), one jitted decode stepping
  all slots, optional digital-reference accuracy counters, and the drift
  lifecycle hooks (:meth:`ServingEngine.age_to`, :class:`DriftPolicy`,
  refresh) -- so a long-running server ages the paper's programmed chip in
  place while it serves, with zero programming events asserted. A serving
  run is an :class:`EngineRun` stepping object (admit / decode / finish),
  so one engine can drive itself to completion (:meth:`ServingEngine.run`)
  or be interleaved with siblings by the fleet router.
* ``fleet.py``     -- :class:`FleetRouter`: N engines over N independent
  chip draws (or artifact replicas) behind one service. Least-loaded
  SLO-aware dispatch, per-chip drift clocks, and *staggered refresh*: a
  chip whose window agreement degrades is drained (in-flight requests
  migrate to siblings as bit-identical continuations), reprogrammed via
  ``steps.refresh_program``, and rejoined with a reset age -- with at most
  ``FleetConfig.max_refreshing`` chips down at once and fleet-wide
  request conservation + programming-event accounting enforced
  (:class:`FleetReport`). ``FleetRouter.run`` is a thin wrapper over the
  async front end's deterministic driver.
* ``async_fleet.py`` -- :class:`AsyncFleetRouter`, the concurrent front
  end over the same fleet. Each chip's :class:`EngineRun` is driven by
  its own worker thread (jitted decode steps release the GIL inside XLA,
  so per-chip decode overlaps in wall clock) under an actor discipline:
  only the owning worker mutates a run; the coordinator -- dispatch,
  health windows, staggered refresh, conservation -- talks to owners via
  command queues and an event queue (statically linted as RL006).
  Arrivals flow through a bounded :class:`AdmissionQueue`
  (:class:`~repro.serving.config.AsyncConfig` ``queue_cap`` +
  block/shed policy -> :class:`QueueFull`), tokens stream per request
  via ``submit_stream -> TokenStream``, and ``deterministic=True``
  drives the identical worker code single-threaded under a virtual
  clock for bit-reproducible chaos tests and benchmarks.

  With ``paged=True`` the slot rectangles become a block/paged KV cache
  (``models.attention.PagedKVCache``): resident memory is the page pool,
  not ``n_slots * s_max``, so ``s_max`` turns into a *virtual* per-slot
  capacity and long-prompt traffic serves at flat memory. Admission
  right-pads prompts to a geometric bucket grid and prefills same-bucket
  requests together, bounding jit prefill traces by the bucket count
  (``ServeReport.n_prefill_traces``) instead of the number of distinct
  prompt lengths.

Continuous batching here is *semantically inert*: slots are independent
(admission prefills a request alone; decode advances each slot at its own
cache position), so per-request generations are bit-identical to serving
the request alone on a fresh engine -- only throughput changes. The
``benchmarks/serving_bench.py`` rows quantify it. Paged serving preserves
the same invariant off the TPU: the paged decode view gathers exactly the
rectangle a slot cache would hold, and right-padded prefill is bitwise
inert because the chunked-attention kv reduction is shape-stable -- so
generations stay bit-identical to the rectangular engine on the same
frozen chip draw. On a TPU the paged decode step reads the pages through
the Pallas paged-attention kernel instead (bf16 rounding apart from the
view, see ``models.attention.PagedKVCache``).
One exception: MoE capacity routing pools tokens across the decode batch
(keep/drop competes for expert capacity), so for the moe family
co-scheduled requests can route differently than solo ones -- serve.py
warns when a trace targets an MoE arch (paged prefill therefore drops to
one request per call for MoE periods).
"""

from repro.serving.async_fleet import (  # noqa: F401
    AdmissionQueue,
    AsyncFleetRouter,
    QueueFull,
    TokenStream,
)
from repro.serving.config import (  # noqa: F401
    AsyncConfig,
    FleetConfig,
    ServingConfig,
)
from repro.serving.engine import (  # noqa: F401
    DriftPolicy,
    EngineRun,
    ServeReport,
    ServingEngine,
)
from repro.serving.fleet import (  # noqa: F401
    FleetRecord,
    FleetReport,
    FleetRouter,
)
from repro.serving.paging import (  # noqa: F401
    PageAllocator,
    bucket_for,
    default_buckets,
)
from repro.serving.requests import (  # noqa: F401
    Request,
    RequestRecord,
    poisson_trace,
)
from repro.serving.scheduler import (  # noqa: F401
    BucketedScheduler,
    ContinuousScheduler,
    StaticBatchScheduler,
)
