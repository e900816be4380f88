"""90th percentile (nearest rank) of the time requests admitted in the
window waited in the engine's queue, from arrival to the start of their
admission, in ms: the program's ``engine.queue_wait`` samples
(``repro.obs``) made from the first start of the window's spans up to
the first gap of more than ``HARNESS_GAP_S`` between two of them, or to
the last end where there is none.

In a traced run the harness stops the profiler between two of its spans
and holds the loop for seconds; the requests that queue meanwhile, and
those behind them, wait on the harness, not on the engine. None where
the program records no samples, or where the recorder's ring dropped
records of that interval."""

from benchmarks.chip import stats

HARNESS_GAP_S = 0.1


def read(r):
    try:
        from repro import obs
    except ImportError:
        return None
    if not r.spans:
        return None
    spans = sorted(r.spans, key=lambda s: s.t0)
    lo = spans[0].t0
    if obs.lost_until() >= lo:
        return None
    ends = [a.t1 for a, b in zip(spans, spans[1:]) if b.t0 - a.t1 > HARNESS_GAP_S]
    hi = ends[0] if ends else max(s.t1 for s in spans)
    waits = [x[7] for x in obs.between(lo, hi, obs.SAMPLE, "engine.queue_wait")]
    return 1e3 * stats.percentile(waits, 90) if waits else None
