"""Share of the gathered (n_slots x s_max) KV view that the decode steps in
the window read, in %: the program's counts ``engine.kv_tokens_read`` over
``engine.kv_tokens_view`` (``repro.obs``), each timed by the decode step
it was made in, between the first start and the last end of the window's
spans. 100 where the step attends over the whole view; on the TPU's
paged-attention kernel, the live slots' lengths rounded up to its compute
block. None where the program records none, or where the recorder's ring
dropped records of that interval."""


def read(r):
    try:
        from repro import obs
    except ImportError:
        return None
    if not r.spans:
        return None
    lo, hi = min(s.t0 for s in r.spans), max(s.t1 for s in r.spans)
    if obs.lost_until() >= lo:
        return None
    got = sum(x[7] for x in obs.between(
        lo, hi, obs.COUNT, "engine.kv_tokens_read"))
    view = sum(x[7] for x in obs.between(
        lo, hi, obs.COUNT, "engine.kv_tokens_view"))
    return 100.0 * got / view if view else None
