"""Share of the prefill work in the window spent on prompt tokens, not on
padding to a bucket or on dummy rows, in %: the program's counts
``engine.prefill_tokens_real`` over ``engine.prefill_tokens_computed``
(``repro.obs``), each timed by the prefill it was made in, between the
first start and the last end of the window's spans. None where the
program records none, or where the recorder's ring dropped records of
that interval."""


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
    real = sum(x[7] for x in obs.between(
        lo, hi, obs.COUNT, "engine.prefill_tokens_real"))
    computed = sum(x[7] for x in obs.between(
        lo, hi, obs.COUNT, "engine.prefill_tokens_computed"))
    return 100.0 * real / computed if computed else None
