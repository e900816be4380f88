"""Seconds of set-up spent compiling programs, a load from the persistent
compilation cache included: the durations of the XLA compile events the
program's recorder timed (``repro.obs``) that start before the first of
the window's spans, summed. None where the program records none, or where
the recorder's ring dropped any record (the oldest go first, so from
set-up)."""


def read(r):
    try:
        from repro import obs
    except ImportError:
        return None
    if not r.spans or obs.dropped():
        return None
    lo = min(s.t0 for s in r.spans)
    compiles = obs.between(float("-inf"), lo, obs.COMPILE)
    return sum(x[3] - x[2] for x in compiles) if compiles else None
