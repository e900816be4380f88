"""Share of the window the host spent in Python's garbage collector, in
%: the collections the program's recorder timed (``repro.obs``, every
generation) that start between the first start and the last end of the
window's spans, over that interval. None where the program records no
collections, or where the recorder's ring dropped records of that
interval."""


def read(r):
    try:
        from repro import obs
    except ImportError:
        return None
    if not r.spans:
        return None
    lo, hi = min(s.t0 for s in r.spans), max(s.t1 for s in r.spans)
    if obs.lost_until() >= lo or hi <= lo:
        return None
    pauses = [x[3] - x[2] for x in obs.between(lo, hi, obs.GC)]
    return 100.0 * sum(pauses) / (hi - lo) if pauses else None
