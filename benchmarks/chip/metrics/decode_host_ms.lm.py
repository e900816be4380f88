"""Host time of one decode step outside its wait for the device, in ms:
the mean over the program's ``engine.decode`` spans (``repro.obs``) that
start between the first start and the last end of the window's spans of
the span's time less its ``engine.decode_sync`` child's (page appends,
the launch, token bookkeeping and retirement). None where the program
records none, or where the recorder's ring dropped records of that
interval."""


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
    sync = {x[5]: x[3] - x[2] for x in obs.between(
        lo, float("inf"), obs.SPAN, "engine.decode_sync")}
    host = [x[3] - x[2] - sync[x[4]]
            for x in obs.between(lo, hi, obs.SPAN, "engine.decode")
            if x[4] in sync]
    return 1e3 * sum(host) / len(host) if host else None
