"""Rows routed to the held experts per token and MoE layer: the program's
counts ``moe.rows_held`` over ``moe.tokens`` (the real tokens each
dropless MoE layer took, summed over layers: prompt positions and live
decode slots, not padding or idle slots), in the prefills and decode steps that
start between the first start and the last end of the window's spans.
With balanced routing it reads ``top_k x held / n_experts`` (4 x 8/32 =
1.0 on this chip); above that the held experts take more than their share
of the traffic. None where the program records none, or where the
recorder's ring dropped records of that interval."""


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
    held = sum(x[7] for x in obs.between(lo, hi, obs.COUNT, "moe.rows_held"))
    rows = sum(x[7] for x in obs.between(lo, hi, obs.COUNT, "moe.tokens"))
    return held / rows if rows else None
