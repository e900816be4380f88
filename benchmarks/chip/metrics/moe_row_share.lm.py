"""Share of the rows the held experts' MVMs ran that a token was routed
to, in %: the program's counts ``moe.rows_held`` (token-expert pairs routed
to an expert this chip holds, over real tokens only: no prefill padding,
no idle decode slot) over ``moe.rows_computed`` (rows the expert MVMs ran,
padding and idle slots included), summed over the prefills and decode
steps that start between the first start and the last end of the window's
spans. 100 would be a grouped MVM that runs only routed real rows; the
dropless layer that runs every held expert over every row reads at most
``top_k / n_experts`` (4/32 of LFM2's: 12.5 %), and less for every pad
row and idle slot. None where the program records none, or where the
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
    ran = sum(x[7] for x in obs.between(lo, hi, obs.COUNT, "moe.rows_computed"))
    return 100.0 * held / ran if ran else None
