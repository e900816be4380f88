"""99th percentile of the gaps between tokens in the window, in ms (the
steps that wait behind a prefill). Not steady enough for an end-to-end
bound: in conv it falls on one of two plateaus, a step behind a 1k or a
2k prefill, from run to run of one seed."""


def read(r):
    return r.counters.get("itl_p99_ms")
