"""Admission's share of the serving loop: time in ``admit`` spans (which
hold the prefills) over time in ``admit`` and ``decode_step`` spans, in
the window, in %."""


def read(r):
    admit, decode = r.span_seconds("admit"), r.span_seconds("decode_step")
    if admit + decode <= 0:
        return None
    return 100.0 * admit / (admit + decode)
