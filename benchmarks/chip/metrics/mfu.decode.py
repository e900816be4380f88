"""Decode's share of the bf16 peak: the model FLOPs of the tokens decoded
in the window over the time of the ``decode_step`` spans, in %."""


def read(r):
    spans = [s for s in r.spans if s.name == "decode_step"]
    t = sum(s.t1 - s.t0 for s in spans)
    work = sum(s.attrs.get("flops", 0) for s in spans)
    if t <= 0 or work <= 0:
        return None
    return 100.0 * work / t / r.peak["bf16_flops_per_s"]
