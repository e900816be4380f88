"""The CNN's share of the bf16 peak: conv and FC FLOPs of the inferences
completed in the window over the window's time, in %."""


def read(r):
    work = r.counters.get("flops")
    t = r.counters.get("window_s")
    if not work or not t:
        return None
    return 100.0 * work / t / r.peak["bf16_flops_per_s"]
