"""Share of the traced window in which no operation ran on the device, in
%. The reader of every ``idle_share.<part>`` metric (``idle_share.lm``,
``idle_share.kws``): one quantity, split by the end-to-end metric it moves."""


def read(r):
    return 100.0 * (1.0 - r.trace["busy_s"] / r.trace["window_s"])
