"""Metric arithmetic: serving tails from token times, spreads of runs.

All times are seconds on the harness's host clock (``time.perf_counter``).
"""

from __future__ import annotations

import math
import statistics


def percentile(values, q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``q``%
    of the sample at or below it."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of an empty sample")
    k = max(1, math.ceil(q / 100.0 * len(xs)))
    return xs[k - 1]


def serve_metrics(due: dict, tokens: dict, t_open: float, t_close: float,
                  t_stop: float) -> dict:
    """End-to-end serving metrics of one window.

    ``due``: rid -> when the request was due, for every request due in
    the window [t_open, t_close). ``tokens``: rid -> the host times at
    which its tokens arrived, in order (any request, window or not).
    ``t_stop``: when the run stopped waiting; a window request with no
    first token by then is a miss: it fails, and its time to first token
    counts as the whole wait, ``t_stop - due``.

    * ``ttft``: every window request's time to first token, from due;
    * ``gaps``: every gap between consecutive tokens of one request whose
      later token arrived inside the window (stalls land here);
    * ``ttft_p90_ms``, ``itl_p99_ms``; ``tpot_ms`` = the summed gaps over
      their count (all decode time over all tokens after the first).
    """
    ttft, failed = [], 0
    for rid, t_due in due.items():
        ts = tokens.get(rid)
        if ts:
            ttft.append(ts[0] - t_due)
        else:
            failed += 1
            ttft.append(t_stop - t_due)
    gaps = []
    for ts in tokens.values():
        for a, b in zip(ts, ts[1:]):
            if t_open <= b < t_close:
                gaps.append(b - a)
    out = {
        "attempted": len(due),
        "failed": failed,
        "n_gaps": len(gaps),
        "ttft_p90_ms": percentile(ttft, 90) * 1e3 if ttft else None,
        "itl_p99_ms": percentile(gaps, 99) * 1e3 if gaps else None,
        "tpot_ms": sum(gaps) / len(gaps) * 1e3 if gaps else None,
    }
    return out


def spread(values) -> float:
    """Inter-quartile distance over the median (Python's quartiles)."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
