"""The one traffic generator: reads a mix's parameters, draws from a seed.

Every seed gets the same work. Lengths are stratified: a run's n requests
take the inverse CDF of the mix's length distribution at the centres of n
equal-probability strata, paired the same way for every seed, so the set
of requests (and so the length tail) is fixed by n alone; the seed only
shuffles their order.
Arrivals are the same: the n inter-arrival gaps are the quantiles of an
exponential at the same stratum centres, scaled to fill the span exactly,
and the seed shuffles their order. Prompt token ids are uniform draws.

A mix file (``traffic/<name>.json``) of kind ``requests`` holds::

    {"kind": "requests", "rate_per_s": R, "lead_in_s": L,
     "prompt": {"median": M, "sigma": S, "min": a, "max": b},
     "output": {"median": M, "sigma": S, "min": a, "max": b},
     "max_total": T}

Lengths are log-normal (``median`` and log-space ``sigma``), clipped to
``[min, max]``; a prompt is cut so that prompt + output <= ``max_total``.
A mix of kind ``batches`` holds ``{"batch": B, "pool": P}``: P host
batches of B standard-normal inputs, made before the window.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass(frozen=True)
class Req:
    """One generated request: due ``arrival_s`` after the run starts."""

    rid: int
    arrival_s: float
    prompt: np.ndarray
    max_new: int
    in_window: bool


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """A host generator for one named stream of a seed (any size of int)."""
    tag = int.from_bytes(stream.encode(), "little")
    return np.random.default_rng([seed & (2**64 - 1), seed >> 64, tag])


def strata(n: int) -> np.ndarray:
    """Centres of n equal-probability strata of (0, 1)."""
    return (np.arange(n) + 0.5) / n


def lognormal_lengths(dist: dict, n: int) -> np.ndarray:
    """The n stratified lengths of a clipped log-normal, ascending."""
    mu = math.log(dist["median"])
    z = np.array([NormalDist().inv_cdf(u) for u in strata(n)])
    x = np.exp(mu + dist["sigma"] * z)
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def arrival_times(n: int, start: float, span: float,
                  rng: np.random.Generator) -> np.ndarray:
    """n arrivals in [start, start + span): stratified exponential gaps,
    scaled to the span, in an order the generator shuffles."""
    if n == 0:
        return np.zeros(0)
    gaps = -np.log1p(-strata(n))
    gaps = gaps / gaps.sum() * span
    gaps = rng.permutation(gaps)
    # the first request is due as the span opens; the last gap runs to
    # the span's end
    return start + np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


def _phase(mix: dict, n: int, start: float, span: float, vocab: int,
           rng: np.random.Generator, rid0: int, in_window: bool) -> list[Req]:
    # prompt and output lengths are paired the same way for every seed,
    # so every seed serves the same set of requests; the seed orders them
    outputs = lognormal_lengths(mix["output"], n)
    outputs = outputs[rng_for(0, "pairing").permutation(n)]
    prompts = np.minimum(lognormal_lengths(mix["prompt"], n),
                         mix["max_total"] - outputs)
    order = rng.permutation(n)
    prompts, outputs = prompts[order], outputs[order]
    times = arrival_times(n, start, span, rng)
    return [
        Req(rid0 + i, float(times[i]),
            rng.integers(0, vocab, size=int(prompts[i]), dtype=np.int32),
            int(outputs[i]), in_window)
        for i in range(n)
    ]


def requests(mix: dict, seed: int, seconds: float, vocab: int) -> list[Req]:
    """Lead-in then window requests of a ``requests`` mix, by arrival.

    The lead-in (``lead_in_s`` at the same rate) fills the decode slots
    before the window opens at ``lead_in_s``; its requests are served but
    not measured.
    """
    rng = rng_for(seed, "traffic")
    rate, lead = float(mix["rate_per_s"]), float(mix["lead_in_s"])
    n_lead = int(round(rate * lead))
    n_win = int(round(rate * seconds))
    out = _phase(mix, n_lead, 0.0, lead, vocab, rng, 0, False)
    out += _phase(mix, n_win, lead, seconds, vocab, rng, n_lead, True)
    return sorted(out, key=lambda r: r.arrival_s)


def batches(mix: dict, seed: int, shape: tuple) -> list[np.ndarray]:
    """The pool of host input batches of a ``batches`` mix."""
    rng = rng_for(seed, "batches")
    return [
        rng.standard_normal((mix["batch"],) + tuple(shape), np.float32)
        for _ in range(int(mix["pool"]))
    ]
