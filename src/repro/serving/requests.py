"""Request-level serving primitives: requests, completion records, traces.

A :class:`Request` is what a client submits: a variable-length prompt, a
generation budget, an optional EOS token, and an arrival time (seconds
relative to the start of the serving run -- 0.0 means "already queued").
The engine fills in a :class:`RequestRecord` when the request retires:
the generated tokens plus the admission/retirement bookkeeping the
scheduler invariants and the latency metrics are computed from.

:func:`poisson_trace` builds the benchmark workload: ``n`` requests with
prompt lengths drawn from a small bucket set (each distinct prompt length
costs one prefill trace -- buckets keep the compile count bounded),
per-request generation budgets uniform in ``new_tokens``, and optional
Poisson arrivals at ``rate`` requests/second (``rate=None``: a saturated
queue, everything arrives at t=0 -- the closed-loop throughput setup).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class Request:
    """One generation request.

    ``prompt`` is a 1-D int token array (any length >= 1). ``features``
    optionally carries non-token prefill inputs for the frontend families
    (``frames`` for audio, ``patches`` for VLM), each with a leading
    batch=1 axis; decode is always token-fed.

    ``first_token_t`` is set only on migration continuations: the time the
    request's FIRST chip emitted its first token. A continuation's prompt
    embeds the tokens already generated elsewhere, so the destination's
    own admission time is not the request's time-to-first-token -- the
    retiring engine records ``first_token_t`` (when set) as the record's
    ``admit_t`` so ``ttft_s`` spans every chip the request touched.
    """

    rid: int
    prompt: np.ndarray
    max_new_tokens: int
    eos_id: Optional[int] = None
    arrival_t: float = 0.0
    features: Optional[dict] = None
    first_token_t: Optional[float] = None

    def __post_init__(self):
        object.__setattr__(
            self, "prompt", np.asarray(self.prompt, np.int32).reshape(-1)
        )
        if self.prompt.size < 1:
            raise ValueError(f"request {self.rid}: empty prompt")
        if self.max_new_tokens < 1:
            raise ValueError(
                f"request {self.rid}: max_new_tokens must be >= 1"
            )


@dataclasses.dataclass
class RequestRecord:
    """What the engine hands back when a request retires."""

    rid: int
    slot: int
    tokens: np.ndarray  # generated token ids, first token from prefill
    n_prompt: int
    admit_step: int  # engine decode-step index at admission
    finish_step: int  # engine decode-step index at retirement
    arrival_t: float
    admit_t: float  # seconds since run start
    finish_t: float
    finished_by: str  # "eos" | "max_tokens"
    #: (n_new, vocab) f32 logits of every emitted position, row i the one
    #: ``tokens[i]`` was taken from (``ServingConfig.record_logits``)
    logits: Optional[np.ndarray] = None

    @property
    def latency_s(self) -> float:
        """Queueing + service time: arrival to retirement."""
        return self.finish_t - self.arrival_t

    @property
    def ttft_s(self) -> float:
        """Time to first token: arrival to end of the admitting prefill
        (the prefill's greedy token is the request's first output)."""
        return self.admit_t - self.arrival_t

    @property
    def n_new(self) -> int:
        return int(self.tokens.size)


def poisson_trace(
    key,
    n: int,
    *,
    vocab: int,
    rate: Optional[float] = None,
    prompt_lens: tuple[int, ...] = (8, 16, 24, 32),
    new_tokens: tuple[int, int] = (8, 128),
    eos_id: Optional[int] = None,
) -> list[Request]:
    """Synthetic variable-length request trace with Poisson arrivals.

    ``rate=None`` (or <= 0) queues every request at t=0. Prompt token ids
    are uniform over the vocabulary; prompt lengths are drawn from the
    ``prompt_lens`` buckets; generation budgets are uniform ints in the
    inclusive ``new_tokens`` range.
    """
    k_len, k_tok, k_new, k_arr = jax.random.split(key, 4)
    lens = np.asarray(
        jax.random.choice(k_len, jnp.asarray(prompt_lens), shape=(n,))
    )
    budgets = np.asarray(
        jax.random.randint(k_new, (n,), new_tokens[0], new_tokens[1] + 1)
    )
    if rate and rate > 0:
        gaps = np.asarray(
            jax.random.exponential(k_arr, (n,), jnp.float32)
        ) / float(rate)
        arrivals = np.cumsum(gaps)
        arrivals[0] = 0.0  # the first request starts the clock
    else:
        arrivals = np.zeros(n)
    out = []
    for i in range(n):
        toks = np.asarray(
            jax.random.randint(
                jax.random.fold_in(k_tok, i), (int(lens[i]),), 0, vocab
            )
        )
        out.append(
            Request(
                rid=i,
                prompt=toks,
                max_new_tokens=int(budgets[i]),
                eos_id=eos_id,
                arrival_t=float(arrivals[i]),
            )
        )
    return out
