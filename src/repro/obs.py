"""The program's recorder: spans, counters and samples in one bounded ring.

Always on, process-wide, in memory. A record is a tuple of numbers and
interned strings::

    (kind, name, t0, t1, id, parent, key, a, b)

``kind`` is one of :data:`SPAN`, :data:`SAMPLE`, :data:`COUNT`,
:data:`COMPILE`, :data:`GC`; ``id`` numbers a span (-1 for the other
kinds); ``parent`` is the id of the innermost span open on the same thread
when the record was made (-1 for none); ``key`` is a request id (samples)
or a serving run's id (the engine's spans), else -1; ``a`` and ``b`` are the
record's two numbers:

==========  ==========================  =======================  ===========
kind        t0, t1                      a                        b
==========  ==========================  =======================  ===========
SPAN        start, end (caller's clock)  caller's number          caller's
SAMPLE      the sample's time (twice)    the value                0
COUNT       the open span's start        the increment            0
COMPILE     start, end (clock.SYSTEM)    0                        0
GC          start, end (clock.SYSTEM)    generation               collected
==========  ==========================  =======================  ===========

A COMPILE record's name is the compiled function's; every other record's
is the name it was made with.

* :func:`span` times a block on the caller's clock (a serving run passes
  its ``now_fn``, so a :class:`~repro.clock.VirtualClock` run records exact
  spans). While a profiler session is active each span is also a
  ``jax.profiler.TraceAnnotation``, so it lies on the device trace's clock.
* :func:`count` adds to a process-wide total and records the increment,
  timed by the span it is made in.
* :func:`sample` records one value of a per-request quantity.
* Two hooks, installed when this module is first imported (``repro``
  imports it): a ``jax.monitoring`` listener on XLA's backend-compile event
  (a persistent-cache load fires it too) and a ``gc.callbacks`` hook. Both
  time on :data:`repro.clock.SYSTEM`. On Linux ``time.monotonic`` and
  ``time.perf_counter`` read the same clock (``CLOCK_MONOTONIC``), so
  these records compare with spans on either.

Records are kept in one ring of :data:`RING` entries; the oldest are
overwritten when the ring is full, and :func:`dropped` counts them. A span
allocates its record's tuple and nothing else: no dict, and its frame
object is reused per thread and nesting depth. A tuple of numbers and
strings leaves the collector's tracking at its first pass, so the recorder
does not feed the full collections it times.
"""

from __future__ import annotations

import gc
import itertools
import sys
import threading

import jax

from repro import clock as clock_lib

SPAN, SAMPLE, COUNT, COMPILE, GC = 0, 1, 2, 3, 4
#: records the ring holds (a serving run of ~2,000 decode steps makes
#: ~15,000)
RING = 1 << 16
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_MASK = RING - 1
_now = clock_lib.SYSTEM.now
_TraceMe = jax.profiler.TraceAnnotation
_is_tracing = _TraceMe.is_enabled

_local = threading.local()
_ids = itertools.count()


#: guards the ring and the counters: the async fleet steps one run per
#: thread. Re-entrant, as a collection can start while a thread holds it.
_lock = threading.RLock()
_ring: list = [None] * RING
_n = 0  # records written
_lost_t1 = float("-inf")  # latest end time of an overwritten record
_counters: dict = {}


def _write(kind, name, t0, t1, id_, parent, key, a, b) -> None:
    global _n, _lost_t1
    with _lock:
        i = _n
        _n = i + 1
        j = i & _MASK
        if i >= RING and _ring[j][3] > _lost_t1:
            _lost_t1 = _ring[j][3]
        _ring[j] = (kind, name, t0, t1, id_, parent, key, a, b)


def _add(name: str, n) -> None:
    with _lock:
        _counters[name] = _counters.get(name, 0) + n


def _stack() -> list:
    try:
        return _local.stack
    except AttributeError:
        _local.stack, _local.pool = [], []
        return _local.stack


def _open_id() -> int:
    stack = _stack()
    return stack[-1].id if stack else -1


# -- spans ------------------------------------------------------------------


class _Span:
    """One open span. Frames are reused per thread and depth: a frame is
    valid inside its ``with`` block only."""

    __slots__ = ("name", "clock", "key", "a", "b", "t0", "id", "parent", "ann")

    def __enter__(self) -> "_Span":
        stack = _local.stack
        self.parent = stack[-1].id if stack else -1
        self.id = next(_ids)
        stack.append(self)
        if _is_tracing():
            self.ann = _TraceMe(self.name)
            self.ann.__enter__()
        if self.t0 is None:
            self.t0 = self.clock()
        return self

    def __exit__(self, *_exc) -> bool:
        t1 = self.clock()
        if self.ann is not None:
            self.ann.__exit__(None, None, None)
            self.ann = None
        _local.stack.pop()
        _write(SPAN, self.name, self.t0, t1, self.id, self.parent,
               self.key, self.a, self.b)
        return False


def span(name: str, clock, key: int = -1, a=0, b=0, t0=None) -> _Span:
    """A block timed on ``clock`` (a function returning seconds), as a
    context manager. ``a`` and ``b`` may be set on the frame inside the
    block; ``t0`` backdates the start (the caller read the clock already).
    Nested spans record their parent; the name must be a string constant
    (it is kept, not copied)."""
    stack = _stack()
    pool = _local.pool
    depth = len(stack)
    if depth == len(pool):
        pool.append(_Span())
    sp = pool[depth]
    sp.name, sp.clock, sp.key, sp.a, sp.b, sp.t0 = name, clock, key, a, b, t0
    sp.ann = None
    return sp


# -- counters and samples ---------------------------------------------------


def count(name: str, n=1) -> None:
    """Add ``n`` to the counter ``name``; the increment is recorded with
    the start time of the innermost open span (NaN outside any)."""
    _add(name, n)
    stack = _stack()
    top = stack[-1] if stack else None
    _write(COUNT, name, top.t0 if top else float("nan"),
           top.t0 if top else float("nan"), -1, top.id if top else -1,
           -1, n, 0)


def sample(name: str, value, t: float, rid: int = -1) -> None:
    """Record one value of a per-request quantity, at time ``t``."""
    _write(SAMPLE, name, t, t, -1, _open_id(), rid, value, 0)


# -- process-wide hooks -----------------------------------------------------


def _on_compile(event: str, duration: float, **kw) -> None:
    if event != COMPILE_EVENT:
        return
    t1 = _now()
    name = sys.intern(str(kw.get("fun_name", "?")))
    _write(COMPILE, name, t1 - duration, t1, -1, _open_id(), -1, 0, 0)
    _add("compile.n", 1)
    _add("compile.s", duration)


_GC_KEYS = tuple((f"gc.gen{g}.n", f"gc.gen{g}.s") for g in range(3))
_gc_t0 = 0.0
_gc_ann = None


def _on_gc(phase: str, info: dict) -> None:
    global _gc_t0, _gc_ann
    if phase == "start":
        if _is_tracing():
            _gc_ann = _TraceMe("gc")
            _gc_ann.__enter__()
        _gc_t0 = _now()
        return
    t1 = _now()
    if _gc_ann is not None:
        _gc_ann.__exit__(None, None, None)
        _gc_ann = None
    gen = info["generation"]
    _write(GC, "gc", _gc_t0, t1, -1, _open_id(), -1, gen, info["collected"])
    k_n, k_s = _GC_KEYS[gen]
    _add(k_n, 1)
    _add(k_s, t1 - _gc_t0)


jax.monitoring.register_event_duration_secs_listener(_on_compile)
gc.callbacks.append(_on_gc)


# -- reading ----------------------------------------------------------------


def cursor() -> int:
    """Records written so far: pass it to :func:`records` later to read
    what came after."""
    return _n


def records(since: int = 0) -> list:
    """Records written at or after ``since`` that the ring still holds,
    oldest first."""
    with _lock:
        n = _n
        return [_ring[i & _MASK] for i in range(max(since, n - RING), n)]


def between(lo: float, hi: float, kind: int, name=None) -> list:
    """Records of ``kind`` (and ``name``) that start in ``[lo, hi]``."""
    return [r for r in records() if r[0] == kind and lo <= r[2] <= hi
            and (name is None or r[1] == name)]


def last(name: str):
    """The newest span named ``name``, or None."""
    for r in reversed(records()):
        if r[0] == SPAN and r[1] == name:
            return r
    return None


def dropped() -> int:
    """Records the ring has overwritten."""
    return max(_n - RING, 0)


def lost_until() -> float:
    """Latest end time of an overwritten record (-inf if none): what the
    ring holds from any later time on is complete."""
    return _lost_t1


def counters() -> dict:
    """Process-wide totals: :func:`count`'s counters, ``compile.n`` and
    ``compile.s``, and ``gc.gen<g>.n`` and ``gc.gen<g>.s`` per generation."""
    with _lock:
        return dict(_counters)


def reset() -> None:
    """Forget every record and counter (a test's clean slate)."""
    global _n, _lost_t1
    with _lock:
        _ring[:] = [None] * RING
        _n, _lost_t1 = 0, float("-inf")
        _counters.clear()
