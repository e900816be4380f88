"""What every driver shares: the window, spans, compile count and trace.

A driver builds its system, warms every shape its traffic uses, then
opens the window with :meth:`Context.open_window`, steps its loop (calling
:meth:`Context.tick` once per step and wrapping each call into the
system in :meth:`Context.span`), and closes it with
:meth:`Context.close_window`. Set-up is everything before the window
opens, counted from process start.

With tracing on, the profiler records a sub-window in the middle of the
window (the trace is large and reading it slows the host); spans then
also go into the trace as ``jax.profiler.TraceAnnotation`` s, so the
reduction can name each idle gap by the span that was open.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import shutil
import sys
import time
from typing import Any, Optional

from benchmarks.chip import trace_reduce

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: the traced sub-window: it opens this share of the window in, and lasts
#: at most TRACE_MAX_S and at most this share of the window
TRACE_AT, TRACE_SHARE, TRACE_MAX_S = 0.25, 0.5, 8.0


@dataclasses.dataclass
class Span:
    name: str
    t0: float
    t1: float
    attrs: dict


class CompileCounter:
    """Counts XLA compiles (a persistent-cache load counts too) while
    armed; one listener per process."""

    _installed: Optional["CompileCounter"] = None

    def __init__(self):
        self.armed = False
        self.count = 0

    @classmethod
    def get(cls) -> "CompileCounter":
        if cls._installed is None:
            import jax

            cls._installed = cls()
            jax.monitoring.register_event_duration_secs_listener(
                cls._installed._on_event
            )
        return cls._installed

    def _on_event(self, event: str, _duration: float, **_kw) -> None:
        if self.armed and event == COMPILE_EVENT:
            self.count += 1


@dataclasses.dataclass
class Context:
    """One run of one cell, as the driver sees it."""

    root: Any
    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    t_process: float
    #: settings that replace the configuration's (the control's lower
    #: precision); empty in a benchmark run
    overrides: dict = dataclasses.field(default_factory=dict)
    spans: list = dataclasses.field(default_factory=list)
    t_open: Optional[float] = None
    t_close: Optional[float] = None
    setup_s: Optional[float] = None
    compiles_in_window: int = 0
    _tracing: bool = False
    _trace_t0: Optional[float] = None
    _trace_done: bool = False
    _window_ann: Any = None

    def log(self, msg: str) -> None:
        print(msg, file=sys.stderr, flush=True)

    def setting(self, key: str):
        return self.overrides.get(key, self.config[key])

    def mark(self, phase: str) -> None:
        """Log the end of a set-up phase, in seconds since process start."""
        self.log(f"setup: {phase} at {time.perf_counter() - self.t_process:.3f} s")

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        if self._tracing:
            import jax

            ann = jax.profiler.TraceAnnotation(name)
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield attrs
        finally:
            t1 = time.perf_counter()
            if self._tracing:
                ann.__exit__(None, None, None)
            self.spans.append(Span(name, t0, t1, attrs))

    # -- the window --------------------------------------------------------

    def open_window(self, t_open: float) -> None:
        """The window opens at ``t_open`` (host clock); set-up ends there."""
        self.t_open = t_open
        self.t_close = t_open + self.seconds
        self.setup_s = t_open - self.t_process
        counter = CompileCounter.get()
        counter.count, counter.armed = 0, True

    def tick(self, now: float) -> None:
        """Start or stop the traced sub-window when it is due."""
        if not self.trace or self.t_open is None or self._trace_done:
            return
        start = self.t_open + TRACE_AT * self.seconds
        length = min(TRACE_MAX_S, TRACE_SHARE * self.seconds)
        if not self._tracing and now >= start:
            self._start_trace()
        elif self._tracing and now >= self._trace_t0 + length:
            self._stop_trace()

    def close_window(self) -> None:
        if self._tracing:
            self._stop_trace()
        counter = CompileCounter.get()
        counter.armed = False
        self.compiles_in_window = counter.count

    def window_spans(self, name: Optional[str] = None) -> list:
        return [s for s in self.spans
                if self.t_open <= s.t0 < self.t_close
                and (name is None or s.name == name)]

    # -- tracing -----------------------------------------------------------

    def trace_dir(self) -> str:
        return os.path.join(str(self.root), ".bench_trace")

    def _start_trace(self) -> None:
        import jax

        shutil.rmtree(self.trace_dir(), ignore_errors=True)
        jax.profiler.start_trace(self.trace_dir())
        self._window_ann = jax.profiler.TraceAnnotation(trace_reduce.WINDOW)
        self._window_ann.__enter__()
        self._tracing = True
        self._trace_t0 = time.perf_counter()

    def _stop_trace(self) -> None:
        import jax

        self._window_ann.__exit__(None, None, None)
        self._tracing = False
        self._trace_done = True
        jax.profiler.stop_trace()

    def read_trace(self) -> Optional[dict]:
        """Reduce the recorded trace, then delete it from disk."""
        if not self._trace_done:
            return None
        names = {s.name for s in self.spans}
        try:
            record = trace_reduce.load(self.trace_dir(), names)
        finally:
            shutil.rmtree(self.trace_dir(), ignore_errors=True)
        return trace_reduce.reduce(record)


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_chips(n: int) -> dict:
    """The accelerator the cell asks for, or exit non-zero before any work."""
    info = device_info()
    if info["platform"] != "tpu":
        raise SystemExit(
            f"benchmark: needs a TPU, JAX found {info['platform']!r}")
    if info["count"] < n:
        raise SystemExit(
            f"benchmark: the cell needs {n} chips, JAX found {info['count']}")
    return info


def memory_peak_bytes() -> Optional[int]:
    """Peak bytes in use on the fullest chip, where the backend reports it."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None
