"""The per-layer metrics that read the program's recorder (``repro.obs``),
on synthetic recorder contents (CPU; no TPU is touched).

Run: ``python -m pytest -q benchmarks/chip/tests`` from the repo root.
"""

from __future__ import annotations

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pathlib  # noqa: E402
import sys  # noqa: E402
from types import SimpleNamespace  # noqa: E402

import pytest  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import repro  # noqa: E402
from repro import obs  # noqa: E402

from benchmarks.chip import harness, run  # noqa: E402

READERS = ("queue_wait_p90_ms.lm", "prefill_useful_share.lm",
           "decode_host_ms.lm", "gc_pause_share.lm", "setup_compile_s")
#: synthetic times are B + seconds: B lies before any real clock reading,
#: so the records the recorder's own hooks make meanwhile fall outside
B = -1000.0
#: the harness's window spans of the synthetic run: B + 100 s to B + 101 s
WINDOW = [harness.Span("admit", B + 100.0, B + 100.2, {}),
          harness.Span("decode_step", B + 100.2, B + 101.0, {})]


def read(name: str, spans=WINDOW):
    return run.read_metric(ROOT, name, SimpleNamespace(spans=spans))


def _clock(*ts):
    """A clock that reads ``ts`` in turn."""
    it = iter(ts)
    return lambda: next(it)


def _span(name, t0, t1, **kw):
    with obs.span(name, _clock(t1), t0=t0, **kw):
        pass


@pytest.fixture(autouse=True)
def clean():
    obs.reset()
    yield
    obs.reset()


def _fill_run():
    """One synthetic serving run: set-up before B + 100 s, the window after."""
    obs._write(obs.COMPILE, "jit(prefill)", B + 10.0, B + 11.5, -1, -1, -1, 0, 0)
    obs._write(obs.COMPILE, "jit(decode)", B + 20.0, B + 22.5, -1, -1, -1, 0, 0)
    obs._write(obs.COMPILE, "jit(late)", B + 100.5, B + 100.6, -1, -1, -1, 0, 0)
    # queue waits: one before the window, ten in it
    obs.sample("engine.queue_wait", 9.0, B + 50.0, 99)
    for i in range(10):
        obs.sample("engine.queue_wait", 0.01 * (i + 1), B + 100.0 + 0.05 * i, i)
    # prefills: one in set-up, two in the window
    for t0, real, computed in ((B + 60.0, 1, 1000), (B + 100.05, 20, 32),
                               (B + 100.3, 10, 32)):
        with obs.span("engine.prefill", _clock(t0 + 0.01), t0=t0):
            obs.count("engine.prefill_tokens_real", real)
            obs.count("engine.prefill_tokens_computed", computed)
    # decode steps: 10 ms with a 7 ms sync, 20 ms with a 12 ms sync, and
    # one after the window
    for t0, dur, sync in ((B + 100.4, 0.010, 0.007),
                          (B + 100.5, 0.020, 0.012), (B + 102.0, 0.5, 0.0)):
        with obs.span("engine.decode", _clock(t0 + dur), t0=t0):
            _span("engine.decode_sync", t0 + 0.001, t0 + 0.001 + sync)
    # collections: 2 ms and 3 ms in the window, 1 s in set-up
    for t0, dur in ((B + 30.0, 1.0), (B + 100.6, 0.002),
                    (B + 100.9, 0.003)):
        obs._write(obs.GC, "gc", t0, t0 + dur, -1, -1, -1, 0, 0)


def test_each_reader_reads_its_interval():
    _fill_run()
    assert read("queue_wait_p90_ms.lm") == pytest.approx(90.0)
    assert read("prefill_useful_share.lm") == pytest.approx(100.0 * 30 / 64)
    assert read("decode_host_ms.lm") == pytest.approx((3.0 + 8.0) / 2)
    assert read("gc_pause_share.lm") == pytest.approx(100.0 * 0.005 / 1.0)
    assert read("setup_compile_s") == pytest.approx(1.5 + 2.5)


def test_queue_wait_stops_at_the_first_harness_stall():
    _fill_run()
    # the harness stops for 2 s between two of its spans (the profiler
    # stopping): the requests admitted after it queued behind the harness
    spans = [harness.Span("admit", B + 100.0, B + 100.5, {}),
             harness.Span("decode_step", B + 102.5, B + 103.0, {})]
    obs.sample("engine.queue_wait", 2.1, B + 102.55, 50)
    obs.sample("engine.queue_wait", 0.5, B + 102.9, 51)
    assert read("queue_wait_p90_ms.lm", spans) == pytest.approx(90.0)
    joined = [harness.Span("admit", B + 100.0, B + 102.5, {}),
              harness.Span("decode_step", B + 102.5, B + 103.0, {})]
    # without the gap, the two later samples count: 12 samples, rank 11
    assert read("queue_wait_p90_ms.lm", joined) == pytest.approx(500.0)


@pytest.mark.parametrize("name", READERS)
def test_a_dropped_ring_reads_none(name):
    _fill_run()
    assert read(name) is not None
    for i in range(obs.RING):
        _span("t.fill", B + 100.0, B + 100.0)
    assert obs.dropped() > 0
    assert read(name) is None


@pytest.mark.parametrize("name", READERS)
def test_nothing_recorded_reads_none(name):
    assert read(name) is None
    _fill_run()
    assert read(name, spans=[]) is None


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_the_recorder_reads_none(name, monkeypatch):
    _fill_run()
    monkeypatch.delattr(repro, "obs")
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert read(name) is None
