"""The per-layer metric ``kv_read_share.lm`` on synthetic recorder contents
(CPU; no TPU is touched).

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

NAME = "kv_read_share.lm"
#: synthetic times lie before any real clock reading (see
#: test_obs_readers.py); the window's spans run from B + 100 s to B + 101 s
B = -1000.0
WINDOW = [harness.Span("admit", B + 100.0, B + 100.2, {}),
          harness.Span("decode_step", B + 100.2, B + 101.0, {})]


def read(spans=WINDOW):
    return run.read_metric(ROOT, NAME, SimpleNamespace(spans=spans))


def _clock(*ts):
    it = iter(ts)
    return lambda: next(it)


@pytest.fixture(autouse=True)
def clean():
    obs.reset()
    yield
    obs.reset()


def _fill_run():
    """Decode steps: one in set-up reading the whole view, two in the
    window reading 300 and 500 of 1000 tokens, one after the window."""
    for t0, got in ((B + 50.0, 1000), (B + 100.3, 300), (B + 100.6, 500),
                    (B + 102.0, 1000)):
        with obs.span("engine.decode", _clock(t0 + 0.01), t0=t0):
            obs.count("engine.kv_tokens_read", got)
            obs.count("engine.kv_tokens_view", 1000)


def test_reads_the_windows_steps():
    _fill_run()
    assert read() == pytest.approx(100.0 * 800 / 2000)


def test_nothing_recorded_reads_none():
    assert read() is None
    _fill_run()
    assert read(spans=[]) is None


def test_a_dropped_ring_reads_none():
    _fill_run()
    for _ in range(obs.RING):
        with obs.span("t.fill", _clock(B + 100.0), t0=B + 100.0):
            pass
    assert obs.dropped() > 0
    assert read() is None


def test_a_program_without_the_recorder_reads_none(monkeypatch):
    _fill_run()
    monkeypatch.delattr(repro, "obs")
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert read() is None
