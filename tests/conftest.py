import contextlib
import os

# Tests must see the single real CPU device (the 512-device override is
# strictly for the dry-run); keep XLA quiet and single-threaded.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_enable_x64", False)
# Entry points under test (``serve.main``) point JAX at a persistent
# compilation cache; tests compile afresh, in every run and every worker.
jax.config.update("jax_enable_compilation_cache", False)


# --------------------------------------------------------------- retraces
#
# The program's recorder (repro.obs) counts XLA compilations: the
# backend_compile event fires exactly once per new trace/compile and never on
# a jit cache hit. It times the persistent-cache lookup too, so a program
# read back from that cache counts as well (tests/test_retrace_fixture.py).
from repro import obs  # noqa: E402


@pytest.fixture
def assert_max_retraces():
    """Context manager factory pinning the jit-compile count of a block.

    Counts every XLA compilation (eager ops included -- they compile
    too), so warm the code path first and assert on the *re-run*::

        rep = engine.run(trace)          # warm: traces once per bucket
        with assert_max_retraces(0):
            engine.run(trace)            # same shapes: zero new traces

    This is the dynamic side of lint rule RL003: the linter proves no
    retrace *hazard* is written down, this fixture proves no retrace
    actually *happens*.
    """

    @contextlib.contextmanager
    def _bound(n_max: int):
        before = obs.counters().get("compile.n", 0)
        yield
        n_new = obs.counters().get("compile.n", 0) - before
        assert n_new <= n_max, (
            f"{n_new} new jit compilation(s) in a block that allows "
            f"{n_max} -- a retrace crept into a warmed path (loop-varying "
            "shape or static arg?)"
        )

    return _bound
