"""``assert_max_retraces`` counts every compile, from the persistent
compilation cache or not.

Entry points such as ``serve.main`` point JAX at a persistent cache; the
tests turn it off, but a retrace served from it must still count.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro import obs

_seen = {"hits": 0}


def _on_event(key: str, **_kw) -> None:
    if key == "/jax/compilation_cache/cache_hits":
        _seen["hits"] += 1


jax.monitoring.register_event_listener(_on_event)


def _compiles() -> int:
    return obs.counters().get("compile.n", 0)


def test_the_persistent_cache_is_off_in_tests():
    assert not jax.config.jax_enable_compilation_cache


def test_a_persistent_cache_hit_counts_as_a_compile(
    assert_max_retraces, tmp_path
):
    opts = {
        "jax_enable_compilation_cache": True,
        "jax_compilation_cache_dir": str(tmp_path),
        "jax_persistent_cache_min_compile_time_secs": 0.0,
        "jax_persistent_cache_min_entry_size_bytes": 0,
    }
    was = {k: getattr(jax.config, k) for k in opts}
    x = jnp.arange(8.0)

    def f(v):
        return jnp.sin(v) * 3.0 + 1.0

    try:
        for k, v in opts.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
        jax.jit(f)(x).block_until_ready()  # compiled and written out
        assert any(tmp_path.iterdir())
        jax.clear_caches()
        before = dict(_seen, compiles=_compiles())
        with pytest.raises(AssertionError, match="new jit compilation"):
            with assert_max_retraces(0):
                jax.jit(f)(x).block_until_ready()  # read back
        # the program came from the cache: each compile event the fixture
        # counted was a cache read
        hits = _seen["hits"] - before["hits"]
        assert hits >= 1
        assert _compiles() - before["compiles"] == hits
    finally:
        for k, v in was.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
