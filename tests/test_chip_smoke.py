"""chip_smoke.py's phases on the CPU, at the olmo-1b smoke preset.

The script runs the same phase functions at olmo-1b's published widths on a
TPU; here they run through the same ``serve`` CLI path at d_model 64 with
the Pallas kernels in interpret mode, and the script's own entry point must
refuse the CPU.
"""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

SMOKE = ("--arch", "olmo-1b")


@pytest.fixture(scope="module")
def served():
    return chip_smoke.serve_phase(SMOKE)


def test_serve_phase_programs_once_and_retires_every_request(served):
    report, eng, argv = served
    assert "--record-logits" in argv
    assert report.n_requests == chip_smoke.N_REQUESTS
    assert report.program_events_delta == 0
    for rec in report.records:
        assert rec.logits.shape == (rec.n_new, eng.cfg.vocab)
        # the recorded rows are the ones the greedy tokens came from
        np.testing.assert_array_equal(rec.logits.argmax(-1), rec.tokens)


def test_reference_phase_agrees_and_its_controls_fail(served):
    out = chip_smoke.reference_phase(*served)
    assert out["positions"] == served[0].n_generated
    assert out["max_rel_err"] <= chip_smoke.TOL_LOGITS
    assert out["shifted_median_rel_err"] > chip_smoke.TOL_LOGITS
    assert out["code_mismatch"] <= chip_smoke.TOL_CODES
    assert out["bf16_code_mismatch"] > chip_smoke.TOL_CODES


def test_kernel_phase_matches_the_oracle_in_interpret_mode(served):
    base = chip_smoke.served_logits(served[0])
    assert chip_smoke.kernel_phase(SMOKE, base, native=False) <= (
        chip_smoke.TOL_LOGITS
    )


def test_compare_served_fails_past_the_tolerance():
    tokens = np.array([1, 2, 3])
    logits = np.ones((3, 4), np.float32)
    want = {0: (tokens, logits)}
    assert chip_smoke.compare_served("same", want, want) == 0.0
    with pytest.raises(AssertionError):
        chip_smoke.compare_served("off", {0: (tokens, 2 * logits)}, want)


def test_reference_mvm_matches_the_jnp_oracle():
    """The float64 reference quantizes exactly as the served MVM does."""
    import jax.numpy as jnp

    from repro.core import engine
    from repro.core.analog import AnalogConfig

    rng = np.random.default_rng(1)
    k, n = 2048, 256
    w = (rng.standard_normal((k, n)) * k**-0.5).astype(np.float32)
    x = rng.standard_normal((4, k)).astype(np.float32)
    plan = engine.plan_for(AnalogConfig().infer(b_adc=8), k, n)
    y = engine.execute_programmed(
        jnp.asarray(x), jnp.asarray(w), jnp.float32(1.0), jnp.float32(1.0),
        jnp.float32(1.0), plan, out_scale=jnp.float32(1.5),
    )
    want = chip_smoke.reference_mvm(x, w, 1.0, 1.0, 1.0, 1.5, 8, 1024)
    lsb = 1.5 / 127
    assert np.mean(np.abs(np.asarray(y) - want) > 0.5 * lsb) < 1e-3


@pytest.mark.parametrize("w_dtype", ["float32", "bfloat16"])
def test_code_check_passes_f32_and_fails_a_bf16_mvm(w_dtype):
    """A bf16 model's programmed layer, called as the model calls it, is
    within TOL_CODES of the float64 reference only with an f32 MVM."""
    import collections
    import dataclasses

    import jax.numpy as jnp

    from repro.core import analog, engine

    rng = np.random.default_rng(2)
    k, n = 512, 256
    layer = {
        "w": jnp.asarray(rng.standard_normal((k, n)) * k**-0.5, jnp.float32),
        "r_adc": jnp.float32(1.0),
        "w_clip_buf": jnp.array([-1.0, 1.0], jnp.float32),
        "out_scale_buf": jnp.float32(1.6),
    }
    Params = collections.namedtuple("Params", "layer gain_s")
    acfg = dataclasses.replace(
        analog.AnalogConfig().infer(b_adc=8), mode=engine.PCM_PROGRAMMED
    )
    share = chip_smoke.code_mismatch(
        Params(layer, jnp.float32(1.0)), acfg, jnp.bfloat16,
        w_dtype=w_dtype, rows=16,
    )
    if w_dtype == "float32":
        assert share <= chip_smoke.TOL_CODES
    else:
        assert share > chip_smoke.TOL_CODES


def test_compile_cache_dir_is_the_env_var_else_one_fixed_path(
    monkeypatch, tmp_path
):
    import jax

    from repro.launch import compile_cache

    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
        assert compile_cache.enable() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        monkeypatch.delenv(compile_cache.ENV)
        want = os.path.join(REPO, ".jax_cache")
        assert compile_cache.enable() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_main_refuses_the_cpu(capsys):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert exc.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


def test_script_alone_fails(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    out = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path,
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


FOUR = r"""
import os, sys, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, %(repo)r)
import chip_smoke
chip_smoke._import_repro()
out = chip_smoke.four_chip_phase(("--arch", "olmo-1b"), 4)
print(json.dumps(out))
"""


def test_four_chip_phase_on_virtual_devices():
    out = subprocess.run(
        [sys.executable, "-c", FOUR % {"repo": REPO}],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["layers"] == 8  # 7 projections (stacked over layers) + head
    assert res["max_rel_err"] <= chip_smoke.TOL_LOGITS
