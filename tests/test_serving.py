"""Serving invariants: prefill + decode == full forward, rolling windows,
stacked <-> unstacked cache layouts, and the serve CLI's flag validation."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.core.analog import AnalogConfig
from repro.models import ModelConfig, init_lm_cache, lm_forward, lm_init
from repro.models.lm import unstack_cache

DIGITAL = AnalogConfig()

FAMILIES = {
    "dense": dict(family="dense", n_layers=4),
    "gqa": dict(family="dense", n_layers=3, n_kv_heads=2),
    "hybrid": dict(family="hybrid", n_layers=8, block_pattern=("rec", "rec", "attn")),
    "ssm": dict(family="ssm", n_layers=2, ssm_state=16),
    "moe": dict(family="moe", n_layers=2, n_experts=4, top_k=2, capacity_factor=8.0),
}


def _cfg(kw):
    cfg = ModelConfig(name="t", **{k: v for k, v in kw.items() if k != "capacity_factor"}).smoke()
    if "capacity_factor" in kw:
        cfg = dataclasses.replace(cfg, capacity_factor=kw["capacity_factor"])
    return cfg


@pytest.mark.parametrize("fam", sorted(FAMILIES))
@pytest.mark.parametrize("unstack", [False, True])
def test_prefill_decode_matches_full(fam, unstack):
    cfg = _cfg(FAMILIES[fam])
    key = jax.random.PRNGKey(0)
    params = lm_init(key, cfg)
    B, S = 2, 20
    toks = jax.random.randint(key, (B, S), 0, cfg.vocab)
    full_logits, _ = lm_forward(params, {"tokens": toks}, DIGITAL, cfg)
    cache = init_lm_cache(cfg, B, 32, jnp.float32)
    _, cache = lm_forward(
        params, {"tokens": toks[:, :16]}, DIGITAL, cfg, cache=cache,
        last_token_only=True,
    )
    if unstack:
        cache = unstack_cache(cache)
    for t in range(16, 20):
        dec, cache = lm_forward(
            params, {"tokens": toks[:, t : t + 1]}, DIGITAL, cfg, cache=cache
        )
        err = float(jnp.max(jnp.abs(dec[:, 0] - full_logits[:, t])))
        assert err < 5e-3, (fam, t, err)


def test_rolling_window_past_window_length():
    cfg = dataclasses.replace(
        _cfg(FAMILIES["hybrid"]), local_window=8
    )
    key = jax.random.PRNGKey(0)
    params = lm_init(key, cfg)
    B, S = 2, 24
    toks = jax.random.randint(key, (B, S), 0, cfg.vocab)
    full_logits, _ = lm_forward(params, {"tokens": toks}, DIGITAL, cfg)
    cache = init_lm_cache(cfg, B, 64, jnp.float32)
    _, cache = lm_forward(
        params, {"tokens": toks[:, :20]}, DIGITAL, cfg, cache=cache,
        last_token_only=True,
    )
    cache = unstack_cache(cache)
    for t in range(20, 24):
        dec, cache = lm_forward(
            params, {"tokens": toks[:, t : t + 1]}, DIGITAL, cfg, cache=cache
        )
        err = float(jnp.max(jnp.abs(dec[:, 0] - full_logits[:, t])))
        assert err < 5e-3, (t, err)


def test_hybrid_cache_is_window_bounded():
    """long_500k feasibility: the hybrid attention cache must be bounded by
    the local window, not the sequence length."""
    cfg = dataclasses.replace(_cfg(FAMILIES["hybrid"]), local_window=32)
    cache = init_lm_cache(cfg, 1, 10_000, jnp.float32)
    kv_leaves = [
        x for x in jax.tree.leaves(cache) if x.ndim >= 4
    ]  # (G, B, S, kv, hd)
    for leaf in kv_leaves:
        assert leaf.shape[2] <= 32


def test_ssm_cache_is_constant_size():
    cfg = _cfg(FAMILIES["ssm"])
    c1 = init_lm_cache(cfg, 1, 100, jnp.float32)
    c2 = init_lm_cache(cfg, 1, 1_000_000, jnp.float32)
    s1 = sum(x.size for x in jax.tree.leaves(c1))
    s2 = sum(x.size for x in jax.tree.leaves(c2))
    assert s1 == s2  # position-free SSD state


def test_last_token_only_prefill_logits():
    cfg = _cfg(FAMILIES["dense"])
    key = jax.random.PRNGKey(0)
    params = lm_init(key, cfg)
    toks = jax.random.randint(key, (2, 16), 0, cfg.vocab)
    full, _ = lm_forward(params, {"tokens": toks}, DIGITAL, cfg)
    cache = init_lm_cache(cfg, 2, 16, jnp.float32)
    last, _ = lm_forward(
        params, {"tokens": toks}, DIGITAL, cfg, cache=cache, last_token_only=True
    )
    assert last.shape[1] == 1
    assert float(jnp.max(jnp.abs(last[:, 0] - full[:, -1]))) < 5e-3


# ------------------------------------------------- serve CLI flag validation

# every mutually-inconsistent combination must die in argument validation
# (SystemExit from argparse.error), before any model work starts
BAD_ARGV = {
    "per_call_without_analog": ["--per-call"],
    "per_call_with_save_program": [
        "--analog", "--per-call", "--save-program", "/tmp/x"
    ],
    "per_call_with_load_program": [
        "--analog", "--per-call", "--load-program", "/tmp/x"
    ],
    "refresh_below_without_schedule": [
        "--analog", "--refresh-below", "0.9"
    ],
    "refresh_below_with_no_ref_check": [
        "--analog", "--drift-schedule", "25,3600",
        "--refresh-below", "0.9", "--no-ref-check",
    ],
    "overrides_without_analog": ["--b-adc-overrides", "lm_head=8"],
    "overrides_with_per_call": [
        "--analog", "--per-call", "--b-adc-overrides", "lm_head=8"
    ],
    "resample_without_program": ["--resample-read-noise"],
    "schedule_without_analog": ["--drift-schedule", "25,3600"],
    "schedule_with_per_call": [
        "--analog", "--per-call", "--drift-schedule", "25,3600"
    ],
    "save_program_without_analog": ["--save-program", "/tmp/x"],
    "arrival_rate_without_trace": ["--analog", "--arrival-rate", "5"],
    "request_trace_with_per_call": [
        "--analog", "--per-call", "--request-trace", "4"
    ],
    "empty_request_trace": ["--analog", "--request-trace", "0"],
    "request_trace_with_vlm_frontend": [
        "--analog", "--arch", "paligemma-3b", "--request-trace", "4"
    ],
    "bad_drift_schedule_spec": ["--analog", "--drift-schedule", "bogus"],
    "bad_b_adc_overrides_spec": [
        "--analog", "--b-adc-overrides", "lm_head=four"
    ],
    "kv_page_size_without_trace": ["--analog", "--kv-page-size", "16"],
    "kv_page_size_zero": [
        "--analog", "--request-trace", "3", "--kv-page-size", "0"
    ],
    "kv_page_size_with_recurrent_family": [
        "--analog", "--arch", "mamba2-2.7b", "--request-trace", "3",
        "--kv-page-size", "16",
    ],
    "kv_pages_without_page_size": [
        "--analog", "--request-trace", "3", "--kv-pages", "8"
    ],
    "prefill_buckets_without_page_size": [
        "--analog", "--request-trace", "3", "--prefill-buckets", "32,64"
    ],
    "bad_prefill_buckets_spec": [
        "--analog", "--request-trace", "3", "--kv-page-size", "16",
        "--prefill-buckets", "bogus",
    ],
    "nonpositive_prefill_buckets": [
        "--analog", "--request-trace", "3", "--kv-page-size", "16",
        "--prefill-buckets", "0,32",
    ],
    "fleet_zero_chips": ["--fleet", "0"],
    "fleet_without_trace": ["--analog", "--fleet", "2"],
    "fleet_without_analog_or_artifact": [
        "--fleet", "2", "--request-trace", "4"
    ],
    "fleet_with_drift_schedule": [
        "--analog", "--fleet", "2", "--request-trace", "4",
        "--drift-schedule", "25,3600",
    ],
    "fleet_with_save_program": [
        "--analog", "--fleet", "2", "--request-trace", "4",
        "--save-program", "/tmp/x",
    ],
    "fleet_with_use_kernel": [
        "--analog", "--fleet", "2", "--request-trace", "4", "--use-kernel"
    ],
    "agreement_slo_without_fleet": [
        "--analog", "--request-trace", "3", "--agreement-slo", "0.5"
    ],
    "agreement_slo_on_fleet_of_one": [
        "--analog", "--fleet", "1", "--request-trace", "3",
        "--agreement-slo", "0.5",
    ],
    "agreement_slo_with_no_ref_check": [
        "--analog", "--fleet", "2", "--request-trace", "4",
        "--agreement-slo", "0.5", "--no-ref-check",
    ],
    "agreement_slo_out_of_range": [
        "--analog", "--fleet", "2", "--request-trace", "4",
        "--agreement-slo", "1.5",
    ],
    "async_without_fleet": ["--analog", "--request-trace", "3", "--async"],
    "async_on_fleet_of_one": [
        "--analog", "--fleet", "1", "--request-trace", "3", "--async"
    ],
    "queue_cap_without_async": [
        "--analog", "--fleet", "2", "--request-trace", "4",
        "--queue-cap", "8",
    ],
    "queue_cap_zero": [
        "--analog", "--fleet", "2", "--request-trace", "4",
        "--async", "--queue-cap", "0",
    ],
    "fused_decode_without_program": ["--fused-decode"],
    "fused_decode_with_per_call": [
        "--analog", "--per-call", "--fused-decode"
    ],
    "fused_decode_with_use_kernel": [
        "--analog", "--fused-decode", "--use-kernel"
    ],
    "fused_decode_with_paged_kv": [
        "--analog", "--request-trace", "3", "--kv-page-size", "16",
        "--fused-decode",
    ],
    "fused_decode_with_fleet": [
        "--analog", "--fleet", "2", "--request-trace", "4",
        "--fused-decode",
    ],
    "fused_decode_with_mesh": [
        "--analog", "--fused-decode", "--mesh-model", "2"
    ],
    "fused_decode_with_recurrent_family": [
        "--analog", "--arch", "mamba2-2.7b", "--fused-decode"
    ],
    "fused_decode_with_qkv_bias_arch": [
        "--analog", "--arch", "qwen2-72b", "--fused-decode"
    ],
    "n_layers_without_published": ["--arch", "olmo-1b", "--n-layers", "2"],
    "n_layers_zero": ["--arch", "olmo-1b", "--published", "--n-layers", "0"],
    "n_layers_past_published_depth": [
        "--arch", "olmo-1b", "--published", "--n-layers", "17"
    ],
}


@pytest.mark.parametrize("argv,want_layers", [
    (["--arch", "olmo-1b"], None),
    (["--arch", "olmo-1b", "--published"], 16),
    (["--arch", "olmo-1b", "--published", "--n-layers", "4"], 4),
])
def test_serve_model_config_published_depth_cut(argv, want_layers):
    """``--published`` keeps every published width and only ``--n-layers``
    cuts depth; without it the CPU smoke preset is served."""
    from repro import configs
    from repro.launch import serve

    cfg = serve.model_config(serve.build_parser().parse_args(argv))
    if want_layers is None:
        assert cfg == configs.get_smoke("olmo-1b")
        return
    pub = configs.get("olmo-1b")
    assert (cfg.d_model, cfg.n_heads, cfg.hd, cfg.d_ff, cfg.vocab,
            cfg.dtype) == (2048, 16, 128, 8192, 50304, pub.dtype)
    assert cfg.n_layers == want_layers


@pytest.mark.parametrize("name", sorted(BAD_ARGV))
def test_serve_cli_rejects_inconsistent_flags(name, monkeypatch, capsys):
    from repro.launch import serve

    monkeypatch.setattr("sys.argv", ["serve"] + BAD_ARGV[name])
    with pytest.raises(SystemExit) as exc:
        serve.main()
    assert exc.value.code == 2, name
    err = capsys.readouterr().err
    assert "error:" in err, (name, err)


def test_serve_cli_request_trace_smoke(monkeypatch, capsys):
    """Continuous batching end-to-end through the CLI: a short Poisson
    trace over the compiled chip, zero programming events during serving."""
    from repro.launch import serve

    monkeypatch.setattr(
        "sys.argv",
        ["serve", "--analog", "--batch", "2", "--prompt-len", "8",
         "--tokens", "4", "--request-trace", "3", "--arrival-rate", "200"],
    )
    serve.main()
    out = capsys.readouterr().out
    assert "serving: mode=continuous requests=3" in out
    assert "program_events_delta=0" in out
    assert "accuracy_vs_digital_ref:" in out


def test_serve_cli_fleet_smoke(monkeypatch, capsys):
    """Fleet serving end-to-end through the CLI: two independent chip
    draws behind the router, request conservation and the fleet-wide
    programming-event accounting visible in the summary."""
    from repro.launch import serve

    monkeypatch.setattr(
        "sys.argv",
        ["serve", "--analog", "--batch", "2", "--prompt-len", "8",
         "--tokens", "4", "--request-trace", "6", "--arrival-rate", "200",
         "--fleet", "2", "--agreement-slo", "0.01"],
    )
    serve.main()
    out = capsys.readouterr().out
    assert "programmed 2 independent chip draws" in out
    assert "fleet: chips=2 requests=6" in out
    assert "program_events_delta=0" in out
    assert "accuracy_vs_digital_ref:" in out


def test_serve_cli_async_fleet_smoke(monkeypatch, capsys):
    """The threaded front end through the CLI: same fleet, same
    conservation evidence, plus the greppable async throughput line."""
    from repro.launch import serve

    monkeypatch.setattr(
        "sys.argv",
        ["serve", "--analog", "--batch", "2", "--prompt-len", "8",
         "--tokens", "4", "--request-trace", "6", "--arrival-rate", "200",
         "--fleet", "2", "--async", "--queue-cap", "16"],
    )
    serve.main()
    out = capsys.readouterr().out
    assert "async fleet: workers=2 queue_cap=16" in out
    assert "fleet: chips=2 requests=6" in out
    assert "program_events_delta=0" in out


def test_serve_cli_fleet_of_one_is_the_single_engine_path(monkeypatch,
                                                          capsys):
    """--fleet 1 must serve exactly like no --fleet at all: same
    generations, same accuracy counters, no router in sight."""
    from repro.launch import serve

    argv = ["serve", "--analog", "--batch", "2", "--prompt-len", "8",
            "--tokens", "4", "--request-trace", "3",
            "--arrival-rate", "200"]
    outs = []
    for extra in ([], ["--fleet", "1"]):
        monkeypatch.setattr("sys.argv", argv + extra)
        serve.main()
        outs.append(capsys.readouterr().out)
    for out in outs:
        assert "fleet:" not in out
        assert "serving: mode=continuous requests=3" in out

    def stable(out):
        return [
            line for line in out.splitlines()
            if line.startswith(("generated token ids",
                                "accuracy_vs_digital_ref:"))
        ]

    assert stable(outs[0]) == stable(outs[1])


def test_serve_cli_fused_decode_smoke(monkeypatch, capsys):
    """--fused-decode end-to-end through the CLI: the whole decode step
    runs as one Pallas grid, and the generations + accuracy counters are
    byte-identical to the per-layer decode path."""
    from repro.launch import serve

    argv = ["serve", "--analog", "--batch", "2", "--prompt-len", "8",
            "--tokens", "4", "--request-trace", "3",
            "--arrival-rate", "200"]
    outs = []
    for extra in ([], ["--fused-decode"]):
        monkeypatch.setattr("sys.argv", argv + extra)
        serve.main()
        outs.append(capsys.readouterr().out)
    for out in outs:
        assert "serving: mode=continuous requests=3" in out
        assert "program_events_delta=0" in out

    def stable(out):
        return [
            line for line in out.splitlines()
            if line.startswith(("generated token ids",
                                "accuracy_vs_digital_ref:"))
        ]

    assert stable(outs[0]) == stable(outs[1])


def test_serve_cli_paged_request_trace_smoke(monkeypatch, capsys):
    """Paged serving end-to-end through the CLI: --kv-page-size switches
    the engine to the paged cache + bucketed admission; the serving
    contract (zero programming events) and the trace bound still hold."""
    from repro.launch import serve

    monkeypatch.setattr(
        "sys.argv",
        ["serve", "--analog", "--batch", "2", "--prompt-len", "8",
         "--tokens", "4", "--request-trace", "3", "--arrival-rate", "200",
         "--kv-page-size", "8", "--prefill-buckets", "16,32"],
    )
    serve.main()
    out = capsys.readouterr().out
    assert "serving: mode=bucketed requests=3" in out
    assert "program_events_delta=0" in out
    assert "prefill_traces=" in out
