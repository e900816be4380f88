"""CPU tests of the LFM2-MoE cell's benchmark files (no TPU is touched):
its FLOP counts, its two per-layer readers on counts recorded from a CPU
run of the program, and a toy cell of the configuration run through
``run.main`` as a new cell, against the plain reference.

Run: ``python -m pytest -q benchmarks/chip/tests`` from the repo root.
"""

from __future__ import annotations

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from types import SimpleNamespace  # noqa: E402

import pytest  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402

from benchmarks.chip import flops_moe, harness, run  # noqa: E402

jax.config.update("jax_enable_compilation_cache", False)
HERE = ROOT / "benchmarks" / "chip"
CELL = json.loads((HERE / "configs" / "lfm2moe-b8.json").read_text())

TOY = dict(
    CELL, name="toy-lfm2", hidden_size=64, intermediate_size=128,
    moe_intermediate_size=96, num_attention_heads=4, num_key_value_heads=2,
    num_hidden_layers=5, num_experts=2, num_experts_published=8,
    expert_share_first=2, vocab_size=256, n_slots=4, s_max=128,
    check_tokens=40,
    # toy readings on the CPU: sound runs 0.0, the 6-bit control 0.72-1.04
    # (bf16 MVMs 0.11-0.22)
    limits={"p99_logit_gap": 0.4},
)
TOY_MIX = {
    "kind": "requests", "rate_per_s": 6.0, "lead_in_s": 0.5,
    "prompt": {"median": 20, "sigma": 0.6, "min": 4, "max": 100},
    "output": {"median": 6, "sigma": 0.5, "min": 2, "max": 16},
    "max_total": 128,
}


def test_decode_flops_by_hand():
    """A decoded token of the cell: per conv layer in_proj + out_proj + 3
    taps a channel, per attention layer q, k, v, o; the dense layers'
    FFNs; each MoE layer's router over 32 experts plus 4 x 8/32 = 1
    expert FFN of 1792; the head. Attention: 4 x 2048 a key position."""
    d, ff, mf = 2048, 7168, 1792
    conv = d * 3 * d + d * d + 3 * d
    attn = 2 * d * d + 2 * d * 8 * 64
    moe = d * 32 + 1.0 * 3 * d * mf
    macs = 2 * (conv + 3 * d * ff) + (attn + moe) + 3 * (conv + moe)
    head = 2 * d * 16384
    assert flops_moe.layer_macs(CELL) == pytest.approx(macs)
    assert flops_moe.decode_flops(CELL, 1000) == pytest.approx(
        2 * macs + head + 4 * d * 1000)
    # a prompt of n: n tokens through the layers, n(n+1)/2 causal pairs
    n = 300
    assert flops_moe.prefill_flops(CELL, n) == pytest.approx(
        2 * macs * n + 4 * d * n * (n + 1) // 2 + head)
    # the expert share follows the configuration: all 32 held is 4 FFNs
    whole = dict(CELL, num_experts=32)
    assert flops_moe.layer_macs(whole) - macs == pytest.approx(4 * 3 * 3 * d * mf)


#: counts of ``moe.*`` as a CPU run of the program recorded them
#: (``tests/test_lfm2.py``'s toy: three MoE layers, 2 of 8 experts held,
#: top-4; prompts of 20, 9 and 30 tokens in one prefill of 4 rows of 32,
#: a dummy row and pad positions among them, then one decode step of 4
#: slots, one idle), each with the span it was made in. Only real rows
#: count as tokens or as routed rows; the MVMs ran over all of them.
RECORDED = [
    ("engine.admit", {"moe.rows_held": 174, "moe.rows_computed": 768,
                      "moe.rows_max_expert": 32, "moe.tokens": 177}),
    ("engine.decode", {"moe.rows_held": 9, "moe.rows_computed": 24,
                       "moe.rows_max_expert": 3, "moe.tokens": 9}),
]


def _replay(t0):
    from repro import obs

    for i, (span, counts) in enumerate(RECORDED):
        with obs.span(span, iter([t0 + i + 0.5]).__next__, t0=t0 + i):
            for name, v in counts.items():
                obs.count(name, v)


@pytest.mark.parametrize("name,want", [
    ("moe_row_share.lm", 100.0 * 183 / 792),
    ("moe_rows_per_token.lm", 183 / 186),
])
def test_moe_readers_on_recorded_counts(name, want):
    from repro import obs

    obs.reset()
    base = -1000.0  # before any real clock reading
    spans = [harness.Span("admit", base, base + 1.9, {}),
             harness.Span("decode_step", base + 1.9, base + 2.0, {})]
    _replay(base)
    got = run.read_metric(ROOT, name, SimpleNamespace(spans=spans))
    assert got == pytest.approx(want)
    # counts outside the window's spans are not read
    late = [harness.Span("admit", base + 5, base + 6, {})]
    assert run.read_metric(ROOT, name, SimpleNamespace(spans=late)) is None
    obs.reset()


def toy_root(tmp_path: pathlib.Path) -> pathlib.Path:
    """A checkout with the toy cell added as new files only."""
    root = tmp_path / "checkout"
    shutil.copytree(HERE, root / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "src").symlink_to(ROOT / "src")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    path = "benchmarks/chip/configs/toy-lfm2.json"
    (root / path).write_text(json.dumps(TOY))
    (root / "benchmarks/chip/traffic/toy-mix.json").write_text(json.dumps(TOY_MIX))
    bench["configs"].append({"name": "toy-lfm2", "source": "toy", "file": path,
                             "reduced": [], "why": "toy"})
    bench["workloads"].append({"name": "toy-lfm2.cell", "config": "toy-lfm2",
                               "traffic": "toy-mix", "chips": 1, "why": "toy"})
    for m in bench["end_to_end"]:
        if m["name"] in ("ttft_p90_ms", "tpot_ms"):
            m["workloads"].append("toy-lfm2.cell")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def run_cell(root, capsys, **kw) -> dict:
    result = run.main(["--workload", "toy-lfm2.cell", "--seed", str(2**31 + 11),
                       "--seconds", "2", "--trace", "0"], root=root, **kw)
    line = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(line) == json.loads(json.dumps(result))
    return result


def test_toy_cell_correct_and_its_control_not(tmp_path, capsys, monkeypatch):
    """The toy cell runs through ``run.main`` as a new cell would, and
    reads correct; the 6-bit control of the same cell does not."""
    monkeypatch.setattr(harness, "require_chips", lambda n: harness.device_info())
    root = toy_root(tmp_path)
    res = run_cell(root, capsys)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] == 12 and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "ttft_p90_ms", "tpot_ms"}
    res = run_cell(root, capsys, overrides={"b_adc": 6})
    assert res["correct"] is False
