"""CPU tests of the on-chip benchmark's harness (no TPU is touched).

Run: ``python -m pytest -q benchmarks/chip/tests`` from the repo root.
The toy cells add a configuration and a traffic mix as new files in a
copy of the benchmark and run them through ``run.main`` with the chip
check patched, as a later change adding a cell would.
"""

from __future__ import annotations

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import json  # noqa: E402
import pathlib  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402

from benchmarks.chip import gen, harness, run, stats, trace_reduce  # noqa: E402

jax.config.update("jax_enable_compilation_cache", False)
HERE = ROOT / "benchmarks" / "chip"

TOY_LM = {
    "name": "toy-lm", "source": "toy", "driver": "lm_serve", "reference": "olmo",
    "arch": "olmo-1b", "hidden_size": 64, "intermediate_size": 128,
    "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
    "num_hidden_layers": 2, "vocab_size": 256, "max_position_embeddings": 128,
    "rope_theta": 10000.0, "norm_eps": 1e-6, "activation_dtype": "bfloat16",
    "b_adc": 8, "t_hours": 24.0, "n_slots": 4, "page_size": 16, "n_pages": None,
    "check_tokens": 40,
    # toy readings on the CPU: sound runs 0.0, the 6-bit control >= 0.16
    "limits": {"max_logit_gap": 0.1},
}
TOY_MIX = {
    "kind": "requests", "rate_per_s": 6.0, "lead_in_s": 0.5,
    "prompt": {"median": 20, "sigma": 0.6, "min": 4, "max": 100},
    "output": {"median": 6, "sigma": 0.5, "min": 2, "max": 16},
    "max_total": 128,
}
TOY_CNN = {
    "name": "toy-cnn", "source": "toy", "driver": "cnn_batch",
    "reference": "analognet", "input_hw": [12, 6], "in_channels": 1,
    "convs": [
        {"name": "conv1", "kh": 3, "kw": 3, "c_in": 1, "c_out": 8, "stride": 1},
        {"name": "conv2", "kh": 3, "kw": 3, "c_in": 8, "c_out": 8, "stride": 2},
    ],
    "fc_width": 8, "n_classes": 4, "b_adc": 8, "t_hours": 24.0,
    "check_answers": 64,
    # toy readings on the CPU: sound runs <= 2 ADC steps, the 6-bit control >= 0.037
    "limits": {"max_logit_err": 0.03},
}
TOY_BATCHES = {"kind": "batches", "batch": 32, "pool": 2}


# --------------------------------------------------------------- helpers


def toy_root(tmp_path: pathlib.Path) -> pathlib.Path:
    """A checkout with the toy cells added as new files only."""
    root = tmp_path / "checkout"
    shutil.copytree(HERE, root / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "src").symlink_to(ROOT / "src")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for name, cfg, mix, data in (("toy-lm", TOY_LM, "toy-mix", TOY_MIX),
                                 ("toy-cnn", TOY_CNN, "toy-batches", TOY_BATCHES)):
        path = f"benchmarks/chip/configs/{name}.json"
        (root / path).write_text(json.dumps(cfg))
        (root / "benchmarks/chip/traffic" / f"{mix}.json").write_text(json.dumps(data))
        bench["configs"].append({"name": name, "source": "toy", "file": path,
                                 "reduced": [], "why": "toy"})
        bench["workloads"].append({"name": f"{name}.cell", "config": name,
                                   "traffic": mix, "chips": 1, "why": "toy"})
    for m in bench["end_to_end"]:
        if m["name"] in ("ttft_p90_ms", "tpot_ms"):
            m["workloads"].append("toy-lm.cell")
        if m["name"] == "inferences_per_s":
            m["workloads"].append("toy-cnn.cell")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture
def on_cpu(monkeypatch):
    """Skip the harness's look for a chip; everything else runs."""
    monkeypatch.setattr(harness, "require_chips", lambda n: harness.device_info())


def run_cell(root, cell, capsys, seed=2**31 + 11, seconds=2.0, **kw) -> dict:
    result = run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                       str(seconds), "--trace", "0"], root=root, **kw)
    line = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(line) == json.loads(json.dumps(result))
    return result


# --------------------------------------------------------------- tests


def test_run_refuses_a_cpu(capsys):
    with pytest.raises(SystemExit) as exc:
        run.main(["--workload", "olmo1b-b8-conv", "--seed", "1",
                  "--seconds", "1", "--trace", "0"])
    assert "TPU" in str(exc.value)
    assert capsys.readouterr().out == ""


def test_run_refuses_a_tree_without_the_program(tmp_path, capsys):
    root = tmp_path / "bare"
    shutil.copytree(HERE, root / "benchmarks" / "chip")
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    with pytest.raises(SystemExit):
        run.main(["--workload", "kws-b8-batch", "--seed", "1",
                  "--seconds", "1", "--trace", "0"], root=root)
    assert capsys.readouterr().out == ""


def test_names_and_units_use_allowed_characters():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    name = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
    unit = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")
    names = [c["name"] for c in bench["configs"]]
    names += [w[k] for w in bench["workloads"] for k in ("name", "config", "traffic")]
    names += [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    names += [k for c in bench["configs"] for k in c["reduced"]]
    assert all(name.fullmatch(n) for n in names), names
    units = [m["unit"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert all(unit.fullmatch(u) for u in units), units
    texts = [c[k] for c in bench["configs"] for k in ("why", "source")]
    texts += [w["why"] for w in bench["workloads"]]
    texts += [m["layer"] for m in bench["per_layer"]] + bench["command"]
    assert all(0 < len(t) <= 200 and "\n" not in t and "\t" not in t
               for t in texts), texts
    for c in bench["configs"]:
        assert (ROOT / c["file"]).is_file()
    for w in bench["workloads"]:
        assert (HERE / "traffic" / f"{w['traffic']}.json").is_file()
    for m in bench["per_layer"]:
        assert run.reader_path(ROOT, m["name"]).is_file(), m["name"]


@pytest.mark.parametrize("mix", ["azure-conv", "azure-code"])
def test_stratified_traffic_same_work_every_seed(mix):
    spec = json.loads((HERE / "traffic" / f"{mix}.json").read_text())
    a = gen.requests(spec, 2**31 + 5, 20.0, 50304)
    b = gen.requests(spec, 7, 20.0, 50304)
    n_lead = round(spec["rate_per_s"] * spec["lead_in_s"])
    n_win = round(spec["rate_per_s"] * 20.0)
    assert len(a) == len(b) == n_lead + n_win
    assert sum(r.in_window for r in a) == n_win
    # every stratum used once: the same multiset of lengths for any seed
    for key in (lambda r: r.prompt.size, lambda r: r.max_new):
        assert sorted(map(key, a)) == sorted(map(key, b))
    outs = sorted(r.max_new for r in a if r.in_window)
    assert outs == sorted(gen.lognormal_lengths(spec["output"], n_win))
    assert all(r.prompt.size + r.max_new <= spec["max_total"] for r in a)
    # the same gaps, in another order
    ta = np.diff([r.arrival_s for r in a if r.in_window])
    tb = np.diff([r.arrival_s for r in b if r.in_window])
    assert not np.allclose(ta, tb)
    assert [r.max_new for r in a] != [r.max_new for r in b]
    win = [r.arrival_s for r in a if r.in_window]
    assert spec["lead_in_s"] <= min(win) and max(win) < spec["lead_in_s"] + 20.0


def test_sample_reads_the_longest_and_many_slots():
    """The check reads the longest finished request and at least
    MIN_OTHERS others, even where the longest alone holds enough tokens."""
    from types import SimpleNamespace

    from benchmarks.chip.drivers import lm_serve

    recs = [SimpleNamespace(rid=i, tokens=np.zeros(n, np.int32))
            for i, n in enumerate([5, 900, 7, 30, 12, 8, 40, 3, 9, 11, 6, 25])]
    picked = lm_serve.sample(recs, 2**31 + 3, min_tokens=400)
    assert picked[0].rid == 1
    assert len(picked) == lm_serve.MIN_OTHERS + 1
    assert len({r.rid for r in picked}) == len(picked)
    again = lm_serve.sample(recs, 2**31 + 3, min_tokens=400)
    assert [r.rid for r in again] == [r.rid for r in picked]
    other = lm_serve.sample(recs, 2**31 + 4, min_tokens=400)
    assert [r.rid for r in other] != [r.rid for r in picked]
    many = lm_serve.sample(recs, 2**31 + 3, min_tokens=1040)
    assert sum(r.tokens.size for r in many) >= 1040
    assert lm_serve.sample(recs[:3], 5, min_tokens=400) != []


def test_parts_of_one_quantity_share_a_reader():
    for name in ("idle_share.lm", "idle_share.kws"):
        assert run.reader_path(ROOT, name) == HERE / "metrics" / "idle_share.py"
    assert run.reader_path(ROOT, "mfu.kws") == HERE / "metrics" / "mfu.kws.py"


def test_mvm_dtype_casts_programmed_weights_only():
    import jax.numpy as jnp

    from benchmarks.chip import weights

    tree = {"blocks": [{"w": jnp.ones((4, 3)), "out_scale_buf": jnp.ones(()),
                        "w_clip_buf": jnp.ones((2,))}],
            "embed": {"w": jnp.ones((5, 4))}}
    out = weights.with_mvm_dtype(tree, "bfloat16")
    assert out["blocks"][0]["w"].dtype == jnp.bfloat16
    assert out["blocks"][0]["w_clip_buf"].dtype == jnp.float32
    assert out["blocks"][0]["out_scale_buf"].dtype == jnp.float32
    assert out["embed"]["w"].dtype == jnp.float32


def test_serve_metrics_arithmetic():
    # window [10, 20); request 1 due at 10, first token at 10.2, then a
    # token every 0.05 s except one stalled gap of 0.5 s; request 2 due at
    # 12 gets nothing (a miss); request 3 (lead-in) decodes inside it
    t1 = [10.2, 10.25, 10.30, 10.80, 10.85]
    due = {1: 10.0, 2: 12.0}
    tokens = {1: t1, 3: [9.0, 9.9, 10.1]}
    m = stats.serve_metrics(due, tokens, 10.0, 20.0, t_stop=25.0)
    assert m["attempted"] == 2 and m["failed"] == 1
    # ttft: 0.2 and the miss's whole wait 13.0; nearest-rank p90 is 13.0
    assert m["ttft_p90_ms"] == pytest.approx(13000.0)
    # gaps ending in the window: 0.05, 0.05, 0.5, 0.05 and 0.2 (request 3)
    gaps = [0.05, 0.05, 0.5, 0.05, 0.2]
    assert m["n_gaps"] == 5
    assert m["tpot_ms"] == pytest.approx(sum(gaps) / 5 * 1e3)
    assert m["itl_p99_ms"] == pytest.approx(500.0)
    assert stats.percentile(range(1, 101), 90) == 90
    assert stats.spread([1, 2, 3, 4, 5]) == pytest.approx(
        (4.5 - 1.5) / 3.0)


def test_trace_reduction_by_hand():
    rec = {
        "devices": {"/device:TPU:0": {
            "modules": [["jit_decode(3)", 100, 300], ["jit_decode(3)", 600, 300]],
            "ops": [["fusion.1", 100, 200], ["convert.2", 300, 100],
                    ["fusion.1", 600, 300]],
        }},
        "host": [[trace_reduce.WINDOW, 0, 1000], ["admit", 0, 100],
                 ["decode_step", 100, 420], ["admit", 520, 80],
                 ["decode_step", 600, 300]],
    }
    red = trace_reduce.reduce(rec)
    assert red["window_s"] == pytest.approx(1e-6)
    assert red["busy_s"] == pytest.approx(600e-9)
    assert red["modules"]["jit_decode"] == {"s": pytest.approx(600e-9), "calls": 2}
    assert red["device_ops"][0] == ["jit_decode/fusion.1", pytest.approx(500e-9)]
    # idle [0, 100) in admit; [400, 600) mostly in the first decode_step
    # span (120 of it, 80 in the next admit); [900, 1000) under no span
    gaps = sorted((n, round(s * 1e9)) for n, s in red["idle_gaps"])
    assert gaps == [("admit", 100), ("decode_step", 200), ("none", 100)]


def test_trace_reduction_of_a_recorded_chip_trace():
    """A 40 ms slice of an olmo1b-b8-conv trace recorded on a TPU v5e: the
    event record of ``trace_reduce.load``, cut to the events overlapping
    40 ms that open 1 ms before an ``admit`` span."""
    rec = json.loads((HERE / "testdata" / "trace_conv_slice.json").read_text())
    red = trace_reduce.reduce(rec)
    assert red["n_devices"] == 1
    assert 0 < red["busy_s"] <= red["window_s"]
    assert red["modules"]["jit_decode"]["calls"] >= 1
    assert sum(s for _, s in red["device_ops"]) <= red["busy_s"] + 1e-12
    assert {n for n, _ in red["idle_gaps"]} <= {"admit", "decode_step", "none"}


# --------------------------------------------------------------- toy cells


def test_toy_lm_cell_runs_correct(tmp_path, capsys, on_cpu):
    res = run_cell(toy_root(tmp_path), "toy-lm.cell", capsys)
    assert res["correct"] is True
    assert res["attempted"] == 12 and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "ttft_p90_ms", "tpot_ms"}
    assert list(res)[-1] == "checks"
    assert res["checks"]["max_logit_gap"]["value"] <= 0.1


def test_toy_lm_control_and_fault_fail(tmp_path, capsys, on_cpu, monkeypatch):
    """The 6-bit control, and a served token altered where the decode step
    produces it, both come out not correct."""
    root = toy_root(tmp_path)
    res = run_cell(root, "toy-lm.cell", capsys, overrides={"b_adc": 6})
    assert res["correct"] is False

    from repro.serving import engine as engine_mod

    real = engine_mod.ServingEngine.__init__

    def broken(self, *a, **kw):
        real(self, *a, **kw)
        decode = self._decode

        def altered(*args):
            nxt, last, cache = decode(*args)
            return (nxt + 1) % self.cfg.vocab, last, cache

        self._decode = altered

    monkeypatch.setattr(engine_mod.ServingEngine, "__init__", broken)
    res = run_cell(root, "toy-lm.cell", capsys)
    assert res["correct"] is False


def test_toy_cnn_cell_control_and_fault(tmp_path, capsys, on_cpu, monkeypatch):
    root = toy_root(tmp_path)
    res = run_cell(root, "toy-cnn.cell", capsys, seconds=1.0)
    assert res["correct"] is True
    assert set(res["metrics"]) == {"setup_s", "inferences_per_s"}
    res = run_cell(root, "toy-cnn.cell", capsys, seconds=1.0, overrides={"b_adc": 6})
    assert res["correct"] is False

    from repro.models import analognet

    real = analognet.cnn_apply

    def altered(*a, **kw):
        y = real(*a, **kw)
        return y.at[:, 0].add(0.5)

    monkeypatch.setattr(analognet, "cnn_apply", altered)
    res = run_cell(root, "toy-cnn.cell", capsys, seconds=1.0)
    assert res["correct"] is False
