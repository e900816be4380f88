#!/usr/bin/env python3
"""Chip smoke: serve olmo-1b from one programmed PCM chip on a TPU.

    python chip_smoke.py               # one chip: phases a-d
    python chip_smoke.py --four-chips  # four chips: sharded vs one-chip

Drives the system's main path once, through the ``repro.launch.serve`` CLI
(``serve.run``, the body of ``serve.main``), in this one process (a child
would find the chip held). The model is olmo-1b at its published widths --
d_model 2048, 16 x 128 MHA heads, d_ff 8192, vocab 50304, bf16 activations
-- cut to 4 of its 16 layers: one stage of a four-stage pipeline
deployment, except that this chip also keeps the
embedding (the first stage's) and the head (the last stage's) so that one
chip serves a whole model. Weights are random from a seed, programmed once
into a simulated PCM chip with 8-bit ADCs, and served to an 8-request trace.

Phases on one chip:
  a  device gate: a TPU, or exit non-zero before anything runs;
  b  serve the trace: the chip is programmed once (program_events_delta=0)
     and every request retires; prints program, compile and peak memory;
  c  the served prefill-then-decode logits agree with a full-sequence
     forward of the same programmed params (no cache, no batching, f32
     matmuls at ``highest``), and every programmed layer, called as the
     model calls it, agrees with a float64 reference -- tightly enough
     that the same call with its MVM run in bf16 fails (both tolerances
     are stated, with their reasons, below);
  d  the same trace through the native Pallas MVM kernel (every
     ``pallas_call`` on the path has ``interpret=False``) agrees with b,
     and the kernel's layer outputs agree with the float64 reference.
With ``--four-chips`` only: the trace served by a chip programmed over a
four-device mesh (``--mesh-model 4``) agrees with the same chip programmed
on device 0 alone -- bit-identical effective weights, logits within the
tolerance -- and its weights span all four devices.

Any failed phase raises, so the process exits non-zero and prints no
``ok``. The last line of stdout is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.
Compiled programs persist in ``$JAX_COMPILATION_CACHE_DIR`` when it is set,
else in ``<repo>/.jax_cache``.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys

REPO = os.path.dirname(os.path.abspath(__file__))

#: the model: olmo-1b's published config cut to 4 of its 16 layers
PUBLISHED = ("--arch", "olmo-1b", "--published", "--n-layers", "4")
#: the traffic: 8 requests (prompts of 8-32 tokens, 8-16 new tokens each)
#: arriving as a Poisson stream at 100 requests/s over 4 decode slots, on a
#: chip programmed once at b_adc 8; logits are kept for phases c and d
TRACE = (
    "--analog", "--b-adc", "8", "--request-trace", "8",
    "--arrival-rate", "100", "--batch", "4", "--prompt-len", "32",
    "--tokens", "16", "--record-logits",
)
N_REQUESTS = 8

#: Largest relative L2 error ||a - r|| / ||r|| of any position's logits
#: between two servings of the same programmed chip whose f32 sums run in a
#: different order: cached batched decode vs one causal pass (phase c), the
#: Pallas kernel vs the jnp oracle (d), a four-device mesh vs one device.
#: A reordered sum moves an ADC code by one LSB only where the partial sum
#: sits at a code boundary, but every projection re-quantizes: one flipped
#: code shifts the next layer's partial sums by ~1/8 LSB each and flips a
#: few percent of its codes, so within three projections most codes of the
#: analog network jitter by one LSB. That bounds the logits' error at about
#: one LSB over a typical code: 3.2 % at most at d_model 512 and 1024 on
#: the CPU. A wiring fault is far larger: phase c shows that comparing each
#: position with the next one's exceeds this at most positions.
#: Precision is checked per MVM instead (TOL_CODES), where no cascade runs.
TOL_LOGITS = 0.1

#: Largest share of a programmed layer's outputs that may be off a float64
#: NumPy reference by more than half an ADC step, the layer being called as
#: the model calls it (``analog.linear_apply`` on activations in the model's
#: dtype) and the reference rounded to that dtype as the device rounds its
#: result. Both see the same DAC codes (activations on a DAC tie are
#: zeroed); an f32 sum of at most 1024 products then lies within ~1e-7 of
#: the float64 one relative to its spread, and a code differs only at a
#: boundary: at most one output per layer, 2.4e-4 of 4096 at d_model 512
#: in bf16 on the CPU. A bf16 MVM rounds every DAC level and weight to 8
#: bits and moves 0.077 of the outputs there; so does the analog path run
#: at the model's bf16. Phase c runs the same call on the layer's weights
#: stored in bf16, which runs its MVM in bf16, as the control that must
#: fail.
TOL_CODES = 1e-3


@contextlib.contextmanager
def phase(name: str):
    """Time a block as the recorder's span ``name`` (``repro.obs``). Yields
    a dict that holds, after the block, its wall seconds (``wall``) and the
    seconds JAX spent in backend compiles inside it (``compile``)."""
    from repro import clock, obs

    out = {}
    c0 = obs.counters().get("compile.s", 0.0)
    with obs.span(name, clock.SYSTEM.now):
        yield out
    _, _, t0, t1, *_ = obs.last(name)
    out["wall"] = t1 - t0
    out["compile"] = obs.counters().get("compile.s", 0.0) - c0


def _log(msg: str) -> None:
    print(msg, flush=True)


def _import_repro():
    """Make ``src/`` importable; fail when this file stands alone."""
    src = os.path.join(REPO, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise SystemExit(
            f"chip_smoke: no repro package under {src}; run from a checkout"
        )
    if src not in sys.path:
        sys.path.insert(0, src)


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def device_info() -> dict:
    import jax

    d = jax.devices()[0]
    return {
        "platform": d.platform,
        "kind": d.device_kind,
        "count": len(jax.devices()),
    }


def device_gate(min_count: int = 1) -> dict:
    """Phase a: refuse anything but a TPU with enough chips."""
    info = device_info()
    _log(f"phase a: platform={info['platform']} kind={info['kind']} "
         f"count={info['count']}")
    if info["platform"] != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU, JAX found {info['platform']!r}"
        )
    if info["count"] < min_count:
        raise SystemExit(
            f"chip_smoke: needs {min_count} chips, found {info['count']}"
        )
    return info


def rel_err(a, r):
    """Per-row relative L2 error of logits ``a`` against reference ``r``."""
    import numpy as np

    a = np.asarray(a, np.float64)
    r = np.asarray(r, np.float64)
    return np.linalg.norm(a - r, axis=-1) / np.linalg.norm(r, axis=-1)


def analog_layers(tree, path: str = ""):
    """``(path, node)`` of every programmed analog layer in a param tree."""
    if isinstance(tree, dict):
        if "w" in tree and "out_scale_buf" in tree:
            yield path, tree
            return
        for k, v in tree.items():
            yield from analog_layers(v, f"{path}/{k}")
    elif isinstance(tree, (tuple, list)):
        names = getattr(tree, "_fields", range(len(tree)))
        for k, v in zip(names, tree):
            yield from analog_layers(v, f"{path}/{k}")


def compare_served(name: str, got: dict, want: dict) -> float:
    """Compare two servings of one trace, request by request.

    ``got``/``want`` map rid -> (tokens, logits). Positions are compared up
    to and including the first whose token differs: past it the two
    streams feed different inputs (random weights leave close top-2
    logits, so a tie can break either way).
    """
    import numpy as np

    worst, n_pos = 0.0, 0
    for rid, (tok_w, lg_w) in sorted(want.items()):
        tok_g, lg_g = got[rid]
        n = min(tok_g.size, tok_w.size)
        diff = np.nonzero(tok_g[:n] != tok_w[:n])[0]
        n_cmp = n if diff.size == 0 else int(diff[0]) + 1
        err = rel_err(lg_g[:n_cmp], lg_w[:n_cmp])
        worst = max(worst, float(err.max()))
        n_pos += n_cmp
    _log(f"{name}: {n_pos} positions, max rel_err={worst!r} "
         f"(tolerance {TOL_LOGITS})")
    if not worst <= TOL_LOGITS:
        raise AssertionError(
            f"{name}: logits differ by rel_err {worst!r} > {TOL_LOGITS}"
        )
    return worst


def reference_mvm(x, w, r_adc, gain_s, w_max, out_scale, bits, tile_rows):
    """DAC -> per-row-tile crossbar MVM -> ADC -> GDC in float64 NumPy."""
    import numpy as np

    def quant(v, r, b):
        r = abs(r) + 1e-9
        step = r / (2 ** (b - 1) - 1)
        return np.round(np.clip(v, -r, r) / step) * step

    x = np.asarray(x, np.float64)
    w = np.asarray(w, np.float64)
    r_dac = abs(r_adc) * abs(gain_s) / (abs(w_max) + 1e-9)
    xq = quant(x, r_dac, bits + 1)
    y = sum(
        quant(xq[..., t:t + tile_rows] @ w[t:t + tile_rows], r_adc, bits)
        for t in range(0, w.shape[0], tile_rows)
    )
    return y * out_scale


def code_mismatch(params, acfg, dtype, *, w_dtype=None, rows: int = 8):
    """Largest per-layer share of outputs where a programmed layer, run
    through the model's own layer call, is off the float64 reference.

    Every programmed layer (each member of a stacked group) runs through
    ``analog.linear_apply`` under ``acfg`` -- the jnp oracle, or the Pallas
    kernel when ``acfg.use_kernel`` -- jitted at the default matmul
    precision, on random activations in the model's ``dtype``, as serving
    runs it. Its output, in that dtype, is compared with
    :func:`reference_mvm` of the same activations rounded to that dtype;
    an output is off where the two differ by more than half an ADC step.
    ``w_dtype`` stores each layer's effective weights in that dtype first,
    so that its MVM runs in it (the control); the reference keeps them f32.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import analog, engine

    @jax.jit
    def run(layer, x, gain_s):
        ctx = analog.AnalogCtx(cfg=acfg, gain_s=gain_s)
        return analog.linear_apply(layer, x, ctx)

    gain_s = params.gain_s
    rng = np.random.default_rng(0)
    worst = 0.0
    for _path, node in analog_layers(params):
        for i in np.ndindex(*node["w"].shape[:-2]):
            # the MVM alone: a bias is added after the ADC, in the digital
            # domain (none in the chip-smoke model)
            layer = {
                k: v for k, v in jax.tree.map(lambda a: a[i], node).items()
                if k != "b"
            }
            w = np.asarray(layer["w"])
            if w_dtype is not None:
                layer["w"] = layer["w"].astype(w_dtype)
            bits = engine.bits_of(layer.get("b_adc_buf")) or acfg.b_adc
            r_adc = abs(float(layer["r_adc"]))
            w_max = float(layer["w_clip_buf"][1])
            out_scale = float(layer["out_scale_buf"])
            r_dac = r_adc * abs(float(gain_s)) / (abs(w_max) + 1e-9)
            # (batch, positions, features), as the model calls the layer
            x = rng.standard_normal((1, rows, w.shape[0]))
            x = np.asarray(jnp.asarray(x * r_dac / 2, dtype), np.float64)
            # no activation on a DAC tie, where the code would depend on
            # how the device rounds a division (a TPU divides through a
            # reciprocal): bf16 activations sit on ties often when the DAC
            # step is near a power of two, as 1/255 is at unit ranges
            frac = np.abs(x / ((r_dac + 1e-9) / (2**bits - 1))) % 1.0
            x[np.abs(frac - 0.5) < 1e-3] = 0.0
            y = np.asarray(run(layer, jnp.asarray(x, dtype), gain_s),
                           np.float64)
            want = reference_mvm(
                x, w, r_adc,
                float(gain_s), w_max, out_scale, bits, acfg.tile_rows,
            )
            # rounded as the device rounds its f32 result to the model dtype
            want = np.asarray(
                jnp.asarray(want, jnp.float32).astype(dtype), np.float64
            )
            scale = (r_adc + 1e-9) / (2 ** (bits - 1) - 1) * out_scale
            share = float(np.mean(np.abs(y - want) > 0.5 * scale))
            worst = max(worst, share)
    return worst


def served_logits(report) -> dict:
    """rid -> (tokens, logits) of a serving report, copied to the host."""
    return {r.rid: (r.tokens, r.logits) for r in report.records}


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def serve_phase(model_args, extra=()):
    """Phase b: serve the trace through the ``serve`` CLI in this process.

    Returns ``(report, engine, argv)``: the engine holds the programmed
    chip that served.
    """
    from repro.launch import serve

    argv = [*model_args, *TRACE, *extra]
    _log("serve " + " ".join(argv))
    with phase("smoke.serve") as served:
        report, eng = serve.run(argv)
    if report.n_requests != N_REQUESTS:
        raise AssertionError(
            f"{report.n_requests} of {N_REQUESTS} requests retired"
        )
    if report.program_events_delta != 0:
        raise AssertionError(
            f"serving reprogrammed the chip: program_events_delta="
            f"{report.program_events_delta}"
        )
    if any(r.logits is None or r.logits.shape[0] != r.n_new
           for r in report.records):
        raise AssertionError("a request record lacks its logits")
    _log(f"served {report.n_requests} requests, {report.n_generated} "
         f"tokens: serve.run wall={served['wall']:.3f}s (run "
         f"{report.wall:.3f}s)")
    return report, eng, argv


def reference_phase(report, eng, argv) -> dict:
    """Phase c: served logits vs a full-sequence forward on the chip, and
    every programmed layer vs a float64 reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch import serve
    from repro.models.lm import lm_forward

    args = serve.build_parser().parse_args(argv)
    cfg = serve.model_config(args)
    prompts = {r.rid: r.prompt for r in serve.request_trace(args, cfg)}
    if eng.acfg.use_kernel:
        raise ValueError("the reference replays the jnp execute path")

    # one padded length for every request: one compile, and under causal
    # attention the padding cannot reach the compared positions
    s_len = eng.s_max

    @jax.jit
    def forward(params, tokens):
        return lm_forward(params, {"tokens": tokens}, eng.acfg, cfg)[0][0]

    errs, shifted = [], []
    with jax.default_matmul_precision("highest"):
        for rec in sorted(report.records, key=lambda r: r.rid):
            seq = np.concatenate([prompts[rec.rid], rec.tokens[:-1]])
            padded = np.zeros((1, s_len), np.int32)
            padded[0, : seq.size] = seq
            lo = rec.n_prompt - 1
            ref = np.asarray(
                forward(eng.params, padded)[lo : lo + rec.n_new], np.float32
            )
            errs.append(rel_err(rec.logits, ref))
            # wiring control: each served position against the next one's
            shifted.append(rel_err(rec.logits[:-1], ref[1:]))
    err, shifted = np.concatenate(errs), np.concatenate(shifted)
    out = {
        "positions": int(err.size),
        "max_rel_err": float(err.max()),
        "median_rel_err": float(np.median(err)),
        "shifted_median_rel_err": float(np.median(shifted)),
    }
    out["code_mismatch"] = code_mismatch(eng.params, eng.acfg, cfg.dtype)
    # control: the same layer call with its MVM run in bf16
    out["bf16_code_mismatch"] = code_mismatch(
        eng.params, eng.acfg, cfg.dtype, w_dtype=jnp.bfloat16
    )
    _log(f"phase c: served vs full forward over {out['positions']} "
         f"positions: max rel_err={out['max_rel_err']!r} median="
         f"{out['median_rel_err']!r} (tolerance {TOL_LOGITS}); one position "
         f"off: median rel_err={out['shifted_median_rel_err']!r}")
    _log(f"phase c: layer outputs vs float64, worst layer: "
         f"{out['code_mismatch']!r} differ (tolerance {TOL_CODES}); with "
         f"the MVM in bf16 {out['bf16_code_mismatch']!r}")
    if not out["max_rel_err"] <= TOL_LOGITS:
        raise AssertionError(f"phase c: served logits off the reference: {out}")
    if not out["shifted_median_rel_err"] > TOL_LOGITS:
        raise AssertionError(
            f"phase c: TOL_LOGITS cannot tell adjacent positions apart: {out}"
        )
    if not out["code_mismatch"] <= TOL_CODES:
        raise AssertionError(f"phase c: layers off the reference: {out}")
    if not out["bf16_code_mismatch"] > TOL_CODES:
        raise AssertionError(
            f"phase c: TOL_CODES cannot tell a bf16 MVM from an f32 one: "
            f"{out}"
        )
    return out


def kernel_phase(model_args, base: dict, *, native: bool = True) -> float:
    """Phase d: the trace through the Pallas MVM kernel vs phase b."""
    import jax.numpy as jnp

    from benchmarks.common import pallas_calls
    from repro.launch import serve
    from repro.models.lm import lm_forward

    report, eng, argv = serve_phase(
        model_args, extra=("--use-kernel", "--no-ref-check")
    )
    cfg = serve.model_config(serve.build_parser().parse_args(argv))
    flags = [
        bool(p["interpret"]) for p in pallas_calls(
            lambda p, t: lm_forward(p, {"tokens": t}, eng.acfg, cfg)[0],
            eng.params, jnp.zeros((1, 8), jnp.int32),
        )
    ]
    _log(f"phase d: {len(flags)} pallas_call(s) on the forward, "
         f"interpret={sorted(set(flags))}")
    if not flags or any(f == native for f in flags):
        raise AssertionError(
            f"phase d: want every pallas_call with interpret={not native}, "
            f"got {flags}"
        )
    codes = code_mismatch(eng.params, eng.acfg, cfg.dtype)
    _log(f"phase d: kernel layer outputs vs float64, worst layer: "
         f"{codes!r} differ (tolerance {TOL_CODES})")
    if not codes <= TOL_CODES:
        raise AssertionError(f"phase d: kernel codes off: {codes!r}")
    return compare_served("phase d: kernel vs jnp oracle",
                          served_logits(report), base)


def four_chip_phase(model_args, n_chips: int = 4) -> dict:
    """The sharded chip (``--mesh-model``) vs the chip on device 0 alone."""
    import jax
    import numpy as np

    # the digital reference counters stay off: they would add compiles and
    # nothing to this comparison
    extra = ("--no-ref-check",)
    report, eng, _ = serve_phase(
        model_args, extra=extra + ("--mesh-model", str(n_chips))
    )
    spans = {}
    for path, node in analog_layers(eng.params):
        sh = node["w"].sharding
        spans[path] = len(sh.device_set)
        if len(sh.device_set) != n_chips or sh.is_fully_replicated:
            raise AssertionError(
                f"{path}: programmed weights on {sh} -- not sharded over "
                f"{n_chips} devices"
            )
    # the source weights (the digital reference and the refresh source)
    # are laid out on the mesh too, not left whole on device 0
    src_spans = {
        len(a.sharding.device_set) for a in jax.tree.leaves(eng.src_params)
    }
    if src_spans != {n_chips}:
        raise AssertionError(
            f"source weights span {sorted(src_spans)} devices, not {n_chips}"
        )
    per_device = [
        (d.id, (d.memory_stats() or {}).get("peak_bytes_in_use"))
        for d in jax.devices()
    ]
    _log(f"four chips: {len(spans)} analog layers, each sharded over "
         f"{n_chips} devices; peak_bytes_in_use per device {per_device}")
    sharded = served_logits(report)
    w_sharded = {p: np.asarray(n["w"]) for p, n in analog_layers(eng.params)}
    del report, eng
    gc.collect()

    report, eng, _ = serve_phase(model_args, extra=extra)
    n_diff = 0
    for path, node in analog_layers(eng.params):
        if not np.array_equal(np.asarray(node["w"]), w_sharded[path]):
            n_diff += 1
            _log(f"  w_eff differs at {path}")
    _log(f"four chips vs one: w_eff bit-identical in "
         f"{len(w_sharded) - n_diff} of {len(w_sharded)} layers")
    if n_diff:
        raise AssertionError(f"{n_diff} programmed layers differ")
    worst = compare_served("four chips vs one: logits",
                           sharded, served_logits(report))
    return {"layers": len(w_sharded), "max_rel_err": worst}


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded four-chip path and its "
                         "one-chip comparison")
    args = ap.parse_args(argv)
    _import_repro()
    import jax

    from repro.launch import compile_cache

    n_chips = 4 if args.four_chips else 1
    info = device_gate(n_chips)
    _log(f"compile cache: {compile_cache.enable()}")

    if args.four_chips:
        with phase("smoke.four_chip") as p:
            four_chip_phase(PUBLISHED, n_chips)
        _log(f"four-chip phase: {p['wall']:.3f}s, "
             f"backend compile {p['compile']:.3f}s")
    else:
        with phase("smoke.b") as p:
            report, eng, argv_b = serve_phase(PUBLISHED)
        stats = jax.devices()[0].memory_stats() or {}
        _log(f"phase b: {p['wall']:.3f}s, backend compile "
             f"{p['compile']:.3f}s, peak_bytes_in_use="
             f"{stats.get('peak_bytes_in_use')} of "
             f"{stats.get('bytes_limit')}")
        _log(report.summary())

        with phase("smoke.c") as p:
            reference_phase(report, eng, argv_b)
        _log(f"phase c: {p['wall']:.3f}s, backend compile "
             f"{p['compile']:.3f}s")
        base = served_logits(report)
        del report, eng
        gc.collect()

        with phase("smoke.d") as p:
            kernel_phase(PUBLISHED, base)
        _log(f"phase d: {p['wall']:.3f}s, backend compile "
             f"{p['compile']:.3f}s")
        stats = jax.devices()[0].memory_stats() or {}
        _log(f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}")

    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
