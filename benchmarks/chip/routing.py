#!/usr/bin/env python3
"""How far routing moves the program's logits off the reference's, on one
programmed chip, for a configuration with dropless MoE layers.

    python3 benchmarks/chip/routing.py --config lfm2moe-b8 --seeds 11,12 \\
        [--rows 4] [--length 2048] [--b-adc 6]

For each seed the chip is programmed as the cell's driver programs it and
the plain reference rebuilds it. ``--rows`` sequences of ``--length``
uniform token ids drawn from the seed then run, each in one causal pass
with no cache, through

* the program's ``lm_forward``, recording every MoE layer's chosen experts,
* the reference's ``forward``, recording its chosen experts, and
* the program again with the reference's choice imposed in every layer.

At every position the gap is the check's: the reference's best logit minus
its logit of the program's argmax. A position's routing disagrees where
some layer chose another set of experts than the reference. Prints one
JSON line a seed: the share of positions whose routing disagrees, and the
gaps' max, p99, mean and share above 0 over the positions that disagree,
those that agree, and all of them with the reference's routing imposed.
``--b-adc 6`` programs the program's chip with the control's 6-bit ADCs
(``control.py``); the reference keeps the configuration's.

Neither the benchmark nor the tests run this; PERF.md gives what it read.
Needs the chip, like ``run.py``.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402


def _stats(g: np.ndarray) -> dict:
    if not g.size:
        return {"n": 0}
    return {"n": int(g.size), "max": float(g.max()),
            "p99": float(np.quantile(g, 0.99)), "mean": float(g.mean()),
            "share_off": float((g > 0).mean())}


def readings(c: dict, seed: int, rows: int, length: int, b_adc=None) -> dict:
    """The one line of a seed (see the module's docstring)."""
    import jax
    import jax.numpy as jnp

    from benchmarks.chip import gen, weights
    from benchmarks.chip.drivers import lfm2moe_serve as drv
    from benchmarks.chip.reference import lfm2_moe as ref
    from repro.core.analog import AnalogConfig
    from repro.launch import steps
    from repro.models import lm
    from repro.models import moe as moe_lib

    mc = drv.model_config(c)
    shapes = jax.eval_shape(lambda k: lm.lm_init(k, mc), jax.random.PRNGKey(0))
    acfg = AnalogConfig().infer(
        b_adc=int(b_adc or c["b_adc"]), t_seconds=float(c["t_hours"]) * 3600.0)
    program = steps.program_for_serving(
        drv.fill(shapes, seed), acfg, weights.seed_key(seed, "program"))
    params, pcfg = program.params, program.cfg
    del program  # the PCM state: only the effective weights are read here
    state = ref.program(c, seed, float(c["t_hours"]) * 3600.0)
    toks = gen.rng_for(seed, "routing").integers(
        0, int(c["vocab_size"]), (rows, length)).astype(np.int32)

    route = moe_lib.route_sigmoid_bias
    seen: list = []

    def recorded(logits, bias, k):
        ids, w = route(logits, bias, k)
        seen.append(ids)
        return ids, w

    def imposed(given):
        it = iter(given)

        def choose(logits, bias, k):
            ids = next(it)
            s = jax.nn.sigmoid(logits.astype(jnp.float32))
            w = jnp.take_along_axis(s, ids, axis=-1)
            return ids, w / jnp.sum(w, axis=-1, keepdims=True)

        return choose

    def program_fwd(p, t, given=None):
        seen.clear()
        moe_lib.route_sigmoid_bias = recorded if given is None else imposed(given)
        try:
            lg, _ = lm.lm_forward(p, {"tokens": t[None]}, pcfg, mc)
        finally:
            moe_lib.route_sigmoid_bias = route
        return lg[0].astype(jnp.float32), jnp.stack(seen) if seen else None

    ref_route = ref.route

    def ref_recorded(x, router, bias, k):
        ids, w = ref_route(x, router, bias, k)
        seen.append(ids.astype(jnp.int32))
        return ids, w

    def ref_fwd(st, t):
        seen.clear()
        ref.route = ref_recorded
        try:
            lg = ref.forward.__wrapped__(st, t, dims=ref.dims(c), bits=int(c["b_adc"]))
        finally:
            ref.route = ref_route
        return lg, jnp.stack(seen)

    def gap(ref_lg, lg):
        pick = jnp.argmax(lg, axis=-1)
        return ref_lg.max(-1) - jnp.take_along_axis(ref_lg, pick[:, None], -1)[:, 0]

    free_fn = jax.jit(lambda p, t: program_fwd(p, t))
    ref_fn = jax.jit(ref_fwd)
    forced_fn = jax.jit(lambda p, t, ids: program_fwd(p, t, list(ids)))
    g_free, g_forced, off = [], [], []
    for t in toks:
        t = jnp.asarray(t)
        lg, ids = free_fn(params, t)
        r_lg, r_ids = ref_fn(state, t)
        f_lg, _ = forced_fn(params, t, r_ids)
        same = jnp.all(jnp.sort(ids, -1) == jnp.sort(r_ids, -1), axis=-1)  # (L, S)
        off.append(np.asarray(~jnp.all(same, axis=0)))
        g_free.append(np.asarray(gap(r_lg, lg)))
        g_forced.append(np.asarray(gap(r_lg, f_lg)))
    g_free, g_forced, off = map(np.concatenate, (g_free, g_forced, off))
    return {"seed": seed, "b_adc": acfg.b_adc, "positions": int(off.size),
            "routing_disagrees_share": float(off.mean()),
            "disagreeing": _stats(g_free[off]), "agreeing": _stats(g_free[~off]),
            "all": _stats(g_free), "routing_imposed": _stats(g_forced)}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", required=True, help="comma list")
    ap.add_argument("--rows", type=int, default=4)
    ap.add_argument("--length", type=int, default=2048)
    ap.add_argument("--b-adc", type=int, default=None)
    args = ap.parse_args(argv)
    c = json.loads((ROOT / "benchmarks/chip/configs" / f"{args.config}.json").read_text())
    for seed in (int(s) for s in args.seeds.split(",")):
        line = readings(c, seed, args.rows, args.length, args.b_adc)
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
