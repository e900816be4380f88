#!/usr/bin/env python3
"""Find the knee of a served LM cell: the highest offered rate with no
growing backlog. Run once, on the chip, when a cell is defined; the
cell's mix then fixes half of it.

    python3 benchmarks/chip/sweep.py --workload olmo1b-b8-conv \\
        --rates 1,2,4 --seconds 30 --seeds 5,6 [--workload ... --rates ...]

One process programs the chip and warms it once per configuration, then
serves each (cell, rate, seed) window of the mix at that rate. Per window
it prints one JSON line: time to first token p50/p90, requests due and
served, and the backlog (requests due but not yet admitted) at the
window's middle and close. A backlog that grows from middle to close
means the rate is past the knee.
"""

from __future__ import annotations

import argparse
import bisect
import gc
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks.chip import gen, harness, run, stats  # noqa: E402
from benchmarks.chip.drivers import lm_serve  # noqa: E402


def backlog(due: dict, tokens: dict, t: float) -> int:
    """Window requests due by ``t`` that had no first token by ``t``."""
    firsts = sorted(tokens[r][0] for r in due if r in tokens)
    arrivals = sorted(due.values())
    return bisect.bisect_right(arrivals, t) - bisect.bisect_right(firsts, t)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", action="append", required=True)
    ap.add_argument("--rates", action="append", required=True,
                    help="comma list, one per --workload")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True, help="comma list")
    args = ap.parse_args(argv)
    run.setup_jax(ROOT)
    harness.require_chips(1)
    built = {}
    for cell_name, rates in zip(args.workload, args.rates):
        _, cell, config, mix = run.load_cell(ROOT, cell_name)
        for seed in (int(s) for s in args.seeds.split(",")):
            if cell["config"] not in built:
                built.clear()
                gc.collect()
                ctx = harness.Context(ROOT, cell, config, mix, seed, 1.0, False,
                                      time.perf_counter())
                eng, _ = lm_serve.build(ctx)
                lm_serve.warm_up(eng, int(config["vocab_size"]))
                built[cell["config"]] = eng
            eng = built[cell["config"]]
            for rate in (float(r) for r in rates.split(",")):
                rmix = dict(mix, rate_per_s=rate)
                ctx = harness.Context(ROOT, cell, config, rmix, seed,
                                      args.seconds, False, time.perf_counter())
                reqs = gen.requests(rmix, seed, args.seconds, int(config["vocab_size"]))
                served = lm_serve.serve(ctx, eng, reqs)
                m = stats.serve_metrics(served["due"], served["tokens"], ctx.t_open,
                                        ctx.t_close, served["t_stop"])
                ttft = [served["tokens"][r][0] - t for r, t in served["due"].items()
                        if r in served["tokens"]]
                mid = ctx.t_open + args.seconds / 2
                steps = ctx.window_spans("decode_step")
                print(json.dumps({
                    "workload": cell_name, "rate_per_s": rate, "seed": seed,
                    "due": m["attempted"], "no_first_token": m["failed"],
                    "ttft_p50_ms": stats.percentile(ttft, 50) * 1e3 if ttft else None,
                    "ttft_p90_ms": m["ttft_p90_ms"], "tpot_ms": m["tpot_ms"],
                    "itl_p99_ms": m["itl_p99_ms"],
                    "backlog_mid": backlog(served["due"], served["tokens"], mid),
                    "backlog_close": backlog(served["due"], served["tokens"], ctx.t_close),
                    "mean_active": (sum(s.attrs["n_active"] for s in steps)
                                    / max(len(steps), 1)),
                    "compiles_in_window": ctx.compiles_in_window,
                }), flush=True)
                del served
                gc.collect()


if __name__ == "__main__":
    main()
