"""Benchmark driver: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. Default budgets are reduced
(CPU-feasible); ``--full`` runs the complete protocol. ``--only <prefix>``
filters benchmarks. ``--json PATH`` additionally writes the rows as a JSON
document (with commit/timestamp metadata when available) -- the nightly CI
workflow uploads it as an artifact so the perf trajectory is recorded
per-commit. ``--require name1,name2`` exits non-zero unless every named
row was produced (and no suite errored out from under it) -- the nightly
gate that keeps tracked rows (program-once speedup, bitwidth sweep,
serve_drift_24h) from silently disappearing.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _row_to_record(row: str) -> dict:
    name, us, derived = (row.split(",", 2) + ["", ""])[:3]
    try:
        us_f = float(us)
    except ValueError:
        us_f = None
    return {"name": name, "us_per_call": us_f, "derived": derived}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma list of suite-name prefixes to run")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write rows as JSON (for CI artifacts)")
    ap.add_argument("--require", default=None, metavar="NAMES",
                    help="comma list of row names that must be present; "
                         "exit 1 if any is missing or any suite errored")
    args = ap.parse_args()
    fast = not args.full

    from repro.launch import compile_cache

    compile_cache.enable()

    from benchmarks import (
        appxC_heuristic,
        fig7_drift,
        fig8_layerwise,
        fig9_micronet,
        fleet_bench,
        kernels_bench,
        pipeline_bench,
        serving_bench,
        table1_ablation,
        table2_aoncim,
        table3_depthwise,
    )

    suites = [
        ("table2_aoncim", table2_aoncim.run),
        ("table3_depthwise", table3_depthwise.run),
        ("fig8_layerwise", fig8_layerwise.run),
        ("pipeline", pipeline_bench.run),
        ("serving", serving_bench.run),
        ("fleet", fleet_bench.run),
        ("kernels", kernels_bench.run),
        ("table1_ablation", table1_ablation.run),
        ("fig7_drift", fig7_drift.run),
        ("fig9_micronet", fig9_micronet.run),
        ("appxC_heuristic", appxC_heuristic.run),
    ]
    only = (
        [p.strip() for p in args.only.split(",") if p.strip()]
        if args.only
        else None
    )
    records: list[dict] = []
    print("name,us_per_call,derived")
    for name, fn in suites:
        if only and not any(name.startswith(p) for p in only):
            continue
        t0 = time.time()
        try:
            for row in fn(fast=fast):
                print(row)
                sys.stdout.flush()
                records.append(_row_to_record(row))
        except Exception as e:  # keep the suite running
            row = f"{name}_ERROR,0,{type(e).__name__}:{e}"
            print(row)
            records.append(_row_to_record(row))
        wall = f"{name}_suite_wall,{(time.time()-t0)*1e6:.0f},"
        print(wall)
        records.append(_row_to_record(wall))

    if args.json:
        doc = {
            "commit": os.environ.get("GITHUB_SHA"),
            "ref": os.environ.get("GITHUB_REF"),
            "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
            "full": args.full,
            "rows": records,
        }
        with open(args.json, "w") as f:
            json.dump(doc, f, indent=1)
        print(f"wrote {len(records)} rows to {args.json}", file=sys.stderr)

    if args.require:
        names = {r["name"] for r in records}
        need = {n.strip() for n in args.require.split(",") if n.strip()}
        missing = sorted(need - names)
        errored = sorted(n for n in names if n.endswith("_ERROR"))
        if missing or errored:
            print(f"required bench rows missing: {missing}; "
                  f"errored suites: {errored}", file=sys.stderr)
            sys.exit(1)


if __name__ == "__main__":
    main()
