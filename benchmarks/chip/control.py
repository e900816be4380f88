#!/usr/bin/env python3
"""Readings for the limits of ``correct``: one cell over many seeds in one
process, as the program states it or with the control's lower precision.

    python3 benchmarks/chip/control.py --workload olmo1b-b8-conv \\
        --seeds 11,12,13 --seconds 15 [--b-adc 6] [--mvm-dtype bfloat16]

Each seed is one whole run of the cell (``run.main``: set-up, window,
reference check) and prints its result line. The controls lower one
precision of the program while the reference keeps the configuration's.
``--b-adc 6`` programs the chip with 6-bit ADCs (7-bit DACs), the
program's own next precision below the configuration's 8 bits: the
control, whose readings set the upper end of each limit. ``--mvm-dtype
bfloat16`` runs every crossbar MVM on bf16 weights and DAC levels (the
configuration's MVM is f32 at ``Precision.HIGHEST``); its readings fall
inside the sound runs' range (see PERF.md).
The benchmark's own runs never run this; it is how the limits in
``configs/*.json`` were read (see PERF.md). Needs the chip, like
``run.py``.
"""

from __future__ import annotations

import argparse
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from benchmarks.chip import run  # noqa: E402


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma list")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--b-adc", type=int, default=None)
    ap.add_argument("--mvm-dtype", default=None)
    args = ap.parse_args(argv)
    overrides = {}
    if args.b_adc is not None:
        overrides["b_adc"] = args.b_adc
    if args.mvm_dtype is not None:
        overrides["mvm_dtype"] = args.mvm_dtype
    for seed in (int(s) for s in args.seeds.split(",")):
        run.main(["--workload", args.workload, "--seed", str(seed),
                  "--seconds", str(args.seconds), "--trace", "0"],
                 overrides=overrides)


if __name__ == "__main__":
    main()
