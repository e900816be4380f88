#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark and print its result line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix are found by name in
``BENCHMARK.json`` at the checkout's root; the configuration file names
its driver (``drivers/<driver>.py``) and its plain reference. The last
line of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics, each read by
``metrics/<name>.py``), ``device``, with ``--trace 1`` ``breakdown``,
and last ``checks``: each number compared for ``correct``, with its
limit. The checks are also the last lines of standard error.

Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result. JAX's persistent compilation cache is
``$JAX_COMPILATION_CACHE_DIR`` if set, else ``<root>/.jax_cache``.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.chip import flops  # noqa: E402
from benchmarks.chip import harness  # noqa: E402


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def load_cell(root: pathlib.Path, name: str) -> tuple[dict, dict, dict, dict]:
    """(benchmark, cell, configuration, traffic mix) of a cell name."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"benchmark: no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = json.loads((root / entry["file"]).read_text())
    mix = root / "benchmarks" / "chip" / "traffic" / f"{cell['traffic']}.json"
    return bench, cell, config, json.loads(mix.read_text())


def metrics_of(bench: dict, cell: str, kind: str) -> list[dict]:
    """The cell's ``end_to_end`` or ``per_layer`` metric entries."""
    return [m for m in bench[kind] if cell in m.get("workloads", [cell])]


def reader_path(root: pathlib.Path, name: str) -> pathlib.Path:
    """``metrics/<name>.py``; where that file is absent,
    ``metrics/<stem>.py``, the reader that the metrics ``<stem>.<part>``
    share (one quantity, split by the end-to-end metric it moves)."""
    path = root / "benchmarks" / "chip" / "metrics" / f"{name}.py"
    return path if path.exists() else path.with_name(name.split(".")[0] + ".py")


def read_metric(root: pathlib.Path, name: str, readings) -> "float | None":
    """Run the metric's reader (:func:`reader_path`) on this run's readings."""
    path = reader_path(root, name)
    spec = importlib.util.spec_from_file_location(f"_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(readings)


def setup_jax(root: pathlib.Path) -> None:
    """Import the program and fix the compilation cache's directory."""
    src = root / "src"
    if not (src / "repro").is_dir():
        raise SystemExit(f"benchmark: no program under {src}")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    import jax

    jax.config.update(
        "jax_compilation_cache_dir",
        os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(root / ".jax_cache"),
    )
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None, root: pathlib.Path = ROOT, overrides=None) -> dict:
    args = parse(argv)
    bench, cell, config, mix = load_cell(root, args.workload)
    setup_jax(root)
    info = harness.require_chips(cell["chips"])
    ctx = harness.Context(
        root=root, cell=cell, config=config, traffic=mix, seed=args.seed,
        seconds=args.seconds, trace=bool(args.trace), t_process=T_PROCESS,
        overrides=dict(overrides or {}),
    )
    driver = importlib.import_module(f"benchmarks.chip.drivers.{config['driver']}")
    out = driver.run(ctx)
    if ctx.compiles_in_window:
        raise SystemExit(
            f"benchmark: {ctx.compiles_in_window} compile(s) inside the "
            "window: warm-up missed a shape")

    device = dict(info, memory_peak_bytes=out["memory_peak_bytes"])
    result = {"correct": out["correct"], "attempted": out["attempted"],
              "failed": out["failed"]}
    if args.trace:
        reduced = out["trace"]
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        readings = Readings(ctx, out, reduced, flops.peak(info["kind"]))
        values = {m["name"]: (read_metric(root, m["name"], readings), m["unit"])
                  for m in metrics_of(bench, cell["name"], "per_layer")}
        breakdown = {"device_ops": reduced["device_ops"],
                     "idle_gaps": reduced["idle_gaps"]}
    else:
        values = {m["name"]: (out["metrics"][m["name"]], m["unit"])
                  for m in metrics_of(bench, cell["name"], "end_to_end")}
        breakdown = None
    result["metrics"] = {k: {"value": v, "unit": u}
                         for k, (v, u) in values.items() if v is not None}
    result["device"] = device
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in out["checks"].items()}
    for k, (v, lim) in out["checks"].items():
        ctx.log(f"check {k}: {v!r} (limit {lim!r})")
    print(json.dumps(result), flush=True)
    return result


class Readings:
    """What a per-layer metric reader may read: the window's spans, the
    driver's counters, the reduced trace and the chip's peaks."""

    def __init__(self, ctx, out: dict, trace: dict, peak: dict):
        self.spans = ctx.window_spans()
        self.counters = out["counters"]
        self.trace = trace
        self.peak = peak

    def span_seconds(self, name: str) -> float:
        return sum(s.t1 - s.t0 for s in self.spans if s.name == name)


if __name__ == "__main__":
    main()
