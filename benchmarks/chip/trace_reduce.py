"""From a profiler trace to device busy time, op times and idle gaps.

:func:`load` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a
small event record; :func:`reduce` turns that record into the numbers the
per-layer metrics read. The record is plain JSON, so a recorded one can
be kept and reduced again in a test.

Record layout::

    {"devices": {plane: {"ops": [[name, start_ns, dur_ns], ...],
                         "modules": [[name, start_ns, dur_ns], ...]}},
     "host": [[span name, start_ns, dur_ns], ...]}

Device planes are the ``/device:TPU:<n>`` planes; their ``XLA Ops`` line
holds one event per operation run, the ``XLA Modules`` line one per
program run. Host events are kept only for the harness's own span names
(written with ``jax.profiler.TraceAnnotation``), which share the clock of
the device events.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

#: the harness span that brackets the traced window
WINDOW = "bench_window"


def load(trace_dir: str, span_names) -> dict:
    """The event record of the newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    keep = set(span_names) | {WINDOW}
    rec: dict = {"devices": {}, "host": []}
    for plane in data.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                slot = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(line.name)
                if slot:
                    dev[slot] = [[e.name, e.start_ns, e.duration_ns] for e in line.events]
            rec["devices"][plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                rec["host"] += [[e.name, e.start_ns, e.duration_ns]
                                for e in line.events if e.name in keep]
    return rec


def _union(intervals):
    """Merged, sorted [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _op_name(name: str) -> str:
    """'%convert.9 = f32[8,16]{1,0:T(8,128)} convert(...)' ->
    '%convert.9 f32[8,16] convert': the op, its result and its kind (a
    tuple result is written 'tuple')."""
    if " = " not in name:
        return name[:120]
    op, rest = name.split(" = ", 1)
    kind = re.findall(r"(?:^|[\s}])([a-z][\w-]*)\(", rest)
    result = "tuple" if rest.startswith("(") else re.split(r"[{ ]", rest, 1)[0]
    return f"{op} {result} {kind[0] if kind else '?'}"


def _module_name(name: str) -> str:
    """'jit_decode(12)' -> 'jit_decode'."""
    return re.sub(r"\(\d+\)$", "", name)


def reduce(rec: dict, top: int = 10) -> dict:
    """Busy and idle time, op and program times, longest idle gaps.

    Everything is clipped to the traced window (the :data:`WINDOW` host
    span). ``busy_s`` is the union of operation intervals, averaged over
    the devices; an idle gap is an interval inside the window in which no
    operation runs on a device, named by the host span that overlaps it
    most (``"none"`` where no span is open). Op times are keyed
    ``<program>/<op>``.
    """
    wins = [(s, s + d) for n, s, d in rec["host"] if n == WINDOW]
    if not wins:
        raise ValueError("the trace holds no window span")
    w0, w1 = wins[-1]
    spans = sorted((s, s + d, n) for n, s, d in rec["host"] if n != WINDOW)
    span_starts = [s for s, _, _ in spans]
    busy_total, ops, modules, gaps = 0.0, {}, {}, []
    for dev in rec["devices"].values():
        mods = sorted((s, s + d, _module_name(n)) for n, s, d in dev["modules"]
                      if s < w1 and s + d > w0)
        starts = [m[0] for m in mods]
        for s, e, n in mods:
            t, c = modules.get(n, (0.0, 0))
            modules[n] = (t + (min(e, w1) - max(s, w0)) * 1e-9, c + 1)
        ivals = []
        for name, s, d in dev["ops"]:
            s0, e0 = max(s, w0), min(s + d, w1)
            if e0 <= s0:
                continue
            ivals.append((s0, e0))
            i = bisect.bisect_right(starts, s) - 1
            prog = mods[i][2] if i >= 0 and mods[i][1] >= s else "?"
            key = f"{prog}/{_op_name(name)}"
            ops[key] = ops.get(key, 0.0) + (e0 - s0) * 1e-9
        merged = _union(ivals)
        busy_total += sum(e - s for s, e in merged) * 1e-9
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 > g0:
                gaps.append((_span_at(spans, span_starts, g0, g1),
                             (g1 - g0) * 1e-9))
    n_dev = max(len(rec["devices"]), 1)
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy_total / n_dev,
        "n_devices": len(rec["devices"]),
        "modules": {n: {"s": t / n_dev, "calls": c / n_dev}
                    for n, (t, c) in modules.items()},
        "device_ops": sorted(([k, v / n_dev] for k, v in ops.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(([n, s] for n, s in gaps), key=lambda g: -g[1])[:top],
    }


def _span_at(spans, starts, g0: int, g1: int) -> str:
    """The span overlapping [g0, g1) most, or 'none'. The harness's spans
    follow one another, so only the few that start last before g1 can
    overlap the gap."""
    best, name = 0, "none"
    i = bisect.bisect_left(starts, g1)
    for s, e, n in spans[max(i - 4, 0):i]:
        ov = min(e, g1) - max(s, g0)
        if ov > best:
            best, name = ov, n
    return name
