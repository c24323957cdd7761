"""Reduce a JAX profiler trace to the benchmark's device numbers.

``load(trace_dir)`` reads the newest ``*.xplane.pb`` under ``trace_dir``
with ``jax.profiler.ProfileData`` and keeps three things: every device
operation (``XLA Ops`` line of each ``/device:TPU:<n>`` plane), every device
program execution (``XLA Modules`` line), and the host spans the harness
opened with ``jax.profiler.TraceAnnotation`` (names starting ``bench:``).
The result is a plain dict (see ``SYNTHETIC`` in the tests for its shape),
so ``reduce`` can be checked on a small file checked in beside them.

``reduce(space)`` gives, over the traced window (the ``bench:window`` span,
else the extent of the device operations):

* ``busy_s`` — the union of the intervals in which an operation ran on a
  device, clipped to the window, averaged over the devices;
* ``window_s`` — the window's length;
* ``op_s`` / ``module_s`` — device seconds by the name the trace prints,
  summed over devices and divided by their number;
* ``device_ops`` — the 10 operations that took the most time;
* ``idle_gaps`` — the 10 longest gaps in device 0's busy union, each
  labelled with the innermost harness span that covered its midpoint.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench:"
WINDOW_SPAN = SPAN_PREFIX + "window"
TOP = 10


def load(trace_dir: str) -> dict:
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(max(files, key=os.path.getmtime))
    devices, spans = [], []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            dev = {"name": plane.name, "ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(
                    line.name)
                if key:
                    dev[key] = [(e.name, e.start_ns, e.duration_ns)
                                for e in line.events]
            devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name[len(SPAN_PREFIX):], e.start_ns,
                           e.duration_ns) for e in line.events
                          if e.name.startswith(SPAN_PREFIX)]
    return {"devices": devices, "spans": spans}


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _label(spans, t: float) -> str:
    """Innermost harness span (other than the window) covering ``t``."""
    best = None
    for name, s, d in spans:
        if name != "window" and s <= t <= s + d and (best is None
                                                     or d < best[1]):
            best = (name, d)
    return best[0] if best else "outside spans"


def _by_name(events, lo, hi, n_dev) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for name, s, d in events:
        d = min(s + d, hi) - max(s, lo)
        if d > 0:
            out[name] = out.get(name, 0.0) + d * 1e-9 / n_dev
    return out


def reduce(space: dict) -> dict:
    devices = [d for d in space["devices"] if d["ops"]]
    if not devices:
        raise ValueError("the trace holds no device operation")
    win = [(s, s + d) for n, s, d in space["spans"]
           if n == WINDOW_SPAN[len(SPAN_PREFIX):]]
    if win:
        lo, hi = win[0][0], win[-1][1]
    else:
        lo = min(s for d in devices for _, s, _ in d["ops"])
        hi = max(s + du for d in devices for _, s, du in d["ops"])
    n_dev = len(devices)
    busy = []
    for d in devices:
        merged = _clip(_union([(s, s + du) for _, s, du in d["ops"]]), lo, hi)
        busy.append(merged)
    busy_s = sum(e - s for m in busy for s, e in m) * 1e-9 / n_dev
    ops: Dict[str, float] = {}
    modules: Dict[str, float] = {}
    for d in devices:
        for k, v in _by_name(d["ops"], lo, hi, n_dev).items():
            ops[k] = ops.get(k, 0.0) + v
        for k, v in _by_name(d["modules"], lo, hi, n_dev).items():
            modules[k] = modules.get(k, 0.0) + v
    edges = [lo] + [t for s, e in busy[0] for t in (s, e)] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "busy_s": busy_s,
        "window_s": (hi - lo) * 1e-9,
        "devices": n_dev,
        "op_s": ops,
        "module_s": modules,
        "device_ops": sorted(([k, v] for k, v in ops.items()),
                             key=lambda kv: -kv[1])[:TOP],
        "idle_gaps": [[_label(space["spans"], (s + e) / 2), (e - s) * 1e-9]
                      for s, e in gaps[:TOP]],
    }
