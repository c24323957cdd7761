"""repro.obs — opt-in observability: telemetry planes, timelines, manifests.

Layers (see docs/observability.md):

* ``planes``   — device-side metric planes gated by ``MemParams.telemetry``
                 (per-bank per-cause stalls/waits, per-core read/write
                 provenance, queue high-water marks, latency histograms).
* ``timeline`` — Chrome-trace/Perfetto JSON export of replay decisions
                 (write-mode flips, region re-selections, recode backlog,
                 arbiter grants) for ``chrome://tracing`` / ui.perfetto.dev.
* ``runlog``   — structured run manifests (config + static signature, git
                 SHA, device topology, wall times) attached to every
                 ``BENCH_*.json`` by ``benchmarks.common.emit``.
* ``report``   — stall-attribution markdown reports (per-bank heatmap
                 tables, coded vs uncoded) for the fig18/19/20 suites,
                 plus the ``--serve`` request-path section.
* ``serve``    — serving metric planes for the coded KV page pool (bank
                 load/latency histograms, read provenance, recode backlog)
                 and host-side request lifecycle spans (ServeLog).
* ``spans``    — ``span(name, **counts)``: ``repro:``-prefixed profiler
                 spans of the sweep engine, counts as the span's stats.

``core/state.py`` imports ``repro.obs.planes``; everything else here pulls
in the sweep layer, so the submodules load lazily to keep the core import
graph acyclic.
"""
from repro.obs.planes import (HIST_BINS, READ_CLASSES, STALL_CAUSES,
                              WAIT_CAUSES, WRITE_CLASSES, Telemetry,
                              TelemetrySnapshot, init_telemetry, lat_bin,
                              snapshot)

__all__ = [
    "HIST_BINS", "READ_CLASSES", "STALL_CAUSES", "WAIT_CAUSES",
    "WRITE_CLASSES", "Telemetry", "TelemetrySnapshot", "init_telemetry",
    "lat_bin", "snapshot", "timeline", "runlog", "report", "serve",
]


def __getattr__(name):
    if name in ("timeline", "runlog", "report", "serve"):
        import importlib
        return importlib.import_module(f"repro.obs.{name}")
    raise AttributeError(f"module 'repro.obs' has no attribute {name!r}")
