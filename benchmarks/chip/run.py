"""Run one cell of ``BENCHMARK.json`` once, on the chips of this machine.

    python3 -m benchmarks.chip.run --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Everything is found by name: the cell's entry in ``BENCHMARK.json`` names a
configuration (``configs/<config>.json``, whose ``"path"`` picks the window
runner ``paths/<path>.py``) and a traffic mix (``traffic/<mix>.json``, read
by ``traffic.py``); each per-layer metric is read by ``metrics/<name>.py``.
A new cell, configuration, mix or metric is new files plus new entries.

A run fails (exit 2, no result) where JAX finds no TPU or fewer chips than
the cell asks for, and where the program (``src/repro``) is missing. It
keeps JAX's compilation cache in ``<checkout>/.jax_cache``, makes its
inputs from ``--seed``, warms up the cell's own shapes (that is
``setup_s``), measures for ``--seconds``, and then checks what the window
produced against the plain reference. With ``--trace 1`` it profiles part
of the window and reports the per-layer metrics instead of the end-to-end
ones. Side numbers go to earlier lines; the last line of standard output is
the JSON result, and the numbers compared, each beside its limit, are the
last lines of standard error.
"""
from __future__ import annotations

import argparse
import contextlib
import importlib
import importlib.util
import json
import os
import shutil
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_traces"


class NotRunnable(Exception):
    """The run cannot produce a result here (exit 2)."""


# ------------------------------------------------------------------ cells
def load_cell(name: str, root: Path = ROOT, bench_dir: Path = HERE) -> dict:
    """The cell's entry with its configuration, mix and metric entries."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise NotRunnable(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]

    def applies(m):
        return name in m.get("workloads", [name])

    return {
        "name": name, "chips": cell["chips"],
        "config": json.loads((root / conf["file"]).read_text()),
        "traffic": json.loads(
            (bench_dir / "traffic" / f"{cell['traffic']}.json").read_text()),
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
        "bench_dir": bench_dir,
    }


def reader(bench_dir: Path, metric: str):
    """``read(ctx)`` of ``metrics/<metric>.py``: a number, or None where
    the run gave it nothing to read."""
    path = bench_dir / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"_bench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def runner_class(config: dict):
    return importlib.import_module(
        f"{__package__}.paths.{config['path']}").Runner


# ------------------------------------------------------------ measurement
@contextlib.contextmanager
def compile_events():
    """Count XLA compilations and their seconds inside the block."""
    import jax.monitoring

    seen = {"n": 0, "s": 0.0}

    def listen(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            seen["n"] += 1
            seen["s"] += secs

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        yield seen
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)


class Tracer:
    """Profiles what the runner brackets with start()/stop(), inside a
    ``bench:window`` span; a no-op when tracing is off."""

    def __init__(self, out_dir):
        self.out_dir, self.ann = out_dir, None

    def start(self):
        if self.out_dir is None:
            return
        import jax

        jax.profiler.start_trace(str(self.out_dir))
        self.ann = jax.profiler.TraceAnnotation("bench:window")
        self.ann.__enter__()

    def stop(self):
        if self.ann is None:
            return
        import jax

        self.ann.__exit__(None, None, None)
        self.ann = None
        jax.profiler.stop_trace()


def span(name: str):
    import jax

    return jax.profiler.TraceAnnotation(f"bench:{name}")


def device_info(chips: int) -> dict:
    import jax

    devs = jax.devices()
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devs[:chips]]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs),
            "memory_peak_bytes": max((p for p in peaks if p is not None),
                                     default=None)}


def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             log=print) -> dict:
    """Set up, measure and check one cell; return the result object."""
    import jax

    cfg, mix = cell["config"], cell["traffic"]
    drv = runner_class(cfg)(cfg, mix, seed)
    t0 = time.perf_counter()
    with compile_events() as setup_compiles:
        setup_side = drv.setup()
    setup_s = time.perf_counter() - t0
    log(json.dumps({"setup": setup_side, "setup_s": setup_s,
                    "compiles": setup_compiles}))
    trace_dir = None
    if trace:
        trace_dir = TRACE_DIR / cell["name"]
        shutil.rmtree(trace_dir, ignore_errors=True)
    with compile_events() as window_compiles:
        samples = drv.window(seconds, span, Tracer(trace_dir))
    log(json.dumps({"compiles_in_window": window_compiles["n"],
                    "compile_s_in_window": window_compiles["s"]}))
    log(json.dumps({"side": drv.side()}))
    device = device_info(cell["chips"])
    metrics, breakdown = {}, None
    if trace:
        from . import trace_reduce

        reduced = trace_reduce.reduce(trace_reduce.load(str(trace_dir)))
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        breakdown = {k: reduced[k] for k in ("device_ops", "idle_gaps")}
        ctx = {"trace": reduced, "samples": samples, "config": cfg,
               "traffic": mix}
        for m in cell["per_layer"]:
            value = reader(cell["bench_dir"], m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        e2e = drv.end_to_end(samples)
        e2e["setup_s"] = setup_s
        for m in cell["end_to_end"]:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    drv.release()
    jax.clear_caches()
    checks = drv.check()
    correct = all(c["limit"] is not None and c["value"] <= c["limit"]
                  for c in checks.values())
    out = {"correct": correct, "attempted": samples.get("attempted", 1),
           "failed": samples.get("failed", 0), "metrics": metrics,
           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        if not (ROOT / "src" / "repro").is_dir():
            raise NotRunnable(f"the program is missing: no {ROOT / 'src/repro'}")
        cell = load_cell(args.workload)
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
        sys.path.insert(0, str(ROOT / "src"))
        import jax

        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        devs = jax.devices()
        if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
            raise NotRunnable(
                f"needs {cell['chips']} TPU chip(s), found {len(devs)} "
                f"{devs[0].platform} device(s)")
    except NotRunnable as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    print(json.dumps({"device": device_info(cell["chips"])}), flush=True)
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                   log=lambda s: print(s, flush=True))
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
