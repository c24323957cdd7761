#!/usr/bin/env python3
"""Walk trips per simulated cycle of one chip-benchmark call, counted on
the CPU.

The read and write pattern builders (``repro.core.controller``) are each a
``lax.while_loop`` with one trip per queued candidate. Under the sweep
engine's ``vmap`` a walk runs as many trips as the fullest point of the
batch needs. This script runs call ``--call`` of a mix (the points and
traces ``benchmarks/chip`` makes from ``--seed``) through the sweep engine
on the CPU, with each walk's trip bound reported to the host every cycle,
and prints the batch-max read and write trips per cycle: the mean over
the call and over its first ``--first`` cycles. The program is
bit-identical across platforms, so the counts are the chip's.

    JAX_PLATFORMS=cpu PYTHONPATH=src python scripts/walk_trips.py \\
        --mix coded_zoo --seed 1234

Prints one JSON line. Divide a call's device time by its cycles and by
the walk trips per cycle to price a walk trip.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
CONFIG = "paper_memsys"


@contextlib.contextmanager
def counting_walks(records):
    """Report each walk's trip bound (per point, every cycle) into
    ``records[kind]`` while programs are traced under this context."""
    import jax
    import jax.numpy as jnp
    from repro.core import controller as ctl

    orig = {name: getattr(ctl, name) for name in
            ("_walk_bounds", "build_read_pattern", "build_write_pattern")}
    kind = []

    def walk_bounds(cand_age, cand_valid):
        rank, n_trips = orig["_walk_bounds"](cand_age, cand_valid)
        sink = records[kind[-1]]

        def record(trips):
            sink.append(trips.copy())
            return trips

        seen = jax.pure_callback(
            record, jax.ShapeDtypeStruct((), jnp.int32), n_trips,
            vmap_method="expand_dims")
        return rank, jnp.minimum(n_trips, seen)   # keeps the callback live

    def builder(name, k):
        def run(*args, **kwargs):
            kind.append(k)
            try:
                return orig[name](*args, **kwargs)
            finally:
                kind.pop()
        return run

    ctl._walk_bounds = walk_bounds
    ctl.build_read_pattern = builder("build_read_pattern", "read")
    ctl.build_write_pattern = builder("build_write_pattern", "write")
    try:
        yield
    finally:
        for name, fn in orig.items():
            setattr(ctl, name, fn)


def count(mix_name: str, seed: int, call: int = 0, first: int = 670):
    import numpy as np

    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    from benchmarks.chip import traffic
    from benchmarks.chip.paths.memsys import device_traces, sweep_points
    from repro import sweep

    cfg = json.loads((ROOT / "benchmarks/chip/configs" /
                      f"{CONFIG}.json").read_text())
    mix = json.loads((ROOT / "benchmarks/chip/traffic" /
                      f"{mix_name}.json").read_text())
    points = traffic.memsys_call(mix, cfg, seed, call)
    records = {"read": [], "write": []}
    with counting_walks(records):
        results = sweep.run_points(sweep_points(cfg, mix, points),
                                   device_traces(points))
    trips = len(records["read"])
    if len(records["write"]) != trips:
        raise RuntimeError(f"the read walk reported {trips} cycles, the "
                           f"write walk {len(records['write'])}")
    # ``loop_trips``: cycles the engine's loop ran (the points step in
    # lockstep until the last is quiescent); ``cycles``: the longest
    # point's simulated cycles
    out = {"mix": mix_name, "seed": seed, "call": call, "loop_trips": trips,
           "cycles": max(r.cycles for r in results), "points": len(results),
           "failed": sum(not r.completed for r in results)}
    for k, recs in records.items():
        per_cycle = np.array([int(np.max(r)) for r in recs])
        per_point = np.stack(recs)                  # (cycles, points)
        out[f"{k}_trips_per_cycle"] = float(per_cycle.mean())
        out[f"{k}_trips_first_{first}"] = float(per_cycle[:first].mean())
        out[f"{k}_candidates_per_point"] = float(per_point.mean())
        out[f"{k}_cycles_walked"] = float(np.mean(per_cycle > 0))
    out["trips_per_cycle"] = (out["read_trips_per_cycle"]
                              + out["write_trips_per_cycle"])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--mix", default="coded_zoo",
                    help="a traffic mix of benchmarks/chip/traffic")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--call", type=int, default=0,
                    help="which of the mix's distinct calls")
    ap.add_argument("--first", type=int, default=670,
                    help="also average over the first this many cycles")
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    print(json.dumps(count(args.mix, args.seed, args.call, args.first)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
