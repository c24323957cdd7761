"""Cycle-throughput benchmark: absolute scheduler regression vs trajectory.

Evaluates the 16-point ``bench_sweep`` α×r grid (2 α × 2 r × 2 trace
generators × 2 seeds — one masked compiled program) through the looped
(``sim.ramulator.simulate``, one compile per point) and batched
(``repro.sweep``) pipelines, with a warm repeat of the batched path where
compile cost is amortized away. Per-point results must be identical across
pipelines and across repeats (the engine-equivalence contract; *semantic*
correctness is anchored to the NumPy golden model by
tests/test_conformance.py, not here).

Since the reference scheduler's retirement there is no second implementation
to race, so the gate is the **absolute warm-batched throughput** regressed
against the checked-in perf trajectory: the previous commit's repo-root
``BENCH_cycle_throughput.json`` records warm ``sim_cycles/s``, and this run
fails if it falls below ``--min-frac`` of that baseline (default 0.3 —
deliberately loose on purpose: the trajectory file travels across machines
AND the ``--smoke`` grid differs from the full grid, while warm throughput
is a per-cycle rate that varies far less than 0.3× across either; the
trajectory plot, not the gate, is the precision instrument). Emits
``experiments/bench/BENCH_cycle_throughput.json``; only a *passing full*
run refreshes the repo-root baseline copy — a smoke run must not replace
the full trajectory, and a regressed run must not ratchet the floor down
to its own regressed number.

``--smoke`` shrinks the grid and skips the looped pipeline — CI runs it on
every push and gates against the checked-in (full-run) baseline.
"""
from __future__ import annotations

import argparse
import json
import os

from benchmarks.common import REPO_ROOT, Timer, emit, table
from repro.sim.ramulator import simulate
from repro.sweep import run_points
from repro.sweep.engine import clear_caches
from benchmarks.bench_sweep import make_grid

BASELINE_PATH = os.path.join(REPO_ROOT, "BENCH_cycle_throughput.json")


def _sim_cycles(results) -> int:
    return sum(r.cycles for r in results)


def load_baseline():
    """Warm-batched sim_cycles/s from the checked-in trajectory file, or
    None when absent/unreadable. Deliberately not keyed on grid shape or
    tier: the checked-in baseline is always a full run and the smoke gate
    compares against it too (the loose ``--min-frac`` floor absorbs the
    cross-grid difference — without this, CI's smoke step could never arm)."""
    if not os.path.exists(BASELINE_PATH):
        return None
    try:
        with open(BASELINE_PATH) as f:
            blob = json.load(f)
    except (OSError, ValueError):
        return None
    for row in blob.get("rows", []):
        # current schema has one warm-batched row; the pre-retirement schema
        # carried a scheduler column — take its vectorized row
        if (row.get("path") == "batched (warm)"
                and row.get("scheduler", "vectorized") == "vectorized"):
            return float(row["sim_cycles/s"])
    return None


def run(length: int = 48, n_rows: int = 128, smoke: bool = False,
        min_frac: float = 0.3):
    if smoke:
        length, n_rows = 16, 64
    baseline = load_baseline()
    pts = make_grid(length=length, n_rows=n_rows)
    rows = []
    looped = None
    traces = None
    if not smoke:
        from repro.sweep.workloads import build_trace
        traces = [build_trace(pt) for pt in pts]
        with Timer() as t_loop:
            looped = [simulate(pt.scheme, tr, pt.n_rows, alpha=pt.alpha,
                               r=pt.r, n_cycles=pt.resolved_cycles(),
                               select_period=pt.select_period,
                               wq_hi=pt.wq_hi, wq_lo=pt.wq_lo,
                               queue_depth=pt.queue_depth)
                      for pt, tr in zip(pts, traces)]
        rows.append({"path": "looped", "wall_s": round(t_loop.s, 2),
                     "sim_cycles/s": round(_sim_cycles(looped) / t_loop.s, 1)})
    with Timer() as t_cold:
        batched = run_points(pts, traces=traces)
    with Timer() as t_warm:
        batched2 = run_points(pts, traces=traces)
    assert batched == batched2, "batched path is nondeterministic"
    identical = looped is None or batched == looped
    warm_tput = _sim_cycles(batched) / t_warm.s
    rows.append({"path": "batched (cold)", "wall_s": round(t_cold.s, 2),
                 "sim_cycles/s": round(_sim_cycles(batched) / t_cold.s, 1)})
    rows.append({"path": "batched (warm)", "wall_s": round(t_warm.s, 2),
                 "sim_cycles/s": round(warm_tput, 1)})

    print(f"\n== bench_cycles: {len(pts)}-point grid, length={length}, "
          f"n_rows={n_rows}{' [smoke]' if smoke else ''} ==")
    print(table(rows, ["path", "wall_s", "sim_cycles/s"]))
    ident = "IDENTICAL" if identical else "MISMATCH"
    print(f"per-point results across paths/repeats: {ident}")
    regressed = False
    if baseline is None:
        print("no comparable checked-in baseline — recording trajectory only")
    else:
        frac = warm_tput / baseline
        regressed = frac < min_frac
        print(f"warm batched {warm_tput:.1f} sim_cycles/s vs checked-in "
              f"baseline {baseline:.1f} ({frac:.2f}x, floor {min_frac:g}x) "
              f"-> {'FAIL' if regressed else 'PASS'}")
    # the repo-root copy IS the checked-in regression baseline — only a
    # PASSING FULL run may refresh it: a smoke run would replace the full
    # trajectory with an incomparable grid, and a regressed run would
    # ratchet the floor down to its own regressed number before exiting
    # nonzero (self-disarming the gate on the next run)
    emit("BENCH_cycle_throughput", rows, {
        "n_points": len(pts), "length": length, "n_rows": n_rows,
        "smoke": smoke, "identical": identical,
        "baseline_sim_cycles_per_s": baseline, "min_frac": min_frac,
        "regressed": regressed,
    }, root=not smoke and identical and not regressed,
        headline={"warm_sim_cycles_per_s": round(warm_tput, 1)},
        timings={"cold_s": t_cold.s, "warm_s": t_warm.s})
    return identical and not regressed


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--length", type=int, default=48)
    ap.add_argument("--n-rows", type=int, default=128)
    ap.add_argument("--smoke", action="store_true",
                    help="small grid, batched-only (CI)")
    ap.add_argument("--min-frac", type=float, default=0.3,
                    help="fail below this fraction of the checked-in "
                         "warm-batched baseline")
    args = ap.parse_args()
    clear_caches()
    ok = run(length=args.length, n_rows=args.n_rows, smoke=args.smoke,
             min_frac=args.min_frac)
    raise SystemExit(0 if ok else 1)
