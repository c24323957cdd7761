"""Benchmark entry point: ``PYTHONPATH=src python -m benchmarks.run``.

Runs every paper-anchored harness (one per table/figure) plus the TPU
serving adaptations, then prints the roofline aggregation if dry-run
artifacts exist. Use ``--fast`` for the reduced CI-sized sweep."""
from __future__ import annotations

import argparse
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    args = ap.parse_args()
    t0 = time.time()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from benchmarks import (bench_cycles, bench_embedding, bench_kernels,
                            bench_kvbank, bench_serve, bench_stream,
                            bench_sweep, fig18_dedup, fig19_split,
                            fig20_ramp, fig_faults, roofline_report,
                            tab_schemes)

    tab_schemes.run()
    fig18_dedup.run(length=48 if args.fast else 96)
    fig19_split.run(length=48 if args.fast else 96)
    fig20_ramp.run(length=48 if args.fast else 96)
    fig_faults.run(smoke=args.fast)
    bench_sweep.run(length=32 if args.fast else 48)
    bench_cycles.run(smoke=args.fast)
    bench_stream.run(smoke=args.fast)
    bench_kvbank.run()
    bench_kernels.run(smoke=args.fast)
    bench_serve.run(smoke=args.fast)
    bench_embedding.run()
    roofline_report.run("pod16x16")
    roofline_report.run("pod2x16x16")

    # the per-commit perf trajectory collects root-level BENCH_*.json files;
    # mirror the bench artifacts there so the trajectory actually records
    from benchmarks.common import mirror_bench_to_root
    for path in mirror_bench_to_root():
        print(f"perf artifact -> {path}")
    print(f"\nall benchmarks done in {time.time() - t0:.1f}s")


if __name__ == "__main__":
    main()
