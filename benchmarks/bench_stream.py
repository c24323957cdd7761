"""Streamed-replay throughput: requests/sec vs single-shot on a long trace.

Replays one banded trace through (a) single-shot ``run()`` — the whole trace
materialized as one device array, the path whose device footprint grows with
trace length — and (b) ``repro.traces.stream.stream_replay`` with a fixed
``chunk_len`` staging buffer. Streamed results must be bit-identical to
single-shot (the chunked-replay contract, enforced here and in
tests/test_traces.py); the interesting number is the streaming overhead —
host staging + the per-chunk device round trip — which is what a
longer-than-memory trace costs over the (impossible) single-shot ideal.

Emits ``experiments/bench/BENCH_stream_throughput.json`` plus a repo-root
copy (the per-commit perf trajectory collects root-level ``BENCH_*.json``).

``--smoke`` shrinks the trace for CI and fails only on a result mismatch;
the full run also fails if streaming drops below ``--floor`` of single-shot
throughput. ``--requests N`` scales the trace (the nightly million-request
soak lives in tests/test_traces.py::test_stream_million_requests).
"""
from __future__ import annotations

import argparse

import numpy as np

from benchmarks.common import Timer, emit, table


def run(length: int = 2048, chunk_len: int = 256, n_cores: int = 8,
        smoke: bool = False, floor: float = 0.25):
    if smoke:
        length, chunk_len = 128, 32
    from repro.core.codes import get_tables
    from repro.core.state import make_params, make_tunables
    from repro.core.system import CodedMemorySystem, drain_bound
    from repro.sim.trace import TraceSpec, banded_trace
    from repro.traces import chunk_iter, stream_replay, strip_windows

    n_banks, n_rows = 8, 512
    spec = TraceSpec(n_cores=n_cores, length=length, n_banks=n_banks,
                     n_rows=n_rows, seed=0)
    trace = banded_trace(spec)
    n_requests = int(np.asarray(trace.valid).sum())
    t = get_tables("scheme_i")
    p = make_params(t, n_rows=n_rows, alpha=0.25, r=0.05)
    sys_ = CodedMemorySystem(t, p, n_cores=n_cores,
                             tunables=make_tunables(select_period=256))
    bound = drain_bound(n_cores, length)

    rows = []
    with Timer() as t_cold:
        single = sys_.run(trace, bound)
    with Timer() as t_single:
        single = sys_.run(trace, bound)
    rows.append({"path": "single-shot (warm)", "wall_s": round(t_single.s, 2),
                 "requests/s": round(n_requests / t_single.s, 1)})

    with Timer() as t_scold:
        streamed = stream_replay(sys_, trace, chunk_len=chunk_len)
    with Timer() as t_stream:
        streamed = stream_replay(sys_, trace, chunk_len=chunk_len)
    rows.append({"path": f"streamed chunk={chunk_len} (warm)",
                 "wall_s": round(t_stream.s, 2),
                 "requests/s": round(n_requests / t_stream.s, 1)})
    with Timer() as t_chunks:
        streamed2 = stream_replay(sys_, chunk_iter(trace, chunk_len),
                                  chunk_len=chunk_len)
    rows.append({"path": "streamed chunked-source (warm)",
                 "wall_s": round(t_chunks.s, 2),
                 "requests/s": round(n_requests / t_chunks.s, 1)})

    identical = (strip_windows(streamed) == single
                 and strip_windows(streamed2) == single)
    ratio = t_single.s / t_stream.s
    print(f"\n== bench_stream: {n_requests} requests, length={length}, "
          f"chunk_len={chunk_len}{' [smoke]' if smoke else ''} ==")
    print(table(rows, ["path", "wall_s", "requests/s"]))
    ident = "IDENTICAL" if identical else "MISMATCH"
    print(f"streamed vs single-shot results: {ident}")
    print(f"streamed throughput = {ratio:.2f}x single-shot "
          f"(floor {floor:g}x{' waived in smoke' if smoke else ''})")
    ok = identical and (smoke or ratio >= floor)
    emit("BENCH_stream_throughput", rows, {
        "n_requests": n_requests, "length": length, "chunk_len": chunk_len,
        "n_cores": n_cores, "smoke": smoke, "identical": identical,
        "streamed_vs_single_shot": ratio, "floor": floor,
        "cold_single_s": t_cold.s, "cold_streamed_s": t_scold.s,
        "windows": len(streamed.window_read_latency),
    }, root=True,
        headline={"streamed_requests_per_s": round(n_requests / t_stream.s, 1),
                  "streamed_vs_single_shot": round(ratio, 3)},
        timings={"single_warm_s": t_single.s, "streamed_warm_s": t_stream.s})
    return ok


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--length", type=int, default=2048,
                    help="trace length per core")
    ap.add_argument("--chunk-len", type=int, default=256)
    ap.add_argument("--n-cores", type=int, default=8)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny trace, identity check only (CI)")
    ap.add_argument("--floor", type=float, default=0.25,
                    help="min streamed/single-shot throughput ratio")
    args = ap.parse_args()
    ok = run(length=args.length, chunk_len=args.chunk_len,
             n_cores=args.n_cores, smoke=args.smoke, floor=args.floor)
    raise SystemExit(0 if ok else 1)
