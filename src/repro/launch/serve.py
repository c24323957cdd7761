"""Serving launcher: continuous batching demo over synthetic requests.

  PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --reduced \
      --requests 8 --slots 4 --telemetry

Reports steady-state decode throughput (a warmup request triggers prefill +
decode compilation before the timed run, so tok/s no longer includes jit
time), per-request TTFT/ITL from the host-side lifecycle log, and — with
``--telemetry`` — the device serve-plane summary (read provenance, saved
port cycles, recode backlog) for the coded KV pool backend.
"""
from __future__ import annotations

import argparse
import time

import jax

from repro.configs.base import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import lm
from repro.obs import serve as obs_serve
from repro.runtime.server import Request, ServeConfig, Server


def _mk_requests(cfg, n, base=0):
    return [Request(rid=base + i,
                    prompt=[(7 * (base + i) + j) % max(cfg.vocab // 2, 2) + 1
                            for j in range(5 + i % 7)])
            for i in range(n)]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-prompt", type=int, default=32)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--uncoded", action="store_true",
                    help="uncoded KV pool (no parity arrays)")
    ap.add_argument("--telemetry", action="store_true",
                    help="device serve metric planes + summary")
    ap.add_argument("--page", type=int, default=0,
                    help="pool page size in tokens (0: config default)")
    ap.add_argument("--recode-budget", type=int, default=None,
                    help="parity rows recoded per step (default: all)")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    params = lm.init_serving_params(cfg, jax.random.key(0),
                                    max_seq=args.max_seq)
    sc = ServeConfig(n_slots=args.slots, max_prompt=args.max_prompt,
                     max_seq=args.max_seq, max_new_tokens=args.max_new,
                     coded=not args.uncoded, telemetry=args.telemetry,
                     page=args.page, recode_budget=args.recode_budget)
    srv = Server(cfg, sc, params)

    # warmup: one request end to end compiles prefill + decode, so the timed
    # run below measures steady-state serving, not jit time.
    for r in _mk_requests(cfg, 1, base=10_000):
        srv.submit(r)
    srv.run_until_drained()
    warm_steps = srv.steps_run

    reqs = _mk_requests(cfg, args.requests)
    t0 = time.perf_counter()
    for r in reqs:
        srv.submit(r)
    srv.run_until_drained()
    dt = time.perf_counter() - t0
    n_tok = sum(len(r.out) for r in reqs)
    for r in reqs[:4]:
        print(f"req {r.rid}: {r.out}")
    backend = ("coded pool" if sc.coded else "uncoded pool") \
        if srv.pooled else "ring cache"
    rate = f"{n_tok / dt:.1f} tok/s" if dt > 0 else "n/a tok/s"
    print(f"served {len(reqs)} requests / {n_tok} tokens in {dt:.2f}s "
          f"({rate} steady-state, {srv.steps_run - warm_steps} decode "
          f"steps, {backend})")
    spans = [s for s in srv.log.spans() if s["rid"] < 10_000]
    for s in spans:
        itl = s["inter_token_s"]
        mean_itl = 1e3 * sum(itl) / len(itl) if itl else 0.0
        print(f"  req {s['rid']}: wait {1e3 * s['admission_wait_s']:.1f} ms"
              f" ttft {1e3 * s['ttft_s']:.1f} ms"
              f" mean-itl {mean_itl:.1f} ms ({s['n_tokens']} tokens)")
    snap = srv.serve_snapshot()
    if snap is not None:
        print(obs_serve.format_summary(snap))


if __name__ == "__main__":
    main()
