"""Chip smoke run: both main paths of the system, once, on a TPU.

    python chip_smoke.py               # one chip: phases memsys, serve
    python chip_smoke.py --four-chips  # four chips: the sharded sweep only

Phase ``memsys`` runs the paper's memory system (``configs/paper_memsys``:
8 data banks, 8 cores, 512 rows, queue depth 10, select period 256) over
the ``paper_fig18`` suite (uncoded plus schemes I-III x five alphas, 16
points) through ``repro.sweep.run_points``. It checks the uncoded point and
scheme I at alpha=1 field by field against the NumPy oracle, and that every
coded scheme at alpha=1 takes fewer cycles than uncoded.

Phase ``serve`` serves qwen2.5-3b at its published widths (bf16 weights
from a seed) through ``runtime.server.Server`` over the coded KV pool, three
times: coded with the Pallas gather, coded with the reference gather, and
uncoded with the Pallas gather. It checks that the three give the same
tokens, that every token is in the vocabulary, and that the Pallas decode
step holds a compiled TPU kernel (``tpu_custom_call``). On the pool the
coded run leaves behind, it checks the Pallas gather bit-exact against the
reference gather and every degraded read equal to its direct read.

``--four-chips`` runs the 16 points sharded over four devices and
``stream_replay_points`` sharded on three of them, and checks both
bit-identical to the unsharded runs.

Each phase prints one JSON line. The last line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Without a TPU the script exits 2 and prints no result; a failed check exits
1. Everything runs in this one process, which holds the chip; no child
process is started and no libtpu flag is set.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path
from unittest import mock

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.monitoring  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.base import get_config  # noqa: E402
from repro.configs.paper_memsys import (PAPER_ALPHAS, PAPER_SCHEMES,  # noqa: E402
                                        MemSysConfig)
from repro.kernels.coded_kv_decode.ops import gather_pool_layer  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import lm  # noqa: E402
from repro.oracle import OracleMemorySystem, OracleParams  # noqa: E402
from repro.runtime.server import Request, ServeConfig, Server  # noqa: E402
from repro.sweep import SweepPoint, engine, run_points  # noqa: E402
from repro.sweep.workloads import build_trace, suite  # noqa: E402
from repro.traces.stream import stream_replay_points, strip_windows  # noqa: E402

MEMSYS_LENGTH = 4096       # requests per core
SERVE_ARCH = "qwen2.5-3b"
SERVE_VARIANTS = (("coded_pallas", True, "pallas"),
                  ("coded_reference", True, "reference"),
                  ("uncoded_pallas", False, "pallas"))


@contextlib.contextmanager
def compile_seconds():
    """Yield a one-element list that accumulates the seconds XLA spends
    compiling inside the block (tracing and lowering are not counted: their
    events nest, and a persistent-cache hit skips the compile)."""
    total = [0.0]

    def listen(name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            total[0] += secs

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        yield total
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)


def peak_bytes_in_use():
    """The device's peak allocation so far in this process (None where the
    backend does not report it)."""
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def paper_points(length: int):
    """The paper's deployment under the Fig 18 axes (16 points)."""
    m = MemSysConfig()
    base = SweepPoint(n_data=m.n_data, n_banks=m.n_data, n_cores=m.n_cores,
                      n_rows=m.n_rows, queue_depth=m.queue_depth,
                      select_period=m.select_period, length=length)
    return suite("paper_fig18", base, schemes=PAPER_SCHEMES,
                 alphas=PAPER_ALPHAS, r=m.r)


def oracle_result(pt: SweepPoint):
    op = OracleParams.derive(
        pt.n_rows, pt.alpha, pt.r, n_data=pt.n_data,
        queue_depth=pt.queue_depth, recode_cap=pt.recode_cap,
        recode_budget=pt.recode_budget, coalesce=pt.coalesce,
        encode_rows_per_cycle=pt.encode_rows_per_cycle,
        select_period=pt.select_period, wq_hi=pt.wq_hi, wq_lo=pt.wq_lo)
    om = OracleMemorySystem(pt.scheme, op, n_cores=pt.n_cores)
    st = om.run(build_trace(pt), pt.resolved_cycles(),
                stop_when_quiescent=True)
    return om.result(st)


def phase_memsys(length: int = MEMSYS_LENGTH) -> dict:
    pts = paper_points(length)
    t0 = time.perf_counter()
    with compile_seconds() as comp:
        results = run_points(pts)
    wall = time.perf_counter() - t0
    cycles = {f"{pt.scheme}@{pt.alpha}": r.cycles
              for pt, r in zip(pts, results)}
    uncoded = cycles["uncoded@1.0"]
    t1 = time.perf_counter()
    oracle_equal = {
        f"{pt.scheme}@{pt.alpha}": strip_windows(r) == oracle_result(pt)
        for pt, r in zip(pts, results)
        if pt.scheme == "uncoded" or (pt.scheme == "scheme_i"
                                      and pt.alpha == 1.0)}
    checks = {
        "oracle_equal": oracle_equal,
        "coded_below_uncoded_at_alpha_1": {
            s: cycles[f"{s}@1.0"] < uncoded for s in PAPER_SCHEMES},
        "all_completed": all(r.completed for r in results),
    }
    ok = (len(oracle_equal) == 2 and all(oracle_equal.values())
          and all(checks["coded_below_uncoded_at_alpha_1"].values())
          and checks["all_completed"])
    return {"phase": "memsys", "ok": ok, "points": len(pts),
            "length": length, "wall_s": wall, "compile_s": comp[0],
            "oracle_s": time.perf_counter() - t1,
            "peak_bytes_in_use": peak_bytes_in_use(), "cycles": cycles,
            "checks": checks}


def _requests(cfg, n: int, max_prompt: int, seed: int):
    rng = np.random.default_rng(seed)
    lens = rng.integers(8, max_prompt + 1, n)
    return [[int(t) for t in rng.integers(1, cfg.vocab, int(n_tok))]
            for n_tok in lens]


def gather_checks(pool, seed: int) -> dict:
    """Bit-exactness of the pool gather on the pool a coded run left
    behind: the Pallas gather equals the reference gather under a seeded
    mix of direct and degraded reads (with one free page), and every page
    rebuilt from sibling ^ parity equals its direct read. Tokens alone are
    a weak witness here: random deep weights emit few distinct tokens."""
    nb, slots = pool.k_banks.shape[1:3]
    rng = np.random.default_rng(seed)
    table = np.arange(nb * slots, dtype=np.int32)[None, :]
    table[0, -1] = -1
    mixed = rng.random(table.shape) < 0.5

    def gather(layer, use_parity, kernel):
        k, v = gather_pool_layer(
            pool.k_banks[layer], pool.v_banks[layer], pool.k_par[layer],
            pool.v_par[layer], jnp.asarray(table), jnp.asarray(use_parity),
            pool.k_banks.dtype, kernel=kernel)
        return np.asarray(k), np.asarray(v)

    exact, consistent, written = True, True, True
    for layer in (0, pool.k_banks.shape[0] - 1):
        pal, ref = gather(layer, mixed, "pallas"), gather(layer, mixed,
                                                           "reference")
        direct = gather(layer, np.zeros_like(mixed), "reference")
        degraded = gather(layer, np.ones_like(mixed), "reference")
        exact &= all(np.array_equal(a, b) for a, b in zip(pal, ref))
        consistent &= all(np.array_equal(a, b)
                          for a, b in zip(direct, degraded))
        written &= bool(direct[0].any())
    return {"gather_bit_exact": exact, "parity_consistent": consistent,
            "pool_written": written}


def phase_serve(cfg=None, *, n_requests: int = 8, max_new: int = 16,
                n_slots: int = 4, max_prompt: int = 64, max_seq: int = 256,
                seed: int = 0) -> dict:
    cfg = cfg or get_config(SERVE_ARCH)
    on_tpu = jax.devices()[0].platform == "tpu"
    t0 = time.perf_counter()
    params = lm.init_serving_params(cfg, jax.random.key(seed),
                                    max_seq=max_seq)
    jax.block_until_ready(params)
    init_s = time.perf_counter() - t0
    prompts = _requests(cfg, n_requests, max_prompt, seed)
    tokens, runs = {}, {}
    pallas_native = None
    for name, coded, kernel in SERVE_VARIANTS:
        sc = ServeConfig(n_slots=n_slots, max_prompt=max_prompt,
                         max_seq=max_seq, max_new_tokens=max_new,
                         coded=coded, kernel=kernel)
        t1 = time.perf_counter()
        with compile_seconds() as comp:
            srv = Server(cfg, sc, params)
            for rid, prompt in enumerate(prompts):
                srv.submit(Request(rid=rid, prompt=prompt))
            done = srv.run_until_drained()
        wall = time.perf_counter() - t1
        if pallas_native is None and kernel == "pallas":
            text = srv.decode.lower(srv.params, srv.tokens,
                                    srv.cache).as_text()
            pallas_native = "tpu_custom_call" in text
        tokens[name] = {r.rid: list(r.out) for r in done}
        runs[name] = {"wall_s": wall, "compile_s": comp[0],
                      "answered": len(done), "decode_steps": srv.steps_run}
        if name == "coded_pallas":
            pool_checks = gather_checks(srv.cache["pool"], seed)
        del srv
    first = tokens[SERVE_VARIANTS[0][0]]
    checks = {
        **pool_checks,
        "all_answered": all(
            len(t) == n_requests and all(len(o) == max_new
                                         for o in t.values())
            for t in tokens.values()),
        "tokens_identical": all(t == first for t in tokens.values()),
        "tokens_in_vocab": all(0 <= x < cfg.vocab for t in tokens.values()
                               for o in t.values() for x in o),
        # the kernel is compiled exactly where a TPU runs it; elsewhere
        # Pallas interprets it
        "pallas_native": pallas_native,
    }
    ok = (checks["all_answered"] and checks["tokens_identical"]
          and checks["tokens_in_vocab"] and pallas_native == on_tpu
          and all(pool_checks.values()))
    param_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    return {"phase": "serve", "ok": ok, "arch": cfg.name,
            "layers": cfg.n_layers, "d_model": cfg.d_model,
            "vocab": cfg.vocab, "param_bytes": param_bytes,
            "param_init_s": init_s, "requests": n_requests,
            "new_tokens": max_new, "runs": runs,
            "peak_bytes_in_use": peak_bytes_in_use(), "checks": checks,
            "distinct_tokens": len({x for o in first.values() for x in o}),
            "first_tokens": first.get(0, [])[:8]}


def _sharding_spy(device_sets):
    """Wrap the engine's batched scan to record the device set of each
    batch's state as the scan hands it back."""
    scan = engine._scan_batch

    def spy(sys_, st_b, *rest):
        out = scan(sys_, st_b, *rest)
        device_sets.append(len(out.done_cycle.sharding.device_set))
        return out
    return mock.patch.object(engine, "_scan_batch", spy)


def phase_four_chips(length: int = MEMSYS_LENGTH) -> dict:
    n_dev = len(jax.devices())
    pts = paper_points(length)
    device_sets = []
    t0 = time.perf_counter()
    with compile_seconds() as comp:
        with _sharding_spy(device_sets):
            sharded = run_points(pts, shard=True)
    sharded_s = time.perf_counter() - t0
    engine.clear_caches()
    unsharded = run_points(pts, shard=False)
    stream_pts = [pt for pt in pts
                  if pt.scheme == "scheme_ii" and pt.alpha < 1.0][:3]
    traces = [build_trace(pt) for pt in stream_pts]
    t1 = time.perf_counter()
    streamed = stream_replay_points(stream_pts, traces, shard=True)
    stream_s = time.perf_counter() - t1
    engine.clear_caches()
    streamed_1 = stream_replay_points(stream_pts, traces, shard=False)
    want = [unsharded[pts.index(pt)] for pt in stream_pts]
    checks = {
        "devices": n_dev,
        "state_device_sets": device_sets,
        "sweep_sharded_equal": sharded == unsharded,
        "stream_sharded_equal": streamed == streamed_1,
        "stream_equal_sweep": [strip_windows(s) for s in streamed] == want,
    }
    ok = (n_dev == 4 and bool(device_sets)
          and all(n == 4 for n in device_sets)
          and checks["sweep_sharded_equal"]
          and checks["stream_sharded_equal"]
          and checks["stream_equal_sweep"])
    return {"phase": "four_chips", "ok": ok, "points": len(pts),
            "stream_points": len(stream_pts), "length": length,
            "sharded_wall_s": sharded_s, "sharded_compile_s": comp[0],
            "stream_sharded_wall_s": stream_s,
            "peak_bytes_in_use": peak_bytes_in_use(), "checks": checks}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded sweep over four chips")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found platform {dev.platform!r}",
              file=sys.stderr)
        return 2
    enable_compile_cache()
    phases = ([phase_four_chips] if args.four_chips
              else [phase_memsys, phase_serve])
    ok = True
    for phase in phases:
        out = phase()
        print(json.dumps(out), flush=True)
        ok = ok and out["ok"]
    if not ok:
        print("chip_smoke: a check failed", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
