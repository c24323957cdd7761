"""Window runner of the memory-system simulator: whole ``repro.sweep.
run_points`` calls over traces made from the seed.

Set-up makes the traces of the mix's ``distinct_calls`` calls and puts
them on the device, then runs the first call once (which compiles, or loads
the program from the cache). The work of set-up is fixed by the data: it
does not depend on how fast the program runs. The window runs whole calls,
the prepared ones in turn, until ``seconds`` have passed. The check runs
the copied NumPy golden model over ``CHECK_PER_KIND`` points of each trace
kind, drawn from the seed among the points the window ran, and counts the
result fields the program does not reproduce.
"""
from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

from .. import traffic
from ..reference import memsys as ref

CHECK_SALT = 99
CHECK_PER_KIND = 2


def sweep_points(cfg: dict, mix: dict, call: List[dict]):
    from repro.sweep import SweepPoint

    base = SweepPoint(
        scheme=mix["scheme"], alpha=mix["alpha"], r=cfg["r"],
        n_rows=cfg["n_rows"], n_data=cfg["n_data"], n_banks=cfg["n_data"],
        n_cores=cfg["n_cores"], queue_depth=cfg["queue_depth"],
        select_period=cfg["select_period"], length=cfg["length"],
        recode_cap=cfg["recode_cap"], recode_budget=cfg["recode_budget"],
        coalesce=cfg["coalesce"],
        encode_rows_per_cycle=cfg["encode_rows_per_cycle"],
        wq_hi=cfg["wq_hi"], wq_lo=cfg["wq_lo"], write_frac=cfg["write_frac"])
    return [base.replace(trace=p["kind"], seed=p["seed"]) for p in call]


def device_traces(call: List[dict]):
    import jax.numpy as jnp
    from repro.core.system import Trace

    return [Trace(**{k: jnp.asarray(p["trace"][k]) for k in ref.TRACE_FIELDS})
            for p in call]


class Runner:
    def __init__(self, cfg: dict, mix: dict, seed: int):
        self.cfg, self.mix, self.seed = cfg, mix, seed
        self.calls: List[dict] = []   # host traces of the prepared calls
        self.ran: List[tuple] = []    # (prepared call index, results)
        self.call_s: List[float] = []

    def _prepare(self, index: int) -> dict:
        call = traffic.memsys_call(self.mix, self.cfg, self.seed, index)
        return {"host": call, "points": sweep_points(self.cfg, self.mix, call),
                "traces": device_traces(call),
                "requests": int(sum(p["trace"]["valid"].sum() for p in call))}

    def _run(self, call: dict):
        from repro import sweep

        return sweep.run_points(call["points"], call["traces"])

    def setup(self) -> Dict:
        self.calls = [self._prepare(i)
                      for i in range(self.mix["distinct_calls"])]
        t0 = time.perf_counter()
        self._run(self.calls[0])
        return {"warm_call_s": time.perf_counter() - t0,
                "prepared_calls": len(self.calls)}

    def window(self, seconds: float, span, tracer) -> Dict:
        """Whole calls until ``seconds`` have passed; ``tracer`` brackets
        the first call of a traced run."""
        t0 = time.perf_counter()
        i, calls = 0, []
        while True:
            call = self.calls[i % len(self.calls)]
            if i == 0:
                tracer.start()
            s = time.perf_counter()
            with span("run_points"):
                results = self._run(call)
            e = time.perf_counter()
            if i == 0:
                tracer.stop()
            self.ran.append((i % len(self.calls), results))
            self.call_s.append(e - s)
            calls.append({"start_s": s - t0, "end_s": e - t0,
                          "requests": call["requests"],
                          "trips": max(r.cycles for r in results)})
            i += 1
            if e - t0 >= seconds:
                break
        points = [r for _, rs in self.ran for r in rs]
        return {"calls": calls, "window_s": calls[-1]["end_s"],
                "traced_trips": calls[0]["trips"], "attempted": len(points),
                "failed": sum(not r.completed for r in points)}

    def end_to_end(self, samples: Dict) -> Dict[str, float]:
        reqs = sum(c["requests"] for c in samples["calls"])
        return {"sim_requests_per_s": reqs / samples["window_s"]}

    def side(self) -> Dict:
        """Simulated statistics of the window, by trace kind (means), and
        how many calls the window ran."""
        by: Dict[str, List] = {}
        for idx, results in self.ran:
            for p, r in zip(self.calls[idx]["host"], results):
                by.setdefault(p["kind"], []).append(r)
        stats = {k: {f: float(np.mean([getattr(r, f) for r in rs]))
                     for f in ("cycles", "degraded_reads", "parked_writes",
                               "switches", "stall_cycles", "rc_dropped")}
                 for k, rs in by.items()}
        return {"calls": len(self.ran), "call_s": self.call_s,
                "by_trace": stats}

    def sample(self, n_ran: int) -> List[tuple]:
        """``CHECK_PER_KIND`` distinct ran points of each trace kind, drawn
        from the seed: ``(index into the calls ran, point index)``."""
        rng = np.random.default_rng(
            traffic.child_seeds(self.seed, 1, salt=CHECK_SALT)[0])
        per = self.mix["seeds_per_call"]
        out = []
        for k in range(len(self.mix["traces"])):
            picks = rng.choice(n_ran * per, CHECK_PER_KIND, replace=False)
            out += [(int(q // per), k * per + int(q % per)) for q in picks]
        return out

    def release(self):
        for c in self.calls:
            c["traces"] = None

    def check(self) -> Dict[str, Dict]:
        differing = 0
        for ran, j in self.sample(len(self.ran)):
            idx, results = self.ran[ran]
            p = self.calls[idx]["host"][j]
            want = ref.run(self.cfg, self.mix["scheme"], self.mix["alpha"],
                           p["trace"])
            differing += ref.fields_differing(
                results[j] if j < len(results) else None, want)
        return {"fields_differing": {"value": differing, "limit": 0}}
