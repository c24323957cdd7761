"""The memory-system cells' control: the reference put in the program's
place with one stated guarantee broken, the queue depth one below the
configuration's. The comparison has to read it as not correct.

    python3 -m benchmarks.chip.control --workload <cell> --seeds 1 2 3

runs it at the cell's own size over the points a one-call window would
check, and prints ``fields_differing`` for each seed. The benchmark's own
runs never run it; ``tests/test_chipbench_faults.py`` runs it at a small
size through the whole harness.
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np

from .reference import memsys as ref


def control_results(cfg: dict, mix: dict, traces) -> list:
    """What the control returns in place of ``run_points``."""
    return [ref.run(cfg, mix["scheme"], mix["alpha"], tr,
                    queue_depth=cfg["queue_depth"] - 1) for tr in traces]


def run_points_control(cfg: dict, mix: dict):
    """A stand-in for ``repro.sweep.run_points`` computing the control."""
    def run_points(points, traces, *args, **kwargs):
        host = [{k: np.asarray(getattr(t, k)) for k in ref.TRACE_FIELDS}
                for t in traces]
        return control_results(cfg, mix, host)
    return run_points


def readings(cfg: dict, mix: dict, seed: int) -> int:
    from .paths.memsys import Runner
    from . import traffic

    drv = Runner(cfg, mix, seed)
    call = traffic.memsys_call(mix, cfg, seed, 0)
    differing = 0
    for _, j in drv.sample(1):
        tr = call[j]["trace"]
        got = control_results(cfg, mix, [tr])[0]
        want = ref.run(cfg, mix["scheme"], mix["alpha"], tr)
        differing += ref.fields_differing(got, want)
    return differing


def main(argv=None) -> int:
    from .run import load_cell

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    for seed in args.seeds:
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control_fields_differing": readings(
                              cell["config"], cell["traffic"], seed)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
