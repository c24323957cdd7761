"""The memory system's plain reference: the copied NumPy golden model run
over one point's trace, at the configuration's own settings."""
from __future__ import annotations

from typing import Dict, Optional

import numpy as np

from .oracle_model import OracleMemorySystem, OracleParams, OracleResult

TRACE_FIELDS = ("bank", "row", "is_write", "data", "valid")


def drain_bound(n_cores: int, length: int) -> int:
    """Cycle budget to drain ``length`` requests per core: every request
    served alone on one port, half again for stalled pushes, plus 64 cycles
    of cold start and settling (the bound the paper's model runs to)."""
    return int(n_cores * length * 1.5) + 64


def run(cfg: dict, scheme: str, alpha: float, trace: Dict[str, np.ndarray],
        queue_depth: Optional[int] = None) -> OracleResult:
    """Simulate one point. ``queue_depth`` overrides the configuration's
    (the control breaks that guarantee)."""
    op = OracleParams.derive(
        cfg["n_rows"], alpha, cfg["r"], n_data=cfg["n_data"],
        queue_depth=queue_depth or cfg["queue_depth"],
        recode_cap=cfg["recode_cap"], recode_budget=cfg["recode_budget"],
        coalesce=cfg["coalesce"],
        encode_rows_per_cycle=cfg["encode_rows_per_cycle"],
        select_period=cfg["select_period"], wq_hi=cfg["wq_hi"],
        wq_lo=cfg["wq_lo"])
    om = OracleMemorySystem(scheme, op, n_cores=cfg["n_cores"])
    st = om.run(tuple(trace[k] for k in TRACE_FIELDS),
                drain_bound(cfg["n_cores"], cfg["length"]),
                stop_when_quiescent=True)
    return om.result(st)


def fields_differing(got, want: OracleResult) -> int:
    """How many of the reference's result fields the program's result
    does not reproduce exactly (a missing result differs in all)."""
    if got is None:
        return len(want._fields)
    return sum(getattr(got, f, None) != getattr(want, f)
               for f in want._fields)
