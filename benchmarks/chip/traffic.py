"""The traffic generator: reads a mix file (``traffic/<mix>.json``) and
makes the cell's inputs from ``--seed``. The program receives only what
this module makes.

A ``memsys_points`` mix is the sweep points an architect submits per call:
every trace kind x ``seeds_per_call`` seeds, on one scheme and alpha, for
``distinct_calls`` calls that the window runs in turn. The memory traces
are a copy of ``repro.sim.trace``'s synthetic generators (PARSEC-like
bands, split bands, ramps, uniform, Zipf), kept here so that no program
change alters the traffic it is judged on. A test holds the copy equal to
the original.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

SEED_SPACE = 2 ** 31 - 1


def child_seeds(seed: int, n: int, salt: int = 0) -> List[int]:
    """``n`` seeds for the program's trace generators, drawn from the run's
    ``--seed`` (any whole number, also above 32 bits)."""
    ss = np.random.SeedSequence([int(seed) & ((1 << 64) - 1), salt])
    return [int(x) for x in np.random.default_rng(ss).integers(
        0, SEED_SPACE, n)]


# ------------------------------------------------------ memory-system traces
def _pack(spec, addr, rng) -> Dict[str, np.ndarray]:
    valid = (addr >= 0) & (rng.random(addr.shape) < spec["issue_prob"])
    addr = np.maximum(addr, 0)
    bank = (addr % spec["n_banks"]).astype(np.int32)
    row = ((addr // spec["n_banks"]) % spec["n_rows"]).astype(np.int32)
    is_write = rng.random(addr.shape) < spec["write_frac"]
    data = rng.integers(1, 1 << 30, addr.shape).astype(np.int32)
    return {"bank": bank, "row": row, "is_write": is_write & valid,
            "data": data, "valid": valid}


def _band_walk(spec, centers, width, rng, drift_per_cycle=0.0,
               band_weights=None):
    n_cores, length = spec["n_cores"], spec["length"]
    n_banks = spec["n_banks"]
    n_bands = len(centers)
    space = n_banks * spec["n_rows"]
    if band_weights is None:
        band_weights = np.ones(n_bands) / n_bands
    base = [1, 1, 1, 1, 2, 2, n_banks, n_banks]
    strides = [base[c % len(base)] for c in range(n_cores)]
    addr = np.full((n_cores, length), -1, np.int64)
    for c in range(n_cores):
        stride = int(strides[c])
        band = rng.choice(n_bands, p=band_weights)
        pos = int(centers[band] - width // 2
                  + rng.integers(0, max(width, 1)))
        for t in range(length):
            u = rng.random()
            if u < 0.02:
                band = rng.choice(n_bands, p=band_weights)
                pos = int(centers[band] - width // 2
                          + rng.integers(0, max(width, 1)))
            elif u < 0.05:
                pos += int(rng.integers(-8, 9))
            center = centers[band] + drift_per_cycle * t
            lo = int(center - width // 2)
            hi = lo + max(width, 1)
            if pos < lo or pos >= hi:
                pos = lo + (pos - lo) % max(width, 1)
            addr[c, t] = pos % space
            pos += stride
    return addr


def _banded(spec, rng, n_bands=2):
    space = spec["n_banks"] * spec["n_rows"]
    width = max(space // 32, spec["n_banks"] * 4)
    centers = (np.arange(n_bands) + 0.5) * (space / n_bands)
    w = np.ones(n_bands)
    w[: min(2, n_bands)] = 4.0
    w /= w.sum()
    return _band_walk(spec, centers.astype(np.int64), width, rng, 0.0, w)


def _split(spec, rng, n_bands=8):
    space = spec["n_banks"] * spec["n_rows"]
    width = max(space // (4 * n_bands), spec["n_banks"])
    centers = ((np.arange(n_bands) + 0.5) * (space / n_bands)).astype(np.int64)
    return _band_walk(spec, centers, width, rng)


def _ramp(spec, rng, n_bands=2):
    space = spec["n_banks"] * spec["n_rows"]
    width = max(space // 16, spec["n_banks"] * 4)
    centers = ((np.arange(n_bands) + 0.5) * (space / n_bands)).astype(np.int64)
    drift = (space / 2) / max(spec["length"], 1)
    return _band_walk(spec, centers, width, rng, drift_per_cycle=drift)


def _uniform(spec, rng):
    space = spec["n_banks"] * spec["n_rows"]
    return rng.integers(0, space, (spec["n_cores"], spec["length"])
                        ).astype(np.int64)


def _zipf(spec, rng, a=1.2, hot_banks=(0, 1)):
    shape = (spec["n_cores"], spec["length"])
    rows = np.minimum(rng.zipf(a, shape) - 1, spec["n_rows"] - 1)
    banks = rng.choice(np.asarray(hot_banks), shape)
    return (rows * spec["n_banks"] + banks).astype(np.int64)


TRACE_KINDS = {"banded": _banded, "split": _split, "ramp": _ramp,
               "uniform": _uniform, "zipf": _zipf}


def memory_trace(kind: str, seed: int, *, n_cores: int, length: int,
                 n_banks: int, n_rows: int, write_frac: float,
                 issue_prob: float = 1.0) -> Dict[str, np.ndarray]:
    """One (n_cores, length) request stream per core: bank, row, is_write,
    data, valid."""
    spec = dict(n_cores=n_cores, length=length, n_banks=n_banks,
                n_rows=n_rows, write_frac=write_frac, issue_prob=issue_prob)
    rng = np.random.default_rng(seed)
    return _pack(spec, TRACE_KINDS[kind](spec, rng), rng)


def memsys_call(mix: dict, cfg: dict, seed: int, call: int) -> List[dict]:
    """The points of one ``run_points`` call: ``(kind, trace seed, trace)``
    for every kind x ``seeds_per_call``; ``call`` indexes the run's
    ``distinct_calls``."""
    kinds = mix["traces"]
    n = mix["seeds_per_call"]
    seeds = child_seeds(seed, len(kinds) * n, salt=call + 1)
    out = []
    for i, kind in enumerate(kinds):
        for j in range(n):
            s = seeds[i * n + j]
            out.append({"kind": kind, "seed": s, "trace": memory_trace(
                kind, s, n_cores=cfg["n_cores"], length=cfg["length"],
                n_banks=cfg["n_data"], n_rows=cfg["n_rows"],
                write_frac=cfg["write_frac"])})
    return out
