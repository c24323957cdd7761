"""Runtime recompile guard: assert a code region compiled nothing new.

The static side of the compile-key story lives in
``repro.analysis.jaxpr`` (jaxpr-hash equality across a signature class);
this module is the runtime complement — a context manager that watches the
jit caches of the repo's long-lived compiled entry points and fails if a
region of code triggered more compilations than it budgeted for:

    with recompile_guard("sweep") as g:
        engine.run_points(grid(base, r=(0.05, 0.1, 0.2), seed=range(4)))
    assert g.compiles() == 1          # ONE program for the whole grid

    with recompile_guard("kernels.xor_encode", max_compiles=1):
        for seed in range(8):         # same shapes: first call compiles,
            encode_parities(...)      # the rest must hit the cache

Budgets are *upper bounds* checked at context exit (``max_compiles=None``
disables the check and just records); exact-count assertions use
``g.compiles()``. Relies on jit's ``_cache_size()`` introspection.

Guarded entry points are *named* so tests don't import engine internals;
``GUARDED`` maps a stable name to a lazy import of the jitted callable.
"""
from __future__ import annotations

import contextlib
from typing import Callable, Dict, List, Optional, Tuple, Union


def _sweep_scan():
    from repro.sweep import engine
    return engine._scan_batch


def _stream_chunk():
    from repro.traces import stream
    return stream._run_chunk_batch


def _k_xor_encode():
    from repro.kernels.xor_encode import kernel
    return kernel.encode_parities_pallas


def _k_xor_gather():
    from repro.kernels.xor_gather import kernel
    return kernel.gather_decode_pallas


def _k_kv_decode():
    from repro.kernels.coded_kv_decode import kernel
    return kernel.coded_kv_decode_pallas


def _k_pool_gather():
    from repro.kernels.coded_kv_decode import kernel
    return kernel.gather_pool_pallas


GUARDED: Dict[str, Callable[[], Callable]] = {
    "sweep": _sweep_scan,
    "stream": _stream_chunk,
    "kernels.xor_encode": _k_xor_encode,
    "kernels.xor_gather": _k_xor_gather,
    "kernels.coded_kv_decode": _k_kv_decode,
    "kernels.pool_gather": _k_pool_gather,
}


def resolve(target: Union[str, Callable]) -> Callable:
    if callable(target):
        return target
    try:
        return GUARDED[target]()
    except KeyError:
        raise KeyError(f"unknown guarded entry point {target!r}; "
                       f"have {sorted(GUARDED)}") from None


def cache_size(target: Union[str, Callable]) -> int:
    """Compiled-program count of a jitted callable."""
    return resolve(target)._cache_size()


class RecompileError(AssertionError):
    """A guarded region compiled more programs than it budgeted for."""


class GuardRecord:
    """Per-target compile deltas of one guarded region (filled on exit;
    ``compiles()`` may also be read mid-region)."""

    def __init__(self, targets: List[Tuple[str, Callable, int]]):
        self._targets = targets

    def deltas(self) -> Dict[str, int]:
        return {name: cache_size(fn) - before
                for name, fn, before in self._targets}

    def compiles(self) -> int:
        return sum(self.deltas().values())


@contextlib.contextmanager
def recompile_guard(*targets: Union[str, Callable],
                    max_compiles: Optional[int] = 0):
    """Fail (``RecompileError``) if the region compiles more than
    ``max_compiles`` new programs across ``targets`` (default: none —
    everything must hit existing caches). Targets are ``GUARDED`` names or
    jitted callables; no targets means all ``GUARDED`` entry points.
    """
    names = list(targets) if targets else sorted(GUARDED)
    resolved: List[Tuple[str, Callable, int]] = []
    for t in names:
        fn = resolve(t)
        before = cache_size(fn)
        label = t if isinstance(t, str) else getattr(t, "__name__", str(t))
        resolved.append((label, fn, before))
    rec = GuardRecord(resolved)
    yield rec
    if max_compiles is not None:
        deltas = rec.deltas()
        total = sum(deltas.values())
        if total > max_compiles:
            grown = {k: v for k, v in deltas.items() if v}
            raise RecompileError(
                f"guarded region compiled {total} new program(s) "
                f"(budget {max_compiles}): {grown} — a static argument is "
                "leaking into the compile key (see docs/analysis.md)")
