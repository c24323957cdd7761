"""Static invariant verification for the coded-memory reproduction.

Three layers, one CLI (``python -m repro.analysis``), one CI gate:

* ``repro.analysis.schemes`` — GF(2) proofs over every coding scheme in
  ``repro.core.codes``: erasure tolerance, per-row read degree (disjoint
  recovery sets), locality, parity-stride alias freedom, and the signed
  certificate (``certificates.json``) the test suite consumes.
* ``repro.analysis.jaxpr``   — abstract-eval lint of the compiled
  programs: compile-key completeness per ``static_signature`` class,
  scan-carry structural stability, flag-off jaxpr identity.
* ``repro.analysis.rules``   — AST lint of repo conventions: oracle
  purity, tracer-safe branching, active-geometry indexing, wide-counter
  accumulation, bench-manifest contracts.

``repro.analysis.guard`` is the runtime complement: a ``recompile_guard``
context manager asserting a code region compiled nothing new.

See docs/analysis.md for what each layer proves and how to extend it.
"""
from repro.analysis.base import Finding, format_findings
from repro.analysis.guard import (GuardRecord, RecompileError, cache_size,
                                  recompile_guard)

__all__ = [
    "Finding", "format_findings",
    "GuardRecord", "RecompileError", "cache_size", "recompile_guard",
]
