"""Shared benchmark utilities: result table formatting + JSON artifacts.

Artifact contract (docs/observability.md):

* every blob carries a ``manifest`` block (``repro.obs.runlog``): git SHA,
  device topology, versions, argv — ``scripts/check_bench_manifests.py``
  fails CI when a root ``BENCH_*.json`` lacks one;
* root-level ``BENCH_*.json`` files keep a ``history`` list — one
  ``{ts, git_sha, headline}`` entry per emitting run, appended (never
  overwritten) so the perf trajectory survives re-runs on one commit tree.
"""
from __future__ import annotations

import json
import os
import sys
import time
from typing import Any, Dict, List, Optional

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
ART_DIR = os.path.join(REPO_ROOT, "experiments", "bench")
HISTORY_CAP = 500   # root history entries kept (newest last)


def _runlog():
    """Lazy ``repro.obs.runlog`` import — benchmarks run as scripts, so
    ``src`` may not be on the path yet."""
    try:
        from repro.obs import runlog
    except ImportError:
        sys.path.insert(0, os.path.join(REPO_ROOT, "src"))
        from repro.obs import runlog
    return runlog


def emit(name: str, rows: List[Dict[str, Any]],
         meta: Optional[Dict[str, Any]] = None, root: bool = False,
         headline: Optional[Dict[str, Any]] = None,
         timings: Optional[Dict[str, float]] = None):
    """Write ``experiments/bench/<name>.json``; with ``root=True`` also
    merge into the repo-root copy (the per-commit perf trajectory collects
    root-level ``BENCH_*.json`` files — without it it records nothing).

    ``headline`` is the one-line summary recorded in the root ``history``
    (e.g. ``{"warm_tput": 1.2e6}``); ``timings`` lands in the manifest."""
    os.makedirs(ART_DIR, exist_ok=True)
    manifest = _runlog().run_manifest(timings=timings)
    blob = {"name": name, "meta": meta or {}, "manifest": manifest,
            "headline": headline or {}, "rows": rows}
    path = os.path.join(ART_DIR, f"{name}.json")
    with open(path, "w") as f:
        json.dump(blob, f, indent=1, default=float)
    if root:
        _write_root(name, blob)
    return path


def _load_history(path: str) -> List[Dict[str, Any]]:
    try:
        with open(path) as f:
            hist = json.load(f).get("history", [])
        return hist if isinstance(hist, list) else []
    except (OSError, ValueError):
        return []


def _write_root(name: str, blob: Dict[str, Any]) -> str:
    """Replace the root blob's rows but APPEND to its run history.

    The history entry is keyed by the manifest's ``created_unix`` so
    mirroring an already-rooted blob (``mirror_bench_to_root`` after an
    ``emit(root=True)``) dedups instead of double-counting the run."""
    path = os.path.join(REPO_ROOT, f"{name}.json")
    history = _load_history(path)
    man = blob.get("manifest", {})
    entry = {"ts": man.get("created_unix"), "git_sha": man.get("git_sha"),
             "headline": blob.get("headline") or {}}
    if not any(h.get("ts") == entry["ts"] for h in history):
        history.append(entry)
    history = history[-HISTORY_CAP:]
    with open(path, "w") as f:
        json.dump({**blob, "history": history}, f, indent=1, default=float)
    return path


def mirror_bench_to_root():
    """Merge every ``experiments/bench/BENCH_*.json`` into the repo root
    (the trajectory contract: perf artifacts live at the root, named
    BENCH_*). Root ``history`` is preserved and appended to, never
    clobbered — this used to be a plain copy, which erased it."""
    import glob
    merged = []
    for src in sorted(glob.glob(os.path.join(ART_DIR, "BENCH_*.json"))):
        with open(src) as f:
            blob = json.load(f)
        name = os.path.splitext(os.path.basename(src))[0]
        merged.append(_write_root(name, blob))
    return merged


def table(rows: List[Dict[str, Any]], cols: List[str]) -> str:
    if not rows:
        return "(empty)"
    widths = {c: max(len(c), max(len(_fmt(r.get(c))) for r in rows)) for c in cols}
    head = " | ".join(c.ljust(widths[c]) for c in cols)
    sep = "-+-".join("-" * widths[c] for c in cols)
    body = "\n".join(
        " | ".join(_fmt(r.get(c)).ljust(widths[c]) for c in cols) for r in rows
    )
    return f"{head}\n{sep}\n{body}"


def _fmt(v) -> str:
    if v is None:
        return "-"
    if isinstance(v, float):
        if v == 0:
            return "0"
        if abs(v) >= 1e5 or abs(v) < 1e-3:
            return f"{v:.3e}"
        return f"{v:.4g}"
    return str(v)


class Timer:
    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *a):
        self.s = time.perf_counter() - self.t0
