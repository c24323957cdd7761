"""Layer 3 — repo-rule AST lint: project conventions proved from source.

Four conventions keep the simulator correct and the oracle honest; each is
encoded here as an AST rule so violations surface at lint time instead of
as conformance drift or silent recompiles:

* **oracle-purity** — ``repro.oracle`` exists to catch shared
  misconceptions, so it must not import jax (or any non-oracle ``repro``
  module): a jax import would let the golden model inherit the very code
  paths it is supposed to check.
* **tracer-branch** — inside *traced* functions (the ones that run under
  ``jit``/``vmap``/``scan``), Python ``if``/``while`` and ``int()``/
  ``float()``/``bool()`` must only touch *static* values (params,
  shapes, ``x is None`` structure checks). Anything else is a
  ``TracerBoolConversionError`` at best and a silent
  concretization/recompile at worst.
* **static-geometry** — row→region/slot indexing in traced code must
  divide by the *active* geometry (``active_geometry``/
  ``TunableParams.*_active``), never ``// p.region_size`` on the
  allocated fields: under a padded group allocation the allocated stride
  is the *storage* layout, and using it to derive a region id silently
  mis-addresses every sub-allocation point. (Parity-row addressing
  ``slot * rs_alloc + i % rs_active`` legitimately *multiplies* by the
  allocated stride — only ``//`` and ``%`` by an allocated field are
  flagged, and the two intentional storage-layout sites carry waivers.)
* **narrow-counter** — the wide (lo, hi) uint32 counters
  (``stall_cycles``, ``read/write_latency_sum``) saturate silently if
  accumulated with ``+`` in a scan body; accumulation must go through
  ``repro.core.state.wide_add``.

Classification is explicit: every function in the scanned files must be
listed as TRACED or HOST below (wildcards ``Class.*`` / ``*`` cover
all-host modules). An unlisted function is itself a finding — new traced
code cannot silently skip the lint.

A finding can be waived where the code is right and the rule is
conservative: put ``# analysis: <rule-id>`` on the offending line (or the
line above) with a neighbouring comment saying why.

``scripts/check_bench_manifests.py`` is folded in as the
**bench-manifest** rule so ``python -m repro.analysis --strict`` covers
benchmark-contract drift too.
"""
from __future__ import annotations

import ast
import os
import re
from typing import Dict, Iterable, List, Optional, Set

from repro.analysis.base import Finding, REPO_ROOT, python_files, rel

# --------------------------------------------------------------- rule scope
# traced-code rules apply to the cycle-engine surface: everything the
# compiled programs are built from
TRACED_SCOPE = ("src/repro/core", "src/repro/faults", "src/repro/obs/planes.py",
                "src/repro/obs/serve.py", "src/repro/runtime/kvbank.py")
ORACLE_SCOPE = "src/repro/oracle"

# modules the oracle may import: stdlib + numpy, and its own package
ORACLE_ALLOWED_ROOTS = {
    "numpy", "dataclasses", "itertools", "typing", "collections", "math",
    "functools", "enum", "__future__", "repro.oracle",
}

GEOM_FIELDS = {"region_size", "n_regions", "n_slots"}
WIDE_FIELDS = {"stall_cycles", "read_latency_sum", "write_latency_sum"}

# names whose attributes are static (host-side) by contract: params and
# scheme tables are plain python/numpy containers, never tracers
STATIC_ROOTS = {"p", "params", "self", "t", "tables", "fault_plan", "plan"}
# attributes that are static on *any* object (array metadata)
STATIC_ATTRS = {"shape", "ndim", "dtype", "size", "weak_type"}
# calls that yield static values when their arguments are static;
# _concrete_int is static unconditionally (it is the sanctioned probe that
# returns None for tracers)
STATIC_CALLS = {"len", "isinstance", "hasattr", "callable", "min", "max",
                "round", "tuple", "sorted", "range", "getattr", "type"}
ALWAYS_STATIC_CALLS = {"_concrete_int"}

# ------------------------------------------------- function classification
# every function in TRACED_SCOPE must appear in exactly one of these maps
# (qualified as "func" or "Class.method"; "Class.*" and "*" are wildcards).
TRACED_FUNCTIONS: Dict[str, Set[str]] = {
    "src/repro/core/controller.py": {
        "_walk_bounds", "_Walk.*", "_first_min", "_pick", "_onehot",
        "_count", "_take", "_col", "_cell", "_put", "_compact",
        "build_read_pattern",
        "build_write_pattern", "_rc_push"},
    "src/repro/core/recoding.py": {"recode_step"},
    "src/repro/core/dynamic.py": {
        "_on", "_encode_region_data", "_set_slot_valid", "priors_layout",
        "dynamic_step"},
    "src/repro/core/state.py": {
        "bank_table", "cells", "set_cells", "_lane_rows", "columns",
        "set_columns", "active_geometry", "wide_zero", "wide_add",
        "init_state"},
    "src/repro/core/system.py": {
        "quiescent", "CodedMemorySystem._arbiter",
        "CodedMemorySystem._read_values", "CodedMemorySystem._commit_writes",
        "CodedMemorySystem.cycle_fn", "CodedMemorySystem._run",
        "CodedMemorySystem.run_chunk"},
    "src/repro/faults/plan.py": {
        "init_fault_state", "bank_down", "bank_rebuilding", "stutter_busy"},
    "src/repro/faults/inject.py": {
        "drop_unservable", "rebuild_scan", "quiescent_fault_pending"},
    "src/repro/obs/planes.py": {"init_telemetry", "lat_bin"},
    "src/repro/obs/serve.py": {
        "init_serve_telemetry", "update_serve_telemetry"},
    "src/repro/runtime/kvbank.py": {
        "init_state", "append_token", "recode", "_budget_rows",
        "pool_read_sets", "plan_reads", "_plan_from_tables", "gather_kv",
        "read_latencies", "pool_write_index", "pool_mark_stale",
        "pool_write_layer", "pool_write_layer_fused", "pool_plan",
        "pool_install", "pool_recode", "pool_permute"},
}
HOST_FUNCTIONS: Dict[str, Set[str]] = {
    "src/repro/core/__init__.py": {"*"},
    "src/repro/core/codes.py": {"*"},
    "src/repro/core/controller.py": {"jtables", "write_slots"},
    "src/repro/core/state.py": {
        "make_tunables", "wide_total", "derive_geometry", "make_params",
        "_concrete_int", "bank_view"},
    "src/repro/core/system.py": {
        "drain_bound", "result_from_host", "CodedMemorySystem.__init__",
        "CodedMemorySystem.init", "CodedMemorySystem.run",
        "CodedMemorySystem.summarize"},
    "src/repro/faults/__init__.py": {"*"},
    "src/repro/faults/plan.py": {"FaultPlan.*", "plan_from_spec"},
    "src/repro/obs/planes.py": {
        "TelemetrySnapshot.*", "_find_tele", "snapshot"},
    "src/repro/obs/serve.py": {
        "ServeSnapshot.*", "ServeLog.*", "_Req.*", "snapshot",
        "format_summary"},
    "src/repro/runtime/kvbank.py": {
        "pool_init", "pool_coded", "parity_members"},
}

_WAIVER_RE = re.compile(r"#\s*analysis:\s*([\w-]+)")


def _waivers(source: str) -> Dict[int, Set[str]]:
    """{line (1-based): waived rule ids} — a waiver also covers the line
    directly below it, so it can sit above a long statement."""
    out: Dict[int, Set[str]] = {}
    for i, line in enumerate(source.splitlines(), start=1):
        for m in _WAIVER_RE.finditer(line):
            out.setdefault(i, set()).add(m.group(1))
            out.setdefault(i + 1, set()).add(m.group(1))
    return out


def _matches(qualname: str, names: Set[str]) -> bool:
    if "*" in names or qualname in names:
        return True
    cls = qualname.split(".")[0]
    return f"{cls}.*" in names and "." in qualname


# --------------------------------------------------------- oracle purity
def check_oracle_purity(root: Optional[str] = None) -> List[Finding]:
    base = root if root is not None else f"{REPO_ROOT}/{ORACLE_SCOPE}"
    out: List[Finding] = []
    for path in python_files(base):
        tree = _parse(path, out)
        if tree is None:
            continue
        for node in ast.walk(tree):
            mods: List[str] = []
            if isinstance(node, ast.Import):
                mods = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom) and node.level == 0:
                mods = [node.module or ""]
            for mod in mods:
                if not _oracle_import_ok(mod):
                    out.append(Finding(
                        "oracle-purity", f"{rel(path)}:{node.lineno}",
                        f"oracle module imports {mod!r} — the golden model "
                        "must stay pure NumPy/stdlib (no jax, no shared "
                        "repro code) so it cannot inherit a core "
                        "misconception", line=node.lineno))
    return out


def _oracle_import_ok(mod: str) -> bool:
    return any(mod == allowed or mod.startswith(allowed + ".")
               for allowed in ORACLE_ALLOWED_ROOTS)


# ------------------------------------------------------- traced-code rules
def check_traced_rules(paths: Optional[Iterable[str]] = None,
                       traced: Optional[Set[str]] = None,
                       host: Optional[Set[str]] = None) -> List[Finding]:
    """tracer-branch + static-geometry + narrow-counter + classification
    completeness over the traced scope. Explicit ``traced``/``host`` sets
    override the per-file classification maps (used by the analyzer's own
    fixture tests)."""
    if paths is None:
        paths = _traced_scope_files()
    out: List[Finding] = []
    for path in paths:
        out.extend(_check_traced_file(path, traced=traced, host=host))
    return out


def _traced_scope_files() -> List[str]:
    files: List[str] = []
    for entry in TRACED_SCOPE:
        full = f"{REPO_ROOT}/{entry}"
        if entry.endswith(".py"):
            files.append(full)
        else:
            files.extend(python_files(full))
    return files


def _parse(path: str, out: List[Finding]):
    try:
        with open(path, "r", encoding="utf-8") as fh:
            source = fh.read()
        return ast.parse(source, filename=path)
    except (OSError, SyntaxError) as e:
        out.append(Finding("parse-error", rel(path), str(e)))
        return None


def _check_traced_file(path: str, traced: Optional[Set[str]] = None,
                       host: Optional[Set[str]] = None) -> List[Finding]:
    out: List[Finding] = []
    try:
        with open(path, "r", encoding="utf-8") as fh:
            source = fh.read()
        tree = ast.parse(source, filename=path)
    except (OSError, SyntaxError) as e:
        return [Finding("parse-error", rel(path), str(e))]
    rpath = rel(path)
    if traced is None:
        traced = TRACED_FUNCTIONS.get(rpath, set())
    if host is None:
        host = HOST_FUNCTIONS.get(rpath, set())
    waivers = _waivers(source)

    def visit_scope(body, prefix: str):
        for node in body:
            if isinstance(node, ast.ClassDef):
                visit_scope(node.body, f"{node.name}.")
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                qual = prefix + node.name
                is_traced = _matches(qual, traced)
                is_host = _matches(qual, host)
                if not is_traced and not is_host:
                    out.append(Finding(
                        "rule-classification", f"{rpath}:{node.lineno}",
                        f"function {qual!r} is not classified as TRACED or "
                        "HOST in repro.analysis.rules — new functions in "
                        "the cycle-engine surface must be classified so "
                        "the tracer rules cover them", line=node.lineno))
                elif is_traced:
                    _FunctionLint(rpath, qual, waivers, out).run(node)
                # host functions: no tracer rules, but nested defs under a
                # classified function inherit its classification, so stop.

    visit_scope(tree.body, "")
    return out


class _FunctionLint:
    """Single-pass lint of one traced function's body.

    Tracks two alias sets as assignments are encountered in source order:
    names bound to *static* expressions (usable in branches/casts) and
    names bound to *allocated-geometry* fields (illegal as ``//``/``%``
    divisors). Conditional (``IfExp``) binds deliberately do not propagate
    allocated-ness: ``rs if rs_active is None else rs_active`` is the
    sanctioned static-indexing fallback, not a stride leak.
    """

    def __init__(self, rpath: str, qual: str,
                 waivers: Dict[int, Set[str]], out: List[Finding]):
        self.rpath = rpath
        self.qual = qual
        self.waivers = waivers
        self.out = out
        self.static_names: Set[str] = set()
        self.geom_names: Set[str] = set()

    # ------------------------------------------------------------ plumbing
    def run(self, fn) -> None:
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign):
                self._track_assign(node)
            elif isinstance(node, (ast.If, ast.While)):
                self._check_branch(node.test, kind=type(node).__name__)
            elif isinstance(node, ast.IfExp):
                self._check_branch(node.test, kind="conditional expression")
            elif isinstance(node, ast.Call):
                self._check_cast(node)
                self._check_wide_kwargs(node)
            elif isinstance(node, ast.BinOp):
                self._check_geometry(node)
                self._check_wide_binop(node)
            elif isinstance(node, ast.AugAssign):
                self._check_wide_augassign(node)

    def _flag(self, rule: str, node, message: str) -> None:
        line = getattr(node, "lineno", 0)
        if rule in self.waivers.get(line, ()):
            return
        self.out.append(Finding(
            rule, f"{self.rpath}:{line}",
            f"in traced function {self.qual!r}: {message}", line=line))

    # ----------------------------------------------------- alias tracking
    def _track_assign(self, node: ast.Assign) -> None:
        targets = node.targets[0]
        if isinstance(targets, ast.Tuple) and isinstance(node.value, ast.Tuple) \
                and len(targets.elts) == len(node.value.elts):
            pairs = list(zip(targets.elts, node.value.elts))
        else:
            pairs = [(targets, node.value)]
        for tgt, val in pairs:
            if not isinstance(tgt, ast.Name):
                continue
            if self._is_static(val):
                self.static_names.add(tgt.id)
            else:
                self.static_names.discard(tgt.id)
            if self._is_alloc_geometry(val):
                self.geom_names.add(tgt.id)
            else:
                self.geom_names.discard(tgt.id)

    # ------------------------------------------------- static-test grammar
    def _is_static(self, node) -> bool:
        if isinstance(node, ast.Constant):
            return True
        if isinstance(node, ast.Name):
            return node.id in self.static_names
        if isinstance(node, ast.Attribute):
            if node.attr in STATIC_ATTRS:
                return True
            root = node
            while isinstance(root, ast.Attribute):
                root = root.value
            return isinstance(root, ast.Name) and root.id in STATIC_ROOTS
        if isinstance(node, ast.Subscript):
            return self._is_static(node.value) and self._is_static(node.slice)
        if isinstance(node, (ast.Tuple, ast.List)):
            return all(self._is_static(e) for e in node.elts)
        if isinstance(node, ast.Compare):
            # pytree-structure checks (`x is None`) are static regardless
            # of what x holds — None-ness is resolved at trace time
            if all(isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops) \
                    and all(isinstance(c, ast.Constant) and c.value is None
                            for c in node.comparators):
                return True
            return (self._is_static(node.left)
                    and all(self._is_static(c) for c in node.comparators))
        if isinstance(node, ast.BoolOp):
            return all(self._is_static(v) for v in node.values)
        if isinstance(node, ast.UnaryOp):
            return self._is_static(node.operand)
        if isinstance(node, ast.BinOp):
            return self._is_static(node.left) and self._is_static(node.right)
        if isinstance(node, ast.IfExp):
            return (self._is_static(node.test) and self._is_static(node.body)
                    and self._is_static(node.orelse))
        if isinstance(node, ast.Call):
            fname = node.func.id if isinstance(node.func, ast.Name) else None
            if fname in ALWAYS_STATIC_CALLS:
                return True
            if fname in STATIC_CALLS or fname in ("int", "float", "bool"):
                return all(self._is_static(a) for a in node.args)
            return False
        return False

    def _check_branch(self, test, kind: str) -> None:
        if not self._is_static(test):
            self._flag(
                "tracer-branch", test,
                f"python {kind} on a value that is not statically "
                "resolvable (params/shapes/`is None`) — on a tracer this "
                "is a TracerBoolConversionError or a silent "
                "concretization; use jnp.where/lax.cond")

    def _check_cast(self, node: ast.Call) -> None:
        fname = node.func.id if isinstance(node.func, ast.Name) else None
        if fname in ("int", "float", "bool") and node.args \
                and not self._is_static(node.args[0]):
            self._flag(
                "tracer-branch", node,
                f"{fname}() on a value that is not statically resolvable — "
                "concretizes a tracer (use .astype / _concrete_int on the "
                "host side)")

    # --------------------------------------------------- static geometry
    def _is_alloc_geometry(self, node) -> bool:
        if isinstance(node, ast.Attribute):
            return (node.attr in GEOM_FIELDS
                    and isinstance(node.value, ast.Name)
                    and node.value.id in STATIC_ROOTS)
        if isinstance(node, ast.Name):
            return node.id in self.geom_names
        return False

    def _check_geometry(self, node: ast.BinOp) -> None:
        if not isinstance(node.op, (ast.FloorDiv, ast.Mod)):
            return
        if self._is_alloc_geometry(node.right):
            opname = "//" if isinstance(node.op, ast.FloorDiv) else "%"
            field = (node.right.attr if isinstance(node.right, ast.Attribute)
                     else node.right.id)
            self._flag(
                "static-geometry", node,
                f"`{opname} {field}` divides by the *allocated* geometry — "
                "under a padded group allocation this mis-addresses every "
                "sub-allocation point; index with the active geometry "
                "(active_geometry / TunableParams.*_active)")

    # ----------------------------------------------------- narrow counter
    def _contains_plain_add(self, node) -> bool:
        return any(isinstance(n, ast.BinOp)
                   and isinstance(n.op, (ast.Add, ast.Sub))
                   for n in ast.walk(node))

    def _check_wide_binop(self, node: ast.BinOp) -> None:
        if not isinstance(node.op, (ast.Add, ast.Sub)):
            return
        for side in (node.left, node.right):
            if isinstance(side, ast.Attribute) and side.attr in WIDE_FIELDS:
                self._flag(
                    "narrow-counter", node,
                    f"`{side.attr}` is a wide (lo, hi) counter — plain "
                    "`+`/`-` corrupts the limb pair (and a narrow uint32 "
                    "would overflow in long scans); accumulate with "
                    "repro.core.state.wide_add")

    def _check_wide_augassign(self, node: ast.AugAssign) -> None:
        tgt = node.target
        if isinstance(tgt, ast.Attribute) and tgt.attr in WIDE_FIELDS:
            self._flag(
                "narrow-counter", node,
                f"augmented assignment to wide counter `{tgt.attr}` — "
                "accumulate with repro.core.state.wide_add")

    def _check_wide_kwargs(self, node: ast.Call) -> None:
        for kw in node.keywords:
            if kw.arg in WIDE_FIELDS and self._contains_plain_add(kw.value):
                self._flag(
                    "narrow-counter", kw.value,
                    f"`{kw.arg}=` is built with plain `+`/`-` — wide "
                    "counters must be accumulated with "
                    "repro.core.state.wide_add")


# ------------------------------------------------------- kernel interpret
# non-test code that pins the Pallas interpreter: the production default is
# interpret=None (resolved from the backend by kernels.common.resolve_interpret)
KERNEL_INTERPRET_SCOPE = ("src/repro", "benchmarks")


def check_kernel_interpret(
        roots: Optional[Iterable[str]] = None) -> List[Finding]:
    """Flag ``interpret=True`` hard-coded at non-test kernel call sites.

    The kernel wrappers default to ``interpret=None``, which resolves to
    native compilation on TPU and the Pallas interpreter elsewhere
    (``repro.kernels.common.resolve_interpret``). A call site that pins
    ``True`` silently runs the CPU interpreter on hardware — tests may pin
    it (they are not scanned); anything else needs an
    ``# analysis: kernel-interpret`` waiver."""
    bases = (list(roots) if roots is not None
             else [f"{REPO_ROOT}/{e}" for e in KERNEL_INTERPRET_SCOPE])
    out: List[Finding] = []
    for base in bases:
        paths = [base] if os.path.isfile(base) else python_files(base)
        for path in paths:
            out.extend(_check_interpret_file(path))
    return out


def _check_interpret_file(path: str) -> List[Finding]:
    out: List[Finding] = []
    try:
        with open(path, "r", encoding="utf-8") as fh:
            source = fh.read()
        tree = ast.parse(source, filename=path)
    except (OSError, SyntaxError) as e:
        return [Finding("parse-error", rel(path), str(e))]
    waivers = _waivers(source)
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        for kw in node.keywords:
            if kw.arg != "interpret":
                continue
            if not (isinstance(kw.value, ast.Constant)
                    and kw.value.value is True):
                continue
            line = kw.value.lineno
            if "kernel-interpret" in (waivers.get(line, set())
                                      | waivers.get(node.lineno, set())):
                continue
            out.append(Finding(
                "kernel-interpret", f"{rel(path)}:{line}",
                "kernel call hard-codes interpret=True — on TPU this "
                "silently executes the Pallas CPU interpreter; pass "
                "interpret=None and let resolve_interpret pick the "
                "backend (tests may pin True)", line=line))
    return out


# -------------------------------------------------------- bench manifests
def check_bench_manifests() -> List[Finding]:
    """Fold scripts/check_bench_manifests.py in as an analysis rule."""
    import importlib.util

    path = f"{REPO_ROOT}/scripts/check_bench_manifests.py"
    spec = importlib.util.spec_from_file_location("check_bench_manifests",
                                                  path)
    if spec is None or spec.loader is None:          # pragma: no cover
        return [Finding("bench-manifest", rel(path),
                        "cannot load scripts/check_bench_manifests.py")]
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return [Finding("bench-manifest", rel(path), problem)
            for problem in mod.check(REPO_ROOT)]


# ------------------------------------------------------------- layer entry
def run(strict: bool = False,
        paths: Optional[Iterable[str]] = None) -> List[Finding]:
    del strict
    out = check_oracle_purity()
    out += check_traced_rules(paths)
    out += check_kernel_interpret()
    out += check_bench_manifests()
    return out
