"""Layer 2 — jaxpr lint: prove compile-key and carry invariants statically.

The sweep engine's batching story rests on three invariants that used to be
re-proved by hand (or by counting live compiles) every time a flag or axis
landed:

* **Compile-key completeness** — ``repro.sweep.grid.static_signature`` must
  be a *complete* compile key: any two points in one signature class must
  trace to byte-identical jaxprs through the engine's program
  (``cycle_fn`` over the class's shared allocation). A static argument
  leaking into the traced program (a python int baked in from the point,
  a shape derived from α/r outside the masked geometry) shows up here as a
  jaxpr hash split within one class — without running a sweep or counting
  compiles.
* **Carry stability** — the scan carry must be a structural fixed point:
  ``cycle_fn``'s output state must have exactly the input state's pytree
  structure and per-leaf shape/dtype/weak_type. Any drift (a counter
  promoted by a stray python scalar, a new leaf appearing under a flag)
  would re-trace every chunk of a streamed replay.
* **Flag-off identity** — with ``telemetry=False``/``faults=False`` the
  carry must hold ``tele is None``/``fault is None`` (an absent pytree
  node, not a zeroed plane) and the jaxpr must be byte-identical whether
  the flags are passed explicitly or defaulted — the static gating trick
  (``MemParams.telemetry``/``faults``/``traced_geometry``) that keeps
  flags-off programs bit-identical to the pre-flag baseline. A flag that
  starts leaking traced ops into the off path splits these jaxprs.

Everything here is abstract evaluation: ``jax.make_jaxpr`` /
``jax.eval_shape`` only — no device program ever runs, so the lint is fast
enough for the fast CI tier. The runtime complement is
``repro.analysis.guard.recompile_guard`` (live compile counting in tests).
"""
from __future__ import annotations

import hashlib
import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax

from repro.analysis.base import Finding


# ---------------------------------------------------------------- helpers
def _avalize(tree):
    """Concrete pytree -> ShapeDtypeStruct pytree (weak_type preserved)."""
    def conv(x):
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            return jax.ShapeDtypeStruct(
                x.shape, x.dtype, weak_type=bool(getattr(x, "weak_type",
                                                         False)))
        return x
    return jax.tree.map(conv, tree)


def _aval_fingerprint(tree) -> str:
    """Stable string of a pytree's structure + per-leaf aval."""
    leaves, treedef = jax.tree.flatten(tree)
    parts = [str(treedef)]
    for leaf in leaves:
        parts.append(f"{getattr(leaf, 'shape', ())}/"
                     f"{getattr(leaf, 'dtype', type(leaf).__name__)}/"
                     f"w{int(bool(getattr(leaf, 'weak_type', False)))}")
    return ";".join(parts)


def jaxpr_hash(fn, *avals) -> str:
    """SHA-256 of the closed jaxpr ``fn`` traces to on ``avals``."""
    jpr = jax.make_jaxpr(fn)(*avals)
    return hashlib.sha256(str(jpr).encode("utf-8")).hexdigest()


def _point_program_inputs(pt, sys):
    """(state, trace, tunables) aval trees exactly as the engine would trace
    them for ``pt`` on the shared system ``sys``."""
    from repro.sweep import engine, workloads

    tn = engine.stack_tunables([pt], sys.p.queue_depth)
    tn1 = jax.tree.map(lambda x: x[0], tn)
    st = sys.init(tn1)
    if sys.p.faults:
        fault = jax.tree.map(lambda x: x[0],
                             engine._stack_faults([pt], sys.p))
        st = st._replace(mem=st.mem._replace(fault=fault))
    trace = workloads.build_trace(pt)
    return _avalize(st), _avalize(trace), _avalize(tn1)


# ------------------------------------------------- compile-key completeness
def lint_program_class(label: str, programs: Sequence[Tuple]) -> List[Finding]:
    """Core compile-key check, program-agnostic (fixture-testable): each
    entry of ``programs`` is ``(fn, input_trees...)`` claiming membership
    in ONE compile class; all must produce identical input avals and an
    identical jaxpr, or the class would compile more than one program."""
    fingerprints: Dict[str, int] = {}
    hashes: Dict[str, int] = {}
    for k, (fn, *inputs) in enumerate(programs):
        fingerprints.setdefault(_aval_fingerprint(tuple(inputs)), k)
        hashes.setdefault(jaxpr_hash(fn, *inputs), k)
    if len(fingerprints) > 1:
        ks = sorted(fingerprints.values())
        return [Finding(
            "jaxpr-static-leak", label,
            f"members {ks[0]} and {ks[1]} of one compile class trace "
            "different program-input shapes/dtypes — a static coordinate "
            "is leaking out of the class key (the class would compile "
            "more than one program)")]
    if len(hashes) > 1:
        ks = sorted(hashes.values())
        return [Finding(
            "jaxpr-static-leak", label,
            f"members {ks[0]} and {ks[1]} of one compile class trace "
            "different jaxprs despite identical input avals — a python "
            "value is baked into the traced program")]
    return []


def lint_signature_classes(points: Sequence) -> List[Finding]:
    """Every point of one ``static_signature`` class must produce identical
    program-input avals and an identical ``cycle_fn`` jaxpr on the class's
    shared group allocation — the static proof behind 'one program per
    grid'."""
    from repro.sweep import engine
    from repro.sweep.grid import batch_geometry_alloc, partition

    out: List[Finding] = []
    for batch in partition(list(points)):
        pts = batch.points
        traced = len({pt.derived_slots()[:2] for pt in pts}) > 1
        sys = engine.system_for(pts[0],
                                geometry_alloc=batch_geometry_alloc(pts),
                                traced_geometry=traced)
        programs = [(sys.cycle_fn, *_point_program_inputs(pt, sys))
                    for pt in pts]
        out.extend(lint_program_class(f"signature:{batch.signature}",
                                      programs))
    return out


def count_distinct_programs(points: Sequence) -> int:
    """Distinct (signature, cycle_fn jaxpr) programs a sweep would compile —
    the static analogue of the ``sweep_compile_count`` fixture delta."""
    from repro.sweep import engine
    from repro.sweep.grid import batch_geometry_alloc, partition

    seen = set()
    for batch in partition(list(points)):
        pts = batch.points
        traced = len({pt.derived_slots()[:2] for pt in pts}) > 1
        sys = engine.system_for(pts[0],
                                geometry_alloc=batch_geometry_alloc(pts),
                                traced_geometry=traced)
        st_a, tr_a, tn_a = _point_program_inputs(pts[0], sys)
        seen.add(jaxpr_hash(sys.cycle_fn, st_a, tr_a, tn_a))
    return len(seen)


# ------------------------------------------------------------ loop bodies
def while_body_primitives(closed_jaxpr, scope: str) -> List[Dict[str, int]]:
    """Primitive counts of the body of every ``while`` traced under the
    named scope ``scope``, nested calls and loops included, in trace
    order. A batched index inside a loop body shows up here as a
    ``gather`` or ``scatter``: one device op per trip that does not fuse."""
    found: List[Dict[str, int]] = []

    def count(jpr, into):
        for e in jpr.eqns:
            into[e.primitive.name] = into.get(e.primitive.name, 0) + 1
            for sub in jax.core.jaxprs_in_params(e.params):
                count(sub, into)

    def visit(jpr):
        for e in jpr.eqns:
            if (e.primitive.name == "while"
                    and scope in str(e.source_info.name_stack)):
                prims: Dict[str, int] = {}
                count(e.params["body_jaxpr"].jaxpr, prims)
                found.append(prims)
            for sub in jax.core.jaxprs_in_params(e.params):
                visit(sub)

    visit(closed_jaxpr.jaxpr)
    return found


# ----------------------------------------------------------- row-wide work
# Primitives that may output a whole bank: in-place updates, and the
# wrappers that carry the state (a ``while`` whose predicate is batched is
# checked on its own, below).
ROW_WIDE_EXEMPT = ("dynamic_update_slice", "while", "pjit", "jit")

# Row-wide equations that are truly needed, by (function, primitive), with
# the reason. A function is the innermost frame of the program's source.
ROW_WIDE_ALLOWED: Dict[Tuple[str, str], str] = {
    ("_on", "while"): (
        "core/dynamic.py: a loop of at most one trip that runs on the "
        "cycles where some point's region encode completes or a region is "
        "evicted; batched, it selects the parity state it carries once per "
        "such cycle, not every cycle"),
}


def _frame(eqn) -> str:
    from jax._src import source_info_util

    fr = source_info_util.user_frame(eqn.source_info.traceback)
    return fr.function_name.rsplit(".", 1)[-1] if fr else "?"


def _wide(widths):
    """Predicate on shapes: a whole bank wide. ``widths`` holds a bank's
    widths: a whole number is a dimension, a tuple a run of dimensions (a
    table kept in rows of lanes, ``core.state.bank_table``)."""
    dims = {w for w in widths if isinstance(w, int)}
    runs = [tuple(w) for w in widths if not isinstance(w, int)]

    def is_wide(shape):
        return bool(set(shape) & dims) or any(
            shape[k:k + len(r)] == r for r in runs
            for k in range(len(shape) - len(r) + 1))
    return is_wide


def row_wide_equations(closed_jaxpr, widths) -> List[Tuple[str, str, tuple]]:
    """``(function, primitive, shape)`` of every equation, sub-jaxprs
    included, that outputs an array a whole bank wide, other than scatters
    and ``ROW_WIDE_EXEMPT``; and of every ``while`` whose predicate is
    batched and which carries such an array (its lowering selects each
    carried array per trip). ``widths`` as for ``_wide``."""
    is_wide = _wide(widths)
    found: List[Tuple[str, str, tuple]] = []

    def wide(avals):
        return next((a.shape for a in avals
                     if is_wide(tuple(getattr(a, "shape", ())))), None)

    def visit(jpr):
        for e in jpr.eqns:
            for sub in jax.core.jaxprs_in_params(e.params):
                visit(sub)
            name = e.primitive.name
            if name == "while":
                shape = wide([v.aval for v in e.outvars])
                batched = e.params["cond_jaxpr"].out_avals[0].shape != ()
                if batched and shape is not None:
                    found.append((_frame(e), name, shape))
            elif not (name.startswith("scatter") or name in ROW_WIDE_EXEMPT):
                shape = wide([v.aval for v in e.outvars])
                if shape is not None:
                    found.append((_frame(e), name, shape))

    visit(closed_jaxpr.jaxpr)
    return found


def wide_scatters(closed_jaxpr, widths,
                  scope: str = "") -> List[Tuple[tuple, str, int]]:
    """``(shape, dtype, updates a point)`` of every scatter, sub-jaxprs
    included, traced under the named scope ``scope`` into an array a whole
    bank wide (``widths`` as for ``_wide``). The updates a point are the
    index tuples the scatter is given for one point of a batch, each a
    cell (or a window of cells) written or dropped: a TPU scatter costs
    each of them, dropped or not."""
    is_wide = _wide(widths)
    found: List[Tuple[tuple, str, int]] = []

    def visit(jpr):
        for e in jpr.eqns:
            for sub in jax.core.jaxprs_in_params(e.params):
                visit(sub)
            if not (e.primitive.name.startswith("scatter")
                    and scope in str(e.source_info.name_stack)):
                continue
            operand, indices = e.invars[0].aval, e.invars[1].aval
            if not is_wide(operand.shape):
                continue
            # the last dimension of the indices holds one index tuple
            batch = e.params["dimension_numbers"].scatter_indices_batching_dims
            per_point = [n for k, n in enumerate(indices.shape[:-1])
                         if k not in batch]
            found.append((operand.shape, str(operand.dtype),
                          math.prod(per_point)))

    visit(closed_jaxpr.jaxpr)
    return found


def _derived(e, flags) -> List[bool]:
    """Per output of the first-order equation ``e``: whether it derives
    from an input marked in ``flags``, as an array shaped as one (an
    in-place update, a select, a copy) and not a value read out of it."""
    marked = {(tuple(v.aval.shape), v.aval.dtype)
              for v, f in zip(e.invars, flags) if f}
    return [(tuple(v.aval.shape), v.aval.dtype) in marked
            for v in e.outvars]


def wide_gathers(closed_jaxpr, widths, scope: str = "",
                 sources: Optional[Sequence[int]] = None
                 ) -> List[Tuple[tuple, str, int]]:
    """``(shape, dtype, cells a point)`` of every gather, sub-jaxprs
    included, traced under the named scope ``scope`` from an array a whole
    bank wide (``widths`` as for ``_wide``). The cells a point are the
    elements the gather reads for one point of a batch: from a table kept
    in rows of lanes a TPU gather costs what it reads. Given ``sources``
    (positions among the jaxpr's inputs), only gathers from those inputs
    count: from an input itself or from what in-place updates, selects,
    calls and loops make of it."""
    from jax.extend.core import Literal

    is_wide = _wide(widths)
    found: List[Tuple[tuple, str, int]] = []

    def visit(jpr, flags, collect):
        """Walk ``jpr`` whose inputs are marked by ``flags``; the marks of
        its outputs."""
        marked = {v for v, f in zip(jpr.invars, flags) if f}

        def on(vs):
            return [sources is None or (not isinstance(v, Literal)
                                        and v in marked) for v in vs]

        for e in jpr.eqns:
            ins, prm = on(e.invars), e.params
            name = e.primitive.name
            if name == "while":
                nc, nb = prm["cond_nconsts"], prm["body_nconsts"]
                consts, carry = ins[nc:nc + nb], ins[nc + nb:]
                body = prm["body_jaxpr"].jaxpr
                while True:          # the marks a carry takes in any trip
                    out = visit(body, consts + carry, False)
                    new = [a or b for a, b in zip(carry, out)]
                    if new == carry:
                        break
                    carry = new
                visit(prm["cond_jaxpr"].jaxpr, ins[:nc] + carry, collect)
                outs = visit(body, consts + carry, collect)
            else:
                subs = list(jax.core.jaxprs_in_params(prm))
                if len(subs) == 1 and len(subs[0].invars) == len(ins):
                    outs = visit(subs[0], ins, collect)    # a call
                else:
                    # any other form (a branch, a scatter's combiner): its
                    # sub-jaxprs see every input marked where one is
                    for sub in subs:
                        visit(sub, [any(ins)] * len(sub.invars), collect)
                    outs = _derived(e, ins)
            marked.update(v for v, f in zip(e.outvars, outs) if f)
            if not (collect and name == "gather" and ins[0]
                    and scope in str(e.source_info.name_stack)):
                continue
            operand, indices = e.invars[0].aval, e.invars[1].aval
            if not is_wide(operand.shape):
                continue
            # the last dimension of the indices holds one index tuple
            dn = prm["dimension_numbers"]
            per_point = [n for k, n in enumerate(indices.shape[:-1])
                         if k not in dn.start_indices_batching_dims]
            window = [n for k, n in enumerate(prm["slice_sizes"])
                      if k not in dn.operand_batching_dims]
            found.append((operand.shape, str(operand.dtype),
                          math.prod(per_point) * math.prod(window)))
        return [sources is None or v in marked for v in jpr.outvars]

    jpr = closed_jaxpr.jaxpr
    visit(jpr, [sources is None or k in sources
                for k in range(len(jpr.invars))], True)
    return found


def cycle_state_input(sys, field: str) -> int:
    """The position of the state leaf ``mem.<field>`` among the inputs of
    ``sys.cycle_fn``'s jaxpr (``batched_cycle_jaxpr``): the state's leaves
    come first."""
    st = jax.eval_shape(sys.init)
    leaves, treedef = jax.tree.flatten(st)
    return getattr(jax.tree.unflatten(treedef, range(len(leaves))).mem,
                   field)


def batched_cycle_jaxpr(pt=None, batch: int = 2):
    """(the jaxpr of ``cycle_fn`` as the sweep engine runs it, under
    ``vmap`` over ``batch`` points; a bank's widths for ``_wide``; the
    system) for ``pt``, by default 8,192-row banks, where the pattern
    builders' lookups pick gathers (``core/state.py``)."""
    from repro.sweep import engine
    from repro.sweep.grid import SweepPoint

    pt = pt if pt is not None else SweepPoint(n_rows=8192, length=8,
                                              alpha=0.25, r=0.05)
    sys = engine.system_for(pt)
    inputs = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct((batch,) + a.shape, a.dtype),
        _point_program_inputs(pt, sys))
    jpr = jax.make_jaxpr(jax.vmap(sys.cycle_fn))(*inputs)
    m = inputs[0].mem
    widths = (sys.p.n_rows, sys.p.n_slots * sys.p.region_size,
              m.banks_data.shape[2:], m.parity_data.shape[2:])
    return jpr, widths, sys


def lint_row_wide(pt=None, batch: int = 2) -> List[Finding]:
    """A simulated cycle's device work must follow its requests, not the
    memory it holds: no equation of the batched ``cycle_fn`` (as the sweep
    engine runs it, under ``vmap``) may output an array a whole bank wide
    (a dimension of ``n_rows``) or a whole parity bank wide (``n_slots x
    region_size``), except in-place updates, the loops that carry the
    state and ``ROW_WIDE_ALLOWED``. Checked at 8,192 rows, where the
    pattern builders' lookups pick gathers (``core/state.py``); a finding
    is named by its innermost function (a one-hot lookup or write of a bank
    table: ``_onehot``, ``_count``)."""
    jpr, widths, sys = batched_cycle_jaxpr(pt, batch)
    rows, par_rows = widths[:2]
    return [Finding("jaxpr-row-wide", f"cycle_fn[{fn}]",
                    f"{prim} outputs {shape}, a whole bank wide (rows "
                    f"{rows}, parity rows {par_rows}): a cycle's work "
                    "grows with the memory, not with its requests")
            for fn, prim, shape in row_wide_equations(jpr, widths)
            if (fn, prim) not in ROW_WIDE_ALLOWED]


# --------------------------------------------------------- carry stability
def lint_carry_stability(pt=None) -> List[Finding]:
    """``cycle_fn`` must map its carry to an identical-structure carry:
    same treedef, same shape/dtype/weak_type per leaf. Checked on
    representative systems: flags off, telemetry on, faults on, and a
    traced-geometry padded allocation."""
    from repro.sweep.grid import SweepPoint

    base = pt if pt is not None else SweepPoint(n_rows=32, length=8,
                                                alpha=0.5, r=0.25)
    variants = [
        ("flags-off", base),
        ("telemetry", base.replace(telemetry=True)),
        ("faults", base.replace(faults=(("bank", 0, 2, 5),))),
    ]
    out: List[Finding] = []
    for label, vpt in variants:
        out.extend(_carry_findings(label, vpt))
    out.extend(_carry_findings(
        "traced-geometry", base,
        geometry_alloc=tuple(2 * g for g in base.derived_slots()),
        traced=True))
    return out


def lint_carry(label: str, fn, carry, *args, pick=None) -> List[Finding]:
    """Core carry-stability check, program-agnostic (fixture-testable):
    abstract-eval ``fn(carry, *args)`` and require the output carry to
    match ``carry`` exactly in treedef and per-leaf shape/dtype/weak_type.
    ``pick`` extracts the carry from the output (default: the output
    itself, or element 0 of a tuple — the ``(state, emit)`` convention)."""
    out = jax.eval_shape(fn, carry, *args)
    if pick is not None:
        out = pick(out)
    elif isinstance(out, tuple) and len(out) == 2:
        out = out[0]
    if _aval_fingerprint(carry) != _aval_fingerprint(out):
        drift = _first_leaf_drift(carry, out)
        return [Finding(
            "jaxpr-carry-drift", label,
            f"scan carry is not structurally stable: {drift} — every "
            "chunk/scan step would re-trace (dtype/shape/weak_type drift "
            "in the carry)")]
    return []


def _carry_findings(label: str, pt, geometry_alloc=None,
                    traced: bool = False) -> List[Finding]:
    from repro.sweep import engine

    sys = engine.system_for(pt, geometry_alloc=geometry_alloc,
                            traced_geometry=traced)
    st_a, tr_a, tn_a = _point_program_inputs(pt, sys)
    return lint_carry(f"cycle_fn[{label}]", sys.cycle_fn, st_a, tr_a, tn_a)


def _first_leaf_drift(a, b) -> str:
    la, ta = jax.tree.flatten(a)
    lb, tb = jax.tree.flatten(b)
    if str(ta) != str(tb):
        return f"treedef changed: {ta} -> {tb}"
    for i, (x, y) in enumerate(zip(la, lb)):
        sx = (getattr(x, "shape", None), getattr(x, "dtype", None),
              bool(getattr(x, "weak_type", False)))
        sy = (getattr(y, "shape", None), getattr(y, "dtype", None),
              bool(getattr(y, "weak_type", False)))
        if sx != sy:
            return f"leaf {i}: {sx} -> {sy}"
    return "unknown drift"


# --------------------------------------------------------- flag-off identity
def lint_flag_identity(pt=None) -> List[Finding]:
    """Flags-off must mean *absent*, not zeroed: the off-state carries
    ``tele is None`` / ``fault is None``, the off-jaxpr is byte-identical
    whether flags are defaulted or passed explicitly False, and turning a
    flag on genuinely changes the program (the flag is load-bearing)."""
    from repro.core.codes import get_tables
    from repro.core.state import make_params
    from repro.core.system import CodedMemorySystem
    from repro.sweep import engine
    from repro.sweep.grid import SweepPoint

    base = pt if pt is not None else SweepPoint(n_rows=32, length=8,
                                                alpha=0.5, r=0.25)
    out: List[Finding] = []
    sys_off = engine.system_for(base)
    st = sys_off.init()
    if st.mem.tele is not None or st.mem.fault is not None:
        out.append(Finding(
            "jaxpr-flag-leak", "MemState[flags-off]",
            "telemetry/fault leaves present with the flags off — the "
            "flags-off carry must have the pre-flag tree structure "
            "(tele=None, fault=None)"))
        return out
    st_a, tr_a, tn_a = _point_program_inputs(base, sys_off)
    h_off = jaxpr_hash(sys_off.cycle_fn, st_a, tr_a, tn_a)

    # an explicitly-flagged-off system must trace the identical program
    tables = get_tables(base.scheme, n_data=base.n_data)
    params = make_params(tables, n_rows=base.n_rows, alpha=base.alpha,
                         r=base.r, queue_depth=base.queue_depth,
                         telemetry=False, faults=False)
    sys_explicit = CodedMemorySystem(tables, params, n_cores=base.n_cores)
    h_explicit = jaxpr_hash(sys_explicit.cycle_fn, st_a, tr_a, tn_a)
    if h_off != h_explicit:
        out.append(Finding(
            "jaxpr-flag-leak", "cycle_fn[flags-off]",
            "explicit telemetry=False/faults=False traces a different "
            "jaxpr than the defaulted flags — the off path is not the "
            "pre-flag baseline program"))

    # each flag alone must change the traced program (it is load-bearing —
    # a flag whose on-jaxpr equals the off-jaxpr does nothing)
    for label, vpt in (("telemetry", base.replace(telemetry=True)),
                       ("faults", base.replace(faults=(("bank", 0, 2),)))):
        sys_on = engine.system_for(vpt)
        o_st, o_tr, o_tn = _point_program_inputs(vpt, sys_on)
        h_on = jaxpr_hash(sys_on.cycle_fn, o_st, o_tr, o_tn)
        if h_on == h_off:
            out.append(Finding(
                "jaxpr-flag-leak", f"cycle_fn[{label}-on]",
                f"{label}=True traces the same jaxpr as the off program — "
                "the flag no longer gates any computation"))
    return out


# ------------------------------------------------- pooled serve-step lints
def lint_serve_step() -> List[Finding]:
    """The pooled decode step's observability contract, proved statically:

    * **tele-off absence** — with ``tele=None`` the cache carries an absent
      leaf (not a zeroed plane) and the step is a structural fixed point of
      its carry; the telemetry plane must never change the pool avals.
    * **tele is load-bearing** — turning the plane on must change the
      traced program (otherwise the metrics cost nothing because they
      measure nothing).
    * **coded is a compile switch** — the uncoded pool (zero-size parity
      arrays) must trace a genuinely different program, not a masked
      branch of the coded one; same for disabling the ReCoding unit
      (``recode_budget=-1``)."""
    import dataclasses

    import jax.numpy as jnp

    from repro.configs.base import get_config
    from repro.models import lm
    from repro.obs.serve import init_serve_telemetry
    from repro.runtime import kvbank as kb
    from repro.runtime.steps import make_pooled_serve_step

    cfg = dataclasses.replace(get_config("qwen2.5-3b").reduced(), kv_page=4)
    kvcfg = kb.KVBankConfig(n_banks=cfg.kv_banks, page=4,
                            pool_pages=4 * cfg.kv_banks, max_pages=4)
    b = 2
    params_a = jax.eval_shape(
        lambda: lm.init_params(cfg, jax.random.key(0), max_seq=16))
    tok_a = jax.ShapeDtypeStruct((b,), jnp.int32)

    def pool_aval(coded):
        return jax.eval_shape(lambda: kb.pool_init(
            kvcfg, cfg.n_layers, b, cfg.n_kv, cfg.head_dim,
            jnp.dtype(cfg.compute_dtype), coded=coded))

    tele_a = jax.eval_shape(
        lambda: init_serve_telemetry(kvcfg.n_banks))
    step = make_pooled_serve_step(cfg, kvcfg)
    variants = {
        "off": (step, {"pool": pool_aval(True), "tele": None}),
        "tele-on": (step, {"pool": pool_aval(True), "tele": tele_a}),
        "uncoded": (step, {"pool": pool_aval(False), "tele": None}),
        "no-recode": (make_pooled_serve_step(cfg, kvcfg, recode_budget=-1),
                      {"pool": pool_aval(True), "tele": None}),
    }
    out: List[Finding] = []
    hashes: Dict[str, str] = {}
    for label, (fn, cache_a) in variants.items():
        out.extend(lint_carry(
            f"pooled_serve_step[{label}]",
            lambda carry, p, _fn=fn: _fn(p, *carry),
            (tok_a, cache_a), params_a, pick=lambda o: o))
        hashes[label] = jaxpr_hash(fn, params_a, tok_a, cache_a)
    for label, why in (
            ("tele-on", "the serve metric planes no longer measure "
                        "anything"),
            ("uncoded", "the coded/uncoded pool switch no longer selects "
                        "a different compiled program"),
            ("no-recode", "recode_budget=-1 no longer disables the "
                          "ReCoding unit")):
        if hashes[label] == hashes["off"]:
            out.append(Finding(
                "jaxpr-flag-leak", f"pooled_serve_step[{label}]",
                f"traces the same jaxpr as the baseline step — {why}"))
    return out


# ------------------------------------------------------------- layer entry
def default_lint_points() -> List:
    """The representative grid the CLI lints: an α×r×scheme×tunable spread
    exercising every signature-class mechanism (masked r axis, sub/full
    coverage split, telemetry and fault programs)."""
    from repro.sweep.grid import SweepPoint, grid

    base = SweepPoint(n_rows=32, length=8)
    pts = grid(base, scheme=("scheme_i", "uncoded"),
               alpha=(0.25, 0.5), r=(0.125, 0.25),
               seed=(0, 1), select_period=(64, 128))
    pts += grid(base, alpha=(1.0,), r=(0.25,), seed=(0, 1))   # full coverage
    pts += [base.replace(telemetry=True),
            base.replace(faults=(("bank", 0, 2, 5),)),
            base.replace(faults=(("stutter", 1, 3),))]
    return pts


def run(strict: bool = False,
        points: Optional[Sequence] = None) -> List[Finding]:
    del strict
    pts = list(points) if points is not None else default_lint_points()
    out = lint_signature_classes(pts)
    out += lint_carry_stability()
    out += lint_flag_identity()
    out += lint_row_wide()
    out += lint_serve_step()
    return out
