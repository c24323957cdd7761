"""Self-tests for the ``repro.analysis`` static-verification layers.

Two halves, per the admission discipline the analyzers enforce on the rest
of the repo: (1) every rule must FLAG its checked-in known-bad fixture in
``tests/data/analysis/`` — a rule that cannot fail is not a check; and
(2) the real ``src/`` tree must pass every layer clean (the jaxpr layer's
full sweep is ``-m slow``; a small signature-class probe runs in the fast
tier)."""
import importlib.util
import json
import os
from functools import partial

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import guard, jaxpr, rules, schemes

DATA = os.path.join(os.path.dirname(__file__), "data", "analysis")


def _by_rule(findings):
    out = {}
    for f in findings:
        out.setdefault(f.rule, []).append(f)
    return out


def _load_fixture_module(name):
    spec = importlib.util.spec_from_file_location(
        f"_analysis_fixture_{name}", os.path.join(DATA, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------------------ oracle purity
def test_oracle_purity_flags_impure_fixture():
    fs = rules.check_oracle_purity(root=os.path.join(DATA, "bad_oracle"))
    assert {f.rule for f in fs} == {"oracle-purity"}
    flagged = {f.message.split("'")[1] for f in fs}
    assert flagged == {"jax.numpy", "repro.core.codes", "repro.obs"}


def test_oracle_purity_clean_on_src():
    assert rules.check_oracle_purity() == []


# -------------------------------------------------------- traced-code rules
def test_traced_rules_flag_fixture():
    path = os.path.join(DATA, "bad_traced.py")
    fs = rules.check_traced_rules(
        paths=[path],
        traced={"branch_on_traced", "static_geometry_index",
                "narrow_counters", "clean_traced"},
        host=set())
    by = _by_rule(fs)
    assert set(by) == {"tracer-branch", "static-geometry", "narrow-counter",
                       "rule-classification"}
    # branch_on_traced: python If + int() cast + IfExp, each on a tracer
    tb = by["tracer-branch"]
    assert len(tb) == 3 and all("branch_on_traced" in f.message for f in tb)
    # static_geometry_index: // and % directly, plus // through the alias
    sg = by["static-geometry"]
    assert len(sg) == 3
    assert all("static_geometry_index" in f.message for f in sg)
    # narrow_counters: binop, augassign, and the kwarg site (the kwarg's
    # inner + may be flagged twice; count distinct lines)
    nc = by["narrow-counter"]
    assert all("narrow_counters" in f.message for f in nc)
    assert len({f.line for f in nc}) == 3
    # unclassified_helper is neither TRACED nor HOST
    rc = by["rule-classification"]
    assert len(rc) == 1 and "unclassified_helper" in rc[0].message
    # clean_traced: static tests, `is None`, shape attrs, the waiver
    # comment, and the IfExp geometry bind must all stay silent
    assert not any("clean_traced" in f.message for f in fs)


def test_traced_rules_clean_on_src():
    assert rules.check_traced_rules() == []


def test_bench_manifest_rule_clean():
    assert rules.check_bench_manifests() == []


# ------------------------------------------------------- kernel interpret
def test_kernel_interpret_flags_fixture():
    fs = rules.check_kernel_interpret(
        roots=[os.path.join(DATA, "bad_interpret.py")])
    assert {f.rule for f in fs} == {"kernel-interpret"}
    # only the unwaived pin is flagged: the waived call and the
    # False/None/default sites all stay silent
    assert len(fs) == 1 and fs[0].line == 15


def test_kernel_interpret_clean_on_src():
    """src/ and benchmarks/ must never pin interpret=True (tests are out of
    scope; they may pin it freely)."""
    assert rules.check_kernel_interpret() == []


# ------------------------------------------------------- scheme certificates
def _bad_scheme():
    with open(os.path.join(DATA, "bad_scheme.json")) as fh:
        return json.load(fh)


def test_scheme_admission_gate_flags_under_tolerant_fixture():
    spec = _bad_scheme()
    entry = schemes.analyze_scheme(
        spec["name"], members=[tuple(m) for m in spec["members"]],
        phys=spec["phys"], n_data=spec["n_data"])
    fs = schemes.verify_scheme_claims(spec["name"], entry,
                                      declared=spec["declared"])
    assert {f.rule for f in fs} == {"scheme-under-tolerant"}
    # the finding names a concrete unservable loss set (bank 2 or 3)
    assert "(2,)" in fs[0].message or "(3,)" in fs[0].message


def test_scheme_without_declared_claims_is_rejected():
    entry = schemes.analyze_scheme("scheme_i")
    fs = schemes.verify_scheme_claims("not_a_declared_scheme", entry)
    assert [f.rule for f in fs] == ["scheme-undeclared"]


def test_serving_rule_soundness_check_fires():
    """Tampering the serving tolerance beyond GF(2) rank must be caught —
    the analyzer cross-checks its own serving rule against linear algebra."""
    entry = schemes.analyze_scheme("scheme_i")
    entry["serving_tolerance"]["1"] = (
        entry["serving_tolerance"]["1"] + [[0]])
    fs = schemes.verify_scheme_claims("scheme_i", entry)
    assert "scheme-serving-unsound" in {f.rule for f in fs}


def test_scheme_layer_clean_on_src():
    assert schemes.run() == []


def test_kv_pool_is_certified_subcode():
    """The serving pool's pairwise layout is certified like any scheme —
    present in certificates.json, claims proved, and every parity group is
    verbatim a scheme_i parity (the subcode cross-check)."""
    saved = schemes.load_certificates()
    assert "kv_pool" in saved["schemes"]
    entry = schemes.analyze_scheme("kv_pool", *schemes.pool_tables())
    assert entry == saved["schemes"]["kv_pool"]
    assert schemes.verify_scheme_claims("kv_pool", entry) == []
    assert entry["full_tolerance_k"] == 1
    assert entry["read_degree_min"] == 2
    assert schemes.check_pool_subcode() == []


def test_pool_subcode_check_fires_on_wrong_parent():
    """A parent without the pool's pairs must be rejected (the check is
    load-bearing, not vacuous)."""
    fs = schemes.check_pool_subcode(parent="uncoded")
    assert fs and all(f.rule == "pool-subcode" for f in fs)


# ----------------------------------------------------------- jaxpr analysis
def test_jaxpr_lint_flags_baked_python_value():
    mod = _load_fixture_module("bad_jaxpr")
    aval = jax.ShapeDtypeStruct((8,), jnp.float32)
    fs = jaxpr.lint_program_class("fixture:baked", [
        (partial(mod.baked_scale, scale=2.0), aval),
        (partial(mod.baked_scale, scale=3.0), aval),
    ])
    assert [f.rule for f in fs] == ["jaxpr-static-leak"]
    assert "baked" in fs[0].message


def test_jaxpr_lint_flags_aval_split():
    mod = _load_fixture_module("bad_jaxpr")
    fs = jaxpr.lint_program_class("fixture:aval-split", [
        (partial(mod.baked_scale, scale=2.0),
         jax.ShapeDtypeStruct((8,), jnp.float32)),
        (partial(mod.baked_scale, scale=2.0),
         jax.ShapeDtypeStruct((16,), jnp.float32)),
    ])
    assert [f.rule for f in fs] == ["jaxpr-static-leak"]
    assert "shapes/dtypes" in fs[0].message


def test_jaxpr_lint_clean_class_passes():
    mod = _load_fixture_module("bad_jaxpr")
    aval = jax.ShapeDtypeStruct((8,), jnp.float32)
    fn = partial(mod.baked_scale, scale=2.0)
    assert jaxpr.lint_program_class("fixture:ok", [(fn, aval), (fn, aval)]) \
        == []


def test_jaxpr_lint_flags_carry_drift():
    mod = _load_fixture_module("bad_jaxpr")
    carry = jax.ShapeDtypeStruct((), jnp.int32)
    x = jax.ShapeDtypeStruct((4,), jnp.float32)
    fs = jaxpr.lint_carry("fixture:drift", mod.drifting_carry, carry, x)
    assert [f.rule for f in fs] == ["jaxpr-carry-drift"]
    assert "float32" in fs[0].message
    assert jaxpr.lint_carry("fixture:stable", mod.stable_carry, carry, x) \
        == []


def test_signature_class_clean_on_small_grid():
    """Fast-tier probe of the real engine: two points of one signature
    class must share one program (full sweep: ``-m slow`` below)."""
    from repro.sweep.grid import SweepPoint

    pts = [SweepPoint(n_rows=32, length=8, alpha=a, r=0.25, seed=s)
           for a, s in ((0.5, 0), (0.7, 1))]
    assert jaxpr.lint_signature_classes(pts) == []


def test_pooled_serve_step_lint_clean():
    """The pooled decode step's observability contract holds: tele=None is
    an absent leaf with a stable carry, tele-on/uncoded/no-recode each
    trace genuinely different programs."""
    assert jaxpr.lint_serve_step() == []


def test_row_wide_rule_flags_fixtures():
    """Batched, a whole-bank select and a loop whose trip count differs by
    point are row-wide; an in-place update, and a loop all points step
    together, are not."""
    mod = _load_fixture_module("bad_jaxpr")
    bank = jax.ShapeDtypeStruct((2, 512), jnp.int32)
    rows = jax.ShapeDtypeStruct((2, 4), jnp.int32)
    pred = jax.ShapeDtypeStruct((2,), bool)
    n = jax.ShapeDtypeStruct((2,), jnp.int32)

    def found(fn, *args, in_axes=0):
        jpr = jax.make_jaxpr(jax.vmap(fn, in_axes=in_axes))(*args)
        return [(f, p) for f, p, _ in jaxpr.row_wide_equations(jpr, (512,))]

    assert ("whole_bank_select", "select_n") in found(
        mod.whole_bank_select, bank, rows, rows, pred)
    assert found(mod.in_place_update, bank, rows, rows) == []
    assert found(mod.loop_carrying_the_bank, bank, n) == [
        ("loop_carrying_the_bank", "while")]
    assert found(mod.loop_carrying_the_bank, bank, 3,
                 in_axes=(0, None)) == []
    # a bank kept in rows of lanes: (points, banks, rows / 128, 128)
    laned = jax.ShapeDtypeStruct((2, 8, 4, 128), jnp.int32)
    val = jax.ShapeDtypeStruct((2,), jnp.int32)
    jpr = jax.make_jaxpr(jax.vmap(mod.whole_bank_select))(laned, rows, val,
                                                            pred)
    assert ("whole_bank_select", "select_n") in [
        (f, p) for f, p, _ in jaxpr.row_wide_equations(jpr, ((4, 128),))]


def test_row_wide_rule_flags_one_hot_lookups_of_a_bank(monkeypatch):
    """The builders' one-hot lookups over a whole bank (all of them, as
    before the gather path existed: the state keeps every bank table
    (banks, rows) wide) are what the rule flags; the program as it is
    passes."""
    from repro.core import state
    from repro.sweep import engine

    assert jaxpr.lint_row_wide() == []
    monkeypatch.setattr(state, "ONEHOT_MAX_COLS", 1 << 30)
    engine.clear_caches()
    try:
        flagged = {f.location for f in jaxpr.lint_row_wide()}
    finally:
        engine.clear_caches()
    assert {"cycle_fn[_onehot]", "cycle_fn[_count]"} <= flagged
    assert all(f.startswith("cycle_fn[") for f in flagged)


def test_write_datapath_scatters_follow_the_ports():
    """In the batched ``cycle_fn`` at 65,536 rows (every bank table kept in
    rows of lanes), each scatter of the write datapath into a table a bank
    or a parity bank wide is given at most its table's slot count of cells
    a point, from the ports (``write_slots``), not one for every candidate
    of the queues (80)."""
    from repro.core.controller import write_slots
    from repro.sweep.grid import SweepPoint

    jpr, widths, sys = jaxpr.batched_cycle_jaxpr(
        SweepPoint(n_rows=65536, length=8, alpha=0.25, r=0.05))
    p, slots = sys.p, write_slots(sys.p)
    m = sys.init().mem
    found = sorted((shape[1:], dtype, n) for shape, dtype, n in
                   jaxpr.wide_scatters(jpr, widths, scope="cycle.patterns"))
    bank, par = m.banks_data.shape, m.parity_data.shape
    assert found == sorted([
        (bank, "int32", slots.banks_data), (bank, "int32", slots.golden),
        (bank, "int32", slots.fresh_loc),
        (par, "int32", slots.parity_data), (par, "bool", slots.parity_valid),
    ])
    assert (slots.banks_data, slots.parity_data, slots.golden) == (
        p.n_data, p.n_ports - p.n_data, p.n_ports)
    assert max(n for _, _, n in found) < p.n_data * p.queue_depth


@pytest.mark.slow
def test_jaxpr_layer_clean_on_src():
    assert jaxpr.run() == []


# ----------------------------------------------------------- recompile guard
def test_recompile_guard_counts_and_fails():
    f = jax.jit(lambda x: x * 2)
    with guard.recompile_guard(f, max_compiles=1) as g:
        f(jnp.ones(4))
        f(jnp.ones(4))                      # cache hit
    assert g.compiles() == 1
    with pytest.raises(guard.RecompileError):
        with guard.recompile_guard(f, max_compiles=0):
            f(jnp.ones(8))                  # new shape -> new program
    with guard.recompile_guard(f, max_compiles=None) as g:
        f(jnp.ones(16))                     # record-only mode never raises
    assert g.compiles() == 1


def test_recompile_guard_unknown_target():
    with pytest.raises(KeyError):
        guard.resolve("no_such_entry_point")
