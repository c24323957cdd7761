"""Differential conformance: the NumPy golden model is the ground truth.

The production scheduler (``repro.core``, vectorized jax) is checked against
``repro.oracle`` — an independent, sequential, pure-NumPy re-derivation of
the paper's cycle semantics that shares no code (not even the scheme
tables) with the system under test. Four layers, each asserting **bit
equality**, not statistical closeness:

1. *tables* — the independently derived code schemes agree;
2. *plans* — randomized controller states produce identical read/write
   plans and recode outcomes (hypothesis-driven when installed, seeded
   NumPy fallback otherwise);
3. *workloads* — full simulations agree on every state leaf, every
   statistic, and the per-cycle read datapath;
4. *streams & masked geometry* — the chunked replay driver and the sweep
   engine's padded α×r batching agree with the oracle run at each point's
   exact geometry (at least one masked grid point per scheme).

See docs/testing.md for the contract and how to evolve the scheduler
without reintroducing a second jax implementation.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import assert_state_matches_oracle, oracle_twin, rand_trace

from repro.core import controller as ctl
from repro.core.codes import get_tables
from repro.core.recoding import recode_step as jax_recode_step
from repro.core.state import ONEHOT_MAX_COLS, make_params, make_tunables
from repro.core.system import CodedMemorySystem, drain_bound
from repro import oracle
from repro.oracle import (OracleMemorySystem, OracleParams, build_read_plan,
                          build_write_plan, oracle_scheme)
from repro.oracle import recode_step as oracle_recode_step

SCHEMES = ["scheme_i", "scheme_ii", "scheme_iii", "replication_2", "uncoded"]

_read_jax = jax.jit(ctl.build_read_pattern, static_argnums=0)
_write_jax = jax.jit(ctl.build_write_pattern, static_argnums=0)
_recode_jax = jax.jit(jax_recode_step, static_argnums=0)


# ------------------------------------------------------------------- tables
@pytest.mark.parametrize("scheme", SCHEMES + ["replication_4"])
def test_oracle_tables_match_core(scheme):
    """The oracle's independently derived scheme tables agree with the
    production ones — members, physical packing, port ids and per-bank
    serving options. (Divergence here would invalidate every other layer.)"""
    from repro.analysis import schemes as anl

    t = get_tables(scheme)
    o = oracle_scheme(scheme, t.n_data)
    assert o.n_data == t.n_data
    assert o.n_parities == len(t.scheme.members)
    assert o.n_ports == t.n_ports
    # hash both derivations against the checked-in certificate; on
    # divergence, name the scheme and the first differing parity instead
    # of failing with a bare tuple assert
    cert_hash = anl.load_certificates()["schemes"][scheme]["table_sha256"]
    core_hash = anl.table_hash(t.scheme.members, t.scheme.phys)
    oracle_hash = anl.table_hash(o.members, o.phys)
    if not (core_hash == oracle_hash == cert_hash):
        diff = anl.diff_tables(scheme, t.scheme.members, t.scheme.phys,
                               o.members, o.phys)
        raise AssertionError(
            f"{scheme}: table derivations diverge (core={core_hash[:12]} "
            f"oracle={oracle_hash[:12]} certificate={cert_hash[:12]}):\n"
            + "\n".join(diff or ["(tables equal — certificate is stale: run "
                                 "python -m repro.analysis "
                                 "--write-certificates)"]))
    assert tuple(o.members) == tuple(t.scheme.members)
    assert tuple(o.phys) == tuple(t.scheme.phys)
    for j in range(o.n_parities):
        assert o.par_port(j) == int(t.par_port[j])
    for b in range(o.n_data):
        opts = o.options(b)
        assert len(opts) == int(t.opt_n[b])
        for k, (j, sibs) in enumerate(opts):
            assert j == int(t.opt_parity[b, k])
            want = tuple(int(s) for s in t.opt_sibs[b, k] if s >= 0)
            assert sibs == want


def test_mode_numbering_contract():
    """Plan `mode` values are compared elementwise across implementations,
    so the action numbering is a shared contract, re-derived on both
    sides."""
    assert (oracle.MODE_FROM_SYM, oracle.MODE_DIRECT, oracle.MODE_OPT0,
            oracle.MODE_REDIRECT, oracle.MODE_UNSERVED) == (
        ctl.MODE_FROM_SYM, ctl.MODE_DIRECT, ctl.MODE_OPT0, ctl.MODE_REDIRECT,
        ctl.MODE_UNSERVED)
    assert (oracle.WMODE_DIRECT, oracle.WMODE_PARK0, oracle.WMODE_UNSERVED
            ) == (ctl.WMODE_DIRECT, ctl.WMODE_PARK0, ctl.WMODE_UNSERVED)


# ---------------------------------------------------------- randomized plans
@functools.lru_cache(maxsize=None)
def _geom(scheme, n_rows=16, alpha=1.0, r=0.25, rc_cap=8):
    t = get_tables(scheme)
    p = make_params(t, n_rows=n_rows, alpha=alpha, r=r, recode_cap=rc_cap)
    op = OracleParams.derive(n_rows, alpha, r, n_data=t.n_data,
                             recode_cap=rc_cap)
    om = OracleMemorySystem(scheme, op, n_cores=4)
    return t, p, ctl.jtables(t), om


def _rand_mem(rng, t, p, n_rows):
    """Random reachable controller state: freshness points only at real
    logical parities (a bank with no parities can never be parked), the
    recode ring fill includes FULL, the region map is a random partial
    injection."""
    nb = p.n_data
    n_logical = len(t.scheme.members)
    fresh = np.asarray(
        rng.integers(0, n_logical + 1, (nb, n_rows))
        * (rng.random((nb, n_rows)) < 0.25), np.int32)
    pv = rng.random((p.n_parities, p.n_slots * p.region_size)) < 0.7
    rslot = np.full(p.n_regions, -1, np.int32)
    slots = rng.permutation(p.n_slots)
    regs = rng.permutation(p.n_regions)
    k = rng.integers(0, min(p.n_slots, p.n_regions) + 1)
    rslot[regs[:k]] = slots[:k]
    cap = p.recode_cap
    fill = int(rng.integers(0, cap + 1))
    rcv = np.zeros(cap, bool)
    rcv[rng.permutation(cap)[:fill]] = True
    rcb = np.where(rcv, rng.integers(0, nb, cap), -1).astype(np.int32)
    rcr = np.where(rcv, rng.integers(0, n_rows, cap), -1).astype(np.int32)
    parked = rng.integers(0, 3, p.n_regions).astype(np.int32)
    return fresh, pv, rslot, parked, rcb, rcr, rcv


def _rand_cands(rng, p, n_rows, n=24):
    cb = rng.integers(0, p.n_data, n).astype(np.int32)
    ci = rng.integers(0, n_rows, n).astype(np.int32)
    ca = rng.integers(0, 50, n).astype(np.int32)   # age ties likely
    cv = rng.random(n) < 0.8
    pb = np.append(rng.random(p.n_ports) < 0.3, False)
    return cb, ci, ca, cv, pb


def _assert_plans_equal(got, want, label):
    """jax plan pytree vs oracle plan namedtuple, matched by field name."""
    for name in want._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(got, name)), np.asarray(getattr(want, name)),
            err_msg=f"{label}: field {name!r}")


def check_plan_conformance(scheme, seed):
    n_rows = 16
    t, p, jt, om = _geom(scheme)
    rng = np.random.default_rng(seed)
    fresh, pv, rslot, parked, rcb, rcr, rcv = _rand_mem(rng, t, p, n_rows)
    cb, ci, ca, cv, pb = _rand_cands(rng, p, n_rows)
    got = _read_jax(p, jt, *map(jnp.asarray,
                                (cb, ci, ca, cv, pb, fresh, pv, rslot)))
    want = build_read_plan(om, cb, ci, ca, cv, pb, fresh, pv, rslot)
    _assert_plans_equal(got, want, f"ReadPlan {scheme} seed={seed}")
    got = _write_jax(p, jt, *map(jnp.asarray,
                                 (cb, ci, ca, cv, pb, fresh, pv, rslot,
                                  parked, rcb, rcr, rcv)))
    want = build_write_plan(om, cb, ci, ca, cv, pb, fresh, pv, rslot,
                            parked, rcb, rcr, rcv)
    _assert_plans_equal(got, want, f"WritePlan {scheme} seed={seed}")


def check_recode_conformance(scheme, seed):
    n_rows = 16
    t, p, jt, om = _geom(scheme)
    rng = np.random.default_rng(seed)
    fresh, pv, rslot, parked, rcb, rcr, rcv = _rand_mem(rng, t, p, n_rows)
    pb = np.append(rng.random(p.n_ports) < 0.3, False)
    banks = rng.integers(0, 1 << 20, (p.n_data, n_rows)).astype(np.int32)
    pdata = rng.integers(0, 1 << 20, pv.shape).astype(np.int32)
    got = _recode_jax(p, jt, *map(jnp.asarray,
                                  (pb, fresh, pv, parked, rcb, rcr, rcv,
                                   rslot, banks, pdata)))
    want = oracle_recode_step(om, pb, fresh, pv, parked, rcb, rcr, rcv,
                              rslot, banks, pdata)
    _assert_plans_equal(got, want, f"RecodeOut {scheme} seed={seed}")


@pytest.mark.parametrize("scheme", SCHEMES)
def test_plan_conformance_random_states(scheme):
    """Read/write plans are bit-identical to the golden model across random
    queue/port/freshness/parity/ring states (incl. full recode rings)."""
    for seed in range(6):
        check_plan_conformance(scheme, seed)


def _batch_states(scheme, seed, n_points=8, n=24):
    """``n_points`` random states, each with another count of valid
    candidates (none to all), duplicate (bank, row) candidates, candidates
    sharing a row across banks, and a full recode ring at every third
    point."""
    n_rows = 16
    t, p, jt, om = _geom(scheme)
    rng = np.random.default_rng(seed)
    pts = []
    for j, n_valid in enumerate(np.linspace(0, n, n_points).astype(int)):
        fresh, pv, rslot, parked, rcb, rcr, rcv = _rand_mem(rng, t, p,
                                                            n_rows)
        if j % 3 == 1:
            rcv = np.ones_like(rcv)
            rcb = rng.integers(0, p.n_data, rcv.size).astype(np.int32)
            rcr = rng.integers(0, n_rows, rcv.size).astype(np.int32)
        cb, ci, ca, _, pb = _rand_cands(rng, p, n_rows, n)
        cb[4:8], ci[4:8] = cb[0:4], ci[0:4]           # duplicate cells
        ci[8:12] = ci[0]                               # one row, many banks
        cv = np.zeros(n, bool)
        cv[rng.permutation(n)[:n_valid]] = True
        down = rng.random(p.n_data) < 0.25
        pts.append((cb, ci, ca, cv, pb, fresh, pv, rslot, parked, rcb, rcr,
                    rcv, down))
    return p, jt, om, pts


@pytest.mark.parametrize("degraded", [False, True], ids=["all-up", "down"])
@pytest.mark.parametrize("scheme", SCHEMES)
def test_batched_plan_conformance(scheme, degraded):
    """The builders vmapped over a batch of states, as the sweep engine
    runs them: the walk's trip counter is batched, and the walk runs as
    many trips as the fullest point needs. Every point's plan equals the
    golden model's, field by field; ``degraded`` gives each point its own
    down banks (the write builder's ``down=``)."""
    p, jt, om, pts = _batch_states(scheme, seed=11 + degraded)
    cols = [jnp.asarray(np.stack(a)) for a in zip(*pts)]
    read = jax.jit(jax.vmap(functools.partial(ctl.build_read_pattern, p, jt)))
    got_r = read(*cols[:8])

    def write(*args):
        return ctl.build_write_pattern(p, jt, *args[:12],
                                       down=args[12] if degraded else None)

    got_w = jax.jit(jax.vmap(write))(*cols)
    for j, (cb, ci, ca, cv, pb, fresh, pv, rslot, parked, rcb, rcr, rcv,
            down) in enumerate(pts):
        label = f"{scheme} point {j} ({int(cv.sum())} valid)"
        _assert_plans_equal(
            jax.tree.map(lambda x: x[j], got_r),
            build_read_plan(om, cb, ci, ca, cv, pb, fresh, pv, rslot),
            f"ReadPlan {label}")
        _assert_plans_equal(
            jax.tree.map(lambda x: x[j], got_w),
            build_write_plan(om, cb, ci, ca, cv, pb, fresh, pv, rslot,
                             parked, rcb, rcr, rcv,
                             down=down if degraded else None),
            f"WritePlan {label}")


@pytest.mark.parametrize("scheme", ["scheme_i", "scheme_iii"])
def test_recode_conformance_random_states(scheme):
    for seed in range(6):
        check_recode_conformance(scheme, 1000 + seed)


# ------------------------------------------------------------ full workloads
def _system(scheme, n_rows=32, alpha=0.25, r=0.125, n_cores=4,
            select_period=16, **kw):
    t = get_tables(scheme)
    p = make_params(t, n_rows=n_rows, alpha=alpha, r=r, recode_cap=8, **kw)
    tn = make_tunables(queue_depth=p.queue_depth, select_period=select_period)
    return CodedMemorySystem(t, p, n_cores=n_cores, tunables=tn)


def check_workload_conformance(scheme, alpha, r, seed, write_frac=0.45):
    sys_ = _system(scheme, alpha=alpha, r=r)
    om = oracle_twin(sys_)
    rng = np.random.default_rng(seed)
    trace = rand_trace(rng, 4, 20, sys_.p.n_data, 32, write_frac=write_frac)
    n_cycles = 96
    st, _ = sys_._run(sys_.init(), trace, n_cycles)
    ost = om.run(trace, n_cycles)
    assert_state_matches_oracle(
        st, ost, f"{scheme} α={alpha} r={r} seed={seed}")
    from repro.traces.stream import strip_windows
    assert strip_windows(sys_.summarize(st)) == om.result(ost)


@pytest.mark.parametrize("scheme,alpha,r", [
    ("scheme_i", 1.0, 0.25),
    ("scheme_i", 0.25, 0.125),      # dynamic coding engaged
    ("uncoded", 1.0, 0.25),
    ("replication_2", 0.25, 0.125),
    pytest.param("scheme_ii", 0.5, 0.125, marks=pytest.mark.slow),
    pytest.param("scheme_iii", 1.0, 0.25, marks=pytest.mark.slow),
])
def test_full_workload_conformance(scheme, alpha, r):
    """End-to-end: every state leaf and every statistic of a full simulation
    equals the golden model's, write-heavy mixes included."""
    check_workload_conformance(scheme, alpha, r, seed=7)
    check_workload_conformance(scheme, alpha, r, seed=8, write_frac=0.7)


# ------------------------------------------------- banks of 8,192 rows
# Above ``state.ONEHOT_MAX_COLS`` columns the state keeps a bank's tables
# in rows of 128 lanes, the builders, the ReCoding unit and the commit look
# their cells up by gather and write them by scatter, and the dynamic unit
# encodes one region's rows: the same contract holds there. r = 0.05 makes regions of 410 rows, so a region
# encode spans 6 cycles.
WIDE_ROWS = 8192
TRACE_KINDS = ("banded", "split", "ramp", "uniform", "zipf")


@pytest.mark.parametrize("scheme,alpha", [("scheme_i", 0.25),
                                          ("uncoded", 0.25)])
def test_wide_bank_state_conformance(scheme, alpha):
    """Every state leaf of a full simulation over 8,192-row banks equals
    the golden model's. The traffic spreads over 8 regions, more than the
    5 parity slots, so regions are selected, encoded over 6 cycles and
    evicted, and rows park and recode."""
    sys_ = _system(scheme, n_rows=WIDE_ROWS, alpha=alpha, r=0.05)
    assert sys_.p.n_rows > ONEHOT_MAX_COLS
    assert sys_.p.n_slots * sys_.p.region_size > ONEHOT_MAX_COLS
    m0 = sys_.init().mem
    assert {x.ndim for x in (m0.fresh_loc, m0.parity_valid, m0.banks_data,
                             m0.parity_data, m0.golden)} == {3}
    om = oracle_twin(sys_)
    trace = rand_trace(np.random.default_rng(11), 4, 160, sys_.p.n_data,
                       8 * sys_.p.region_size, write_frac=0.6)
    st, _ = sys_._run(sys_.init(), trace, 560)
    ost = om.run(trace, 560)
    assert_state_matches_oracle(st, ost, f"{scheme} {WIDE_ROWS} rows")
    res = sys_.summarize(st)
    assert res.completed
    if scheme != "uncoded":
        assert res.switches > sys_.p.n_slots and res.parked_writes > 0


def test_mixed_bank_table_forms_state_conformance():
    """At 4,096 rows the data banks are kept in rows of lanes while the
    parity tables (5 slots of 205 rows) stay (banks, rows): the two forms
    in one program give every state leaf of the golden model."""
    sys_ = _system("scheme_i", n_rows=4096, alpha=0.25, r=0.05)
    m0 = sys_.init().mem
    assert (m0.banks_data.ndim, m0.parity_data.ndim) == (3, 2)
    om = oracle_twin(sys_)
    trace = rand_trace(np.random.default_rng(12), 4, 120, sys_.p.n_data,
                       8 * sys_.p.region_size, write_frac=0.6)
    st, _ = sys_._run(sys_.init(), trace, 420)
    assert_state_matches_oracle(st, om.run(trace, 420), "4096 rows")
    assert sys_.summarize(st).switches > sys_.p.n_slots


# ------------------------------------------------ write slots
# The write datapath compacts the cells a cycle writes into each table
# into ``ctl.write_slots(p)`` slots before it scatters them; a cell past
# the last slot would be lost. The golden model's own plans count, cycle
# by cycle, how many distinct cells each table is written at.
def _count_written_cells(om, peaks):
    """Wrap ``om``'s commit so that every write cycle raises ``peaks[table]``
    to the distinct cells written into ``table`` that cycle."""
    commit = om._commit_writes
    p = om.p

    def counting(st, plan, cb, ci, ca, cv, cd):
        cells = {name: set() for name in ctl.WriteSlots._fields}
        for c in np.flatnonzero(plan.served):
            b, i, mode = max(int(cb[c]), 0), max(int(ci[c]), 0), int(
                plan.mode[c])
            slot = int(st.region_slot[i // p.rs_active])
            pr = max(slot, 0) * p.region_size + i % p.rs_active
            cells["golden"].add((b, i))
            cells["fresh_loc"].add((b, i))
            if mode == ctl.WMODE_DIRECT:
                cells["banks_data"].add((b, i))
                if slot >= 0:
                    cells["parity_valid"].update(
                        (j, pr) for j, _ in om.options[b])
            else:
                j = om.options[b][mode - ctl.WMODE_PARK0][0]
                cells["parity_data"].add((j, pr))
                cells["parity_valid"].add((j, pr))
        # freshness changes at no cell but the served ones
        moved = set(zip(*np.nonzero(plan.fresh_loc != st.fresh_loc)))
        assert moved <= cells["fresh_loc"]
        for name, written in cells.items():
            peaks[name] = max(peaks.get(name, 0), len(written))
        commit(st, plan, cb, ci, ca, cv, cd)

    om._commit_writes = counting


def _hot_write_storm(rng, n_cores, length, n_data, hot_rows):
    """Write-heavy requests of every core onto a few hot rows of every
    bank, so that several cores write one cell and one cycle serves
    several writes to it."""
    from repro.core.system import Trace
    shape = (n_cores, length)
    return Trace(
        bank=jnp.asarray(rng.integers(0, n_data, shape), jnp.int32),
        row=jnp.asarray(rng.choice(hot_rows, shape), jnp.int32),
        is_write=jnp.asarray(rng.random(shape) < 0.85),
        data=jnp.asarray(rng.integers(1, 1 << 20, shape), jnp.int32),
        valid=jnp.ones(shape, bool))


@pytest.mark.parametrize("scheme,n_rows,dead_bank", [
    ("scheme_i", 512, False),
    ("scheme_i", 512, True),
    ("scheme_i", WIDE_ROWS, False),
    ("scheme_ii", WIDE_ROWS, False),
    ("scheme_iii", WIDE_ROWS, False),
    ("uncoded", WIDE_ROWS, False),
    ("scheme_i", WIDE_ROWS, True),
])
def test_write_slots_hold_every_written_cell(scheme, n_rows, dead_bank):
    """Under write storms onto hot cells, no cycle writes more distinct
    cells into a table than its slot count, and every state leaf equals
    the golden model's. At 8,192 rows the tables are kept in rows of
    lanes, so the datapath writes from the slots; at 512 they are indexed
    by one-hot and written from every candidate. ``dead_bank``: bank 2
    fails at cycle 12 and never recovers, so its writes park."""
    from repro.faults import FaultPlan

    t = get_tables(scheme)
    p = make_params(t, n_rows=n_rows, alpha=1.0, r=0.25, faults=dead_bank)
    assert p.coalesce == (scheme != "uncoded")
    sys_ = CodedMemorySystem(t, p, n_cores=8, tunables=make_tunables(
        queue_depth=p.queue_depth, select_period=16))
    plan = (FaultPlan(p.n_data, p.n_ports, bank_faults=((2, 12, -1),))
            if dead_bank else None)
    om = oracle_twin(sys_)
    peaks = {}
    _count_written_cells(om, peaks)
    rng = np.random.default_rng(21)
    trace = _hot_write_storm(rng, 8, 48, p.n_data,
                             rng.choice(n_rows, 6, replace=False))
    n_cycles = 120
    st, _ = sys_._run(sys_.init(fault_plan=plan), trace, n_cycles)
    ost = om.run(trace, n_cycles, st=om.init_state(fault_plan=plan))
    assert_state_matches_oracle(st, ost, f"{scheme} {n_rows} rows")
    slots = ctl.write_slots(p)
    for name in ctl.WriteSlots._fields:
        assert peaks[name] <= getattr(slots, name), (name, peaks, slots)
    # the storm fills the data ports, and the parity ports where there are
    assert peaks["banks_data"] == slots.banks_data
    if scheme != "uncoded":
        assert peaks["parity_data"] == slots.parity_data
        assert peaks["golden"] > p.n_data
    if dead_bank:
        assert int(jax.device_get(st.mem.fault.dead_cycles)[2]) > 0


@pytest.mark.parametrize("scheme", ["scheme_i", "uncoded"])
def test_wide_bank_sweep_matches_oracle(scheme):
    """The sweep engine's batched program over 8,192-row banks, on the five
    trace kinds at alpha 0.25, gives every result field of the golden
    model."""
    from repro.sweep import SweepPoint, run_points
    from repro.sweep.workloads import build_trace
    from repro.traces.stream import strip_windows

    base = SweepPoint(scheme=scheme, alpha=0.25, r=0.05, n_rows=WIDE_ROWS,
                      length=192, select_period=64)
    pts = [base.replace(trace=k, seed=5) for k in TRACE_KINDS]
    got = run_points(pts)
    for pt, res in zip(pts, got):
        op = OracleParams.derive(pt.n_rows, pt.alpha, pt.r,
                                 n_data=pt.n_data, recode_cap=pt.recode_cap,
                                 select_period=pt.select_period,
                                 wq_hi=pt.wq_hi, wq_lo=pt.wq_lo,
                                 queue_depth=pt.queue_depth)
        om = OracleMemorySystem(scheme, op, n_cores=pt.n_cores)
        ost = om.run(build_trace(pt), pt.resolved_cycles(),
                     stop_when_quiescent=True)
        assert strip_windows(res) == om.result(ost), pt.trace
    if scheme != "uncoded":
        assert all(r.switches > 0 for r in got)


def test_per_cycle_datapath_conformance():
    """Cycle-by-cycle CycleOut equality: which reads are served, from where,
    and the exact values the XOR-decode datapath returns — not just final
    state. Catches compensating errors that cancel by drain time."""
    sys_ = _system("scheme_i", alpha=0.25, r=0.125)
    om = oracle_twin(sys_)
    rng = np.random.default_rng(3)
    trace = rand_trace(rng, 4, 16, sys_.p.n_data, 32)
    st = sys_.init()
    ost = om.init_state()
    tr_np = tuple(np.asarray(x) for x in trace)
    for cyc in range(64):
        st, out = sys_.cycle_fn(st, trace)
        oout = om.cycle(ost, tr_np)
        for name in ("r_served", "r_bank", "r_row", "r_value", "n_served"):
            np.testing.assert_array_equal(
                np.asarray(getattr(out, name)), getattr(oout, name),
                err_msg=f"cycle {cyc}: {name}")
    assert_state_matches_oracle(st, ost, "per-cycle run")


# ------------------------------------------------------------ chunked streams
def _split_trace(trace, cuts):
    """Cut a trace into time-axis chunks at the given offsets."""
    from repro.core.system import Trace
    arrs = [np.asarray(x) for x in trace]
    T = arrs[0].shape[1]
    prev = 0
    out = []
    for c in list(cuts) + [T]:
        if c > prev:
            out.append(Trace(*(jnp.asarray(a[:, prev:c]) for a in arrs)))
            prev = c
    return out


def check_stream_conformance(seed, chunk_len, cuts):
    from repro.traces import stream_replay, strip_windows
    sys_ = _system("scheme_i", alpha=0.25, r=0.125, n_cores=3)
    om = oracle_twin(sys_)
    rng = np.random.default_rng(seed)
    tlen = 10
    trace = rand_trace(rng, 3, tlen, sys_.p.n_data, 32)
    got = stream_replay(sys_, _split_trace(trace, sorted(cuts)),
                        chunk_len=chunk_len)
    ost = om.run(trace, drain_bound(3, tlen), stop_when_quiescent=True)
    assert strip_windows(got) == om.result(ost), (seed, chunk_len, cuts)


@pytest.mark.parametrize("chunk_len,cuts", [
    (1, ()), (3, (2, 5)), (10, (1, 2, 3, 4, 9)), (14, (5,)),
])
def test_chunked_stream_matches_oracle(chunk_len, cuts):
    """Arbitrary staging lengths × arbitrary source splits: the chunked
    replay equals the golden model on the concatenated stream — the oracle
    (which has no notion of chunks) anchors split-invariance."""
    check_stream_conformance(5, chunk_len, cuts)


# --------------------------------------------------------- masked α×r points
@pytest.mark.parametrize("scheme", SCHEMES)
def test_masked_geometry_grid_matches_oracle(scheme):
    """An α×r grid runs as ONE padded-geometry program per scheme (the
    engine's r-mask batching); every point must equal the oracle run at the
    point's own exact geometry — the masked grid point per scheme the
    conformance contract requires."""
    from repro.sweep import SweepPoint, grid, partition, run_points
    from repro.sweep.workloads import build_trace
    from repro.traces.stream import strip_windows

    t = get_tables(scheme)
    base = SweepPoint(scheme=scheme, n_rows=32, n_cores=3,
                      n_banks=t.n_data, n_data=t.n_data, length=10,
                      select_period=16, recode_cap=8)
    pts = grid(base, alpha=(0.25, 0.5), r=(0.125, 0.25))
    assert len({pt.derived_slots() for pt in pts}) > 1   # genuinely masked
    assert len(partition(pts)) == 1                      # one padded program
    got = run_points(pts)
    for pt, res in zip(pts, got):
        op = OracleParams.derive(pt.n_rows, pt.alpha, pt.r,
                                 n_data=pt.n_data, recode_cap=pt.recode_cap,
                                 select_period=pt.select_period,
                                 wq_hi=pt.wq_hi, wq_lo=pt.wq_lo,
                                 queue_depth=pt.queue_depth)
        om = OracleMemorySystem(scheme, op, n_cores=pt.n_cores)
        ost = om.run(build_trace(pt), pt.resolved_cycles(),
                     stop_when_quiescent=True)
        assert strip_windows(res) == om.result(ost), pt


# ----------------------------------------------------------------- hypothesis
try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                       # pragma: no cover
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    @settings(max_examples=25)
    @given(st.integers(0, 2**31 - 1), st.sampled_from(SCHEMES))
    def test_plan_conformance_hypothesis(seed, scheme):
        check_plan_conformance(scheme, seed)

    @settings(max_examples=10)
    @given(st.integers(0, 2**31 - 1),
           st.sampled_from(["scheme_i", "scheme_iii"]))
    def test_recode_conformance_hypothesis(seed, scheme):
        check_recode_conformance(scheme, seed)

    @settings(max_examples=6)
    @given(st.integers(0, 2**31 - 1),
           st.sampled_from([("scheme_i", 0.25, 0.125), ("scheme_i", 1.0, 0.25),
                            ("uncoded", 1.0, 0.25)]),
           st.floats(0.2, 0.8))
    def test_workload_conformance_hypothesis(seed, cfg, write_frac):
        scheme, alpha, r = cfg
        check_workload_conformance(scheme, alpha, r, seed,
                                   write_frac=write_frac)

    @settings(max_examples=10)
    @given(st.integers(0, 2**31 - 1),
           st.sampled_from([1, 2, 3, 5, 7, 10, 13]),
           st.lists(st.integers(1, 9), max_size=4, unique=True))
    def test_chunked_stream_conformance_hypothesis(seed, chunk_len, cuts):
        """Random traces × random source splits × random staging lengths:
        streamed replay == the golden model, stats and latencies exact."""
        check_stream_conformance(seed, chunk_len, cuts)
