"""Pattern-builder unit tests: the paper's worked examples (§III-B, Fig 3,
Fig 12, Fig 14) plus structural invariants (port exclusivity)."""
import jax.numpy as jnp
import pytest

from repro.core import controller as ctl
from repro.core.codes import get_tables
from repro.core.state import make_params


def _setup(scheme="scheme_i", n_rows=64, alpha=1.0, r=0.25):
    t = get_tables(scheme)
    p = make_params(t, n_rows=n_rows, alpha=alpha, r=r)
    jt = ctl.jtables(t)
    fresh = jnp.zeros((p.n_data, p.n_rows), jnp.int32)
    pv = jnp.ones((p.n_parities, p.n_slots * p.region_size), bool)
    rslot = jnp.arange(p.n_regions, dtype=jnp.int32)
    return t, p, jt, fresh, pv, rslot


def _read(p, jt, fresh, pv, rslot, banks, rows, coalesce=True):
    n = len(banks)
    plan = ctl.build_read_pattern(
        p._replace(coalesce=coalesce), jt,
        jnp.asarray(banks, jnp.int32), jnp.asarray(rows, jnp.int32),
        jnp.arange(n, dtype=jnp.int32), jnp.ones((n,), bool),
        jnp.zeros((p.n_ports + 1,), bool), fresh, pv, rslot,
    )
    return plan


def test_fig3_two_reads_one_bank():
    """Fig 3: two reads to bank a — one direct, one via sibling+parity."""
    t, p, jt, fresh, pv, rslot = _setup()
    plan = _read(p, jt, fresh, pv, rslot, [0, 0], [1, 5])
    assert bool(plan.served.all())
    modes = set(int(m) for m in plan.mode)
    assert int(plan.n_degraded) >= 1          # one request used the parity path


def test_best_case_10_requests_scheme_i():
    """§III-B1 best case: 10 requests to one coded group in one cycle.

    The paper's hand-crafted schedule reaches 10; that schedule needs a
    lookahead the (paper's own, Fig 11) flowchart builder doesn't have —
    "up to 10" is the *code's* capacity. Our age-order greedy provably
    reaches ≥ 9 on this workload (one chain seeded from the wrong direct
    read); the sim-level results (Fig 18 repro) are driven by the average
    case, where the two are indistinguishable."""
    t, p, jt, fresh, pv, rslot = _setup("scheme_i")
    banks = [0, 1, 2, 3, 0, 1, 2, 3, 2, 3]
    rows = [1, 1, 1, 1, 2, 2, 2, 2, 3, 3]
    plan = _read(p, jt, fresh, pv, rslot, banks, rows)
    assert int(plan.n_served) >= 9            # greedy: best-case − 1
    assert int(plan.n_degraded) >= 5          # chained decodes engaged
    # port exclusivity is structural: the builder marks ports busy; verify
    # the count of consumed ports never exceeds the port budget
    assert int(plan.port_busy[:-1].sum()) <= p.n_ports


def test_worst_case_no_parity_use():
    """§III-B1 worst case: non-consecutive rows -> only direct reads."""
    t, p, jt, fresh, pv, rslot = _setup("scheme_i", n_rows=64, alpha=1.0, r=0.25)
    banks = [0, 0, 1, 1, 2, 2, 3, 3]
    rows = [1, 2, 8, 9, 10, 11, 14, 15]
    plan = _read(p, jt, fresh, pv, rslot, banks, rows, coalesce=False)
    # Paper §III-B1: worst-case reads/cycle == number of data banks in the
    # group (4). A degraded read may substitute for a direct one (it burns a
    # sibling port), but no schedule serves more than 4 here (max matching
    # over the 10 group ports with no shareable symbols).
    assert int(plan.n_served) == 4


def test_stale_parity_blocks_degraded_read():
    t, p, jt, fresh, pv, rslot = _setup()
    pv = pv.at[:, :].set(False)               # all parities stale
    plan = _read(p, jt, fresh, pv, rslot, [0, 0, 0], [1, 2, 3], coalesce=False)
    # only the direct read can be served
    assert int(plan.n_served) == 1
    assert int(plan.n_degraded) == 0


def test_redirect_read_from_parked_value():
    """Status 10: the fresh value lives in a parity slot — read it there."""
    t, p, jt, fresh, pv, rslot = _setup()
    fresh = fresh.at[0, 1].set(1)             # parked in logical parity 0
    plan = _read(p, jt, fresh, pv, rslot, [0], [1])
    assert bool(plan.served[0])
    assert int(plan.mode[0]) == ctl.MODE_REDIRECT


def test_write_pattern_parks_conflicting_writes():
    """Fig 14: multiple writes to one bank -> one direct + parked extras."""
    t, p, jt, fresh, pv, rslot = _setup()
    n = 4
    rc = jnp.full((p.recode_cap,), -1, jnp.int32)
    plan = ctl.build_write_pattern(
        p, jt,
        jnp.asarray([0, 0, 0, 0], jnp.int32),
        jnp.asarray([1, 2, 3, 4], jnp.int32),
        jnp.arange(n, dtype=jnp.int32), jnp.ones((n,), bool),
        jnp.zeros((p.n_ports + 1,), bool), fresh, pv, rslot,
        jnp.zeros((p.n_regions,), jnp.int32), rc, rc,
        jnp.zeros((p.recode_cap,), bool),
    )
    assert int(plan.n_served) == 4            # 1 direct + 3 parked
    assert int(plan.n_parked) == 3
    # parked rows are tracked in fresh_loc and parities invalidated
    assert int((plan.fresh_loc > 0).sum()) == 3
    # every parked/direct write enqueued a recode request
    assert int(plan.rc_valid.sum()) == 4


def test_write_capacity_scheme_i_group():
    """8 writes across 4 banks of one group all land in one cycle."""
    t, p, jt, fresh, pv, rslot = _setup()
    banks = [0, 0, 1, 1, 2, 2, 3, 3]
    rows = [1, 2, 3, 4, 5, 6, 7, 8]
    n = len(banks)
    rc = jnp.full((p.recode_cap,), -1, jnp.int32)
    plan = ctl.build_write_pattern(
        p, jt, jnp.asarray(banks, jnp.int32), jnp.asarray(rows, jnp.int32),
        jnp.arange(n, dtype=jnp.int32), jnp.ones((n,), bool),
        jnp.zeros((p.n_ports + 1,), bool), fresh, pv, rslot,
        jnp.zeros((p.n_regions,), jnp.int32), rc, rc,
        jnp.zeros((p.recode_cap,), bool),
    )
    assert int(plan.n_served) == 8
    assert int(plan.n_parked) == 4


@pytest.mark.parametrize("faults", [(), (("bank", 0, 2, 5),)],
                         ids=["no-faults", "bank-fault"])
@pytest.mark.parametrize("scheme", ["scheme_i", "uncoded"])
def test_walk_bodies_hold_no_gather_or_scatter(scheme, faults):
    """Under the sweep engine's vmap every index inside a walk body is
    batched, and a batched index compiles to a gather or a scatter: one
    device op per walk trip that does not fuse. The read and write walks
    of the paper's deployment (8 banks, 8 cores, queue depth 10, 512 rows,
    alpha 0.25, r 0.05) keep their state per candidate and hold none."""
    import jax

    from repro.analysis.jaxpr import while_body_primitives
    from repro.configs.paper_memsys import MemSysConfig
    from repro.sweep import engine, workloads
    from repro.sweep.grid import SweepPoint

    cfg = MemSysConfig()
    pts = [SweepPoint(scheme=scheme, n_rows=cfg.n_rows, alpha=0.25, r=cfg.r,
                      n_data=cfg.n_data, n_banks=cfg.n_data,
                      n_cores=cfg.n_cores, queue_depth=cfg.queue_depth,
                      select_period=cfg.select_period, length=16,
                      faults=faults, seed=s) for s in range(2)]
    sys = engine.system_for(pts[0])
    tn = engine.stack_tunables(pts, sys.p.queue_depth)
    st = engine._batched_init(sys, tn)
    if sys.p.faults:
        st = st._replace(mem=st.mem._replace(
            fault=engine._stack_faults(pts, sys.p)))
    trace = workloads.stack_traces([workloads.build_trace(pt) for pt in pts])
    jpr = jax.make_jaxpr(jax.vmap(sys.cycle_fn))(st, trace, tn)
    walks = while_body_primitives(jpr, "cycle.patterns")
    assert len(walks) == 2                     # the read walk, the write walk
    for prims in walks:
        bad = {k: v for k, v in prims.items()
               if k == "gather" or k.startswith("scatter")}
        assert not bad, bad
