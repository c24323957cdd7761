"""Shared fixtures. NOTE: no XLA_FLAGS here — smoke tests and benches must
see the real single device; only launch/dryrun.py forces 512 host devices."""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_enable_x64", False)

# ---------------------------------------------------------------- hypothesis
# CI runs the property-based suites derandomized (fixed seed, no deadline):
# conformance failures must be reproducible from the log, and CI machines
# make wall-clock deadlines flaky. Locally the default profile keeps random
# exploration but still drops the deadline (jit compiles dominate first
# calls). Select explicitly with HYPOTHESIS_PROFILE=ci|dev.
try:
    from hypothesis import settings as _hsettings

    _hsettings.register_profile("ci", derandomize=True, deadline=None,
                                print_blob=True)
    _hsettings.register_profile("dev", deadline=None)
    _hsettings.load_profile(os.environ.get(
        "HYPOTHESIS_PROFILE", "ci" if os.environ.get("CI") else "dev"))
except ImportError:                                       # pragma: no cover
    pass  # hypothesis is an optional dev dependency; seeded fallbacks run

# Default geometries for coded-memory-system tests. The cycle engine is
# compile-dominated on CPU, so tests should share these small shapes (and
# thereby jit caches) rather than inventing their own: n_rows/lengths large
# enough to exercise multi-region dynamic coding, small enough that the fast
# tier stays fast. Heavier sweeps belong behind ``-m slow``.
SMALL_N_ROWS = 64
SMALL_TRACE_LEN = 32


def rand_trace(rng, n_cores, length, n_banks, n_rows, write_frac=0.45):
    """Seeded random request streams — the shared test-trace builder
    (import as ``from conftest import rand_trace``)."""
    from repro.core.system import Trace
    return Trace(
        bank=jnp.asarray(rng.integers(0, n_banks, (n_cores, length)), jnp.int32),
        row=jnp.asarray(rng.integers(0, n_rows, (n_cores, length)), jnp.int32),
        is_write=jnp.asarray(rng.random((n_cores, length)) < write_frac),
        data=jnp.asarray(rng.integers(1, 1 << 20, (n_cores, length)), jnp.int32),
        valid=jnp.asarray(rng.random((n_cores, length)) < 0.9),
    )


# ------------------------------------------------------------------- oracle
# Helpers shared by the conformance suites (tests/test_conformance.py,
# tests/test_scheduler_equiv.py): build the NumPy golden-model twin of a
# production system and assert full state equality against it.

def oracle_twin(system):
    """The ``repro.oracle`` golden model configured like ``system`` (a
    ``CodedMemorySystem``): same allocation, same active geometry, same
    tunables. The oracle derives its own scheme tables from the name."""
    from repro.oracle import OracleMemorySystem, OracleParams

    p, tn = system.p, system.tunables
    int32_max = np.iinfo(np.int32).max

    def active(v, alloc):
        v = int(v)
        return alloc if v == int32_max else min(v, alloc)

    op = OracleParams(
        n_data=p.n_data, n_rows=p.n_rows, region_size=p.region_size,
        n_regions=p.n_regions, n_slots=p.n_slots, n_active=p.n_active,
        queue_depth=p.queue_depth, recode_cap=p.recode_cap,
        recode_budget=p.recode_budget, coalesce=p.coalesce,
        encode_rows_per_cycle=p.encode_rows_per_cycle,
        region_size_active=active(tn.region_size_active, p.region_size),
        n_regions_active=active(tn.n_regions_active, p.n_regions),
        n_slots_active=active(tn.n_slots_active, p.n_active),
        select_period=int(tn.select_period), wq_hi=int(tn.wq_hi),
        wq_lo=int(tn.wq_lo), telemetry=p.telemetry, faults=p.faults)
    return OracleMemorySystem(system.tables.scheme.name, op,
                              n_cores=system.n_cores)


_ORACLE_ARRAY_FIELDS = (
    "fresh_loc", "parity_valid", "region_slot", "slot_region",
    "access_count", "parked_count", "rc_bank", "rc_row", "rc_valid",
    "rq_row", "rq_age", "rq_valid", "wq_row", "wq_age", "wq_valid",
    "wq_data", "banks_data", "parity_data", "golden")
_ORACLE_SCALAR_FIELDS = (
    "enc_region", "enc_remaining", "enc_slot", "switches", "write_mode",
    "cycle", "served_reads", "served_writes", "degraded_reads",
    "parked_writes", "rc_dropped")
_ORACLE_WIDE_FIELDS = ("read_latency_sum", "write_latency_sum",
                       "stall_cycles")


def assert_state_matches_oracle(st, ost, label=""):
    """Every leaf of a SimState equals the golden model's: the memory
    arrays bit for bit (including stale queue/ring contents — retired slots
    keep identical residue in both models), the scalars exactly, the wide
    (lo, hi) counters as integers."""
    from repro.core.state import bank_view, wide_total

    host = jax.device_get(st)
    m = host.mem
    for name in _ORACLE_ARRAY_FIELDS:
        got, want = np.asarray(getattr(m, name)), getattr(ost, name)
        if got.ndim == want.ndim + 1:   # a bank table in rows of lanes
            flat = got.reshape(got.shape[0], -1)
            assert not flat[:, want.shape[1]:].any(), \
                f"{label}: field {name!r} wrote past its width"
            got = bank_view(got, want.shape[1])
        np.testing.assert_array_equal(got, want,
                                      err_msg=f"{label}: field {name!r}")
    for name in _ORACLE_SCALAR_FIELDS:
        assert int(getattr(m, name)) == int(getattr(ost, name)), \
            f"{label}: field {name!r}"
    for name in _ORACLE_WIDE_FIELDS:
        assert wide_total(getattr(m, name)) == getattr(ost, name), \
            f"{label}: field {name!r}"
    np.testing.assert_array_equal(np.asarray(host.core_ptr), ost.core_ptr,
                                  err_msg=f"{label}: core_ptr")
    assert int(host.done_cycle) == ost.done_cycle, f"{label}: done_cycle"
    # telemetry planes (repro.obs): both models carry them or neither does;
    # each plane must match the oracle's independent derivation exactly
    assert (m.tele is None) == (ost.tele is None), \
        f"{label}: telemetry presence mismatch"
    if m.tele is not None:
        from repro.obs.planes import Telemetry

        for name in Telemetry._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(m.tele, name)).astype(np.int64),
                np.asarray(getattr(ost.tele, name)),
                err_msg=f"{label}: tele.{name}")
    # fault leaf (repro.faults): schedule + progress, compared field by
    # field against the oracle's independent re-derivation
    assert (m.fault is None) == (ost.fault is None), \
        f"{label}: fault presence mismatch"
    if m.fault is not None:
        from repro.faults.plan import FaultState

        for name in FaultState._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(m.fault, name)).astype(np.int64),
                np.asarray(getattr(ost.fault, name)).astype(np.int64),
                err_msg=f"{label}: fault.{name}")


@pytest.fixture(scope="session")
def rng_key():
    return jax.random.key(0)


@pytest.fixture(scope="session")
def small_geom():
    """(n_rows, trace_length) for quick end-to-end memory-system tests."""
    return SMALL_N_ROWS, SMALL_TRACE_LEN


@pytest.fixture
def sweep_compile_count():
    """Callable returning how many device programs the sweep engine has
    compiled so far (the jit cache size of its batched scan). Take a delta
    around ``run_points`` to assert the compile count of a grid."""
    from repro.analysis import guard

    return lambda: guard.cache_size("sweep")


@pytest.fixture
def compile_guard():
    """The generalized recompile guard (``repro.analysis.recompile_guard``):
    yields the context-manager factory.

        with compile_guard("kernels.xor_encode", max_compiles=1):
            ...   # region may compile at most one new program

    Targets are ``repro.analysis.guard.GUARDED`` names or jitted
    callables; ``g.compiles()``/``g.deltas()`` give exact counts."""
    from repro.analysis import guard

    return guard.recompile_guard
