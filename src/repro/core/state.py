"""Pytree state for the coded memory system (controller + banks).

Freshness model (a bit-exact refinement of the paper's 2-bit code status
table, §IV-A):

  * ``fresh_loc[b, i]`` — where the logically-fresh value of data bank ``b``
    row ``i`` lives: ``0`` = in the data bank; ``j+1`` = *parked* raw in
    logical parity bank ``j``'s row slot (paper status ``10``).
  * ``parity_valid[j, r]`` — logical parity ``j``'s slot row ``r`` currently
    equals the XOR of its members' *data-bank-stored* rows. Cleared by any
    member direct-write (paper status ``01``) or by parking (status ``10``);
    restored by the ReCoding unit or by a fresh region encode.

  Degraded read of ``(b, i)`` via parity ``j`` therefore requires
  ``parity_valid[j, r(i)]`` *and* ``fresh_loc[b, i] == 0``. Sibling rows are
  read from their data banks; their XOR with the parity reconstructs the
  data-bank value of ``b`` exactly even if a sibling's own fresh value is
  parked elsewhere (the parity was computed from data-bank contents).

Dynamic coding (§IV-E): rows are grouped into ``n_regions`` regions of
``region_size`` rows; ``region_slot[g]`` maps region ``g`` to a parity slot
(or -1), giving parity row ``r(i) = region_slot[i // rs] * rs + i % rs``.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.codes import CodeTables
from repro.faults.plan import FaultPlan, FaultState, init_fault_state
from repro.obs.planes import Telemetry, init_telemetry

NOP_PORT_PAD = 1  # port_busy has one trailing dummy slot used as a no-op sink


class MemParams(NamedTuple):
    """Static geometry (python ints; hashable, used as jit static args).

    Anything that is a *value* rather than a *shape* — the write-drain
    thresholds and the dynamic-coding selection period — lives in
    ``TunableParams`` instead, so sweeps can batch over it without
    recompiling (one compiled program serves a whole tunable grid).

    ``region_size`` / ``n_regions`` / ``n_slots`` are *allocation* shapes:
    a sweep group may pad them up to the group maximum and run each point
    at its own traced geometry (``TunableParams.region_size_active`` /
    ``n_regions_active`` / ``n_slots_active``, see ``active_geometry``).
    ``n_active`` is the allocation's true parity-slot budget — it can be 0
    (α < r: the point is uncoded) even though storage keeps a ≥1 floor.
    """

    n_data: int
    n_parities: int
    n_ports: int          # data + physical parity banks
    n_rows: int           # L, rows per data bank
    region_size: int      # rs (allocated stride of one parity slot)
    n_regions: int        # ceil(L / rs) (allocated)
    n_slots: int          # parity slots = floor(alpha / r), capped at
                          # n_regions; ≥1 storage floor (allocated)
    n_active: int         # slots usable for coded regions (0 when α < r)
    queue_depth: int
    recode_cap: int
    max_syms: int         # symbol-set capacity bound; must cover
                          # n_ports (enforced by ``make_params``) so the
                          # per-cycle symbol set can never saturate
    recode_budget: int    # max recode entries retired per cycle
    coalesce: bool        # allow FROM_SYM / chained-decode reuse (off for the
                          # uncoded Ramulator-like baseline)
    encode_rows_per_cycle: int = 64  # encoder bandwidth; the traced
                                     # per-point encode latency is
                                     # max(1, region_size_active // this)
    traced_geometry: bool = False    # True: region indexing uses the traced
                                     # TunableParams.*_active geometry (a
                                     # padded multi-geometry sweep group);
                                     # False: the allocation IS the geometry
                                     # and indexing stays static (no traced
                                     # divisions — the exact pre-masking
                                     # program)
    telemetry: bool = False          # True: carry repro.obs.planes metric
                                     # planes through the cycle loop (stall/
                                     # wait attribution, provenance, queue
                                     # HWMs, latency histograms). False: the
                                     # ``tele`` leaf is None and the traced
                                     # program is bit-identical to one built
                                     # before the flag existed (same gating
                                     # style as ``traced_geometry``)
    faults: bool = False             # True: carry a repro.faults.FaultState
                                     # leaf (bank-erasure schedule, rebuild
                                     # progress, availability counters) and
                                     # weave the fault hooks into cycle_fn.
                                     # False: the ``fault`` leaf is None and
                                     # the program is bit-identical to the
                                     # pre-fault one (same gating style as
                                     # ``telemetry``)


class TunableParams(NamedTuple):
    """Per-point scalar knobs (traced jnp arrays; a ``vmap`` batch axis).

    These affect only data values inside the cycle engine, never array
    shapes, so a batch of configurations differing in nothing but these
    can share one compiled program. ``repro.sweep`` exploits exactly that.

    The three ``*_active`` fields carry a point's own α/r geometry inside a
    padded group allocation (``make_params``'s ``*_alloc`` arguments):
    indexing uses the traced values, extra slots/regions/rows are masked
    off. Defaults of INT32_MAX clamp to the allocation (exact geometry).
    """

    select_period: jnp.ndarray  # () int32 — T, dynamic re-selection period
    wq_hi: jnp.ndarray          # () int32 — write-drain hysteresis thresholds
    wq_lo: jnp.ndarray          # () int32
    n_slots_active: jnp.ndarray  # () int32 — parity-slot budget this point may
                                 # use (≤ MemParams.n_active; lets an α axis
                                 # batch over one max-α allocation)
    region_size_active: jnp.ndarray  # () int32 — this point's own rs
    n_regions_active: jnp.ndarray    # () int32 — this point's own ⌈L/rs⌉


def make_tunables(
    queue_depth: int = 10,
    select_period: int = 512,
    wq_hi: int = 8,
    wq_lo: int = 2,
    n_slots_active: int = jnp.iinfo(jnp.int32).max,
    region_size_active: int = jnp.iinfo(jnp.int32).max,
    n_regions_active: int = jnp.iinfo(jnp.int32).max,
) -> TunableParams:
    hi = min(int(wq_hi), queue_depth - 1)
    return TunableParams(
        select_period=jnp.int32(max(int(select_period), 1)),
        wq_hi=jnp.int32(hi),
        # crossed hysteresis thresholds (lo > hi) would flap write_mode every
        # cycle: entering write mode at occupancy >= hi and staying only
        # while occupancy > lo > hi means no state is ever stable
        wq_lo=jnp.int32(min(int(wq_lo), hi)),
        n_slots_active=jnp.int32(n_slots_active),
        region_size_active=jnp.int32(region_size_active),
        n_regions_active=jnp.int32(n_regions_active),
    )


def active_geometry(p: MemParams, tn: TunableParams):
    """(region_size_active, n_regions_active) for this point.

    With ``p.traced_geometry`` these are traced int32 scalars — the tunable
    defaults (INT32_MAX) clamp to the allocation, a padded group allocation
    sees each point's own geometry. Without it they are the static python
    ints themselves (a single-geometry system compiles with no traced
    divisions at all; any ``*_active`` tunables are ignored by
    construction because they equal the allocation). Parity row addressing
    always keeps the *allocated* slot stride: row ``i`` of a slot lives at
    ``slot * p.region_size + i % region_size_active``."""
    if not p.traced_geometry:
        return p.region_size, p.n_regions
    rs_a = jnp.minimum(tn.region_size_active, p.region_size)
    nr_a = jnp.minimum(tn.n_regions_active, p.n_regions)
    return rs_a, nr_a


# --------------------------------------------------------------- wide counters
# 64-bit statistics accumulators as (lo, hi) uint32 limb pairs. jnp.int64
# silently degrades to int32 unless the global ``jax_enable_x64`` flag is on
# (which would flip default dtypes across the whole program), so the wide
# counters emulate 64-bit exactly with explicit 32-bit dtypes instead —
# independent of the flag.

def wide_zero() -> jnp.ndarray:
    """A zeroed 64-bit accumulator: shape (2,) uint32 = (lo, hi) limbs."""
    return jnp.zeros((2,), jnp.uint32)


def wide_add(acc: jnp.ndarray, inc) -> jnp.ndarray:
    """``acc + inc`` for a non-negative scalar ``inc`` < 2**32."""
    lo = acc[0] + jnp.asarray(inc).astype(jnp.uint32)
    return jnp.stack([lo, acc[1] + (lo < acc[0]).astype(jnp.uint32)])


def wide_total(acc) -> int:
    """Host-side python int value of a wide accumulator."""
    a = np.asarray(acc)
    return int(a[..., 0]) + (int(a[..., 1]) << 32)


def derive_geometry(n_rows: int, alpha: float, r: float):
    """(region_size, n_regions, n_slots) implied by an (n_rows, α, r) point.

    Shared by ``make_params`` and ``repro.sweep.grid.static_signature`` so the
    sweep layer can reason about which points share compiled shapes.

    ``n_slots`` is 0 when α < r: the parity budget cannot hold even one
    region, so the point is explicitly uncoded (no free slot is granted —
    that would overstate coverage at tiny α).
    """
    region_size = max(1, int(round(n_rows * r)))
    n_regions = -(-n_rows // region_size)
    n_slots = min(int(np.floor(alpha / r + 1e-9)), n_regions)
    return region_size, n_regions, max(n_slots, 0)


def make_params(
    tables: CodeTables,
    n_rows: int,
    alpha: float,
    r: float,
    queue_depth: int = 10,
    recode_cap: int = 64,
    max_syms: int = 96,
    encode_rows_per_cycle: int = 64,
    recode_budget: int = 4,
    coalesce: bool = True,
    n_slots_alloc: Optional[int] = None,
    region_size_alloc: Optional[int] = None,
    n_regions_alloc: Optional[int] = None,
    traced_geometry: bool = False,
    telemetry: bool = False,
    faults: bool = False,
) -> MemParams:
    if max_syms < tables.n_ports:
        # the builders' symbol set (per-candidate flags) has true set
        # semantics; the scheduling contract (plans equal the sequential
        # golden model's) additionally requires that a capacity-bounded
        # symbol list could never saturate, which holds when max_syms
        # covers the per-cycle port-claim bound. Reject configurations
        # below it instead of silently changing chained-decode behaviour.
        raise ValueError(
            f"max_syms={max_syms} < n_ports={tables.n_ports}: the symbol "
            "capacity must cover the per-cycle port-claim bound (see "
            "docs/testing.md)")
    region_size, n_regions, n_slots = derive_geometry(n_rows, alpha, r)
    full = n_slots >= n_regions
    # ---- group allocation: a sweep batches several α/r geometries over one
    # compiled shape by padding region/parity state to the group maxima; the
    # per-point geometry rides in ``TunableParams.{region_size,n_regions,
    # n_slots}_active`` and masks the padding off.
    if region_size_alloc is not None:
        if region_size_alloc < region_size:
            raise ValueError(f"region_size_alloc={region_size_alloc} < "
                             f"derived region_size={region_size}")
        region_size = region_size_alloc
    if n_regions_alloc is not None:
        if n_regions_alloc < n_regions:
            raise ValueError(f"n_regions_alloc={n_regions_alloc} < "
                             f"derived n_regions={n_regions}")
        n_regions = n_regions_alloc
    # §IV-E says "up to α/r − 1 regions" with one reserved for staging, but the
    # paper's own experiment discussion (§V-C: "⌊α/r⌋ = 2 … we can select 2
    # regions" at α=0.1, r=0.05) uses ⌊α/r⌋ active regions; we follow §V-C and
    # model staging as the in-flight slot being unusable during its encode.
    n_active = n_slots
    if n_slots_alloc is not None:
        if n_slots_alloc < n_slots:
            raise ValueError(
                f"n_slots_alloc={n_slots_alloc} < derived n_slots={n_slots}")
        if (n_slots_alloc >= n_regions) != full:
            raise ValueError(
                "n_slots_alloc must not change full-coverage status "
                f"(alloc {n_slots_alloc}, derived {n_slots}, regions {n_regions})")
        n_slots = n_active = n_slots_alloc
    return MemParams(
        n_data=tables.n_data,
        n_parities=max(tables.n_parities, 1),
        n_ports=tables.n_ports,
        n_rows=n_rows,
        region_size=region_size,
        n_regions=n_regions,
        n_slots=max(n_slots, 1),   # storage floor; the true budget is n_active
        n_active=n_active,
        queue_depth=queue_depth,
        recode_cap=recode_cap,
        max_syms=max_syms,
        recode_budget=recode_budget,
        coalesce=coalesce if tables.n_parities > 0 else False,
        encode_rows_per_cycle=encode_rows_per_cycle,
        traced_geometry=traced_geometry,
        telemetry=telemetry,
        faults=faults,
    )


# ------------------------------------------------------------ bank tables
# The tables a bank wide (``fresh_loc``, ``parity_valid``, the data) are
# logically (banks, width). Up to ``ONEHOT_MAX_COLS`` columns the state
# keeps them so and the controller indexes them by one-hot (see the note
# in ``core/controller.py``). Wider, it keeps them in rows of ``LANES``:
# (banks, ceil(width / LANES), LANES), the tail padded. A TPU scatter
# addresses its operand as one flat run of elements; a (banks, width) array
# tiled (8, 128) is not one, so the compiler relays the whole array out and
# back around each scatter into it (at 65,536 rows, ~1 ms or more of a
# cycle on a v5e), while rows of 128 lanes already are one. The helpers
# below index either form; which one a table is follows from its shape.
ONEHOT_MAX_COLS = 2048
LANES = 128


def bank_table(x):
    """The stored form of a logical (banks, width) table."""
    n, width = x.shape
    # static: a shape against a module constant
    if width <= ONEHOT_MAX_COLS:  # analysis: tracer-branch
        return x
    return jnp.pad(x, ((0, 0), (0, -width % LANES))).reshape(n, -1, LANES)


def bank_view(x, width: int):
    """The logical (banks, ``width``) table of a stored one (NumPy or
    JAX)."""
    return x.reshape(x.shape[0], -1)[:, :width]


def cells(table, rows, cols):
    """``table[rows, cols]`` of a stored table, by gather."""
    if table.ndim == 3:
        return table[rows, cols // LANES, cols % LANES]
    return table[rows, cols]


def set_cells(table, rows, cols, vals):
    """``table`` with ``table[rows, cols] = vals`` by scatter; a row out of
    range drops its write."""
    if table.ndim == 3:
        return table.at[rows, cols // LANES, cols % LANES].set(
            vals, mode="drop")
    return table.at[rows, cols].set(vals, mode="drop")


def _lane_rows(table, start, size):
    """The rows of ``LANES`` that hold columns ``start .. start + size`` (in
    range) of a table in rows of ``LANES``: (the (banks, k) row indices, the
    offset of ``start`` in the first row). Whole rows of lanes are what a
    gather or scatter here addresses: a window that spans the banks has the
    compiler lay the whole table out again first."""
    n, t, _ = table.shape
    k = min(-(-size // LANES) + 1, t)
    first = jnp.minimum(start // LANES, t - k)
    rows = first + jnp.arange(k)
    return (jnp.arange(n)[:, None], rows[None, :]), start - first * LANES


def columns(table, start, size: int):
    """``table[:, start:start + size]`` of a stored table, for an in-range
    window: reads those columns' rows alone."""
    if table.ndim == 3:
        at, off = _lane_rows(table, start, size)
        win = table[at].reshape(table.shape[0], -1)
        return jax.lax.dynamic_slice(win, (0, off), (table.shape[0], size))
    return jax.lax.dynamic_slice(table, (0, start), (table.shape[0], size))


def set_columns(table, vals, start):
    """``table`` with columns ``start ..`` (in range) set to ``vals``:
    writes those columns' rows alone."""
    if table.ndim == 3:
        at, off = _lane_rows(table, start, vals.shape[1])
        rows = table[at]                              # (banks, k, LANES)
        win = jax.lax.dynamic_update_slice(
            rows.reshape(table.shape[0], -1), vals, (0, off))
        return table.at[at].set(win.reshape(rows.shape))
    return jax.lax.dynamic_update_slice(table, vals, (0, start))


class MemState(NamedTuple):
    """Dynamic controller state (all jnp arrays; a scan carry). The tables
    a bank wide are stored as ``bank_table`` gives them."""

    # freshness / code status
    fresh_loc: jnp.ndarray      # (n_data, L) int32
    parity_valid: jnp.ndarray   # (n_par, n_slots * rs) bool
    # dynamic coding
    region_slot: jnp.ndarray    # (n_regions,) int32, -1 = uncoded
    slot_region: jnp.ndarray    # (n_slots,) int32, -1 = free/staging
    access_count: jnp.ndarray   # (n_regions,) int32 (windowed)
    parked_count: jnp.ndarray   # (n_regions,) int32
    enc_region: jnp.ndarray     # () int32, -1 = idle
    enc_remaining: jnp.ndarray  # () int32
    enc_slot: jnp.ndarray       # () int32 slot being encoded (-1 idle)
    switches: jnp.ndarray       # () int32
    # recode ring buffer
    rc_bank: jnp.ndarray        # (RC,) int32
    rc_row: jnp.ndarray         # (RC,) int32
    rc_valid: jnp.ndarray       # (RC,) bool
    # read/write queues (per data bank)
    rq_row: jnp.ndarray         # (n_data, D) int32
    rq_age: jnp.ndarray         # (n_data, D) int32 (issue cycle; INT32_MAX empty)
    rq_valid: jnp.ndarray       # (n_data, D) bool
    wq_row: jnp.ndarray
    wq_age: jnp.ndarray
    wq_valid: jnp.ndarray
    wq_data: jnp.ndarray        # (n_data, D) int32 write payloads
    write_mode: jnp.ndarray     # () bool (write-drain hysteresis)
    cycle: jnp.ndarray          # () int32
    # data-carrying banks (scalar word per row; the datapath reference and
    # the substrate for the correctness invariants in tests)
    banks_data: jnp.ndarray     # (n_data, L) int32
    parity_data: jnp.ndarray    # (n_par, n_slots * rs) int32
    golden: jnp.ndarray         # (n_data, L) int32 memory-order reference
    # stats (event counters are int32 — bounded by trace size; the
    # per-cycle-growing accumulators are wide (lo, hi) uint32 pairs, see
    # ``wide_zero``: they overflow int32 on long traces)
    served_reads: jnp.ndarray   # () int32
    served_writes: jnp.ndarray  # () int32
    degraded_reads: jnp.ndarray  # () int32 (reads served via parity/symbols)
    parked_writes: jnp.ndarray  # () int32
    read_latency_sum: jnp.ndarray  # (2,) uint32 wide accumulator
    write_latency_sum: jnp.ndarray  # (2,) uint32 wide accumulator
    stall_cycles: jnp.ndarray   # (2,) uint32 wide (core-stall events)
    rc_dropped: jnp.ndarray     # () int32 (recode requests lost to a full ring)
    # opt-in leaves: None unless the matching MemParams flag is set — a None
    # leaf is an empty pytree node, so the flags-off carry has exactly the
    # pre-flag tree structure and the compiled program is unchanged. These
    # MUST stay the trailing fields, in this order (older pickled/positional
    # states keep their layout; new opt-in leaves append after ``fault``).
    tele: Optional[Telemetry] = None
    # fault-injection schedule + progress (repro.faults): None unless
    # MemParams.faults
    fault: Optional[FaultState] = None


def _concrete_int(x) -> Optional[int]:
    """Host value of ``x``, or None when it is a tracer (vmap/jit)."""
    try:
        return int(x)
    except Exception:
        return None


def init_state(p: MemParams, tn: Optional[TunableParams] = None,
               region_priors=None, n_cores: int = 8,
               fault_plan: Optional[FaultPlan] = None) -> MemState:
    """Initial controller state.

    With ``tn`` (the batched-sweep path), the point's *active* geometry
    shapes the initial region map and parity validity inside the allocated
    arrays: padded regions/slots stay unmapped (-1) and padded parity rows
    stay invalid, so a padded program is bit-identical per point to an
    exactly allocated one. Without ``tn``, the allocation is the geometry.

    ``region_priors`` (sub-coverage systems only) warm-starts the dynamic
    coding unit: a ranked int32 array of hot region ids (-1 padded) — e.g.
    ``repro.traces.profiler.TraceProfile.region_priors`` — whose leading
    entries are pre-mapped into parity slots with their parities already
    valid (all banks are zero at init, so the all-zero parity rows are the
    true XOR of their members). See ``repro.core.dynamic.priors_layout``.

    ``n_cores`` only sizes the telemetry provenance planes; the
    telemetry-off state does not depend on it.

    ``fault_plan`` (a ``repro.faults.FaultPlan``) installs a bank-erasure /
    port-stutter schedule; requires ``MemParams.faults``. With the flag on
    but no plan, the no-fault schedule is carried (nothing ever fails) —
    same compiled program, schedule-only difference.
    """
    if fault_plan is not None and not p.faults:
        raise ValueError("init_state got a fault_plan but the system was "
                         "built without make_params(faults=True) — the "
                         "schedule would be silently ignored")
    if fault_plan is not None and (fault_plan.n_data != p.n_data
                                   or fault_plan.n_ports != p.n_ports):
        raise ValueError(
            f"FaultPlan geometry ({fault_plan.n_data} data banks, "
            f"{fault_plan.n_ports} ports) does not match MemParams "
            f"({p.n_data}, {p.n_ports})")
    if tn is not None and not p.traced_geometry:
        # a non-traced system ignores the geometry actives entirely — reject
        # explicit values that disagree with the allocation instead of
        # silently simulating a hybrid configuration (tracers are exempt:
        # the sweep engine only builds non-traced systems for uniform
        # batches whose actives equal the allocation)
        sentinel = jnp.iinfo(jnp.int32).max
        for v, alloc, name in ((tn.region_size_active, p.region_size,
                                "region_size_active"),
                               (tn.n_regions_active, p.n_regions,
                                "n_regions_active")):
            cv = _concrete_int(v)
            # host-only: _concrete_int returns None for tracers, so the
            # second clause never sees one  # analysis: tracer-branch
            if cv is not None and cv not in (alloc, sentinel):
                raise ValueError(
                    f"TunableParams.{name}={cv} differs from the allocation "
                    f"({alloc}) but the system was built without "
                    "make_params(traced_geometry=True) — the traced value "
                    "would be silently ignored")
    n_slot_rows = p.n_slots * p.region_size
    if p.n_active >= p.n_regions:
        # static full coverage: identity region->slot map, all (active)
        # parities valid — the dynamic unit never remaps
        if tn is None or not p.traced_geometry:
            region_slot = jnp.arange(p.n_regions, dtype=jnp.int32)
            slot_region = jnp.arange(p.n_slots, dtype=jnp.int32)
            parity_valid = jnp.ones((p.n_parities, n_slot_rows), bool)
        else:
            rs_a, nr_a = active_geometry(p, tn)
            rid = jnp.arange(p.n_regions, dtype=jnp.int32)
            region_slot = jnp.where(rid < nr_a, rid, -1)
            sid = jnp.arange(p.n_slots, dtype=jnp.int32)
            slot_region = jnp.where(sid < nr_a, sid, -1)
            row = jnp.arange(n_slot_rows, dtype=jnp.int32)
            # storage-layout walk at the allocated parity-row stride, not a
            # data-row region lookup  # analysis: static-geometry
            active = (row // p.region_size < nr_a) & (row % p.region_size < rs_a)
            parity_valid = jnp.broadcast_to(active, (p.n_parities, n_slot_rows))
    elif region_priors is not None:
        from repro.core.dynamic import priors_layout
        region_slot, slot_region, parity_valid = priors_layout(
            p, tn, region_priors)
    else:
        region_slot = jnp.full((p.n_regions,), -1, jnp.int32)
        slot_region = jnp.full((p.n_slots,), -1, jnp.int32)
        parity_valid = jnp.zeros((p.n_parities, n_slot_rows), bool)
    z = jnp.int32(0)
    return MemState(
        fresh_loc=bank_table(jnp.zeros((p.n_data, p.n_rows), jnp.int32)),
        parity_valid=bank_table(parity_valid),
        region_slot=region_slot,
        slot_region=slot_region,
        access_count=jnp.zeros((p.n_regions,), jnp.int32),
        parked_count=jnp.zeros((p.n_regions,), jnp.int32),
        enc_region=jnp.int32(-1),
        enc_remaining=z,
        enc_slot=jnp.int32(-1),
        switches=z,
        rc_bank=jnp.full((p.recode_cap,), -1, jnp.int32),
        rc_row=jnp.full((p.recode_cap,), -1, jnp.int32),
        rc_valid=jnp.zeros((p.recode_cap,), bool),
        rq_row=jnp.full((p.n_data, p.queue_depth), -1, jnp.int32),
        rq_age=jnp.full((p.n_data, p.queue_depth), jnp.iinfo(jnp.int32).max, jnp.int32),
        rq_valid=jnp.zeros((p.n_data, p.queue_depth), bool),
        wq_row=jnp.full((p.n_data, p.queue_depth), -1, jnp.int32),
        wq_age=jnp.full((p.n_data, p.queue_depth), jnp.iinfo(jnp.int32).max, jnp.int32),
        wq_valid=jnp.zeros((p.n_data, p.queue_depth), bool),
        wq_data=jnp.zeros((p.n_data, p.queue_depth), jnp.int32),
        write_mode=jnp.array(False),
        cycle=z,
        banks_data=bank_table(jnp.zeros((p.n_data, p.n_rows), jnp.int32)),
        parity_data=bank_table(
            jnp.zeros((p.n_parities, n_slot_rows), jnp.int32)),
        golden=bank_table(jnp.zeros((p.n_data, p.n_rows), jnp.int32)),
        served_reads=z,
        served_writes=z,
        degraded_reads=z,
        parked_writes=z,
        read_latency_sum=wide_zero(),
        write_latency_sum=wide_zero(),
        stall_cycles=wide_zero(),
        rc_dropped=z,
        tele=(init_telemetry(p.n_data, n_cores, p.queue_depth)
              if p.telemetry else None),
        fault=((fault_plan.state() if fault_plan is not None
                else init_fault_state(p.n_data, p.n_ports))
               if p.faults else None),
    )
