"""Read/write pattern builders (paper §IV-B, §IV-C; Figs 11-14).

Both builders are *greedy matchers*: candidates (queued requests) are
visited oldest-first; each is assigned the cheapest feasible serving action
this memory cycle, where cost counts the single-port banks the action
consumes. Ties prefer parity-based service over direct reads so that data
ports remain available for rows without parity coverage — this reproduces
the paper's best-case chained-decode schedules (§III-B) to within one
request.

Read actions (cost → score = 2*cost + is_direct):
  * FROM_SYM  — the row was already fetched/decoded this cycle (chained
                decode, free).
  * DIRECT    — read the data bank.
  * OPT(k)    — degraded read via logical parity k-th option: parity port +
                any sibling rows not already materialized this cycle.
  * REDIRECT  — the fresh value is parked in a parity bank (status ``10``);
                read it from the parity's port.

Write actions:
  * DIRECT    — write the data bank; invalidates covering parities; enqueues
                a recode request.
  * PARK(k)   — write the raw value into the corresponding row of parity
                option k (paper Fig 14); sets ``fresh_loc = j+1``; enqueues a
                recode request. Requires recode-queue space so the parked
                value can always be drained back.

Scheduling algorithm (the per-cycle hot path)
---------------------------------------------
A naive matcher walks **all** N = ``n_data × queue_depth`` candidate slots
sequentially and re-scans a symbol list per candidate — an O(N · max_syms)
chain per simulated cycle, paid in full even when every queue is empty,
that neither ``vmap`` nor sharding can hide. The builders here implement
the same greedy semantics with cost that tracks the work a cycle actually
contains:

  * **compacted trip count** — candidates are ranked by age with invalid
    slots keyed to +inf, and the walk stops after the last valid position
    (`lax.while_loop`). Idle queues cost zero iterations; the engine's
    post-drain cycles and the off-duty builder of each read/write cycle
    (see ``CodedMemorySystem.cycle_fn``) collapse to the fixed setup cost.
  * **state keyed by candidate** — everything a trip reads or writes lives
    in one per-candidate table (``_Walk``): the candidate's geometry
    (parity options, sibling and port ids), and the state of exactly the
    banks, ports and cells its own scoring looks at. The chained-decode
    symbol set is one flag per (candidate, bank it may need) for the
    candidate's row: true set semantics, no capacity. ``make_params``
    still bounds ``max_syms`` from below (>= ``n_ports``) so that a
    capacity-bounded implementation of the same semantics could never
    saturate — the per-cycle symbol count is bounded by port claims.
  * **lookups before the walk, writes after it** — the table is built
    once per call, by one-hot lookups (``_take``, ``_cell``; a gather
    where the state keeps a bank in rows of lanes, see
    ``state.bank_table``); a trip
    picks its candidate's row with one masked reduction and updates every
    row by compare-and-select, so the loop body holds no gather or
    scatter. Under the sweep engine's ``vmap`` the trip counter is
    batched, and every indexed read or write inside the loop would become
    a batched gather or scatter: an op that does not fuse, whose launch a
    trip pays each time. The write walk's write-only state
    (``fresh_loc``, ``parity_valid``, ``parked_count``, the recode ring)
    is recorded per candidate and applied once after it (``_put``); into
    a table kept in rows of lanes, the cells it writes are first
    compacted into as many slots as the cycle has ports (``write_slots``,
    ``_compact``).

The greedy semantics are genuinely sequential only across candidates that
contend (same ports, or symbols on the same row of one parity group), so
serving decisions cannot simply be computed independently — but everything
*around* that chain is vectorized: the core arbiter ranks cores per
destination queue and scatters once, the write datapath commits the
youngest write of each cell, and the ReCoding unit retires ring entries
in budget-bounded parallel rounds (see ``system.py`` / ``recoding.py``).

Correctness contract: plans are **bit-identical** to the pure-NumPy golden
model in ``repro.oracle`` — an independent, sequential re-derivation of
the paper's matcher that shares no code with this package. The
differential suite in tests/test_conformance.py enforces it on randomized
states and full workloads; see docs/testing.md.

Region geometry is traced, not static: both builders take an optional
``rs_active`` (the point's own region size inside a padded sweep
allocation, see ``state.active_geometry``). Region lookups use it;
parity-row addressing keeps the *allocated* ``p.region_size`` stride so
padded slots never alias. ``None`` (the default) means the allocation is
the geometry.
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

from repro.core.codes import MAX_OPTS, CodeTables
from repro.core.state import MemParams, cells, set_cells

INT32_MAX = jnp.iinfo(jnp.int32).max
INF_SCORE = jnp.int32(1 << 30)

# read modes (reported per candidate)
MODE_UNSERVED = -1
MODE_FROM_SYM = 0
MODE_DIRECT = 1
MODE_OPT0 = 2                      # MODE_OPT0 + k  for option k
MODE_REDIRECT = MODE_OPT0 + MAX_OPTS

# write modes
WMODE_UNSERVED = -1
WMODE_DIRECT = 0
WMODE_PARK0 = 1                    # WMODE_PARK0 + k


class JTables(NamedTuple):
    """Device copies of the static code tables (a pytree)."""

    par_members: jnp.ndarray   # (n_par, MAX_SIBS+1)
    par_port: jnp.ndarray      # (n_par,)
    opt_parity: jnp.ndarray    # (n_data, MAX_OPTS)
    opt_sibs: jnp.ndarray      # (n_data, MAX_OPTS, MAX_SIBS)
    opt_n: jnp.ndarray         # (n_data,)


def jtables(tables: CodeTables) -> JTables:
    return JTables(
        par_members=jnp.asarray(tables.par_members),
        par_port=jnp.asarray(tables.par_port),
        opt_parity=jnp.asarray(tables.opt_parity),
        opt_sibs=jnp.asarray(tables.opt_sibs),
        opt_n=jnp.asarray(tables.opt_n),
    )


class ReadPlan(NamedTuple):
    served: jnp.ndarray      # (N,) bool
    mode: jnp.ndarray        # (N,) int32
    port_busy: jnp.ndarray   # (n_ports+1,) bool (updated)
    n_served: jnp.ndarray    # () int32
    n_degraded: jnp.ndarray  # () int32 — served via parity/symbol reuse


class WritePlan(NamedTuple):
    served: jnp.ndarray       # (N,) bool
    mode: jnp.ndarray         # (N,) int32
    port_busy: jnp.ndarray
    fresh_loc: jnp.ndarray
    parity_valid: jnp.ndarray
    parked_count: jnp.ndarray
    rc_bank: jnp.ndarray
    rc_row: jnp.ndarray
    rc_valid: jnp.ndarray
    n_served: jnp.ndarray
    n_parked: jnp.ndarray
    n_rc_dropped: jnp.ndarray  # () int32 — recode requests lost to a full ring


class WriteSlots(NamedTuple):
    """The most cells one cycle's write datapath writes into each table:
    the static number of slots its writes into a table kept in rows of
    lanes are compacted into before they are scattered (``_compact``)."""

    banks_data: int
    parity_data: int
    golden: int
    fresh_loc: int
    parity_valid: int


def write_slots(p: MemParams) -> WriteSlots:
    """Each table's slot count, from the ports. A served write claims one
    port, and the write builder claims a port at most once a cycle (its
    ``busy`` flags; a down or stuttering port is busy from the start, so
    faults only lower the count). So a cycle serves at most ``n_ports``
    writes: at most ``n_data`` direct ones, each on its data bank's port,
    and at most ``n_ports - n_data`` parked ones, each on its physical
    parity bank's port.

    * ``banks_data``: the direct writes.
    * ``parity_data``: the parked writes (at least one slot, which an
      uncoded scheme leaves empty).
    * ``golden``, ``fresh_loc``: every served write; ``fresh_loc`` changes
      at no other cell.
    * ``parity_valid``: a direct write invalidates at most ``MAX_OPTS``
      parity cells (its bank's options at its row), a parked one the one
      cell it parks in."""
    n_par_ports = max(p.n_ports - p.n_data, 1)
    return WriteSlots(banks_data=p.n_data, parity_data=n_par_ports,
                      golden=p.n_ports, fresh_loc=p.n_ports,
                      parity_valid=p.n_data * MAX_OPTS + n_par_ports)


def _walk_bounds(cand_age, cand_valid):
    """Walk position of every candidate + trip bound covering every valid
    candidate.

    The walk visits candidates oldest first, ties in queue order (a stable
    sort); invalid slots sort to the back via an +inf key. ``rank[c]`` is
    candidate c's position, counted by comparison, so the tables stay in
    queue order and no permutation is gathered. The walk only needs to
    reach the last position holding a valid candidate (invalid ones are
    no-ops in the body, so skipping the tail is unobservable)."""
    n = cand_age.shape[0]
    key = jnp.where(cand_valid, cand_age, INT32_MAX)
    idx = jnp.arange(n, dtype=jnp.int32)
    before = ((key[None, :] < key[:, None])
              | ((key[None, :] == key[:, None])
                 & (idx[None, :] < idx[:, None])))
    rank = jnp.sum(before, axis=1, dtype=jnp.int32)
    return rank, jnp.max(jnp.where(cand_valid, rank, -1)) + 1


class _Walk:
    """Per-candidate fields of a greedy walk, side by side in one (N, C)
    int32 table.

    A trip reads its candidate's whole row with one masked reduction
    (``row``) and writes the walk's state back with elementwise selects
    over the table (``cols``, ``keys``), so its loop body holds no gather
    or scatter and compiles to a few fused kernels however many fields it
    tracks. ``keys`` holds, per column of a field given a key array, the
    bank or port that column tracks (-1 elsewhere): an update to one bank
    or port is a compare against it."""

    def __init__(self, fields, keys):
        n = next(iter(fields.values())).shape[0]
        self.spans, cols, kcols, o = {}, [], [], 0
        for name, a in fields.items():
            w = math.prod(a.shape[1:])
            self.spans[name] = (o, w, a.shape[1:], a.dtype)
            cols.append(a.reshape(n, w).astype(jnp.int32))
            kcols.append(jnp.broadcast_to(keys.get(name, -1),
                                          a.shape).reshape(n, w))
            o += w
        self.table = jnp.concatenate(cols, axis=1)
        self.keys = jnp.concatenate(kcols, axis=1)
        self._col = np.arange(o)   # a static layout: masks are constants

    def get(self, x, name):
        """Field ``name`` of the table (N, C) or of one row (C,)."""
        o, w, shape, dtype = self.spans[name]
        return x[..., o:o + w].reshape(x.shape[:-1] + shape).astype(dtype)

    def row(self, table, sel):
        """The fields of the row ``sel`` (a one-hot mask over the
        candidates) picks, by a masked reduction: no gather, even where the
        trip counter is batched."""
        r = jnp.sum(jnp.where(sel[:, None], table, 0), axis=0)
        return {name: self.get(r, name) for name in self.spans}

    def cols(self, *names):
        """(C,) mask of the columns of fields ``names``."""
        m = np.zeros(self._col.shape, bool)
        for name in names:
            o, w, _, _ = self.spans[name]
            m |= (self._col >= o) & (self._col < o + w)
        return m

    def spread(self, name, values):
        """(C,) vector holding ``values`` in the columns of ``name``."""
        o = self.spans[name][0]
        out = jnp.zeros(self._col.shape, jnp.int32)
        for j, v in enumerate(values):
            out = jnp.where(self._col == o + j, v, out)
        return out


def _first_min(scores):
    """(index, score) of the first smallest of a list of scalars: argmin
    unrolled into selects, so it fuses with the work around it."""
    act, best = jnp.int32(0), scores[0]
    for a, s in enumerate(scores[1:], 1):
        take = s < best
        act = jnp.where(take, a, act)
        best = jnp.where(take, s, best)
    return act, best


def _pick(values, k):
    """``values[k]`` for a static-length sequence of scalars and a traced
    ``k``, unrolled into selects."""
    out = values[0]
    for j in range(1, len(values)):
        out = jnp.where(k == j, values[j], out)
    return out


# Indexing by traced indices compiles to gathers and scatters, which a TPU
# runs element by element: on a v5e a batched gather of 3,200 elements took
# 25-38 us, and a scatter sorts its keys first. A one-hot lookup instead
# compares every index with every column, or contracts 0/1 matrices with
# small whole numbers (exact in float32): work that grows with the table's
# width. The small tables (queues, ports, code tables, region maps) are
# always indexed by one-hot. The tables a bank wide (``fresh_loc``,
# ``parity_valid``, the data) are indexed by one-hot up to
# ``state.ONEHOT_MAX_COLS`` columns; wider, the state keeps them in rows of
# 128 lanes (``state.bank_table``) and they are indexed by gather and
# scatter, where a one-hot would cost a cycle what the memory holds, not
# what its requests touch. Measured on one TPU v5e: a loop trip of 40
# points, each looking up (or writing) 80 cells of an (8, W) int32 table,
# in microseconds:
#
#       W     cell: one-hot / gather   write: one-hot / scatter
#     512              9.8 / 49.3               14.3 / 26.5
#   2,048             20.5 / 48.0               25.3 / 28.3
#   8,192             64.1 / 46.8               73.2 / 36.1
#  65,536            463.1 / 49.1              498.5 / 504.4
#
# The one-hot's cost is the table's width; the scatter's 504 us at 65,536
# is the relayout of the whole (8, W) array that a TPU scatter makes, which
# the rows of 128 lanes avoid (``core/state.py``). From a table in rows of
# lanes a gather costs what it reads (same loop, 65,536 columns: 80 cells
# a point 52 us, 640 cells 396 us), so ``_cell`` gathers the fewer cells
# of its two ways.


def _onehot(idx, n):
    """(..., n) float32 one-hot rows of ``idx`` (all zero out of range)."""
    return (idx[..., None] == jnp.arange(n)).astype(jnp.float32)


def _count(spec, *operands):
    """``einsum`` of one-hots and small whole numbers, exact in float32."""
    import jax

    return jnp.einsum(spec, *operands, precision=jax.lax.Precision.HIGHEST)


def _take(table, idx):
    """``table[idx]`` for a small table and integer ``idx`` of any shape,
    as indexing does it (a negative index counts from the end, the rest
    clamp), by compare and select over the table's rows."""
    size = table.shape[0]
    idx = jnp.clip(jnp.where(idx < 0, idx + size, idx), 0, size - 1)
    hit = idx[..., None] == jnp.arange(size)
    hit = hit.reshape(hit.shape + (1,) * (table.ndim - 1))
    return jnp.sum(jnp.where(hit, table, 0), axis=idx.ndim).astype(
        table.dtype)


def _col(table, cols, small=True):
    """``table[:, cols]`` of a stored bank table with the table's rows
    last: (..., banks), for in-range ``cols``. By gather from a table in
    rows of lanes, else by one-hot: a contraction where the values are
    ``small`` whole numbers, else compare and select."""
    # static: the stored form and a python flag
    if table.ndim == 3:
        # one cell a gathered element: a gather whose window spans the
        # banks has the compiler lay the whole table out again first
        return cells(table, jnp.arange(table.shape[0]), cols[..., None])
    hit = _onehot(cols, table.shape[1])
    if small:  # analysis: tracer-branch
        return _count("...r,xr->...x", hit,
                      table.astype(jnp.float32)).astype(table.dtype)
    return jnp.sum(jnp.where(hit[..., None, :] > 0, table, 0),
                   axis=-1).astype(table.dtype)


def _cell(table, rows, cols, small=True):
    """``table[rows, cols]`` of a stored bank table for in-range indices:
    the columns by ``_col`` and the row by compare and select, or, from a
    table in rows of lanes where that gathers fewer cells, by gather."""
    # static: the cells each way would gather, counted from the shapes
    n_cells = math.prod(jnp.broadcast_shapes(rows.shape, cols.shape))
    by_cell = table.ndim == 3 and n_cells <= cols.size * table.shape[0]
    if by_cell:  # analysis: tracer-branch
        return cells(table, rows, cols)
    at_cols = _col(table, cols, small)
    hit = rows[..., None] == jnp.arange(table.shape[0])
    return jnp.sum(jnp.where(hit, at_cols, 0), axis=-1).astype(table.dtype)


def _put(table, rows, cols, vals, mask, small=True):
    """``table`` with ``table[rows, cols] = vals`` where ``mask`` (indices
    in range; a cell written more than once gets equal values). By
    scatter into a table in rows of lanes, else by one-hot: a
    contraction where the values are ``small`` whole numbers, else
    compare and select."""
    rows, cols, vals, mask = (jnp.broadcast_to(a, mask.shape).reshape(-1)
                              for a in (rows, cols, vals, mask))
    if table.ndim == 3:
        return set_cells(table, jnp.where(mask, rows, table.shape[0]), cols,
                         vals.astype(table.dtype))
    on_row = _onehot(jnp.where(mask, rows, -1), table.shape[0])
    on_col = _onehot(cols, table.shape[1])
    if small:  # analysis: tracer-branch
        copies = _count("nx,nr->xr", on_row, on_col)
        total = _count("nx,nr->xr", on_row * vals[:, None], on_col)
        return jnp.where(copies > 0, total / jnp.maximum(copies, 1),
                         table).astype(table.dtype)
    hit = (on_row[:, :, None] * on_col[:, None, :]) > 0        # (n, x, r)
    new = jnp.max(jnp.where(hit, vals[:, None, None], jnp.iinfo(
        jnp.int32).min), axis=0)
    return jnp.where(jnp.any(hit, axis=0), new, table).astype(table.dtype)


def _compact(table, n_slots, mask, *cols):
    """The writes into ``table`` where ``mask`` holds, as (mask, each of
    ``cols``) to write from. Into a table kept in rows of lanes they are
    compacted, first to last, into ``n_slots`` slots: its scatter costs
    each update it is given, kept or dropped. An entry past the last slot
    is left out: the caller proves that there are none. A rank from a
    cumulative sum and compare and select over the (slots, entries) grid,
    since an index gather or scatter here would be such an op itself. A
    table indexed by one-hot, whose write costs its width, takes them as
    they are: there, on a v5e, the compactions cost a 512-row cycle more
    than the smaller writes saved."""
    # static: the stored form of the table
    if table.ndim != 3:
        return (mask,) + cols
    rank = jnp.cumsum(mask, dtype=jnp.int32) - 1
    on = mask & (rank == jnp.arange(n_slots, dtype=jnp.int32)[:, None])

    def of_slot(x):
        s = on.reshape(on.shape + (1,) * (x.ndim - 1))
        return jnp.sum(jnp.where(s, x[None], 0), axis=1).astype(x.dtype)

    return (jnp.any(on, axis=1),) + tuple(of_slot(x) for x in cols)


def build_read_pattern(
    p: MemParams,
    t: JTables,
    cand_bank: jnp.ndarray,
    cand_row: jnp.ndarray,
    cand_age: jnp.ndarray,
    cand_valid: jnp.ndarray,
    port_busy: jnp.ndarray,
    fresh_loc: jnp.ndarray,
    parity_valid: jnp.ndarray,
    region_slot: jnp.ndarray,
    rs_active=None,
) -> ReadPlan:
    import jax

    n = cand_bank.shape[0]
    rs = p.region_size
    rs_a = rs if rs_active is None else rs_active
    rank, n_trips = _walk_bounds(cand_age, cand_valid)
    nop = jnp.int32(p.n_ports)
    oob = jnp.int32(p.n_data)
    K = MAX_OPTS

    # ---- per-candidate tables, looked up once (read state is loop-invariant)
    b = jnp.maximum(cand_bank, 0)
    i = jnp.maximum(cand_row, 0)
    fl = _cell(fresh_loc, b, i)
    slot = _take(region_slot, i // rs_a)
    coded = slot >= 0
    pr = jnp.maximum(slot, 0) * rs + i % rs_a
    hold_port = _take(t.par_port, jnp.maximum(fl - 1, 0))
    # a negative port id (scheme with no parities) points the claim at the
    # dummy sink slot, where the lookup of the padded -1 lands
    hold_idx = jnp.where(hold_port < 0, nop, hold_port)
    optj = _take(t.opt_parity, b)             # (N, K)
    optjj = jnp.maximum(optj, 0)
    opt_pv = ((optj >= 0) & coded[:, None]
              & _cell(parity_valid, optjj, pr[:, None]))
    opt_pport = _take(t.par_port, optjj)
    opt_pport = jnp.where(opt_pport < 0, nop, opt_pport)
    sibs = _take(t.opt_sibs, b)
    s0, s1 = sibs[:, :, 0], sibs[:, :, 1]
    may_serve = cand_valid & (fl == 0)
    # The banks and ports a candidate's scoring looks up; the walk keeps
    # their state per candidate. ``have``: the bank of each column has the
    # candidate's row materialized this cycle (the chained-decode symbol
    # set); ``busy``: the port of each column is claimed. ``claim`` records
    # the ports each candidate claimed, for ``port_busy`` after the walk.
    sym_bank = jnp.concatenate([b[:, None], s0, s1], axis=1)      # (N, 1+2K)
    port_ix = jnp.concatenate([b[:, None], jnp.maximum(s0, 0),
                               jnp.maximum(s1, 0), opt_pport,
                               hold_idx[:, None]], axis=1)        # (N, 2+3K)
    w = _Walk(dict(
        b=b, i=i, may=may_serve, can_rd=cand_valid & (fl > 0), hold=hold_idx,
        opt_may=may_serve[:, None] & opt_pv, pport=opt_pport, s0=s0, s1=s1,
        have=jnp.zeros(sym_bank.shape, bool),
        busy=_take(port_busy, port_ix),
        mode=jnp.full((n,), MODE_UNSERVED, jnp.int32),
        claim=jnp.full((n, 4), nop, jnp.int32),
    ), keys=dict(have=sym_bank, busy=port_ix))

    def cond(carry):
        return carry[0] < n_trips

    def body(carry):
        k, table = carry
        sel = rank == k
        c = w.row(table, sel)
        h, bz = c["have"], c["busy"]
        s0r, s1r = c["s0"], c["s1"]                    # (K,)

        # --- score every action ------------------------------------------
        f_sym = c["may"] & h[0] & bool(p.coalesce)
        f_dir = c["may"] & ~bz[0]
        scores = [jnp.where(f_sym, 0, INF_SCORE),
                  jnp.where(f_dir, 3, INF_SCORE)]
        need0, need1 = [], []
        for j in range(K):
            sa0 = h[1 + j] & (s0r[j] >= 0)
            sa1 = h[1 + K + j] & (s1r[j] >= 0)
            ok0 = (s0r[j] < 0) | sa0 | ~bz[1 + j]
            ok1 = (s1r[j] < 0) | sa1 | ~bz[1 + K + j]
            need0.append((s0r[j] >= 0) & ~sa0)
            need1.append((s1r[j] >= 0) & ~sa1)
            feas = c["opt_may"][j] & ~bz[1 + 2 * K + j] & ok0 & ok1
            cost = (1 + need0[j].astype(jnp.int32)
                    + need1[j].astype(jnp.int32))
            scores.append(jnp.where(feas, 2 * cost, INF_SCORE))
        f_rd = c["can_rd"] & ~bz[1 + 3 * K]
        act, best = _first_min(scores + [jnp.where(f_rd, 2, INF_SCORE)])
        found = best < INF_SCORE

        is_dir = found & (act == 1)
        is_opt = found & (act >= 2) & (act < 2 + K)
        is_rd = found & (act == 2 + K)
        k_sel = jnp.clip(act - 2, 0, K - 1)
        need0_sel = is_opt & _pick(need0, k_sel)
        need1_sel = is_opt & _pick(need1, k_sel)
        sib0 = _pick([jnp.maximum(s0r[j], 0) for j in range(K)], k_sel)
        sib1 = _pick([jnp.maximum(s1r[j], 0) for j in range(K)], k_sel)

        # --- claim ports (every trip's masked claims land on the sink slot,
        # as the ref's do) and materialize symbols (true set semantics)
        p_dir = jnp.where(is_dir, c["b"], nop)
        p_par = jnp.where(is_opt, _pick([c["pport"][j] for j in range(K)],
                                        k_sel),
                          jnp.where(is_rd, c["hold"], nop))
        p_s0 = jnp.where(need0_sel, sib0, nop)
        p_s1 = jnp.where(need1_sel, sib1, nop)
        x_b = jnp.where(is_dir | is_opt, c["b"], oob)
        x_s0 = jnp.where(need0_sel, sib0, oob)
        x_s1 = jnp.where(need1_sel, sib1, oob)
        key = w.keys
        same_row = (w.get(table, "i") == c["i"])[:, None]
        hit = ((w.cols("have") & same_row
                & ((key == x_b) | (key == x_s0) | (key == x_s1)))
               | (w.cols("busy")
                  & ((key == p_dir) | (key == p_par) | (key == p_s0)
                     | (key == p_s1) | (key == nop))))
        table = jnp.where(hit, 1, table)
        table = jnp.where(
            w.cols("mode", "claim") & sel[:, None],
            w.spread("mode", [jnp.where(found, act, MODE_UNSERVED)])
            + w.spread("claim", [p_dir, p_par, p_s0, p_s1]), table)
        return k + 1, table

    _, table = jax.lax.while_loop(cond, body, (jnp.int32(0), w.table))
    # the claims land once, after the walk; the sink is marked busy even
    # when the walk never reaches a valid candidate, so its state is
    # deterministic for downstream consumers
    ports = jnp.arange(port_busy.shape[0], dtype=jnp.int32)
    port_busy = (port_busy
                 | jnp.any(w.get(table, "claim")[:, :, None] == ports,
                           axis=(0, 1))
                 | (ports == nop))
    mode = w.get(table, "mode")
    served = mode != MODE_UNSERVED
    n_served = jnp.sum(served).astype(jnp.int32)
    n_degraded = jnp.sum(
        served & ((mode == MODE_FROM_SYM) | ((mode >= MODE_OPT0) & (mode < MODE_REDIRECT)))
    ).astype(jnp.int32)
    return ReadPlan(served, mode, port_busy, n_served, n_degraded)


def build_write_pattern(
    p: MemParams,
    t: JTables,
    cand_bank: jnp.ndarray,
    cand_row: jnp.ndarray,
    cand_age: jnp.ndarray,
    cand_valid: jnp.ndarray,
    port_busy: jnp.ndarray,
    fresh_loc: jnp.ndarray,
    parity_valid: jnp.ndarray,
    region_slot: jnp.ndarray,
    parked_count: jnp.ndarray,
    rc_bank: jnp.ndarray,
    rc_row: jnp.ndarray,
    rc_valid: jnp.ndarray,
    rs_active=None,
    down=None,
) -> WritePlan:
    import jax

    n = cand_bank.shape[0]
    rs = p.region_size
    rs_a = rs if rs_active is None else rs_active
    rank, n_trips = _walk_bounds(cand_age, cand_valid)
    nop = jnp.int32(p.n_ports)
    K = MAX_OPTS

    # ---- per-candidate tables, looked up once ---------------------------
    b = jnp.maximum(cand_bank, 0)
    i = jnp.maximum(cand_row, 0)
    region = i // rs_a
    slot = _take(region_slot, region)
    coded = slot >= 0
    pr = jnp.maximum(slot, 0) * rs + i % rs_a
    optj = _take(t.opt_parity, b)             # (N, K)
    optjj = jnp.maximum(optj, 0)
    opt_pport = _take(t.par_port, optjj)
    opt_pport = jnp.where(opt_pport < 0, nop, opt_pport)
    mem = _take(t.par_members, optjj)         # (N, K, MAX_SIBS+1)
    memc = jnp.maximum(mem, 0)
    park_base = 2 + jnp.arange(K, dtype=jnp.int32)
    # ---- degraded-write mode (``down`` = currently-down data banks).
    # A candidate is *sticky* when its own bank is down or any parity
    # option covering it has a down member: its park stays parked (no
    # recode request) until the rebuild sweep drains it — retiring the park
    # early would rewrite a member bank and strand the down-covering
    # parities invalid, killing the down bank's degraded readability. The
    # scoring shift prefers (a) normal parks, (b) parks into parities
    # whose members are all alive, (c) parks into down-covering parities,
    # (d) a direct write (which invalidates EVERY covering parity row) —
    # strictly last for a sticky-but-alive bank. Sticky parks also waive
    # the recode-queue-space requirement (they don't enqueue).
    if down is None:
        sticky = jnp.zeros((n,), bool)
        dir_score = jnp.ones((n,), jnp.int32)
        park_score = jnp.broadcast_to(park_base, (n, K))
    else:
        opt_down = jnp.any((mem >= 0) & (mem != b[:, None, None])
                           & _take(down, memc), axis=2)      # (N, K)
        sticky = _take(down, b) | jnp.any(
            (optj >= 0) & coded[:, None] & opt_down, axis=1)
        dir_score = jnp.where(sticky, 2 + 2 * MAX_OPTS + 2, 1)
        park_score = park_base + jnp.where(opt_down, MAX_OPTS + 2, 0)
    # The walk's state, per candidate. ``fl``: freshness of its own cell
    # (column 0) and of its options' parity-group members at its row; a
    # write updates every copy of its cell, and copies of one cell stay
    # equal, so one scatter of column 0 writes ``fresh_loc`` back after the
    # walk. ``busy``: the port of each column is claimed. The recode ring
    # only fills during the walk, the t-th push into the t-th free slot;
    # ``pend``: the candidate's cell is in the ring. ``delta`` records each
    # candidate's change of its region's parked count, ``push`` the ordinal
    # of its push (-1: none).
    fl_bank = jnp.concatenate([b[:, None], mem.reshape(n, -1)], axis=1)
    port_ix = jnp.concatenate([b[:, None], opt_pport], axis=1)   # (N, 1+K)
    free = ~rc_valid
    n_free = jnp.sum(free).astype(jnp.int32)
    w = _Walk(dict(
        b=b, i=i, valid=cand_valid, dir_score=dir_score, sticky=sticky,
        need_rc_dir=coded & (_take(t.opt_n, b) > 0),
        park_possible=cand_valid[:, None] & (optj >= 0) & coded[:, None],
        pport=opt_pport, optjj=optjj, park_score=park_score, mem=mem,
        busy=_take(port_busy, port_ix),
        fl=_cell(fresh_loc, jnp.maximum(fl_bank, 0), i[:, None]),
        pend=jnp.any(rc_valid & (rc_bank == b[:, None])
                     & (rc_row == i[:, None]), axis=1),
        mode=jnp.full((n,), WMODE_UNSERVED, jnp.int32),
        delta=jnp.zeros((n,), jnp.int32), push=jnp.full((n,), -1, jnp.int32),
    ), keys=dict(busy=port_ix, fl=fl_bank, pend=b))

    def cond(carry):
        return carry[0] < n_trips

    def body(carry):
        k, table, n_ins, dropped = carry
        sel = rank == k
        c = w.row(table, sel)
        bz, flr, mem_c, optjj_c = c["busy"], c["fl"], c["mem"], c["optjj"]
        bc, ic, flc = c["b"], c["i"], flr[0]
        rc_space = n_ins < n_free

        # --- score direct + park options ---------------------------------
        f_dir = c["valid"] & ~bz[0]
        scores = [jnp.where(f_dir, c["dir_score"], INF_SCORE)]
        for j in range(K):
            occ = jnp.bool_(False)
            for s in range(mem_c.shape[1]):
                occ = occ | ((mem_c[j, s] >= 0) & (mem_c[j, s] != bc)
                             & (flr[1 + j * mem_c.shape[1] + s]
                                == optjj_c[j] + 1))
            park_feas = (c["park_possible"][j] & ~bz[1 + j] & ~occ
                         & (rc_space | c["sticky"]))
            scores.append(jnp.where(park_feas, c["park_score"][j], INF_SCORE))
        act, best = _first_min(scores)
        found = best < INF_SCORE
        is_dir = found & (act == 0)
        is_park = found & (act >= 1)
        k_sel = jnp.clip(act - 1, 0, K - 1)
        j_sel = _pick([optjj_c[j] for j in range(K)], k_sel)
        p_claim = jnp.where(is_dir, bc, jnp.where(
            is_park, _pick([c["pport"][j] for j in range(K)], k_sel), nop))

        # --- freshness bookkeeping ---------------------------------------
        was_parked = flc > 0
        new_fl = jnp.where(is_dir, 0, jnp.where(is_park, j_sel + 1, flc))
        d = (is_park.astype(jnp.int32) * (~was_parked).astype(jnp.int32)
             - is_dir.astype(jnp.int32) * was_parked.astype(jnp.int32))
        # recode request so freshness is eventually restored (a sticky park
        # stays parked — the rebuild sweep enqueues it once its down
        # parity-group member is recovering, see repro.faults.inject)
        need_rc = (is_dir & c["need_rc_dir"]) | (is_park & ~c["sticky"])
        dup = c["pend"]
        push = need_rc & ~dup & rc_space
        dropped = dropped + (need_rc & ~dup & ~rc_space).astype(jnp.int32)

        key = w.keys
        cell = (w.get(table, "i") == ic)[:, None] & (key == bc)
        table = jnp.where(
            w.cols("busy") & ((key == p_claim) | (key == nop))
            | (w.cols("pend") & cell & push), 1, table)
        table = jnp.where(w.cols("fl") & cell, new_fl, table)
        table = jnp.where(
            w.cols("mode", "delta", "push") & sel[:, None],
            w.spread("mode", [jnp.where(found, act, WMODE_UNSERVED)])
            + w.spread("delta", [d])
            + w.spread("push", [jnp.where(push, n_ins, -1)]), table)
        return k + 1, table, n_ins + push.astype(jnp.int32), dropped

    carry = (jnp.int32(0), w.table, jnp.int32(0), jnp.int32(0))
    _, table, _, dropped = jax.lax.while_loop(cond, body, carry)
    mode_w = w.get(table, "mode")

    # ---- apply the walk's writes once, after it --------------------------
    is_dir = mode_w == WMODE_DIRECT
    is_park = mode_w >= WMODE_PARK0
    opt_sel = (jnp.arange(K, dtype=jnp.int32)
               == (mode_w - WMODE_PARK0)[:, None])                 # (N, K)
    j_sel = jnp.sum(jnp.where(opt_sel, optjj, 0), axis=1)
    p_park = jnp.sum(jnp.where(opt_sel, opt_pport, 0), axis=1)
    claim = jnp.where(is_dir, b, jnp.where(is_park, p_park, nop))
    ports = jnp.arange(port_busy.shape[0], dtype=jnp.int32)
    port_busy = (port_busy | jnp.any(claim[:, None] == ports, axis=0)
                 | (ports == nop))                  # deterministic sink
    # a cell's freshness changes only where a write is served, and every
    # copy of a cell is equal: the served candidates write theirs back,
    # from as many slots as ``write_slots`` proves a cycle serves
    slots = write_slots(p)
    served = mode_w != WMODE_UNSERVED
    ok, b_s, i_s, fl_s = _compact(fresh_loc, slots.fresh_loc, served, b, i,
                                  w.get(table, "fl")[:, 0])
    fresh_loc = _put(fresh_loc, b_s, i_s, fl_s, ok)
    parked_count = parked_count + jnp.sum(
        jnp.where(region[:, None] == jnp.arange(parked_count.shape[0]),
                  w.get(table, "delta")[:, None], 0), axis=0)
    # parity invalidation
    inv = ((optj >= 0) & coded[:, None]
           & (is_dir[:, None]
              | (is_park[:, None] & (optjj == j_sel[:, None]))))
    ok, j_s, pr_s = _compact(parity_valid, slots.parity_valid,
                             inv.reshape(-1), optjj.reshape(-1),
                             jnp.broadcast_to(pr[:, None], inv.shape)
                             .reshape(-1))
    parity_valid = _put(parity_valid, j_s, pr_s, False, ok)
    # the t-th push of the walk fills the t-th free slot of the ring
    slot_rank = jnp.where(free, jnp.cumsum(free) - 1, -2)
    hit = slot_rank[:, None] == w.get(table, "push")[None, :]   # (cap, N)
    filled = jnp.any(hit, axis=1)
    rc_bank = jnp.where(filled, jnp.sum(jnp.where(hit, b, 0), axis=1),
                        rc_bank)
    rc_row = jnp.where(filled, jnp.sum(jnp.where(hit, i, 0), axis=1), rc_row)
    rc_valid = rc_valid | filled

    n_served = jnp.sum(served).astype(jnp.int32)
    n_parked = jnp.sum(is_park).astype(jnp.int32)
    return WritePlan(served, mode_w, port_busy, fresh_loc, parity_valid,
                     parked_count, rc_bank, rc_row, rc_valid, n_served,
                     n_parked, dropped)


def _rc_push(rc_bank, rc_row, rc_valid, b, i, do):
    """Push (b, i) into the recode ring unless present; returns ok flag.
    The first free slot is taken by a one-hot select, not a scatter."""
    dup = jnp.any(rc_valid & (rc_bank == b) & (rc_row == i))
    free = ~rc_valid
    has_free = jnp.any(free)
    first = jnp.arange(free.shape[0]) == jnp.argmax(free)  # first free slot
    put = first & do & ~dup & has_free
    rc_bank = jnp.where(put, b, rc_bank)
    rc_row = jnp.where(put, i, rc_row)
    rc_valid = rc_valid | put
    ok = dup | has_free
    return rc_bank, rc_row, rc_valid, ok
