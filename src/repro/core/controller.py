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
    once per call: each candidate's coded address by ``locate`` (the one
    derivation of a request's region, parity row and parity options,
    shared by every unit of the cycle), the bank tables' cells by
    ``state.bank_cell`` (one-hot, or gather where the state keeps a bank
    in rows of lanes: ``core/state.py`` alone knows the two forms).
    Freshness is looked up over a cell's neighbourhood alone
    (``JTables.near``: its own bank, then every other member of its
    options' parities; 4 banks under schemes I and II, up to 7 under
    scheme III, 1 uncoded), not over every bank at its row: no decision
    reads another bank's freshness, and a gather costs what it reads
    (``fresh_cells``). A
    trip picks its candidate's row with one masked reduction and updates
    every row by compare-and-select, so the loop body holds no gather or
    scatter. Under the sweep engine's ``vmap`` the trip counter is
    batched, and every indexed read or write inside the loop would become
    a batched gather or scatter: an op that does not fuse, whose launch a
    trip pays each time. The write walk's write-only state
    (``fresh_loc``, ``parity_valid``, ``parked_count``, the recode ring)
    is recorded per candidate and applied once after it
    (``state.bank_put``, given as many slots as the cycle has ports,
    ``write_slots``). Each plan carries the address it served (the parity
    and row, and a read's siblings), so the datapath reads and writes the
    data values there and looks nothing up again.

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
allocation, see ``state.active_geometry``) and pass it to ``locate``.
``None`` (the default) means the allocation is the geometry.
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

from repro.core.codes import MAX_OPTS, CodeTables
from repro.core.state import MemParams, bank_cell, bank_put, in_lanes, take

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
    near: jnp.ndarray          # (n_data, U) each bank's neighbourhood


def _neighbourhoods(tables: CodeTables) -> np.ndarray:
    """(n_data, U) int32: row b holds bank b, then every other member of
    b's options' parities in ascending order, -1 padded to U, the widest
    neighbourhood of the code. A cell's freshness decides nothing about
    another cell of its row outside these banks."""
    near = []
    for b in range(tables.n_data):
        opts = tables.opt_parity[b]
        mem = tables.par_members[opts[opts >= 0]]
        near.append([b] + sorted(set(mem[mem >= 0].tolist()) - {b}))
    u = max(map(len, near))
    return np.array([r + [-1] * (u - len(r)) for r in near], np.int32)


def jtables(tables: CodeTables) -> JTables:
    return JTables(
        par_members=jnp.asarray(tables.par_members),
        par_port=jnp.asarray(tables.par_port),
        opt_parity=jnp.asarray(tables.opt_parity),
        opt_sibs=jnp.asarray(tables.opt_sibs),
        opt_n=jnp.asarray(tables.opt_n),
        near=jnp.asarray(_neighbourhoods(tables)),
    )


class Loc(NamedTuple):
    """Where requests' cells live in the coded memory (``locate``), each
    field shaped as the requests (``...``)."""

    b: jnp.ndarray       # (...) data bank (clamped to >= 0)
    i: jnp.ndarray       # (...) row (clamped to >= 0)
    region: jnp.ndarray  # (...) region id
    slot: jnp.ndarray    # (...) the region's parity slot, -1 = uncoded
    coded: jnp.ndarray   # (...) bool, slot >= 0
    pr: jnp.ndarray      # (...) parity row
    optj: jnp.ndarray    # (..., K) the bank's options' parities, -1 pad
    optjj: jnp.ndarray   # (..., K) optj clamped to >= 0
    pport: jnp.ndarray   # (..., K) their ports; the sink (n_ports) where
                         # none
    sibs: jnp.ndarray    # (..., K, MAX_SIBS) their sibling banks, -1 pad
    mem: jnp.ndarray     # (..., K, MAX_SIBS+1) their members, -1 pad
    near: jnp.ndarray    # (..., U) the bank's neighbourhood (``b`` first),
                         # -1 pad


def locate(p: MemParams, t: JTables, region_slot, bank, row,
           rs_a=None) -> Loc:
    """The coded address of requests to (``bank``, ``row``): the one
    derivation every unit of the cycle uses. ``rs_a`` is the point's own
    region size (``state.active_geometry``; ``None``: the allocation's).
    Regions are counted at ``rs_a``; a parity row keeps the allocated
    ``p.region_size`` stride, so padded slots never alias. The lookups are
    by compare and select (``take``); a field a caller does not use is
    dead code under ``jit``."""
    rs = p.region_size
    rs_a = rs if rs_a is None else rs_a
    b = jnp.maximum(bank, 0)
    i = jnp.maximum(row, 0)
    region = i // rs_a
    slot = take(region_slot, region)
    optj = take(t.opt_parity, b)
    optjj = jnp.maximum(optj, 0)
    pport = take(t.par_port, optjj)
    # a negative port id (scheme with no parities) points a claim at the
    # dummy sink slot, where the lookup of the padded -1 lands
    return Loc(b=b, i=i, region=region, slot=slot, coded=slot >= 0,
               pr=jnp.maximum(slot, 0) * rs + i % rs_a, optj=optj,
               optjj=optjj, pport=jnp.where(pport < 0, p.n_ports, pport),
               sibs=take(t.opt_sibs, b), mem=take(t.par_members, optjj),
               near=take(t.near, b))


class ReadPlan(NamedTuple):
    served: jnp.ndarray      # (N,) bool
    mode: jnp.ndarray        # (N,) int32
    port_busy: jnp.ndarray   # (n_ports+1,) bool (updated)
    n_served: jnp.ndarray    # () int32
    n_degraded: jnp.ndarray  # () int32 — served via parity/symbol reuse
    # the served address the read datapath decodes from
    par: jnp.ndarray         # (N,) int32 parity read: the chosen option's
                             # (OPT) or the holder of a parked value
                             # (REDIRECT); -1 where none
    prow: jnp.ndarray        # (N,) int32 parity row of the candidate's row
    sibs: jnp.ndarray        # (N, MAX_SIBS) int32 sibling banks XORed in
                             # (OPT); -1 where none


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
    # the served address the write datapath commits to
    par: jnp.ndarray          # (N,) int32 parity parked into (PARK); -1 else
    prow: jnp.ndarray         # (N,) int32 parity row of the candidate's row


class WriteSlots(NamedTuple):
    """The most cells one cycle's write datapath writes into each table:
    the static number of slots its writes into a table kept in rows of
    lanes are compacted into before they are scattered
    (``state.set_cells``)."""

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


def fresh_cells(p: MemParams, t: JTables) -> int:
    """The ``fresh_loc`` cells a point's read builder, write builder and
    ReCoding unit gather in a cycle: each candidate's own cell, and each
    candidate's and each ring entry's neighbourhood at its row
    (``JTables.near``, U banks). ``state.bank_cell`` gathers these by cell,
    since a neighbourhood is never wider than the banks. 0 where the table
    is read by one-hot (``state.in_lanes``)."""
    if not in_lanes(p.n_rows):
        return 0
    n, u = p.n_data * p.queue_depth, t.near.shape[1]
    return n + n * u + p.recode_cap * u


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
        better = s < best
        act = jnp.where(better, a, act)
        best = jnp.where(better, s, best)
    return act, best


def _pick(values, k):
    """``values[k]`` for a static-length sequence of scalars and a traced
    ``k``, unrolled into selects."""
    out = values[0]
    for j in range(1, len(values)):
        out = jnp.where(k == j, values[j], out)
    return out


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
    rank, n_trips = _walk_bounds(cand_age, cand_valid)
    nop = jnp.int32(p.n_ports)
    oob = jnp.int32(p.n_data)
    K = MAX_OPTS

    # ---- per-candidate tables, looked up once (read state is loop-invariant)
    loc = locate(p, t, region_slot, cand_bank, cand_row, rs_active)
    b, i, optj, opt_pport = loc.b, loc.i, loc.optj, loc.pport
    fl = bank_cell(fresh_loc, b, i)
    hold_port = take(t.par_port, jnp.maximum(fl - 1, 0))
    hold_idx = jnp.where(hold_port < 0, nop, hold_port)
    opt_pv = ((optj >= 0) & loc.coded[:, None]
              & bank_cell(parity_valid, loc.optjj, loc.pr[:, None]))
    s0, s1 = loc.sibs[:, :, 0], loc.sibs[:, :, 1]
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
        busy=take(port_busy, port_ix),
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
    # the address each served read decodes from: its chosen option's
    # parity and siblings, or the parity holding its parked value
    opt_sel = (jnp.arange(K, dtype=jnp.int32)
               == (mode - MODE_OPT0)[:, None])                     # (N, K)
    is_opt = jnp.any(opt_sel, axis=1)
    par = jnp.where(mode == MODE_REDIRECT, fl - 1, jnp.where(
        is_opt, jnp.sum(jnp.where(opt_sel, optj, 0), axis=1), -1))
    sibs = jnp.where(is_opt[:, None], jnp.sum(
        jnp.where(opt_sel[:, :, None], loc.sibs, 0), axis=1), -1)
    return ReadPlan(served, mode, port_busy, n_served, n_degraded, par,
                    loc.pr, sibs)


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
    rank, n_trips = _walk_bounds(cand_age, cand_valid)
    nop = jnp.int32(p.n_ports)
    K = MAX_OPTS

    # ---- per-candidate tables, looked up once ---------------------------
    loc = locate(p, t, region_slot, cand_bank, cand_row, rs_active)
    b, i, coded, pr = loc.b, loc.i, loc.coded, loc.pr
    optj, optjj, opt_pport, mem = loc.optj, loc.optjj, loc.pport, loc.mem
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
                           & take(down, memc), axis=2)       # (N, K)
        sticky = take(down, b) | jnp.any(
            (optj >= 0) & coded[:, None] & opt_down, axis=1)
        dir_score = jnp.where(sticky, 2 + 2 * MAX_OPTS + 2, 1)
        park_score = park_base + jnp.where(opt_down, MAX_OPTS + 2, 0)
    # The walk's state, per candidate. ``fl``: freshness at its row of the
    # banks of its neighbourhood (``near``: its own cell in column 0, then
    # every other member of its options' parities, -1 padded); a write
    # updates every copy of its cell, and copies of one cell stay equal, so
    # one scatter of column 0 writes ``fresh_loc`` back after the walk.
    # ``busy``: the port of each column is claimed. The recode ring
    # only fills during the walk, the t-th push into the t-th free slot;
    # ``pend``: the candidate's cell is in the ring. ``delta`` records each
    # candidate's change of its region's parked count, ``push`` the ordinal
    # of its push (-1: none).
    port_ix = jnp.concatenate([b[:, None], opt_pport], axis=1)   # (N, 1+K)
    free = ~rc_valid
    n_free = jnp.sum(free).astype(jnp.int32)
    w = _Walk(dict(
        b=b, i=i, valid=cand_valid, dir_score=dir_score, sticky=sticky,
        need_rc_dir=coded & (take(t.opt_n, b) > 0),
        park_possible=cand_valid[:, None] & (optj >= 0) & coded[:, None],
        pport=opt_pport, optjj=optjj, park_score=park_score, mem=mem,
        busy=take(port_busy, port_ix),
        fl=bank_cell(fresh_loc, jnp.maximum(loc.near, 0), i[:, None]),
        pend=jnp.any(rc_valid & (rc_bank == b[:, None])
                     & (rc_row == i[:, None]), axis=1),
        mode=jnp.full((n,), WMODE_UNSERVED, jnp.int32),
        delta=jnp.zeros((n,), jnp.int32), push=jnp.full((n,), -1, jnp.int32),
    ), keys=dict(busy=port_ix, fl=loc.near, pend=b))

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
        # an option is occupied where another member's value is parked in
        # it; a member's freshness is the ``fl`` column keyed by its bank
        near_c = take(t.near, bc)
        f_dir = c["valid"] & ~bz[0]
        scores = [jnp.where(f_dir, c["dir_score"], INF_SCORE)]
        for j in range(K):
            occ = jnp.bool_(False)
            for m in mem_c[j]:
                fl_m = jnp.sum(jnp.where(near_c == m, flr, 0))
                occ = occ | ((m >= 0) & (m != bc) & (fl_m == optjj_c[j] + 1))
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
    fresh_loc = bank_put(fresh_loc, b, i, w.get(table, "fl")[:, 0], served,
                         n_slots=slots.fresh_loc)
    parked_count = parked_count + jnp.sum(
        jnp.where(loc.region[:, None] == jnp.arange(parked_count.shape[0]),
                  w.get(table, "delta")[:, None], 0), axis=0)
    # parity invalidation
    inv = ((optj >= 0) & coded[:, None]
           & (is_dir[:, None]
              | (is_park[:, None] & (optjj == j_sel[:, None]))))
    parity_valid = bank_put(parity_valid, optjj, pr[:, None], False, inv,
                            n_slots=slots.parity_valid)
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
                     n_parked, dropped, jnp.where(is_park, j_sel, -1), pr)
