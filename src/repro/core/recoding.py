"""ReCoding unit (paper §IV-D).

A ring of pending recode requests ``(bank, row)``. Every cycle, after the
pattern builders have claimed their ports, the unit retires up to
``recode_budget`` entries whose required ports are all idle. Retiring an
entry for ``(b, i)``:

  * if the fresh value is parked in parity ``j`` (``fresh_loc == j+1``),
    reads it from ``j``'s port and writes it back to data bank ``b``;
  * re-computes every stale parity covering ``b`` at row ``i`` by reading all
    member data banks and writing the parity banks;
  * restores ``fresh_loc = 0`` and ``parity_valid = True``.

All port charges for one entry land in a single cycle (the paper does not
specify the recode micro-schedule; this charges the same port-cycles).
Entries whose region is currently uncoded are dropped — nothing to restore
(region eviction is blocked while any row is parked, see dynamic.py).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.codes import MAX_OPTS, MAX_SIBS
from repro.core.controller import JTables, _cell, _col, _put, _take
from repro.core.state import MemParams


class RecodeOut(NamedTuple):
    port_busy: jnp.ndarray
    fresh_loc: jnp.ndarray
    parity_valid: jnp.ndarray
    parked_count: jnp.ndarray
    rc_valid: jnp.ndarray
    banks_data: jnp.ndarray
    parity_data: jnp.ndarray
    n_recoded: jnp.ndarray


def recode_step(
    p: MemParams,
    t: JTables,
    port_busy: jnp.ndarray,
    fresh_loc: jnp.ndarray,
    parity_valid: jnp.ndarray,
    parked_count: jnp.ndarray,
    rc_bank: jnp.ndarray,
    rc_row: jnp.ndarray,
    rc_valid: jnp.ndarray,
    region_slot: jnp.ndarray,
    banks_data: jnp.ndarray,
    parity_data: jnp.ndarray,
    rs_active=None,
    down=None,
) -> RecodeOut:
    """Retire up to ``recode_budget`` ring entries whose ports are all idle.

    Vectorized as a *cursor walk*: only retirements mutate shared state
    (moot removals clear just the entry's own slot), so the sequential scan
    collapses to at most ``recode_budget + 1`` trips. Each trip evaluates
    every remaining entry's work set and port needs in parallel under the
    current state, retires the first feasible one past the cursor, and
    removes the moot entries the scan passed over on the way (their view is
    unchanged within a trip — nothing between two retirements mutates
    state). Retirement order, port charges, budget accounting and the ring
    left behind are bit-identical to a sequential scan — enforced against
    the golden model's (``repro.oracle.recode_step``) by
    tests/test_conformance.py; an empty or workless ring costs one trip.

    The walk carries only what its decisions read, per entry: the
    freshness of every bank at the entry's row and the validity of each
    of its options' parities at its parity row, looked up once before it
    (``_col``, ``_cell``). A
    retirement updates the copies of the cells it writes, by compare and
    select, and records itself; the data it moves (the restored row, the
    recomputed parities, which decide nothing) and the state it leaves are
    written once after the walk, in retirement order. So the loop body
    holds no gather or scatter and carries nothing a bank wide.

    ``down`` (fault injection, repro.faults): hard-down data banks. A
    parity recompute that would read a hard-down member is *blocked* (the
    bank's stored rows are unreadable) — on a parked retire the blocked
    parity is invalidated rather than recomputed, exactly like the
    member-parked blocking above, so a dead bank's covering parities never
    re-validate with unreadable inputs. Entries whose OWN bank is
    hard-down become moot and are dropped — they could otherwise pin the
    ring forever; the rebuild sweep re-enqueues their cells once the bank
    recovers.
    """
    rs = p.region_size
    rs_a = rs if rs_active is None else rs_active
    cap = rc_valid.shape[0]
    n_rec = p.recode_budget
    b = jnp.maximum(rc_bank, 0)                 # (E,)
    i = jnp.maximum(rc_row, 0)
    region = i // rs_a
    slot = _take(region_slot, region)
    coded = slot >= 0
    pr = jnp.maximum(slot, 0) * rs + i % rs_a
    optj = _take(t.opt_parity, b)               # (E, K)
    optjj = jnp.maximum(optj, 0)
    opt_pport = _take(t.par_port, optjj)
    mem = _take(t.par_members, optjj)           # (E, K, MAX_SIBS+1)
    memc = jnp.maximum(mem, 0)
    epos = jnp.arange(cap, dtype=jnp.int32)
    banks = jnp.arange(p.n_data, dtype=jnp.int32)
    pars = jnp.arange(p.n_parities, dtype=jnp.int32)
    ports = jnp.arange(port_busy.shape[0], dtype=jnp.int32)
    nsink = jnp.int32(p.n_ports)     # masked-index slot: never busy/claimed
    on_b = b[:, None] == banks                                  # (E, n_data)
    on_mem = memc[..., None] == banks                     # (E, K, S, n_data)
    on_opt = optjj[..., None] == pars                        # (E, K, n_par)
    if down is not None:
        # fault-blocking is loop-invariant: down membership doesn't change
        # within a cycle
        blocked_f = jnp.any((mem >= 0) & (mem != b[:, None, None])
                            & _take(down, memc), axis=2)     # (E, K)
        self_down = _take(down, b)                           # (E,)

    def cond(carry):
        cursor, budget = carry[0], carry[1]
        return (budget > 0) & (cursor < cap)

    def body(carry):
        (cursor, budget, port_busy, fl_row, pv, rc_valid, rec_e,
         rec_do, rec_inv, rec_fl) = carry
        # ---- per-entry work set under the current state ------------------
        fl = jnp.sum(jnp.where(on_b, fl_row, 0), axis=1)
        parked = fl > 0
        holder = jnp.maximum(fl - 1, 0)
        mem_fl = jnp.sum(jnp.where(on_mem, fl_row[:, None, None, :], 0),
                         axis=3)                               # (E, K, S)
        blocked = jnp.any(
            (mem >= 0) & (mem != b[:, None, None])
            & (mem_fl == optjj[:, :, None] + 1), axis=2)         # (E, K)
        if down is not None:
            blocked = blocked | blocked_f
        need = (optj >= 0) & coded[:, None] & (~pv | parked[:, None])
        recompute = need & ~blocked
        blocked_l = need & blocked
        has_work = parked | jnp.any(recompute, axis=1)
        if down is not None:
            has_work = has_work & ~self_down
        pending = rc_valid & (epos > cursor)
        work = pending & coded & has_work
        moot = pending & ~(coded & has_work)

        # needed ports as an (E, 2 + K + K*(MAX_SIBS+1)) index matrix;
        # masked entries point at the never-busy sink slot
        rc_k = recompute & work[:, None]
        needed_idx = jnp.concatenate([
            jnp.where(work, b, nsink)[:, None],
            jnp.where(work & parked, _take(t.par_port, holder),
                      nsink)[:, None],
            jnp.where(rc_k, opt_pport, nsink),
            jnp.where(rc_k[:, :, None] & (mem >= 0), memc,
                      nsink).reshape(cap, -1),
        ], axis=1)
        busy = port_busy & (ports < p.n_ports)
        tf = work & ~jnp.any((needed_idx[..., None] == ports) & busy,
                             axis=(1, 2))

        # ---- retire the first feasible entry past the cursor -------------
        any_tf = jnp.any(tf)
        e = jnp.argmax(tf).astype(jnp.int32)     # first True (0 if none)
        sel = (epos == e) & any_tf
        seg_end = jnp.where(any_tf, e, cap)
        # moot entries the scan walked past are dropped (budget still > 0
        # at their turn — cond guarantees it, and nothing in the segment
        # between two retirements mutates their inputs)
        rc_valid = rc_valid & ~(moot & (epos < seg_end)) & ~sel

        def of_e(x):
            """Entry ``e``'s row of a per-entry array (zeros if none)."""
            s = sel.reshape(sel.shape + (1,) * (x.ndim - 1))
            return jnp.sum(jnp.where(s, x, 0), axis=0).astype(x.dtype)

        port_busy = port_busy | (jnp.any(
            of_e(needed_idx)[:, None] == ports, axis=0)
            & (ports < p.n_ports) & any_tf)
        do_k = of_e(recompute)                             # (K,)
        inv_k = of_e(blocked_l) & of_e(parked)
        # the cells it writes, in every entry's copies: its own freshness,
        # and the validity of the parities it recomputes or invalidates
        fl_row = jnp.where((i[:, None] == of_e(i)) & (banks == of_e(b))
                           & any_tf, 0, fl_row)
        on_k = of_e(on_opt)                                # (K, n_par)
        wrote = jnp.any(on_k & (do_k | inv_k)[:, None], axis=0)
        now = jnp.any(on_k & do_k[:, None], axis=0)
        pv = jnp.where((pr == of_e(pr))[:, None]
                       & jnp.any(on_opt & wrote, axis=2),
                       jnp.any(on_opt & now, axis=2), pv)
        at = (jnp.arange(n_rec) == p.recode_budget - budget) & any_tf
        rec_e = jnp.where(at, e, rec_e)
        rec_do = jnp.where(at[:, None], do_k, rec_do)
        rec_inv = jnp.where(at[:, None], inv_k, rec_inv)
        rec_fl = jnp.where(at, of_e(fl), rec_fl)

        cursor = jnp.where(any_tf, e, jnp.int32(cap))
        budget = budget - any_tf.astype(jnp.int32)
        return (cursor, budget, port_busy, fl_row, pv, rc_valid, rec_e,
                rec_do, rec_inv, rec_fl)

    no_k = jnp.zeros((n_rec, MAX_OPTS), bool)
    carry = (jnp.int32(-1), jnp.int32(p.recode_budget), port_busy,
             _col(fresh_loc, i), _cell(parity_valid, optjj, pr[:, None]),
             rc_valid,
             jnp.full((n_rec,), -1, jnp.int32), no_k, no_k,
             jnp.zeros((n_rec,), jnp.int32))
    (_, budget, port_busy, _, _, rc_valid, rec_e, rec_do, rec_inv,
     rec_fl) = jax.lax.while_loop(cond, body, carry)

    # ---- the retirements' writes, in retirement order ---------------------
    on_rec = rec_e[:, None] == epos                            # (R, E)

    def of_rec(x):
        """Each retirement's row of a per-entry array."""
        s = on_rec.reshape(on_rec.shape + (1,) * (x.ndim - 1))
        return jnp.sum(jnp.where(s, x[None], 0), axis=1).astype(x.dtype)

    done = rec_e >= 0
    rb, ri, rpr, rj, rmem = (of_rec(x) for x in (b, i, pr, optjj, mem))
    r_parked = done & (rec_fl > 0)
    # a parked row comes back from its parity row, which no earlier
    # retirement this cycle recomputed (the parked value blocks it)
    restored = _cell(parity_data, jnp.maximum(rec_fl - 1, 0), rpr,
                     small=False)
    # each member's value as the retirement saw it: restored by an earlier
    # (or this) retirement of the same cell, else as stored
    earlier = jnp.arange(n_rec)[None, :] <= jnp.arange(n_rec)[:, None]
    by = (earlier[:, :, None] & r_parked[None, :, None]
          & (rb[None, :, None] == banks)
          & (ri[None, :, None] == ri[:, None, None]))       # (R, R, n_data)
    seen = jnp.where(jnp.any(by, axis=1),
                     jnp.sum(jnp.where(by, restored[None, :, None], 0),
                             axis=1),
                     _col(banks_data, ri, small=False))      # (R, n_data)
    at_mem = jnp.maximum(rmem, 0)[..., None] == banks       # (R, K, S, n)
    member = jnp.sum(jnp.where(at_mem, seen[:, None, None, :], 0), axis=3)
    val = jnp.zeros(rj.shape, jnp.int32)
    for mm in range(MAX_SIBS + 1):
        val = val ^ jnp.where(rmem[:, :, mm] >= 0, member[:, :, mm], 0)

    def last(mask):
        """``mask`` less the writes a later retirement's write in ``mask``
        to the same parity cell overrides."""
        cell = ((rj[:, :, None, None] == rj[None, None])
                & (rpr[:, None, None, None] == rpr[None, None, :, None]))
        later = jnp.arange(n_rec)[None, :] > jnp.arange(n_rec)[:, None]
        return mask & ~jnp.any(cell & later[:, None, :, None]
                               & mask[None, None], axis=(2, 3))

    rows = rpr[:, None]
    parity_data = _put(parity_data, rj, rows, val, last(rec_do), small=False)
    parity_valid = _put(parity_valid, rj, rows, rec_do,
                        last(rec_do | rec_inv))
    banks_data = _put(banks_data, rb, ri, restored, r_parked, small=False)
    fresh_loc = _put(fresh_loc, rb, ri, jnp.int32(0), done)
    parked_count = parked_count - jnp.sum(
        r_parked[:, None]
        & (of_rec(region)[:, None] == jnp.arange(parked_count.shape[0])),
        axis=0, dtype=jnp.int32)
    return RecodeOut(port_busy, fresh_loc, parity_valid, parked_count,
                     rc_valid, banks_data, parity_data,
                     jnp.int32(p.recode_budget) - budget)
