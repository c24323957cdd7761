"""Device-side fault hooks for ``CodedMemorySystem.cycle_fn``.

Three pieces, called in cycle order (all behind the static
``MemParams.faults`` flag, so the faults-off program is untouched):

1. ``drop_unservable`` — *fail-fast semantics*. A queued request that can
   never be served under the current hard failures is dropped and counted
   (``unserved_reads`` / ``lost_writes``) instead of occupying its queue
   slot forever: a read of a hard-down bank whose fresh value is in-bank
   and which no valid parity option can decode (every option is invalid or
   needs another hard-down sibling), and a write to a hard-down bank with
   no parity coverage to park into. Deliberately *non-speculative*: a
   hard-down bank with a recovery scheduled in the future still fails its
   requests fast — the controller doesn't model "wait for repair" QoS (see
   docs/faults.md). Rebuilding banks are exempt (service is imminent).
2. Port seeding — a down bank's data port reads busy to both pattern
   builders; stuttering ports likewise (done inline in ``cycle_fn``).
3. ``rebuild_scan`` — *online rebuild*. While any bank is rebuilding, a
   flat cursor sweeps every (bank, row) cell at ``recode_budget`` cells
   per cycle, pushing cells that are parked elsewhere or have a stale
   covering parity into the recode ring; the ReCoding unit then restores /
   recomputes them under its normal port and budget discipline (with the
   rebuilding bank's own port granted back to it). The bank rejoins —
   ``rebuilt`` latches, clearing ``down`` — only when the sweep has
   finished and no restorable work remains anywhere.

Every rule here is re-derived sequentially by the NumPy golden model
(``repro.oracle.model``) and enforced bit-exactly by the chaos-conformance
suite (tests/test_faults.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.controller import _rc_push
from repro.core.state import cells
from repro.faults.plan import FaultState, NEVER


def drop_unservable(p, t, down_hard, rq_row, rq_valid, wq_row, wq_valid,
                    fresh_loc, parity_valid, region_slot, rs_active):
    """Clear queue slots whose requests are unservable under ``down_hard``.

    Returns ``(rq_valid, wq_valid, n_unserved, n_lost)``. Pure per-cell
    predicate (no cross-candidate interaction), so the vectorized form is
    trivially order-independent and the oracle's loop matches it exactly.
    """
    rs = p.region_size
    dq = p.queue_depth
    cb = jnp.repeat(jnp.arange(p.n_data, dtype=jnp.int32), dq)

    def read_side(rows, valid):
        i = jnp.maximum(rows.reshape(-1), 0)
        slot = region_slot[i // rs_active]
        coded = slot >= 0
        pr = jnp.maximum(slot, 0) * rs + i % rs_active
        optj = t.opt_parity[cb]                              # (N, K)
        optjj = jnp.maximum(optj, 0)
        opt_ok = ((optj >= 0) & coded[:, None]
                  & cells(parity_valid, optjj, pr[:, None]))
        sibs = t.opt_sibs[cb]                                # (N, K, S)
        sib_dead = jnp.any((sibs >= 0) & down_hard[jnp.maximum(sibs, 0)],
                           axis=2)
        return valid.reshape(-1), i, coded, opt_ok & ~sib_dead

    rv, ri, _, viable = read_side(rq_row, rq_valid)
    drop_r = (rv & down_hard[cb] & (cells(fresh_loc, cb, ri) == 0)
              & ~jnp.any(viable, axis=1))

    wv = wq_valid.reshape(-1)
    wi = jnp.maximum(wq_row.reshape(-1), 0)
    w_coded = region_slot[wi // rs_active] >= 0
    drop_w = wv & down_hard[cb] & (~w_coded | (t.opt_n[cb] == 0))

    return (rq_valid & ~drop_r.reshape(p.n_data, dq),
            wq_valid & ~drop_w.reshape(p.n_data, dq),
            jnp.sum(drop_r).astype(jnp.int32),
            jnp.sum(drop_w).astype(jnp.int32))


def rebuild_scan(p, t, fault: FaultState, cycle, rebuilding, down_hard,
                 fresh_loc, parity_valid, region_slot, rc_bank, rc_row,
                 rc_valid, rs_active, nr_active):
    """Advance the online-rebuild sweep; latch ``rebuilt`` on completion.

    Runs after the ReCoding unit (pushes become retirable next cycle). The
    cursor walks cells ``0 .. n_data*n_rows`` at ``recode_budget`` cells
    per cycle and resets to 0 whenever a bank's recovery begins, so a
    recovery arriving mid-sweep always gets a full pass. A cell is pushed
    when its fresh value is parked elsewhere or any covering parity is
    stale (reads of never-rewritten rows must not wait on the bank's
    direct port forever); the push stalls the cursor when the ring is
    momentarily full. Cells outside the point's active geometry are
    untouched by construction and skipped. Completion requires the sweep
    done, the ring drained, and no parked cell left on any bank that is
    not still hard-down (a hard-down bank's parked rows are *its* future
    rebuild's work, not this one's).
    """
    total = p.n_data * p.n_rows
    any_rb = jnp.any(rebuilding)
    newly = jnp.any((fault.recover_at == cycle) & (fault.fail_at <= cycle)
                    & ~fault.rebuilt)
    ptr = jnp.where(newly, 0, fault.rebuild_ptr)
    rs = p.region_size

    def body(_, carry):
        ptr, rc_bank, rc_row, rc_valid = carry
        cell = jnp.minimum(ptr, total - 1)
        x = cell // p.n_rows
        i = cell % p.n_rows
        in_range = any_rb & (ptr < total)
        region = i // rs_active
        in_geom = (region < nr_active) & (i % rs_active < rs_active)
        slot = region_slot[jnp.minimum(region, region_slot.shape[0] - 1)]
        coded = slot >= 0
        pr = jnp.maximum(slot, 0) * rs + i % rs_active
        optj = t.opt_parity[x]
        stale = jnp.any((optj >= 0) & coded
                        & ~cells(parity_valid, jnp.maximum(optj, 0), pr))
        need = in_range & in_geom & ((cells(fresh_loc, x, i) > 0) | stale)
        rc_bank, rc_row, rc_valid, ok = _rc_push(
            rc_bank, rc_row, rc_valid, x, i, need)
        advance = in_range & (~need | ok)
        return ptr + advance.astype(jnp.int32), rc_bank, rc_row, rc_valid

    ptr, rc_bank, rc_row, rc_valid = jax.lax.fori_loop(
        0, p.recode_budget, body, (ptr, rc_bank, rc_row, rc_valid))

    pending_park = jnp.any(jnp.any(fresh_loc > 0, axis=tuple(
        range(1, fresh_loc.ndim))) & ~down_hard)
    complete = (ptr >= total) & ~jnp.any(rc_valid) & ~pending_park
    rebuilt = fault.rebuilt | (rebuilding & complete)
    return rc_bank, rc_row, rc_valid, fault._replace(
        rebuilt=rebuilt, rebuild_ptr=ptr)


def quiescent_fault_pending(fault: FaultState, cycle) -> jnp.ndarray:
    """True while a scheduled fault event can still change observable state
    — an un-failed bank with a failure pending, or a failed bank with a
    recovery scheduled (its rebuild must finish before the run's fixed
    point is reached). Used by ``system.quiescent``; works on single and
    batched states (trailing-axis reduction)."""
    cyc = jnp.asarray(cycle)[..., None]
    down = (fault.fail_at <= cyc) & ~fault.rebuilt
    pending = (((fault.fail_at > cyc) & (fault.fail_at < NEVER))
               | (down & (fault.recover_at < NEVER)))
    return jnp.any(pending, axis=-1)
