"""Dynamic coding unit (paper §IV-E).

Rows are partitioned into ``n_regions`` regions of ``region_size`` rows;
parity banks can hold ``n_slots = ⌊α/r⌋`` coded regions (capped at
``n_regions``; at α=1 everything is coded statically and this unit is a
no-op — reproducing the paper's "zero switches at α=1").

Every ``select_period`` cycles the unit compares the hottest *uncoded*
region's (windowed) access count against the coldest *coded* region:

  * if a parity slot is free, the hottest uncoded region with any accesses is
    encoded into it;
  * otherwise, if the hottest uncoded region is strictly hotter than the
    coldest coded region (LFU), the LFU region is evicted — unless it holds
    parked writes (``parked_count > 0``), which must drain first — and the
    hot region is encoded into the freed slot.

Encoding takes ``max(1, region_size_active // encode_rows_per_cycle)``
cycles (the point's own region size, not the allocation); the slot is
unusable in flight
(the paper's "reserved staging region"). Completion writes the parity data
(XOR of member data banks over the whole region), marks ``parity_valid`` and
counts one *switch* (the Fig-18 bar metric). Counts decay by half each
period (windowed LFU).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.codes import MAX_SIBS
from repro.core.controller import JTables
from repro.core.state import (MemParams, TunableParams, active_geometry,
                              columns, set_columns)

INT32_MAX = jnp.iinfo(jnp.int32).max


class DynOut(NamedTuple):
    region_slot: jnp.ndarray
    slot_region: jnp.ndarray
    access_count: jnp.ndarray
    parity_valid: jnp.ndarray
    parity_data: jnp.ndarray
    enc_region: jnp.ndarray
    enc_remaining: jnp.ndarray
    enc_slot: jnp.ndarray
    switches: jnp.ndarray


def _on(pred, fn, x):
    """``fn(x)`` where ``pred`` holds, else ``x``: a loop of at most one
    trip. Under the sweep engine's ``vmap`` a ``cond`` would run ``fn``
    for every point on every cycle and select; the loop runs it only on
    the cycles where some point's ``pred`` holds (a point whose ``pred``
    is false keeps ``x``)."""
    return jax.lax.while_loop(lambda c: ~c[0], lambda c: (True, fn(c[1])),
                              (~pred, x))[1]


def _encode_region_data(
    p: MemParams, t: JTables, banks_data: jnp.ndarray, parity_data: jnp.ndarray,
    region: jnp.ndarray, slot: jnp.ndarray, rs_a: jnp.ndarray,
) -> jnp.ndarray:
    """Write XOR parities of ``region``'s rows into ``slot``'s parity rows.

    ``rs_a`` is the point's traced region size; slot stride stays the
    allocated ``p.region_size``, and padded lanes (offset ≥ rs_a) write 0
    into parity rows that no read/recode ever addresses. Reads and writes
    one region's rows: rows past the bank's end (the last region's tail)
    read the bank's last row."""
    rs = p.region_size
    off = jnp.arange(rs)
    start = region * rs_a
    lo = jnp.clip(start, 0, p.n_rows - rs)      # the window inside the bank
    win = columns(banks_data, lo, rs)
    tail = jnp.broadcast_to(win[:, -1:], win.shape)
    rows = jax.lax.dynamic_slice(jnp.concatenate([win, tail], axis=1),
                                 (0, start - lo), (p.n_data, rs))
    vals = jnp.zeros((p.n_parities, rs), jnp.int32)
    for mm in range(MAX_SIBS + 1):
        m = t.par_members[:, mm]  # (n_par,)
        vals = vals ^ jnp.where((m >= 0)[:, None], rows[jnp.maximum(m, 0)], 0)
    vals = jnp.where((off < rs_a)[None, :], vals, 0)
    start = jnp.maximum(slot, 0) * rs
    return set_columns(parity_data, vals, start)


def _set_slot_valid(p: MemParams, parity_valid, slot, valid):
    """``parity_valid`` with ``slot``'s rows set to ``valid`` (broadcast
    to the slot's rows of every parity)."""
    rs = p.region_size
    start = jnp.maximum(slot, 0) * rs
    return set_columns(parity_valid,
                       jnp.broadcast_to(valid, (p.n_parities, rs)), start)


def priors_layout(p: MemParams, tn, priors):
    """(region_slot, slot_region, parity_valid) pre-mapping profiled hot
    regions into parity slots — the warm start ``init_state`` applies when a
    trace profile's region-priors are available.

    ``priors`` is a ranked int32 array of *distinct* region ids, hottest
    first, -1 padded (``repro.traces.profiler.TraceProfile.region_priors``
    emits exactly this). The leading entries fill parity slots 0.. up to the
    point's slot budget; ids outside the active region range and -1 padding
    are skipped without shifting later entries into their slots. Parity rows
    of the mapped slots are marked valid: at init every data bank is zero,
    so the all-zero parity rows already equal the XOR of their members —
    the same consistency argument the full-coverage identity map relies on.

    From here the unit proceeds exactly as from a cold start: the seeded
    regions are ordinary coded regions (evictable by LFU once colder than
    the hottest uncoded region), so a stale prior costs at most one
    re-selection period — the cold start pays that period anyway.
    """
    rs = p.region_size
    if tn is None:
        rs_a, nr_a = p.region_size, p.n_regions
        budget = jnp.int32(p.n_active)
    else:
        rs_a, nr_a = active_geometry(p, tn)
        budget = jnp.minimum(tn.n_slots_active, p.n_active)
    pr = jnp.asarray(priors, jnp.int32).reshape(-1)
    k = pr.shape[0]
    if k == 0:
        return (jnp.full((p.n_regions,), -1, jnp.int32),
                jnp.full((p.n_slots,), -1, jnp.int32),
                jnp.zeros((p.n_parities, p.n_slots * rs), bool))
    sid = jnp.arange(p.n_slots)
    cand = jnp.where(sid < k, pr[jnp.minimum(sid, k - 1)], -1)
    ok = (sid < budget) & (cand >= 0) & (cand < nr_a)
    slot_region = jnp.where(ok, cand, -1).astype(jnp.int32)
    region_slot = jnp.full((p.n_regions,), -1, jnp.int32).at[
        jnp.where(ok, cand, p.n_regions)].set(
        sid.astype(jnp.int32), mode="drop")
    row = jnp.arange(p.n_slots * rs)
    # parity rows are *stored* at the allocated stride (slot * rs_alloc +
    # i % rs_active); this walks that storage layout, not a region id
    active = ok[row // rs] & (row % rs < rs_a)  # analysis: static-geometry
    parity_valid = jnp.broadcast_to(active, (p.n_parities, p.n_slots * rs))
    return region_slot, slot_region, parity_valid


def dynamic_step(
    p: MemParams,
    t: JTables,
    tn: TunableParams,
    cycle: jnp.ndarray,
    region_slot: jnp.ndarray,
    slot_region: jnp.ndarray,
    access_count: jnp.ndarray,
    parked_count: jnp.ndarray,
    parity_valid: jnp.ndarray,
    parity_data: jnp.ndarray,
    banks_data: jnp.ndarray,
    enc_region: jnp.ndarray,
    enc_remaining: jnp.ndarray,
    enc_slot: jnp.ndarray,
    switches: jnp.ndarray,
    quiesce=None,
) -> DynOut:
    if p.n_active >= p.n_regions:  # static full coverage: unit disabled
        return DynOut(region_slot, slot_region, access_count, parity_valid,
                      parity_data, enc_region, enc_remaining, enc_slot, switches)
    rs = p.region_size
    rs_a, nr_a = active_geometry(p, tn)

    # ---- encode in flight ---------------------------------------------------
    in_flight = enc_region >= 0
    enc_remaining = jnp.where(in_flight, enc_remaining - 1, 0)
    complete = in_flight & (enc_remaining <= 0)
    # completion: install mapping, write parity data, validate rows. The
    # loop carries the region (the encode leaves none in flight), so the
    # region's read depends on the loop and the compiler cannot hoist it
    # out, to run on every cycle.
    def encode(c):
        pd, pv, region = c
        start = jnp.maximum(enc_slot, 0) * rs
        old = columns(pv, start, rs)
        return (_encode_region_data(p, t, banks_data, pd, region, enc_slot,
                                    rs_a),
                _set_slot_valid(p, pv, enc_slot,
                                old | (jnp.arange(rs) < rs_a)),
                jnp.int32(-1))

    parity_data, parity_valid, _ = _on(
        complete, encode, (parity_data, parity_valid, enc_region))
    region_slot = region_slot.at[jnp.maximum(enc_region, 0)].set(
        jnp.where(complete, enc_slot, region_slot[jnp.maximum(enc_region, 0)])
    )
    slot_region = slot_region.at[jnp.maximum(enc_slot, 0)].set(
        jnp.where(complete, enc_region, slot_region[jnp.maximum(enc_slot, 0)])
    )
    switches = switches + complete.astype(jnp.int32)
    enc_region = jnp.where(complete, -1, enc_region)
    enc_slot = jnp.where(complete, -1, enc_slot)

    # ---- periodic selection --------------------------------------------------
    # ``quiesce``: the workload already drained — no traffic left to adapt
    # to, so no new encodes start (in-flight ones still complete above).
    period = (cycle % tn.select_period == 0) & (cycle > 0)
    select = period & (enc_region < 0)
    if quiesce is not None:
        select = select & ~quiesce
    coded = region_slot >= 0
    # hottest uncoded *active* region (padded regions past the point's own
    # n_regions never exist: their counts stay 0 and they are masked here)
    region_active = jnp.arange(p.n_regions) < nr_a
    cand_counts = jnp.where(coded | ~region_active, -1, access_count)
    cand = jnp.argmax(cand_counts).astype(jnp.int32)
    cand_count = cand_counts[cand]
    # coldest coded, evictable (no parked rows) region
    evict_counts = jnp.where(coded & (parked_count == 0), access_count, INT32_MAX)
    victim = jnp.argmin(evict_counts).astype(jnp.int32)
    victim_count = evict_counts[victim]
    # slots at or past the point's traced budget are never offered as free:
    # a sweep can allocate parity state once at the grid's max ⌊α/r⌋ and let
    # each point use only its own budget (repro.sweep batches α this way).
    # p.n_active caps it statically — 0 for an α < r (uncoded) allocation.
    budget = jnp.minimum(tn.n_slots_active, p.n_active)
    free_slot_mask = (slot_region < 0) & (jnp.arange(p.n_slots) < budget)
    has_free = jnp.any(free_slot_mask)
    free_slot = jnp.argmax(free_slot_mask).astype(jnp.int32)

    start_free = select & has_free & (cand_count > 0)
    start_evict = select & ~has_free & (cand_count > victim_count) & (victim_count < INT32_MAX)

    # eviction: clear victim's slot + validity (whole allocated stride —
    # padded rows are invalid anyway)
    vslot = jnp.maximum(region_slot[victim], 0)
    parity_valid = _on(start_evict,
                       lambda pv: _set_slot_valid(p, pv, vslot, False),
                       parity_valid)
    region_slot = region_slot.at[victim].set(
        jnp.where(start_evict, -1, region_slot[victim])
    )
    slot_region = slot_region.at[vslot].set(
        jnp.where(start_evict, -1, slot_region[vslot])
    )

    start = start_free | start_evict
    tgt_slot = jnp.where(start_evict, vslot, free_slot)
    enc_region = jnp.where(start, cand, enc_region)
    enc_slot = jnp.where(start, tgt_slot, enc_slot)
    # encode latency follows the point's own region size, not the allocation
    enc_cycles = jnp.maximum(1, rs_a // p.encode_rows_per_cycle).astype(jnp.int32)
    enc_remaining = jnp.where(start, enc_cycles, enc_remaining)

    # windowed counts decay each period
    access_count = jnp.where(period, access_count // 2, access_count)
    return DynOut(region_slot, slot_region, access_count, parity_valid,
                  parity_data, enc_region, enc_remaining, enc_slot, switches)
