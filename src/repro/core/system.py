"""The coded memory system: core arbiter + bank queues + access scheduler.

One ``cycle_fn`` call = one memory clock cycle (paper Fig 2 / §IV):

  1. **Core arbiter** — each core's pending request is pushed into its
     destination bank's read/write queue; a full queue stalls the core.
  2. **Access scheduler** — a write-drain hysteresis picks read or write mode
     (the paper serves writes "only when the write bank queues are nearly
     full"); the corresponding pattern builder schedules this cycle's
     accesses across data + parity ports.
  3. **Datapath** — served reads return values (direct / XOR-decode /
     redirect); served writes commit payloads to data banks or park them in
     parity rows. ``golden`` tracks memory order for the test invariants.
  4. **ReCoding unit** — retires stale-parity work using leftover ports.
  5. **Dynamic coding unit** — hot-region selection / encode / evict.

``run()`` wraps ``cycle_fn`` in a ``lax.scan`` for trace-driven simulation
(the Ramulator-replacement used by the benchmarks). ``run_chunk()`` advances
an explicit ``SimState`` carry over a fixed-shape staged chunk of a longer
stream — the device half of ``repro.traces.stream.stream_replay``, which
replays arbitrarily long traces under a constant device-memory footprint.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import controller as ctl
from repro.core.codes import MAX_OPTS, MAX_SIBS, CodeTables
from repro.core.dynamic import dynamic_step
from repro.core.recoding import recode_step
from repro.core.state import (MemParams, MemState, TunableParams,
                              active_geometry, cells, init_state,
                              make_tunables, set_cells, wide_add, wide_total)
from repro.faults import inject as finject
from repro.faults import plan as fplan
from repro.obs import planes as obs


class Trace(NamedTuple):
    """Per-core request streams. Invalid entries are idle cycles."""

    bank: jnp.ndarray      # (n_cores, T) int32
    row: jnp.ndarray       # (n_cores, T) int32
    is_write: jnp.ndarray  # (n_cores, T) bool
    data: jnp.ndarray      # (n_cores, T) int32 write payloads
    valid: jnp.ndarray     # (n_cores, T) bool


def drain_bound(n_cores: int, length: int, backlog: int = 0) -> int:
    """Worst-case cycle budget to drain ``length`` requests per core.

    Derivation: the system serves at least one access per cycle whenever any
    queue is non-empty (the write-drain hysteresis always picks a non-empty
    side), so ``n_cores * length`` requests fully serialized on a single
    port need at most ``n_cores * length`` service cycles. The 1.5 factor
    covers cycles where a request is in flight but its queue push stalled on
    a full destination queue (a stalled core retries every cycle, and every
    such cycle is also a service cycle for the queue blocking it — 0.5 per
    request over-counts this deliberately), and the +64 constant covers the
    cold start (empty queues) and the post-drain settling of the recoding /
    dynamic-coding units.

    ``backlog`` adds carried-over work that is *already queued* when the
    budget starts — the chunked-replay case (``CodedMemorySystem.run_chunk``),
    where up to ``2 * n_data * queue_depth`` requests from the previous chunk
    may still occupy the read+write queues. It is counted like any other
    request (one service cycle each).

    This is the single shared bound for the looped (``sim.ramulator``),
    batched (``repro.sweep``) and streamed (``repro.traces``) paths — do not
    re-derive it inline.
    """
    return int((n_cores * length + backlog) * 1.5) + 64


class SimState(NamedTuple):
    mem: MemState
    core_ptr: jnp.ndarray   # (n_cores,) int32
    done_cycle: jnp.ndarray  # () int32, -1 until the workload drains


def quiescent(st: "SimState") -> jnp.ndarray:
    """Per-point observable fixed point: workload drained (``done_cycle``
    latched), encoder idle, recode ring empty. After this, every further
    cycle is an observable no-op (the dynamic unit starts nothing new after
    drain — ``dynamic_step``'s ``quiesce``), which is what makes every
    early exit bit-identical to running a bound out. The ONE definition
    shared by the sweep engine's batched early exit, ``run_chunk``'s
    chunk-exit, and the streaming drivers — new drain conditions must land
    here, not in per-caller copies. Works on single and batched states
    (trailing-axis reduction over the ring).

    With fault injection on, a point also isn't quiescent while a
    scheduled fault event (a pending failure, or a failure with a recovery
    whose rebuild hasn't completed) can still change observable state —
    see ``repro.faults.inject.quiescent_fault_pending``."""
    m = st.mem
    q = ((st.done_cycle >= 0) & (m.enc_region < 0)
         & ~jnp.any(m.rc_valid, axis=-1))
    if m.fault is not None:
        q = q & ~finject.quiescent_fault_pending(m.fault, m.cycle)
    return q


class CycleOut(NamedTuple):
    """Per-cycle introspection (read datapath results for invariant tests)."""

    r_served: jnp.ndarray  # (N,) bool
    r_bank: jnp.ndarray    # (N,) int32
    r_row: jnp.ndarray     # (N,) int32
    r_value: jnp.ndarray   # (N,) int32
    n_served: jnp.ndarray  # () int32 (reads+writes)


class SimResult(NamedTuple):
    cycles: int
    completed: bool
    served_reads: int
    served_writes: int
    degraded_reads: int
    parked_writes: int
    switches: int
    recode_backlog: int
    stall_cycles: int
    avg_read_latency: float
    avg_write_latency: float
    rc_dropped: int = 0   # recode requests lost to a full ring (write path)
    # per-window critical-word latency stats, filled by the streaming replay
    # driver (``repro.traces.stream``): one (n_served, avg_latency) pair per
    # replay window. Empty for single-shot runs, so equality comparisons
    # between engine paths are unaffected; strip with
    # ``repro.traces.stream.strip_windows`` before comparing streamed vs
    # single-shot results.
    window_read_latency: tuple = ()
    window_write_latency: tuple = ()
    # fault-injection availability stats (repro.faults); all 0 when the
    # ``faults`` flag is off, so pre-fault result comparisons are unaffected
    unserved_reads: int = 0      # reads fail-fast-dropped (unservable)
    lost_writes: int = 0         # writes dropped with no parity coverage
    fault_degraded_reads: int = 0  # reads served degraded because their
                                   # bank was down (subset of degraded_reads)
    dead_bank_cycles: int = 0    # sum over banks of cycles spent down
                                 # (counted until the workload drains)


def result_from_host(m: MemState, done_cycle) -> SimResult:
    """One point's SimResult from host-side (numpy) MemState leaves — the
    single assembly point shared by ``CodedMemorySystem.summarize`` and the
    sweep engine's ``summarize_batch`` (new stats get wired exactly once)."""
    dc = int(done_cycle)
    sr = int(m.served_reads)
    sw = int(m.served_writes)
    f = m.fault
    return SimResult(
        cycles=dc if dc >= 0 else int(m.cycle),
        completed=dc >= 0,
        served_reads=sr,
        served_writes=sw,
        degraded_reads=int(m.degraded_reads),
        parked_writes=int(m.parked_writes),
        switches=int(m.switches),
        recode_backlog=int(np.sum(m.rc_valid)),
        stall_cycles=wide_total(m.stall_cycles),
        avg_read_latency=wide_total(m.read_latency_sum) / max(sr, 1),
        avg_write_latency=wide_total(m.write_latency_sum) / max(sw, 1),
        rc_dropped=int(m.rc_dropped),
        unserved_reads=int(f.unserved_reads) if f is not None else 0,
        lost_writes=int(f.lost_writes) if f is not None else 0,
        fault_degraded_reads=int(f.fault_degraded) if f is not None else 0,
        dead_bank_cycles=int(np.sum(f.dead_cycles)) if f is not None else 0,
    )


class CodedMemorySystem:
    """Facade owning the static tables/params; methods are jit-compiled.

    ``tunables`` holds the default traced knobs (write-drain thresholds,
    selection period); each ``cycle_fn``/``run`` call may override them with
    an explicit ``TunableParams`` — that is how ``repro.sweep`` batches a
    grid of tunables through one compiled program.
    """

    def __init__(self, tables: CodeTables, params: MemParams, n_cores: int = 8,
                 tunables: Optional[TunableParams] = None):
        self.tables = tables
        self.p = params
        self.t = ctl.jtables(tables)
        self.n_cores = n_cores
        self.tunables = (tunables if tunables is not None
                         else make_tunables(queue_depth=params.queue_depth))

    # ------------------------------------------------------------------ init
    def init(self, tn: Optional[TunableParams] = None,
             region_priors=None, fault_plan=None) -> SimState:
        """Initial state; ``tn`` masks a padded group allocation down to the
        point's active geometry (see ``init_state``). ``region_priors`` is a
        ranked array of hot region ids (e.g. from
        ``repro.traces.profiler``) pre-mapped into parity slots so the
        dynamic coding unit starts warm instead of cold. ``fault_plan``
        installs a ``repro.faults.FaultPlan`` erasure/stutter schedule
        (requires ``make_params(faults=True)``)."""
        return SimState(
            mem=init_state(self.p, tn, region_priors=region_priors,
                           n_cores=self.n_cores, fault_plan=fault_plan),
            core_ptr=jnp.zeros((self.n_cores,), jnp.int32),
            done_cycle=jnp.int32(-1),
        )

    # --------------------------------------------------------------- arbiter
    def _arbiter(self, st: SimState, trace: Trace, rs_a, stream_end=None):
        """Push each core's pending request into its destination queue.

        Vectorized: cores are ranked within their destination (bank, r/w)
        queue by core index — the service order a sequential walk takes —
        and all pushes land in one scatter. The first ``rank`` free slots of
        a queue go to the first ``rank`` ranked cores, so slot assignment,
        full-queue stalls and pointer advances are bit-identical to the
        sequential golden model (``repro.oracle``, conformance-tested).

        ``stream_end`` (chunked replay): per-core count of staged requests —
        a core whose pointer reaches its stream end has consumed its whole
        request stream; INT32_MAX marks "more data beyond this chunk" (the
        chunk driver exits before such a core can over-run the staging
        buffer). ``None`` (single-shot) means the trace length is the end
        for every core — the exact pre-chunking program.
        """
        p = self.p
        m = st.mem
        tlen = trace.bank.shape[1]
        nc = self.n_cores
        car = jnp.arange(nc)

        pos = st.core_ptr
        in_range = pos < (tlen if stream_end is None else stream_end)
        pc = jnp.minimum(pos, tlen - 1)
        v = trace.valid[car, pc] & in_range
        b = jnp.maximum(trace.bank[car, pc], 0)
        i = jnp.maximum(trace.row[car, pc], 0)
        isw = trace.is_write[car, pc]
        payload = trace.data[car, pc]

        older = jnp.tril(jnp.ones((nc, nc), bool), k=-1)
        same_bank = b[:, None] == b[None, :]
        want_r = v & ~isw
        want_w = v & isw
        rank_r = jnp.sum(same_bank & older & want_r[None, :], axis=1)
        rank_w = jnp.sum(same_bank & older & want_w[None, :], axis=1)
        free_r = jnp.sum(~m.rq_valid, axis=1)
        free_w = jnp.sum(~m.wq_valid, axis=1)
        full = jnp.where(isw, rank_w >= free_w[b], rank_r >= free_r[b])
        push = v & ~full
        pr_ = push & ~isw
        pw_ = push & isw

        def rank_to_slot(valid):
            """(n_data, D) queue validity → map[bank, rank] = rank-th free slot."""
            d = valid.shape[1]
            fr = ~valid
            free_rank = jnp.cumsum(fr, axis=1) - 1
            return jnp.full((p.n_data, d), d, jnp.int32).at[
                jnp.arange(p.n_data)[:, None],
                jnp.where(fr, free_rank, d)
            ].set(jnp.broadcast_to(jnp.arange(d, dtype=jnp.int32),
                                   (p.n_data, d)), mode="drop")

        dq = p.queue_depth
        slot_r = rank_to_slot(m.rq_valid)[b, jnp.minimum(rank_r, dq - 1)]
        slot_w = rank_to_slot(m.wq_valid)[b, jnp.minimum(rank_w, dq - 1)]
        oob = jnp.int32(p.n_data)
        br = jnp.where(pr_, b, oob)
        bw = jnp.where(pw_, b, oob)
        cyc = jnp.broadcast_to(m.cycle, (nc,))
        rq_row = m.rq_row.at[br, slot_r].set(i, mode="drop")
        rq_age = m.rq_age.at[br, slot_r].set(cyc, mode="drop")
        rq_valid = m.rq_valid.at[br, slot_r].set(True, mode="drop")
        wq_row = m.wq_row.at[bw, slot_w].set(i, mode="drop")
        wq_age = m.wq_age.at[bw, slot_w].set(cyc, mode="drop")
        wq_valid = m.wq_valid.at[bw, slot_w].set(True, mode="drop")
        wq_data = m.wq_data.at[bw, slot_w].set(payload, mode="drop")
        access_count = m.access_count.at[
            jnp.where(push, i // rs_a, p.n_regions)].add(1, mode="drop")
        stalls = wide_add(m.stall_cycles, jnp.sum(v & full))
        ptr = pos + (in_range & (push | ~v)).astype(jnp.int32)

        tele = m.tele
        if p.telemetry:
            # the full-queue rejection above is the ONLY core-stall source,
            # so this per-bank per-cause plane sums exactly to stall_cycles
            stall = v & full
            stall_cause = tele.stall_cause.at[
                jnp.where(stall, b, oob), isw.astype(jnp.int32)
            ].add(1, mode="drop")
            # provenance carriers: the core id lands in the SAME slot the
            # request scatter above picked, so the serve step can attribute
            # each served candidate to its issuing core
            car32 = car.astype(jnp.int32)
            tele = tele._replace(
                stall_cause=stall_cause,
                rq_core=tele.rq_core.at[br, slot_r].set(car32, mode="drop"),
                wq_core=tele.wq_core.at[bw, slot_w].set(car32, mode="drop"),
            )
        mem = m._replace(
            rq_row=rq_row, rq_age=rq_age, rq_valid=rq_valid, wq_row=wq_row,
            wq_age=wq_age, wq_valid=wq_valid, wq_data=wq_data,
            access_count=access_count, stall_cycles=stalls, tele=tele,
        )
        return st._replace(mem=mem, core_ptr=ptr)

    # ----------------------------------------------------------- read values
    def _read_values(self, m: MemState, plan: ctl.ReadPlan, cb, ci, rs_a):
        """Vectorized XOR-decode datapath for the served reads."""
        p, t = self.p, self.t
        rs = p.region_size
        b = jnp.maximum(cb, 0)
        i = jnp.maximum(ci, 0)
        slot = m.region_slot[i // rs_a]
        pr = jnp.maximum(slot, 0) * rs + i % rs_a
        direct_val = cells(m.banks_data, b, i)
        fl = cells(m.fresh_loc, b, i)
        holder = jnp.maximum(fl - 1, 0)
        redirect_val = cells(m.parity_data, holder, pr)
        k = jnp.clip(plan.mode - ctl.MODE_OPT0, 0, MAX_OPTS - 1)
        j = jnp.maximum(t.opt_parity[b, k], 0)
        dec = cells(m.parity_data, j, pr)
        for mm in range(MAX_SIBS):
            s = t.opt_sibs[b, k, mm]
            dec = dec ^ jnp.where(
                s >= 0, cells(m.banks_data, jnp.maximum(s, 0), i), 0)
        val = jnp.where(
            plan.mode == ctl.MODE_REDIRECT, redirect_val,
            jnp.where((plan.mode >= ctl.MODE_OPT0) & (plan.mode < ctl.MODE_REDIRECT),
                      dec, direct_val),
        )
        return jnp.where(plan.served, val, 0)

    # ------------------------------------------------------- write datapath
    def _commit_writes(self, m: MemState, plan: ctl.WritePlan, cb, ci_, ca,
                       cd, rs_a):
        """Commit served write payloads in age order (last write wins).

        Vectorized: rather than walking candidates in a fori_loop, each
        served candidate's age order is compared with that of the served
        candidates writing the same cell; only the youngest served write
        per cell lands — the same value the sequential walk leaves behind.
        Into a table kept in rows of lanes, the cells that land are
        compacted into as many slots as ``ctl.write_slots`` proves a cycle
        writes, so its scatter is given those and not every candidate of
        the queues.
        """
        p, t = self.p, self.t
        rs = p.region_size
        b = jnp.maximum(cb, 0)
        i = jnp.maximum(ci_, 0)
        idx = jnp.arange(cb.shape[0], dtype=jnp.int32)
        # served candidates are valid: age order, ties in queue order
        younger = ((ca[None, :] > ca[:, None])
                   | ((ca[None, :] == ca[:, None])
                      & (idx[None, :] > idx[:, None])))
        slot = m.region_slot[i // rs_a]
        pr = jnp.maximum(slot, 0) * rs + i % rs_a
        kk = jnp.clip(plan.mode - ctl.WMODE_PARK0, 0, MAX_OPTS - 1)
        j = jnp.maximum(t.opt_parity[b, kk], 0)
        is_dir = plan.served & (plan.mode == ctl.WMODE_DIRECT)
        is_park = plan.served & (plan.mode >= ctl.WMODE_PARK0)
        slots = ctl.write_slots(p)

        def commit(table, n_slots, mask, rows, cols):
            # no younger write in ``mask`` lands on the same cell: compared
            # among the candidates, so the work follows them, not the banks
            later = ((rows[None, :] == rows[:, None])
                     & (cols[None, :] == cols[:, None])
                     & mask[None, :] & younger)
            win = mask & ~jnp.any(later, axis=1)
            ok, r_s, c_s, v_s = ctl._compact(table, n_slots, win, rows, cols,
                                             cd)
            return set_cells(table, jnp.where(ok, r_s, table.shape[0]), c_s,
                             v_s)

        return (commit(m.banks_data, slots.banks_data, is_dir, b, i),
                commit(m.parity_data, slots.parity_data, is_park, j, pr),
                commit(m.golden, slots.golden, plan.served, b, i))

    # ------------------------------------------------------------- one cycle
    @functools.partial(jax.jit, static_argnums=0)
    def cycle_fn(self, st: SimState, trace: Trace,
                 tn: Optional[TunableParams] = None,
                 stream_end: Optional[jnp.ndarray] = None):
        p, t = self.p, self.t
        if tn is None:
            tn = self.tunables
        # the point's own region geometry (== the allocation unless this
        # program serves a padded sweep group, see state.active_geometry)
        rs_a, nr_a = active_geometry(p, tn)
        # once the workload has drained there is no traffic to react to: the
        # dynamic unit stops starting encodes, so the system reaches a
        # quiescent fixed point (done + recode empty + encoder idle) that
        # lets the sweep engine cut trailing dead cycles without changing
        # any observable statistic.
        was_done = st.done_cycle >= 0
        with jax.named_scope("cycle.arbiter"):
            st = self._arbiter(st, trace, rs_a, stream_end)
            m = st.mem
            if p.telemetry:
                # post-arbiter occupancy is the per-cycle maximum (slots
                # only free up in the serve step below)
                m = m._replace(tele=m.tele._replace(
                    rq_hwm=jnp.maximum(m.tele.rq_hwm, jnp.sum(
                        m.rq_valid, axis=1, dtype=jnp.int32)),
                    wq_hwm=jnp.maximum(m.tele.wq_hwm, jnp.sum(
                        m.wq_valid, axis=1, dtype=jnp.int32)),
                ))
        # the access scheduler: fault masks, write-drain hysteresis, then
        # (called further down) the read and write pattern builders
        with jax.named_scope("cycle.patterns"):
            n_cand = p.n_data * p.queue_depth
            port_busy0 = jnp.zeros((p.n_ports + 1,), bool)
            bank_ids = jnp.repeat(jnp.arange(p.n_data, dtype=jnp.int32),
                                  p.queue_depth)

            # ---- fault injection (repro.faults): derive this cycle's
            # fault predicates, count dead cycles, fail-fast-drop
            # unservable queue entries, and seed the builders' port mask so
            # a down bank's port reads permanently busy (and stuttering
            # ports transiently busy). Ordering matters and is mirrored
            # exactly by the oracle: drops land after the arbiter (the
            # request was accepted and counted) and before the write-drain
            # hysteresis reads queue occupancy.
            if p.faults:
                fs = m.fault
                down = fplan.bank_down(fs, m.cycle)
                rebuilding = fplan.bank_rebuilding(fs, m.cycle)
                down_hard = down & ~rebuilding
                stut = fplan.stutter_busy(fs, m.cycle)
                # dead cycles are counted until the workload drains
                # (afterwards a permanently-dead bank would count forever,
                # breaking the quiescent fixed point the early-exit paths
                # rely on)
                dead_inc = (down & ~was_done).astype(jnp.uint32)
                rq_v2, wq_v2, n_uns, n_lost = finject.drop_unservable(
                    p, t, down_hard, m.rq_row, m.rq_valid, m.wq_row,
                    m.wq_valid, m.fresh_loc, m.parity_valid, m.region_slot,
                    rs_a)
                fs = fs._replace(
                    dead_cycles=fs.dead_cycles + dead_inc,
                    unserved_reads=fs.unserved_reads + n_uns,
                    lost_writes=fs.lost_writes + n_lost)
                m = m._replace(rq_valid=rq_v2, wq_valid=wq_v2, fault=fs)
                if p.telemetry:
                    m = m._replace(tele=m.tele._replace(
                        dead_cycles=m.tele.dead_cycles + dead_inc))
                port_busy0 = port_busy0.at[: p.n_data].set(down)
                port_busy0 = port_busy0.at[: p.n_ports].set(
                    port_busy0[: p.n_ports] | stut)

            # write-drain hysteresis
            wq_occ = jnp.max(jnp.sum(m.wq_valid, axis=1))
            any_r = jnp.any(m.rq_valid)
            any_w = jnp.any(m.wq_valid)
            wm = jnp.where(m.write_mode, wq_occ > tn.wq_lo,
                           wq_occ >= tn.wq_hi)
            serve_writes = (wm | (~any_r & any_w)) & any_w

        def do_reads(m, active=True):
            cb = bank_ids
            ci_ = m.rq_row.reshape(-1)
            ca = m.rq_age.reshape(-1)
            cv = m.rq_valid.reshape(-1) & active
            plan = ctl.build_read_pattern(
                p, t, cb, ci_, ca, cv, port_busy0, m.fresh_loc, m.parity_valid,
                m.region_slot, rs_a,
            )
            vals = self._read_values(m, plan, cb, ci_, rs_a)
            lat = jnp.sum(jnp.where(plan.served, m.cycle - ca, 0))
            tele = m.tele
            if p.telemetry:
                # provenance class from the plan's action id; latency
                # histogram over served candidates; unserved-but-valid
                # candidates count a read-conflict wait cycle on their bank.
                # (With ``active=False`` — the masked off-duty branch — cv
                # and plan.served are all False, so every scatter here drops
                # and the merged ``pick`` takes the other branch's updates.)
                cls = jnp.where(
                    plan.mode == ctl.MODE_DIRECT, 0,
                    jnp.where(plan.mode == ctl.MODE_FROM_SYM, 1,
                              jnp.where(plan.mode >= ctl.MODE_REDIRECT, 3, 2)))
                if p.faults:
                    # degraded serves whose cause is a down bank get their
                    # own provenance class (redirects to a parked copy are
                    # a freshness artifact, not a fault symptom — class 3)
                    cls = jnp.where(down[cb] & ((cls == 1) | (cls == 2)),
                                    4, cls)
                core = jnp.where(plan.served, tele.rq_core.reshape(-1),
                                 jnp.int32(self.n_cores))
                tele = tele._replace(
                    read_mode_core=tele.read_mode_core.at[core, cls].add(
                        1, mode="drop"),
                    lat_hist_read=tele.lat_hist_read.at[
                        jnp.where(plan.served, obs.lat_bin(m.cycle - ca),
                                  obs.HIST_BINS)].add(1, mode="drop"),
                    wait_cause=tele.wait_cause.at[
                        jnp.where(cv & ~plan.served, cb, jnp.int32(p.n_data)),
                        obs.WAIT_READ].add(1, mode="drop"),
                )
            fault = m.fault
            if p.faults:
                deg_f = plan.served & down[cb] & (
                    (plan.mode == ctl.MODE_FROM_SYM)
                    | ((plan.mode >= ctl.MODE_OPT0)
                       & (plan.mode < ctl.MODE_REDIRECT)))
                fault = fault._replace(
                    fault_degraded=fault.fault_degraded
                    + jnp.sum(deg_f).astype(jnp.int32))
            m = m._replace(
                rq_valid=m.rq_valid & ~plan.served.reshape(p.n_data, p.queue_depth),
                served_reads=m.served_reads + plan.n_served,
                degraded_reads=m.degraded_reads + plan.n_degraded,
                read_latency_sum=wide_add(m.read_latency_sum, lat),
                tele=tele,
                fault=fault,
            )
            out = CycleOut(plan.served, cb, ci_, vals, plan.n_served)
            return m, plan.port_busy, out

        def do_writes(m, active=True):
            cb = bank_ids
            ci_ = m.wq_row.reshape(-1)
            ca = m.wq_age.reshape(-1)
            cv = m.wq_valid.reshape(-1) & active
            cd = m.wq_data.reshape(-1)
            plan = ctl.build_write_pattern(
                p, t, cb, ci_, ca, cv, port_busy0, m.fresh_loc, m.parity_valid,
                m.region_slot, m.parked_count, m.rc_bank, m.rc_row, m.rc_valid,
                rs_a, down=down if p.faults else None,
            )
            banks_data, parity_data, golden = self._commit_writes(
                m, plan, cb, ci_, ca, cd, rs_a)
            lat = jnp.sum(jnp.where(plan.served, m.cycle - ca, 0))
            tele = m.tele
            if p.telemetry:
                cls = (plan.mode >= ctl.WMODE_PARK0).astype(jnp.int32)
                core = jnp.where(plan.served, tele.wq_core.reshape(-1),
                                 jnp.int32(self.n_cores))
                tele = tele._replace(
                    write_mode_core=tele.write_mode_core.at[core, cls].add(
                        1, mode="drop"),
                    lat_hist_write=tele.lat_hist_write.at[
                        jnp.where(plan.served, obs.lat_bin(m.cycle - ca),
                                  obs.HIST_BINS)].add(1, mode="drop"),
                    wait_cause=tele.wait_cause.at[
                        jnp.where(cv & ~plan.served, cb, jnp.int32(p.n_data)),
                        obs.WAIT_WRITE].add(1, mode="drop"),
                )
            m = m._replace(
                tele=tele,
                wq_valid=m.wq_valid & ~plan.served.reshape(p.n_data, p.queue_depth),
                fresh_loc=plan.fresh_loc,
                parity_valid=plan.parity_valid,
                parked_count=plan.parked_count,
                rc_bank=plan.rc_bank, rc_row=plan.rc_row, rc_valid=plan.rc_valid,
                served_writes=m.served_writes + plan.n_served,
                parked_writes=m.parked_writes + plan.n_parked,
                rc_dropped=m.rc_dropped + plan.n_rc_dropped,
                write_latency_sum=wide_add(m.write_latency_sum, lat),
                banks_data=banks_data, parity_data=parity_data, golden=golden,
            )
            out = CycleOut(
                jnp.zeros((n_cand,), bool), cb, ci_, jnp.zeros((n_cand,), jnp.int32),
                plan.n_served,
            )
            return m, plan.port_busy, out

        # Under vmap, ``lax.cond`` would evaluate both branches for every
        # point anyway — at the full cost of each builder's walk over loaded
        # queues. Instead run both branches, one after the other, with the
        # off-duty builder's candidates masked invalid: its compacted walk
        # exits at once and it leaves the state exactly as it found it (no
        # candidate is served, so every update is a no-op), so the state
        # needs no per-point choice between the branches. Only the ports
        # each claimed and the cycle's read outputs are picked.
        with jax.named_scope("cycle.patterns"):
            m, pb_r, out_r = do_reads(m, active=~serve_writes)
            m, pb_w, out_w = do_writes(m, active=serve_writes)
            pick = lambda w, r: jax.tree.map(              # noqa: E731
                lambda x, y: jnp.where(serve_writes, x, y), w, r)
            port_busy, out = pick(pb_w, pb_r), pick(out_w, out_r)
            m = m._replace(write_mode=wm)

        # recoding unit uses leftover ports. A REBUILDING bank's port is
        # granted back to it here (and only here): the builders saw it
        # busy, so the rebuild's restores/recomputes get the port the bank
        # cannot yet use for service. Stutter still applies.
        with jax.named_scope("cycle.recode"):
            if p.faults:
                rc_pb = port_busy.at[: p.n_data].set(
                    jnp.where(rebuilding, stut[: p.n_data],
                              port_busy[: p.n_data]))
            else:
                rc_pb = port_busy
            rc = recode_step(
                p, t, rc_pb, m.fresh_loc, m.parity_valid, m.parked_count,
                m.rc_bank, m.rc_row, m.rc_valid, m.region_slot, m.banks_data,
                m.parity_data, rs_a, down=down_hard if p.faults else None,
            )
            m = m._replace(
                fresh_loc=rc.fresh_loc, parity_valid=rc.parity_valid,
                parked_count=rc.parked_count, rc_valid=rc.rc_valid,
                banks_data=rc.banks_data, parity_data=rc.parity_data,
            )
            if p.telemetry:
                # ring entries still pending after the recode unit ran charge a
                # recode-budget/port-starvation wait cycle to their bank
                tele = m.tele
                m = m._replace(tele=tele._replace(
                    recode_retired=tele.recode_retired
                    + rc.n_recoded.astype(jnp.uint32),
                    wait_cause=tele.wait_cause.at[
                        jnp.where(m.rc_valid, jnp.maximum(m.rc_bank, 0),
                                  jnp.int32(p.n_data)),
                        obs.WAIT_RECODE].add(1, mode="drop"),
                ))
            # online rebuild: sweep cells into the recode ring while any bank
            # is rebuilding; latch ``rebuilt`` (the bank rejoins) on completion
            if p.faults:
                rb_bank, rb_row, rb_valid, fs2 = finject.rebuild_scan(
                    p, t, m.fault, m.cycle, rebuilding, down_hard, m.fresh_loc,
                    m.parity_valid, m.region_slot, m.rc_bank, m.rc_row,
                    m.rc_valid, rs_a, nr_a)
                m = m._replace(rc_bank=rb_bank, rc_row=rb_row,
                               rc_valid=rb_valid, fault=fs2)
        # dynamic coding unit
        with jax.named_scope("cycle.dynamic"):
            dy = dynamic_step(
                p, t, tn, m.cycle, m.region_slot, m.slot_region,
                m.access_count, m.parked_count, m.parity_valid,
                m.parity_data, m.banks_data, m.enc_region, m.enc_remaining,
                m.enc_slot, m.switches, quiesce=was_done,
            )
            m = m._replace(
                region_slot=dy.region_slot, slot_region=dy.slot_region,
                access_count=dy.access_count, parity_valid=dy.parity_valid,
                parity_data=dy.parity_data, enc_region=dy.enc_region,
                enc_remaining=dy.enc_remaining, enc_slot=dy.enc_slot,
                switches=dy.switches,
            )
        # completion bookkeeping: a core is consumed once its pointer passes
        # its stream end (the full trace length in single-shot mode; the
        # staged request count for a chunk whose stream is exhausted;
        # never, for a chunk with more data behind it — INT32_MAX)
        tlen = trace.bank.shape[1]
        consumed = jnp.all(
            st.core_ptr >= (tlen if stream_end is None else stream_end))
        drained = ~jnp.any(m.rq_valid) & ~jnp.any(m.wq_valid)
        done = consumed & drained
        done_cycle = jnp.where((st.done_cycle < 0) & done, m.cycle, st.done_cycle)
        m = m._replace(cycle=m.cycle + 1)
        return SimState(m, st.core_ptr, done_cycle), out

    # ------------------------------------------------------------------- run
    @functools.partial(jax.jit, static_argnums=(0, 3))
    def _run(self, st: SimState, trace: Trace, n_cycles: int,
             tn: Optional[TunableParams] = None):
        def body(st, _):
            st, out = self.cycle_fn(st, trace, tn)
            return st, out.n_served

        return jax.lax.scan(body, st, None, length=n_cycles)

    def run(self, trace: Trace, n_cycles: int,
            tn: Optional[TunableParams] = None,
            st: Optional[SimState] = None,
            fault_plan=None) -> SimResult:
        """Single-shot replay; ``st`` carries in an explicit initial state
        (the chunked-replay driver threads states the same way).
        ``fault_plan`` installs an erasure/stutter schedule on the fresh
        initial state (ignored when ``st`` is given — put the plan in the
        state you pass)."""
        tn = tn if tn is not None else self.tunables
        st, _ = self._run(
            st if st is not None else self.init(tn, fault_plan=fault_plan),
            trace, n_cycles, tn)
        return self.summarize(st)

    # ----------------------------------------------------------- chunked run
    # NOTE: the SimState carry is deliberately NOT donated (unlike the sweep
    # engine's _scan_batch): a fresh init_state aliases one zero scalar
    # across several leaves (and priors/traced inits hold broadcast views),
    # and donating an aliased buffer twice is a runtime error on the first
    # chunk. The state is a small constant per chunk; the footprint bound
    # comes from the fixed staging-buffer shape.
    @functools.partial(jax.jit, static_argnums=(0, 4))
    def run_chunk(self, st: SimState, trace: Trace, stream_end: jnp.ndarray,
                  n_cycles: int, tn: Optional[TunableParams] = None) -> SimState:
        """One streaming-replay step: advance ``st`` over a staged chunk.

        ``trace`` is a fixed-shape staging buffer holding the next (up to)
        ``tlen`` requests of each core's stream, starting at each core's own
        global position; ``stream_end[c]`` is the number of staged requests
        for core ``c`` if its stream ends inside this buffer, else INT32_MAX.
        Runs cycles until (a) some core with more data behind the buffer has
        consumed all its staged requests (*starved* — the driver restages and
        calls again; the exit happens between cycles, so every executed cycle
        sees exactly the requests the single-shot program would), (b) the
        system is fully quiescent (workload done, recode ring empty, encoder
        idle — the same observable fixed point the sweep engine's early exit
        uses), or (c) the per-chunk ``drain_bound`` budget runs out.

        One compiled program serves the whole stream: the chunk shape, the
        budget and the tunables treedef are the only compile keys.
        """
        tlen = trace.bank.shape[1]

        def cond(carry):
            st, i = carry
            starved = jnp.any((st.core_ptr >= tlen) & (stream_end > tlen))
            return (i < n_cycles) & ~starved & ~quiescent(st)

        def body(carry):
            st, i = carry
            st, _ = self.cycle_fn(st, trace, tn, stream_end)
            return st, i + 1

        st, _ = jax.lax.while_loop(cond, body, (st, jnp.int32(0)))
        return st

    def summarize(self, st: SimState) -> SimResult:
        host = jax.device_get(st)
        return result_from_host(host.mem, host.done_cycle)
