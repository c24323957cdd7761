"""Batched sweep engine: one compiled program per static shape, not per point.

The looped reference path (``repro.sim.ramulator.simulate``) pays a fresh
``jax.jit`` trace + compile, a full ``lax.scan`` launch and a host↔device
sync for every sweep point. This engine instead:

  1. partitions the sweep by static signature (``repro.sweep.grid``),
  2. ``vmap``s ``CodedMemorySystem.cycle_fn`` over the point axis of each
     partition — seeds, trace contents and ``TunableParams`` all batch —
  3. runs one ``lax.scan`` over cycles for the whole partition, and
  4. summarizes with a single device→host transfer per partition.

Per-point results are bit-identical to the looped path (the cycle engine is
pure integer arithmetic; ``vmap`` of ``cond`` evaluates both branches and
selects, which cannot change the selected values). tests/test_sweep.py and
benchmarks/bench_sweep.py both verify this.

With more than one device, the batch's point axis is padded with masked
dummy points (replicas of the last real point, stripped again in
``summarize_batch``) up to the next device-count multiple and sharded
across a 1-D "sweep" mesh (``repro.launch.mesh.make_sweep_mesh``); ``jit``
then partitions the scan across devices automatically — on every real
grid, not just ones whose size happens to divide the device count.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.codes import get_tables
from repro.core.controller import fresh_cells, write_slots
from repro.core.state import TunableParams, make_params, make_tunables
from repro.core.system import (CodedMemorySystem, SimResult, SimState, Trace,
                               quiescent, result_from_host)
from repro.launch.mesh import make_sweep_mesh
from repro.obs.spans import span
from repro.sweep import workloads
from repro.sweep.grid import (GridBatch, SweepPoint, batch_geometry_alloc,
                              partition, static_signature)

# One system (= one set of jit caches) per (static signature, geometry
# allocation), so re-running a suite — or growing it along batchable axes —
# never recompiles.
_SYSTEMS: Dict[Tuple, CodedMemorySystem] = {}


def system_for(pt: SweepPoint,
               geometry_alloc: Optional[Tuple[int, int, int]] = None,
               traced_geometry: bool = False) -> CodedMemorySystem:
    # static_signature deliberately drops α and r, so the cache must key on
    # the actual (region_size, n_regions, n_slots) allocation — two
    # geometries must not share an exactly-allocated system (an explicit
    # alloc equal to the derived geometry builds identical params, so one
    # key covers both). ``traced_geometry`` keys too: a single-geometry
    # batch compiles the cheaper static-indexing program.
    alloc = geometry_alloc if geometry_alloc is not None else pt.derived_slots()
    sig = (static_signature(pt), alloc, traced_geometry)
    sys = _SYSTEMS.get(sig)
    if sys is None:
        rs_alloc, nr_alloc, ns_alloc = alloc
        tables = get_tables(pt.scheme, n_data=pt.n_data)
        params = make_params(tables, n_rows=pt.n_rows, alpha=pt.alpha, r=pt.r,
                             queue_depth=pt.queue_depth, coalesce=pt.coalesce,
                             recode_cap=pt.recode_cap, max_syms=pt.max_syms,
                             encode_rows_per_cycle=pt.encode_rows_per_cycle,
                             recode_budget=pt.recode_budget,
                             n_slots_alloc=ns_alloc,
                             region_size_alloc=rs_alloc,
                             n_regions_alloc=nr_alloc,
                             traced_geometry=traced_geometry,
                             telemetry=pt.telemetry,
                             faults=bool(pt.faults))
        sys = CodedMemorySystem(tables, params, n_cores=pt.n_cores)
        _SYSTEMS[sig] = sys
    return sys


def stack_tunables(points: Sequence[SweepPoint],
                   queue_depth: int) -> TunableParams:
    tns = []
    for pt in points:
        rs, nr, ns = pt.derived_slots()
        tns.append(make_tunables(queue_depth=queue_depth,
                                 select_period=pt.select_period,
                                 wq_hi=pt.wq_hi, wq_lo=pt.wq_lo,
                                 n_slots_active=ns,
                                 region_size_active=rs,
                                 n_regions_active=nr))
    return jax.tree.map(lambda *xs: jnp.stack(xs), *tns)


def _batched_init(sys: CodedMemorySystem, tn_b: TunableParams,
                  priors_b=None) -> SimState:
    """Per-point initial states: each point's active geometry masks the
    shared allocation (identity region maps sized to *its* n_regions, etc.).
    ``priors_b`` (B, K) optionally warm-starts each point's dynamic coding
    unit with profiled hot regions (``repro.traces.profiler``)."""
    if priors_b is None:
        return jax.vmap(sys.init)(tn_b)
    return jax.vmap(sys.init)(tn_b, priors_b)


def _stack_faults(points: Sequence[SweepPoint], p):
    """Per-point fault schedules → one batched FaultState (the schedule is
    carry data, so points with *different* plans batch through one compiled
    program — same trick as the tunables)."""
    from repro.faults.plan import init_fault_state, plan_from_spec

    states = []
    for pt in points:
        plan = plan_from_spec(pt.faults, p.n_data, p.n_ports)
        states.append(plan.state() if plan is not None
                      else init_fault_state(p.n_data, p.n_ports))
    return jax.tree.map(lambda *xs: jnp.stack(xs), *states)


def _pad_points(n_points: int) -> int:
    """Rows of padding needed to land on a device-count multiple (0 if the
    size already divides, or on a single device)."""
    n_dev = len(jax.devices())
    if n_dev <= 1:
        return 0
    return (-n_points) % n_dev


def _replicate_tail(tree, pad: int):
    """Append ``pad`` copies of the last point along the batch axis. The
    replicas quiesce exactly when their original does, so they never extend
    the early-exit while_loop; ``summarize_batch`` strips their rows."""
    return jax.tree.map(
        lambda x: jnp.concatenate([x, jnp.repeat(x[-1:], pad, axis=0)]), tree)


def _maybe_shard(trees, n_points: int):
    """Lay the (already padded) point axis across devices."""
    n_dev = len(jax.devices())
    if n_dev <= 1 or n_points % n_dev != 0:
        return trees
    from jax.sharding import NamedSharding, PartitionSpec as P
    sharding = NamedSharding(make_sweep_mesh(), P("sweep"))
    return tuple(jax.device_put(t, sharding) for t in trees)


def _all_quiescent(st_b: SimState) -> jnp.ndarray:
    """True when no point can change any observable statistic anymore (the
    shared ``repro.core.system.quiescent`` fixed point, over the batch)."""
    return jnp.all(quiescent(st_b))


@functools.partial(jax.jit, static_argnums=(0, 4), donate_argnums=(1,))
def _scan_batch(sys: CodedMemorySystem, st_b: SimState, trace_b: Trace,
                tn_b: TunableParams, n_cycles: int) -> SimState:
    vstep = jax.vmap(sys.cycle_fn)

    # while_loop instead of a fixed-length scan: the drain bound ``n_cycles``
    # is a worst case (full serialization on one port); real sweeps quiesce
    # far earlier, and post-quiescence cycles are observable no-ops, so
    # early exit is bit-identical to running the bound out.
    def cond(carry):
        st, i = carry
        with jax.named_scope("loop.quiescence"):
            return (i < n_cycles) & ~_all_quiescent(st)

    def body(carry):
        st, i = carry
        st, _out = vstep(st, trace_b, tn_b)
        return st, i + 1

    st, _ = jax.lax.while_loop(cond, body, (st_b, jnp.int32(0)))
    return st


def summarize_batch(st_b: SimState,
                    n_points: Optional[int] = None) -> List[SimResult]:
    """Batched SimState → per-point SimResults in one device→host transfer.

    ``n_points`` strips the masked dummy rows a padded-for-sharding batch
    carries past the real points."""
    host = jax.device_get(st_b)
    n = np.asarray(host.done_cycle).shape[0] if n_points is None else n_points
    return [result_from_host(jax.tree.map(lambda x: x[b], host.mem),
                             host.done_cycle[b])
            for b in range(n)]


def _stack_priors(priors: Sequence, n_points: int):
    """Ragged per-point region-prior arrays → one -1-padded (B, K) array."""
    arrs = [np.asarray(pr if pr is not None else [], np.int32).reshape(-1)
            for pr in priors]
    k = max((a.size for a in arrs), default=0)
    if k == 0:
        return None
    out = np.full((n_points, k), -1, np.int32)
    for b, a in enumerate(arrs):
        out[b, :a.size] = a
    return jnp.asarray(out)


def run_batch(batch: GridBatch, traces: Optional[Sequence[Trace]] = None,
              shard: bool = True,
              region_priors: Optional[Sequence] = None,
              collect_telemetry: bool = False):
    """Evaluate one shape-compatible batch as a single device program.

    With ``collect_telemetry`` the return is ``(results, snapshots)`` where
    ``snapshots`` aligns with the batch points: a
    ``repro.obs.planes.TelemetrySnapshot`` per telemetry-on point, None for
    telemetry-off ones (the planes ride the same device program; collecting
    them costs one extra host transfer of a few small arrays per point)."""
    pts = batch.points
    pad = _pad_points(len(pts)) if shard else 0
    with span("sweep.batch", points=len(pts), pad=pad):
        # geometry indexing is traced only when this batch actually mixes
        # (region_size, n_regions) geometries; a uniform batch (trace/seed/
        # tunable/α sweeps at one r) compiles the static-indexing program —
        # masking costs nothing unless it is used
        traced = len({pt.derived_slots()[:2] for pt in pts}) > 1
        sys = system_for(pts[0], geometry_alloc=batch_geometry_alloc(pts),
                         traced_geometry=traced)
        with span("sweep.stack", points=len(pts) + pad):
            if traces is None:
                traces = [workloads.build_trace(pt, index=i)
                          for i, pt in zip(batch.indices, pts)]
            for pt, tr in zip(pts, traces):
                if tuple(tr.bank.shape) != (pt.n_cores, pt.length):
                    raise ValueError(
                        f"trace shape {tuple(tr.bank.shape)} does not match "
                        f"point geometry ({pt.n_cores}, {pt.length})")
            trace_b = workloads.stack_traces(traces)
            tn_b = stack_tunables(pts, sys.p.queue_depth)
            priors_b = (_stack_priors(region_priors, len(pts))
                        if region_priors is not None else None)
            fault_b = _stack_faults(pts, sys.p) if sys.p.faults else None
            if pad:
                trace_b = _replicate_tail(trace_b, pad)
                tn_b = _replicate_tail(tn_b, pad)
                if priors_b is not None:
                    priors_b = _replicate_tail(priors_b, pad)
                if fault_b is not None:
                    fault_b = _replicate_tail(fault_b, pad)
        with span("sweep.init", points=len(pts) + pad) as s:
            st_b = _batched_init(sys, tn_b, priors_b)
            if fault_b is not None:
                # install the per-point schedules over the vmapped init's
                # no-fault default (vmap can't thread the host-side plans)
                st_b = st_b._replace(mem=st_b.mem._replace(fault=fault_b))
            s.set_metadata(state_bytes=sum(
                x.nbytes for x in jax.tree.leaves(st_b)))
        if shard:
            with span("sweep.shard"):
                st_b, trace_b, tn_b = _maybe_shard((st_b, trace_b, tn_b),
                                                   len(pts) + pad)
        with span("sweep.dispatch"):
            st = _scan_batch(sys, st_b, trace_b, tn_b,
                             pts[0].resolved_cycles())
        with span("sweep.wait"):
            st = jax.block_until_ready(st)
        with span("sweep.summarize", points=len(pts)) as s:
            host = jax.device_get(st)
            # the points step in lockstep, so every point's cycle counter
            # holds the while-loop's trip count
            trips = int(np.max(host.mem.cycle))
            # the cells a cycle's write datapath has room for (one a port)
            # beside the writes a point served on average in a trip, and
            # the freshness cells a point gathers in a trip
            writes = int(np.sum(host.mem.served_writes[:len(pts)]))
            s.set_metadata(trips=trips,
                           write_slots=write_slots(sys.p).golden,
                           writes_per_trip=writes / max(len(pts) * trips, 1),
                           fresh_cells=fresh_cells(sys.p, sys.t))
            results = summarize_batch(host, n_points=len(pts))
        if not collect_telemetry:
            return results
        from repro.obs.planes import snapshot
        snaps = [snapshot(host, point=b) if host.mem.tele is not None
                 else None for b in range(len(pts))]
        return results, snaps


def run_points(points: Sequence[SweepPoint],
               traces: Optional[Sequence[Trace]] = None,
               shard: bool = True,
               region_priors: Optional[Sequence] = None,
               collect_telemetry: bool = False):
    """Evaluate an arbitrary sweep; results align with ``points`` order.

    ``region_priors`` aligns 1:1 with ``points``: each entry is None (cold
    start) or a ranked hot-region array warm-starting that point's dynamic
    coding unit (``repro.traces.profiler.TraceProfile.region_priors``).

    ``collect_telemetry`` returns ``(results, snapshots)`` — a per-point
    ``TelemetrySnapshot`` (None for telemetry-off points); see ``run_batch``.
    """
    if traces is not None and len(traces) != len(points):
        raise ValueError("traces must align 1:1 with points")
    if region_priors is not None and len(region_priors) != len(points):
        raise ValueError("region_priors must align 1:1 with points")
    results: List[Optional[SimResult]] = [None] * len(points)
    snaps: List = [None] * len(points)
    with span("sweep.call", points=len(points)) as s:
        batches = partition(points)
        s.set_metadata(partitions=len(batches))
        for batch in batches:
            btraces = ([traces[i] for i in batch.indices]
                       if traces is not None else None)
            bpriors = ([region_priors[i] for i in batch.indices]
                       if region_priors is not None else None)
            out = run_batch(batch, btraces, shard, bpriors,
                            collect_telemetry=collect_telemetry)
            bres, bsnaps = out if collect_telemetry else (out, None)
            for k, i in enumerate(batch.indices):
                results[i] = bres[k]
                if bsnaps is not None:
                    snaps[i] = bsnaps[k]
    if collect_telemetry:
        return results, snaps
    return results  # type: ignore[return-value]


def run_sweep(points: Sequence[SweepPoint],
              traces: Optional[Sequence[Trace]] = None,
              shard: bool = True,
              region_priors: Optional[Sequence] = None):
    """Evaluate a sweep and wrap it in a ``SweepResultSet`` (results store)."""
    from repro.sweep.results import SweepRecord, SweepResultSet
    res = run_points(points, traces=traces, shard=shard,
                     region_priors=region_priors)
    return SweepResultSet([SweepRecord(pt, r) for pt, r in zip(points, res)])


def clear_caches():
    """Drop memoized systems (and their jit caches) — mainly for tests."""
    _SYSTEMS.clear()
