"""Host spans and named scopes of the sweep engine (``repro.obs.spans``).

A profiled ``run_points`` call writes one ``repro:sweep.*`` span per phase,
nested as the engine runs them and carrying their counts as event stats;
the compiled loop names its device operations with five scopes; and
neither changes a result or compiles anything.
"""
import contextlib
import glob
import os
import re

import jax
import pytest
from conftest import SMALL_N_ROWS, SMALL_TRACE_LEN

from repro.obs.spans import span
from repro.sweep import engine
from repro.sweep.grid import SweepPoint, partition

PHASES = ("sweep.stack", "sweep.init", "sweep.shard", "sweep.dispatch",
          "sweep.wait", "sweep.summarize")
SCOPES = ("cycle.arbiter", "cycle.patterns", "cycle.recode",
          "cycle.dynamic", "loop.quiescence")

# two partitions (coded and uncoded) of two points each
POINTS = [SweepPoint(scheme=s, alpha=0.25, r=0.125, n_rows=SMALL_N_ROWS,
                     length=SMALL_TRACE_LEN, n_cores=4, trace=t, seed=1)
          for s in ("scheme_i", "uncoded") for t in ("banded", "zipf")]


@contextlib.contextmanager
def compiles():
    seen = []

    def listen(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            seen.append(secs)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        yield seen
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)


def repro_spans(trace_dir):
    """``[(name, start_ns, end_ns, stats)]`` of the ``repro:`` host spans
    in the newest profile under ``trace_dir``, in start order."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    pd = ProfileData.from_file(max(files, key=os.path.getmtime))
    out = [(e.name[len("repro:"):], e.start_ns, e.start_ns + e.duration_ns,
            dict(e.stats))
           for plane in pd.planes if plane.name.startswith("/host:")
           for line in plane.lines for e in line.events
           if e.name.startswith("repro:")]
    return sorted(out, key=lambda s: s[1])


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """Results of a warm call with the profiler off, then on (counting the
    compiles of the profiled call), and the profiled call's spans."""
    off = engine.run_points(POINTS)
    trace_dir = str(tmp_path_factory.mktemp("profile"))
    with compiles() as seen, jax.profiler.trace(trace_dir):
        on = engine.run_points(POINTS)
    return off, on, seen, repro_spans(trace_dir)


def test_run_points_writes_every_sweep_span(profiled):
    _, results, _, spans = profiled
    calls = [s for s in spans if s[0] == "sweep.call"]
    batches = [s for s in spans if s[0] == "sweep.batch"]
    assert len(calls) == 1
    _, c0, c1, cstats = calls[0]
    assert cstats == {"points": len(POINTS),
                      "partitions": len(partition(POINTS))}
    assert len(batches) == cstats["partitions"]
    assert sum(b[3]["points"] for b in batches) == len(POINTS)
    for batch, (name, b0, b1, bstats) in zip(partition(POINTS), batches):
        assert c0 <= b0 <= b1 <= c1
        assert bstats == {"points": len(batch.points), "pad": 0}
        inside = [s for s in spans if b0 <= s[1] and s[2] <= b1
                  and s[0] != "sweep.batch"]
        # each phase once, in the engine's order, one after the other
        assert [s[0] for s in inside] == list(PHASES)
        assert all(a[2] <= b[1] for a, b in zip(inside, inside[1:]))
        stats = {s[0]: s[3] for s in inside}
        assert stats["sweep.stack"]["points"] == len(batch.points)
        # the loop's trip count: one trip past the last point's done cycle
        # where nothing is left to drain after it (uncoded), at least that
        last = max(results[i].cycles for i in batch.indices)
        trips = stats["sweep.summarize"]["trips"]
        if batch.points[0].scheme == "uncoded":
            assert trips == last + 1
        else:
            assert trips >= last + 1


def test_init_counts_the_state_it_puts_on_the_device(profiled):
    """``sweep.init`` counts the bytes of the batched simulator state:
    every leaf of the vmapped init, as large as the memory simulated."""
    _, _, _, spans = profiled
    inits = [s[3] for s in spans if s[0] == "sweep.init"]
    for batch, stats in zip(partition(POINTS), inits):
        sys_ = engine.system_for(batch.points[0])
        tn_b = engine.stack_tunables(batch.points, sys_.p.queue_depth)
        st_b = jax.eval_shape(
            lambda tn, s=sys_: engine._batched_init(s, tn), tn_b)
        want = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(st_b))
        assert stats == {"points": len(batch.points), "state_bytes": want}
        # the three bank-wide int32 arrays alone: fresh_loc, banks, golden
        n = len(batch.points)
        assert want > 3 * 4 * n * sys_.p.n_data * SMALL_N_ROWS


def test_summarize_counts_the_write_slots_a_trip_uses(profiled):
    """``sweep.summarize`` gives the cells a cycle's write datapath has
    room for (``write_slots``, one a port) and the writes a point served
    on average in a loop trip (``writes_per_trip``), which they bound."""
    from repro.core.controller import write_slots

    _, results, _, spans = profiled
    stats = [s[3] for s in spans if s[0] == "sweep.summarize"]
    for batch, st in zip(partition(POINTS), stats):
        p = engine.system_for(batch.points[0]).p
        assert st["write_slots"] == write_slots(p).golden == p.n_ports
        writes = sum(results[i].served_writes for i in batch.indices)
        assert writes > 0
        assert st["writes_per_trip"] == pytest.approx(
            writes / (len(batch.points) * st["trips"]))
        assert 0 < st["writes_per_trip"] <= st["write_slots"]


def test_profiling_changes_no_result_and_compiles_nothing(profiled):
    off, on, seen, _ = profiled
    assert on == off
    assert seen == []


def test_scan_program_carries_every_scope():
    """Each scope lands in the locations of the lowered ``_scan_batch``
    program, from which XLA writes each operation's ``op_name``."""
    batch = partition(POINTS[:2])[0]
    pts = batch.points
    sys = engine.system_for(pts[0])
    from repro.sweep import workloads
    trace_b = workloads.stack_traces([workloads.build_trace(pt, index=i)
                                      for i, pt in zip(batch.indices, pts)])
    tn_b = engine.stack_tunables(pts, sys.p.queue_depth)
    st_b = engine._batched_init(sys, tn_b)
    text = engine._scan_batch.lower(
        sys, st_b, trace_b, tn_b, pts[0].resolved_cycles()).as_text(
            debug_info=True)
    for scope in SCOPES:
        assert re.search(f'["/]{re.escape(scope)}["/]', text), scope


def test_span_counts_read_back_as_event_stats(tmp_path):
    """Counts given when the span opens and through ``set_metadata`` are
    the stats of the one event; with no profiler a span is a no-op."""
    with span("unit.untraced", n=1) as s:
        s.set_metadata(m=2)
    with jax.profiler.trace(str(tmp_path)):
        with span("unit.outer", points=40) as s:
            with span("unit.inner"):
                jax.numpy.arange(4).sum().block_until_ready()
            s.set_metadata(trips=7)
    spans = repro_spans(str(tmp_path))
    assert [(n, st) for n, _, _, st in spans] == [
        ("unit.outer", {"points": 40, "trips": 7}), ("unit.inner", {})]
    (_, o0, o1, _), (_, i0, i1, _) = spans
    assert o0 <= i0 <= i1 <= o1
