"""Observability layer (repro.obs): planes, manifests, timeline, artifacts.

Three contracts, in test order:

1. **Telemetry off is free and invisible** — the default ``MemState`` carries
   ``tele=None`` (an empty pytree node), the legacy field layout is frozen,
   a telemetry-off sweep compiles the same number of programs as before, and
   the telemetry-on run's ``SimResult`` equals the off run's bit for bit.
2. **Telemetry on is ground-truthed** — every plane sums exactly to the
   engine's own aggregates and matches the NumPy golden model's independent
   derivation (conformance), including under forced queue-full stalls.
3. **Artifacts carry provenance** — manifests have the promised fields, root
   BENCH blobs append (never overwrite) history, the mirror dedups, the
   manifest CI check catches stripped blobs, and the timeline and report
   exporters produce non-empty, loadable artifacts.
"""
import json
import os

import jax
import numpy as np
import pytest
from conftest import (assert_state_matches_oracle, oracle_twin, rand_trace,
                      SMALL_N_ROWS, SMALL_TRACE_LEN)

from repro.core.codes import get_tables
from repro.core.state import MemParams, MemState, make_params, make_tunables
from repro.core.system import CodedMemorySystem
from repro.obs import planes
from repro.obs.planes import TelemetrySnapshot, snapshot
from repro.sweep.engine import run_points
from repro.sweep.grid import SweepPoint, static_signature


def _system(scheme="scheme_i", n_rows=SMALL_N_ROWS, alpha=0.25, r=0.125,
            n_cores=4, telemetry=False, **kw):
    t = get_tables(scheme)
    p = make_params(t, n_rows=n_rows, alpha=alpha, r=r, recode_cap=8,
                    telemetry=telemetry, **kw)
    tn = make_tunables(queue_depth=p.queue_depth, select_period=16)
    return CodedMemorySystem(t, p, n_cores=n_cores, tunables=tn)


def _trace(sys_, seed=7, length=20, write_frac=0.45):
    rng = np.random.default_rng(seed)
    return rand_trace(rng, sys_.n_cores, length, sys_.p.n_data, sys_.p.n_rows,
                      write_frac=write_frac)


# --------------------------------------------------- 1. telemetry off is free
def test_off_state_carries_no_planes():
    """Disabled telemetry is a ``None`` leaf — the scan carry has the same
    pytree structure as before the feature existed, which is what makes the
    compiled program identical (no dead counter traffic to DCE away)."""
    sys_ = _system(telemetry=False)
    st = sys_.init()
    assert st.mem.tele is None
    assert sys_.p.telemetry is False


def test_field_layout_frozen():
    """The observability fields sit strictly LAST in MemParams/MemState (so
    positional construction of the legacy prefix keeps meaning what it
    meant), and the legacy prefix itself is locked — a rename or reorder
    here silently breaks checkpoint/pytree compatibility."""
    assert MemParams._fields[-2:] == ("telemetry", "faults")
    assert MemState._fields[-2:] == ("tele", "fault")
    assert MemParams._field_defaults["telemetry"] is False
    assert MemParams._field_defaults["faults"] is False
    assert MemState._field_defaults["tele"] is None
    assert MemState._field_defaults["fault"] is None
    # telemetry forces a distinct compiled program via the sweep static key
    # (its slot sits just before the trailing faults flag)
    pt = SweepPoint(n_rows=SMALL_N_ROWS, length=SMALL_TRACE_LEN)
    on, off = static_signature(pt.replace(telemetry=True)), static_signature(pt)
    assert on != off and on[:-2] == off[:-2] and on[-1] == off[-1]


def test_on_off_results_identical():
    """Turning the planes on must not change a single observable statistic:
    same SimResult, and every non-telemetry state leaf bit-identical."""
    sys_off = _system(telemetry=False)
    sys_on = _system(telemetry=True)
    tr = _trace(sys_off)
    n = 96
    st_off, _ = sys_off._run(sys_off.init(), tr, n)
    st_on, _ = sys_on._run(sys_on.init(), tr, n)
    assert sys_off.summarize(st_off) == sys_on.summarize(st_on)
    off_leaves = jax.device_get(st_off.mem._replace(tele=None))
    on_leaves = jax.device_get(st_on.mem._replace(tele=None))
    for name, a, b in zip(MemState._fields, off_leaves, on_leaves):
        if isinstance(a, tuple):
            continue    # nested pytrees compared leaf-wise below anyway
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"leaf {name!r}")


def test_off_sweep_compile_count_unchanged(sweep_compile_count):
    """A telemetry-off grid costs exactly the programs it cost before the
    feature; adding a telemetry-on twin point adds exactly one program."""
    from repro.sweep.engine import clear_caches
    clear_caches()
    base = SweepPoint(n_rows=SMALL_N_ROWS, length=SMALL_TRACE_LEN,
                      alpha=0.25, r=0.125)
    pts_off = [base.replace(seed=s) for s in range(3)]
    n0 = sweep_compile_count()
    run_points(pts_off)
    assert sweep_compile_count() - n0 == 1
    run_points(pts_off + [base.replace(seed=9, telemetry=True)])
    assert sweep_compile_count() - n0 == 2


# ------------------------------------------- 2. telemetry on is ground-truthed
def _run_with_planes(write_frac=0.45, seed=7, **kw):
    sys_ = _system(telemetry=True, **kw)
    tr = _trace(sys_, seed=seed, write_frac=write_frac)
    st, _ = sys_._run(sys_.init(), tr, 96)
    return sys_, st, sys_.summarize(st), snapshot(st)


def test_plane_sums_match_aggregates():
    """Each plane partitions an engine aggregate exactly — stalls by (bank,
    cause), served reads by (core, provenance), served writes by (core,
    mode), latency sums by histogram mass."""
    _, st, res, snap = _run_with_planes()
    assert snap.stall_total() == res.stall_cycles
    assert snap.served_reads() == res.served_reads
    assert snap.served_writes() == res.served_writes
    assert snap.degraded_reads() == res.degraded_reads
    assert snap.parked_writes() == res.parked_writes
    assert int(snap.lat_hist_read.sum()) == res.served_reads
    assert int(snap.lat_hist_write.sum()) == res.served_writes
    d = snap.as_dict()
    assert d["derived"]["served_reads"] == res.served_reads
    assert "rq_core" not in d   # provenance carriers are not counters


def test_stall_planes_under_queue_pressure():
    """Force queue-full stalls (tiny queues, all traffic on two banks) and
    check the per-(bank, cause) attribution still sums exactly — the planes
    must count the stall storm, not just the calm case."""
    sys_ = _system(telemetry=True, n_cores=8, queue_depth=2)
    rng = np.random.default_rng(5)
    tr = rand_trace(rng, 8, 24, sys_.p.n_data, sys_.p.n_rows, write_frac=0.3)
    tr = tr._replace(
        bank=(tr.bank % 2).astype(tr.bank.dtype),
        valid=np.ones_like(np.asarray(tr.valid)))
    st, _ = sys_._run(sys_.init(), tr, 128)
    res, snap = sys_.summarize(st), snapshot(st)
    assert res.stall_cycles > 0, "stress workload failed to stall"
    assert snap.stall_total() == res.stall_cycles
    # all traffic targets banks {0, 1}: no other bank may record a stall
    assert int(np.asarray(snap.stall_cause)[2:].sum()) == 0


@pytest.mark.parametrize("scheme,write_frac", [
    ("scheme_i", 0.45), ("uncoded", 0.7),
    pytest.param("scheme_ii", 0.45, marks=pytest.mark.slow),
])
def test_telemetry_conformance(scheme, write_frac):
    """The golden model re-derives every plane independently (its own queue
    provenance carriers, its own latency binning); full-state conformance
    now includes them bit for bit."""
    sys_ = _system(scheme, telemetry=True)
    om = oracle_twin(sys_)
    tr = _trace(sys_, seed=11, write_frac=write_frac)
    st, _ = sys_._run(sys_.init(), tr, 96)
    ost = om.run(tr, 96)
    assert st.mem.tele is not None and ost.tele is not None
    assert_state_matches_oracle(st, ost, f"telemetry {scheme}")


def test_lat_bin_matches_oracle_binning():
    """Production threshold-count binning == oracle bit_length binning over
    the whole meaningful latency range (two independent derivations)."""
    from repro.oracle.model import _lat_bin
    lats = np.arange(0, 1 << 16, dtype=np.int32)
    got = np.asarray(planes.lat_bin(lats))
    want = np.asarray([_lat_bin(int(v)) for v in lats])
    np.testing.assert_array_equal(got, want)


def test_sweep_collect_telemetry():
    """``run_points(collect_telemetry=True)`` returns per-point snapshots
    aligned with results (None for off points) across mixed batches."""
    base = SweepPoint(n_rows=SMALL_N_ROWS, length=SMALL_TRACE_LEN,
                      alpha=0.25, r=0.125)
    pts = [base, base.replace(telemetry=True, seed=1),
           base.replace(telemetry=True, scheme="uncoded", alpha=1.0)]
    results, snaps = run_points(pts, collect_telemetry=True)
    assert snaps[0] is None
    for res, snap in zip(results[1:], snaps[1:]):
        assert isinstance(snap, TelemetrySnapshot)
        assert snap.stall_total() == res.stall_cycles
        assert snap.served_reads() == res.served_reads


# ----------------------------------------------- 3. artifacts carry provenance
def test_run_manifest_fields():
    from repro.obs.runlog import MANIFEST_SCHEMA, run_manifest
    pt = SweepPoint(n_rows=SMALL_N_ROWS, telemetry=True)
    man = run_manifest(config=pt, timings={"warm_s": 0.123456})
    assert man["schema"] == MANIFEST_SCHEMA
    assert len(man["git_sha"]) == 40 or man["git_sha"] == "unknown"
    assert {"python", "jax", "numpy"} <= set(man["versions"])
    assert man["devices"]["n_devices"] >= 1
    assert man["config"]["static_signature"] == list(static_signature(pt))
    assert man["config"]["telemetry"] is True
    assert man["timings"]["warm_s"] == 0.1235
    json.dumps(man)     # the whole block must be JSON-clean


@pytest.fixture
def bench_dirs(tmp_path, monkeypatch):
    """Point benchmarks.common at a scratch repo root + artifact dir."""
    import benchmarks.common as common
    art = tmp_path / "experiments" / "bench"
    monkeypatch.setattr(common, "REPO_ROOT", str(tmp_path))
    monkeypatch.setattr(common, "ART_DIR", str(art))
    return common, tmp_path, art


def test_emit_appends_root_history(bench_dirs):
    """Re-running a root benchmark APPENDS to the trajectory history; the
    previous runs' entries survive (this used to be an overwrite)."""
    common, root, art = bench_dirs
    rows = [{"path": "batched (warm)", "sim_cycles/s": 100.0}]
    common.emit("BENCH_x", rows, root=True, headline={"tput": 100.0})
    common.emit("BENCH_x", [{"path": "batched (warm)",
                             "sim_cycles/s": 120.0}],
                root=True, headline={"tput": 120.0})
    blob = json.loads((root / "BENCH_x.json").read_text())
    assert isinstance(blob["manifest"], dict)
    assert [h["headline"]["tput"] for h in blob["history"]] == [100.0, 120.0]
    assert blob["rows"][0]["sim_cycles/s"] == 120.0   # rows: latest run


def test_mirror_merges_instead_of_clobbering(bench_dirs):
    """``mirror_bench_to_root`` preserves existing root history and dedups
    the entry already appended by ``emit(root=True)``."""
    common, root, art = bench_dirs
    common.emit("BENCH_y", [{"v": 1}], root=True, headline={"v": 1})
    common.emit("BENCH_y", [{"v": 2}], root=True, headline={"v": 2})
    common.mirror_bench_to_root()
    hist = json.loads((root / "BENCH_y.json").read_text())["history"]
    assert [h["headline"]["v"] for h in hist] == [1, 2]   # no duplicate


def test_load_baseline_reads_new_schema(bench_dirs, monkeypatch):
    """bench_cycles' regression gate still finds its number in the
    manifest-era blob layout."""
    import benchmarks.bench_cycles as bc
    common, root, art = bench_dirs
    common.emit("BENCH_cycle_throughput",
                [{"path": "batched (warm)", "sim_cycles/s": 4321.0}],
                root=True)
    monkeypatch.setattr(bc, "BASELINE_PATH",
                        str(root / "BENCH_cycle_throughput.json"))
    assert bc.load_baseline() == 4321.0


def test_check_bench_manifests(tmp_path):
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "scripts"))
    from check_bench_manifests import check
    # the real repo root must pass (CI runs exactly this)
    repo_root = os.path.join(os.path.dirname(__file__), "..")
    assert check(repo_root) == []
    # a stripped blob must be caught, with the filename named
    (tmp_path / "BENCH_bad.json").write_text(json.dumps({"rows": []}))
    problems = check(str(tmp_path))
    assert any("BENCH_bad" in p and "manifest" in p for p in problems)
    assert check(str(tmp_path / "empty-missing")) != []


def test_timeline_export(tmp_path):
    """Host-stepped replay produces a loadable Chrome trace with span,
    counter, and metadata events, and the manifest rides in otherData."""
    from repro.obs.timeline import export_chrome_trace, record_timeline
    sys_ = _system(telemetry=False, n_cores=4)
    tr = _trace(sys_, seed=3, length=16)
    events = record_timeline(sys_, tr, chunk_len=8, max_cycles=256)
    phases = {e["ph"] for e in events}
    assert "M" in phases and "C" in phases and "i" in phases
    spans = [e for e in events if e["ph"] == "B"]
    ends = [e for e in events if e["ph"] == "E"]
    assert len(spans) == len(ends)      # every span closed
    path = export_chrome_trace(events, str(tmp_path / "tl.json"))
    blob = json.loads(open(path).read())
    assert blob["traceEvents"] and blob["otherData"]["manifest"]["git_sha"]
    ts = [e["ts"] for e in blob["traceEvents"] if "ts" in e]
    assert ts == sorted(ts)             # monotonic timeline


def test_stall_report_smoke(tmp_path):
    """End-to-end report on a trimmed fig18: files written, planes checked
    against aggregates internally, JSON twin machine-readable."""
    from repro.obs.report import stall_report
    out = stall_report("paper_fig18", out_dir=str(tmp_path), smoke=True)
    md = open(out["md_path"]).read()
    assert "Per-bank heatmap" in md and "uncoded" in md
    blob = json.loads(open(out["json_path"]).read())
    assert blob["manifest"]["git_sha"]
    assert len(blob["points"]) == len(out["points"]) >= 2
    for prow, res in zip(blob["points"], out["results"]):
        assert prow["telemetry"]["derived"]["stall_total"] \
            == res.stall_cycles
