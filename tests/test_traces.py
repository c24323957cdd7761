"""repro.traces: chunked replay must be bit-identical to single-shot run(),
ingestion formats must round-trip, the profiler must recover the synthetic
generators' band structure, and its region-priors must never hurt."""
import os

import numpy as np
import pytest

import jax.numpy as jnp

from conftest import rand_trace
from repro.core.codes import get_tables
from repro.core.state import make_params, make_tunables
from repro.core.system import CodedMemorySystem, Trace, drain_bound
from repro.sim.trace import TraceSpec, addr_to_bank_row, banded_trace
from repro.traces import (TraceSource, chunk_iter, load_npz, load_trace,
                          profile_trace, requests_to_trace, save_npz,
                          stream_file, stream_replay, stream_replay_points,
                          strip_windows)
from repro.traces.formats import iter_gem5, iter_ramulator

DATA = os.path.join(os.path.dirname(__file__), "data")

N_ROWS, N_CORES, TLEN = 32, 3, 10


def _system(alpha=0.25, r=0.125):
    t = get_tables("scheme_i")
    p = make_params(t, n_rows=N_ROWS, alpha=alpha, r=r, recode_cap=8)
    return CodedMemorySystem(t, p, n_cores=N_CORES,
                             tunables=make_tunables(select_period=8))


# one shared system (= one jit cache) for the whole module
_SYS = _system()


def _split(trace: Trace, cuts):
    """Cut a trace into chunks at the given time offsets."""
    arrs = [np.asarray(x) for x in trace]
    T = arrs[0].shape[1]
    prev = 0
    for c in list(cuts) + [T]:
        if c > prev:
            yield Trace(*(jnp.asarray(a[:, prev:c]) for a in arrs))
            prev = c


# ------------------------------------------------------------ chunked replay
@pytest.mark.parametrize("lazy", [False, True], ids=["trace", "lazy"])
@pytest.mark.parametrize("chunk_len", [1, 3, 10, 14])
def test_stream_replay_bit_identical(chunk_len, lazy):
    """Any staging chunk length — including 1 and tails longer than the
    trace — replays bit-identically to single-shot run(), from an in-memory
    trace and from a lazy iterator whose length is unknown until it ends
    (each stream's end must still reach the replay on time)."""
    sys_ = _SYS
    for seed in (0, 5):
        rng = np.random.default_rng(seed)
        trace = rand_trace(rng, N_CORES, TLEN, sys_.p.n_data, N_ROWS)
        single = sys_.run(trace, drain_bound(N_CORES, TLEN))
        source = _split(trace, []) if lazy else trace
        got = stream_replay(sys_, source, chunk_len=chunk_len)
        assert strip_windows(got) == single, seed


def test_stream_replay_source_splits_invisible(compile_guard):
    """The rolling-window source normalizes arbitrary ingest chunking: the
    same staging length over differently-split sources is identical — and
    shares one compiled chunk program (ingest chunking must never reach
    the compile key)."""
    sys_ = _SYS
    rng = np.random.default_rng(9)
    trace = rand_trace(rng, N_CORES, TLEN, sys_.p.n_data, N_ROWS)
    single = sys_.run(trace, drain_bound(N_CORES, TLEN))
    splits = ([2], [1, 2, 3, 4, 9], [5], [])
    with compile_guard("stream", max_compiles=None) as g:
        got = stream_replay(sys_, _split(trace, splits[0]), chunk_len=4)
        assert strip_windows(got) == single, splits[0]
        first = g.compiles()
        for cuts in splits[1:]:
            got = stream_replay(sys_, _split(trace, cuts), chunk_len=4)
            assert strip_windows(got) == single, cuts
    assert g.compiles() == first, "ingest split leaked into the compile key"


def test_stream_replay_window_stats_account_for_all_latency():
    """The per-window latency series partitions the scalar sums exactly."""
    sys_ = _SYS
    rng = np.random.default_rng(3)
    trace = rand_trace(rng, N_CORES, TLEN, sys_.p.n_data, N_ROWS)
    res = stream_replay(sys_, trace, chunk_len=3)
    n_r = sum(n for n, _ in res.window_read_latency)
    n_w = sum(n for n, _ in res.window_write_latency)
    assert n_r == res.served_reads and n_w == res.served_writes
    tot_r = sum(n * avg for n, avg in res.window_read_latency)
    assert tot_r == pytest.approx(res.avg_read_latency * max(n_r, 1))


def test_window_deltas_sum_to_totals_fig18_workload():
    """On a fig18-style point (banded trace, coded scheme, telemetry on)
    the per-window series partitions every run total exactly: served
    counts, latency sums, and — with the planes enabled — the per-window
    log2 latency-histogram deltas, whose mass equals each window's count
    and whose sum equals the final histogram."""
    from repro.obs.planes import HIST_BINS, snapshot
    from repro.sweep.engine import system_for
    from repro.sweep.workloads import build_trace
    from repro.sweep import SweepPoint
    pt = SweepPoint(scheme="scheme_i", trace="banded", alpha=0.25, r=0.05,
                    n_rows=64, length=32, select_period=16, telemetry=True)
    sys_ = system_for(pt)
    res = stream_replay(sys_, build_trace(pt), chunk_len=8,
                        tn=sys_.tunables)
    assert len(res.window_read_latency) > 1, "need multiple windows"
    for series, total, avg in (
            (res.window_read_latency, res.served_reads,
             res.avg_read_latency),
            (res.window_write_latency, res.served_writes,
             res.avg_write_latency)):
        assert sum(w[0] for w in series) == total
        assert sum(w[0] * w[1] for w in series) \
            == pytest.approx(avg * max(total, 1))
        # telemetry windows carry the histogram delta as a 3rd element
        hists = np.array([w[2] for w in series])
        assert hists.shape[1] == HIST_BINS
        assert (hists >= 0).all()
        np.testing.assert_array_equal(hists.sum(axis=1),
                                      [w[0] for w in series])
    # ... and the window deltas reassemble the final device-side planes
    trace = build_trace(pt)
    st, _ = sys_._run(sys_.init(), trace,
                      drain_bound(sys_.n_cores, trace.bank.shape[1]))
    snap = snapshot(st)
    np.testing.assert_array_equal(
        np.array([w[2] for w in res.window_read_latency]).sum(axis=0),
        snap.lat_hist_read)
    np.testing.assert_array_equal(
        np.array([w[2] for w in res.window_write_latency]).sum(axis=0),
        snap.lat_hist_write)


def test_stream_replay_batched_matches_engine():
    """The chunk axis composes with the engine's point axis: a whole
    shape-compatible batch streams as one vmapped program, per-point
    bit-identical to the batched single-shot engine."""
    from repro.sweep import SweepPoint, grid, run_points
    from repro.sweep.workloads import build_trace
    base = SweepPoint(scheme="scheme_i", alpha=0.25, r=0.125, n_rows=N_ROWS,
                      n_cores=N_CORES, n_banks=8, length=TLEN,
                      select_period=16)
    pts = grid(base, alpha=(0.25, 0.5), seed=(0, 1))
    traces = [build_trace(pt) for pt in pts]
    want = run_points(pts, traces=traces)
    got = stream_replay_points(pts, traces, chunk_len=4)
    assert [strip_windows(g) for g in got] == want


@pytest.mark.slow
@pytest.mark.timeout(600)   # two full compiles on a forced 4-device host
def test_stream_points_padded_sharding_multidevice_subprocess():
    """Multi-device chunked replay: a streamed batch whose size does NOT
    divide the device count is padded with masked replica points, sharded
    across a forced 4-device host every chunk step, and returns the same
    per-point results as the unsharded single-shot engine (replicas
    stripped)."""
    import subprocess
    import sys

    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
assert len(jax.devices()) == 4
from repro.sweep import SweepPoint, grid, run_points
from repro.sweep.engine import clear_caches
from repro.traces import stream_replay_points, strip_windows

BASE = SweepPoint(scheme="scheme_i", alpha=0.25, r=0.125, n_rows=32,
                  n_cores=3, n_banks=8, length=10, select_period=16)
pts = grid(BASE, alpha=(0.25, 0.5), r=(0.125, 0.25), seed=(0, 1))[:6]
assert len(pts) % 4 != 0          # forces the pad-to-device-multiple path
from repro.sweep.workloads import build_trace
traces = [build_trace(pt) for pt in pts]
streamed = stream_replay_points(pts, traces, chunk_len=4, shard=True)
clear_caches()                    # fresh program, no sharding
want = run_points(pts, traces=traces, shard=False)
assert len(streamed) == len(pts)
for i, (a, b) in enumerate(zip(streamed, want)):
    assert strip_windows(a) == b, (i, a, b)
print("STREAM_SHARDED_OK")
"""
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "STREAM_SHARDED_OK" in out.stdout, out.stdout + out.stderr


# -------------------------------------------------------- hypothesis variant
try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                       # pragma: no cover
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    @settings(max_examples=10, deadline=None)
    @given(st.integers(0, 2**31 - 1),
           st.sampled_from([1, 2, 3, 5, 7, 10, 13]),
           st.lists(st.integers(1, TLEN - 1), max_size=4, unique=True))
    def test_stream_replay_random_splits_hypothesis(seed, chunk_len, cuts):
        """Random traces × random source splits × random staging lengths:
        streamed == single-shot, bit for bit (the oracle-anchored variant
        lives in tests/test_conformance.py)."""
        sys_ = _SYS
        rng = np.random.default_rng(seed)
        trace = rand_trace(rng, N_CORES, TLEN, sys_.p.n_data, N_ROWS)
        single = sys_.run(trace, drain_bound(N_CORES, TLEN))
        got = stream_replay(sys_, _split(trace, sorted(cuts)),
                            chunk_len=chunk_len)
        assert strip_windows(got) == single


# ------------------------------------------------------------------ source
def test_trace_source_rolling_window_trims():
    """The rolling window holds only (spread + stage) columns: staging at
    advanced positions drops the consumed prefix."""
    rng = np.random.default_rng(1)
    trace = rand_trace(rng, 2, 64, 4, 16)
    src = TraceSource.from_chunks(chunk_iter(trace, 8), prefetch=False)
    src.stage(np.array([0, 0]), 4)
    assert src.base == 0
    src.stage(np.array([40, 42]), 4)
    assert src.base == 40                     # consumed columns were dropped
    buffered = src._buf[0].shape[1]
    assert buffered <= 16                     # spread (2) + stage, chunk-rounded
    # staging is position-exact despite the trim
    chunk, se = src.stage(np.array([40, 42]), 4)
    np.testing.assert_array_equal(np.asarray(chunk.row)[0],
                                  np.asarray(trace.row)[0, 40:44])
    np.testing.assert_array_equal(np.asarray(chunk.row)[1],
                                  np.asarray(trace.row)[1, 42:46])


def test_trace_source_prefetch_propagates_ingest_errors():
    """A failed ingest must fail the replay, not masquerade as a short
    stream (the background prefetch thread relays its exception)."""
    def bad_chunks():
        rng = np.random.default_rng(0)
        yield rand_trace(rng, 2, 4, 4, 16)
        raise ValueError("malformed line 17")

    src = TraceSource.from_chunks(bad_chunks(), prefetch=True)
    with pytest.raises(ValueError, match="malformed line 17"):
        src.stage(np.array([0, 0]), 64)   # needs data past the first chunk


def test_requests_to_trace_refuses_truncation():
    """A too-small ``length`` must raise, not silently drop the stream's
    tail and report results for a trace that never fully replayed."""
    with pytest.raises(ValueError, match="stream has 10"):
        requests_to_trace(np.arange(10), np.zeros(10, bool), n_cores=2,
                          length=3)
    from repro.sweep.workloads import build_trace, file_point
    # and a file: sweep point whose length is too small names the point
    lines = "".join(f"{i} R\n" for i in range(40))
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "big.trace")
        with open(path, "w") as f:
            f.write(lines)
        pt = file_point(os.path.join(DATA, "tiny_trace.npz")).replace(
            trace=f"file:{path}", length=2, n_cores=2, suite="s")
        with pytest.raises(ValueError) as ei:
            build_trace(pt, index=7)
        assert "[7]" in str(ei.value) and "stream has 40" in str(ei.value)


def test_file_point_rejects_mismatched_geometry(tmp_path):
    """An .npz mapped for a different memory geometry must fail loudly —
    inside jit the out-of-range rows would clamp and corrupt results."""
    from repro.sweep.workloads import build_trace, file_point
    rng = np.random.default_rng(0)
    path = save_npz(os.path.join(tmp_path, "big.npz"),
                    rand_trace(rng, 2, 6, 8, 512))   # rows up to 511
    pt = file_point(path, n_rows=64, n_banks=8)      # but a 64-row system
    with pytest.raises(ValueError, match="different memory geometry"):
        build_trace(pt)


def test_trace_source_stream_end_marks_tails():
    rng = np.random.default_rng(2)
    trace = rand_trace(rng, 2, 10, 4, 16)
    src = TraceSource.from_trace(trace)
    _, se = src.stage(np.array([0, 8]), 4)
    se = np.asarray(se)
    assert se[0] > 4          # more data behind the buffer
    assert se[1] == 2         # stream ends inside: 2 staged requests remain
    assert not src.exhausted(np.array([10, 9]))
    assert src.exhausted(np.array([10, 10]))


# ------------------------------------------------------------------ formats
def test_ramulator_fixture_golden():
    reqs = list(iter_ramulator(os.path.join(DATA, "tiny_ramulator.trace")))
    assert reqs == [(0, False), (5, True), (17, False), (3, True),
                    (9, False), (12, False)]
    tr = requests_to_trace(*zip(*reqs), n_cores=2, n_banks=4, n_rows=8)
    bank, row = addr_to_bank_row(np.array([0, 5, 17, 3, 9, 12]), 4, 8)
    # round-robin deal: request i -> core i % 2, slot i // 2
    np.testing.assert_array_equal(np.asarray(tr.bank),
                                  bank.reshape(3, 2).T)
    np.testing.assert_array_equal(np.asarray(tr.row),
                                  row.reshape(3, 2).T)
    np.testing.assert_array_equal(np.asarray(tr.is_write),
                                  [[False, False, False], [True, True, False]])
    assert np.asarray(tr.valid).all()


def test_gem5_fixture_golden():
    reqs = list(iter_gem5(os.path.join(DATA, "tiny_gem5.gem5")))
    assert reqs == [(0x000, False), (0x040, True), (0x080, False),
                    (0x100, True), (0x140, False)]
    tr = load_trace(os.path.join(DATA, "tiny_gem5.gem5"), n_cores=1,
                    n_banks=4, n_rows=8, line_bytes=64)
    np.testing.assert_array_equal(np.asarray(tr.bank), [[0, 1, 2, 0, 1]])
    np.testing.assert_array_equal(np.asarray(tr.row), [[0, 0, 0, 1, 1]])
    np.testing.assert_array_equal(np.asarray(tr.is_write),
                                  [[False, True, False, True, False]])


def test_npz_fixture_roundtrip_and_replay():
    """The canonical .npz form is lossless, and an ingested file replays
    through the batched sweep engine exactly like its in-memory original."""
    path = os.path.join(DATA, "tiny_trace.npz")
    tr = load_npz(path)
    spec = TraceSpec(n_cores=4, length=12, n_banks=8, n_rows=64, seed=7)
    want = banded_trace(spec)
    for a, b in zip(tr, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_npz_save_load_roundtrip(tmp_path):
    rng = np.random.default_rng(4)
    trace = rand_trace(rng, 3, 9, 8, 32)
    path = save_npz(os.path.join(tmp_path, "t.npz"), trace)
    back = load_npz(path)
    for a, b in zip(back, trace):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_stream_file_matches_whole_file_load(tmp_path):
    """Lazy chunked file reading deals requests and synthesizes payloads
    exactly like a whole-file load — chunk boundaries are invisible."""
    lines = [f"{16 * i + (i % 5)} {'W' if i % 3 == 0 else 'R'}\n"
             for i in range(23)]
    path = os.path.join(tmp_path, "long.trace")
    with open(path, "w") as f:
        f.writelines(lines)
    whole = load_trace(path, n_cores=2, n_banks=4, n_rows=32)
    for chunk_len in (4, 9):       # 9: short tail chunk (23 = 18 + 5 reqs)
        chunks = list(stream_file(path, chunk_len, n_cores=2, n_banks=4,
                                  n_rows=32))
        cat = [np.concatenate([np.asarray(getattr(c, f)) for c in chunks],
                              axis=1) for f in Trace._fields]
        # the tail chunk is SHORT, not padded: the concatenation must equal
        # the whole-file load column for column (a padded tail would append
        # idle columns that delay the replay's completion cycle)
        for name, a, b in zip(Trace._fields, cat, whole):
            np.testing.assert_array_equal(a, np.asarray(b),
                                          err_msg=f"{name} chunk={chunk_len}")


def test_file_point_rides_sweep_engine():
    """A file: point flows through partition/batch/replay like any other."""
    from repro.sim.ramulator import simulate
    from repro.sweep import run_points
    from repro.sweep.workloads import build_trace, file_point
    path = os.path.join(DATA, "tiny_trace.npz")
    pt = file_point(path, alpha=0.25, r=0.125, n_rows=64, select_period=16)
    assert (pt.n_cores, pt.length) == (4, 12)
    tr = build_trace(pt)
    got = run_points([pt])[0]
    want = simulate(pt.scheme, tr, pt.n_rows, alpha=pt.alpha, r=pt.r,
                    n_cycles=pt.resolved_cycles(),
                    select_period=pt.select_period)
    assert got == want


def test_build_trace_error_names_point():
    """The error path names the failing point (suite + index), not just the
    unknown key — chunked file-backed sweeps are unattributable otherwise."""
    from repro.sweep import run_points
    from repro.sweep.workloads import build_trace, suite
    pts = suite("trace_zoo")
    bad = pts[2].replace(trace="no_such_generator")
    with pytest.raises(KeyError) as ei:
        build_trace(bad, index=2)
    msg = str(ei.value)
    assert "trace_zoo" in msg and "[2]" in msg and "no_such_generator" in msg
    with pytest.raises(FileNotFoundError) as ei2:
        run_points([pts[0].replace(trace="file:/does/not/exist.npz")])
    assert "trace_zoo" in str(ei2.value) and "[0]" in str(ei2.value)


# ----------------------------------------------------------------- profiler
def test_profiler_recovers_generator_bands():
    """Fig 15 reproduction: band detection on ``banded_trace`` recovers the
    generator's band count and extents."""
    n_banks, n_rows = 8, 512
    spec = TraceSpec(n_cores=8, length=400, n_banks=n_banks, n_rows=n_rows,
                     seed=0)
    trace = banded_trace(spec, n_bands=2)
    prof = profile_trace(trace, n_banks=n_banks, n_rows=n_rows, window=256)
    bands = prof.bands()
    assert len(bands) == 2
    space = n_banks * n_rows
    width_rows = max(space // 32, n_banks * 4) // n_banks
    tol = 2 * prof.bin_rows
    for i, band in enumerate(bands):
        center = (i + 0.5) * space / 2 / n_banks   # generator band center
        assert abs(band.center - center) <= tol
        assert abs((band.row_hi - band.row_lo + 1) - width_rows) <= 2 * tol
        assert band.persistence >= 0.5
    assert sum(b.weight for b in bands) > 0.9      # bands carry the traffic
    # profile basics ride along
    assert prof.n_requests == int(np.asarray(trace.valid).sum())
    assert 0.15 < prof.write_frac < 0.45


def test_profiler_streaming_equals_one_shot():
    """Chunked accumulation is the same profile as one-shot (windows are
    request-aligned, so chunk boundaries are invisible)."""
    spec = TraceSpec(n_cores=4, length=200, n_banks=8, n_rows=128, seed=1)
    trace = banded_trace(spec)
    one = profile_trace(trace, 8, 128, window=64)
    chunked = profile_trace(chunk_iter(trace, 17), 8, 128, window=64)
    assert one.n_windows == chunked.n_windows
    np.testing.assert_array_equal(one.row_hist, chunked.row_hist)
    np.testing.assert_array_equal(one.presence, chunked.presence)
    np.testing.assert_allclose(one.bank_window_var, chunked.bank_window_var)


def test_profiler_empty_trace():
    """A trace with no valid requests: zero counts, no windows, no bands,
    all-padding priors, and a defined (zero) Fano factor — not NaNs."""
    rng = np.random.default_rng(0)
    trace = rand_trace(rng, 2, 8, 4, 16)._replace(
        valid=jnp.zeros((2, 8), bool), is_write=jnp.zeros((2, 8), bool))
    prof = profile_trace(trace, n_banks=4, n_rows=16, window=4)
    assert prof.n_requests == prof.reads == prof.writes == 0
    assert prof.n_windows == 0
    assert prof.bank_hist.sum() == 0 and prof.row_hist.sum() == 0
    assert prof.bands() == []
    assert prof.write_frac == 0.0
    assert prof.burstiness == 0.0
    np.testing.assert_array_equal(prof.region_priors(4, 4, k=3),
                                  [-1, -1, -1])


def test_profiler_single_bank_trace():
    """Every request on one bank: the histogram concentrates, the hot bank's
    windowed counts are constant (zero variance ⇒ Fano 0 per bank), and
    band detection still sees the row band."""
    rng = np.random.default_rng(1)
    n_banks, n_rows, T = 4, 64, 32
    trace = rand_trace(rng, 2, T, 1, n_rows)._replace(
        bank=jnp.full((2, T), 2, jnp.int32),
        row=jnp.asarray(rng.integers(8, 16, (2, T)), jnp.int32),
        valid=jnp.ones((2, T), bool))
    prof = profile_trace(trace, n_banks=n_banks, n_rows=n_rows, window=16)
    assert prof.bank_hist[2] == prof.n_requests == 2 * T
    assert prof.bank_hist.sum() == prof.bank_hist[2]
    # full 16-request windows always hold 16 bank-2 requests: variance 0
    assert prof.bank_window_var[2] == 0.0
    assert prof.burstiness == 0.0
    bands = prof.bands(min_weight=0.5)
    assert len(bands) == 1
    assert bands[0].row_lo >= 8 - prof.bin_rows
    assert bands[0].row_hi <= 15 + prof.bin_rows


def test_profiler_window_larger_than_trace():
    """A window that never fills leaves the presence statistics empty —
    band detection must report no bands rather than divide by zero, while
    the aggregate histograms still accumulate."""
    rng = np.random.default_rng(2)
    trace = rand_trace(rng, 2, 10, 4, 32)
    prof = profile_trace(trace, n_banks=4, n_rows=32, window=512)
    assert prof.n_windows == 0
    assert prof.n_requests > 0
    assert prof.row_hist.sum() == prof.n_requests
    assert prof.bands() == []
    assert prof.burstiness == 0.0
    # priors need no windows — they rank the aggregate row histogram
    pri = prof.region_priors(8, 4)
    assert pri.size > 0


def test_profiler_all_writes_mix():
    """A pure-write stream: the mix saturates at 1.0 and the read counter
    stays zero (windowing, bands and priors are operation-agnostic)."""
    rng = np.random.default_rng(3)
    T = 24
    trace = rand_trace(rng, 2, T, 4, 32)._replace(
        is_write=jnp.ones((2, T), bool), valid=jnp.ones((2, T), bool))
    prof = profile_trace(trace, n_banks=4, n_rows=32, window=8)
    assert prof.write_frac == 1.0
    assert prof.reads == 0 and prof.writes == prof.n_requests == 2 * T
    assert prof.n_windows == (2 * T) // 8


def test_region_priors_rank_hot_regions():
    spec = TraceSpec(n_cores=8, length=300, n_banks=8, n_rows=256, seed=2)
    trace = banded_trace(spec, n_bands=2)
    prof = profile_trace(trace, 8, 256, window=128)
    rs = 13                                        # r=0.05 over 256 rows
    n_regions = -(-256 // rs)
    pri = prof.region_priors(rs, n_regions, k=4)
    assert pri.shape == (4,)
    counts = np.zeros(n_regions, np.int64)
    np.add.at(counts, np.arange(256) // rs, prof.row_hist)
    ranked = np.argsort(-counts, kind="stable")
    np.testing.assert_array_equal(pri, ranked[:4])
    # hot regions must carry real traffic
    assert counts[pri[0]] > counts.mean()


@pytest.mark.parametrize("suite_name,kw", [
    ("paper_fig18", dict(schemes=("scheme_i",), alphas=(0.1, 0.25))),
])
def test_region_priors_never_increase_stalls_fast(suite_name, kw):
    _check_priors_no_stall_regression(suite_name, kw)


@pytest.mark.slow
@pytest.mark.parametrize("suite_name,kw", [
    ("paper_fig18", {}),
    ("paper_fig19", {}),
    ("paper_fig20", {}),
])
def test_region_priors_never_increase_stalls(suite_name, kw):
    _check_priors_no_stall_regression(suite_name, kw)


def _check_priors_no_stall_regression(suite_name, kw):
    """Seeding the dynamic unit with profiled region-priors must never cost
    stall cycles vs a cold start on the paper-figure suites."""
    from repro.sweep import SweepPoint, run_points
    from repro.sweep.workloads import build_trace, suite
    base_pt = SweepPoint(n_rows=64, n_cores=8, n_banks=8, length=48,
                         select_period=32)
    pts = suite(suite_name, base_pt, **kw)
    traces = [build_trace(pt) for pt in pts]
    priors = []
    for pt, tr in zip(pts, traces):
        prof = profile_trace(tr, n_banks=pt.n_banks, n_rows=pt.n_rows,
                             window=64)
        rs, nr, ns = pt.derived_slots()
        priors.append(prof.region_priors(rs, nr, k=max(ns, 1)))
    cold = run_points(pts, traces=traces)
    seeded = run_points(pts, traces=traces, region_priors=priors)
    cold_stalls = sum(r.stall_cycles for r in cold)
    seeded_stalls = sum(r.stall_cycles for r in seeded)
    assert seeded_stalls <= cold_stalls, (suite_name, seeded_stalls,
                                          cold_stalls)


# --------------------------------------------------------------- drain bound
def test_drain_bound_single_helper():
    """One helper, one derivation: the looped driver's default budget IS
    drain_bound, and the chunked budget only adds the carried backlog."""
    from repro.sim.ramulator import default_n_cycles
    from repro.traces.stream import chunk_bound
    rng = np.random.default_rng(0)
    trace = rand_trace(rng, 3, 10, 8, 32)
    assert default_n_cycles(trace) == drain_bound(3, 10)
    sys_ = _SYS
    backlog = 2 * sys_.p.n_data * sys_.p.queue_depth
    assert chunk_bound(sys_, 16) == drain_bound(sys_.n_cores, 16,
                                                backlog=backlog)
    assert drain_bound(3, 10, backlog=5) > drain_bound(3, 10)


# ------------------------------------------------------------- slow soak
@pytest.mark.slow
@pytest.mark.timeout(3600)
def test_stream_million_requests():
    """A ≥1M-request trace replays through stream_replay under a fixed
    per-chunk device footprint, completes, and serves every request."""
    from repro.sim.trace import uniform_trace
    n_cores, chunk_cols, n_chunks = 8, 2048, 62
    n_banks, n_rows = 8, 512
    total = n_cores * chunk_cols * n_chunks
    assert total >= 1_000_000

    def chunks():
        for i in range(n_chunks):
            spec = TraceSpec(n_cores=n_cores, length=chunk_cols,
                             n_banks=n_banks, n_rows=n_rows, seed=1000 + i)
            yield uniform_trace(spec)

    t = get_tables("scheme_i")
    p = make_params(t, n_rows=n_rows, alpha=1.0, r=0.05)
    sys_ = CodedMemorySystem(t, p, n_cores=n_cores)
    res = stream_replay(sys_, chunks(), chunk_len=chunk_cols)
    assert res.completed
    assert res.served_reads + res.served_writes == total
    assert len(res.window_read_latency) >= n_chunks


# ------------------------------------------- flaky sources & retry/backoff
class _FlakyChunks:
    """Re-pullable iterator (NOT a generator) that fails transiently:
    ``fail_on[i] = n`` makes the pull of chunk ``i`` raise n times before
    succeeding — the shape of an NFS hiccup or racing writer."""

    def __init__(self, chunks, fail_on):
        self.chunks, self.i, self.fails = list(chunks), 0, dict(fail_on)

    def __iter__(self):
        return self

    def __next__(self):
        if self.fails.get(self.i, 0) > 0:
            self.fails[self.i] -= 1
            raise OSError(f"transient read error at chunk {self.i}")
        if self.i >= len(self.chunks):
            raise StopIteration
        c = self.chunks[self.i]
        self.i += 1
        return c


def _drain_source(src, n_cores, chunk_len=4):
    """Stage a source to exhaustion, returning the staged bank columns."""
    pos = np.zeros(n_cores, np.int64)
    out = []
    while not src.exhausted(pos):
        chunk, _ = src.stage(pos, chunk_len)
        out.append(np.asarray(chunk.bank))
        pos += chunk_len
    return out


@pytest.mark.parametrize("prefetch", [False, True])
def test_flaky_source_retries_then_streams_identically(prefetch):
    """Transient read errors inside the retry budget are invisible: the
    staged stream equals the in-memory trace, chunk for chunk."""
    rng = np.random.default_rng(11)
    trace = rand_trace(rng, N_CORES, 20, 8, N_ROWS)
    chunks = list(chunk_iter(trace, 4))
    src = TraceSource.from_chunks(
        _FlakyChunks(chunks, fail_on={1: 2, 3: 1}), prefetch=prefetch,
        retries=3, backoff=0.001)
    got = _drain_source(src, N_CORES)
    want = _drain_source(TraceSource.from_chunks(iter(chunks),
                                                 prefetch=False), N_CORES)
    assert len(got) == len(want)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("prefetch", [False, True])
def test_flaky_source_exhausted_retries_raises(prefetch):
    """Once the bounded retry budget is spent the original exception
    surfaces (on the consumer thread) — never a silently short stream."""
    rng = np.random.default_rng(12)
    chunks = list(chunk_iter(rand_trace(rng, 2, 12, 4, 16), 4))
    src = TraceSource.from_chunks(_FlakyChunks(chunks, fail_on={1: 99}),
                                  prefetch=prefetch, retries=2,
                                  backoff=0.001)
    with pytest.raises(OSError, match="transient read error at chunk 1"):
        _drain_source(src, 2)


def test_generator_sources_never_retry():
    """A generator is dead after raising — retrying ``next()`` on one
    yields StopIteration, i.e. a silently truncated stream. The retry
    helper must therefore re-raise generator errors immediately even with
    budget left."""
    from repro.traces.source import _pull_retry

    rng = np.random.default_rng(13)
    chunk = rand_trace(rng, 2, 4, 4, 16)

    def gen():
        yield chunk
        raise OSError("boom")

    it = gen()
    assert _pull_retry(it, 5, 0.001) is chunk
    with pytest.raises(OSError, match="boom"):
        _pull_retry(it, 5, 0.001)


# --------------------------------------------- malformed on-disk traces
def test_trace_format_error_names_file_and_line(tmp_path):
    from repro.traces import TraceFormatError

    p = tmp_path / "bad.trace"
    p.write_text("0x100 R\n0x200\n")
    with pytest.raises(TraceFormatError, match=r"bad\.trace:2"):
        list(iter_ramulator(str(p)))
    g = tmp_path / "bad.gem5"
    g.write_text("100,r,0x40\n101,w\n")
    with pytest.raises(TraceFormatError, match=r"bad\.gem5:2"):
        list(iter_gem5(str(g)))
    # TraceFormatError subclasses ValueError: pre-existing handlers keep
    # catching ingestion failures
    assert issubclass(TraceFormatError, ValueError)


_GARBAGE_LINES = {
    "ramulator": [
        "0x",                  # truncated address
        "R",                   # op with no address
        "deadbeef Q",          # neither token parses
        "0x10 0x20",           # two addresses, no op
        "\x00\x01\x02",        # binary junk
        "W W W",               # ops with no address
        "12 34",               # two addresses (decimal), no op
    ],
    "gem5": [
        "0x",                  # one column
        "R",                   # one column
        "1,r",                 # missing the address column
        "tick r 0x40",         # non-numeric tick
        "deadbeef Q",          # two columns, neither parses
        "\x00\x01\x02",        # binary junk
        "W W W",               # non-numeric tick and address
        "1,z,0x40",            # unknown command token
    ],
}


def test_malformed_text_traces_fuzz(tmp_path):
    """Truncated / garbage / wrong-arity lines spliced into otherwise-valid
    Ramulator and gem5 traces always raise TraceFormatError pointing at the
    exact file:line — never a different exception type, never silent
    acceptance."""
    from repro.traces import TraceFormatError

    rng = np.random.default_rng(7)
    good = {"ramulator": [f"0x{rng.integers(0, 1 << 20):x} "
                          f"{'R' if rng.random() < 0.5 else 'W'}"
                          for _ in range(8)],
            "gem5": [f"{i},{'r' if rng.random() < 0.5 else 'w'},"
                     f"0x{rng.integers(0, 1 << 20):x}" for i in range(8)]}
    parsers = {"ramulator": iter_ramulator, "gem5": iter_gem5}
    ext = {"ramulator": ".trace", "gem5": ".gem5"}
    for fmt in ("ramulator", "gem5"):
        for trial, bad in enumerate(_GARBAGE_LINES[fmt]):
            lines = list(good[fmt])
            at = int(rng.integers(0, len(lines) + 1))
            lines.insert(at, bad)
            path = tmp_path / f"{fmt}_{trial}{ext[fmt]}"
            path.write_text("\n".join(lines) + "\n")
            with pytest.raises(TraceFormatError) as ei:
                list(parsers[fmt](str(path)))
            assert ei.value.path == str(path), (fmt, bad)
            assert ei.value.line == at + 1, (fmt, bad)


def test_malformed_npz_traces_fuzz(tmp_path):
    """The third format: corrupt, truncated, and wrong-keyed .npz files all
    raise TraceFormatError naming the file."""
    from repro.traces import TraceFormatError

    garbage = tmp_path / "garbage.npz"
    garbage.write_bytes(b"\x13\x37 not a zip archive")
    with pytest.raises(TraceFormatError, match="garbage"):
        load_npz(str(garbage))

    wrong = tmp_path / "wrong.npz"
    np.savez(str(wrong), bank=np.zeros((2, 2), np.int32))
    with pytest.raises(TraceFormatError, match="missing"):
        load_npz(str(wrong))

    rng = np.random.default_rng(8)
    whole = tmp_path / "ok.npz"
    save_npz(str(whole), rand_trace(rng, 2, 6, 4, 16))
    blob = whole.read_bytes()
    for frac in (0.2, 0.6, 0.95):          # truncate at several depths
        cut = tmp_path / f"cut_{frac}.npz"
        cut.write_bytes(blob[: int(len(blob) * frac)])
        with pytest.raises(TraceFormatError):
            load_npz(str(cut))
    # and load_trace routes .npz through the same guarded loader
    with pytest.raises(TraceFormatError):
        load_trace(str(garbage))


# --------------------------------------------- checkpointed stream replay
def test_stream_replay_points_kill_and_resume(tmp_path, compile_guard):
    """A replay killed mid-stream resumes from its last committed
    checkpoint bit-identically: the final per-point SimResults (window
    series included) equal the uninterrupted run's. Checkpointing and
    resuming must also reuse the uninterrupted run's compiled chunk
    program — restored carries may not drift in structure or dtype."""
    from repro.checkpoint import latest_step
    from repro.sweep import SweepPoint
    from repro.sweep.workloads import build_trace

    base = SweepPoint(scheme="scheme_i", alpha=0.25, r=0.125, n_rows=N_ROWS,
                      n_cores=N_CORES, n_banks=8, length=TLEN,
                      select_period=16)
    pts = [base.replace(seed=s) for s in (0, 1)]
    traces = [build_trace(pt) for pt in pts]
    ckdir = str(tmp_path / "ck")

    with compile_guard("stream", max_compiles=None) as g:
        want = stream_replay_points(pts, traces, chunk_len=4)
        first = g.compiles()

        # "kill": stop mid-stream after checkpoints have committed
        stream_replay_points(pts, traces, chunk_len=4, checkpoint_dir=ckdir,
                             checkpoint_every=1, max_cycles=8)
        assert latest_step(ckdir) is not None   # a committed step exists
        got = stream_replay_points(pts, traces, chunk_len=4,
                                   checkpoint_dir=ckdir, checkpoint_every=1,
                                   resume=True)
    assert got == want
    assert g.compiles() == first, \
        "checkpoint/resume recompiled the chunk program (carry drift)"

    # resume without a checkpoint directory is a configuration error
    with pytest.raises(ValueError, match="resume"):
        stream_replay_points(pts, traces, chunk_len=4, resume=True)
