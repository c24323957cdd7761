"""The program-trace loader and its readers on a small recorded-shape
trace (``data/program_trace.json``: planes, lines and events as
``ProfileData`` gives them, and each program's instruction scopes as the
HLO protos give them; times in ns).

The call runs two batches of one scan program, of 3 and 5 trips. The
trace records two runs of the loop condition (``fusion.6``) in each, 4 of
the call's 10 (a loop's condition runs once a trip and once to exit); the
profiler dropped device 0's events over [4000, 4800]. Device 0 is idle
inside the waits over [2550, 3400], [3450, 3500] and [9050, 9500] (1350
ns of the window's 9200 recorded ns), and inside ``sweep.init`` over
[600, 700] and [800, 1000]. A ``cycle.patterns`` while op over [1200,
2000] holds two nested ops.
"""
import json
import types
from pathlib import Path

import pytest

from benchmarks.chip import program_trace as pt  # as the readers import it

from ..run import reader

DATA = Path(__file__).parent / "data" / "program_trace.json"
BENCH = Path(__file__).resolve().parents[1]
NEW = ("sweep_host_ms", "sweep_wait_ms", "idle_in_wait.memsys",
       "device_programs_per_call", "trip_us.arbiter", "trip_us.patterns",
       "trip_us.recode", "trip_us.dynamic", "trip_us.quiescence",
       "trip_coverage")
TRIP_US = tuple(m for m in NEW if m.startswith("trip_us."))


def planes(doc):
    """``ProfileData``-shaped planes from the JSON document."""
    def event(name, start, dur, stats):
        return types.SimpleNamespace(name=name, start_ns=start,
                                     duration_ns=dur,
                                     stats=list(stats.items()))
    return [types.SimpleNamespace(
        name=p["name"], lines=[types.SimpleNamespace(
            name=ln["name"], events=[event(*e) for e in ln["events"]])
            for ln in p["lines"]]) for p in doc["planes"]]


def parse(doc):
    return pt.parse(planes(doc), doc["programs"])


def read_all(monkeypatch, doc):
    parsed = parse(doc)
    monkeypatch.setattr(pt, "load", lambda path=None: parsed)
    return {m: reader(BENCH, m)({}) for m in NEW}


def ops_line(doc):
    return doc["planes"][1]["lines"][1]["events"]


@pytest.fixture
def doc():
    return json.loads(DATA.read_text())


def test_loader_keeps_spans_scoped_ops_and_modules(doc):
    t = parse(doc)
    assert t["window"] == (0, 10000)
    assert [s[0] for s in t["spans"][:3]] == ["sweep.call", "sweep.batch",
                                              "sweep.stack"]
    assert t["spans"][0][3] == {"points": 4, "partitions": 2}
    assert len(t["spans"]) == 15          # repro: spans only
    assert len(t["modules"]) == 4         # device 0 only
    assert t["dropped"] == [(4000, 4800)]
    # the two ops nested in the while op are not listed
    assert len(t["ops"]) == 16
    assert t["ops"][:3] == [("broadcast.1", None, 700, 800),
                            ("fusion.1", "cycle.arbiter", 1100, 1200),
                            ("while.2", "cycle.patterns", 1200, 2000)]
    assert ("copy.9", None, 3500, 4000) in t["ops"]
    assert pt.recorded_trips(t) == 4
    assert pt.trip_coverage(t) == 0.4


def test_a_line_out_of_start_order_gives_the_same_ops(doc):
    want = parse(doc)["ops"]
    ops_line(doc).reverse()
    assert parse(doc)["ops"] == want


def test_a_program_is_found_by_its_name_without_the_id(doc):
    doc["programs"] = {"jit__scan_batch(99)": doc["programs"][
        "jit__scan_batch(7)"]}
    assert parse(doc)["ops"][1] == ("fusion.1", "cycle.arbiter", 1100, 1200)


def test_readers_match_the_hand_worked_values(monkeypatch, doc):
    got = read_all(monkeypatch, doc)
    want = {
        # the call's 9600 ns less its two waits
        "sweep_host_ms": 2000e-6,
        "sweep_wait_ms": (3700 + 3900) * 1e-6,
        # the dropped stretch [4000, 4800] is neither idle nor window
        "idle_in_wait.memsys": 100.0 * (850 + 50 + 450) / 9200,
        "device_programs_per_call": 4,
        # op time under each scope over the 4 recorded trips, in us
        "trip_us.arbiter": (100 + 200) / 4 * 1e-3,
        "trip_us.patterns": (800 + 1200 + 1350) / 4 * 1e-3,
        "trip_us.recode": (300 + 400) / 4 * 1e-3,
        "trip_us.dynamic": (200 + 200) / 4 * 1e-3,
        "trip_us.quiescence": 4 * 50 / 4 * 1e-3,
        "trip_coverage": 100.0 * 4 / 10,
    }
    assert got == pytest.approx(want)


def test_idle_inside_init_is_not_idle_in_wait(monkeypatch, doc):
    """Fill the waits' idle with ops: the device is still idle inside
    ``sweep.init``, and none of it counts."""
    ops_line(doc).extend([["copy.a", 2550, 850, {}], ["copy.b", 3450, 50, {}],
                          ["copy.c", 9050, 450, {}]])
    ops_line(doc).sort(key=lambda e: e[1])
    assert read_all(monkeypatch, doc)["idle_in_wait.memsys"] == 0.0


def test_idle_inside_wait_counts_where_only_the_wait_idles(monkeypatch,
                                                           doc):
    """Fill the init's idle instead: the waits' idle share is unchanged."""
    ops_line(doc).extend([["copy.a", 600, 100, {}], ["copy.b", 800, 200, {}]])
    ops_line(doc).sort(key=lambda e: e[1])
    assert read_all(monkeypatch, doc)["idle_in_wait.memsys"] \
        == pytest.approx(100.0 * 1350 / 9200)


def test_without_the_dropped_stretch_it_reads_as_idle(monkeypatch, doc):
    doc["planes"][1]["lines"][2]["events"] = []
    assert read_all(monkeypatch, doc)["idle_in_wait.memsys"] \
        == pytest.approx(21.5)


def test_a_trace_that_dropped_most_of_the_wait_reads_none(monkeypatch,
                                                          doc):
    """Dropped over [1200, 9400]: 200 of the waits' 7600 ns recorded."""
    doc["planes"][1]["lines"][2]["events"][0][1:3] = [1200, 8200]
    assert read_all(monkeypatch, doc)["idle_in_wait.memsys"] is None


def test_a_trace_that_recorded_few_trips_reads_no_scope_time(monkeypatch,
                                                             doc):
    """Batches of 30 and 48 trips: the 4 recorded runs are 5% of 80, under
    the floor; the coverage itself is still read."""
    host = doc["planes"][0]["lines"][0]["events"]
    for e, trips in zip((e for e in host if e[0] == "repro:sweep.summarize"),
                        (30, 48)):
        e[3]["trips"] = trips
    got = read_all(monkeypatch, doc)
    assert got["trip_coverage"] == pytest.approx(5.0)
    assert [got[m] for m in TRIP_US] == [None] * len(TRIP_US)


def test_a_program_without_spans_or_scopes_reads_none(monkeypatch, doc):
    """The parent program opens no ``repro:`` span and names no scope: the
    readers that need them find nothing; the module count stands."""
    host = doc["planes"][0]["lines"][0]
    host["events"] = [e for e in host["events"]
                      if not e[0].startswith("repro:")]
    doc["programs"] = {"jit__scan_batch(7)": {}}
    got = read_all(monkeypatch, doc)
    assert got.pop("device_programs_per_call") == 4
    assert got == {m: None for m in got}


def test_load_reads_spans_and_scopes_from_a_profile(tmp_path, monkeypatch):
    """``load`` parses the newest ``.xplane.pb`` under the trace directory
    once: the spans, and the scopes from the HLO protos of its programs."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def arbiter(x):
        with jax.named_scope("cycle.arbiter"):
            return jnp.cumsum(x * 3 + 1)

    monkeypatch.setattr(pt, "TRACE_DIR", tmp_path)
    assert pt.newest(tmp_path) is None and pt.load() is None
    x = jnp.arange(64)
    arbiter(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path / "run")):
        with jax.profiler.TraceAnnotation("repro:sweep.call", points=3):
            arbiter(x).block_until_ready()
    path = pt.newest(tmp_path)
    t = pt.load()
    assert [(s[0], s[3]) for s in t["spans"]] == [("sweep.call",
                                                   {"points": 3})]
    assert pt.load(path) is t
    scopes = {p: m for p, m in pt.hlo_scopes(path).items()
              if p.startswith("jit_arbiter")}
    assert scopes and all(set(m.values()) == {"cycle.arbiter"}
                          for m in scopes.values())


def test_a_file_the_decoder_misreads_raises(tmp_path):
    """A trace whose protobuf the decoder cannot follow is an error, not a
    trace without scopes."""
    path = tmp_path / "bad.xplane.pb"
    path.write_bytes(b"\x0f\x00")          # wire type 7 does not exist
    with pytest.raises(ValueError):
        pt.hlo_scopes(str(path))
