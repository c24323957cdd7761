"""The 65,536-row deployment of the paper's controller: its cell resolves
through the harness, its configuration says where every value comes from,
and the state-size reader reads the program's count (and nothing from a
program that counts none)."""
import json
from pathlib import Path

import pytest

from benchmarks.chip import program_trace as pt  # as the readers import it

from .. import run
from ..run import reader
from .test_chipbench_program_trace import parse

CELL = "memsys.ddr4_8gb.coded_zoo"
BENCH = json.loads((run.ROOT / "BENCHMARK.json").read_text())
DATA = Path(__file__).parent / "data" / "program_trace.json"
MEMSYS_CELLS = {"memsys.paper8.coded_zoo", "memsys.paper8.uncoded_zoo", CELL}


def test_the_cell_resolves_to_the_ddr4_deployment():
    cell = run.load_cell(CELL)
    cfg, paper = cell["config"], run.load_cell(
        "memsys.paper8.coded_zoo")["config"]
    assert cell["chips"] == 1
    assert cell["traffic"] == run.load_cell(
        "memsys.paper8.coded_zoo")["traffic"]
    assert cfg["path"] == "memsys" and cfg["n_rows"] == 65536
    assert "JESD79-4" in cfg["source"] and "2001.09599" in cfg["source"]
    assert cfg["reduced"] == ["length"]
    assert set(cfg["assumed"]) == set(paper["assumed"]) - {"n_rows"}
    assert "n_rows" in cfg["origin"] and "3,277" in cfg["origin"]["region"]
    # every value as the 512-row deployment's, but the rows
    differ = {k for k in set(cfg) | set(paper)
              if k not in ("name", "source", "n_rows", "assumed", "origin",
                           "deployment") and cfg.get(k) != paper.get(k)}
    assert differ == set()
    names = {m["name"] for m in cell["end_to_end"]}
    assert names == {"sim_requests_per_s", "setup_s"}


def test_the_cell_is_named_by_every_memsys_metric():
    """Every memsys metric lists the cell, but for the ``trip_us.*`` scope
    readers: they read only a trace the profiler cut before the main
    loop's own event, which a 65,536-row call does on some seeds only."""
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert CELL in e2e["sim_requests_per_s"]["workloads"]
    for m in BENCH["per_layer"]:
        scoped = m["name"].startswith("trip_us.")
        assert set(m["workloads"]) == MEMSYS_CELLS - (
            {CELL} if scoped else set()), m["name"]
    assert {m["name"] for m in run.load_cell(CELL)["per_layer"]} == {
        m["name"] for m in BENCH["per_layer"]
        if not m["name"].startswith("trip_us.")}


@pytest.fixture
def doc():
    return json.loads(DATA.read_text())


def _state_mb(monkeypatch, doc):
    parsed = parse(doc)
    monkeypatch.setattr(pt, "load", lambda path=None: parsed)
    return reader(run.HERE, "sweep_state_mb")({})


def test_state_size_sums_the_batches_init_counts(monkeypatch, doc):
    host = doc["planes"][0]["lines"][0]["events"]
    for e in host:
        if e[0] == "repro:sweep.init":
            e[3]["state_bytes"] = 145_000_000
    assert _state_mb(monkeypatch, doc) == pytest.approx(290.0)


def test_state_size_reads_nothing_from_a_program_that_counts_none(
        monkeypatch, doc):
    assert _state_mb(monkeypatch, doc) is None
