"""The harness: its refusals, and that a new cell is new files plus new
entries, with no edit to a file that is there."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from .. import run

BENCH = json.loads((run.ROOT / "BENCHMARK.json").read_text())
ARGS = ["--workload", BENCH["workloads"][0]["name"], "--seed", str(2 ** 40),
        "--seconds", "1", "--trace", "0"]


def _run(cwd, env_extra=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "-m", "benchmarks.chip.run",
                           *ARGS], cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=300)


def test_refuses_to_run_without_a_tpu():
    p = _run(run.ROOT)
    assert p.returncode == 2, p.stderr
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_refuses_to_run_without_the_program(tmp_path):
    shutil.copy(run.ROOT / "BENCHMARK.json", tmp_path)
    for path in BENCH["paths"]:
        shutil.copytree(run.ROOT / path, tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_the_benchmark_file_keeps_to_its_form():
    import re

    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    configs = {c["name"] for c in BENCH["configs"]}
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (run.ROOT / c["file"]).is_file()
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert (run.HERE / "traffic" / f"{w['traffic']}.json").is_file()
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    for m in metrics:
        assert name.match(m["name"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert (run.HERE / "metrics" / f"{m['name']}.py").is_file()
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
    cells = [w["name"] for w in BENCH["workloads"]]
    assert len(set(cells)) == len(cells)
    assert all(name.match(n) for n in cells + list(configs))


STUB_PATH = """
import time


class Runner:
    def __init__(self, cfg, mix, seed):
        self.ops = mix["ops_per_call"]

    def setup(self):
        return {}

    def window(self, seconds, span, tracer):
        t0 = time.perf_counter()
        with span("stub_call"):
            time.sleep(0.01)
        return {"window_s": time.perf_counter() - t0, "attempted": 1,
                "failed": 0}

    def end_to_end(self, samples):
        return {"stub_ops_per_s": self.ops / samples["window_s"]}

    def side(self):
        return {}

    def release(self):
        pass

    def check(self):
        return {"stub_answers_wrong": {"value": 0, "limit": 0}}
"""

DRIVE_STUB = """
import json
from pathlib import Path
from benchmarks.chip import run
root = Path.cwd()
memsys = run.load_cell("memsys.paper8.coded_zoo", root=root)
print(json.dumps([m["name"] for m in memsys["end_to_end"]]))
cell = run.load_cell("stub.tiny.steady", root=root)
print(json.dumps(run.run_cell(cell, 2 ** 40, 0.01, False,
                              log=lambda s: None)))
"""


def _throwaway_copy(tmp_path):
    """A copy of the benchmark with a throwaway configuration, two mixes,
    a window runner of a new path, an end-to-end metric of that path only
    and a per-layer metric, all added as new files and new entries (and
    the new memsys cell named among the cells of the metric it reports)."""
    bench_dir = tmp_path / "benchmarks" / "chip"
    shutil.copytree(run.HERE, bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    cfg = json.loads((run.HERE / "configs" / "paper_memsys.json").read_text())
    (bench_dir / "configs" / "small_memsys.json").write_text(
        json.dumps({**cfg, "name": "small_memsys", "n_rows": 64}))
    (bench_dir / "configs" / "stub.json").write_text(
        json.dumps({"name": "stub", "path": "stub_path"}))
    (bench_dir / "paths" / "stub_path.py").write_text(STUB_PATH)
    (bench_dir / "traffic" / "zipf_only.json").write_text(json.dumps(
        {"generator": "memsys_points", "scheme": "scheme_ii",
         "alpha": 0.5, "traces": ["zipf"], "seeds_per_call": 2,
         "distinct_calls": 1}))
    (bench_dir / "traffic" / "steady.json").write_text(
        json.dumps({"ops_per_call": 100}))
    (bench_dir / "metrics" / "calls.memsys.py").write_text(
        "def read(ctx):\n    return float(len(ctx['samples']['calls']))\n")
    bench = json.loads(json.dumps(BENCH))
    bench["configs"] += [
        {"name": "small_memsys", "source": "test",
         "file": "benchmarks/chip/configs/small_memsys.json",
         "reduced": ["n_rows"], "why": "test"},
        {"name": "stub", "source": "test",
         "file": "benchmarks/chip/configs/stub.json", "reduced": [],
         "why": "test"}]
    bench["workloads"] += [
        {"name": "memsys.small.zipf", "config": "small_memsys",
         "traffic": "zipf_only", "chips": 1, "why": "test"},
        {"name": "stub.tiny.steady", "config": "stub", "traffic": "steady",
         "chips": 1, "why": "test"}]
    for m in bench["end_to_end"]:
        if m["name"] == "sim_requests_per_s":
            m["workloads"].append("memsys.small.zipf")
    bench["end_to_end"].append(
        {"name": "stub_ops_per_s", "unit": "ops/s", "better": "higher",
         "bound": 0.05, "source": "host_clock",
         "workloads": ["stub.tiny.steady"]})
    bench["per_layer"].append(
        {"name": "calls.memsys", "unit": "calls", "better": "higher",
         "source": "host_clock", "layer": "sweep engine",
         "moves": "sim_requests_per_s", "workloads": ["memsys.small.zipf"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    for f in run.HERE.rglob("*.py"):
        assert (bench_dir / f.relative_to(run.HERE)).read_text() == \
            f.read_text()
    return bench_dir


def test_a_new_cell_is_new_files_and_entries(tmp_path):
    """A throwaway memsys configuration, mix and per-layer metric are found
    by name."""
    bench_dir = _throwaway_copy(tmp_path)
    cell = run.load_cell("memsys.small.zipf", root=tmp_path,
                         bench_dir=bench_dir)
    assert cell["config"]["n_rows"] == 64
    assert cell["traffic"]["scheme"] == "scheme_ii"
    assert [m["name"] for m in cell["per_layer"]] == ["calls.memsys"]
    assert [m["name"] for m in cell["end_to_end"]] == [
        "sim_requests_per_s", "setup_s"]
    assert run.runner_class(cell["config"]).__module__.endswith(
        "paths.memsys")
    read = run.reader(bench_dir, "calls.memsys")
    assert read({"samples": {"calls": [1, 2, 3]}}) == 3.0


def test_a_new_path_with_its_own_metric_runs_beside_the_cells(tmp_path):
    """A cell of a new path, whose runner reports an end-to-end metric of
    its own, runs through the unedited harness, and the existing cells do
    not take that metric up."""
    _throwaway_copy(tmp_path)
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("PYTHONPATH", None)
    p = subprocess.run([sys.executable, "-c", DRIVE_STUB], cwd=tmp_path,
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr
    memsys_metrics, out = [json.loads(x)
                           for x in p.stdout.strip().splitlines()[-2:]]
    assert memsys_metrics == ["sim_requests_per_s", "setup_s"]
    assert out["correct"] is True
    assert set(out["metrics"]) == {"stub_ops_per_s", "setup_s"}
    assert out["metrics"]["stub_ops_per_s"]["unit"] == "ops/s"
    assert out["checks"] == {"stub_answers_wrong": {"value": 0, "limit": 0}}


def test_setup_work_does_not_grow_when_the_program_gets_faster(monkeypatch):
    """Set-up prepares the mix's fixed number of calls: a faster stand-in
    for ``run_points`` prepares as many and makes set-up shorter, not
    longer."""
    import time

    from repro import sweep

    from ..paths.memsys import Runner

    cell = run.load_cell("memsys.paper8.coded_zoo")
    cell["config"].update(length=32, n_rows=64)
    cell["traffic"]["seeds_per_call"] = 1
    took, prepared = {}, {}
    for name, call_s in (("slow", 0.5), ("fast", 0.01)):
        monkeypatch.setattr(sweep, "run_points",
                            lambda pts, trs, s=call_s: time.sleep(s))
        t0 = time.perf_counter()
        side = Runner(cell["config"], cell["traffic"], 7).setup()
        took[name] = time.perf_counter() - t0
        prepared[name] = side["prepared_calls"]
    assert prepared["fast"] == prepared["slow"] == \
        cell["traffic"]["distinct_calls"]
    assert took["fast"] < took["slow"]


def test_an_unknown_cell_is_not_runnable():
    with pytest.raises(run.NotRunnable):
        run.load_cell("no.such.cell")
