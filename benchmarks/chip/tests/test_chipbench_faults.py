"""The check that decides ``correct``, driven through the whole harness at
a small size on the CPU (the harness's look for a chip is skipped): sound
runs pass, and the control and each fault the cells can have fail.

The control is the reference with the queue depth one below the
configuration's, in the program's place; the faults are a scan that returns
its state unchanged, half of the batch left out (its results replaced by
the other half's), and one answer altered where it is produced."""
import pytest

from .. import control, run

SMALL = {"length": 64, "n_rows": 64}
SEED = 2 ** 40 + 3


def memsys_cell():
    cell = run.load_cell("memsys.paper8.coded_zoo")
    cell["config"].update(SMALL)
    cell["traffic"]["seeds_per_call"] = 2
    cell["traffic"]["distinct_calls"] = 2
    return cell


def _unchanged(monkeypatch, cell):
    from repro.sweep import engine

    monkeypatch.setattr(engine, "_scan_batch", lambda sys_, st, *a: st)


def _half_batch(monkeypatch, cell):
    from repro.sweep import engine

    summarize = engine.summarize_batch

    def half(st, n_points=None):
        res = summarize(st, n_points)
        h = len(res) // 2
        return res[:h] + res[:len(res) - h]
    monkeypatch.setattr(engine, "summarize_batch", half)


def _altered(monkeypatch, cell):
    from repro.sweep import engine

    summarize = engine.summarize_batch

    def alter(st, n_points=None):
        res = summarize(st, n_points)
        return [r._replace(degraded_reads=r.degraded_reads + 1) for r in res]
    monkeypatch.setattr(engine, "summarize_batch", alter)


def _control(monkeypatch, cell):
    from repro import sweep

    monkeypatch.setattr(sweep, "run_points", control.run_points_control(
        cell["config"], cell["traffic"]))


def test_a_sound_memsys_run_is_correct():
    out = run.run_cell(memsys_cell(), SEED, 0.5, False, log=lambda s: None)
    assert out["correct"], out["checks"]
    assert out["checks"]["fields_differing"] == {"value": 0, "limit": 0}
    assert set(out["metrics"]) == {"sim_requests_per_s", "setup_s"}
    assert out["attempted"] >= 10 and out["failed"] == 0


@pytest.mark.parametrize("fault", [_control, _unchanged, _half_batch,
                                   _altered])
def test_the_control_and_each_fault_fail_the_memsys_check(monkeypatch,
                                                          fault):
    cell = memsys_cell()
    fault(monkeypatch, cell)
    out = run.run_cell(cell, SEED, 0.5, False, log=lambda s: None)
    assert not out["correct"]
    assert out["checks"]["fields_differing"]["value"] > 0


def test_the_control_readings_at_a_small_size():
    cell = memsys_cell()
    assert control.readings(cell["config"], cell["traffic"], SEED) > 0
