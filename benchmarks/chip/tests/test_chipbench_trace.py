"""The trace reduction on a small synthetic trace (times in ns)."""
import json
from pathlib import Path

import pytest

from .. import trace_reduce

DATA = Path(__file__).parent / "data" / "synthetic_trace.json"


@pytest.fixture
def reduced():
    return trace_reduce.reduce(json.loads(DATA.read_text()))


def test_busy_is_the_union_clipped_to_the_window(reduced):
    # device 0: [100, 250] (two overlapping ops) + [400, 600] + [900, 1000]
    # (clipped at the window's end) = 450 ns; device 1: [0, 500] = 500 ns
    assert reduced["busy_s"] == pytest.approx(475e-9)
    assert reduced["window_s"] == pytest.approx(1000e-9)
    assert reduced["devices"] == 2


def test_kernel_time_by_name_averages_over_devices(reduced):
    assert reduced["op_s"]["fusion.1"] == pytest.approx((100 + 100 + 500)
                                                        / 2 * 1e-9)
    assert reduced["op_s"]["gather_pool"] == pytest.approx(100e-9)
    assert reduced["module_s"]["jit__scan_batch(1)"] == pytest.approx(
        900 / 2 * 1e-9)
    assert reduced["device_ops"][0] == ["fusion.1", pytest.approx(350e-9)]


def test_idle_gaps_longest_first_with_the_innermost_span(reduced):
    gaps = reduced["idle_gaps"]
    assert [g[0] for g in gaps] == ["admit", "run_points", "outside spans"]
    assert [g[1] for g in gaps] == pytest.approx([300e-9, 150e-9, 100e-9])


def test_without_a_window_span_the_ops_bound_the_window():
    space = json.loads(DATA.read_text())
    space["spans"] = []
    assert trace_reduce.reduce(space)["window_s"] == pytest.approx(1100e-9)


def test_a_trace_without_device_ops_is_refused():
    with pytest.raises(ValueError):
        trace_reduce.reduce({"devices": [], "spans": []})
