"""Share of the recorded part of the traced window in which device 0 ran no
operation while the host was inside ``repro:sweep.wait`` (%): idle that no
host work of the sweep engine explains. Where the profiler dropped device
events the trace cannot say, so that stretch leaves both the idle and the
window; None where it leaves under ``MIN_RECORDED_WAIT`` of the wait."""
from benchmarks.chip import program_trace as pt


def read(ctx):
    t = pt.load()
    waits = pt.sweep_spans(t, "sweep.wait")
    if not waits:
        return None
    lo, hi = t["window"]
    win = [(lo, hi)]
    wait = [(max(s, lo), min(e, hi))
            for s, e in pt.union((s, e) for _, s, e, _ in waits)]
    wait_ns = pt.overlap(wait, win)
    if wait_ns - pt.overlap(wait, t["dropped"]) \
            < pt.MIN_RECORDED_WAIT * wait_ns:
        return None
    busy = pt.union([(s, e) for _, _, s, e in t["ops"]] + t["dropped"])
    idle = wait_ns - pt.overlap(wait, busy)
    return 100.0 * idle / (hi - lo - pt.overlap(t["dropped"], win))
