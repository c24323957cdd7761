"""Time the traced ``run_points`` call spent waiting for the scan program
(ms): the length of its ``repro:sweep.wait`` spans."""
from benchmarks.chip import program_trace as pt


def read(ctx):
    spans = pt.sweep_spans(pt.load(), "sweep.wait")
    if not spans:
        return None
    return sum(e - s for _, s, e, _ in spans) * 1e-6
