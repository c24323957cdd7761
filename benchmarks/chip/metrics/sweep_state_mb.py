"""Simulator state the traced ``run_points`` call put on the device (MB):
the ``state_bytes`` counts of its ``repro:sweep.init`` spans, one a batch.
None where the program counts none."""
from benchmarks.chip import program_trace as pt


def read(ctx):
    counts = [st["state_bytes"]
              for _, _, _, st in pt.sweep_spans(pt.load(), "sweep.init")
              if "state_bytes" in st]
    return sum(counts) * 1e-6 if counts else None
