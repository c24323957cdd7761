"""Device programs run in the traced ``run_points`` call: ``XLA Modules``
events on device 0 in the traced window (count)."""
from benchmarks.chip import program_trace as pt


def read(ctx):
    t = pt.load()
    if t is None or t["window"] is None or not t["modules"]:
        return None
    return sum(pt.in_window(t, s, e) for _, s, e in t["modules"])
