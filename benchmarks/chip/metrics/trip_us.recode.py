"""Device time of the ops under the ``cycle.recode`` scope per while-loop
trip of the traced call (microseconds)."""
from benchmarks.chip import program_trace as pt


def read(ctx):
    return pt.scope_trip_us(pt.load(), "cycle.recode")
