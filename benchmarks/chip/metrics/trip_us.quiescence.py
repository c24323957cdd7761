"""Device time of the ops under the ``loop.quiescence`` scope (the scan
loop's condition) per while-loop trip of the traced call (microseconds)."""
from benchmarks.chip import program_trace as pt


def read(ctx):
    return pt.scope_trip_us(pt.load(), "loop.quiescence")
