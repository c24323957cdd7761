"""Share of the traced call's while-loop trips that the device trace
recorded (%): the denominator's check for the ``trip_us.*`` readers, which
divide by the recorded trips alone and read None below
``MIN_TRIP_COVERAGE``. Under 100% where the profiler dropped events."""
from benchmarks.chip import program_trace as pt


def read(ctx):
    cover = pt.trip_coverage(pt.load())
    return None if cover is None else 100.0 * cover
