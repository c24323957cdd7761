"""Device time of the sweep engine's scan program per while-loop trip
(microseconds): the ``XLA Modules`` seconds of ``_scan_batch`` in the traced
call over its trips (the batch's largest simulated cycle count)."""


def read(ctx):
    secs = sum(v for k, v in ctx["trace"]["module_s"].items()
               if "_scan_batch" in k)
    trips = ctx["samples"].get("traced_trips")
    if not secs or not trips:
        return None
    return secs / trips * 1e6
