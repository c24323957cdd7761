"""Host time of the traced ``run_points`` call outside the device wait
(ms): the self time of its ``repro:sweep.*`` spans other than
``sweep.wait`` (stacking, init, dispatch, summarising, ...)."""
from benchmarks.chip import program_trace as pt


def read(ctx):
    spans = pt.sweep_spans(pt.load())
    if not spans:
        return None
    return sum(own for s, own in zip(spans, pt.span_self_ns(spans))
               if s[0] != "sweep.wait") * 1e-6
