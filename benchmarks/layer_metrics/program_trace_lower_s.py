"""``program_trace_lower_s``: seconds of Python tracing and lowering of the
fleet's programs before the set-up end: the spans ``fleet.trace`` and
``fleet.lower`` of the state and the train program, summed
(``setup_spans``). What ``compile_s``, timed from outside, cannot see.

Layer: CLI / compile. Source: the program's spans. Moves ``setup_s``.
"""

from benchmarks.layer_metrics import setup_spans


def read(view):
    return setup_spans.reading(view, "trace_lower")
