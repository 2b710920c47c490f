"""``setup_before_command_s``: seconds from the process's start to the start
of the span ``fleet.command``: the interpreter's and the harness's imports and
the backend's start-up, before the program's first span (``setup_spans``).

Layer: CLI / compile. Source: the program's span, against the harness's
process start. Moves ``setup_s``.
"""

from benchmarks.layer_metrics import setup_spans


def read(view):
    return setup_spans.reading(view, "before_command")
