"""``first_fetch_wait_s``: seconds the build loop's thread waits for the first
slice's data (the first slice's span ``fleet.prefetch_wait``): the fetch and
assembly that no slice runs beside (``setup_spans``).

Layer: provider fetch and assembly. Source: the program's span. Moves
``setup_s``.
"""

from benchmarks.layer_metrics import setup_spans


def read(view):
    return setup_spans.reading(view, "first_fetch_wait")
