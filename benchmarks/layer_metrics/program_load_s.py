"""``program_load_s``: seconds of XLA compiling, or of loading from the
persistent compile cache, of the fleet's programs before the set-up end: the
spans ``fleet.compile`` of the state and the train program, summed
(``setup_spans``; each says ``cache``: hit, miss or off). The inside twin of
``compile_s`` for the fleet's programs; ``compile_s`` counts besides the small
programs the job compiles on the way (its keys, the checkpoint's).

Layer: CLI / compile. Source: the program's spans. Moves ``setup_s``.
"""

from benchmarks.layer_metrics import setup_spans


def read(view):
    return setup_spans.reading(view, "load")
