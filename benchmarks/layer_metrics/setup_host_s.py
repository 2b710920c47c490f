"""``setup_host_s``: seconds of the set-up's host work before the first
fetch is waited on: the spans ``fleet.command`` (the command's imports, the
compile cache, the config and the mesh), ``fleet.preamble`` (resume scan,
journal, buckets) and ``fleet.plan`` (the bucket's spec and slices), summed
(``setup_spans``).

Layer: fleet build loop. Source: the program's spans. Moves ``setup_s``.
"""

from benchmarks.layer_metrics import setup_spans


def read(view):
    return setup_spans.reading(view, "host")
