"""``artifact_commit_mb_per_s``: bytes of a machine's result over the seconds
its commit took (span ``fleet.commit`` and its ``bytes``: model graph, result
installed, metadata, journal, the artifact's files streamed into the staged
generation, their SHA-256, the ``CURRENT`` swap, registry key), over the
machines of the steady slices (``slice_spans``). A program whose commit spans
carry no ``bytes`` gives nothing.

Layer: artifact commit. Source: the program's span. Moves
``machines_per_hour``.
"""

from benchmarks.layer_metrics import slice_spans


def read(view):
    return slice_spans.mb_per_s("fleet.commit")
