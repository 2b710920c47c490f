"""``setup_unattributed_s``: seconds between the start of ``fleet.command``
and the set-up end that no measured span covers: the union of
``fleet.command``, ``fleet.preamble``, ``fleet.plan`` and the first slice's
phases, each second counted once, taken from the stretch's length
(``setup_spans``). More than a few tenths of a second means a phase of the
set-up has no span yet.

Layer: fleet build loop. Source: the program's spans. Moves ``setup_s``.
"""

from benchmarks.layer_metrics import setup_spans


def read(view):
    return setup_spans.reading(view, "unattributed")
