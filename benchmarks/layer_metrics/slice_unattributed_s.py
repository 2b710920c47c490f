"""``slice_unattributed_s``: self time of the span ``fleet.slice``: its
duration less what its child spans cover, mean over the steady slices
(``fleet_spans``: the first bucket's committed slices after the job's first
and before its last). More than a few milliseconds means a phase of the build
loop has no span yet.

Layer: fleet build loop. Source: the program's spans. Moves
``machines_per_hour``.
"""

from benchmarks.layer_metrics import fleet_spans


def read(view):
    return fleet_spans.steady_mean(lambda one: one["self_s"])
