"""``prefetch_wait_s_per_slice``: seconds the build loop's thread is blocked
on the prefetch worker for a slice's data (span ``fleet.prefetch_wait``), mean
over the steady slices (``fleet_spans``: the first bucket's committed slices
after the job's first and before its last). Near 0 while fetch and assembly
hide behind the slice before.

Layer: provider fetch and assembly. Source: the program's span. Moves
``machines_per_hour``.
"""

from benchmarks.layer_metrics import fleet_spans


def read(view):
    return fleet_spans.mean_phase_seconds("fleet.prefetch_wait")
