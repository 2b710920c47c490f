"""``commit_wait_s_per_slice``: seconds the build loop's thread waits, with a
slice's result fetched, for the commit worker to end the commit of the slice
before (span ``fleet.commit_wait``), mean over the steady slices
(``fleet_spans``: the first bucket's committed slices after the job's first
and before its last). 0 while a commit hides whole behind the slice that
trains beside it; anything else is the part of the commit that still sets the
pace. A program that commits on the loop's own thread waits on no worker and
reads 0.0.

Layer: artifact commit. Source: the program's span. Moves
``machines_per_hour``.
"""

from benchmarks.layer_metrics import fleet_spans


def read(view):
    return fleet_spans.mean_phase_seconds("fleet.commit_wait")
