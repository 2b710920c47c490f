"""``ingest_s_per_slice``: seconds from a slice's prepared data to its
dispatch: keys, batch assembly and the layout-matched ``device_put`` (spans
``fleet.ingest``) plus the executable look-up between them (``fleet.program``:
a memo hit in a steady slice), mean over the steady slices (``fleet_spans``:
the first bucket's committed slices after the job's first and before its
last).

Layer: host→device ingest. Source: the program's spans. Moves
``machines_per_hour``.
"""

from benchmarks.layer_metrics import fleet_spans


def read(view):
    return fleet_spans.mean_phase_seconds("fleet.ingest", "fleet.program")
