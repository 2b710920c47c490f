"""``result_fetch_s_per_slice``: seconds of ``jax.device_get`` on a slice's
finished result (span ``fleet.result_fetch``, which starts when the result is
ready on the device), mean over the steady slices (``fleet_spans``: the first
bucket's committed slices after the job's first and before its last).

Layer: device→host result fetch. Source: the program's span. Moves
``machines_per_hour``.
"""

from benchmarks.layer_metrics import fleet_spans


def read(view):
    return fleet_spans.mean_phase_seconds("fleet.result_fetch")
