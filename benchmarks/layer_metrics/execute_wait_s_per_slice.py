"""``execute_wait_s_per_slice``: seconds from the train program's dispatch
until its result is ready on the device, as the build loop's thread waits
them (span ``fleet.execute``), mean over the steady slices (``fleet_spans``:
the first bucket's committed slices after the job's first and before its
last). The host's view of ``train_device_s_per_slice``: the difference is
dispatch and the wake-up.

Layer: fleet train program. Source: the program's span. Moves
``machines_per_hour``.
"""

from benchmarks.layer_metrics import fleet_spans


def read(view):
    return fleet_spans.mean_phase_seconds("fleet.execute")
