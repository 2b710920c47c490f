"""``checkpoint_s_per_slice``: seconds the build loop's thread spends on the
slice checkpoint: the look for one to restore, the call that starts the async
save, and the wait for it after the commits (spans ``fleet.checkpoint_restore``,
``fleet.checkpoint_save``, ``fleet.checkpoint_wait``), mean over the steady
slices (``fleet_spans``: the first bucket's committed slices after the job's
first and before its last).

Layer: artifact commit. Source: the program's spans. Moves
``machines_per_hour``.
"""

from benchmarks.layer_metrics import fleet_spans


def read(view):
    return fleet_spans.mean_phase_seconds(
        "fleet.checkpoint_restore", "fleet.checkpoint_save",
        "fleet.checkpoint_wait",
    )
