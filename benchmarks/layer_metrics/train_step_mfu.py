"""``train_step_mfu``: the whole build step's share of the chip's peak: the
matrix-product operations one slice needs (``benchmarks/flops_bytes.py``)
times the commit periods of the traced stretch, over the stretch's length
times the published bf16 peak (there is no float32 peak; see ``peaks.json``).
Idle time between slices counts against it.

Layer: fleet train program, whole step. Source: device trace. Moves
``machines_per_hour``.
"""

from benchmarks.layer_metrics import train_device_s_per_slice


def read(view):
    if train_device_s_per_slice.read(view) is None:
        return None
    trace, peak, counts = view["trace"], view["peak"], view["counts"]
    return 100.0 * counts["flops"] * trace["periods"] / (
        trace["window_s"] * trace["chips"] * peak["flops_per_s"]
    )
