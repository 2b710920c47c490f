"""``fleet_train_roofline``: the least time the chip could take for one
slice of the fleet train program, max(operations / peak FLOP/s, least bytes
/ peak bytes/s) from ``benchmarks/flops_bytes.py``, over the device time one
run of the program took. The harness prints which bound it is.

Layer: kernels (XLA fusions of the train step). Source: device trace. Moves
``machines_per_hour``.
"""

from benchmarks import flops_bytes
from benchmarks.layer_metrics.train_device_s_per_slice import runs


def read(view):
    whole = runs(view)
    if not whole:
        return None
    least = flops_bytes.least_seconds(view["counts"], view["peak"])
    return 100.0 * least["seconds"] / (sum(whole) / len(whole))
