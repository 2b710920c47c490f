"""``fleet_train_roofline``: the least time the chip could take for one
slice of the fleet train program, max(operations / peak FLOP/s, least bytes
/ peak bytes/s) from ``benchmarks/flops_bytes.py``, over the device time the
program took a slice (``train_device_s_per_slice``). The harness prints
which bound it is.

Layer: kernels (XLA fusions of the train step). Source: device trace. Moves
``machines_per_hour``.
"""

from benchmarks import flops_bytes
from benchmarks.layer_metrics import train_device_s_per_slice


def read(view):
    device_s = train_device_s_per_slice.read(view)
    if device_s is None:
        return None
    least = flops_bytes.least_seconds(view["counts"], view["peak"])
    return 100.0 * least["seconds"] / device_s
