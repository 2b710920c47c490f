"""``result_fetch_mb_per_s``: bytes of a slice's finished result over the
seconds ``jax.device_get`` took to bring them to the host (span
``fleet.result_fetch`` and its ``bytes``, which starts when the result is
ready on the device), over the steady slices (``slice_spans``). For a model of
gigabytes this is the device-to-host link's rate; for one of a megabyte, the
call's overhead.

Layer: device→host result fetch. Source: the program's span. Moves
``machines_per_hour``.
"""

from benchmarks.layer_metrics import slice_spans


def read(view):
    return slice_spans.mb_per_s("fleet.result_fetch")
