"""``peak_hbm_gb``: ``memory_stats()["peak_bytes_in_use"]`` of the fullest
chip, read after the window and before the reference runs, in GB (1e9). It
is the allocator's peak: the batches and results the job holds. The TPU
runtime keeps a program's temporaries elsewhere and this counter does not
see them (``PERF.md`` section 7).

Layer: device. Source: the runtime's counter. Moves ``machines_per_hour``.
"""


def read(view):
    peak = view.get("memory_peak_bytes")
    if peak is None or view["platform"] == "cpu":
        return None
    return peak / 1e9
