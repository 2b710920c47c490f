"""``device_idle_pct``: 1 - (union of device-op intervals) / traced stretch,
a whole number of commit periods by the store's own file times.

Layer: device. Source: device trace. Moves ``machines_per_hour``.
"""


def read(view):
    trace = view.get("trace")
    if not trace or trace["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
