"""``train_device_s_per_slice``: device time of one run of the fleet train
program (the XLA module the configuration names), mean over the whole runs
inside the traced stretch.

Layer: fleet train program. Source: device trace. Moves ``machines_per_hour``.
"""


def runs(view):
    trace = view.get("trace")
    if not trace:
        return []
    module = view["run"]["config"].get("train_module")
    found = trace["modules"]
    if module not in found:
        return []
    return found[module]


def read(view):
    whole = runs(view)
    return sum(whole) / len(whole) if whole else None
