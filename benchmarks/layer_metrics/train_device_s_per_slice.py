"""``train_device_s_per_slice``: device seconds the fleet train program (the
XLA module the configuration names, ``train_module``) occupies inside the
traced stretch, over the stretch's commit periods. A run is cut at the
stretch's edges, so in steady state this is one run's device time wherever
inside a period the runs begin and end.

Layer: fleet train program. Source: device trace. Moves ``machines_per_hour``.
"""


def read(view):
    trace = view.get("trace")
    if not trace:
        return None
    inside = trace["module_s"].get(view["run"]["config"].get("train_module"))
    return inside / trace["periods"] if inside else None
