"""``commit_s_per_machine``: seconds of the commit loop (model graph, result
installed, metadata, journal, generation commit, registry key: span
``fleet.commit_loop``, one ``fleet.commit`` a machine inside it) and of the
manifest write after it (``fleet.manifest``), over the machines of the slice;
mean over the steady slices (``fleet_spans``: the first bucket's committed
slices after the job's first and before its last).

Layer: artifact commit. Source: the program's spans. Moves
``machines_per_hour``.
"""

from benchmarks.layer_metrics import fleet_spans


def read(view):
    return fleet_spans.steady_mean(
        lambda one: (
            one["phases"].get("fleet.commit_loop", 0.0)
            + one["phases"].get("fleet.manifest", 0.0)
        ) / max(one["machines"], 1)
    )
