"""``fetch_s_per_machine``: mean thread-seconds of provider fetch, resampling,
join and assembly for one machine (``TimeSeriesDataset.get_data``), over the
fetches that ended inside the window.

Layer: provider fetch and assembly. Source: the clock in the benchmark's own
dataset class. Moves ``machines_per_hour``.
"""


def read(view):
    fetches = view["window_fetches"]
    if not fetches:
        return None
    return sum(f["seconds"] for f in fetches) / len(fetches)
