"""``attention_key_blocks_visited_pct``: the score tiles the attention
kernels' grids are BUILT to visit in the window layers, forward and
backward, over what causal attention over the whole sequence visits there,
in percent. By the block sizes a quarter to a third at 8,192 rows and a
window of 1,024. It is the program's band arithmetic (``visited_blocks``,
from the same ``_key_band`` / ``_query_band`` the grids and index maps are
made of), carried on the machine's result (summed over its final fit) and
held by the slice's span as ``attention_key_blocks``: ``(machines, layer
kind (sliding, full), (forward, backward), (visited, causal))``, over the
steady slices (``slice_spans``). It moves when the tile or the window does.
It is no reading of the device: a kernel changed to mask what it should
skip, with that arithmetic left alone, would read the same. That the grids
skip what they say is held by the poison test in
``tests/test_flash_attention.py``; what the skipping is worth in device
seconds waits for a reader of the ``window_attention`` calls (``PERF.md``
section 7 item 9).

A program whose spans carry no such counts gives ``None`` and the metric is
left out of the line.

Layer: attention. Source: the program's counter. Moves
``machines_per_hour``. Lower is better.
"""

import numpy as np

from benchmarks.harness import log
from benchmarks.layer_metrics import slice_spans


def read(view):
    slices = slice_spans.steady()
    if not slices:
        return None
    counted = [
        one["attrs"]["attention_key_blocks"] for one in slices
        if "attention_key_blocks" in one["attrs"]
    ]
    if not counted:
        return None
    tiles = np.asarray(counted, np.float64)  # (slices, machines, 2, 2, 2)
    by_kind = tiles.sum(axis=(0, 1, 3))  # (kind, (visited, causal))
    if by_kind[0, 1] <= 0:
        return None
    full = 100.0 * by_kind[1, 0] / max(by_kind[1, 1], 1.0)
    log(
        f"attention tiles over the steady slices' final fits: window layers "
        f"{by_kind[0, 0]:.0f} visited of {by_kind[0, 1]:.0f} causal, full layers "
        f"{by_kind[1, 0]:.0f} of {by_kind[1, 1]:.0f} ({full:.1f}%)"
    )
    return 100.0 * by_kind[0, 0] / by_kind[0, 1]
