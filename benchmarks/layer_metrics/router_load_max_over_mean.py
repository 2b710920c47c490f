"""``router_load_max_over_mean``: how unevenly the router spreads a layer's
token-slots over ALL its experts, those held on other chips too: for each
steady slice's machine (``slice_spans``), the largest over the expert layers
of the busiest expert's token-slots over the mean an expert received, then
the mean over the machines and slices. 1.0 is an even load; the chip that
holds the busiest expert sets the pace of a layer whose experts are spread
over chips. Read from the counts the machine's result carries (summed over
its final fit) and the slice's span holds as ``routed_tokens`` (an expert
layer a row, an expert a column). A program that does not count them gives
nothing here.

Beside it on stderr: the share of all token-slots that fell on the experts
this chip holds (``experts_held`` of the configuration's reference model;
``held / all`` under an even router), which sets the rows of the grouped
product here.

Layer: expert layer. Source: the program's counter. Moves
``machines_per_hour``. Lower is better.
"""

import numpy as np

from benchmarks.harness import log
from benchmarks.layer_metrics import slice_spans


def read(view):
    slices = slice_spans.steady()
    if not slices:
        return None
    counted = [one["attrs"]["routed_tokens"] for one in slices if "routed_tokens" in one["attrs"]]
    if not counted:
        return None
    slots = np.asarray(counted, np.float64)  # (slices, machines, layers, experts)
    busiest = slots.max(axis=-1) / np.maximum(slots.mean(axis=-1), 1.0)
    ratio = float(np.mean(busiest.max(axis=-1)))
    held = list(view["run"]["config"].get("reference_model", {}).get("experts_held", ()))
    if held and slots.sum() > 0:
        log(
            f"router load: {100.0 * slots[..., held].sum() / slots.sum():.3f}% of all "
            f"token-slots fell on the {len(held)} held experts "
            f"({100.0 * len(held) / slots.shape[-1]:.3f}% under an even router); an "
            f"expert received {slots.min():.0f} to {slots.max():.0f} over a fit, the "
            f"largest layer's busiest over mean {ratio:.4f}"
        )
    return ratio
