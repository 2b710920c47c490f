"""``expert_load_max_over_mean``: how unevenly the router loaded the experts
this chip holds: the most token-slots a held expert received over the mean
of the held experts, a layer at a time, averaged over the expert layers (the
prediction module's among them), the machines and the steady slices
(``slice_spans``). 1.0 is an even load; the grouped product's time follows
the busiest group. Read from the counts the machine's result carries (summed
over its final fit) and the slice's span holds as ``expert_tokens``.

Beside it on stderr: the share of all token-slots that fell on held experts
(``k * held / all`` expected under a uniform router), and the fewest and most
a held expert received.

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
    counted = [one["attrs"]["expert_tokens"] for one in slices if "expert_tokens" in one["attrs"]]
    if not counted:
        return None
    tokens = np.asarray(counted, np.float64)  # (slices, machines, layers, held)
    mean = tokens.mean(axis=-1)
    ratio = float(np.mean(tokens.max(axis=-1) / np.maximum(mean, 1.0)))
    model = view["run"]["config"].get("reference_model", {})
    if {"num_experts_per_tok", "n_routed_experts"} <= set(model) and view.get("counts"):
        # token-slots of one layer over one fit: every position of every
        # sequence of every step chooses k experts
        steps = view["counts"]["train_steps"] / (int(model["n_splits"]) + 1)
        slots = (steps * model["batch_size"] * view["run"]["config"]["tags"]
                 * model["lookback"] * model["num_experts_per_tok"])
        held = tokens.shape[-1]
        log(
            f"expert load: {100.0 * tokens.sum(axis=-1).mean() / slots:.3f}% of a "
            f"layer's token-slots fell on the {held} held experts "
            f"({100.0 * held / model['n_routed_experts']:.3f}% expected of a uniform "
            f"router); a held expert received {tokens.min():.0f} to {tokens.max():.0f} "
            f"over a fit, max over mean {ratio:.4f}"
        )
    return ratio
