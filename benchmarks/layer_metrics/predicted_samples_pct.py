"""``predicted_samples_pct``: the sample passes the fleet train program
predicted over what predicting every padded sample in each of a machine's
K+1 fits would be, in percent, where the fits run in sequence. Each fit
predicts only the samples its result reads: a fold its own test samples
(``n_real // (K+1)``), the final fit none while a fold covers the machine,
its real samples where none does. The machine's result carries both counts
(``predicted_samples``, ``predictable_samples``), held by the slice's span,
summed over the steady slices (``slice_spans``). It is the program's own
arithmetic, not a reading of the device; the predict's seconds are in
``train_device_s_per_slice``.

A program whose spans carry no such counts (the vmapped fold mode, or a
program from before the counter) gives ``None`` and the metric is left out
of the line.

Layer: fleet train program. Source: the program's counter. Moves
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
        (one["attrs"]["predicted_samples"], one["attrs"]["predictable_samples"])
        for one in slices
        if "predicted_samples" in one["attrs"]
        and "predictable_samples" in one["attrs"]
    ]
    if not counted:
        return None
    predicted = float(sum(np.sum(p) for p, _ in counted))
    predictable = float(sum(np.sum(q) for _, q in counted))
    if predictable <= 0:
        return None
    log(
        f"predicted samples over the steady slices: {predicted:.0f} of "
        f"{predictable:.0f} a prediction of every padded sample in every fit "
        "would be"
    )
    return 100.0 * predicted / predictable
