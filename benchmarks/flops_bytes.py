"""Operations and bytes one fleet-build slice needs, from shapes alone.

The benchmark's own arithmetic (the program's ``fleet_flops_accounting``
reads XLA's cost analysis of the loop bodies and multiplies by trip counts;
``benchmarks/tests/test_flops_bytes.py`` holds the two against each other at
a small shape). One sample's forward operations and its sample layout are the
model kind's own (``benchmarks/reference/models/<kind>.py``); nothing here
names a kind. Matrix products only: 2·m·n·k each, the backward pass twice
the forward (gradients with respect to weights and to inputs; the first
layer's input gradient is not needed and not counted) unless the kind counts
a training sample itself (``train_flops``: experts that see a share of the
tokens, attention that grows with the position); recomputed operations are
never counted. Elementwise work, scalers, masks and percentiles are left out,
so the count is a floor and a share of peak computed from it cannot be
flattered. The least bytes are the data rows and, where the kind states them
(``state_bytes``), the weights, gradients and optimizer moments that every
optimizer step reads and writes.
"""

from __future__ import annotations

from typing import Dict

from benchmarks.reference import models


def slice_counts(model: Dict, n_machines: int, n_rows: int, n_features: int) -> Dict[str, float]:
    """Trip counts, flops and least bytes of ONE slice of ``n_machines``."""
    kind = models.for_kind(model)
    n_samples = models.layout(model).n_samples(n_rows)
    batch = model["batch_size"]
    steps = -(-n_samples // batch)
    padded = steps * batch
    fits = model["n_splits"] + 1
    fwd = kind.forward_flops(model, n_features)
    train_steps = fits * model["epochs"] * steps
    train_flops = train_steps * batch * models.train_flops(model, n_features)
    predict_flops = fits * padded * fwd["total"]
    # least traffic: every fit reads its rows (inputs are the targets, so
    # once) in every epoch and once more to predict; a windowed model can
    # keep a row for the L windows it belongs to, so rows, not windows
    row_bytes = 4.0 * n_features
    bytes_moved = fits * (model["epochs"] + 1) * n_rows * row_bytes
    bytes_moved += train_steps * models.state_bytes(model, n_features)
    return {
        "train_steps": float(train_steps),
        "sequential_steps": float(model["epochs"] * steps),
        "flops": n_machines * (train_flops + predict_flops),
        "bytes": n_machines * bytes_moved,
    }


def least_seconds(counts: Dict[str, float], peak: Dict[str, float]) -> Dict[str, object]:
    by_flops = counts["flops"] / peak["flops_per_s"]
    by_bytes = counts["bytes"] / peak["hbm_bytes_per_s"]
    return {
        "seconds": max(by_flops, by_bytes),
        "bound": "flops" if by_flops >= by_bytes else "bytes",
        "by_flops_s": by_flops,
        "by_bytes_s": by_bytes,
    }
