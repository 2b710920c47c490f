"""One module per model kind of the plain reference, found by name.

A configuration's ``reference_model`` has a ``kind``; the module
``benchmarks/reference/models/<kind>.py`` is that kind's whole description.
It has to bring (``REQUIRED``):

* ``layout(model) -> (lookback, target_offset)`` or ``(lookback,
  target_offset, rows_out)``: a sample reads ``lookback`` rows and is judged
  against the ``rows_out`` consecutive rows that end ``target_offset`` rows
  after its first (an autoencoder's ``lookback-1``: the window's last row);
  consecutive samples start ``rows_out`` rows apart, so that no row is
  predicted twice and none between two samples is left out. ``rows_out`` is
  1 where the kind does not state it: every window, one row apart, judged
  against one row (:class:`Layout`);
* ``init(model, key, n_features, n_out)``: the initial parameters;
* ``apply(model, params, windows)``: ``(B, lookback, F) -> (B, n_out)``, or
  ``(B, rows_out, n_out)`` where ``rows_out`` is over 1;
* ``forward_flops(model, n_features)``: ``{"total", "first_layer"}`` matrix
  product operations of one sample's forward pass.

It may bring (``OPTIONAL``; a kind without one gets the default, which is
what ``lstm`` and ``dense`` run):

* ``loss(model, params, windows, targets) -> (B,)``: each sample's loss, in
  the parameters' dtype; ``targets`` are the scaled target rows, shaped as
  ``apply``'s result. Default: the mean squared error of ``apply``;
* ``train_flops(model, n_features)``: the operations one sample's training
  needs, forward and backward, nothing recomputed counted. Default:
  ``3 * total - first_layer`` of ``forward_flops``;
* ``state_bytes(model, n_features)``: the bytes of parameters, gradients and
  optimizer moments that one optimizer step must read and write. Default 0:
  a small model's state stays out of the roofline's bytes.

The configuration's ``reference_model`` may state ``micro_batch``: the
reference then takes a batch's gradient in blocks of that many samples.

``reference/build.py`` and ``flops_bytes.py`` name no kind: a new one is a
new file here and its ``reference_model`` entry in the configuration's file.
"""

from __future__ import annotations

import importlib
from typing import Any, Dict, NamedTuple

import jax
import jax.numpy as jnp

ACT = {"tanh": jnp.tanh, "linear": lambda x: x, "relu": jax.nn.relu,
       "sigmoid": jax.nn.sigmoid}

REQUIRED = ("layout", "init", "apply", "forward_flops")
OPTIONAL = ("loss", "train_flops", "state_bytes")


def affine(p, x):
    y = x @ p["kernel"]
    return y + p["bias"] if "bias" in p else y


def for_kind(model: Dict[str, Any]):
    return importlib.import_module(f"benchmarks.reference.models.{model['kind']}")


class Layout(NamedTuple):
    """Which rows a sample reads and which it is judged against. The real
    rows sit at the end of the padded axis, so the samples are laid from the
    end: the last one's last target is the last row, and the ``lead`` rows in
    front that no whole step reaches belong to no sample (none where
    ``rows_out`` is 1). Samples lie ``rows_out`` rows apart: every row from
    the first sample's first target on is predicted exactly once."""

    lookback: int
    target_offset: int
    rows_out: int = 1

    @property
    def reach(self) -> int:
        """Rows from a sample's first to its last, window or target."""
        return max(self.lookback - 1, self.target_offset)

    def n_samples(self, n_rows: int) -> int:
        return (n_rows - 1 - self.reach) // self.rows_out + 1

    def lead(self, n_rows: int) -> int:
        return (n_rows - 1 - self.reach) % self.rows_out


def layout(model: Dict[str, Any]) -> Layout:
    """The kind's layout, refused where it asks for a target in front of its
    sample."""
    found = Layout(*(int(n) for n in for_kind(model).layout(model)))
    if min(found.lookback, found.rows_out) < 1 or found.target_offset < 0:
        raise ValueError(f"kind {model['kind']!r}: no such layout: {found}")
    if found.rows_out > found.target_offset + 1:
        raise ValueError(
            f"kind {model['kind']!r}: {found} puts a sample's first target "
            f"in front of its first row"
        )
    return found


def train_flops(model: Dict[str, Any], n_features: int) -> float:
    """One sample's training operations: the kind's own count, or the backward
    pass at twice the forward less the first layer's input gradient."""
    kind = for_kind(model)
    if hasattr(kind, "train_flops"):
        return float(kind.train_flops(model, n_features))
    fwd = kind.forward_flops(model, n_features)
    return 3.0 * fwd["total"] - fwd["first_layer"]


def state_bytes(model: Dict[str, Any], n_features: int) -> float:
    kind = for_kind(model)
    return float(kind.state_bytes(model, n_features)) if hasattr(kind, "state_bytes") else 0.0
