"""One module per model kind of the plain reference, found by name.

A configuration's ``reference_model`` has a ``kind``; the module
``benchmarks/reference/models/<kind>.py`` is that kind's whole description:

* ``layout(model) -> (lookback, target_offset)``: sample ``i`` reads rows
  ``i .. i+lookback-1`` and is judged against row ``i+target_offset`` (an
  autoencoder's ``lookback-1``: the window's last row);
* ``init(model, key, n_features, n_out)``: the initial parameters;
* ``apply(model, params, windows)``: ``(B, lookback, F) -> (B, n_out)``;
* ``forward_flops(model, n_features)``: ``{"total", "first_layer"}`` matrix
  product operations of one sample's forward pass.

``reference/build.py`` and ``flops_bytes.py`` name no kind: a new one is a
new file here and its ``reference_model`` entry in the configuration's file.
"""

from __future__ import annotations

import importlib
from typing import Any, Dict

import jax
import jax.numpy as jnp

ACT = {"tanh": jnp.tanh, "linear": lambda x: x, "relu": jax.nn.relu,
       "sigmoid": jax.nn.sigmoid}


def affine(p, x):
    y = x @ p["kernel"]
    return y + p["bias"] if "bias" in p else y


def for_kind(model: Dict[str, Any]):
    return importlib.import_module(f"benchmarks.reference.models.{model['kind']}")
