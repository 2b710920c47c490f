"""Feed-forward autoencoder: Dense layers of ``widths`` with ``funcs``, a
Dense head of ``out_func``. One row in, the same row out."""

from __future__ import annotations

from typing import Sequence

import flax.linen as nn
import jax.numpy as jnp

from benchmarks.reference.models import ACT, affine


class _Init(nn.Module):
    widths: Sequence[int]
    n_out: int

    @nn.compact
    def __call__(self, x):
        for width in self.widths:
            x = nn.Dense(width)(x)
        return nn.Dense(self.n_out)(x)


def layout(model):
    return 1, 0


def init(model, key, n_features: int, n_out: int):
    module = _Init(tuple(model["widths"]), n_out)
    return module.init(key, jnp.zeros((1, n_features), jnp.float32))["params"]


def apply(model, params, windows):
    h = windows[:, 0, :]
    for i, func in enumerate(model["funcs"]):
        h = ACT[func](affine(params[f"Dense_{i}"], h))
    return ACT[model["out_func"]](affine(params[f"Dense_{len(model['funcs'])}"], h))


def forward_flops(model, n_features: int):
    dims = [n_features, *model["widths"], n_features]
    per_layer = [2.0 * a * b for a, b in zip(dims[:-1], dims[1:])]
    return {"total": sum(per_layer), "first_layer": per_layer[0]}
