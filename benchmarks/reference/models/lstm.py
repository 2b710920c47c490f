"""LSTM autoencoder: stacked LSTM layers of ``widths`` units with ``funcs``,
a Dense head of ``out_func`` on the last step's hidden state of the last
layer. A window of ``lookback`` rows in, its last row out."""

from __future__ import annotations

from typing import Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

from benchmarks.reference.models import ACT, affine


class _Init(nn.Module):
    units: Sequence[int]
    n_out: int

    @nn.compact
    def __call__(self, x):
        for width in self.units:
            x = nn.RNN(nn.OptimizedLSTMCell(width))(x)
        return nn.Dense(self.n_out)(x[:, -1, :])


def layout(model):
    return int(model["lookback"]), int(model["lookback"]) - 1


def init(model, key, n_features: int, n_out: int):
    module = _Init(tuple(model["widths"]), n_out)
    sample = jnp.zeros((1, int(model["lookback"]), n_features), jnp.float32)
    return module.init(key, sample)["params"]


def apply(model, params, windows):
    seq = windows
    for i, (units, func) in enumerate(zip(model["widths"], model["funcs"])):
        cell = params[f"OptimizedLSTMCell_{i}"]
        act = ACT[func]

        def step(carry, x_t, cell=cell, act=act):
            c, h = carry
            gate = {
                g: affine(cell["i" + g], x_t) + affine(cell["h" + g], h)
                for g in "ifgo"
            }
            c = jax.nn.sigmoid(gate["f"]) * c + jax.nn.sigmoid(gate["i"]) * act(gate["g"])
            h = jax.nn.sigmoid(gate["o"]) * act(c)
            return (c, h), h

        zeros = jnp.zeros((seq.shape[0], units), seq.dtype)
        _, outs = jax.lax.scan(step, (zeros, zeros), jnp.swapaxes(seq, 0, 1))
        seq = jnp.swapaxes(outs, 0, 1)
    return ACT[model["out_func"]](affine(params["Dense_0"], seq[:, -1, :]))


def forward_flops(model, n_features: int):
    total, first, n_in = 0.0, 0.0, n_features
    for i, units in enumerate(model["widths"]):
        # four gates, input and recurrent products, every time step
        total += 2.0 * 4 * units * (n_in + units) * model["lookback"]
        if i == 0:
            first = 2.0 * 4 * units * n_in * model["lookback"]
        n_in = units
    total += 2.0 * n_in * n_features  # Dense head on the last step
    return {"total": total, "first_layer": first}
