"""Flax modules for the model zoo.

These replace the Keras graphs the reference's factories build
(``gordo_components/model/factories/feedforward_autoencoder.py`` and
``lstm_autoencoder.py`` [UNVERIFIED]). TPU notes:

- ``compute_dtype`` defaults to float32 but the bench configs flip it to
  bfloat16: params stay float32 (``param_dtype``), activations/matmuls run
  on the MXU in bf16, and the final output is cast back to float32 so losses
  and anomaly scores keep full precision.
- The LSTM stack uses ``nn.RNN`` (``lax.scan`` over time) — sequence lengths
  here are lookback windows of order 10², so the scan is short and every
  per-step matmul is batched across the window batch.
- Everything is shape-static and side-effect free: the same ``apply`` is
  used single-model, ``vmap``-ed across a fleet axis, and ``shard_map``-ed
  over a mesh without change.
"""

from __future__ import annotations

from typing import Any, Callable, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

_ACTIVATIONS: dict = {
    "linear": lambda x: x,
    "tanh": nn.tanh,
    "relu": nn.relu,
    "sigmoid": nn.sigmoid,
    "elu": nn.elu,
    "selu": nn.selu,
    "softplus": nn.softplus,
    "softmax": nn.softmax,
    "gelu": nn.gelu,
    "swish": nn.swish,
}


def activation(name: str) -> Callable:
    """Resolve a Keras-style activation name (parity: factory ``*_func``
    hyperparams take the same strings ported configs already use)."""
    try:
        return _ACTIVATIONS[name]
    except KeyError:
        raise ValueError(
            f"Unknown activation {name!r}; supported: {sorted(_ACTIVATIONS)}"
        ) from None


def resolve_dtype(dtype: Any):
    if isinstance(dtype, str):
        return jnp.dtype(dtype)
    return dtype


class DenseAutoencoderModule(nn.Module):
    """Encoder/decoder MLP: ``(batch, F) → (batch, F_out)``.

    Mirrors the reference's ``feedforward_model`` Keras graph: Dense layers of
    ``encoding_dims`` then ``decoding_dims`` with per-layer activations, and a
    final Dense to ``n_features_out`` with ``out_func``.
    """

    encoding_dims: Sequence[int]
    decoding_dims: Sequence[int]
    n_features_out: int
    encoding_funcs: Sequence[str]
    decoding_funcs: Sequence[str]
    out_func: str = "linear"
    compute_dtype: Any = "float32"

    @nn.compact
    def __call__(self, x: jnp.ndarray, deterministic: bool = True) -> jnp.ndarray:
        dtype = resolve_dtype(self.compute_dtype)
        h = x.astype(dtype)
        for dim, func in zip(self.encoding_dims, self.encoding_funcs):
            h = activation(func)(nn.Dense(dim, dtype=dtype)(h))
        for dim, func in zip(self.decoding_dims, self.decoding_funcs):
            h = activation(func)(nn.Dense(dim, dtype=dtype)(h))
        out = activation(self.out_func)(nn.Dense(self.n_features_out, dtype=dtype)(h))
        return out.astype(jnp.float32)


class LSTMModule(nn.Module):
    """Stacked LSTM over a lookback window: ``(batch, L, F) → (batch, F_out)``.

    Mirrors the reference's ``lstm_model`` Keras graph: LSTM layers of
    ``units`` (full sequences between layers), inter-layer dropout, then a
    Dense head on the final timestep's hidden state with ``out_func`` — the
    same graph serves reconstruction and forecast; only the target differs
    (the off-by-one contract in :mod:`gordo_components_tpu.ops.windowing`).
    """

    units: Sequence[int]
    n_features_out: int
    funcs: Sequence[str]
    dropout: float = 0.0
    recurrent_dropout: float = 0.0  # accepted for config parity; not applied
    out_func: str = "linear"
    compute_dtype: Any = "float32"

    @nn.compact
    def __call__(self, x: jnp.ndarray, deterministic: bool = True) -> jnp.ndarray:
        dtype = resolve_dtype(self.compute_dtype)
        h = x.astype(dtype)
        for i, (n_units, func) in enumerate(zip(self.units, self.funcs)):
            cell = nn.OptimizedLSTMCell(
                n_units, activation_fn=activation(func), dtype=dtype
            )
            # one scope per layer's scan over the window (autodiff carries
            # it to the transpose): a device trace tells the layers apart
            with jax.named_scope(f"lstm_layer_{i}"):
                h = nn.RNN(cell)(h)
            if self.dropout > 0.0:
                h = nn.Dropout(rate=self.dropout)(h, deterministic=deterministic)
        last = h[:, -1, :]
        out = activation(self.out_func)(
            nn.Dense(self.n_features_out, dtype=dtype)(last)
        )
        return out.astype(jnp.float32)
