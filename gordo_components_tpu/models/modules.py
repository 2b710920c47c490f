"""Flax modules for the model zoo.

These replace the Keras graphs the reference's factories build
(``gordo_components/model/factories/feedforward_autoencoder.py`` and
``lstm_autoencoder.py`` [UNVERIFIED]). TPU notes:

- ``compute_dtype`` defaults to float32; where a config flips it to
  bfloat16: params stay float32 (``param_dtype``), activations/matmuls run
  on the MXU in bf16, and the final output is cast back to float32 so losses
  and anomaly scores keep full precision.
- The LSTM stack declares its parameters through ``nn.OptimizedLSTMCell``
  (tree and initial draw are Flax's) and runs them through
  :func:`lstm_sequence`, a ``lax.scan`` over the lookback window with its own
  differentiation rule. Both time loops hold only what depends on the
  previous step. Forward: ``h_{t-1}·W_h``, the elementwise cell, and the
  input projection ``x_t·W_i`` (it does not depend on the previous step, but
  taken out of the loop as one product over the window it read slower on the
  v5e: a ``(L, batch, 4H)`` stack written and read back costs more than
  ``L`` reads of ``W_i``; PERF.md §6, PR 26). Backward: the carry is
  ``(dh, dc)`` alone; a step emits ``dz_t`` and its sum over the batch and
  computes ``dh_{t-1} = dz_t·W_hᵀ``. The kernels' gradients are ONE product a
  layer over the stacked ``(L·batch)`` axis after the loop, the input's
  another, so no weight-shaped accumulator rides in a loop carry.
- Everything is shape-static and side-effect free: the same ``apply`` is
  used single-model, ``vmap``-ed across a fleet axis, and ``shard_map``-ed
  over a mesh without change.
"""

from __future__ import annotations

from functools import partial
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


def _cell_step(w_i, w_h, b, act, dtype, carry, x_t):
    """One LSTM step, cast for cast what ``nn.OptimizedLSTMCell`` computes:
    gates in the compute dtype, the carry in the dtype promotion gives it
    (float32 under a bfloat16 ``dtype``, as Flax's zero carry is float32)."""
    c, h = carry
    z = (jnp.dot(h.astype(dtype), w_h) + b) + jnp.dot(x_t, w_i)
    zi, zf, zg, zo = jnp.split(z, 4, axis=-1)
    i, f, o = nn.sigmoid(zi), nn.sigmoid(zf), nn.sigmoid(zo)
    c = f * c + i * act(zg)
    h = o * act(c)
    return (c, h), (h, c, i, f, zg, o)


def _scan_window(w_i, w_h, b, x, act, dtype, scope, keep_residuals):
    """The forward time loop: ``(hs, cs, i, f, zg, o)`` streams, time-major,
    or ``hs`` alone (a caller without a gradient pays for no other)."""
    state_dtype = jnp.promote_types(dtype, w_h.dtype)
    xs = jnp.swapaxes(x, 0, 1).astype(dtype)  # (L, B, F): scan over time
    zero = jnp.zeros((xs.shape[1], w_h.shape[0]), state_dtype)
    cell = partial(
        _cell_step, w_i.astype(dtype), w_h.astype(dtype), b.astype(dtype), act, dtype
    )

    def step(carry, x_t):
        carry, streams = cell(carry, x_t)
        return carry, streams if keep_residuals else streams[0]

    with jax.named_scope(scope):
        return jax.lax.scan(step, (zero, zero), xs)[1]


@partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def lstm_sequence(w_i, w_h, b, x, act, dtype, scope):
    """One LSTM layer over a window: ``x (B, L, F) → h (B, L, H)``.

    ``w_i (F, 4H)``, ``w_h (H, 4H)`` and ``b (4H,)`` are the cell's kernels and
    bias, gates in Flax's order ``i, f, g, o``; ``act`` its activation,
    ``dtype`` the compute dtype, ``scope`` the ``jax.named_scope`` of both time
    loops. The zero initial carry is part of the function.

    Differentiated by :func:`_lstm_bwd`, not by transposing the scan: autodiff
    carries the cotangent of every array the loop body closes over through
    the reverse loop (twelve weight-shaped accumulators a layer, each read and
    written at every step, fed by products whose contraction is one batch).
    Here the reverse loop carries ``(dh, dc)`` alone and emits ``dz_t`` (and
    its sum over the batch, the bias gradient's share of the step); the
    kernels' and the input's gradients do not depend on the previous step and
    are computed once, after it, under ``<scope>/stacked_grads``. The same
    sums, accumulated in float32, in another order.
    """
    hs = _scan_window(w_i, w_h, b, x, act, dtype, scope, keep_residuals=False)
    return jnp.swapaxes(hs, 0, 1)


def _lstm_fwd(w_i, w_h, b, x, act, dtype, scope):
    # residuals chosen by hand: the layer's input and output, the cell state,
    # the gates as activated and g's pre-activation (any ``act`` has a
    # derivative there); act(c) and act(zg) are recomputed in the reverse loop
    streams = _scan_window(w_i, w_h, b, x, act, dtype, scope, keep_residuals=True)
    return jnp.swapaxes(streams[0], 0, 1), (w_i, w_h, x, streams)


def _lstm_bwd(act, dtype, scope, residuals, dh_out):
    w_i, w_h, x, (hs, cs, i_s, f_s, zg_s, o_s) = residuals
    w_i_c, w_h_c = w_i.astype(dtype), w_h.astype(dtype)
    xs = jnp.swapaxes(x, 0, 1).astype(dtype)
    zero = jnp.zeros_like(cs[0])

    def step(carry, streams):
        dh, dc = carry
        t, dh_t, c, i, f, zg, o = streams
        # c_{t-1} read in place (zero before the window): no shifted copy
        c_prev = jnp.where(t > 0, cs[jnp.maximum(t - 1, 0)], zero)
        dh = dh + dh_t
        ac, ac_vjp = jax.vjp(act, c)
        g, g_vjp = jax.vjp(act, zg)
        dc = dc + ac_vjp(dh * o)[0]
        d_ig = dc.astype(dtype)
        dz = jnp.concatenate(
            [
                d_ig * g * i * (1 - i),
                (dc * c_prev).astype(dtype) * f * (1 - f),
                g_vjp(d_ig * i)[0],
                (dh * ac).astype(dtype) * o * (1 - o),
            ],
            axis=-1,
        )
        dh_prev = jnp.einsum("bg,hg->bh", dz, w_h_c).astype(dh.dtype)
        return (dh_prev, dc * f), (dz, jnp.sum(dz, axis=0, dtype=w_i.dtype))

    with jax.named_scope(scope):
        steps = jnp.arange(hs.shape[0])
        dhs = jnp.swapaxes(dh_out, 0, 1)
        _, (dzs, db_steps) = jax.lax.scan(
            step, (zero, zero), (steps, dhs, cs, i_s, f_s, zg_s, o_s), reverse=True
        )
        with jax.named_scope("stacked_grads"):
            # both kernels' gradients in ONE product over the stacked axis,
            # [x_t, h_{t-1}]ᵀ·dz: the stacked dz is read once for them and
            # once for dx; db comes from the batch sums the loop emitted
            h_prev = jnp.concatenate([zero[None], hs[:-1]]).astype(dtype)
            dw = jnp.einsum(
                "lbk,lbg->kg",
                jnp.concatenate([xs, h_prev], axis=-1),
                dzs,
                preferred_element_type=w_i.dtype,
            )
            db = jnp.sum(db_steps, axis=0)
            dx = jnp.einsum("lbg,fg->blf", dzs, w_i_c).astype(x.dtype)
    return dw[: xs.shape[-1]], dw[xs.shape[-1] :], db, dx


lstm_sequence.defvjp(_lstm_fwd, _lstm_bwd)


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
            act = activation(func)
            cell = nn.OptimizedLSTMCell(n_units, activation_fn=act, dtype=dtype)
            if self.is_initializing():
                # one step declares the parameters: Flax's tree and draw
                cell(cell.initialize_carry(jax.random.key(0), h[:, 0].shape), h[:, 0])
            p = cell.variables["params"]

            def gates(dense, leaf):
                return jnp.concatenate([p[dense + g][leaf] for g in "ifgo"], axis=-1)

            # one scope per layer on both time loops (and, below it,
            # ``stacked_grads``): a device trace tells the layers apart
            h = lstm_sequence(
                gates("i", "kernel"),
                gates("h", "kernel"),
                gates("h", "bias"),
                h,
                act,
                dtype,
                f"lstm_layer_{i}",
            )
            if self.dropout > 0.0:
                h = nn.Dropout(rate=self.dropout)(h, deterministic=deterministic)
        last = h[:, -1, :]
        out = activation(self.out_func)(
            nn.Dense(self.n_features_out, dtype=dtype)(last)
        )
        return out.astype(jnp.float32)
