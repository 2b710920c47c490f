"""A stand-in kind of the shape the reference has to have room for: sensor
values read as tokens, every tag a sequence of its own.

Each tag's scaled value is binned into ``vocab`` ids. An embedding and a
learned position, ``blocks`` plain blocks (one-head causal attention and a
two-layer perceptron, each behind a parameter-free RMS norm, on the residual
stream), a head over the vocabulary that predicts the NEXT row's bin at every
position, and a second head that predicts the row after it. The loss is the
cross-entropy at every position plus ``aux_weight`` times the second head's;
``apply`` hands back the expected bin centre, in scaled units, for the
``lookback`` rows that follow each position: a sample reads rows ``i ..
i+L-1`` and is judged against rows ``i+1 .. i+L``, and samples lie ``L`` rows
apart, so that every row is predicted once.

It brings every optional function a kind may bring. Not a model anyone runs:
it lives with the tests, which put it where ``for_kind`` looks.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def layout(model):
    L = int(model["lookback"])
    return L, L, L  # lookback, target_offset, rows_out


def _sizes(model):
    return int(model["vocab"]), int(model["width"]), int(model["lookback"]), int(model["blocks"])


def init(model, key, n_features: int, n_out: int):
    V, D, L, n_blocks = _sizes(model)
    keys = iter(jax.random.split(key, 4 + 6 * n_blocks))

    def dense(shape):
        return jax.random.normal(next(keys), shape, jnp.float32) / jnp.sqrt(shape[0])

    params = {
        "embed": 0.1 * jax.random.normal(next(keys), (V, D), jnp.float32),
        "position": 0.1 * jax.random.normal(next(keys), (L, D), jnp.float32),
        "head": 0.1 * dense((D, V)),
        "head_after": 0.1 * dense((D, V)),
    }
    for i in range(n_blocks):
        params[f"block_{i}"] = {
            "q": dense((D, D)), "k": dense((D, D)), "v": dense((D, D)), "o": dense((D, D)),
            "up": dense((D, 2 * D)), "down": dense((2 * D, D)),
        }
    return params


def _bins(model, values):
    V = int(model["vocab"])
    return jnp.clip(jnp.floor(values.astype(jnp.float32) * V), 0, V - 1).astype(jnp.int32)


def _norm(x):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-6)


def _logits(model, params, windows):
    """``(B, L, F)`` scaled values -> both heads' logits ``(B, F, L, V)``."""
    _, D, L, n_blocks = _sizes(model)
    ids = jnp.swapaxes(_bins(model, windows), 1, 2)  # (B, F, L)
    x = params["embed"][ids] + params["position"]
    causal = jnp.tril(jnp.ones((L, L), bool))
    for i in range(n_blocks):
        block = params[f"block_{i}"]
        h = _norm(x)
        scores = (h @ block["q"]) @ jnp.swapaxes(h @ block["k"], -1, -2) / jnp.sqrt(D).astype(x.dtype)
        scores = jnp.where(causal, scores, jnp.asarray(-1e9, x.dtype))
        x = x + (jax.nn.softmax(scores, axis=-1) @ (h @ block["v"])) @ block["o"]
        x = x + jax.nn.gelu(_norm(x) @ block["up"]) @ block["down"]
    x = _norm(x)
    return x @ params["head"], x @ params["head_after"]


def apply(model, params, windows):
    V = int(model["vocab"])
    logits, _ = _logits(model, params, windows)
    centres = ((jnp.arange(V) + 0.5) / V).astype(logits.dtype)
    expected = jax.nn.softmax(logits, axis=-1) @ centres  # (B, F, L)
    return jnp.swapaxes(expected, 1, 2)  # position t: row t+1


def _cross_entropy(logits, ids):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(logp, ids[..., None], axis=-1)[..., 0]


def loss(model, params, windows, targets):
    """Per sample: the mean over tags and positions of the next row's
    cross-entropy, plus ``aux_weight`` times the second head's on the row
    after it (which the last position has no target for)."""
    logits, logits_after = _logits(model, params, windows)
    ids = jnp.swapaxes(_bins(model, targets), 1, 2)  # (B, F, L): rows 1..L
    nxt = jnp.mean(_cross_entropy(logits, ids), axis=(1, 2))
    after = jnp.mean(_cross_entropy(logits_after[:, :, :-1], ids[:, :, 1:]), axis=(1, 2))
    return (nxt + float(model["aux_weight"]) * after).astype(windows.dtype)


def _products(model):
    """Weight matrices' multiply-adds one token meets, and attention's."""
    V, D, L, n_blocks = _sizes(model)
    weights = n_blocks * (4 * D * D + 4 * D * D) + 2 * D * V
    attention = n_blocks * 2 * D * (L + 1) / 2  # scores and mix, causal mean
    return weights, attention


def forward_flops(model, n_features: int):
    weights, attention = _products(model)
    tokens = int(model["lookback"]) * n_features
    # the first products read the embedding's rows, whose gradient is needed
    return {"total": 2.0 * tokens * (weights + attention), "first_layer": 0.0}


def train_flops(model, n_features: int):
    # forward, and twice that backward; the look-up has no product
    return 3.0 * forward_flops(model, n_features)["total"]


def n_parameters(model):
    V, D, L, n_blocks = _sizes(model)
    return V * D + L * D + 2 * D * V + n_blocks * 8 * D * D


def state_bytes(model, n_features: int):
    # float32 weights, gradients and Adam's two moments: an optimizer step
    # reads 16 bytes a parameter and writes 12 (weights and both moments)
    return 28.0 * n_parameters(model)
