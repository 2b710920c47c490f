"""Pure, jittable training loop.

The reference trains with ``keras.Model.fit`` epochs
(``gordo_components/model/models.py`` [UNVERIFIED]). Here the whole fit —
per-epoch shuffling, mini-batch SGD, loss history — is one compiled XLA
program: ``lax.scan`` over epochs, ``lax.scan`` over mini-batches inside,
no host round-trips. Design constraints that matter downstream:

- **Static shapes**: inputs are padded to a whole number of batches with a
  per-row weight vector (pad rows get weight 0), so one compilation covers
  the dataset and the loss is exact.
- **Purity**: ``make_fit_fn`` closes over only the module's apply fn and the
  optax transform; the returned function is (params, X, y, w, key) →
  (params, history). That makes it directly ``vmap``-able over a stacked
  machine axis — the fleet engine reuses this exact function.
- **RNG**: one fold-able key drives shuffling and dropout; per-machine keys
  under vmap give each machine an independent stream.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

_LOSSES = {
    "mse": lambda diff: diff * diff,
    "mean_squared_error": lambda diff: diff * diff,
    "mae": lambda diff: jnp.abs(diff),
    "mean_absolute_error": lambda diff: jnp.abs(diff),
    "huber": lambda diff: optax.huber_loss(diff, jnp.zeros_like(diff)),
}


# the loss of a module that brings its own: ``apply(variables, x, targets=y,
# method="sample_losses") -> ((batch,) losses, counters)``
MODULE_LOSS = "module"


def make_loss_fn(apply_fn: Callable, loss: str = "mse") -> Callable:
    """Weighted per-sample loss: (params, x, y, w, key) → (scalar, counters).

    ``w`` masks padding rows; the mean is over real rows only. ``counters``
    is what the module counts while it computes its own loss (a dict of
    arrays the fit sums over its steps); empty for an elementwise loss.
    """
    if loss != MODULE_LOSS and loss not in _LOSSES:
        raise ValueError(
            f"Unknown loss {loss!r}; supported: {sorted(_LOSSES) + [MODULE_LOSS]}"
        )

    def loss_fn(params, x, y, w, dropout_key):
        kwargs = dict(
            deterministic=dropout_key is None,
            rngs=None if dropout_key is None else {"dropout": dropout_key},
        )
        if loss == MODULE_LOSS:
            per_sample, counters = apply_fn(
                {"params": params}, x, targets=y, method="sample_losses", **kwargs
            )
        else:
            pred = apply_fn({"params": params}, x, **kwargs)
            per_sample = jnp.mean(_LOSSES[loss](pred - y), axis=-1)
            counters = {}
        wsum = jnp.maximum(jnp.sum(w), 1.0)
        return jnp.sum(per_sample * w) / wsum, counters

    return loss_fn


class FitResult(NamedTuple):
    params: Any
    loss_history: jnp.ndarray  # (epochs,) weighted mean loss per epoch
    counters: Any = None  # the loss's counters, summed over the fit's steps
    opt_state: Any = None  # the optimizer's state after the last step


def make_batch_step(
    apply_fn: Callable,
    optimizer: optax.GradientTransformation,
    loss: str = "mse",
    use_dropout: bool = False,
) -> Callable:
    """One mini-batch SGD step: ``((params, opt_state), (x, y, w, key)) →
    ((params, opt_state), (loss, wsum, counters))`` — the scanned body of
    :func:`make_fit_fn`, exposed so FLOP accounting can compile exactly the
    step the training loop runs (XLA's ``cost_analysis`` counts a scan body
    ONCE regardless of trip count, so whole-program flops undercount
    training loops; see ``parallel.fleet.fleet_flops_accounting``)."""
    loss_fn = make_loss_fn(apply_fn, loss)
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def batch_step(carry, batch):
        params, opt_state = carry
        xi, yi, wi, ki = batch
        # named scopes are op metadata: a device trace tells this step's
        # ops (and the loop that holds them) from the predict passes'
        with jax.named_scope("optimizer_step"):
            (batch_loss, counters), grads = grad_fn(
                params, xi, yi, wi, ki if use_dropout else None
            )
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        return (params, opt_state), (batch_loss, jnp.sum(wi), counters)

    return batch_step


def make_fit_fn(
    apply_fn: Callable,
    optimizer: optax.GradientTransformation,
    loss: str = "mse",
    batch_size: int = 32,
    epochs: int = 1,
    shuffle: bool = True,
    use_dropout: bool = False,
    unroll: int = 1,
) -> Callable:
    """Build the compiled training program.

    Returns ``fit(params, X, y, w, key) -> FitResult`` where ``X.shape[0]``
    must be a multiple of ``batch_size`` (see :func:`pad_to_batches`).

    ``unroll`` inlines that many mini-batch steps per loop iteration of the
    inner scan (``lax.scan``'s own knob): tiny fleet models are dominated
    by per-iteration dispatch overhead on TPU, and unrolling lets XLA
    schedule several steps per dispatch. Pure scheduling — the step
    sequence and numerics are unchanged; compile time grows with the
    unrolled body, so memory-/compile-constrained callers keep 1.
    """
    batch_step = make_batch_step(
        apply_fn, optimizer, loss=loss, use_dropout=use_dropout
    )

    def fit(params, X, y, w, key, opt_state=None) -> FitResult:
        """``opt_state``: the optimizer's state to start from (a caller that
        keeps the training state in buffers of its own); fresh by default."""
        n = X.shape[0]
        steps = n // batch_size
        if opt_state is None:
            opt_state = optimizer.init(params)

        def epoch_step(carry, epoch_key):
            params, opt_state = carry
            perm_key, drop_key = jax.random.split(epoch_key)
            if shuffle:
                perm = jax.random.permutation(perm_key, n)
            else:
                perm = jnp.arange(n)
            Xb = X[perm].reshape(steps, batch_size, *X.shape[1:])
            yb = y[perm].reshape(steps, batch_size, *y.shape[1:])
            wb = w[perm].reshape(steps, batch_size)
            drop_keys = jax.random.split(drop_key, steps)

            (params, opt_state), (batch_losses, batch_wsums, counters) = (
                jax.lax.scan(
                    batch_step,
                    (params, opt_state),
                    (Xb, yb, wb, drop_keys),
                    unroll=min(unroll, steps) if steps else 1,
                )
            )
            epoch_loss = jnp.sum(batch_losses * batch_wsums) / jnp.maximum(
                jnp.sum(batch_wsums), 1.0
            )
            return (params, opt_state), (epoch_loss, counters)

        epoch_keys = jax.random.split(key, epochs)
        with jax.named_scope("epoch_loop"):
            (params, opt_state), (history, counters) = jax.lax.scan(
                epoch_step, (params, opt_state), epoch_keys
            )
        return FitResult(
            params=params, loss_history=history,
            # (epochs, steps, ...) a counter: the fit's total
            counters=jax.tree_util.tree_map(
                lambda c: jnp.sum(c, axis=(0, 1)), counters
            ),
            opt_state=opt_state,
        )

    return fit


def pad_to_batches(
    X: np.ndarray, y: np.ndarray, batch_size: int
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Pad ``(X, y)`` with zero rows to a multiple of ``batch_size``; returns
    ``(Xp, yp, w)`` where ``w`` is 1.0 on real rows, 0.0 on padding."""
    n = X.shape[0]
    if n == 0:
        raise ValueError("Cannot fit on an empty dataset")
    steps = max(1, -(-n // batch_size))
    padded = steps * batch_size
    pad = padded - n
    w = np.ones(padded, dtype=np.float32)
    if pad:
        X = np.concatenate([X, np.zeros((pad, *X.shape[1:]), X.dtype)])
        y = np.concatenate([y, np.zeros((pad, *y.shape[1:]), y.dtype)])
        w[n:] = 0.0
    return X, y, w


def make_predict_fn(apply_fn: Callable) -> Callable:
    """Deterministic forward pass: (params, X) → predictions."""

    def predict(params, X):
        return apply_fn({"params": params}, X, deterministic=True)

    return predict
