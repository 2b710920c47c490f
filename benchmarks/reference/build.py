"""Plain reference for one machine's build: scalers, cross-validation, final
fit, error scaler, thresholds, anomaly scores.

Straight ``jax.numpy``, one machine at a time, no windows materialised ahead
of the batch, no fused folds: each fit is its own loop. It imports nothing of
the program and names no model kind: the configuration's ``reference_model``
(a dictionary: ``kind``, ``epochs``, ``batch_size``, ``n_splits``,
``learning_rate`` and what the kind needs) is handed to the kind's module,
``benchmarks/reference/models/<kind>.py``, for its sample layout, its initial
parameters, its forward pass and, where it brings one, its loss. Flax is used there for one thing only: to
draw the initial weights, because the configuration's "same seed, same
weights" is defined through Flax's per-path key folding; the forward passes
read the resulting dictionary with plain matrix products.

``dtype`` is the precision of the whole computation. The reference runs in
float32 under ``jax.default_matmul_precision("highest")``; the control runs
the same code in bfloat16 (parameters, Adam moments, activations, loss).
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from benchmarks.reference import models


# ----------------------------------------------------------------- fit ----
def _minmax(x, mask):
    lo = jnp.min(jnp.where(mask[:, None], x, jnp.inf), axis=0)
    hi = jnp.max(jnp.where(mask[:, None], x, -jnp.inf), axis=0)
    span = hi - lo
    scale = 1.0 / jnp.where(span < 1e-12, 1.0, span)
    return scale, -lo * scale


def _adam_step(params, m, v, grads, t, lr, dtype):
    b1, b2, eps = 0.9, 0.999, 1e-8
    m = jax.tree_util.tree_map(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
    v = jax.tree_util.tree_map(lambda a, g: b2 * a + (1 - b2) * g * g, v, grads)
    c1 = 1 - jnp.asarray(b1, jnp.float32) ** t
    c2 = 1 - jnp.asarray(b2, jnp.float32) ** t
    params = jax.tree_util.tree_map(
        lambda p, a, b: (
            p - lr * (a / c1.astype(dtype)) / (jnp.sqrt(b / c2.astype(dtype)) + eps)
        ).astype(dtype),
        params, m, v,
    )
    return params, m, v


def make_build(model: Dict[str, Any], n_rows: int, n_features: int,
               dtype=jnp.float32, fault: Optional[str] = None):
    """``(build, anomaly, initial)`` for one machine whose targets are rows
    of its inputs, where the kind's layout (``models.Layout``) puts them.
    ``build(X (n_rows, F) raw float32, w (n_rows,), key) -> dict``. Rows with
    ``w == 0`` are the program's padding; they sit where the program puts
    them (in front), because the shuffle is over the padded sample axis. A
    sample counts only where every row of its window and of its targets is
    real. Residuals, the error scaler, thresholds and ``anomaly`` are over
    predicted ROWS, ``rows_out`` to a sample.

    The loss is the kind's own where it brings one (``loss(model, params,
    windows, targets) -> (B,)``), else the mean squared error of its
    ``apply``. Where the configuration states ``micro_batch`` the batch's
    gradient is taken in blocks of that many samples and divided once.

    One fitted model is kept: the fold fits hand back their residuals, the
    final fit its parameters and loss history, and the initial parameters
    are drawn again from their key wherever they are needed (``initial(key)``
    gives them to a caller too: they are no part of ``build``'s result), so
    that the program needs five copies of a model alive at once (the fitted
    one, a fit's current one, Adam's two moments, the gradient) and not one
    more for every fold. What a compiler makes of that is its own: the v5e's
    reads 5.15 copies for a model of many leaves (``tools/size_probe.py``)
    and 3.14 for one leaf, XLA's CPU backend 8.27 for one leaf
    (``tests/test_kinds.py``).

    ``fault`` plants one of the faults the check has to catch, for the
    readings its limits are held against: ``"half_batch"`` leaves half of
    every batch out and takes the mean over the rest; ``"state_unchanged"``
    returns every fit's parameters as they started."""
    kind = models.for_kind(model)
    lay = models.layout(model)
    L, R = lay.lookback, lay.rows_out
    B, n_splits = int(model["batch_size"]), int(model["n_splits"])
    micro = int(model.get("micro_batch", B))
    n_samples = lay.n_samples(n_rows)
    padded = -(-n_samples // B) * B
    steps = padded // B

    def apply(params, windows):
        return kind.apply(model, params, windows)

    @jax.jit  # traced once, however many fits start from it
    def initial(key):
        """What every fit of the machine with this ``key`` starts from."""
        init_key = jax.random.split(key, n_splits + 2)[0]
        return jax.tree_util.tree_map(
            lambda a: a.astype(dtype),
            kind.init(model, init_key, n_features, n_features),
        )

    def sample_rows(n_total, idx):
        """(first row of each sample's window, its target rows (.., R))"""
        first = lay.lead(n_total) + idx * R
        last_target = first + lay.target_offset
        return first, last_target[..., None] - (R - 1) + jnp.arange(R)

    def per_sample_targets(rows):
        # (n, R, F) -> what ``apply`` returns for n samples
        return rows[:, 0] if R == 1 else rows

    def batch_inputs(Xs, idx):
        # samples past the last real one are padding of weight 0
        first, _ = sample_rows(n_rows, jnp.minimum(idx, n_samples - 1))
        return Xs[first[:, None] + jnp.arange(L)[None, :]]

    def sample_losses(params, xb, yb):
        if hasattr(kind, "loss"):
            return kind.loss(model, params, xb, yb).astype(dtype)
        pred = apply(params, xb).astype(dtype)
        return jnp.mean(((pred - yb) ** 2).reshape(yb.shape[0], -1), axis=-1)

    def loss_fn(params, xb, yb, wb):
        return jnp.sum(sample_losses(params, xb, yb) * wb) / jnp.maximum(jnp.sum(wb), 1.0)

    def loss_and_grad(params, xb, yb, wb):
        if fault == "half_batch":
            xb, yb, wb = xb[: B // 2], yb[: B // 2], wb[: B // 2]
        if micro >= xb.shape[0]:
            return jax.value_and_grad(loss_fn)(params, xb, yb, wb)
        if xb.shape[0] % micro:
            raise ValueError(f"micro_batch {micro} does not divide a batch of {xb.shape[0]}")

        def block(carry, blk):
            total, grads = carry
            part, part_grads = jax.value_and_grad(
                lambda p: jnp.sum(sample_losses(p, blk[0], blk[1]) * blk[2])
            )(params)
            return (total + part, jax.tree_util.tree_map(jnp.add, grads, part_grads)), None

        blocks = jax.tree_util.tree_map(
            lambda a: a.reshape(-1, micro, *a.shape[1:]), (xb, yb, wb)
        )
        zero = (jnp.zeros((), dtype), jax.tree_util.tree_map(jnp.zeros_like, params))
        (total, grads), _ = jax.lax.scan(block, zero, blocks)
        weight = jnp.maximum(jnp.sum(wb), 1.0)
        return total / weight, jax.tree_util.tree_map(lambda g: g / weight, grads)

    @jax.jit  # traced once for the folds' loop and the final fit
    def fit(params0, Xs, targets, wt, key):
        zeros = jax.tree_util.tree_map(jnp.zeros_like, params0)

        def batch_step(carry, idx):
            params, m, v, t = carry
            t = t + 1
            loss, grads = loss_and_grad(
                params, batch_inputs(Xs, idx), targets[idx], wt[idx]
            )
            params, m, v = _adam_step(
                params, m, v, grads, t, float(model["learning_rate"]), dtype
            )
            return (params, m, v, t), (loss.astype(jnp.float32),
                                       jnp.sum(wt[idx]).astype(jnp.float32))

        def epoch(carry, epoch_key):
            perm_key, _ = jax.random.split(epoch_key)
            perm = jax.random.permutation(perm_key, padded).reshape(steps, B)
            carry, (losses, wsums) = jax.lax.scan(batch_step, carry, perm)
            return carry, jnp.sum(losses * wsums) / jnp.maximum(jnp.sum(wsums), 1.0)

        carry = (params0, zeros, zeros, jnp.zeros((), jnp.float32))
        (params, _, _, _), history = jax.lax.scan(
            epoch, carry, jax.random.split(key, int(model["epochs"]))
        )
        if fault == "state_unchanged":
            params = params0
        return params, history

    def predict_all(params, Xs):
        chunks = jnp.arange(padded).reshape(steps, B)
        preds = jax.lax.map(
            lambda idx: apply(params, batch_inputs(Xs, idx)), chunks
        )
        return preds.reshape(padded * R, -1).astype(jnp.float32)

    def build(X, w, key):
        real = w > 0
        scale, offset = _minmax(X, real)
        Xs32 = X * scale + offset
        Xs = Xs32.astype(dtype)
        # sample weights and targets over the padded sample axis
        first, target_rows = sample_rows(n_rows, jnp.arange(n_samples))
        win_w = jnp.min(w[first[:, None] + jnp.arange(L)[None, :]], axis=1)
        wt = win_w * jnp.min(w[target_rows], axis=1)
        targets = per_sample_targets(Xs[target_rows])
        raw_targets = X[target_rows]
        pad = padded - n_samples
        wt = jnp.pad(wt, (0, pad)).astype(dtype)
        targets = jnp.pad(targets, ((0, pad),) + ((0, 0),) * (targets.ndim - 1))
        # the program un-scales the PADDED targets: padding rows carry the
        # value the scaler maps 0 to; they have no weight anywhere
        raw_targets = jnp.pad(raw_targets, ((0, pad), (0, 0), (0, 0)))
        raw_targets = raw_targets.reshape(padded * R, -1)

        keys = jax.random.split(key, n_splits + 2)
        fit_key, fold_keys = keys[1], keys[2:]  # keys[0]: ``initial``'s

        # sklearn TimeSeriesSplit over the real samples, as masks
        wt32 = wt.astype(jnp.float32)
        is_real = (wt32 > 0).astype(jnp.float32)
        n_real = jnp.sum(is_real).astype(jnp.int32)
        rank = jnp.cumsum(is_real) - is_real
        test_size = n_real // (n_splits + 1)
        train_masks, test_masks = [], []
        for i in range(n_splits):
            test_start = n_real - (n_splits - i) * test_size
            test_end = test_start + test_size
            train_masks.append(is_real * (rank < test_start))
            test_masks.append(is_real * (rank >= test_start) * (rank < test_end))

        # one fit per fold, one after the other (``lax.map``: traced once,
        # run in sequence), each handing back its residuals alone
        def fold_fit(args):
            weights, key_ = args
            fitted, _ = fit(initial(key), Xs, targets, weights, key_)
            pred_raw = (predict_all(fitted, Xs) - offset) / scale
            return jnp.abs(raw_targets - pred_raw)

        errs = jax.lax.map(fold_fit, (
            jnp.stack([(wt32 * m).astype(dtype) for m in train_masks]), fold_keys,
        ))  # (K, P * R, T)
        # then the final fit, the one model that is kept
        params, history = fit(initial(key), Xs, targets, wt, fit_key)
        masks = jnp.repeat(jnp.stack(test_masks) > 0, R, axis=1)  # (K, P * R)
        cv_mse = jnp.mean(
            jnp.sum(errs**2 * masks[:, :, None], axis=1)
            / jnp.maximum(jnp.sum(masks, axis=1), 1.0)[:, None],
            axis=-1,
        )
        emin = jnp.min(jnp.where(masks[:, :, None], errs, jnp.inf), axis=(0, 1))
        emax = jnp.max(jnp.where(masks[:, :, None], errs, -jnp.inf), axis=(0, 1))
        span = emax - emin
        e_scale = 1.0 / jnp.where(span < 1e-12, 1.0, span)
        e_offset = -emin * e_scale
        scaled = jnp.where(masks[:, :, None], errs * e_scale + e_offset, jnp.nan)
        norms = jnp.where(
            masks, jnp.linalg.norm(jnp.nan_to_num(scaled), axis=-1), jnp.nan
        )
        return {
            "params": params,
            "loss_history": history,
            "input_scale": scale,
            "input_offset": offset,
            "error_scale": e_scale,
            "error_offset": e_offset,
            "cv_mse": cv_mse,
            "total_threshold": jnp.nanpercentile(norms, 99),
            "tag_thresholds": jnp.nanpercentile(
                scaled.reshape(-1, scaled.shape[-1]), 99, axis=0
            ),
        }

    def anomaly(result: Dict[str, Any], X_probe):
        """Mean total anomaly score of raw rows ``X_probe`` under the built
        machine: |x - x_hat| in raw units, error-scaled, L2 over tags."""
        scale, offset = result["input_scale"], result["input_offset"]
        Xs = (X_probe * scale + offset).astype(dtype)
        n_probe = X_probe.shape[0]
        first, target_rows = sample_rows(n_probe, jnp.arange(lay.n_samples(n_probe)))
        inputs = Xs[first[:, None] + jnp.arange(L)[None, :]]
        truth = X_probe[target_rows].reshape(-1, X_probe.shape[1])
        pred = apply(result["params"], inputs).astype(jnp.float32)
        err = jnp.abs(truth - (pred.reshape(truth.shape) - offset) / scale)
        scaled = err * result["error_scale"] + result["error_offset"]
        return jnp.mean(jnp.linalg.norm(scaled, axis=-1))

    return build, anomaly, initial
