"""The compiled fleet-training program.

One machine's ENTIRE build — input/target scaler fit, windowing,
TimeSeriesSplit-style cross-validation, error-scaler fit on out-of-fold
residuals, final fit — is a single pure function of
``(X, y, w, key) → MachineResult``. :func:`train_fleet_arrays` ``vmap``s it
over a stacked machine axis and shards that axis over a mesh: the
reference's N Argo pods become one XLA program (SURVEY.md §2.2, §4.1).

Static-shape strategy (the "hard part" SURVEY.md §8 calls out):

- machines in a bucket share (rows N, features F, targets T, architecture);
  shorter machines are padded with zero-weight rows, and the bucket's
  machine count is padded to a multiple of the mesh size with zero-weight
  machines — masks make padding exact, not approximate;
- CV folds are *weight masks* over the padded row axis, not array slices,
  so one compilation serves every machine regardless of its true row count
  (fold boundaries follow sklearn TimeSeriesSplit on each machine's REAL
  samples — :func:`timeseries_fold_masks` computes them traced from the
  weight vector, so padding never shifts a boundary);
- the per-fold fits reuse the single-machine jittable fit program
  (:func:`gordo_components_tpu.models.train.make_fit_fn`) unchanged — the
  fleet engine is a transform over the single path, not a fork of it.

Residual semantics: the model trains in scaled space; predictions are
inverse-transformed and residuals computed in RAW target units, matching the
reference's canonical ``DiffBasedAnomalyDetector(TransformedTargetRegressor
(Pipeline([scaler, model])), MinMaxScaler())`` configuration.
"""

from __future__ import annotations

import logging
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models.train import FitResult, make_fit_fn, make_predict_fn
from ..observability import compiles, spans
from ..observability.registry import REGISTRY
from ..ops import windowing
from ..ops.scaling import ScalerParams
from ..utils.cache import cached as _cached  # shared FIFO program memo
from .mesh import fleet_sharding, pad_to_multiple

_EPS = 1e-12
logger = logging.getLogger(__name__)

_M_FLEET_PROGRAMS = REGISTRY.counter(
    "gordo_fleet_programs_built_total",
    "Fleet training programs constructed (jit = traced wrapper, compile "
    "deferred to first call; aot = fleet_executable, compile paid here) "
    "and what the persistent compile cache answered for the compile "
    "(hit / miss / off; deferred for jit)",
    labels=("kind", "cache"),
)


class FleetSpec(NamedTuple):
    """Static (compile-time) description of one bucket's machines."""

    module: Any  # flax module — shared architecture
    optimizer: Any  # optax transform
    loss: str
    lookahead: Optional[int]  # None=flat, 0=reconstruction, k>=1=k-step forecast
    lookback_window: int
    scaler: str  # "minmax" | "standard" | "none"
    feature_range: Tuple[float, float]
    batch_size: int
    epochs: int
    n_splits: int  # 0 disables CV (error scaler fits on train residuals)
    use_dropout: bool = False
    # True ⇔ the config wraps the model in a TransformedTargetRegressor:
    # targets train scaled and predictions are inverse-transformed. False
    # (plain Pipeline / bare estimator) ⇔ targets stay raw, matching the
    # single-machine path where Pipeline.fit passes y through untransformed.
    scale_targets: bool = True
    # ("standard" only) (with_mean, with_std)
    scaler_options: Tuple[bool, bool] = (True, True)
    # the TransformedTargetRegressor's own transformer — independent of the
    # input scaler (a config may scale targets but not inputs or vice versa)
    target_scaler: str = "minmax"
    target_feature_range: Tuple[float, float] = (0.0, 1.0)
    target_scaler_options: Tuple[bool, bool] = (True, True)
    # the ONE fact the execution strategy follows, set by _spec_for from the
    # module's remat request (a model that recomputes its activations trades
    # FLOPs for memory already). Not memory-constrained: the K fold fits and
    # the final fit run as one vmapped fit at (K+1)× the step's activations,
    # prediction runs in chunks up to four training batches wide, and a
    # flat-input fit inlines four batch steps a scan iteration.
    # Memory-constrained: the fits run in sequence on one donated training
    # state, prediction stays one batch wide, nothing is unrolled, and
    # slices are sized from the parameter count. :func:`sequential_fits`
    # and :func:`fit_unroll` derive the rest; the two fold modes train the
    # same models (the parity tests of tests/test_fleet.py).
    memory_constrained: bool = False
    # rows a windowed sample is judged against; its samples lie that many
    # rows apart (ops.windowing: "several rows a sample"). 1: every window,
    # a row apart, judged against one row.
    rows_out: int = 1


class MachineBatch(NamedTuple):
    """Stacked per-machine data: X (M,N,F) raw, y (M,N,T) raw, w (M,N) row
    weights (0 on padding), keys (M, key_width) uint32 raw PRNG keys —
    ``key_width`` is impl-dependent (threefry 2, rbg 4); build keys with
    ``jax.random.split`` and size avatars via :func:`prng_key_width`."""

    X: jnp.ndarray
    y: jnp.ndarray
    w: jnp.ndarray
    keys: jnp.ndarray


class MachineResult(NamedTuple):
    params: Any  # model params (stacked under vmap)
    input_scaler: ScalerParams  # (F,)
    target_scaler: ScalerParams  # (T,)
    error_scaler: ScalerParams  # (T,) minmax over |raw residuals|
    loss_history: jnp.ndarray  # (epochs,)
    # (n_splits, len(FLEET_CV_METRICS)) masked fold metrics (or (0, 4))
    cv_scores: jnp.ndarray
    tag_thresholds: jnp.ndarray  # (T,) 99th pct of scaled residuals
    total_threshold: jnp.ndarray  # () 99th pct of residual L2 norms
    # what the model's own loss counted over the final fit's steps (a dict of
    # arrays; empty for an elementwise loss), and the samples the fits
    # predicted (``predicted_samples`` of ``predictable_samples``): span
    # attributes of the slice
    counters: Any = None


FleetResult = MachineResult  # stacked variant returned by train_fleet_arrays


def _masked_minmax(x, w, feature_range) -> ScalerParams:
    lo, hi = feature_range
    mask = (w > 0)[:, None]
    xmin = jnp.min(jnp.where(mask, x, jnp.inf), axis=0)
    xmax = jnp.max(jnp.where(mask, x, -jnp.inf), axis=0)
    # all-padding safety: no real rows → identity scaler
    xmin = jnp.where(jnp.isfinite(xmin), xmin, 0.0)
    xmax = jnp.where(jnp.isfinite(xmax), xmax, 1.0)
    span = xmax - xmin
    scale = (hi - lo) / jnp.where(span < _EPS, 1.0, span)
    return ScalerParams(scale=scale, offset=lo - xmin * scale)


def _masked_standard(x, w, with_mean: bool = True, with_std: bool = True) -> ScalerParams:
    wsum = jnp.maximum(jnp.sum(w), 1.0)
    mean = jnp.sum(x * w[:, None], axis=0) / wsum
    var = jnp.sum((x - mean) ** 2 * w[:, None], axis=0) / wsum
    std = jnp.sqrt(var)
    scale = (
        1.0 / jnp.where(std < _EPS, 1.0, std)
        if with_std
        else jnp.ones_like(std)
    )
    offset = -mean * scale if with_mean else jnp.zeros_like(mean)
    return ScalerParams(scale=scale, offset=offset)


def _fit_scaler(kind: str, options, feature_range, x, w) -> ScalerParams:
    if kind == "minmax":
        return _masked_minmax(x, w, feature_range)
    if kind == "standard":
        with_mean, with_std = options
        return _masked_standard(x, w, with_mean, with_std)
    if kind == "none":
        n = x.shape[1]
        return ScalerParams(scale=jnp.ones(n), offset=jnp.zeros(n))
    raise ValueError(f"Unknown scaler kind {kind!r}")


# column order of the per-fold metric vector the compiled program emits —
# the same four metrics (sklearn ``uniform_average`` semantics) the
# single-machine builder records via models.metrics.METRICS, so fleet and
# single builds expose identical CV metadata keys
FLEET_CV_METRICS = (
    "explained_variance_score",
    "r2_score",
    "mean_squared_error",
    "mean_absolute_error",
)


def _masked_metrics(y, pred, w) -> jnp.ndarray:
    """Weighted fold metrics in :data:`FLEET_CV_METRICS` order, NaN when the
    fold has no real rows (empty folds report as missing, never as a fake
    perfect score). Per-output scores average uniformly across outputs,
    matching sklearn ``multioutput="uniform_average"`` (pinned against
    sklearn by tests/test_fleet_parity.py)."""
    w_total = jnp.sum(w)
    wsum = jnp.maximum(w_total, 1.0)
    wcol = w[:, None]
    diff = y - pred
    # explained variance: 1 - Var(residual)/Var(y)
    dmean = jnp.sum(diff * wcol, axis=0) / wsum
    dvar = jnp.sum((diff - dmean) ** 2 * wcol, axis=0) / wsum
    ymean = jnp.sum(y * wcol, axis=0) / wsum
    yvar = jnp.sum((y - ymean) ** 2 * wcol, axis=0) / wsum
    ev = 1.0 - dvar / jnp.where(yvar < _EPS, 1.0, yvar)
    ev = jnp.mean(jnp.where(yvar < _EPS, jnp.where(dvar < _EPS, 1.0, 0.0), ev))
    # r2: 1 - SS_res/SS_tot (not mean-adjusted residuals)
    ss_res = jnp.sum(diff**2 * wcol, axis=0) / wsum
    r2 = 1.0 - ss_res / jnp.where(yvar < _EPS, 1.0, yvar)
    r2 = jnp.mean(jnp.where(yvar < _EPS, jnp.where(ss_res < _EPS, 1.0, 0.0), r2))
    mse = jnp.mean(jnp.sum(diff**2 * wcol, axis=0) / wsum)
    mae = jnp.mean(jnp.sum(jnp.abs(diff) * wcol, axis=0) / wsum)
    scores = jnp.stack([ev, r2, mse, mae])
    return jnp.where(w_total > 0, scores, jnp.nan)


def timeseries_fold_masks(wt: jnp.ndarray, n_splits: int):
    """sklearn ``TimeSeriesSplit`` fold masks computed per machine on its
    REAL samples (``wt > 0``), traced — one compilation serves machines of
    any true length inside a padded bucket.

    sklearn's rule for ``n`` samples and ``k`` splits: ``test_size = n //
    (k+1)``; split ``i`` tests ranks ``[n-(k-i)*ts, n-(k-i-1)*ts)`` and
    trains on every earlier rank (``sklearn.model_selection.TimeSeriesSplit``
    semantics — parity pinned by tests/test_fleet_parity.py). Masks are in
    rank space over real samples, so padding anywhere on the axis (leading
    row alignment, trailing batch fill) never shifts fold boundaries. A numpy
    ``wt`` gives numpy masks (:func:`fold_predict_chunks` runs the rule at
    trace time)."""
    real = (wt > 0).astype(jnp.float32)
    n_real = real.sum().astype(jnp.int32)
    rank = real.cumsum() - real  # 0-based rank among real samples
    test_size = n_real // (n_splits + 1)
    masks = []
    for i in range(n_splits):
        test_start = n_real - (n_splits - i) * test_size
        test_end = n_real - (n_splits - i - 1) * test_size
        train_mask = real * (rank < test_start)
        test_mask = real * (rank >= test_start) * (rank < test_end)
        masks.append((train_mask, test_mask))
    return masks


def predict_width(spec: FleetSpec, padded: int) -> int:
    """Samples in one predict call of a windowed model. Prediction has no
    optimizer state or backward pass, so its chunks can be wider than the
    training batch: up to 4 training batches in one forward call (largest
    factor of the step count). The bound is RELATIVE to the training step
    because the predict runs under the same vmaps as it: a step that keeps
    its activations holds ~3x its forward pass, so a 4x-wide forward-only
    chunk peaks at ~4/3 of it. A memory-constrained step recomputes them and
    is deliberately small, so its chunks stay one batch wide. Values are
    unchanged: prediction is per-window."""
    if spec.memory_constrained:
        return spec.batch_size
    steps = padded // spec.batch_size
    return spec.batch_size * next(
        k for k in range(min(4, steps), 0, -1) if steps % k == 0
    )


def fold_predict_chunks(padded: int, n_splits: int, width: int) -> int:
    """The chunks of ``width`` samples that hold what any ONE fit of the
    vmapped fold mode (``n_splits`` ≥ 1) predicts, fixed at trace time: a
    fold predicts its test samples, ``n_real // (K+1) ≤ padded // (K+1)``,
    and the final fit every real sample where no fold tests any, which
    :func:`timeseries_fold_masks` leaves to machines of ``n_real ≤ K``.
    The rule itself is run on the machines that ask most of each (every
    sample real; K and K+1 real), and a ``ValueError`` raised where it asks
    more than that bound: a loop of fewer chunks would drop predictions."""
    bound = min(max(padded // (n_splits + 1), n_splits), padded)
    for n_real in (padded, n_splits, n_splits + 1):
        wt = (np.arange(padded) < n_real).astype(np.float32)
        tested = [int(test.sum()) for _, test in timeseries_fold_masks(wt, n_splits)]
        # a fold's test samples, or where none tests any, the fallback's
        wanted = max(tested) if any(tested) else int(wt.sum())
        if wanted > bound:
            raise ValueError(
                f"the fold rule has a fit of a machine of {int(wt.sum())} "
                f"real samples of {padded} predict {wanted}, over the "
                f"{bound} the vmapped fold mode's predict loop holds"
            )
    return -(-bound // width)


def _initial_params(spec: FleetSpec, n_features: int) -> Callable:
    """``init_key → params``: what every fit of a machine starts from."""
    sample_shape = (
        (1, n_features) if spec.lookahead is None
        else (1, spec.lookback_window, n_features)
    )

    def draw(init_key):
        return spec.module.init(
            init_key, jnp.zeros(sample_shape, jnp.float32), deterministic=True
        )["params"]

    return draw


def sequential_fits(spec: FleetSpec) -> bool:
    """Whether the machine's fits (the CV folds', then the final one) run one
    after another on ONE training state: a memory-constrained spec, or one
    with no folds to vmap. Such a program takes that state as a fifth,
    donated argument (:func:`fleet_state` draws it) and hands the
    optimizer's part back."""
    return spec.memory_constrained or spec.n_splits == 0


def fit_unroll(spec: FleetSpec) -> int:
    """Batch steps inlined per iteration of the training scan (``lax.scan``'s
    unroll; scheduling only, the numbers do not change): 4 for a flat-input
    model that is not memory-constrained, whose tiny step is bound by
    dispatch, else 1. A windowed step already holds a time scan or an
    attention stack; that inlining four of them costs the TPU compiler
    minutes is a figure from a deleted rig that no record holds, and the
    chip has not been asked since (ROADMAP D7)."""
    return 4 if spec.lookahead is None and not spec.memory_constrained else 1


def make_machine_program(
    spec: FleetSpec, n_rows: int, n_features: int, n_targets: int
) -> Callable:
    """Pure fn ``(X (N,F), y (N,T), w (N,), key) → MachineResult`` — the
    whole per-machine build as one traceable program. Where the fits run in
    sequence (:func:`sequential_fits`): ``(X, y, w, key, state) →
    (MachineResult, optimizer state)``, ``state`` the machine's training
    state ``(params, optimizer state)`` as buffers of the caller's."""

    apply_fn = spec.module.apply
    fit_kwargs = dict(
        loss=spec.loss,
        batch_size=spec.batch_size,
        epochs=spec.epochs,
        use_dropout=spec.use_dropout,
        unroll=fit_unroll(spec),
    )
    fit_fn = make_fit_fn(apply_fn, spec.optimizer, **fit_kwargs)
    predict_fn = make_predict_fn(apply_fn)

    L = spec.lookback_window
    la = spec.lookahead
    R = spec.rows_out  # rows a sample is judged against (windowed models)
    if la is None:
        n_samples = n_rows
    else:
        n_samples = windowing.n_windows(n_rows, L, la, R)
        if n_samples < spec.batch_size:
            raise ValueError(
                f"Bucket rows {n_rows} give {n_samples} windows "
                f"(< batch_size {spec.batch_size})"
            )
    padded = pad_to_multiple(n_samples, spec.batch_size)

    def per_row(sample_values):
        """A per-sample vector or mask (last axis) as one per predicted row."""
        return sample_values if R == 1 else jnp.repeat(sample_values, R, axis=-1)

    def prepare(Xs, ys, w):
        """Scaled rows → (inputs, targets, sample weights) padded to a whole
        number of batches. Windowing/targets delegate to
        :mod:`gordo_components_tpu.ops.windowing` — the off-by-one contract
        lives there, pinned by its golden tests, not re-derived here.

        For windowed models ``inputs`` is the window START INDEX vector,
        not materialized windows: batches gather their ``(batch, L, F)``
        windows from the scaled rows on the fly (see ``windowed_apply``
        below), so HBM holds ``(n_rows, F)`` instead of the L×-blown-up
        ``(n_windows, L, F)`` tensor — the enabler for plant-scale buckets
        (10k tags × L=32 windows would be ~1 GB per machine materialized).
        Samples lie ``rows_out`` rows apart, laid from the end, each judged
        against its ``rows_out`` rows (``targets (samples, rows_out, T)``
        where that is over 1).

        Row padding may sit ANYWHERE in the row axis (fold boundaries are
        computed on real-sample ranks, so placement is free): a window's
        weight is the MIN of its rows' weights times its target rows'
        weights, so any window touching padding is masked out exactly.
        """
        if la is None:
            inputs, targets, wt = Xs, ys, w
        else:
            starts = windowing.window_starts(n_rows, L, la, R)
            inputs = int(starts[0]) + R * jnp.arange(n_samples)
            targets = windowing.sample_targets(ys, L, la, R)
            target_idx = windowing.window_output_index(n_rows, L, la, R)
            window_w = windowing.sliding_windows(w[:, None], L, la, R)[:, :, 0]
            target_w = w[target_idx]
            if R > 1:
                target_w = jnp.min(target_w.reshape(n_samples, R), axis=1)
            wt = jnp.min(window_w, axis=1) * target_w
        pad = padded - inputs.shape[0]
        if pad:
            inputs = jnp.pad(inputs, ((0, pad),) + ((0, 0),) * (inputs.ndim - 1))
            targets = jnp.pad(
                targets, ((0, pad),) + ((0, 0),) * (targets.ndim - 1)
            )
            wt = jnp.pad(wt, (0, pad))
        return inputs, targets, wt

    draw = _initial_params(spec, n_features)

    def program(X, y, w, key, state=None):
        sx = _fit_scaler(spec.scaler, spec.scaler_options, spec.feature_range, X, w)
        if spec.scale_targets:
            # the TransformedTargetRegressor's transformer — its own kind,
            # independent of the input scaler
            sy = _fit_scaler(
                spec.target_scaler,
                spec.target_scaler_options,
                spec.target_feature_range,
                y,
                w,
            )
        else:
            # no TransformedTargetRegressor in the config: the model trains
            # against raw targets (Pipeline.fit passes y through untouched)
            sy = ScalerParams(
                scale=jnp.ones(n_targets), offset=jnp.zeros(n_targets)
            )
        Xs = X * sx.scale + sx.offset
        ys = y * sy.scale + sy.offset
        inputs, targets, wt = prepare(Xs, ys, w)
        # residuals, error scaler and thresholds are over predicted ROWS
        raw_targets = ((targets - sy.offset) / sy.scale).reshape(-1, n_targets)
        wt_rows = per_row(wt)

        if la is None:
            fit_local = fit_fn
            # a flat sample is one row: its chunk is the whole set
            predict_chunk, width = predict_fn, padded
        else:

            def windowed_apply(variables, starts, **kwargs):
                # (batch,) start indices → gather (batch, L, F) from the
                # scaled rows; grads flow only into params, so this is pure
                # data movement XLA fuses into the model's first op
                return apply_fn(
                    variables, windowing.gather_windows(Xs, starts, L), **kwargs
                )

            fit_local = make_fit_fn(windowed_apply, spec.optimizer, **fit_kwargs)
            predict_chunk = make_predict_fn(windowed_apply)
            width = predict_width(spec, padded)

        def wanted_first(wanted):
            """``(want, count, order)``: the samples where ``wanted > 0``,
            how many they are, and every sample's index with theirs first,
            in index order (a stable sort)."""
            want = wanted > 0
            count = jnp.sum(want)
            return want, count, jnp.argsort(jnp.logical_not(want), stable=True)

        def predict_at(params, wanted):
            """Predictions at the samples where ``wanted > 0``, as rows
            ``(padded·R, T)``, zero at every other row: ONE loop over chunks
            of ``width`` of them (the wanted first, in index order)
            whose trip count is the chunks they fill, so a fit that wants
            none runs none. The forward is in the graph once: a conditional
            around a second one would double an executable that has to fit
            the machines' compile cache (``PERF.md`` §7 item 13)."""
            _, count, order = wanted_first(wanted)

            def chunk(i, out):
                at = i * width + jnp.arange(width)
                samples = order[at]
                pred = predict_chunk(params, inputs[samples])
                # the last chunk's tail past the wanted samples is dropped
                samples = jnp.where(at < count, samples, padded)
                return out.at[samples].set(
                    pred.reshape(width, R, n_targets), mode="drop"
                )

            out = jax.lax.fori_loop(
                0, (count + width - 1) // width, chunk,
                jnp.zeros((padded, R, n_targets)),
            )
            return out.reshape(padded * R, n_targets)

        def predict_within(params, wanted, n_chunks):
            """:func:`predict_at`'s rows from a loop of a STATIC ``n_chunks``
            chunks that hold every wanted sample, for fits under a vmap:
            there a traced trip count would run the longest lane's for every
            lane, with a batched counter and each chunk's write a batched
            scatter, where this loop is unbatched and each chunk writes a
            stack of its own. The rows go back to index order in ONE gather,
            by each wanted sample's rank among them (the inverse of the
            sort's permutation), and every other row is exactly zero."""
            want, _, order = wanted_first(wanted)
            chunks = order[: n_chunks * width].reshape(n_chunks, width)
            preds = jax.lax.map(
                lambda samples: predict_chunk(params, inputs[samples]), chunks
            ).reshape(n_chunks * width, R, n_targets)
            rank = jnp.where(want, jnp.cumsum(want) - 1, n_chunks * width)
            out = preds.at[rank].get(mode="fill", fill_value=0.0)
            return out.reshape(padded * R, n_targets)

        keys = jax.random.split(key, spec.n_splits + 2)
        init_key, fit_key, fold_keys = keys[0], keys[1], keys[2:]

        emin = jnp.full((n_targets,), jnp.inf)
        emax = jnp.full((n_targets,), -jnp.inf)
        n_points = raw_targets.shape[0]
        fold_masks = timeseries_fold_masks(wt, spec.n_splits)
        if spec.n_splits > 0:
            train_masks = jnp.stack([m[0] for m in fold_masks])
            test_masks = jnp.stack([m[1] for m in fold_masks])
        else:
            train_masks = test_masks = jnp.zeros((0, padded))
        # every fit of the machine: the K folds', then the final one
        all_w = jnp.concatenate([train_masks * wt[None, :], wt[None, :]])
        all_keys = jnp.concatenate([fold_keys, fit_key[None]])
        # rank-space folds guarantee a nonempty train region whenever a
        # test region is nonempty; machines too short for any fold
        # (n_real < n_splits+1) get empty test masks here and fall back
        # to final-model residuals below
        sample_test_masks = test_masks * wt[None, :]  # (K, samples)
        fold_test_masks = per_row(sample_test_masks)  # (K, rows)
        # A fit predicts only the samples its result reads: a fold its own
        # test samples, the final fit every real sample where no fold tested
        # any (the fallback below) and none otherwise; the rows left at zero
        # are rows that no mask below reads
        fallback = wt * (jnp.sum(sample_test_masks) == 0)
        predict_masks = jnp.concatenate([sample_test_masks, fallback[None]])
        n_fits = spec.n_splits + 1

        def counted(counters):
            """The fits' counters and the sample passes they predicted, of
            those a prediction of every padded sample in each would be."""
            return {
                **counters,
                "predicted_samples": jnp.sum(predict_masks > 0),
                "predictable_samples": jnp.asarray(n_fits * padded),
            }

        if not sequential_fits(spec):
            # parallel CV: the K fold fits and the final fit are independent
            # programs with identical shapes, so ONE vmapped fit of K+1
            # weight vectors replaces K+1 sequential fits — sequential depth
            # drops to a single fit's epochs×batches at (K+1)× step memory
            # (see FleetSpec.memory_constrained). Per-fit keys match the scan path
            # exactly, so both modes train identical models.
            params0 = draw(init_key)
            fits = jax.vmap(
                lambda wv, kv: fit_local(params0, inputs, targets, wv, kv)
            )(all_w, all_keys)
            with jax.named_scope("cv_predict"):
                if la is None:  # one chunk, the whole set: one call a fit
                    preds = jax.vmap(lambda p: predict_fn(p, inputs))(
                        fits.params
                    )
                else:
                    n_chunks = fold_predict_chunks(padded, spec.n_splits, width)
                    preds = jax.vmap(
                        lambda p, m: predict_within(p, m, n_chunks)
                    )(fits.params, predict_masks)  # (K+1, rows, T)
            preds_raw = (preds - sy.offset) / sy.scale
            errs = jnp.abs(raw_targets[None] - preds_raw)
            fmask = (fold_test_masks > 0)[:, :, None]
            emin = jnp.min(jnp.where(fmask, errs[:-1], jnp.inf), axis=(0, 1))
            emax = jnp.max(jnp.where(fmask, errs[:-1], -jnp.inf), axis=(0, 1))
            cv_scores = jax.vmap(_masked_metrics, in_axes=(None, 0, 0))(
                raw_targets, preds_raw[:-1], fold_test_masks
            )
            final = jax.tree_util.tree_map(lambda a: a[-1], fits)
            final = final._replace(counters=counted(final.counters))
            state_out = None
        else:
            # sequential fits: ONE fit in the compiled graph, scanned over
            # the K+1 stacked weight vectors (the fits share every shape) —
            # an unrolled Python loop would inline K+1 copies of the whole
            # training program and multiply XLA compile time accordingly;
            # vs the vmapped fits this holds step memory at 1×, the right trade
            # for plant-scale remat configs and the only one for a model
            # whose training state fills the chip. The state is the scan's
            # carry, in the caller's buffers, and every fit trains it in
            # place: the first as it came (:func:`machine_state` drew it
            # from this key), each later one after a reset that selects the
            # start over what the last fit left, element by element, into
            # the same buffers (a select on the fit's index, which the
            # compiler cannot fold: a start drawn into buffers of its own
            # would be a second copy of the state, alive through the fit).
            # The final fit runs last and leaves the machine's parameters
            # there.
            def one_fit(carry, xs):
                (params, opt_state), emin, emax = carry
                weights, wtest, wpredict, fit_key_, first = xs
                fresh = draw(init_key)
                reset = lambda new, old: jnp.where(first, old, new)  # noqa: E731
                params = jax.tree_util.tree_map(reset, fresh, params)
                opt_state = jax.tree_util.tree_map(
                    reset, spec.optimizer.init(params), opt_state
                )
                res = fit_local(
                    params, inputs, targets, weights, fit_key_,
                    opt_state=opt_state,
                )
                with jax.named_scope("cv_predict"):
                    pred = predict_at(res.params, wpredict)
                pred_raw = (pred - sy.offset) / sy.scale
                err = jnp.abs(raw_targets - pred_raw)
                mask = (wtest > 0)[:, None]
                emin = jnp.minimum(
                    emin, jnp.min(jnp.where(mask, err, jnp.inf), axis=0)
                )
                emax = jnp.maximum(
                    emax, jnp.max(jnp.where(mask, err, -jnp.inf), axis=0)
                )
                scores = _masked_metrics(raw_targets, pred_raw, wtest)
                return ((res.params, res.opt_state), emin, emax), (
                    scores, err, res.loss_history, res.counters
                )

            if state is None:  # a caller without buffers of its own
                params0 = draw(init_key)
                state = (params0, spec.optimizer.init(params0))
            (state, emin, emax), (scores, errs, histories, counters) = (
                jax.lax.scan(
                    one_fit,
                    (state, emin, emax),
                    (
                        all_w,
                        # the final fit tests nothing, and predicts nothing
                        # unless its residuals are the fallback below
                        jnp.concatenate(
                            [fold_test_masks, jnp.zeros((1, n_points))]
                        ),
                        predict_masks,
                        all_keys,
                        jnp.arange(n_fits) == 0,
                    ),
                )
            )
            cv_scores = scores[:-1]
            final = FitResult(
                params=state[0],
                loss_history=histories[-1],
                counters=counted(
                    jax.tree_util.tree_map(lambda c: c[-1], counters)
                ),
            )
            state_out = state[1]
        err_final = errs[-1]
        with jax.named_scope("error_scaler_thresholds"):
            # final-model residuals over all real rows: the error-scaler
            # source when CV is off, and the per-machine fallback when no CV
            # fold covered this machine's data (short machine in a tall
            # bucket)
            mask_final = (wt_rows > 0)[:, None]
            fmin = jnp.min(jnp.where(mask_final, err_final, jnp.inf), axis=0)
            fmax = jnp.max(jnp.where(mask_final, err_final, -jnp.inf), axis=0)

            use_cv = jnp.sum(fold_test_masks) > 0
            emin = jnp.where(use_cv, emin, fmin)
            emax = jnp.where(use_cv, emax, fmax)
            emin = jnp.where(jnp.isfinite(emin), emin, 0.0)
            emax = jnp.where(jnp.isfinite(emax), emax, 1.0)
            span = emax - emin
            e_scale = 1.0 / jnp.where(span < _EPS, 1.0, span)
            error_scaler = ScalerParams(scale=e_scale, offset=-emin * e_scale)

            # thresholds: 99th percentile of scaled residuals — out-of-fold
            # when CV covered this machine, final-model residuals otherwise
            fallback_mask = wt_rows * jnp.where(use_cv, 0.0, 1.0)
            masks = jnp.concatenate(
                [fold_test_masks, fallback_mask[None]]
            )  # (K+1, rows)
            scaled = errs * error_scaler.scale + error_scaler.offset
            scaled = jnp.where((masks > 0)[:, :, None], scaled, jnp.nan)
            tag_thresholds = jnp.nan_to_num(
                jnp.nanpercentile(scaled.reshape(-1, n_targets), 99, axis=0)
            )
            norms = jnp.linalg.norm(
                jnp.nan_to_num(scaled), axis=-1
            ) + jnp.where(masks > 0, 0.0, jnp.nan)
            total_threshold = jnp.nan_to_num(jnp.nanpercentile(norms, 99))

        result = MachineResult(
            params=final.params,
            input_scaler=sx,
            target_scaler=sy,
            error_scaler=error_scaler,
            loss_history=final.loss_history,
            cv_scores=cv_scores,
            tag_thresholds=tag_thresholds,
            total_threshold=total_threshold,
            counters=final.counters,
        )
        return result if state_out is None else (result, state_out)

    return program


def machine_state(spec: FleetSpec, n_features: int) -> Callable:
    """``key → (params, optimizer state)``: one machine's training state as
    :func:`make_machine_program` draws it at the start of a fit, from the
    same key. :func:`fleet_state` stacks it into buffers the allocator
    counts, which the program trains in place."""
    initial = _initial_params(spec, n_features)

    def draw(key):
        params = initial(jax.random.split(key, spec.n_splits + 2)[0])
        return params, spec.optimizer.init(params)

    return draw


_PROGRAM_CACHE: dict = {}
_PROGRAM_CACHE_MAX = 128  # distinct (spec, shape, mesh) programs kept live


def _over_machines(machine_program: Callable) -> Callable:
    """``machine_program`` over a leading machine axis: ``vmap``, except that
    a slice of ONE machine runs the machine's program as it is (a batch of
    one would turn its conditionals into both branches and its grouped
    products into batched ones). The result is called ``program``: the
    benchmark finds the train program's runs in a device trace by the XLA
    module's name, ``jit_program``."""

    def program(*args):
        if args[0].shape[0] > 1:
            return jax.vmap(machine_program)(*args)
        out = machine_program(*jax.tree_util.tree_map(lambda a: a[0], args))
        return jax.tree_util.tree_map(lambda a: a[None], out)

    return program


def fleet_program(
    spec: FleetSpec,
    n_rows: int,
    n_features: int,
    n_targets: int,
    mesh=None,
):
    """The jitted vmap-over-machines program for one bucket shape, cached so
    repeated calls with the same spec/shape reuse the traced+compiled
    executable (``jax.jit`` keys on function identity — without this cache
    every ``train_fleet_arrays`` call would re-trace).

    The batch buffers are NOT donated: no output has a batch buffer's
    shape, so XLA has nothing to alias them to — on a v5e (PR 21) the
    donation was reported "not usable" for all four inputs and changed
    nothing but the warning count. The training state of a program whose
    fits run in sequence (:func:`sequential_fits`) IS: its fifth argument,
    aliased to the parameters and the optimizer state it hands back."""

    def build():
        _M_FLEET_PROGRAMS.labels("jit", "deferred").inc()
        program = _over_machines(
            make_machine_program(spec, n_rows, n_features, n_targets)
        )
        n_args = 5 if sequential_fits(spec) else 4
        donate = (4,) if sequential_fits(spec) else ()
        if mesh is None:
            return jax.jit(program, donate_argnums=donate)
        shard = fleet_sharding(mesh)
        return jax.jit(
            program,
            in_shardings=(shard,) * n_args,
            out_shardings=shard,
            donate_argnums=donate,
        )

    key = (spec, n_rows, n_features, n_targets, mesh)
    return _cached(_PROGRAM_CACHE, _PROGRAM_CACHE_MAX, key, build)


def _compile_in_stages(program, avatars, name: str):
    """``program.lower(*avatars).compile()``, the same executable, in its
    three stages, each a span under the stage open here (``fleet.program``
    on the build's path): ``fleet.trace`` (the Python function traced to a
    jaxpr), ``fleet.lower`` (to StableHLO) and ``fleet.compile`` (the XLA
    compile, or the persistent cache's load: ``cache`` says which, see
    ``observability.compiles``). ``name`` (``program``) tells the state and
    the train program apart. Returns ``(compiled, cache)``."""
    with spans.stage("fleet.trace", program=name):
        traced = program.trace(*avatars)
    with spans.stage("fleet.lower", program=name):
        lowered = traced.lower()
    with (
        spans.stage("fleet.compile", program=name) as attrs,
        compiles.cache_outcome(attrs),
    ):
        compiled = lowered.compile()
    return compiled, attrs["cache"]


_STATE_CACHE: dict = {}


def fleet_state(spec: FleetSpec, n_machines: int, n_features: int, mesh=None):
    """The compiled program ``keys (M, key_width) → the M machines' training
    states``, cached: what a :func:`sequential_fits` program is handed as its
    fifth argument. Drawn in a program of its own so that the parameters and
    the optimizer's moments are buffers the device's allocator counts and the
    train program trains in place (donated), not temporaries inside it."""

    def build():
        draw = _over_machines(machine_state(spec, n_features))
        draw.__name__ = "machine_states"  # the XLA module: not the train program's
        keys = jax.ShapeDtypeStruct((n_machines, prng_key_width()), jnp.uint32)
        if mesh is None:
            program = jax.jit(draw)
        else:
            shard = fleet_sharding(mesh)
            program = jax.jit(draw, in_shardings=(shard,), out_shardings=shard)
        return _compile_in_stages(program, (keys,), "state")[0]

    key = (spec, n_machines, n_features, mesh)
    return _cached(_STATE_CACHE, _PROGRAM_CACHE_MAX, key, build)


def abstract_state(spec: FleetSpec, n_machines: int, n_features: int):
    """Shapes of :func:`fleet_state`'s result for ``n_machines``."""
    return jax.eval_shape(
        _over_machines(machine_state(spec, n_features)),
        jax.ShapeDtypeStruct((n_machines, prng_key_width()), jnp.uint32),
    )


_EXEC_CACHE: dict = {}
_EXEC_CACHE_MAX = 64


def prng_key_width() -> int:
    """Trailing uint32 width of a raw PRNG key under the active impl
    (threefry: 2, rbg: 4). AOT avatars must advertise the width
    ``jax.random.split`` actually produces, or the strict executable
    rejects every batch under a non-default ``jax_default_prng_impl``
    (ADVICE r2)."""
    return int(jax.eval_shape(jax.random.PRNGKey, 0).shape[-1])


def fleet_executable(
    spec: FleetSpec,
    n_machines: int,
    n_rows: int,
    n_features: int,
    n_targets: int,
    mesh=None,
):
    """AOT-compiled fleet executable + its input formats, cached by
    (spec, shape, mesh).

    Why AOT: ``compiled.input_formats`` exposes the exact device layouts
    (tiling) the executable expects, so callers can ``jax.device_put``
    ingest data straight into the right layout. Feeding plain host arrays
    or default-layout device arrays instead makes EVERY execution pay a
    device-side relayout — measured at ~200 ms for an 18 MB batch on v5e
    vs 0.7 ms program execution, i.e. the relayout would dominate the
    fleet hot loop ~300×.

    Returns ``(compiled, formats)``: the formats of the four batch arrays
    (a :func:`sequential_fits` program's fifth argument, the training state,
    is :func:`fleet_state`'s result as it comes).
    """
    def build():
        program = fleet_program(spec, n_rows, n_features, n_targets, mesh=mesh)
        avatars = (
            jax.ShapeDtypeStruct((n_machines, n_rows, n_features), jnp.float32),
            jax.ShapeDtypeStruct((n_machines, n_rows, n_targets), jnp.float32),
            jax.ShapeDtypeStruct((n_machines, n_rows), jnp.float32),
            jax.ShapeDtypeStruct((n_machines, prng_key_width()), jnp.uint32),
        )
        if sequential_fits(spec):
            avatars += (abstract_state(spec, n_machines, n_features),)
        compiled, cache = _compile_in_stages(program, avatars, "train")
        _M_FLEET_PROGRAMS.labels("aot", cache).inc()
        return compiled, compiled.input_formats[0]

    key = (spec, n_machines, n_rows, n_features, n_targets, mesh)
    return _cached(_EXEC_CACHE, _EXEC_CACHE_MAX, key, build)


def peek_fleet_executable(
    spec: FleetSpec,
    n_machines: int,
    n_rows: int,
    n_features: int,
    n_targets: int,
    mesh=None,
):
    """The cached ``(compiled, formats)`` for this shape, or ``None`` —
    NEVER compiles. For the ingest prefetcher: it places the next slice's
    batch layout-matched only when the program already exists, because a
    worker-side compile would race the unlocked program cache with the
    main thread and contend the (single) device compile slot."""
    key = (spec, n_machines, n_rows, n_features, n_targets, mesh)
    try:
        return _EXEC_CACHE.get(key)
    except TypeError:
        return None


def put_fleet_batch(batch: MachineBatch, formats=None) -> MachineBatch:
    """Device-place a batch, layout-matched when ``formats`` is given (see
    :func:`fleet_executable`). The returned batch's arrays are device
    arrays; transfers are issued immediately so a caller can overlap them
    with an in-flight execution before blocking."""
    keys = batch.keys
    if jax.dtypes.issubdtype(getattr(keys, "dtype", None), jax.dtypes.prng_key):
        keys = jax.random.key_data(keys)  # typed keys → raw uint32 pairs
    args = tuple(
        # host-side cast on mismatch: jnp.asarray would device-place in the
        # DEFAULT layout first, re-paying the relayout this path avoids
        a if getattr(a, "dtype", None) == d else np.asarray(a, d)
        for a, d in zip(
            (batch.X, batch.y, batch.w, keys),
            (jnp.float32, jnp.float32, jnp.float32, jnp.uint32),
        )
    )
    if formats is None:
        placed = [jax.device_put(a) for a in args]
    else:
        placed = [jax.device_put(a, f) for a, f in zip(args, formats)]
    return MachineBatch(*placed)


def compiled_flops(compiled) -> Optional[float]:
    """XLA-reported flops of a compiled executable, or ``None`` on backends
    without cost analysis."""
    try:
        return float(compiled.cost_analysis()["flops"])
    except Exception:  # lint: allow-swallow(XLA cost introspection is optional; None is the documented unknown result)
        return None


def fleet_flops_accounting(
    spec: FleetSpec,
    n_machines: int,
    n_rows: int,
    n_features: int,
    n_targets: int,
) -> Optional[dict]:
    """Trip-count-adjusted FLOP accounting for the fleet program.

    XLA's ``cost_analysis()`` counts a ``lax.scan`` body ONCE regardless of
    trip count, so the whole fleet program's reported flops undercount the
    training loop by roughly ``n_fits × epochs × steps_per_epoch``. This
    helper compiles the loop bodies standalone — the EXACT mini-batch train
    step (:func:`gordo_components_tpu.models.train.make_batch_step`, the
    same function ``make_fit_fn`` scans) and a batch-size-wide predict
    chunk — reads each one's XLA-reported flops, and multiplies by the
    Python-known trip counts from the program structure (no hand FLOP
    model anywhere). ``predict_chunks`` counts BATCH-SIZE-EQUIVALENT
    chunks of every padded sample in every fit, not literal loop
    iterations: the program may execute wider predict chunks
    (:func:`predict_width`; per-chunk flops are linear in width), and
    predicts only the samples its result reads (:func:`fold_predict_chunks`),
    which this count leaves as an over-count.

    The total is a slight UNDERcount still: scaler fits, fold masks,
    thresholds, and metrics (all O(rows×tags) elementwise, no matmuls) are
    excluded rather than risk double-counting the one copy the whole-program
    number already includes. Windowed models are probed on materialized
    ``(batch, L, F)`` windows — the production gather adds zero flops.

    Returns ``None`` when the backend exposes no cost analysis, else::

        {"train_step_flops": ..., "train_steps": ...,
         "predict_chunk_flops": ..., "predict_chunks": ..., "total_flops": ...}
    """
    from ..models.train import make_batch_step

    L, la = spec.lookback_window, spec.lookahead
    if la is None:
        n_samples = n_rows
        x_elem = (n_features,)
    else:
        n_samples = windowing.n_windows(n_rows, L, la, spec.rows_out)
        x_elem = (L, n_features)
    padded = pad_to_multiple(n_samples, spec.batch_size)
    steps_per_epoch = padded // spec.batch_size
    n_fits = spec.n_splits + 1
    train_steps = n_fits * spec.epochs * steps_per_epoch
    predict_chunks = n_fits * steps_per_epoch

    try:
        apply_fn = spec.module.apply
        sample = jnp.zeros((1, *x_elem), jnp.float32)
        params_sd = jax.eval_shape(
            lambda k: spec.module.init(k, sample, deterministic=True)[
                "params"
            ],
            jax.random.PRNGKey(0),
        )
        opt_sd = jax.eval_shape(spec.optimizer.init, params_sd)

        def stack(tree):
            return jax.tree_util.tree_map(
                lambda s: jax.ShapeDtypeStruct(
                    (n_machines, *s.shape), s.dtype
                ),
                tree,
            )

        x_sd = jax.ShapeDtypeStruct(
            (n_machines, spec.batch_size, *x_elem), jnp.float32
        )
        y_elem = (n_targets,) if spec.rows_out == 1 else (spec.rows_out, n_targets)
        y_sd = jax.ShapeDtypeStruct(
            (n_machines, spec.batch_size, *y_elem), jnp.float32
        )
        w_sd = jax.ShapeDtypeStruct((n_machines, spec.batch_size), jnp.float32)
        k_sd = jax.ShapeDtypeStruct((n_machines, prng_key_width()), jnp.uint32)

        step = make_batch_step(
            apply_fn, spec.optimizer, loss=spec.loss,
            use_dropout=spec.use_dropout,
        )

        def machine_step(params, opt_state, x, y, w, key):
            (params, opt_state), _ = step((params, opt_state), (x, y, w, key))
            return params, opt_state

        train_compiled = (
            jax.jit(jax.vmap(machine_step))
            .lower(stack(params_sd), stack(opt_sd), x_sd, y_sd, w_sd, k_sd)
            .compile()
        )
        train_step_flops = compiled_flops(train_compiled)

        def machine_predict(params, x):
            return apply_fn({"params": params}, x, deterministic=True)

        predict_compiled = (
            jax.jit(jax.vmap(machine_predict))
            .lower(stack(params_sd), x_sd)
            .compile()
        )
        predict_chunk_flops = compiled_flops(predict_compiled)
    except Exception:
        # accounting is a measurement aid and must never fail its caller —
        # but a silent None here would be indistinguishable from "backend
        # has no cost analysis", hiding real probe bugs. Log loudly instead.
        logger.warning(
            "fleet_flops_accounting probe failed; MFU will be unreported",
            exc_info=True,
        )
        return None
    if train_step_flops is None or predict_chunk_flops is None:
        return None  # backend without cost analysis (the graceful case)
    return {
        "train_step_flops": train_step_flops,
        "train_steps": train_steps,
        "predict_chunk_flops": predict_chunk_flops,
        "predict_chunks": predict_chunks,
        "total_flops": (
            train_step_flops * train_steps
            + predict_chunk_flops * predict_chunks
        ),
    }


def train_fleet_arrays(
    spec: FleetSpec,
    batch: MachineBatch,
    mesh=None,
) -> MachineResult:
    """Train a stacked bucket of machines; returns stacked results.

    With ``mesh``, the machine axis is sharded over it (machine count must
    be a multiple of the mesh size — pad with zero-weight machines) and XLA
    partitions the whole program; without, the vmapped program runs on the
    default device.

    Host arrays are device-placed layout-matched via the AOT executable
    (:func:`fleet_executable`); keys uint32 dtype aside, any float inputs
    are accepted as-is.

    The three things it does are three stages (``observability.spans``):
    ``fleet.program`` (executable memo hit, or the programs' trace, lower
    and compile, each a stage of its own: :func:`_compile_in_stages`),
    ``fleet.ingest`` (the layout-matched ``device_put``; a no-op for
    arrays the prefetch worker already placed) and ``fleet.execute``
    (dispatch until the result is ready on the device, so the call
    returns a finished result).
    """
    n_machines, n_rows, n_features = batch.X.shape
    n_targets = batch.y.shape[2]
    if mesh is not None and n_machines % mesh.size != 0:
        raise ValueError(
            f"Machine count {n_machines} must divide evenly over mesh size "
            f"{mesh.size}; pad with zero-weight machines "
            "(build_fleet does this automatically)"
        )
    shape = (n_machines, n_rows, n_features, n_targets)
    with spans.stage("fleet.program") as found:
        found["memo_hit"] = (
            peek_fleet_executable(spec, *shape, mesh=mesh) is not None
        )
        if sequential_fits(spec):  # the small program first: what follows
            # the train program's load is then the slice's own work
            draw_state = fleet_state(spec, n_machines, n_features, mesh=mesh)
        compiled, formats = fleet_executable(spec, *shape, mesh=mesh)
    with spans.stage("fleet.ingest", step="device_put"):
        placed = put_fleet_batch(batch, formats)
    with spans.stage("fleet.execute"):
        if sequential_fits(spec):
            # the machines' training state, drawn into buffers of its own
            # and trained in place; the optimizer's part is dropped here
            state = draw_state(placed.keys)
            result, _ = compiled(placed.X, placed.y, placed.w, placed.keys, state)
        else:
            result = compiled(placed.X, placed.y, placed.w, placed.keys)
        # the span ends where the device does. Every output is the one
        # program's and they become ready together, and every caller
        # reads them next, so the wait serialises nothing
        jax.block_until_ready(result)
    return result
