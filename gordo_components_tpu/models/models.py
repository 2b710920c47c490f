"""Estimator wrappers: the reference's Keras estimator API over Flax/optax.

Reference parity: ``gordo_components/model/models.py`` [UNVERIFIED] —
``KerasBaseEstimator`` (kind-dispatched factory, sklearn API, picklable
state), ``KerasAutoEncoder`` (X→X), ``KerasLSTMAutoEncoder`` (window →
window's last row), ``KerasLSTMForecast`` (window → next row). The windowing
off-by-one contract lives in :mod:`gordo_components_tpu.ops.windowing` and is
pinned by golden tests.

TPU notes: ``fit`` compiles one XLA program per (padded-rows, features)
shape; ``predict`` pads row counts up to a shape bucket so a serving process
compiles a handful of programs total instead of one per request size.
"""

from __future__ import annotations

import json
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import windowing
from ..utils.cache import cached
from .base import GordoBase
from .metrics import explained_variance_score
from .register import get_factory
from .train import make_fit_fn, make_predict_fn, pad_to_batches

# value-keyed memo of jitted fit/predict programs: sklearn-style CV clones
# the estimator per fold, and a fresh ``jax.jit`` wrapper per clone would
# re-trace + re-compile an identical program k+1 times per machine (VERDICT
# r2 #5). Keyed on the estimator's full config + feature widths — the same
# scheme as parallel.fleet's program cache — so clones, refits, and
# unpickled copies all share one compiled program per shape.
_PROGRAM_CACHE: dict = {}
_PROGRAM_CACHE_MAX = 64


def _as_float32(X) -> np.ndarray:
    values = getattr(X, "values", X)
    arr = np.asarray(values, dtype=np.float32)
    if arr.ndim == 1:
        arr = arr[:, None]  # sklearn-style 1-D target → single-output column
    return arr


def _round_up_bucket(n: int, minimum: int = 256) -> int:
    """Next power-of-two-ish bucket ≥ n, floored at ``minimum`` — bounds the
    number of distinct predict compilations a long-lived server sees."""
    bucket = minimum
    while bucket < n:
        bucket *= 2
    return bucket


class BaseFlaxEstimator(GordoBase):
    """Common fit/predict machinery; subclasses define the windowing contract
    via ``lookahead`` (None = flat 2-D input, 0 = reconstruction, 1 = one-step
    forecast)."""

    lookahead: Optional[int] = None  # class-level contract
    # the smallest row bucket ``predict`` pads its samples up to
    predict_bucket_min = 256

    def __init__(self, kind: str, **kwargs: Any):
        self.kind = kind
        self.batch_size = int(kwargs.pop("batch_size", 32))
        self.epochs = int(kwargs.pop("epochs", 1))
        self.seed = int(kwargs.pop("seed", 0))
        self.factory_kwargs = kwargs
        # fitted state
        self.params_: Any = None
        self._spec = None
        self._predict_jit = None
        self.history_: list = []
        self.n_features_: Optional[int] = None
        self.n_features_out_: Optional[int] = None
        self.fit_duration_: Optional[float] = None

    # -- windowing contract hooks ------------------------------------------
    @property
    def lookback_window(self) -> int:
        if self.lookahead is None:
            return 1
        return int(self.factory_kwargs.get("lookback_window", 1))

    @property
    def rows_out(self) -> int:
        """Rows a sample is judged against; samples lie that many rows apart
        (``ops.windowing``). 1: every window, judged against one row."""
        return 1

    def _prepare_inputs(self, X: np.ndarray) -> np.ndarray:
        if self.lookahead is None:
            return X
        return np.asarray(
            windowing.sliding_windows(
                X, self.lookback_window, self.lookahead, self.rows_out
            )
        )

    def _prepare_targets(self, y: np.ndarray) -> np.ndarray:
        """Per sample: ``(samples, F)``, or ``(samples, rows_out, F)``."""
        if self.lookahead is None:
            return y
        return np.asarray(
            windowing.sample_targets(
                y, self.lookback_window, self.lookahead, self.rows_out
            )
        )

    # -- compiled-program identity -----------------------------------------
    def _program_key(self) -> tuple:
        """Value key for the shared program cache: everything that shapes
        the traced computation (config + feature widths). Two estimators
        with equal keys build structurally identical flax modules and optax
        transforms, so they can share one jitted program."""
        return (
            type(self).__name__,
            self.kind,
            json.dumps(self.factory_kwargs, sort_keys=True, default=repr),
            self.batch_size,
            self.epochs,
            self.lookahead,
            self.n_features_,
            self.n_features_out_,
        )

    # -- spec / module construction ----------------------------------------
    def _make_spec(self, n_features: int, n_features_out: int):
        factory = get_factory(self.kind)
        spec = factory(
            n_features=n_features,
            n_features_out=n_features_out,
            **self.factory_kwargs,
        )
        expected = "flat" if self.lookahead is None else "window"
        if spec.input_kind != expected:
            raise ValueError(
                f"Model kind {self.kind!r} produces {spec.input_kind!r} inputs "
                f"but {type(self).__name__} requires {expected!r} "
                f"(e.g. use an lstm_* kind with LSTM estimators)"
            )
        return spec

    def _sample_input(self, n_features: int) -> jnp.ndarray:
        if self.lookahead is None:
            return jnp.zeros((1, n_features), jnp.float32)
        return jnp.zeros((1, self.lookback_window, n_features), jnp.float32)

    # -- sklearn API --------------------------------------------------------
    def fit(self, X, y=None, **_kwargs) -> "BaseFlaxEstimator":
        started = time.perf_counter()
        X = _as_float32(X)
        y_arr = X if y is None else _as_float32(y)
        if X.ndim != 2:
            raise ValueError(f"Expected 2-D (rows, features) input, got {X.shape}")
        if len(y_arr) != len(X):
            raise ValueError(
                f"X and y row counts differ: {len(X)} vs {len(y_arr)}"
            )
        targets = self._prepare_targets(y_arr)
        self.n_features_ = int(X.shape[1])
        self.n_features_out_ = int(y_arr.shape[1])

        self._spec = self._make_spec(self.n_features_, self.n_features_out_)
        key = jax.random.PRNGKey(self.seed)
        init_key, fit_key = jax.random.split(key)
        variables = self._spec.module.init(
            init_key, self._sample_input(self.n_features_), deterministic=True
        )
        params = variables["params"]

        dropout_rate = float(self._spec.config.get("dropout", 0.0) or 0.0)
        fit_kwargs = dict(
            loss=self._spec.loss,
            batch_size=self.batch_size,
            epochs=self.epochs,
            use_dropout=dropout_rate > 0.0,
        )
        spec = self._spec
        if self.lookahead is None:
            fit_fn = cached(
                _PROGRAM_CACHE,
                _PROGRAM_CACHE_MAX,
                ("fit",) + self._program_key(),
                lambda: jax.jit(
                    make_fit_fn(spec.module.apply, spec.optimizer, **fit_kwargs)
                ),
            )
            Xp, yp, w = pad_to_batches(X, targets, self.batch_size)
            result = fit_fn(
                params, jnp.asarray(Xp), jnp.asarray(yp), jnp.asarray(w), fit_key
            )
        else:
            # windowed models train on start INDICES: each batch gathers its
            # (batch, L, F) windows from the row matrix inside the compiled
            # loop, so the device holds (n, F) rows — not the L×-blown-up
            # window tensor — and the per-epoch shuffle permutes indices,
            # not windows (same scheme as the fleet program; numerically
            # identical to materialized windows)
            L, la = self.lookback_window, self.lookahead
            n_samples = windowing.n_windows(len(X), L, la, self.rows_out)
            if n_samples <= 0:
                raise ValueError(
                    f"Need at least lookback_window+lookahead={L + la} rows "
                    f"to fit, got {len(X)}"
                )
            apply = spec.module.apply
            optimizer = spec.optimizer

            def fit_windowed(p, rows, starts, y_t, w_t, k):
                def windowed_apply(variables, sb, **kw):
                    return apply(
                        variables, windowing.gather_windows(rows, sb, L), **kw
                    )

                return make_fit_fn(windowed_apply, optimizer, **fit_kwargs)(
                    p, starts, y_t, w_t, k
                )

            fit_fn = cached(
                _PROGRAM_CACHE,
                _PROGRAM_CACHE_MAX,
                ("fit",) + self._program_key(),
                lambda: jax.jit(fit_windowed),
            )
            starts, yp, w = pad_to_batches(
                windowing.window_starts(len(X), L, la, self.rows_out),
                targets, self.batch_size,
            )
            result = fit_fn(
                params,
                jnp.asarray(X),
                jnp.asarray(starts),
                jnp.asarray(yp),
                jnp.asarray(w),
                fit_key,
            )
        self.params_ = result.params
        self.history_ = [float(v) for v in jax.device_get(result.loss_history)]
        self._predict_jit = self._build_predict_jit()
        self.fit_duration_ = time.perf_counter() - started
        return self

    def _build_predict_jit(self):
        """Shared (cached) jitted predict program — clones and unpickled
        copies with equal configs reuse one trace cache, so a served fleet
        of same-architecture machines compiles each request shape once."""
        spec = self._spec
        return cached(
            _PROGRAM_CACHE,
            _PROGRAM_CACHE_MAX,
            ("predict",) + self._program_key(),
            lambda: jax.jit(make_predict_fn(spec.module.apply)),
        )

    def _check_fitted(self):
        if self.params_ is None:
            raise ValueError(
                f"{type(self).__name__} is not fitted; call fit() first"
            )

    def predict(self, X) -> np.ndarray:
        """Predictions aligned per the windowing contract: flat models return
        one row per input row; windowed models return
        ``n - lookback_window + 1 - lookahead`` rows, or the ``rows_out``
        rows of each sample, one sample after another (see
        :func:`~gordo_components_tpu.ops.windowing.window_output_index`)."""
        self._check_fitted()
        X = _as_float32(X)
        inputs = self._prepare_inputs(X)
        n = inputs.shape[0]
        bucket = _round_up_bucket(n, self.predict_bucket_min)
        if bucket != n:
            pad = np.zeros((bucket - n, *inputs.shape[1:]), inputs.dtype)
            inputs = np.concatenate([inputs, pad])
        if not isinstance(jax.tree_util.tree_leaves(self.params_)[0], jax.Array):
            # a loaded or just-built model keeps its parameters where they
            # came from (the host) until it first predicts
            self.params_ = jax.device_put(self.params_)
        out = np.asarray(
            jax.device_get(self._predict_jit(self.params_, jnp.asarray(inputs)))
        )[:n]
        return out.reshape(-1, out.shape[-1])

    def score(self, X, y=None) -> float:
        """Explained variance of predictions vs the contract-aligned targets
        (reference: ``KerasAutoEncoder.score`` / ``KerasLSTMForecast.score``)."""
        self._check_fitted()
        X = _as_float32(X)
        y_arr = X if y is None else _as_float32(y)
        targets = self._prepare_targets(y_arr)
        return explained_variance_score(
            targets.reshape(-1, targets.shape[-1]), self.predict(X)
        )

    # -- introspection / persistence ----------------------------------------
    def get_params(self, deep: bool = True) -> Dict[str, Any]:
        return {
            "kind": self.kind,
            "batch_size": self.batch_size,
            "epochs": self.epochs,
            "seed": self.seed,
            **self.factory_kwargs,
        }

    def set_params(self, **params) -> "BaseFlaxEstimator":
        """sklearn contract: unknown keys are factory hyperparameters, routed
        into ``factory_kwargs`` so the next ``fit`` actually uses them."""
        for key in ("kind", "batch_size", "epochs", "seed"):
            if key in params:
                setattr(self, key, params.pop(key))
        self.factory_kwargs.update(params)
        return self

    # -- pickling: drop compiled closures, keep pure state -------------------
    def __getstate__(self) -> Dict[str, Any]:
        state = self.__dict__.copy()
        state["_spec"] = None
        state["_predict_jit"] = None
        if self.params_ is not None:
            state["params_"] = jax.device_get(self.params_)
        return state

    def __setstate__(self, state: Dict[str, Any]) -> None:
        self.__dict__.update(state)
        if self.params_ is not None:
            self._spec = self._make_spec(self.n_features_, self.n_features_out_)
            self.params_ = jax.tree_util.tree_map(jnp.asarray, self.params_)
            self._predict_jit = self._build_predict_jit()

    def get_metadata(self) -> Dict[str, Any]:
        meta: Dict[str, Any] = {
            "type": type(self).__name__,
            "kind": self.kind,
            "batch_size": self.batch_size,
            "epochs": self.epochs,
            "parameters": dict(self.factory_kwargs),
        }
        if self.params_ is not None:
            meta.update(
                {
                    "history": {"loss": self.history_},
                    "architecture": self._spec.config,
                    "fit_duration_s": self.fit_duration_,
                    "num_parameters": int(
                        sum(p.size for p in jax.tree_util.tree_leaves(self.params_))
                    ),
                }
            )
        return meta

    def get_state(self) -> Dict[str, Any]:
        self._check_fitted()
        return {
            "params": jax.device_get(self.params_),
            "n_features": self.n_features_,
            "n_features_out": self.n_features_out_,
            "history": self.history_,
            "fit_duration": self.fit_duration_,
        }

    def set_state(self, state: Dict[str, Any]) -> "BaseFlaxEstimator":
        self.n_features_ = int(state["n_features"])
        self.n_features_out_ = int(state["n_features_out"])
        self.history_ = list(state.get("history", []))
        self.fit_duration_ = state.get("fit_duration")
        self._spec = self._make_spec(self.n_features_, self.n_features_out_)
        # kept where they are (a fleet build's commit and a load hand over
        # host arrays): ``predict`` places them on the device when it first
        # needs them, so a model of gigabytes is committed without a round
        # trip through the device
        self.params_ = state["params"]
        self._predict_jit = self._build_predict_jit()
        return self


class DenseAutoEncoder(BaseFlaxEstimator):
    """X→X reconstruction with a feedforward kind
    (reference: ``KerasAutoEncoder``)."""

    lookahead = None

    def __init__(self, kind: str = "feedforward_hourglass", **kwargs: Any):
        super().__init__(kind, **kwargs)


class LSTMAutoEncoder(BaseFlaxEstimator):
    """Window → window's own last row (reference: ``KerasLSTMAutoEncoder``).
    ``predict`` row ``j`` corresponds to input row ``j + lookback_window - 1``."""

    lookahead = 0

    def __init__(self, kind: str = "lstm_hourglass", **kwargs: Any):
        super().__init__(kind, **kwargs)


class LSTMForecast(BaseFlaxEstimator):
    """Window → the ``horizon``-th-ahead row (reference:
    ``KerasLSTMForecast`` is the ``horizon=1`` case; ``horizon=k`` is the
    direct multi-step forecast of BASELINE.json ``configs`` entry 3). ``predict`` row
    ``j`` corresponds to input row ``j + lookback_window - 1 + horizon``."""

    lookahead = 1

    def __init__(
        self, kind: str = "lstm_symmetric", horizon: int = 1, **kwargs: Any
    ):
        super().__init__(kind, **kwargs)
        if int(horizon) < 1:
            raise ValueError(f"horizon must be >= 1, got {horizon}")
        self.horizon = int(horizon)
        self.lookahead = self.horizon  # instance overrides the class contract

    def get_params(self, deep: bool = True) -> Dict[str, Any]:
        return {**super().get_params(deep), "horizon": self.horizon}

    def set_params(self, **params) -> "LSTMForecast":
        if "horizon" in params:
            horizon = int(params.pop("horizon"))
            if horizon < 1:  # same contract as __init__ — horizon=0 would
                # silently flip the estimator into reconstruction mode
                raise ValueError(f"horizon must be >= 1, got {horizon}")
            self.horizon = horizon
            self.lookahead = horizon
        return super().set_params(**params)


class MultiStepForecast(LSTMForecast):
    """JOINT multi-step forecast: window → ALL of rows ``t+1..t+horizon``
    predicted together (the other reading of BASELINE config 3's
    "multi-step horizon"; :class:`LSTMForecast` with ``horizon=k`` is the
    direct k-th-ahead variant). The model head emits ``horizon ×
    n_features`` values per window, trained against
    :func:`~gordo_components_tpu.ops.windowing.multi_step_targets`
    flattened to 2-D, so any zoo kind works unchanged. ``predict`` returns
    the flat ``(count, horizon·F)`` sklearn shape; :meth:`predict_steps`
    reshapes to ``(count, horizon, F)``.

    Standalone estimator (sklearn API): the diff-based anomaly head scores
    one row per timestamp, so it pairs with the direct-horizon forecasters,
    not this joint one — the fleet builder and serving engine reject it
    with a clear error instead of mis-scoring.
    """

    joint_horizon = True  # gates: fleet/_spec_for and the serving engine
    # reject this class with a clear error instead of mis-scoring

    def __init__(
        self, kind: str = "lstm_symmetric", horizon: int = 2, **kwargs: Any
    ):
        super().__init__(kind, horizon=horizon, **kwargs)

    def _prepare_targets(self, y: np.ndarray) -> np.ndarray:
        stacked = np.asarray(
            windowing.multi_step_targets(y, self.lookback_window, self.horizon)
        )  # (count, horizon, F)
        return stacked.reshape(stacked.shape[0], -1)

    def _make_spec(self, n_features: int, n_features_out: int):
        # widen the head: joint horizon = horizon × target width outputs
        return super()._make_spec(n_features, n_features_out * self.horizon)

    def predict_steps(self, X) -> np.ndarray:
        """``(count, horizon, F)`` view of :meth:`predict` — step ``s`` of
        row ``j`` forecasts input row ``j + lookback_window + s``."""
        flat = self.predict(X)
        return flat.reshape(flat.shape[0], self.horizon, -1)


class PatchTSTAutoEncoder(LSTMAutoEncoder):
    """Window → window's own last row via the PatchTST transformer kind —
    the rebuild's new model family (BASELINE.json ``configs`` entry 5); same windowing
    contract as :class:`LSTMAutoEncoder`."""

    def __init__(self, kind: str = "patchtst", **kwargs: Any):
        # the estimator's windowing must match the factory's default, or an
        # unspecified lookback_window would window rows of length 1
        kwargs.setdefault("lookback_window", 32)
        super().__init__(kind, **kwargs)


class PatchTSTForecast(LSTMForecast):
    """Window → next row via the PatchTST transformer kind."""

    def __init__(self, kind: str = "patchtst", **kwargs: Any):
        kwargs.setdefault("lookback_window", 32)
        super().__init__(kind, **kwargs)


class MoEMLAForecast(LSTMForecast):
    """Window → the ``lookback_window`` rows that FOLLOW each of its rows: a
    decoder over sensor values read as tokens (a kind on the decoder
    scaffold; ``moe_mla_decoder`` by default), every tag a sequence of its own. A sample reads rows ``i ..
    i+L-1`` and predicts rows ``i+1 .. i+L``; samples lie ``L`` rows apart,
    laid from the end, so every row from the first sample's second on is
    predicted once and ``predict`` returns them in order (tail-aligned, as
    every windowed estimator's). Trains on the module's own loss."""

    predict_bucket_min = 1  # a sample is a whole window of tokens a tag

    def __init__(self, kind: str = "moe_mla_decoder", **kwargs: Any):
        kwargs.setdefault("lookback_window", 64)
        kwargs.pop("horizon", None)  # the next rows, always
        super().__init__(kind, horizon=1, **kwargs)

    @property
    def rows_out(self) -> int:
        return self.lookback_window

    def get_params(self, deep: bool = True) -> Dict[str, Any]:
        params = super().get_params(deep)
        params.pop("horizon")
        return params


class MoEGQAForecast(MoEMLAForecast):
    """:class:`MoEMLAForecast`'s windowing, loss and ``predict`` over the
    ``moe_gqa_decoder`` kind (grouped-query attention, window and full
    layers mixed, softmax scores): the estimator is the scaffold's, the
    name says which block a machine config asks for."""

    def __init__(self, kind: str = "moe_gqa_decoder", **kwargs: Any):
        super().__init__(kind, **kwargs)


class AfMoEForecast(MoEMLAForecast):
    """The same estimator over the ``afmoe_decoder`` kind (gated, query/key
    normed grouped-query attention, rotary in the window layers alone,
    sandwich norms, leading dense layers, sigmoid scores beside a shared
    expert)."""

    def __init__(self, kind: str = "afmoe_decoder", **kwargs: Any):
        super().__init__(kind, **kwargs)


# Aliases so ported reference configs resolve (the serializer rewrites
# `gordo_components.model.models.X` → this module).
KerasAutoEncoder = DenseAutoEncoder
KerasLSTMAutoEncoder = LSTMAutoEncoder
KerasLSTMForecast = LSTMForecast
