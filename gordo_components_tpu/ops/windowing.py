"""Static-shape sliding-window primitives.

The reference windows time-series host-side with Keras' TimeseriesGenerator
(``gordo_components/model/models.py::create_keras_timeseriesgenerator``
[UNVERIFIED — empty reference mount, path-level citation]). Here windowing is
a pure, jittable gather so XLA fuses it with the model's first matmul and the
data never round-trips through host Python.

THE OFF-BY-ONE CONTRACT (pinned by tests/test_ops.py — SURVEY.md §4.5
calls this "subtle and MUST be pinned"):

Given ``x`` with ``n`` rows and ``lookback_window = L``:

- ``sliding_windows(x, L)`` → shape ``(n - L + 1, L, F)``; window ``i`` is
  rows ``[i, i+L)``.
- **Reconstruction** (LSTM autoencoder): window ``i`` targets its own last
  row ``x[i+L-1]``. Usable samples: ``n - L + 1``. Prediction row ``j``
  corresponds to input timestamp index ``j + L - 1``.
- **Forecast** (``lookahead = k >= 1``, the direct multi-step horizon —
  BASELINE.json ``configs`` entry 3): window ``i`` targets the ``k``-th-ahead row
  ``x[i+L-1+k]``. Usable samples: ``n - L + 1 - k``. Prediction row ``j``
  corresponds to input timestamp index ``j + L - 1 + k``. ``k = 1`` is the
  classic next-row forecast.
- **Joint multi-step** (:func:`multi_step_targets`): window ``i`` targets
  ALL of rows ``[i+L, i+L+k)`` — the ``(count, k, F)`` stacked variant for
  models that predict the whole horizon jointly.

- **Several rows a sample** (``rows_out = R > 1``, with ``lookahead = k``):
  a sample still reads ``L`` rows, and is judged against the ``R``
  consecutive rows that END at row ``first + L - 1 + k``. Samples lie ``R``
  rows apart, so that no row is predicted twice and none between two samples
  is left out, and they are laid FROM THE END (the real rows of a padded
  machine sit there): the last sample's last target is the last row, and the
  ``(n - L - k) mod R`` rows in front that no whole step reaches belong to no
  sample. Usable samples: ``(n - L - k) // R + 1``. ``R = 1`` is every case
  above. A next-rows decoder is ``k = 1, R = L``: rows ``i .. i+L-1`` in, rows
  ``i+1 .. i+L`` out.

``window_output_index`` maps prediction rows back to input-row indices so
the server/anomaly layers can attach the correct timestamps.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def n_windows(
    n_rows: int, lookback_window: int, lookahead: int = 0, rows_out: int = 1
) -> int:
    """Number of usable windows for ``n_rows`` of input.

    ``lookahead=0`` → reconstruction (target = last row of window);
    ``lookahead=k >= 1`` → direct ``k``-step forecast (target = the
    ``k``-th row after the window's last). ``rows_out=R`` → the samples lie
    ``R`` rows apart (each judged against ``R`` rows).
    """
    if lookback_window < 1:
        raise ValueError(f"lookback_window must be >= 1, got {lookback_window}")
    if not isinstance(lookahead, (int, np.integer)) or lookahead < 0:
        raise ValueError(f"lookahead must be an int >= 0, got {lookahead}")
    if rows_out < 1 or rows_out > lookback_window + lookahead:
        raise ValueError(
            f"rows_out must lie in 1..lookback_window+lookahead "
            f"({lookback_window + lookahead}: a sample's first target is not "
            f"in front of its first row), got {rows_out}"
        )
    reach = n_rows - lookback_window - lookahead
    return 0 if reach < 0 else reach // rows_out + 1


def window_starts(
    n_rows: int, lookback_window: int, lookahead: int = 0, rows_out: int = 1
) -> np.ndarray:
    """First row of each sample's window: ``rows_out`` apart, laid from the
    end (``arange(count)`` where ``rows_out`` is 1)."""
    count = n_windows(n_rows, lookback_window, lookahead, rows_out)
    lead = max(n_rows - lookback_window - lookahead, 0) % rows_out
    return lead + rows_out * np.arange(count)


def sliding_windows(
    x: jnp.ndarray, lookback_window: int, lookahead: int = 0, rows_out: int = 1
) -> jnp.ndarray:
    """``(n, F) → (count, L, F)`` sliding windows as a static gather
    (``count = n - L + 1 - lookahead`` where ``rows_out`` is 1).

    ``lookahead`` trims trailing windows so the result zips exactly with the
    matching target fn — ``lookahead=0`` ⇄ :func:`reconstruction_targets`,
    ``lookahead=1`` ⇄ :func:`forecast_targets` — keeping the off-by-one
    contract in one place instead of at every call site.

    Jittable; the index matrix is a compile-time constant so XLA lowers this
    to a single gather that fuses into downstream ops.
    """
    n = x.shape[0]
    count = n_windows(n, lookback_window, lookahead, rows_out)
    if count <= 0:
        raise ValueError(
            f"Need at least lookback_window+lookahead={lookback_window + lookahead} "
            f"rows, got {n}"
        )
    starts = window_starts(n, lookback_window, lookahead, rows_out)
    idx = starts[:, None] + np.arange(lookback_window)[None, :]
    return x[idx]


def gather_windows(
    rows: jnp.ndarray, starts: jnp.ndarray, lookback_window: int
) -> jnp.ndarray:
    """``(n, F)`` rows + ``(k,)`` window-start indices → ``(k, L, F)``.

    The lazy twin of :func:`sliding_windows`: training loops batch over
    start indices and gather each batch's windows on the fly, so device
    memory holds the row matrix instead of the L×-blown-up window tensor.
    Window ``i`` is rows ``[starts[i], starts[i]+L)`` — the SAME index
    arithmetic as :func:`sliding_windows`, kept here so the off-by-one
    contract stays in this module.

    Lowered as ONE ``lax.gather`` of ``k`` contiguous ``(L, F)`` slices
    instead of advanced indexing (an XLA gather addressed by ``k x L``
    scalar row starts with slice_sizes ``(1, F)``): on TPU the
    element-addressed form serializes on the scalar core and is the
    lead suspect for the r4 windowed fleets' ~1000x-below-roofline step
    times; the big-slice form is the fast path.
    ``tools/tpu_probe_gathers.py`` A/Bs both on hardware. Compile cost
    is a wash — 13.5 s (this form) vs 13.2 s (indexed) for the full
    LSTM fleet program on XLA:CPU, measured r5.

    Out-of-bounds semantics differ from advanced indexing IN A WAY THAT
    NEVER FIRES: ``mode="clip"`` clamps the window START to ``n - L``
    (one shifted whole window, like ``dynamic_slice``), while advanced
    indexing clamps each row index individually (a window whose tail
    repeats row ``n-1``). Every start the training loop can produce is
    in ``[0, n - L]`` — batches index real windows and padding windows
    carry start 0 — so the two forms are bit-identical in use; do not
    rely on either clamping behavior for a hypothetical OOB start."""
    n_features = rows.shape[1]
    dnums = jax.lax.GatherDimensionNumbers(
        offset_dims=(1, 2),
        collapsed_slice_dims=(),
        start_index_map=(0,),
    )
    with jax.named_scope("gather_windows"):
        return jax.lax.gather(
            rows,
            starts[:, None],
            dnums,
            slice_sizes=(lookback_window, n_features),
            mode="clip",
        )


def reconstruction_targets(x: jnp.ndarray, lookback_window: int) -> jnp.ndarray:
    """Targets for the LSTM-autoencoder contract: row ``i+L-1`` per window."""
    return x[lookback_window - 1 :]


def forecast_targets(
    x: jnp.ndarray, lookback_window: int, lookahead: int = 1
) -> jnp.ndarray:
    """Targets for the direct ``k``-step forecast contract: row
    ``i + L - 1 + lookahead`` per window (``lookahead=1`` → the classic
    next-row forecast)."""
    if lookahead < 1:
        raise ValueError(
            f"forecast lookahead must be >= 1, got {lookahead} "
            "(use reconstruction_targets for lookahead=0)"
        )
    return x[lookback_window - 1 + lookahead :]


def multi_step_targets(
    x: jnp.ndarray, lookback_window: int, horizon: int
) -> jnp.ndarray:
    """Joint-horizon targets: ``(n, F) → (count, horizon, F)`` where window
    ``i`` targets ALL of rows ``[i+L, i+L+horizon)`` and ``count =
    n_windows(n, L, lookahead=horizon)`` — zips exactly with
    ``sliding_windows(x, L, lookahead=horizon)``. The same static-gather
    construction as :func:`sliding_windows`, so it fuses under jit."""
    if horizon < 1:
        raise ValueError(f"horizon must be >= 1, got {horizon}")
    n = x.shape[0]
    count = n_windows(n, lookback_window, horizon)
    if count <= 0:
        raise ValueError(
            f"Need at least lookback_window+horizon={lookback_window + horizon} "
            f"rows, got {n}"
        )
    idx = (
        np.arange(count)[:, None] + lookback_window + np.arange(horizon)[None, :]
    )
    return x[idx]


def sample_targets(
    x: jnp.ndarray, lookback_window: int, lookahead: int = 0, rows_out: int = 1
) -> jnp.ndarray:
    """Each sample's target rows: ``(count, F)`` where ``rows_out`` is 1 (the
    reconstruction / forecast slices above), else ``(count, rows_out, F)``."""
    if rows_out == 1:
        if lookahead == 0:
            return reconstruction_targets(x, lookback_window)
        return forecast_targets(x, lookback_window, lookahead)
    idx = window_output_index(x.shape[0], lookback_window, lookahead, rows_out)
    return x[idx.reshape(-1, rows_out)]


def window_output_index(
    n_rows: int, lookback_window: int, lookahead: int = 0, rows_out: int = 1
) -> np.ndarray:
    """Input-row index each prediction row corresponds to.

    Reconstruction: ``[L-1, …, n-1]``; forecast: ``[L, …, n-1]``; with
    ``rows_out`` over 1, sample by sample, its ``rows_out`` rows: consecutive
    rows that end at ``n-1``. Used to slice timestamps for server responses
    and anomaly frames.
    """
    starts = window_starts(n_rows, lookback_window, lookahead, rows_out)
    last = starts + lookback_window - 1 + lookahead
    return (last[:, None] - (rows_out - 1) + np.arange(rows_out)).reshape(-1)
