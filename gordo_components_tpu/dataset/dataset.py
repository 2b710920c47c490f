"""Time-series dataset assembly: provider series → aligned ``(X, y)``.

Reference parity: ``gordo_components/dataset/datasets.py`` [UNVERIFIED] —
``TimeSeriesDataset`` with per-tag resample/aggregate, inner join on the
timestamp index, optional pandas-query row filtering, and per-tag count
metadata. TPU twist: the joined frames are float32 (the builder re-packs them
contiguously at ``jax.device_put`` time), and the windowing that
the reference did host-side with Keras' TimeseriesGenerator is deferred to
on-device static-shape gathers (:mod:`gordo_components_tpu.ops.windowing`).
"""

from __future__ import annotations

import logging
from datetime import datetime
from typing import Any, Dict, Iterable, List, NamedTuple, Optional, Tuple, Union

import numpy as np
import pandas as pd

from ..observability.registry import REGISTRY
from .base import GordoBaseDataset
from .data_provider.base import GordoBaseDataProvider
from .data_provider.providers import RandomDataProvider
from .sensor_tag import SensorTag, normalize_sensor_tags

logger = logging.getLogger(__name__)


class InsufficientDataError(ValueError):
    """Raised when the assembled dataset has fewer rows than required."""


def _normalize_resolution(resolution: str) -> str:
    """Accept both legacy pandas offsets ("10T", "1H", "30S") and modern
    spellings ("10min", "1h", "30s") — ported gordo configs use the legacy
    uppercase forms, which pandas 3 rejects."""
    legacy = {"T": "min", "H": "h", "S": "s", "L": "ms", "U": "us"}
    for suffix, modern in legacy.items():
        if resolution.endswith(suffix) and (
            resolution[:-1].isdigit() or resolution[:-1] == ""
        ):
            return resolution[:-1] + modern
    return resolution


def _parse_date(value: Union[str, datetime]) -> datetime:
    if isinstance(value, datetime):
        return value
    return pd.Timestamp(value).to_pydatetime()


def join_timeseries(
    series_iterable: Iterable[pd.Series],
    resampling_start: datetime,
    resampling_end: datetime,
    resolution: str,
    aggregation_methods: Union[str, List[str]] = "mean",
    interpolation_method: str = "linear_interpolation",
    interpolation_limit: Optional[str] = "8H",
) -> Tuple[pd.DataFrame, Dict[str, Any]]:
    """Resample each series onto a common grid and inner-join on timestamps.

    Returns the joined frame and per-tag metadata: original / resampled row
    counts and rows dropped by the join — the numbers the reference records
    into build metadata for data-quality debugging.

    Two paths, one result (docs/ARCHITECTURE.md §8): series the numpy path
    handles exactly (float64 values on a ``DatetimeIndex``, a fixed-length
    resolution, ``mean``/``min``/``max``) are binned, filled and joined in
    numpy; anything else goes through pandas' resampler as it always did.
    """
    joined, metadata, _ = _join_timeseries(
        series_iterable, resampling_start, resampling_end, resolution,
        aggregation_methods, interpolation_method, interpolation_limit,
    )
    return joined, metadata


_M_RESAMPLE = REGISTRY.counter(
    "gordo_build_resample_total",
    "join_timeseries calls by the path that resampled and joined them "
    "(numpy / pandas)",
    labels=("path",),
)

# aggregations the numpy path computes exactly as pandas' resampler does
_BINNED_AGGREGATIONS = frozenset({"mean", "min", "max"})
_NAT = np.iinfo(np.int64).min


class _Binned(NamedTuple):
    """One tag resampled in numpy: the grid's bins ``first .. first + n``
    (bin ``k`` starts at ``origin + k * step``), their filled values
    ``(n, columns)`` and the bins no column leaves NaN."""

    first: int
    values: np.ndarray
    kept: np.ndarray


class _Grid(NamedTuple):
    """The resampling grid in one index dtype's integers (``unit``; UTC for
    a tz-aware index, as pandas computes bin edges)."""

    origin: int
    step: int
    offset: Any
    dtype: Any
    unit: str
    tz: Any

    def labels(self, first: int, n: int, name: Any) -> pd.DatetimeIndex:
        """The index pandas' resampler gives bins ``first .. first + n``."""
        start = pd.Timestamp(self.origin + first * self.step, unit=self.unit)
        if self.tz is not None:
            start = start.tz_localize("UTC").tz_convert(self.tz)
        return pd.date_range(
            start=start, periods=n, freq=self.offset, unit=self.unit, name=name
        )

    def frame(self, binned: "_Binned", columns: List[str], name: Any) -> pd.DataFrame:
        """``binned`` as the frame pandas' path resamples the tag into."""
        index = self.labels(binned.first, len(binned.kept), name)
        kept = np.flatnonzero(binned.kept)
        return pd.DataFrame(
            binned.values[kept], index=index.take(kept), columns=columns
        )


def _grid_for(
    series: pd.Series,
    resampling_start: datetime,
    resolution: str,
    known: Optional[_Grid] = None,
) -> Optional[_Grid]:
    """The grid of ``series`` when the numpy path reproduces pandas' bins
    for it exactly, else None; ``known``, a grid of the same call, is
    taken as it is by an index of its dtype."""
    index = series.index
    if not isinstance(index, pd.DatetimeIndex) or series.dtype != np.float64:
        return None
    if known is not None and index.dtype == known.dtype:
        return known
    try:
        offset = pd.tseries.frequencies.to_offset(resolution)
    except ValueError:
        return None
    if not isinstance(offset, pd.offsets.Tick):
        return None
    origin = pd.Timestamp(resampling_start)
    if (origin.tz is None) != (index.tz is None):
        return None  # pandas refuses this pairing; let it say so
    unit = index.unit
    step = pd.Timedelta(offset)
    if step.as_unit(unit) != step or step <= pd.Timedelta(0):
        return None
    return _Grid(
        origin=int(origin.as_unit(unit).asm8.view(np.int64)),
        step=int(step.as_unit(unit).asm8.view(np.int64)),
        offset=offset,
        dtype=index.dtype,
        unit=unit,
        tz=index.tz,
    )


def _fill(column: np.ndarray, method: str, limit: Optional[int]) -> np.ndarray:
    """pandas' ``interpolate(method="linear", limit=...)`` / ``ffill(limit=
    ...)`` forward only: a NaN at most ``limit`` bins after a value is
    filled (on the line to the next value; past the last value, with it),
    the rest of a longer gap and the leading NaNs stay."""
    invalid = np.isnan(column)
    if method == "none" or not invalid.any():
        return column
    valid_at = np.flatnonzero(~invalid)
    if valid_at.size == 0:
        return column
    holes = np.flatnonzero(invalid)
    before = np.searchsorted(valid_at, holes) - 1
    previous = valid_at[np.maximum(before, 0)]
    fill = before >= 0
    if limit is not None:
        fill &= holes - previous <= limit
    if method == "linear_interpolation":
        # the same call pandas makes, on bin positions
        values = np.interp(holes, valid_at, column[valid_at])
    else:
        values = column[previous]
    out = column.copy()
    out[holes[fill]] = values[fill]
    return out


def _bin_series(
    series: pd.Series,
    grid: _Grid,
    aggregations: List[str],
    interpolation_method: str,
    interpolation_steps: Optional[int],
) -> Optional[_Binned]:
    """Resample one tag in numpy, or None where its data holds what this
    path does not reproduce exactly (NaT stamps, infinite values)."""
    stamps = series.index.asi8
    values = series.to_numpy()
    finite = np.isfinite(values)
    all_finite = bool(finite.all())
    if not all_finite and np.isinf(values).any():
        return None
    if stamps.size > 1 and not (stamps[1:] > stamps[:-1]).all():
        # duplicates dropped (the first kept), then sorted
        stamps, first_seen = np.unique(stamps, return_index=True)
        values, finite = values[first_seen], finite[first_seen]
    if stamps[0] == _NAT:  # NaT is the smallest stamp
        return None
    # one buffer of the stamps' size, divided and shifted in place: fresh
    # arrays this large cost more than the arithmetic when the fetch pool's
    # threads allocate side by side
    bins = stamps - grid.origin
    if bins[0] >= 0:
        # the same bins as the floor division below, at a tenth of its cost
        unsigned = bins.view(np.uint64)
        np.floor_divide(unsigned, np.uint64(grid.step), out=unsigned)
    else:
        # floor division: a point before the origin lands in a negative bin
        np.floor_divide(bins, grid.step, out=bins)
    first = int(bins[0])
    n = int(bins[-1]) - first + 1
    if not all_finite:
        bins, values = bins[finite], values[finite]
    at = bins
    at -= first
    counts = np.bincount(at, minlength=n)
    columns = np.full((len(aggregations), n), np.nan)
    for c, method in enumerate(aggregations):
        if method == "mean":
            sums = np.bincount(at, weights=values, minlength=n)
            np.divide(sums, counts, out=columns[c], where=counts > 0)
        elif at.size:
            starts = np.flatnonzero(np.r_[True, at[1:] != at[:-1]])
            reduce = np.minimum if method == "min" else np.maximum
            columns[c, at[starts]] = reduce.reduceat(values, starts)
        columns[c] = _fill(columns[c], interpolation_method, interpolation_steps)
    kept = ~np.isnan(columns).any(axis=0)
    return _Binned(first=first, values=columns.T, kept=kept)


def _kept_run(binned: _Binned) -> Optional[Tuple[int, int]]:
    """The bins ``[lo, hi)`` a tag keeps where they are one non-empty run,
    else None."""
    lo = int(binned.kept.argmax())
    hi = len(binned.kept) - int(binned.kept[::-1].argmax())
    if not binned.kept[lo] or not binned.kept[lo:hi].all():
        return None
    return binned.first + lo, binned.first + hi


def _join_binned(
    tags: List[Tuple[_Binned, List[str], Any]],
    grid: _Grid,
    resampling_start: datetime,
    resampling_end: datetime,
) -> Tuple[pd.DataFrame, int]:
    """The inner join of numpy-resampled tags and its ``[start, end)``
    slice: ``(frame, rows the slice dropped)``. The index goes through the
    same pandas index operations as the pandas path (take, intersection,
    take), so its values, tz and freq are pandas' own; only the values of
    the rows it keeps are gathered."""
    runs = [_kept_run(b) for b, _, _ in tags]
    if all(run is not None for run in runs):
        # each tag keeps one run of bins: the intersection is the run they
        # share, on the grid's freq, under the name they share (None where
        # they differ), as pandas' range intersection gives it
        lo = max(run[0] for run in runs)
        hi = max(lo, min(run[1] for run in runs))
        names = {name for _, _, name in tags}
        index = grid.labels(lo, hi - lo, names.pop() if len(names) == 1 else None)
    else:
        indexes = [
            grid.labels(b.first, len(b.kept), name).take(np.flatnonzero(b.kept))
            for b, _, name in tags
        ]
        index = indexes[0]
        for other in indexes[1:]:
            index = index.intersection(other)
    before_slice = len(index)
    index = index.take(
        np.flatnonzero((index >= resampling_start) & (index < resampling_end))
    )
    rows = (index.asi8 - grid.origin) // grid.step
    columns = [column for _, names, _ in tags for column in names]
    # column-major: the frame's one block takes it without a copy
    values = np.empty((len(rows), len(columns)), order="F")
    contiguous = len(rows) > 0 and rows[-1] - rows[0] == len(rows) - 1
    c = 0
    for b, names, _ in tags:
        if contiguous:
            lo = int(rows[0]) - b.first
            values[:, c:c + len(names)] = b.values[lo:lo + len(rows)]
        else:
            values[:, c:c + len(names)] = b.values[rows - b.first]
        c += len(names)
    frame = pd.DataFrame(values, index=index, columns=columns, copy=False)
    return frame, before_slice - len(index)


def _resample_pandas(
    series: pd.Series,
    resampling_start: datetime,
    resolution: str,
    aggregation_methods: Union[str, List[str]],
    interpolation_method: str,
    interpolation_steps: Optional[int],
) -> pd.DataFrame:
    series = series[~series.index.duplicated(keep="first")].sort_index()
    resampler = series.resample(resolution, origin=pd.Timestamp(resampling_start))
    if isinstance(aggregation_methods, str):
        frame = resampler.agg(aggregation_methods).to_frame(name=series.name)
    else:
        frame = resampler.agg(aggregation_methods)
        frame.columns = [f"{series.name}_{m}" for m in aggregation_methods]
    if interpolation_method == "linear_interpolation":
        frame = frame.interpolate(method="linear", limit=interpolation_steps)
    elif interpolation_method == "ffill":
        frame = frame.ffill(limit=interpolation_steps)
    return frame.dropna()


def _join_timeseries(
    series_iterable: Iterable[pd.Series],
    resampling_start: datetime,
    resampling_end: datetime,
    resolution: str,
    aggregation_methods: Union[str, List[str]],
    interpolation_method: str,
    interpolation_limit: Optional[str],
) -> Tuple[pd.DataFrame, Dict[str, Any], str]:
    """:func:`join_timeseries` and the path it took (``numpy`` where every
    tag was binned in numpy, else ``pandas``)."""
    resolution = _normalize_resolution(resolution)
    if interpolation_method not in ("linear_interpolation", "ffill", "none"):
        raise ValueError(
            f"interpolation_method must be one of 'linear_interpolation', "
            f"'ffill', 'none'; got {interpolation_method!r}"
        )
    metadata: Dict[str, Any] = {}

    interpolation_steps = None
    if interpolation_limit is not None:
        step = pd.Timedelta(resolution)
        interpolation_steps = max(
            1, int(pd.Timedelta(_normalize_resolution(interpolation_limit)) / step)
        )

    aggregations = (
        [aggregation_methods]
        if isinstance(aggregation_methods, str)
        else list(aggregation_methods)
    )
    binnable = (
        bool(aggregations)
        and set(aggregations) <= _BINNED_AGGREGATIONS
        and len(set(aggregations)) == len(aggregations)
    )
    grid: Optional[_Grid] = None
    # each tag as a _Binned (numpy) or a resampled frame (pandas), with its
    # columns and the name of its index
    tags: List[Tuple[Union[_Binned, pd.DataFrame], List[str], Any]] = []
    path = "numpy"
    try:
        for series in series_iterable:
            original_count = len(series)
            if original_count == 0:
                raise InsufficientDataError(f"Tag {series.name!r} has no data")
            if isinstance(aggregation_methods, str):
                names = [series.name]
            else:
                names = [f"{series.name}_{m}" for m in aggregation_methods]
            resampled: Union[_Binned, pd.DataFrame, None] = None
            own = (
                _grid_for(series, resampling_start, resolution, grid)
                if binnable else None
            )
            if own is not None and (grid is None or own == grid):
                grid = own
                resampled = _bin_series(
                    series, grid, aggregations, interpolation_method,
                    interpolation_steps,
                )
            if resampled is None:
                path = "pandas"
                resampled = _resample_pandas(
                    series, resampling_start, resolution, aggregation_methods,
                    interpolation_method, interpolation_steps,
                )
            metadata.setdefault("tags", {})[str(series.name)] = {
                "original_length": original_count,
                "resampled_length": (
                    int(resampled.kept.sum())
                    if isinstance(resampled, _Binned)
                    else len(resampled)
                ),
            }
            tags.append((resampled, names, series.index.name))

        if not tags:
            raise InsufficientDataError("No series to join (empty tag list?)")
        if path == "numpy":
            joined, dropped_by_range_slice = _join_binned(
                tags, grid, resampling_start, resampling_end
            )
        else:
            frames = [
                grid.frame(t, names, index_name) if isinstance(t, _Binned) else t
                for t, names, index_name in tags
            ]
            joined = pd.concat(frames, axis=1, join="inner").dropna()
            before_slice = len(joined)
            joined = joined[
                (joined.index >= resampling_start) & (joined.index < resampling_end)
            ]
            dropped_by_range_slice = before_slice - len(joined)
        for name in list(metadata.get("tags", {})):
            metadata["tags"][name]["dropped_by_join"] = (
                metadata["tags"][name]["resampled_length"]
                - (len(joined) + dropped_by_range_slice)
            )
        metadata["dropped_by_range_slice"] = dropped_by_range_slice
        metadata["joined_length"] = len(joined)
    finally:
        # one count a call that got as far as a tag, raised or not
        if tags or path == "pandas":
            _M_RESAMPLE.labels(path).inc()
    return joined, metadata, path


class TimeSeriesDataset(GordoBaseDataset):
    """Assemble per-tag provider series into aligned ``(X, y)`` matrices.

    Parameters mirror the reference's TimeSeriesDataset so fleet configs port
    verbatim: ``train_start_date`` / ``train_end_date`` (half-open range),
    ``tag_list``, optional ``target_tag_list`` (defaults to ``tag_list`` —
    the autoencoder X→X case), ``resolution`` (pandas offset, legacy "10T"
    accepted), ``row_filter`` (pandas query string evaluated on the joined
    frame), ``aggregation_methods``, and ``row_threshold`` (minimum rows
    after join, else :class:`InsufficientDataError`).
    """

    def __init__(
        self,
        train_start_date: Union[str, datetime],
        train_end_date: Union[str, datetime],
        tag_list: List,
        target_tag_list: Optional[List] = None,
        data_provider: Union[GordoBaseDataProvider, Dict[str, Any], None] = None,
        resolution: str = "10min",
        row_filter: Optional[str] = None,
        aggregation_methods: Union[str, List[str]] = "mean",
        row_threshold: int = 0,
        asset: Optional[str] = None,
        interpolation_method: str = "linear_interpolation",
        interpolation_limit: Optional[str] = "8H",
    ):
        self.train_start_date = _parse_date(train_start_date)
        self.train_end_date = _parse_date(train_end_date)
        if self.train_end_date <= self.train_start_date:
            raise ValueError(
                f"train_end_date ({self.train_end_date}) must be after "
                f"train_start_date ({self.train_start_date})"
            )
        self.tag_list = normalize_sensor_tags(tag_list, asset=asset)
        self.target_tag_list = (
            normalize_sensor_tags(target_tag_list, asset=asset)
            if target_tag_list
            else list(self.tag_list)
        )
        if data_provider is None:
            data_provider = RandomDataProvider()
        elif isinstance(data_provider, dict):
            data_provider = GordoBaseDataProvider.from_dict(data_provider)
        self.data_provider = data_provider
        self.resolution = resolution
        self.row_filter = row_filter
        self.aggregation_methods = aggregation_methods
        self.row_threshold = row_threshold
        self.asset = asset
        self.interpolation_method = interpolation_method
        self.interpolation_limit = interpolation_limit
        self._metadata: Dict[str, Any] = {}
        # the path the last get_data() resampled by: "numpy" or "pandas"
        self.resample_path: Optional[str] = None

        self._init_kwargs = {
            "train_start_date": self.train_start_date.isoformat(),
            "train_end_date": self.train_end_date.isoformat(),
            "tag_list": [t.to_dict() for t in self.tag_list],
            "target_tag_list": [t.to_dict() for t in self.target_tag_list],
            "data_provider": self.data_provider.to_dict(),
            "resolution": resolution,
            "row_filter": row_filter,
            "aggregation_methods": aggregation_methods,
            "row_threshold": row_threshold,
            "asset": asset,
            "interpolation_method": interpolation_method,
            "interpolation_limit": interpolation_limit,
        }

    def _columns_for(self, tags: List[SensorTag]) -> List[str]:
        """Joined-frame column names for ``tags`` under the configured
        aggregation (list aggregation suffixes columns per method)."""
        if isinstance(self.aggregation_methods, str):
            return [t.name for t in tags]
        return [
            f"{t.name}_{m}" for t in tags for m in self.aggregation_methods
        ]

    def get_data(self) -> Tuple[pd.DataFrame, pd.DataFrame]:
        # fetch the union of feature+target tags once, deduped by tag *name*
        # (the column identity); the FIRST spelling wins so a feature tag's
        # asset is never overridden by a colliding target tag
        seen: Dict[str, SensorTag] = {}
        for t in self.tag_list + self.target_tag_list:
            kept = seen.setdefault(t.name, t)
            if kept.asset != t.asset:
                logger.warning(
                    "Tag %r requested with conflicting assets %r and %r; "
                    "loading from %r",
                    t.name,
                    kept.asset,
                    t.asset,
                    kept.asset,
                )
        all_tags: List[SensorTag] = list(seen.values())
        series_iter = self.data_provider.load_series(
            self.train_start_date, self.train_end_date, all_tags
        )
        joined, tag_metadata, self.resample_path = _join_timeseries(
            series_iter,
            self.train_start_date,
            self.train_end_date,
            self.resolution,
            self.aggregation_methods,
            self.interpolation_method,
            self.interpolation_limit,
        )
        filtered_count = 0
        if self.row_filter:
            before = len(joined)
            joined = joined.query(self.row_filter)
            filtered_count = before - len(joined)
        if len(joined) < self.row_threshold:
            raise InsufficientDataError(
                f"Only {len(joined)} rows after join/filter "
                f"(threshold {self.row_threshold})"
            )
        X = joined[self._columns_for(self.tag_list)].astype(np.float32)
        y = joined[self._columns_for(self.target_tag_list)].astype(np.float32)
        self._metadata = {
            "tag_loading_metadata": tag_metadata,
            "rows_filtered": filtered_count,
            "x_shape": list(X.shape),
            "y_shape": list(y.shape),
            "tag_list": [t.name for t in self.tag_list],
            "target_tag_list": [t.name for t in self.target_tag_list],
            "resolution": self.resolution,
            "train_start_date": self.train_start_date.isoformat(),
            "train_end_date": self.train_end_date.isoformat(),
            # full re-creatable config: the server's ?start&end fetch path
            # rebuilds the dataset from this (reference: server-side data
            # fetch via the dataset config embedded in build metadata)
            "dataset_config": self.to_dict(),
        }
        return X, y

    def get_metadata(self) -> Dict[str, Any]:
        return dict(self._metadata)


class RandomDataset(TimeSeriesDataset):
    """TimeSeriesDataset pre-wired to the deterministic RandomDataProvider —
    the reference's test workhorse."""

    def __init__(
        self,
        train_start_date: Union[str, datetime] = "2023-01-01T00:00:00+00:00",
        train_end_date: Union[str, datetime] = "2023-02-01T00:00:00+00:00",
        tag_list: Optional[List] = None,
        **kwargs: Any,
    ):
        if tag_list is None:
            tag_list = ["tag-%d" % i for i in range(4)]
        kwargs.setdefault("data_provider", RandomDataProvider(min_size=600, max_size=900))
        kwargs.setdefault("resolution", "10min")
        super().__init__(
            train_start_date=train_start_date,
            train_end_date=train_end_date,
            tag_list=tag_list,
            **kwargs,
        )
