"""Dataset layer tests, mirroring the reference's test strategy (SURVEY §5):
resample/join correctness, gap handling, row_filter, tag-count metadata,
provider dispatch and round-tripping."""

from datetime import datetime, timezone

import numpy as np
import pandas as pd
import pytest

from gordo_components_tpu.dataset import (
    RandomDataset,
    SensorTag,
    TimeSeriesDataset,
    join_timeseries,
    normalize_sensor_tags,
)
from gordo_components_tpu.dataset.base import GordoBaseDataset
from gordo_components_tpu.dataset.dataset import InsufficientDataError
from gordo_components_tpu.dataset.data_provider import (
    FileDataProvider,
    GordoBaseDataProvider,
    RandomDataProvider,
)
from gordo_components_tpu.dataset.sensor_tag import (
    SensorTagNormalizationError,
    normalize_sensor_tag,
)

UTC = timezone.utc
START = datetime(2023, 1, 1, tzinfo=UTC)
END = datetime(2023, 2, 1, tzinfo=UTC)


class TestSensorTag:
    def test_normalize_forms(self):
        tags = normalize_sensor_tags(
            [
                "ASGB.tag1",
                ["plain-tag", "assetX"],
                {"name": "dict-tag", "asset": "assetY"},
                SensorTag("already", "assetZ"),
            ]
        )
        assert tags[0] == SensorTag("ASGB.tag1", "asgb")
        assert tags[1] == SensorTag("plain-tag", "assetX")
        assert tags[2] == SensorTag("dict-tag", "assetY")
        assert tags[3] == SensorTag("already", "assetZ")

    def test_default_asset_wins_over_unknown(self):
        assert normalize_sensor_tag("unknown-tag", asset="mine").asset == "mine"

    def test_prefix_inference(self):
        assert normalize_sensor_tag("1901.PT.101").asset == "asgb"
        assert normalize_sensor_tag("nonexistent_prefix_tag").asset is None

    def test_bad_specs_raise(self):
        with pytest.raises(SensorTagNormalizationError):
            normalize_sensor_tag({"asset": "no-name"})
        with pytest.raises(SensorTagNormalizationError):
            normalize_sensor_tag(["a", "b", "c"])
        with pytest.raises(SensorTagNormalizationError):
            normalize_sensor_tag(123)


class TestProviders:
    def test_random_provider_deterministic(self):
        provider = RandomDataProvider(seed=7)
        tags = normalize_sensor_tags(["t1", "t2"])
        a = list(provider.load_series(START, END, tags))
        b = list(provider.load_series(START, END, tags))
        for s1, s2 in zip(a, b):
            pd.testing.assert_series_equal(s1, s2)
        # different tags differ
        assert not np.allclose(a[0].values[: len(a[1])], a[1].values[: len(a[0])])

    def test_random_provider_bad_range(self):
        provider = RandomDataProvider()
        with pytest.raises(ValueError):
            list(provider.load_series(END, START, []))

    def test_provider_roundtrip(self):
        provider = RandomDataProvider(min_size=50, max_size=60, seed=3)
        clone = GordoBaseDataProvider.from_dict(provider.to_dict())
        assert isinstance(clone, RandomDataProvider)
        assert clone.min_size == 50 and clone.max_size == 60 and clone.seed == 3

    def test_file_provider(self, tmp_path):
        index = pd.date_range(START, periods=100, freq="10min")
        frame = pd.DataFrame(
            {"timestamp": index, "value": np.arange(100, dtype=float)}
        )
        frame.to_csv(tmp_path / "mytag.csv", index=False)
        provider = FileDataProvider(base_dir=str(tmp_path))
        tag = SensorTag("mytag")
        assert provider.can_handle_tag(tag)
        assert not provider.can_handle_tag(SensorTag("missing"))
        (series,) = list(provider.load_series(START, END, [tag]))
        assert len(series) == 100
        assert series.iloc[5] == 5.0

    def test_file_provider_naive_timestamps(self, tmp_path):
        # naive file timestamps vs tz-aware range must not crash
        index = pd.date_range("2023-01-01", periods=50, freq="10min")  # naive
        pd.DataFrame({"timestamp": index, "value": np.ones(50)}).to_csv(
            tmp_path / "naive.csv", index=False
        )
        provider = FileDataProvider(base_dir=str(tmp_path))
        (series,) = list(provider.load_series(START, END, [SensorTag("naive")]))
        assert len(series) == 50
        assert str(series.index.tz) == "UTC"


class TestJoinTimeseries:
    def _series(self, name, start, periods, freq="10min", values=None):
        index = pd.date_range(start, periods=periods, freq=freq)
        values = values if values is not None else np.arange(periods, dtype=float)
        return pd.Series(values, index=index, name=name)

    def test_inner_join_drops_nonoverlap(self):
        s1 = self._series("a", START, 100)
        s2 = self._series("b", START + pd.Timedelta("300min"), 100)
        joined, meta = join_timeseries(
            [s1, s2], START, END, "10min", interpolation_method="none"
        )
        assert len(joined) == 70  # overlap of [30, 100)
        assert meta["tags"]["a"]["original_length"] == 100
        assert meta["tags"]["a"]["dropped_by_join"] == 30
        assert meta["joined_length"] == 70

    def test_resample_aggregates(self):
        # 1-min data resampled to 10-min means
        s = self._series("a", START, 60, freq="1min")
        joined, _ = join_timeseries([s], START, END, "10min", interpolation_method="none")
        assert len(joined) == 6
        assert joined["a"].iloc[0] == pytest.approx(np.mean(np.arange(10)))

    def test_empty_series_raises(self):
        empty = pd.Series([], index=pd.DatetimeIndex([]), name="e", dtype=float)
        with pytest.raises(InsufficientDataError):
            join_timeseries([empty], START, END, "10min")

    def test_legacy_resolution_spelling(self):
        s = self._series("a", START, 60, freq="1min")
        joined, _ = join_timeseries([s], START, END, "10T", interpolation_method="none")
        assert len(joined) == 6


class TestTimeSeriesDataset:
    def test_get_data_shapes_and_metadata(self):
        dataset = RandomDataset(tag_list=["t1", "t2", "t3"])
        X, y = dataset.get_data()
        assert list(X.columns) == ["t1", "t2", "t3"]
        assert X.shape == y.shape
        assert X.dtypes.iloc[0] == np.float32
        meta = dataset.get_metadata()
        assert meta["x_shape"] == list(X.shape)
        assert "t1" in meta["tag_loading_metadata"]["tags"]

    def test_target_tags(self):
        dataset = RandomDataset(tag_list=["t1", "t2"], target_tag_list=["t2"])
        X, y = dataset.get_data()
        assert list(X.columns) == ["t1", "t2"]
        assert list(y.columns) == ["t2"]

    def test_row_filter(self):
        dataset = RandomDataset(tag_list=["t1", "t2"])
        X_all, _ = dataset.get_data()
        threshold = float(X_all["t1"].median())
        filtered = RandomDataset(tag_list=["t1", "t2"], row_filter=f"`t1` > {threshold}")
        X_f, _ = filtered.get_data()
        assert 0 < len(X_f) < len(X_all)
        assert (X_f["t1"] > threshold).all()

    def test_row_threshold(self):
        with pytest.raises(InsufficientDataError):
            RandomDataset(tag_list=["t1"], row_threshold=10**9).get_data()

    def test_from_dict_roundtrip(self):
        dataset = RandomDataset(tag_list=["t1", "t2"])
        clone = GordoBaseDataset.from_dict(dataset.to_dict())
        X1, _ = dataset.get_data()
        X2, _ = clone.get_data()
        pd.testing.assert_frame_equal(X1, X2)

    def test_bad_date_range(self):
        with pytest.raises(ValueError):
            TimeSeriesDataset(
                train_start_date="2023-02-01", train_end_date="2023-01-01", tag_list=["t"]
            )

    def test_multi_aggregation(self):
        dataset = RandomDataset(
            tag_list=["t1", "t2"], aggregation_methods=["mean", "max"]
        )
        X, y = dataset.get_data()
        assert list(X.columns) == ["t1_mean", "t1_max", "t2_mean", "t2_max"]
        assert (X["t1_max"] >= X["t1_mean"] - 1e-6).all()

    def test_interpolation_roundtrip(self):
        ds = RandomDataset(tag_list=["t1"], interpolation_method="none")
        clone = GordoBaseDataset.from_dict(ds.to_dict())
        X1, _ = ds.get_data()
        X2, _ = clone.get_data()
        pd.testing.assert_frame_equal(X1, X2)

    def test_bad_interpolation_method(self):
        with pytest.raises(ValueError, match="interpolation_method"):
            RandomDataset(tag_list=["t1"], interpolation_method="linear").get_data()

    def test_same_name_different_asset_dedup(self):
        ds = RandomDataset(
            tag_list=[{"name": "t1", "asset": "a"}],
            target_tag_list=[{"name": "t1", "asset": "b"}],
        )
        X, y = ds.get_data()
        assert X.shape[1] == 1 and y.shape[1] == 1


class TestReviewRegressions:
    def test_legacy_hour_resolution(self):
        # ported gordo configs commonly use "1H"
        ds = RandomDataset(tag_list=["t1"], resolution="1H")
        X, _ = ds.get_data()
        assert len(X) > 0

    def test_list_tag_with_none_asset(self):
        tag = normalize_sensor_tag(["ASGB.x", None])
        assert tag.asset == "asgb"

    def test_dedup_keeps_first_spelling(self):
        ds = RandomDataset(
            tag_list=[{"name": "t1", "asset": "a"}],
            target_tag_list=[{"name": "t1", "asset": "b"}],
        )
        seen = {}
        for t in ds.tag_list + ds.target_tag_list:
            seen.setdefault(t.name, t)
        assert seen["t1"].asset == "a"

    def test_influx_password_not_serialized(self):
        from gordo_components_tpu.dataset.data_provider import InfluxDataProvider

        provider = InfluxDataProvider(
            measurement="m", host="h", username="u", password="hunter2", api_key="k"
        )
        serialized = provider.to_dict()
        assert "password" not in serialized
        assert "api_key" not in serialized
        assert serialized["username"] == "u"


class _FakeInfluxClient:
    """Stands in for influxdb.DataFrameClient: returns one frame per query,
    optionally with a naive or non-UTC index or a renamed value column."""

    def __init__(self, frames_by_tag, measurement="m", tz="UTC", value_col="value"):
        self.frames_by_tag = frames_by_tag
        self.measurement = measurement
        self.tz = tz
        self.value_col = value_col
        self.queries = []

    def query(self, q):
        self.queries.append(q)
        import re

        tag = re.search(r"WHERE tag = '([^']*)'", q).group(1)
        values = self.frames_by_tag[tag]
        idx = pd.date_range("2023-01-01", periods=len(values), freq="10min")
        if self.tz is not None:
            idx = idx.tz_localize(self.tz)
        frame = pd.DataFrame({self.value_col: values}, index=idx)
        return {self.measurement: frame}


class TestInfluxProvider:
    def _provider(self, **kwargs):
        from gordo_components_tpu.dataset.data_provider import InfluxDataProvider

        return InfluxDataProvider(measurement="m", **kwargs)

    def _load(self, provider, tags):
        from datetime import datetime, timezone

        return list(
            provider.load_series(
                datetime(2023, 1, 1, tzinfo=timezone.utc),
                datetime(2023, 1, 2, tzinfo=timezone.utc),
                [SensorTag(t, "asset") for t in tags],
            )
        )

    def test_fake_client_round_trip_utc(self):
        client = _FakeInfluxClient({"t1": [1.0, 2.0], "t2": [3.0, 4.0]})
        series = self._load(self._provider(client=client), ["t1", "t2"])
        assert [s.name for s in series] == ["t1", "t2"]
        assert all(str(s.index.tz) == "UTC" for s in series)

    def test_naive_index_localized_to_utc(self):
        client = _FakeInfluxClient({"t1": [1.0, 2.0]}, tz=None)
        (s,) = self._load(self._provider(client=client), ["t1"])
        assert str(s.index.tz) == "UTC"

    def test_foreign_tz_converted_to_utc(self):
        client = _FakeInfluxClient({"t1": [1.0, 2.0]}, tz="Europe/Oslo")
        (s,) = self._load(self._provider(client=client), ["t1"])
        assert str(s.index.tz) == "UTC"
        # 2023-01-01 00:00 Oslo is 2022-12-31 23:00 UTC
        assert s.index[0].hour == 23

    def test_missing_value_column_is_clear_error(self):
        client = _FakeInfluxClient({"t1": [1.0]}, value_col="other")
        with pytest.raises(ValueError, match="no 'value' column"):
            self._load(self._provider(client=client), ["t1"])

    def test_injected_client_feeds_timeseries_dataset(self):
        client = _FakeInfluxClient(
            {"t1": list(range(144)), "t2": list(range(144))}
        )
        provider = self._provider(client=client)
        ds = TimeSeriesDataset(
            data_provider=provider,
            train_start_date="2023-01-01T00:00:00+00:00",
            train_end_date="2023-01-02T00:00:00+00:00",
            tag_list=["t1", "t2"],
            resolution="10min",
        )
        X, y = ds.get_data()
        assert list(X.columns) == ["t1", "t2"]
        assert len(X) > 100


# -- the numpy resampling path against the pandas one ------------------------
# ``_grid_for`` is where join_timeseries reads from its input whether the
# numpy path is exact for it; patched to refuse, the same call goes through
# the pandas path, which is kept as it was.

import gordo_components_tpu.dataset.dataset as dataset_module  # noqa: E402


def _tag(name, minutes, values=None, tz="UTC", unit="ns", seed=0):
    """A series of points ``minutes`` after 2023-01-01 00:00 (wall time in
    ``tz``, or naive), values a seeded walk well away from zero."""
    minutes = np.asarray(minutes, dtype=float)
    if values is None:
        rng = np.random.default_rng(seed)
        values = 20.0 + rng.normal(scale=0.3, size=len(minutes)).cumsum()
    stamps = np.datetime64("2023-01-01T00:00") + (minutes * 60e9).astype(
        "timedelta64[ns]"
    )
    index = pd.DatetimeIndex(stamps).as_unit(unit)
    if tz is not None:
        index = index.tz_localize(tz)
    return pd.Series(np.asarray(values, dtype=float), index=index, name=name)


def _every(step, stop, start=0.0):
    return np.arange(start, stop, step)


def _with_nan(values, *spans):
    values = np.array(values, dtype=float)
    for lo, hi in spans:
        values[lo:hi] = np.nan
    return values


def _gap(lo, hi, step=3.0, stop=2000.0):
    minutes = _every(step, stop)
    return minutes[(minutes < lo) | (minutes >= hi)]


_OSLO_START = pd.Timestamp("2023-01-01", tz="Europe/Oslo").to_pydatetime()
_NAIVE = (datetime(2023, 1, 1), datetime(2023, 1, 3))
_BASE = _every(3.0, 2000.0)
_DUPLICATED = np.concatenate([_BASE, _BASE[::7]])
_SHUFFLE = np.random.default_rng(3).permutation(len(_DUPLICATED))

# case -> (the series, join_timeseries' arguments beyond them)
PARITY_CASES = {
    "duplicates-unsorted": (
        lambda: [_tag("a", _DUPLICATED[_SHUFFLE], 20.0 + _SHUFFLE % 11)], {}),
    "nan-values-and-an-all-nan-bin": (
        lambda: [_tag("a", _BASE, _with_nan(
            20.0 + np.sin(_BASE), (10, 11), (100, 104), (300, 302)))], {}),
    "gap-shorter-than-the-limit": (
        lambda: [_tag("a", _gap(300, 500))], {"interpolation_limit": "4h"}),
    "gap-longer-than-the-limit-partly-filled": (
        lambda: [_tag("a", _gap(300, 900)), _tag("b", _BASE, seed=1)],
        {"interpolation_limit": "1h"}),
    "leading-and-trailing-empty-bins": (
        lambda: [_tag("a", _BASE, _with_nan(
            20.0 + np.cos(_BASE), (0, 25), (len(_BASE) - 30, len(_BASE)))),
            _tag("b", _BASE, seed=1)],
        {"interpolation_limit": "40min"}),
    "points-before-start-and-at-or-after-end": (
        lambda: [_tag("a", _every(3.0, 3000.0, start=-200.0))],
        {"resampling_end": datetime(2023, 1, 2, tzinfo=UTC)}),
    "tz-utc": (lambda: [_tag("a", _BASE), _tag("b", _BASE[5:], seed=1)], {}),
    "tz-europe-oslo": (
        lambda: [_tag("a", _BASE, tz="Europe/Oslo"),
                 _tag("b", _BASE[5:], tz="Europe/Oslo", seed=1)],
        {"resampling_start": _OSLO_START}),
    "tz-naive": (
        lambda: [_tag("a", _BASE, tz=None), _tag("b", _BASE[5:], tz=None, seed=1)],
        {"resampling_start": _NAIVE[0], "resampling_end": _NAIVE[1]}),
    "unit-us": (
        lambda: [_tag("a", _BASE, unit="us"), _tag("b", _BASE, unit="us", seed=1)],
        {}),
    "unit-ns-resolution-legacy-10T": (
        lambda: [_tag("a", _BASE, unit="ns")], {"resolution": "10T"}),
    "start-not-aligned-to-the-step": (
        lambda: [_tag("a", _BASE)],
        {"resampling_start": datetime(2023, 1, 1, 0, 3, 17, tzinfo=UTC)}),
    "aggregation-mean-max-min": (
        lambda: [_tag("a", _BASE, _with_nan(20.0 + np.sin(_BASE), (40, 60))),
                 _tag("b", _BASE, seed=1)],
        {"aggregation_methods": ["mean", "max", "min"],
         "interpolation_limit": "30min"}),
    "ffill-with-a-limit": (
        lambda: [_tag("a", _gap(300, 900)), _tag("b", _BASE, seed=1)],
        {"interpolation_method": "ffill", "interpolation_limit": "1h"}),
    "interpolation-none": (
        lambda: [_tag("a", _gap(300, 500)), _tag("b", _BASE, seed=1)],
        {"interpolation_method": "none"}),
    "ranges-only-partly-overlap": (
        lambda: [_tag("a", _every(3.0, 1200.0)),
                 _tag("b", _every(3.0, 1900.0, start=500.0), seed=1),
                 _tag("c", _every(3.0, 1500.0, start=200.0), seed=2)], {}),
}


class _FixedProvider(GordoBaseDataProvider):
    """Hands out the series it was given, by tag name."""

    def __init__(self, series):
        self.series = {s.name: s for s in series}

    def can_handle_tag(self, tag):
        return tag.name in self.series

    def load_series(self, train_start_date, train_end_date, tag_list, dry_run=False):
        for tag in tag_list:
            yield self.series[tag.name].copy()


def _join_both(series, monkeypatch, **kwargs):
    args = {"resampling_start": START, "resampling_end": END,
            "resolution": "10min", "aggregation_methods": "mean",
            "interpolation_method": "linear_interpolation",
            "interpolation_limit": "8h", **kwargs}
    fast = dataset_module._join_timeseries([s.copy() for s in series], **args)
    with monkeypatch.context() as patched:
        patched.setattr(dataset_module, "_grid_for", lambda *a, **k: None)
        slow = dataset_module._join_timeseries([s.copy() for s in series], **args)
    return fast, slow


def _assert_same_frame(fast, slow):
    assert fast.index.dtype == slow.index.dtype
    assert str(fast.index.tz) == str(slow.index.tz)
    assert fast.index.freq == slow.index.freq
    assert fast.index.name == slow.index.name
    pd.testing.assert_frame_equal(
        fast, slow, check_exact=False, rtol=1e-12, atol=0.0
    )


@pytest.mark.parametrize("case", sorted(PARITY_CASES) + ["row-filter-and-threshold"])
def test_numpy_path_matches_pandas(case, monkeypatch):
    if case == "row-filter-and-threshold":
        series = [_tag("a", _gap(300, 900)), _tag("b", _BASE, seed=1)]

        def dataset(threshold):
            return TimeSeriesDataset(
                START, END, ["a", "b"], target_tag_list=["b"],
                data_provider=_FixedProvider(series), row_filter="`a` > 20",
                row_threshold=threshold, interpolation_limit="1h",
            )

        fast = dataset(0)
        X, y = fast.get_data()
        with monkeypatch.context() as patched:
            patched.setattr(dataset_module, "_grid_for", lambda *a, **k: None)
            slow = dataset(0)
            X_slow, y_slow = slow.get_data()
            with pytest.raises(InsufficientDataError):
                dataset(len(X) + 1).get_data()
        with pytest.raises(InsufficientDataError):
            dataset(len(X) + 1).get_data()
        assert (fast.resample_path, slow.resample_path) == ("numpy", "pandas")
        assert 0 < len(X) and fast.get_metadata()["rows_filtered"] > 0
        _assert_same_frame(X, X_slow)
        _assert_same_frame(y, y_slow)
        assert fast.get_metadata() == slow.get_metadata()
        return
    build, kwargs = PARITY_CASES[case]
    (fast, fast_meta, fast_path), (slow, slow_meta, slow_path) = _join_both(
        build(), monkeypatch, **kwargs
    )
    assert (fast_path, slow_path) == ("numpy", "pandas")
    assert len(fast) > 0
    _assert_same_frame(fast, slow)
    assert list(fast.dtypes) == list(slow.dtypes)
    assert fast_meta == slow_meta


def _resample_counts():
    return {
        path: dataset_module._M_RESAMPLE.collect().get((path,), 0.0)
        for path in ("numpy", "pandas")
    }


@pytest.mark.parametrize(
    "series, aggregation, path",
    [
        # the benchmark's shape: evenly spaced float64 points on a UTC index
        (lambda: [_tag(f"t{i}", _BASE, seed=i) for i in range(3)], "mean", "numpy"),
        (lambda: [_tag("a", _BASE)], "median", "pandas"),
        (lambda: [_tag("a", _BASE)], ["mean", np.mean], "pandas"),
        (lambda: [pd.Series(20.0 + np.arange(50.0), name="a")], "mean", "pandas"),
        (lambda: [_tag("a", _BASE),
                  _tag("b", _BASE).astype(np.int64)], "mean", "pandas"),
    ],
    ids=["benchmark-shaped", "median", "callable", "not-a-datetime-index",
         "an-integer-tag"],
)
def test_resample_counter_counts_each_call_by_its_path(series, aggregation, path):
    before = _resample_counts()
    try:
        _, _, taken = dataset_module._join_timeseries(
            series(), START, END, "10min", aggregation, "none", None
        )
    except TypeError:
        # pandas refuses to resample a non-DatetimeIndex: still its path
        taken = "pandas"
    after = _resample_counts()
    assert taken == path
    assert after[path] == before[path] + 1
    other = "pandas" if path == "numpy" else "numpy"
    assert after[other] == before[other]
