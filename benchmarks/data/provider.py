"""The benchmark's own data provider: seeded synthetic sensor series.

A copy of the semantics of the program's ``RandomDataProvider`` (600-900 raw
points per tag over the date range: a smooth random walk plus a sinusoid plus
an offset), owned by the benchmark so that a later PR cannot change the
traffic by changing the program. ``seed`` is the run's ``--seed``; tag names
carry it too, so two seeds never share a series.

:func:`benchmarks.data.series.raw_series` is the one generator. The provider
wraps it into the ``pd.Series`` the program's dataset expects; the plain
reference (``benchmarks/reference/data.py``) reads the same raw points and
does its own resampling.
"""

from __future__ import annotations

from datetime import datetime
from typing import Iterable, List

import pandas as pd

from gordo_components_tpu.dataset.data_provider.base import GordoBaseDataProvider
from gordo_components_tpu.dataset.sensor_tag import SensorTag

from benchmarks.data.series import raw_series


def _to_ns(moment: datetime) -> int:
    stamp = pd.Timestamp(moment)
    if stamp.tzinfo is None:
        stamp = stamp.tz_localize("UTC")
    return int(stamp.tz_convert("UTC").value)


class SeededProvider(GordoBaseDataProvider):
    def __init__(self, seed: int = 0, min_size: int = 600, max_size: int = 900):
        self._init_kwargs = {
            "seed": seed, "min_size": min_size, "max_size": max_size,
        }
        self.seed = int(seed)
        self.min_size = int(min_size)
        self.max_size = int(max_size)

    def can_handle_tag(self, tag: SensorTag) -> bool:
        return True

    def load_series(
        self,
        train_start_date: datetime,
        train_end_date: datetime,
        tag_list: List[SensorTag],
        dry_run: bool = False,
    ) -> Iterable[pd.Series]:
        if train_end_date <= train_start_date:
            raise ValueError("train_end_date must be after train_start_date")
        if dry_run:
            return
        start_ns, end_ns = _to_ns(train_start_date), _to_ns(train_end_date)
        for tag in tag_list:
            t_ns, values = raw_series(
                tag.name, self.seed, start_ns, end_ns,
                self.min_size, self.max_size,
            )
            index = pd.DatetimeIndex(t_ns, tz="UTC")
            yield pd.Series(values, index=index, name=tag.name)
