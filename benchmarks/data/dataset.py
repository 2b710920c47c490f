"""The benchmark's dataset class: the program's ``TimeSeriesDataset`` with a
clock around ``get_data``.

``build_fleet`` builds datasets from the fleet config by dotted ``type``, so
state cannot be passed in: the :data:`RECORDER` of this module is the one
place the harness reads fetch times from, and the one switch by which it ends
a job between slices. Resampling, joining and assembly are the program's own
code (``super().get_data()``) and are what the clock measures.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional

import numpy as np

from gordo_components_tpu.dataset.dataset import TimeSeriesDataset


class StopBuild(BaseException):
    """Raised at the end of ``get_data`` once the harness has asked the job
    to end: the fetch itself is made and timed first, so that the slice in
    flight trains beside the same fetch load as every other slice.

    A ``BaseException`` on purpose: ``build_fleet`` retries and then isolates
    a machine whose fetch raises an ``Exception``; this one passes through the
    fetch pool and the prefetcher and ends ``build_fleet`` at the next slice
    boundary, after the slice in flight has committed."""


class Recorder:
    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.reset()

    def reset(self) -> None:
        with self._lock:
            self.fetches: List[Dict[str, float]] = []
            self.stop = threading.Event()
            self.first_fetch_at: Optional[float] = None

    def add(self, name: str, started: float, seconds: float, rows: int,
            total: float) -> None:
        with self._lock:
            if self.first_fetch_at is None:
                self.first_fetch_at = started
            self.fetches.append({
                "machine": name, "at": started, "seconds": seconds,
                "rows": rows, "sum": total,
            })

    def snapshot(self) -> List[Dict[str, float]]:
        with self._lock:
            return list(self.fetches)


RECORDER = Recorder()


class TimedDataset(TimeSeriesDataset):
    def __init__(self, *args, machine: str = "", **kwargs):
        super().__init__(*args, **kwargs)
        self.machine = machine
        self._init_kwargs["machine"] = machine

    def get_data(self):
        started = time.perf_counter()
        X, y = super().get_data()
        seconds = time.perf_counter() - started
        RECORDER.add(
            self.machine, started, seconds, int(X.shape[0]),
            float(np.asarray(X.values, np.float64).sum()),
        )
        if RECORDER.stop.is_set():
            raise StopBuild(self.machine)
        return X, y
