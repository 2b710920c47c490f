"""What every driver shares: the cell's files, the device, the clock of
compiles, the deadline, the result line.

A run is ``python benchmarks/run.py --workload <config>.<mix> --seed N
--seconds S --trace 0|1``. The cell is looked up in ``BENCHMARK.json``; its
configuration in ``benchmarks/configs/<config>.json``; its traffic in
``benchmarks/traffic/<mix>.json``, whose ``kind`` names the driver module
``benchmarks/drivers/<kind>.py``; each per-layer metric's reader is
``benchmarks/layer_metrics/<name>.py``. Nothing here names a cell.
"""

from __future__ import annotations

import importlib
import json
import os
import sys
import threading
import time
from typing import Any, Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmarks")
WORK_ROOT = os.path.join(ROOT, ".bench_work")

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"


def log(message: str) -> None:
    sys.stderr.write(f"bench: {message}\n")
    sys.stderr.flush()


def load_json(*parts: str) -> Dict[str, Any]:
    with open(os.path.join(*parts)) as fh:
        return json.load(fh)


def cpu_requested() -> bool:
    return os.environ.get("JAX_PLATFORMS", "").split(",")[0].strip().lower() == "cpu"


def load_cell(workload: str) -> Dict[str, Any]:
    """The cell's entry, configuration and traffic. A name that
    ``BENCHMARK.json`` does not list is taken apart by the naming rule
    ``<config>.<mix>`` only for a rehearsal on the CPU, and only for a mix
    that says ``rehearsal_only``."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cell = next((c for c in bench["workloads"] if c["name"] == workload), None)
    if cell is None:
        config_name, _, mix = workload.partition(".")
        traffic_path = os.path.join(HERE, "traffic", f"{mix}.json")
        if not (cpu_requested() and os.path.exists(traffic_path)
                and load_json(traffic_path).get("rehearsal_only")):
            raise SystemExit(f"bench: no workload {workload!r} in BENCHMARK.json")
        cell = {"name": workload, "config": config_name, "traffic": mix, "chips": 1}
    config_file = next(
        (c["file"] for c in bench["configs"] if c["name"] == cell["config"]),
        # a rehearsal may use a configuration file that no cell uses yet
        os.path.join("benchmarks", "configs", f"{cell['config']}.json"),
    )
    return {
        "bench": bench,
        "cell": cell,
        "config": load_json(ROOT, config_file),
        "traffic": load_json(HERE, "traffic", f"{cell['traffic']}.json"),
    }


def metric_names(bench: Dict[str, Any], group: str, workload: str, reports: List[str]) -> List[str]:
    """Names of ``group``'s metrics this cell has to report: those that list
    it under ``workloads``, and those with no list (per-layer ones only where
    the cell reports the end-to-end metric they move)."""
    names = []
    for metric in bench[group]:
        listed = metric.get("workloads")
        if listed is not None:
            if workload in listed:
                names.append(metric["name"])
        elif group == "end_to_end" or metric["moves"] in reports:
            names.append(metric["name"])
    return names


def device_or_exit(chips: int) -> Dict[str, Any]:
    """What JAX found. No accelerator, or fewer chips than the cell asks
    for: exit 2 with nothing on stdout. ``JAX_PLATFORMS=cpu`` is the one way
    to rehearse on the CPU; such a run never prints a device metric."""
    import jax

    devices = jax.devices()
    found = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if found["platform"] == "cpu":
        if not cpu_requested():
            log(f"JAX found no accelerator ({found}); nothing was run")
            raise SystemExit(2)
    elif found["count"] < chips:
        log(f"the cell asks for {chips} chip(s), JAX has {found}")
        raise SystemExit(2)
    return found


def peak_for(kind: str) -> Dict[str, float]:
    peaks = load_json(HERE, "peaks.json")
    if kind not in peaks:
        raise SystemExit(f"bench: no peaks for device kind {kind!r} in peaks.json")
    return peaks[kind]


class CompileMeter:
    """Every XLA compile of the process, with the time it ended, from JAX's
    own monitoring events (a persistent-cache hit fires the duration event
    too, with the retrieval time)."""

    def __init__(self) -> None:
        import jax

        self.events: List[Dict[str, float]] = []
        self.hits: List[float] = []
        self.misses: List[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == _BACKEND_COMPILE:
            self.events.append({"at": time.perf_counter(), "seconds": float(duration)})

    def _event(self, event, **_):
        if event == _CACHE_HIT:
            self.hits.append(time.perf_counter())
        elif event == _CACHE_MISS:
            self.misses.append(time.perf_counter())

    def cold_seconds(self) -> float:
        """Seconds of compiling so far in a process that missed the
        persistent cache at least once; 0.0 in a run that found every
        program there."""
        return sum(e["seconds"] for e in list(self.events)) if self.misses else 0.0

    def between(self, lo: float, hi: float) -> Dict[str, float]:
        events = [e for e in self.events if lo <= e["at"] <= hi]
        return {
            "programs": len(events),
            "seconds": sum(e["seconds"] for e in events),
            "cache_hits": sum(lo <= t <= hi for t in self.hits),
            "cache_misses": sum(lo <= t <= hi for t in self.misses),
        }


class Deadline:
    """The run's own time limit. ``phase`` is what the run is doing; when a
    limit passes the process says so on stderr and ends with code 4, without
    a result line. The limits are a warm run's, inside the driver's 360 s;
    a run that compiles (a cell's first in a checkout, which the driver
    gives 1,200 s) gets the seconds it spent compiling on top of them, up to
    ``COLD_CAP_S`` from the start."""

    COLD_CAP_S = 1150.0

    def __init__(self, started: float, meter: Optional[CompileMeter] = None):
        self.started = started
        self.meter = meter
        self.phase = "start"
        self.phase_since = started
        self._limits: Dict[str, float] = {}
        self._closed = False
        self._lock = threading.Lock()
        self._thread = threading.Thread(target=self._watch, daemon=True, name="bench-deadline")
        self._thread.start()

    def enter(self, phase: str) -> None:
        with self._lock:
            self.phase, self.phase_since = phase, time.perf_counter()

    def limit(self, name: str, seconds_from_start: Optional[float]) -> None:
        with self._lock:
            if seconds_from_start is None:
                self._limits.pop(name, None)
            else:
                self._limits[name] = self.started + seconds_from_start

    def close(self) -> None:
        self._closed = True

    def _watch(self) -> None:
        while not self._closed:
            time.sleep(0.25)
            now = time.perf_counter()
            cold = self.meter.cold_seconds() if self.meter else 0.0
            with self._lock:
                cap = self.started + self.COLD_CAP_S
                over = [n for n, t in self._limits.items() if now > min(t + cold, cap)]
                phase, since = self.phase, self.phase_since
            if over:
                log(
                    f"DEADLINE {over[0]} passed {now - self.started:.1f}s after "
                    f"start ({cold:.1f}s of them compiling from a cold cache), "
                    f"in phase {phase!r} for {now - since:.1f}s; no result"
                )
                os._exit(4)


def read_layer_metrics(names: List[str], view: Dict[str, Any]) -> Dict[str, float]:
    """Each metric's reader, ``benchmarks/layer_metrics/<name>.py``, is asked
    for its number; one that finds nothing to read returns ``None`` and the
    metric is left out of the line."""
    out = {}
    for name in names:
        reader = importlib.import_module(f"benchmarks.layer_metrics.{name}")
        value = reader.read(view)
        if value is not None:
            out[name] = float(value)
    return out


def result_line(
    bench: Dict[str, Any], correct: bool, attempted: int, failed: int,
    values: Dict[str, float], device: Dict[str, Any],
    breakdown: Optional[Dict[str, Any]], compared: Dict[str, Any],
) -> str:
    units = {m["name"]: m["unit"] for m in bench["end_to_end"] + bench["per_layer"]}
    line: Dict[str, Any] = {
        "correct": bool(correct),
        "attempted": int(attempted),
        "failed": int(failed),
        "metrics": {
            name: {"value": value, "unit": units[name]}
            for name, value in values.items()
        },
        "device": device,
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["compared"] = compared
    return json.dumps(line)
