"""Process-wide labeled metric primitives: Counter / Gauge / Histogram.

The reference has no metrics layer at all (SURVEY.md §6: debugging was
kubectl logs); before this module the rebuild's only telemetry was the
server's ad-hoc ``_Latency`` ring buffer and ``PhaseTimer`` durations that
died with the build process. This registry is the ONE place every layer
(client, server, engine, builder, fleet, watchman) records to, so a
single ``GET /metrics`` — JSON or Prometheus text — sees the whole process.

Design (deliberately mirrors the retired ``_Latency``): lock-LIGHT, not
lock-free — one ``threading.Lock`` per metric, held only for dict/list
mutation; percentile math runs on a snapshot copied under the lock. A
histogram keeps both cumulative buckets (Prometheus exposition) and a
bounded rolling sample window (the JSON p50/p99 view a long-lived server
can afford — unbounded per-request history is exactly what ``_Latency``'s
``keep`` cap existed to prevent).

Get-or-create semantics: ``registry.counter(name, ...)`` returns the
existing metric when one is already registered under ``name`` (many
ModelServer instances in one test process must share series, not crash),
and raises on kind/label mismatch so two call sites can never silently
write incompatible series under one name.
"""

from __future__ import annotations

import bisect
import os
import threading
import time
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

INF = float("inf")

# -- bounded machine cardinality (ARCHITECTURE §22) ---------------------------
# The one label dimension that scales with FLEET SIZE, not with code: a
# 100k-machine fleet must not be able to melt the scrape path (100k text
# lines per family) or the §18 aggregator. Families labeled by machine
# collapse at exposition/snapshot time to the top-K machines by traffic
# plus ONE `machine="other"` aggregate; the in-memory series stay exact
# (a future scoped query could still read them), only the rendered view
# is bounded.
MACHINE_LABEL = "machine"
MACHINE_OTHER = "other"

# The ONE authoritative top-K-by-traffic selection (§24): when the
# telemetry traffic sketch is live, it nominates the kept machines for
# every family, so a scrape shows one consistent survivor set instead of
# per-family re-derivations that can disagree. observability.traffic
# installs the provider at import time (a callable cap -> names); the
# hook keeps the dependency pointed traffic -> registry, never back.
_traffic_topk_provider = None


def set_traffic_topk_provider(provider) -> None:
    global _traffic_topk_provider
    _traffic_topk_provider = provider


def machine_cardinality_cap() -> int:
    """``GORDO_METRICS_MACHINE_CARDINALITY``: distinct machine label
    values rendered per family before top-K + ``other`` collapse
    (default 64; ``0`` disables the bound)."""
    try:
        return int(
            os.environ.get("GORDO_METRICS_MACHINE_CARDINALITY", "64")
        )
    except ValueError:
        return 64


def _merge_histogram_data(into: Dict[str, Any], data: Dict[str, Any]) -> None:
    """le-wise bucket merge (+sum/count) of two ``Histogram.collect``
    series — bucket bounds agree by construction (same metric)."""
    into["buckets"] = [
        (le, acc + other_acc)
        for (le, acc), (_, other_acc) in zip(into["buckets"], data["buckets"])
    ]
    into["sum"] += data["sum"]
    into["count"] += data["count"]
    into["samples"] = (into["samples"] + data["samples"])[-1000:]
    for i, exemplar in (data.get("exemplars") or {}).items():
        current = into["exemplars"].get(i)
        if current is None or exemplar[2] >= current[2]:  # newest wins
            into["exemplars"][i] = exemplar


def bound_machine_cardinality(
    metric: "_Metric", collected: Dict[Tuple[str, ...], Any]
) -> Dict[Tuple[str, ...], Any]:
    """Collapse ``collected`` (a ``metric.collect()`` mapping) so at most
    top-K distinct machine label values survive; the rest aggregate into
    ``machine="other"`` — counters SUM (total traffic is additive),
    gauges take MAX (summing per-machine durations would fabricate a
    value no machine ever reported; the worst straggler is the honest
    scalar), histograms merge le-wise. Ranking is by counter/gauge value
    or histogram count — "traffic", so the named survivors are the ones
    an operator would ask about."""
    if MACHINE_LABEL not in metric.labelnames:
        return collected
    cap = machine_cardinality_cap()
    if cap <= 0:
        return collected
    idx = metric.labelnames.index(MACHINE_LABEL)
    is_hist = isinstance(metric, Histogram)

    def weight(data: Any) -> float:
        return float(data["count"]) if is_hist else float(data)

    totals: Dict[str, float] = {}
    for key, data in collected.items():
        totals[key[idx]] = totals.get(key[idx], 0.0) + weight(data)
    if len(totals) <= cap:
        return collected
    keep: Optional[set] = None
    if _traffic_topk_provider is not None:
        try:
            nominated = _traffic_topk_provider(cap)
        except Exception:  # lint: allow-swallow(a broken traffic sketch must not break metric rendering; the recount below is the documented fallback)
            nominated = None
        if nominated:
            # the sketch ranks by TOTAL traffic across all families;
            # only machines present in THIS family's series can be kept,
            # and any remaining slots fall back to the per-family
            # recount so the cap is always filled
            keep = set(nominated) & set(totals)
            if len(keep) > cap:
                keep = set(
                    sorted(keep, key=lambda m: (-totals[m], m))[:cap]
                )
            elif len(keep) < cap:
                for m in sorted(totals, key=lambda m: (-totals[m], m)):
                    if len(keep) >= cap:
                        break
                    keep.add(m)
    if keep is None:
        keep = set(sorted(totals, key=lambda m: (-totals[m], m))[:cap])
    # "other" is a RESERVED label value once collapse is in play: a real
    # machine named "other" kept verbatim would collide with the
    # synthetic aggregate (counter sums merging into its kept entry,
    # histogram merges mutating its un-copied collect() data) — fold it
    # into the aggregate instead, where its traffic is at least honest
    keep.discard(MACHINE_OTHER)
    out: Dict[Tuple[str, ...], Any] = {}
    for key, data in collected.items():
        if key[idx] in keep:
            out[key] = data
            continue
        okey = key[:idx] + (MACHINE_OTHER,) + key[idx + 1:]
        current = out.get(okey)
        if current is None:
            if is_hist:
                data = {
                    "buckets": list(data["buckets"]),
                    "sum": data["sum"],
                    "count": data["count"],
                    "samples": list(data["samples"]),
                    "exemplars": dict(data.get("exemplars") or {}),
                }
            out[okey] = data
        elif is_hist:
            _merge_histogram_data(current, data)
        elif isinstance(metric, Counter):
            out[okey] = current + data
        else:
            out[okey] = max(current, data)
    return out


_get_trace_id = None


def _current_trace_id() -> str:
    # lazy-bound import: tracing lazily imports this module inside
    # span(), so a top-level import here would be circular; resolved
    # once, then one contextvar read per call (this sits on the
    # histogram observe hot path)
    global _get_trace_id
    if _get_trace_id is None:
        from .tracing import get_trace_id

        _get_trace_id = get_trace_id
    return _get_trace_id()

# latency-oriented default buckets (seconds): sub-ms device dispatches up
# through multi-second compiles land in distinct buckets
DEFAULT_BUCKETS = (
    0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
    0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, INF,
)


def _label_key(labelnames: Sequence[str], values: Sequence[str]) -> str:
    """Canonical series key, rendered prometheus-style so the JSON snapshot
    and the text exposition agree on identity: ``a="x",b="y"`` ('' when
    unlabeled)."""
    return ",".join(f'{n}="{v}"' for n, v in zip(labelnames, values))


class _Metric:
    kind = "untyped"

    def __init__(self, name: str, help: str = "", labelnames: Sequence[str] = ()):
        self.name = name
        self.help = help
        self.labelnames = tuple(labelnames)
        self._lock = threading.Lock()

    def _check_values(self, values: Tuple[str, ...]) -> Tuple[str, ...]:
        if len(values) != len(self.labelnames):
            raise ValueError(
                f"{self.name} takes {len(self.labelnames)} label value(s) "
                f"{self.labelnames}, got {len(values)}"
            )
        return tuple(str(v) for v in values)


class Counter(_Metric):
    """Monotonically increasing float per label set."""

    kind = "counter"

    def __init__(self, name, help="", labelnames=()):
        super().__init__(name, help, labelnames)
        self._values: Dict[Tuple[str, ...], float] = {}

    def labels(self, *values: str) -> "_BoundCounter":
        return _BoundCounter(self, self._check_values(values))

    def inc(self, amount: float = 1.0) -> None:
        self._inc((), amount)

    def _inc(self, values: Tuple[str, ...], amount: float) -> None:
        if amount < 0:
            raise ValueError(f"counter {self.name} cannot decrease")
        with self._lock:
            self._values[values] = self._values.get(values, 0.0) + amount

    def collect(self) -> Dict[Tuple[str, ...], float]:
        with self._lock:
            return dict(self._values)


class _BoundCounter:
    __slots__ = ("_metric", "_values")

    def __init__(self, metric: Counter, values: Tuple[str, ...]):
        self._metric = metric
        self._values = values

    def inc(self, amount: float = 1.0) -> None:
        self._metric._inc(self._values, amount)


class Gauge(_Metric):
    """Last-written float per label set (set/inc/dec)."""

    kind = "gauge"

    def __init__(self, name, help="", labelnames=()):
        super().__init__(name, help, labelnames)
        self._values: Dict[Tuple[str, ...], float] = {}

    def labels(self, *values: str) -> "_BoundGauge":
        return _BoundGauge(self, self._check_values(values))

    def set(self, value: float) -> None:
        self._set((), value)

    def inc(self, amount: float = 1.0) -> None:
        self._inc((), amount)

    def _set(self, values: Tuple[str, ...], value: float) -> None:
        with self._lock:
            self._values[values] = float(value)

    def _inc(self, values: Tuple[str, ...], amount: float) -> None:
        with self._lock:
            self._values[values] = self._values.get(values, 0.0) + amount

    def collect(self) -> Dict[Tuple[str, ...], float]:
        with self._lock:
            return dict(self._values)


class _BoundGauge:
    __slots__ = ("_metric", "_values")

    def __init__(self, metric: Gauge, values: Tuple[str, ...]):
        self._metric = metric
        self._values = values

    def set(self, value: float) -> None:
        self._metric._set(self._values, value)

    def inc(self, amount: float = 1.0) -> None:
        self._metric._inc(self._values, amount)

    def dec(self, amount: float = 1.0) -> None:
        self._metric._inc(self._values, -amount)


def _percentile(samples: Sequence[float], q: float) -> float:
    """Nearest-rank percentile over the bounded sample window — THE one
    rule (``Histogram.stats`` and the snapshot's collapsed series must
    agree)."""
    if not samples:
        return 0.0
    ordered = sorted(samples)
    n = len(ordered)
    return ordered[min(n - 1, int(round(q * (n - 1))))]


class _HistSeries:
    __slots__ = ("bucket_counts", "sum", "count", "samples", "exemplars")

    def __init__(self, n_buckets: int):
        self.bucket_counts = [0] * n_buckets  # per-bucket (non-cumulative)
        self.sum = 0.0
        self.count = 0
        self.samples: List[float] = []  # bounded rolling window
        # bucket index -> (trace_id, value, unix_ts): the most recent
        # traced observation landing in that bucket — the OpenMetrics
        # exemplar linking an aggregate bucket to a concrete request in
        # the flight recorder. Bounded by construction (<= n_buckets
        # entries per series); only observations made under a bound trace
        # id record one.
        self.exemplars: Dict[int, Tuple[str, float, float]] = {}


class Histogram(_Metric):
    """Cumulative-bucket histogram + bounded sample window per label set.

    Buckets serve the Prometheus exposition (exact, unbounded count);
    the ``keep``-bounded sample window serves the JSON p50/p99 view with
    ``_Latency``'s memory contract (a year-old server holds ``keep``
    floats per series, not per-request history).
    """

    kind = "histogram"

    def __init__(self, name, help="", labelnames=(),
                 buckets: Sequence[float] = DEFAULT_BUCKETS, keep: int = 1000):
        super().__init__(name, help, labelnames)
        bounds = sorted(float(b) for b in buckets)
        if not bounds or bounds[-1] != INF:
            bounds.append(INF)
        self.buckets = tuple(bounds)
        self.keep = keep
        self._series: Dict[Tuple[str, ...], _HistSeries] = {}

    def labels(self, *values: str) -> "_BoundHistogram":
        return _BoundHistogram(self, self._check_values(values))

    def observe(self, value: float) -> None:
        self._observe((), value)

    def _observe(self, values: Tuple[str, ...], value: float) -> None:
        value = float(value)
        i = bisect.bisect_left(self.buckets, value)
        # exemplar capture outside the lock: one contextvar read, and a
        # wall-clock read only when a trace is actually bound
        trace_id = _current_trace_id()
        exemplar = (trace_id, value, time.time()) if trace_id else None
        with self._lock:
            series = self._series.get(values)
            if series is None:
                series = self._series[values] = _HistSeries(len(self.buckets))
            series.bucket_counts[i] += 1
            series.sum += value
            series.count += 1
            series.samples.append(value)
            if len(series.samples) > self.keep:
                del series.samples[: -self.keep]
            if exemplar is not None:
                series.exemplars[i] = exemplar

    def collect(self) -> Dict[Tuple[str, ...], Dict[str, Any]]:
        """Snapshot copy: ``{labelvalues: {"buckets": [(le, cumulative)],
        "sum": s, "count": n, "samples": [...], "exemplars":
        {bucket_index: (trace_id, value, ts)}}}``."""
        with self._lock:
            copied = {
                values: (list(s.bucket_counts), s.sum, s.count,
                         list(s.samples), dict(s.exemplars))
                for values, s in self._series.items()
            }
        out: Dict[Tuple[str, ...], Dict[str, Any]] = {}
        for values, (counts, total, count, samples, exemplars) in copied.items():
            cumulative, acc = [], 0
            for le, n in zip(self.buckets, counts):
                acc += n
                cumulative.append((le, acc))
            out[values] = {
                "buckets": cumulative,
                "sum": total,
                "count": count,
                "samples": samples,
                "exemplars": exemplars,
            }
        return out

    def stats(self) -> Dict[Tuple[str, ...], Dict[str, float]]:
        """Percentile view per series (p50/p99/mean over the bounded sample
        window, count over the full lifetime) — the JSON ``/metrics``
        shape the retired ``_Latency.snapshot`` produced."""
        out = {}
        for values, data in self.collect().items():
            samples = data["samples"]
            out[values] = {
                "count": data["count"],
                "p50": _percentile(samples, 0.50),
                "p99": _percentile(samples, 0.99),
                "mean": sum(samples) / len(samples) if samples else 0.0,
            }
        return out


class _BoundHistogram:
    __slots__ = ("_metric", "_values")

    def __init__(self, metric: Histogram, values: Tuple[str, ...]):
        self._metric = metric
        self._values = values

    def observe(self, value: float) -> None:
        self._metric._observe(self._values, value)


class Registry:
    """Named metric collection with get-or-create registration."""

    def __init__(self) -> None:
        self._metrics: Dict[str, _Metric] = {}
        self._lock = threading.Lock()

    def _get_or_create(self, cls, name, help, labelnames, **kwargs) -> Any:
        labelnames = tuple(labelnames)
        with self._lock:
            existing = self._metrics.get(name)
            if existing is not None:
                if not isinstance(existing, cls) or existing.labelnames != labelnames:
                    raise ValueError(
                        f"metric {name!r} already registered as "
                        f"{existing.kind} with labels {existing.labelnames}; "
                        f"requested {cls.kind} with labels {labelnames}"
                    )
                if isinstance(existing, Histogram):
                    # same silent-incompatibility hazard as kind/labels:
                    # observations from a call site expecting different
                    # bucket bounds (or window size) would be binned wrong
                    requested = Histogram(name, help, labelnames, **kwargs)
                    if (existing.buckets != requested.buckets
                            or existing.keep != requested.keep):
                        raise ValueError(
                            f"histogram {name!r} already registered with "
                            f"buckets {existing.buckets} / keep "
                            f"{existing.keep}; requested "
                            f"{requested.buckets} / keep {requested.keep}"
                        )
                return existing
            metric = cls(name, help, labelnames, **kwargs)
            self._metrics[name] = metric
            return metric

    def counter(self, name: str, help: str = "",
                labels: Sequence[str] = ()) -> Counter:
        return self._get_or_create(Counter, name, help, labels)

    def gauge(self, name: str, help: str = "",
              labels: Sequence[str] = ()) -> Gauge:
        return self._get_or_create(Gauge, name, help, labels)

    def histogram(self, name: str, help: str = "",
                  labels: Sequence[str] = (),
                  buckets: Sequence[float] = DEFAULT_BUCKETS,
                  keep: int = 1000) -> Histogram:
        return self._get_or_create(
            Histogram, name, help, labels, buckets=buckets, keep=keep
        )

    def metrics(self) -> List[_Metric]:
        with self._lock:
            return sorted(self._metrics.values(), key=lambda m: m.name)

    def snapshot(self) -> Dict[str, Any]:
        """JSON-able view of every metric: counters/gauges as plain values,
        histograms as {count, sum, mean, p50, p99} per series (keyed
        prometheus-style: ``endpoint="healthz"``)."""
        out: Dict[str, Any] = {}
        for metric in self.metrics():
            if isinstance(metric, Histogram):
                collected = bound_machine_cardinality(
                    metric, metric.collect()
                )
                series = {
                    _label_key(metric.labelnames, values): {
                        "count": data["count"],
                        "sum": data["sum"],
                        "mean": (
                            sum(data["samples"]) / len(data["samples"])
                            if data["samples"] else 0.0
                        ),
                        "p50": _percentile(data["samples"], 0.50),
                        "p99": _percentile(data["samples"], 0.99),
                    }
                    for values, data in collected.items()
                }
            else:
                series = {
                    _label_key(metric.labelnames, values): value
                    for values, value in bound_machine_cardinality(
                        metric, metric.collect()
                    ).items()
                }
            out[metric.name] = {
                "kind": metric.kind,
                "help": metric.help,
                "series": series,
            }
        return out


# THE process-wide registry every layer records to. Tests exercising
# registry semantics construct their own Registry; everything shipping
# telemetry uses this one so one scrape sees the whole process.
REGISTRY = Registry()


def get_registry() -> Registry:
    return REGISTRY
