"""Flight recorder: an always-on bounded buffer of completed request
timelines.

A fleet build's one timeline a job (``meta`` ``kind="fleet-build"``,
thousands of spans for a tall fleet, a few hundred bytes each) is
recorded here too, however the job ends (:func:`build_timeline`);
:meth:`FlightRecorder.latest` finds it. A build process records one; a
process that both serves and builds keeps a build in the ring until 256
later timelines push it out, and in the slow reservoir for good, which a
build's length earns it.

Post-hoc diagnosability is the point: when an operator asks "why did
trace 3f2a... take 900 ms at 04:12", the histograms have already averaged
the answer away. The recorder keeps (1) a ring of the last ``keep``
completed timelines, (2) a reservoir of the ``slow_keep`` SLOWEST
requests seen since boot, and (3) a ring of the last ``error_keep``
errored/shed requests — so a burst of fast healthy traffic can never
flush the one pathological trace you care about out of memory.

Memory contract: everything is bounded. A timeline is a few hundred
bytes (spans are ``__slots__`` objects); at the defaults (256 + 32 + 64
timelines) the recorder holds well under a megabyte regardless of
uptime. Recording is one lock + deque append + (rarely) an O(slow_keep)
insertion — measured within noise of a disabled recorder at saturation
(``tools/perf_smoke.py`` gates this).

``GORDO_FLIGHTREC=0`` disables recording (the perf-comparison mode and
the escape hatch); ``GORDO_FLIGHTREC_KEEP`` / ``_SLOW_KEEP`` /
``_ERROR_KEEP`` size the buffers.
"""

from __future__ import annotations

import contextlib
import json
import logging
import os
import threading
from collections import deque
from typing import Any, Dict, Iterator, List, Optional

from . import spans, tracing
from .spans import Timeline

logger = logging.getLogger(__name__)
BUILD_KIND = "fleet-build"
TIMELINE_FILE = "fleet_build_timeline.json"


def _env_int(name: str, default: int) -> int:
    try:
        return max(1, int(os.environ.get(name, default)))
    except (TypeError, ValueError):
        return default


class FlightRecorder:
    def __init__(
        self,
        keep: Optional[int] = None,
        slow_keep: Optional[int] = None,
        error_keep: Optional[int] = None,
        enabled: Optional[bool] = None,
    ):
        self.keep = keep if keep is not None else _env_int(
            "GORDO_FLIGHTREC_KEEP", 256
        )
        self.slow_keep = slow_keep if slow_keep is not None else _env_int(
            "GORDO_FLIGHTREC_SLOW_KEEP", 32
        )
        self.error_keep = error_keep if error_keep is not None else _env_int(
            "GORDO_FLIGHTREC_ERROR_KEEP", 64
        )
        self._enabled = (
            enabled
            if enabled is not None
            else os.environ.get("GORDO_FLIGHTREC", "1") != "0"
        )
        self._lock = threading.Lock()
        self._ring: "deque[Timeline]" = deque(maxlen=self.keep)
        # slowest-since-boot reservoir, kept sorted ascending by duration
        # (insertion is bisect-free: slow_keep is tiny)
        self._slow: List[Timeline] = []
        self._errors: "deque[Timeline]" = deque(maxlen=self.error_keep)
        self.recorded = 0

    # -- enablement ----------------------------------------------------------
    @property
    def enabled(self) -> bool:
        return self._enabled

    def set_enabled(self, enabled: bool) -> None:
        """Runtime toggle (perf comparisons, tests). Does not clear."""
        self._enabled = bool(enabled)

    # -- recording -----------------------------------------------------------
    def record(self, timeline: Timeline) -> None:
        if not self._enabled:
            return
        if timeline.finished is None:
            timeline.finish()
        duration = timeline.duration
        with self._lock:
            self.recorded += 1
            self._ring.append(timeline)
            if timeline.error:
                self._errors.append(timeline)
            if len(self._slow) < self.slow_keep:
                self._slow.append(timeline)
                self._slow.sort(key=lambda t: t.duration)
            elif self._slow and duration > self._slow[0].duration:
                self._slow[0] = timeline
                self._slow.sort(key=lambda t: t.duration)

    # -- views ---------------------------------------------------------------
    def _all(self) -> List[Timeline]:
        """Ring + reservoirs, deduped by identity, newest ring entries
        first (callers hold no lock; the copies are taken under it)."""
        with self._lock:
            ring = list(self._ring)
            slow = list(self._slow)
            errors = list(self._errors)
        seen: set = set()
        out: List[Timeline] = []
        for timeline in reversed(ring):
            if id(timeline) not in seen:
                seen.add(id(timeline))
                out.append(timeline)
        for timeline in sorted(slow, key=lambda t: -t.duration) + list(errors):
            if id(timeline) not in seen:
                seen.add(id(timeline))
                out.append(timeline)
        return out

    def get(self, trace_id: str) -> Optional[Timeline]:
        for timeline in self._all():
            if timeline.trace_id == trace_id:
                return timeline
        return None

    def latest(self, **meta: Any) -> Optional[Timeline]:
        """The newest kept timeline whose ``meta`` holds every given
        pair — ``latest(kind="fleet-build")`` is the last build job of
        this process, whatever way it ended."""
        for timeline in self._all():
            if all(timeline.meta.get(k) == v for k, v in meta.items()):
                return timeline
        return None

    def slowest(self) -> Optional[Timeline]:
        with self._lock:
            return self._slow[-1] if self._slow else None

    def summaries(self, limit: int = 50) -> Dict[str, Any]:
        """The /debug/requests body: recent rows, the slow reservoir, and
        recent errors — each a :meth:`Timeline.summary` dict."""
        with self._lock:
            ring = list(self._ring)
            slow = list(self._slow)
            errors = list(self._errors)
            recorded = self.recorded
        slowest = slow[-1] if slow else None
        limit = max(0, limit)
        # limit bounds ALL three views: a watchman polling ?limit=1 per
        # status tick must not make the server serialize the full slow +
        # error reservoirs (~100 summary builds) just to read "slowest"
        return {
            "enabled": self._enabled,
            "recorded": recorded,
            "kept": len(ring),
            "slowest": slowest.summary() if slowest is not None else None,
            "requests": [
                t.summary() for t in list(reversed(ring))[:limit]
            ],
            "slow": [t.summary() for t in sorted(
                slow, key=lambda t: -t.duration
            )[:limit]],
            "errors": [t.summary() for t in list(reversed(errors))[:limit]],
        }

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self._slow.clear()
            self._errors.clear()
            self.recorded = 0


# THE process-wide recorder (like observability.REGISTRY): the server
# records into it, /debug/requests reads from it, tests may clear() it.
RECORDER = FlightRecorder()


@contextlib.contextmanager
def build_timeline(
    trace_dir: Optional[str] = None, **meta: Any
) -> Iterator[Timeline]:
    """The fleet build's one timeline, bound for the block.

    The first block that asks owns it: ``gordo fleet-build`` at its entry,
    so that the command's own set-up is on it, or ``build_fleet`` where a
    library calls it. The owner begins it under the job's trace id with
    annotating stages (each is also a ``jax.profiler.TraceAnnotation`` of
    the span's name) and, however the block ends, finishes it, records it
    here, writes it under ``trace_dir`` as :data:`TIMELINE_FILE` (Chrome
    trace events) and logs its phases. A block opened inside the owner's
    records into the same timeline, adds ``meta`` to it and leaves its
    ending to the owner."""
    bound = spans.current_timeline()
    if bound is not None and bound.meta.get("kind") == BUILD_KIND:
        bound.meta.update((k, v) for k, v in meta.items() if v is not None)
        yield bound
        return
    with tracing.trace(tracing.current_or_new()) as trace_id:
        timeline, token = spans.begin(
            trace_id, kind=BUILD_KIND, service="gordo fleet-build", **meta
        )
        timeline.annotate = True
        error = ""
        try:
            yield timeline
        except BaseException as exc:  # the benchmark ends a job with one
            error = f"{type(exc).__name__}: {exc}"
            raise
        finally:
            spans.end(token)
            timeline.finish(status="error" if error else "ok", error=error)
            # kept in memory, written at the end, readable after any ending
            RECORDER.record(timeline)
            if trace_dir:
                _write_timeline(timeline, trace_dir)
            logger.info(
                "Fleet build %s: %s machines in %.1fs; phases: %s",
                timeline.status,
                timeline.meta.get("machines", "no"),
                timeline.duration,
                {
                    name: round(seconds, 3)
                    for name, seconds in sorted(
                        timeline.stage_seconds().items()
                    )
                },
            )


def _write_timeline(timeline: Timeline, trace_dir: str) -> None:
    """Best effort: the timeline is a diagnostic, and this runs while a
    job's own exception may be propagating."""
    path = os.path.join(trace_dir, TIMELINE_FILE)
    try:
        os.makedirs(trace_dir, exist_ok=True)
        with open(path, "w") as fh:
            json.dump(timeline.to_chrome_trace(), fh, default=str)
        logger.info("Fleet build timeline written to %s", path)
    except OSError:
        logger.warning("Could not write %s", path, exc_info=True)
