"""Per-request span timelines: stage-level latency attribution.

PR 1's tracing gives every request ONE id; PR 4's pipelined data plane
split serving into overlapping stages (admission gate, bucket queue,
leader dispatch, device execution, collector fetch, wire encode) that run
on THREE different threads — so when a request's p99 moves, the flat
histograms can say *that* it was slow but not *where*. A
:class:`Timeline` is the per-request answer: named stage spans with
start/duration, point events (deadline expiry, breaker rejection, shed),
and a Chrome trace-event export that loads straight into Perfetto.

Context model: the handler thread binds its timeline to a contextvar
(:func:`begin`), so same-thread code records via :func:`stage` without
plumbing. The PR 4 collector threads and the client's asyncio fan-out do
NOT inherit that contextvar — work crossing those seams carries an
explicit :class:`SpanContext` (:func:`capture` at enqueue,
:func:`bind` / :func:`record_into` on the far side), which also restores
the trace id for log records emitted over there (the PR 4 regression:
collector-side log lines carried no ``X-Gordo-Trace-Id``).

Causality: every span carries an ``id`` and the ``parent`` that caused
it — the stage that was open in the same bound context when it began
(:func:`stage` keeps the open stage in a contextvar; :func:`capture`
carries it across a seam, so :func:`bind` / :func:`record_into` on the
far side parent under the stage that enqueued the work).
:meth:`Timeline.self_seconds` is the view built on it: a span's duration
minus the part of its interval its children cover.

A fleet build is one timeline too (``parallel/build_fleet.py``; its
``trace_id`` is the job's). Such a timeline asks for ``annotate``: each
of its stages is also a ``jax.profiler.TraceAnnotation`` of the same
name, so in any profiler session the span lands on the host plane of the
xplane that holds the device's ops — one clock for both. Request
timelines do not annotate.

Overhead contract: a stage is one ``perf_counter`` pair, one histogram
observe (``gordo_stage_seconds{stage}``), and — when a timeline is bound
— two contextvar writes and one lock-guarded list append. No timeline
bound (recorder disabled) ⇒ only the histogram remains.
"""

from __future__ import annotations

import contextlib
import itertools
import threading
import time
from contextvars import ContextVar
from typing import Any, Dict, Iterator, List, NamedTuple, Optional

from . import tracing
from .registry import REGISTRY

# the canonical stage names (docs/ARCHITECTURE.md §13); stage() accepts
# any name — this tuple is the shared vocabulary, not an enum
STAGES = (
    "route",           # router: placement decision + worker forward
                       # (re-route walks included)
    "admission",       # admission-gate wait (server)
    "queue_wait",      # bucket pending queue until a leader dispatches it
    "megabatch",       # leader's bounded fill window collecting concurrent
                       # submits across machines into one fused dispatch
    "dispatch",        # pre-dispatch seams + async enqueue (leader thread)
    "device_execute",  # enqueue -> fetch-begin (device compute overlap)
    "fetch",           # jax.device_get: remaining compute + D2H copy
    "score",           # whole engine/host scoring call (parent span)
    "encode",          # response wire encoding (npz / fast JSON)
    "chunk_fetch",     # client: one chunk's HTTP round-trip
    "decode",          # client: response body -> arrays
)

_M_STAGE_SECONDS = REGISTRY.histogram(
    "gordo_stage_seconds",
    "Duration of named request stages (the aggregate twin of the "
    "per-request timelines in /debug/requests)",
    labels=("stage",),
)
# bound-series cache: stage() / record_into() run several times per
# request, and labels() re-validates + re-tuples per call otherwise
_BOUND_STAGE: Dict[str, Any] = {}


def _stage_series(name: str):
    bound = _BOUND_STAGE.get(name)
    if bound is None:
        bound = _BOUND_STAGE[name] = _M_STAGE_SECONDS.labels(name)
    return bound

_timeline: ContextVar[Optional["Timeline"]] = ContextVar(
    "gordo_timeline", default=None
)
# id of the stage open in this context (0 = none): the parent of the
# next span begun here
_open_stage: ContextVar[int] = ContextVar("gordo_open_stage", default=0)


class Span:
    __slots__ = ("name", "start", "duration", "thread", "process", "attrs",
                 "id", "parent")

    def __init__(self, name: str, start: float, duration: float,
                 thread: str, attrs: Dict[str, Any], process: str = "",
                 id: int = 0, parent: int = 0):
        self.id = id  # unique within its timeline, from 1
        self.parent = parent  # id of the span that caused it; 0 = a root
        self.name = name
        self.start = start  # seconds since timeline start
        self.duration = duration
        self.thread = thread
        # "" = this process; anything else is a STITCHED lane — a remote
        # process's span merged in by the router (observability.stitch)
        self.process = process
        self.attrs = attrs


class Timeline:
    """One request's stage spans + point events.

    Thread-safe appends: the handler thread, the bucket leader (which may
    be ANOTHER request's handler draining the queue), and the collector
    thread all record into one request's timeline concurrently.
    """

    __slots__ = ("trace_id", "meta", "started_wall", "started", "finished",
                 "status", "error", "spans", "events", "annotate", "_ids",
                 "_lock")

    def __init__(self, trace_id: str, **meta: Any):
        self.trace_id = trace_id
        # True: stage() also opens a jax.profiler.TraceAnnotation of the
        # span's name (the fleet build's timeline sets it)
        self.annotate = False
        self._ids = itertools.count(1)
        self.meta = {k: v for k, v in meta.items() if v is not None}
        self.started_wall = time.time()
        self.started = time.perf_counter()
        self.finished: Optional[float] = None  # perf_counter at finish
        self.status = ""   # e.g. HTTP status, "ok", "error"
        self.error = ""
        self.spans: List[Span] = []
        self.events: List[Dict[str, Any]] = []
        self._lock = threading.Lock()

    # -- recording (any thread) ----------------------------------------------
    def next_id(self) -> int:
        return next(self._ids)

    def add_span(self, name: str, started: float, duration: float,
                 span_id: int = 0, parent: int = 0, **attrs: Any) -> None:
        """``started`` is an absolute ``time.perf_counter()`` reading (the
        recorder converts to timeline-relative) so cross-thread recorders
        never need the timeline's epoch. ``span_id`` is one
        :meth:`next_id` handed out when the span began (children name it
        as their ``parent`` before it is appended); 0 takes a fresh one."""
        if attrs:
            attrs = {k: v for k, v in attrs.items() if v not in (None, "")}
        span = Span(
            name,
            max(0.0, started - self.started),
            max(0.0, duration),
            threading.current_thread().name,
            attrs,
            id=span_id or next(self._ids),
            parent=parent,
        )
        with self._lock:
            self.spans.append(span)

    def add_span_at(self, name: str, rel_start: float, duration: float,
                    thread: str = "", process: str = "",
                    **attrs: Any) -> None:
        """Append a span at an already-TIMELINE-RELATIVE start — how a
        stitched remote process's spans (whose perf_counter epoch means
        nothing here) land in this timeline after clock alignment."""
        if attrs:
            attrs = {k: v for k, v in attrs.items() if v not in (None, "")}
        span = Span(
            name, max(0.0, rel_start), max(0.0, duration),
            thread or threading.current_thread().name, attrs,
            process=process, id=next(self._ids),
        )
        with self._lock:
            self.spans.append(span)

    def add_event(self, name: str, **attrs: Any) -> None:
        event = {
            "t": max(0.0, time.perf_counter() - self.started),
            "name": name,
            **{k: v for k, v in attrs.items() if v not in (None, "")},
        }
        with self._lock:
            self.events.append(event)

    def add_event_at(self, name: str, rel_t: float, process: str = "",
                     **attrs: Any) -> None:
        """Timeline-relative point event (the stitching twin of
        :meth:`add_span_at`)."""
        event = {
            "t": max(0.0, rel_t),
            "name": name,
            **({"process": process} if process else {}),
            **{k: v for k, v in attrs.items() if v not in (None, "")},
        }
        with self._lock:
            self.events.append(event)

    def finish(self, status: str = "", error: str = "") -> None:
        self.finished = time.perf_counter()
        if status:
            self.status = str(status)
        if error:
            self.error = str(error)

    # -- views ---------------------------------------------------------------
    @property
    def duration(self) -> float:
        end = self.finished if self.finished is not None else time.perf_counter()
        return max(0.0, end - self.started)

    def stage_seconds(self) -> Dict[str, float]:
        """Total seconds per stage name (repeated spans — chunked
        backfills, retries — sum)."""
        with self._lock:
            spans = list(self.spans)
        out: Dict[str, float] = {}
        for span in spans:
            out[span.name] = out.get(span.name, 0.0) + span.duration
        return out

    def self_seconds(self) -> Dict[int, float]:
        """Self time per span id: the span's duration minus the part of
        its interval that its children (spans naming it as ``parent``,
        on any thread) cover. What is left is time no finer span
        accounts for."""
        with self._lock:
            spans = list(self.spans)
        children: Dict[int, List[Span]] = {}
        for span in spans:
            if span.parent:
                children.setdefault(span.parent, []).append(span)
        out: Dict[int, float] = {}
        for span in spans:
            lo, hi = span.start, span.start + span.duration
            covered, edge = 0.0, lo
            for child in sorted(
                children.get(span.id, ()), key=lambda c: c.start
            ):
                start = max(child.start, edge)
                end = min(child.start + child.duration, hi)
                if end > start:
                    covered += end - start
                    edge = end
            out[span.id] = max(0.0, span.duration - covered)
        return out

    # parent stages CONTAIN other stages (score wraps the whole engine
    # call; route wraps every stitched worker stage), so counting them in
    # dominance would always blame the parent; they still appear in
    # stage_seconds for the full picture
    _PARENT_STAGES = frozenset({"score", "route"})

    def dominant_stage(self) -> str:
        stages = self.stage_seconds()
        leaves = {
            name: seconds for name, seconds in stages.items()
            if name not in self._PARENT_STAGES
        }
        # host-path machines record only the flat score span — fall back
        # to the parents rather than answering nothing
        stages = leaves or stages
        if not stages:
            return ""
        return max(stages.items(), key=lambda kv: kv[1])[0]

    def summary(self) -> Dict[str, Any]:
        """The /debug/requests listing row: everything an operator needs
        to pick which trace to open."""
        return {
            "trace_id": self.trace_id,
            "started": self.started_wall,
            "duration_ms": round(self.duration * 1000, 3),
            "status": self.status,
            "error": self.error,
            "dominant_stage": self.dominant_stage(),
            "stages_ms": {
                name: round(seconds * 1000, 3)
                for name, seconds in sorted(self.stage_seconds().items())
            },
            **self.meta,
        }

    def to_dict(self) -> Dict[str, Any]:
        with self._lock:
            spans = list(self.spans)
            events = list(self.events)
        return {
            "trace_id": self.trace_id,
            "meta": dict(self.meta),
            "started": self.started_wall,
            "duration_ms": round(self.duration * 1000, 3),
            "status": self.status,
            "error": self.error,
            "dominant_stage": self.dominant_stage(),
            "stages_ms": {
                name: round(seconds * 1000, 3)
                for name, seconds in sorted(self.stage_seconds().items())
            },
            "spans": [
                {
                    "name": span.name,
                    "start_ms": round(span.start * 1000, 3),
                    "duration_ms": round(span.duration * 1000, 3),
                    "thread": span.thread,
                    "id": span.id,
                    **({"parent": span.parent} if span.parent else {}),
                    **({"process": span.process} if span.process else {}),
                    **span.attrs,
                }
                for span in spans
            ],
            "events": events,
        }

    def to_chrome_trace(self) -> Dict[str, Any]:
        """Chrome trace-event JSON (the ``chrome://tracing`` / Perfetto
        format): complete (``ph: "X"``) events in microseconds, one track
        per recording thread, instant (``ph: "i"``) events for the point
        events. STITCHED spans (``Span.process`` set — another process's
        timeline merged in by the router) render as their own process
        lane (pid 2+), so one export shows router and worker side by
        side. ``json.dumps`` of the result is directly loadable."""
        base_us = self.started_wall * 1e6
        with self._lock:
            spans = list(self.spans)
            events = list(self.events)
        # process lanes: "" (this process) is always pid 1; every
        # distinct stitched process label gets its own pid after it
        remote = sorted(
            {span.process for span in spans if span.process}
            | {e["process"] for e in events if e.get("process")}
        )
        pids = {"": 1, **{name: i + 2 for i, name in enumerate(remote)}}
        local_label = str(
            self.meta.get("service") or f"gordo trace {self.trace_id}"
        )
        trace_events: List[Dict[str, Any]] = []
        for process, pid in sorted(pids.items(), key=lambda kv: kv[1]):
            trace_events.append({
                "ph": "M",
                "pid": pid,
                "name": "process_name",
                "args": {"name": process or local_label},
            })
        threads = sorted({(span.process, span.thread) for span in spans})
        tids = {key: i + 1 for i, key in enumerate(threads)}
        for (process, thread), tid in sorted(
            tids.items(), key=lambda kv: kv[1]
        ):
            trace_events.append({
                "ph": "M", "pid": pids[process], "tid": tid,
                "name": "thread_name", "args": {"name": thread},
            })
        for span in spans:
            trace_events.append({
                "ph": "X",
                "pid": pids[span.process],
                "tid": tids.get((span.process, span.thread), 0),
                "name": span.name,
                "cat": "stage",
                "ts": base_us + span.start * 1e6,
                "dur": span.duration * 1e6,
                "args": {
                    "id": span.id,
                    **({"parent": span.parent} if span.parent else {}),
                    **span.attrs,
                },
            })
        for event in events:
            args = {
                k: v for k, v in event.items()
                if k not in ("t", "name", "process")
            }
            trace_events.append({
                "ph": "i",
                "pid": pids.get(event.get("process", ""), 1),
                "tid": 0,
                "name": event["name"],
                "cat": "event",
                "ts": base_us + event["t"] * 1e6,
                "s": "p",  # process-scoped instant
                "args": args,
            })
        return {
            "traceEvents": trace_events,
            "displayTimeUnit": "ms",
            "otherData": {
                "trace_id": self.trace_id,
                "status": self.status,
                **{str(k): str(v) for k, v in self.meta.items()},
            },
        }


# -- context plumbing --------------------------------------------------------


class SpanContext(NamedTuple):
    """Explicit capture of (trace id, timeline, open stage) for crossing
    the seams contextvars do not survive: the engine's collector-thread
    handoff, the client's cross-thread asyncio submission, the fleet
    build's prefetch worker and fetch pool. ``parent`` is the id of the
    stage that was open at capture: what the far side's spans hang
    under."""

    trace_id: str
    timeline: Optional[Timeline]
    parent: int = 0


EMPTY_CONTEXT = SpanContext("", None)


def capture() -> SpanContext:
    return SpanContext(
        tracing.get_trace_id(), _timeline.get(), _open_stage.get()
    )


@contextlib.contextmanager
def bind(ctx: SpanContext) -> Iterator[None]:
    """Re-bind a captured context on another thread/task: log records get
    the trace id back, and :func:`stage`/:func:`event` land in the right
    timeline. Safe with ``EMPTY_CONTEXT`` (binds nothing extra)."""
    trace_token = tracing.set_trace_id(ctx.trace_id) if ctx.trace_id else None
    timeline_token = _timeline.set(ctx.timeline)
    open_token = _open_stage.set(ctx.parent)
    try:
        yield
    finally:
        _open_stage.reset(open_token)
        _timeline.reset(timeline_token)
        if trace_token is not None:
            tracing.reset_trace_id(trace_token)


def current_timeline() -> Optional[Timeline]:
    return _timeline.get()


def begin(trace_id: str, **meta: Any):
    """Start a timeline and bind it to the current context. Returns
    ``(timeline, token)``; pass the token to :func:`end`."""
    timeline = Timeline(trace_id, **meta)
    return timeline, _timeline.set(timeline)


def end(token) -> None:
    """Unbind (the caller finishes/records the timeline itself — status
    is only known at the HTTP boundary)."""
    _timeline.reset(token)


def _annotation(name: str):
    # jax is imported only where a timeline asks for annotations
    from jax.profiler import TraceAnnotation

    return TraceAnnotation(name)


@contextlib.contextmanager
def stage(name: str, **attrs: Any) -> Iterator[Dict[str, Any]]:
    """Record a named stage: always observes ``gordo_stage_seconds``,
    and appends a span when a timeline is bound. Yields the span's
    attrs, so the block can add what it only knows at its end; a stage
    left by an exception says so (``error``: the exception's type).
    Stages opened inside it, or on a far side bound to a context
    captured inside it, name it as their parent."""
    timeline = _timeline.get()
    if timeline is None:
        started = time.perf_counter()
        try:
            yield attrs
        finally:
            _stage_series(name).observe(time.perf_counter() - started)
        return
    span_id = timeline.next_id()
    parent = _open_stage.get()
    token = _open_stage.set(span_id)
    # the annotation's own cost is inside the span it marks (the first
    # imports jax.profiler: a command's first stage holds that import)
    started = time.perf_counter()
    annotation = _annotation(name) if timeline.annotate else None
    if annotation is not None:
        annotation.__enter__()
    try:
        yield attrs
    except BaseException as exc:
        attrs.setdefault("error", type(exc).__name__)
        raise
    finally:
        duration = time.perf_counter() - started
        if annotation is not None:
            annotation.__exit__(None, None, None)
        _open_stage.reset(token)
        _stage_series(name).observe(duration)
        timeline.add_span(
            name, started, duration, span_id=span_id, parent=parent, **attrs
        )


def event(name: str, **attrs: Any) -> None:
    """Point event on the bound timeline (no-op without one)."""
    timeline = _timeline.get()
    if timeline is not None:
        timeline.add_event(name, **attrs)


def record_into(ctx: SpanContext, name: str, started: float,
                duration: float, **attrs: Any) -> None:
    """Record a span into a CAPTURED context's timeline from any thread —
    how the bucket leader and collector attribute dispatch/device/fetch
    time to each batched item's own request, under the stage that was
    open when the context was captured. Observes the aggregate
    histogram exactly once per call, like :func:`stage`."""
    _stage_series(name).observe(max(0.0, duration))
    if ctx.timeline is not None:
        ctx.timeline.add_span(
            name, started, duration, parent=ctx.parent, **attrs
        )


def event_into(ctx: SpanContext, name: str, **attrs: Any) -> None:
    if ctx.timeline is not None:
        ctx.timeline.add_event(name, **attrs)
