"""What the set-up readers share: the job's set-up on the program's own
timeline, from the command's entry to the first commit.

``gordo fleet-build`` begins the job's timeline at its entry (``fleet.command``
and, inside it, ``fleet.config`` and ``fleet.mesh``); the job adds
``fleet.preamble``, each bucket's ``fleet.plan``, and in the first slice
``fleet.program`` split into ``fleet.trace``, ``fleet.lower`` and
``fleet.compile`` (``cache``: hit, miss or off). The **set-up end** is the end
of the ``fleet.commit_loop`` of the job's first committed slice (bucket 0,
slice 0): the program's side of the commit whose store file time opens the
window. Times are placed against ``view["run"]["started"]``, the harness's
process start, on the same ``perf_counter`` clock as the timeline's start (the
job runs on a thread of the harness's process).

A timeline without ``fleet.command`` (a program from before these spans, or a
job begun by a library call) gives ``None``, and so does every reader. The
first call on a timeline writes one line to stderr: the set-up's spans in
order with their seconds, and their sum against the set-up end.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

from benchmarks.harness import log
from benchmarks.layer_metrics import fleet_spans

COMMAND = "fleet.command"
HOST = (COMMAND, "fleet.preamble", "fleet.plan")
TRACE_LOWER = ("fleet.trace", "fleet.lower")
LOAD = "fleet.compile"
# the first slice's own phases: every child span of the slice but the
# manifest's rewrite, which follows the commit loop
FIRST_SLICE = (
    "fleet.prefetch_wait", "fleet.program", "fleet.ingest", "fleet.execute",
    "fleet.result_fetch", "fleet.checkpoint_restore", "fleet.checkpoint_save",
    "fleet.checkpoint_wait", "fleet.commit_wait", "fleet.commit_loop",
)
_PRINTED: set = set()


def _union(intervals: List[Tuple[float, float]], lo: float, hi: float) -> float:
    """Seconds of [lo, hi] that the intervals cover, each counted once."""
    covered, edge = 0.0, lo
    for start, end in sorted(intervals):
        start, end = max(start, edge), min(end, hi)
        if end > start:
            covered += end - start
            edge = end
    return covered


def setup(view: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The set-up's readings, in seconds: ``before_command`` (``None`` where
    the view has no process start), ``host``, ``first_fetch_wait``,
    ``trace_lower``, ``load``, ``unattributed``, and ``measured``, the
    union of the measured spans between the command's start and the set-up
    end."""
    timeline = fleet_spans.build_timeline()
    if timeline is None:
        return None
    spans = sorted(timeline.spans, key=lambda s: s.start)
    command = next((s for s in spans if s.name == COMMAND), None)
    first = next(
        (
            s for s in spans
            if s.name == fleet_spans.SLICE and s.attrs.get("bucket") == 0
            and s.attrs.get("slice") == 0 and "error" not in s.attrs
        ),
        None,
    )
    if command is None or first is None:
        return None
    phases = [s for s in spans if s.parent == first.id and s.name in FIRST_SLICE]
    commit_loop = next((s for s in phases if s.name == "fleet.commit_loop"), None)
    if commit_loop is None:
        return None
    end = commit_loop.start + commit_loop.duration
    before_end = [s for s in spans if s.start + s.duration <= end]
    measured = [s for s in before_end if s.name in HOST] + phases
    covered = _union(
        [(s.start, s.start + s.duration) for s in measured], command.start, end
    )
    started = (view.get("run") or {}).get("started")
    out = {
        "before_command": (
            None if started is None
            else timeline.started + command.start - started
        ),
        "host": sum(s.duration for s in before_end if s.name in HOST),
        "first_fetch_wait": sum(
            s.duration for s in phases if s.name == "fleet.prefetch_wait"
        ),
        "trace_lower": sum(
            s.duration for s in before_end if s.name in TRACE_LOWER
        ),
        "load": sum(s.duration for s in before_end if s.name == LOAD),
        "measured": covered,
        "unattributed": end - command.start - covered,
    }
    key = (timeline.trace_id, timeline.started)
    if key not in _PRINTED:
        _PRINTED.add(key)
        _print(out, spans, command, first, end)
    return out


def _print(found, spans, command, first, end) -> None:
    """One stderr line: the set-up's spans in order, each with its seconds
    (a compile with its program and the cache's answer), and the sum of
    everything against the set-up end."""
    named = HOST + ("fleet.config", "fleet.mesh") + TRACE_LOWER + (LOAD,)
    shown, summed = [], 0.0
    for s in spans:
        if s.start + s.duration > end:
            continue
        own = s.parent == first.id and s.name in FIRST_SLICE
        if s.name in named or own:
            tags = ", ".join(
                f"{k}={s.attrs[k]}" for k in ("program", "cache") if k in s.attrs
            )
            shown.append(f"{s.name}{f'[{tags}]' if tags else ''} {s.duration:.3f}")
        if own or s.name in HOST:
            summed += s.duration
    before = found["before_command"] or 0.0
    log(
        "set-up spans (s): before the command "
        + ("(no process start)" if found["before_command"] is None else f"{before:.3f}")
        + "; " + "; ".join(shown)
        + f"; unattributed {found['unattributed']:.3f}. Before the command "
        f"+ the measured spans ({summed:.3f}, their union {found['measured']:.3f}) "
        f"+ unattributed = {before + summed + found['unattributed']:.3f} against "
        f"the set-up end at {before + end - command.start:.3f}"
    )


def reading(view: Dict[str, Any], key: str) -> Optional[float]:
    """One of :func:`setup`'s readings, or ``None``."""
    found = setup(view)
    return None if found is None else found[key]
