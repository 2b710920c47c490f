"""What the span readers share: the program's own timeline of the build job,
and its steady slices.

``build_fleet`` records one ``Timeline`` a job (``fleet.job`` down to one span
per phase of every slice, each measured by the thread that does the work) and
hands it to the program's flight recorder however the job ends. The job runs
on a thread of the harness's process, so a reader takes the newest timeline
of ``kind="fleet-build"`` from there. A program that records none (the commit
before the spans) gives ``None``, and so does every reader.

The **steady slices** are the committed slices of the first bucket after the
job's first and before its last: the first holds the compile or cache load,
and the last runs with no fetch for a next slice beside it. Where only two
committed, the second is taken. In a run of the cell that is the window's
slice.
"""

from __future__ import annotations

from typing import Dict, List, Optional

SLICE = "fleet.slice"


def build_timeline():
    try:
        from gordo_components_tpu.observability.flightrec import RECORDER

        return RECORDER.latest(kind="fleet-build")
    except (ImportError, AttributeError):  # a program without the spans
        return None


def steady_slices() -> Optional[List[Dict[str, object]]]:
    """For each steady slice: ``seconds`` (the span's duration), ``self_s``
    (its self time: what no child span covers), ``machines``, and ``phases``,
    the summed seconds of its child spans by name."""
    timeline = build_timeline()
    if timeline is None:
        return None
    spans = list(timeline.spans)
    committed = sorted(
        (
            s for s in spans
            if s.name == SLICE and s.attrs.get("bucket") == 0
            and "error" not in s.attrs
        ),
        key=lambda s: s.start,
    )
    steady = committed[1:-1] or committed[1:2]
    if not steady:
        return None
    self_seconds = timeline.self_seconds()
    out = []
    for parent in steady:
        phases: Dict[str, float] = {}
        for s in spans:
            if s.parent == parent.id:
                phases[s.name] = phases.get(s.name, 0.0) + s.duration
        out.append({
            "seconds": parent.duration,
            "self_s": self_seconds[parent.id],
            "machines": int(parent.attrs.get("machines", 0)),
            "phases": phases,
        })
    return out


def steady_mean(value) -> Optional[float]:
    """Mean of ``value(slice)`` over the steady slices; ``None`` where there
    is no timeline or no steady slice."""
    slices = steady_slices()
    if not slices:
        return None
    return sum(value(one) for one in slices) / len(slices)


def mean_phase_seconds(*names: str) -> Optional[float]:
    """Mean over the steady slices of the seconds spent in the child spans
    named."""
    return steady_mean(
        lambda one: sum(one["phases"].get(name, 0.0) for name in names)
    )
