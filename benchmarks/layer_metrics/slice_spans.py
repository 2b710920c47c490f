"""What the readers of a slice's own spans share: the steady slices of the
build job's timeline (``fleet_spans``: the first bucket's committed slices
after the job's first and before its last; the second where only two
committed), each with its attributes and the spans under it.

A program that records no timeline, or whose spans lack an attribute a reader
asks for (the commit before the attribute), gives ``None`` and so does the
reader: the metric is then left out of the line.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

from benchmarks.layer_metrics import fleet_spans


def steady() -> Optional[List[Dict[str, Any]]]:
    """For each steady slice: its ``attrs`` and ``under``, every span below
    it (children and theirs) as ``(name, seconds, attrs)``."""
    timeline = fleet_spans.build_timeline()
    if timeline is None:
        return None
    spans = list(timeline.spans)
    committed = sorted(
        (
            s for s in spans
            if s.name == fleet_spans.SLICE and s.attrs.get("bucket") == 0
            and "error" not in s.attrs
        ),
        key=lambda s: s.start,
    )
    chosen = committed[1:-1] or committed[1:2]
    if not chosen:
        return None
    children: Dict[Any, list] = {}
    for s in spans:
        children.setdefault(s.parent, []).append(s)
    out = []
    for parent in chosen:
        under, queue = [], list(children.get(parent.id, ()))
        while queue:
            s = queue.pop()
            under.append((s.name, float(s.duration), dict(s.attrs)))
            queue.extend(children.get(s.id, ()))
        out.append({"attrs": dict(parent.attrs), "under": under})
    return out


def mb_per_s(span_name: str) -> Optional[float]:
    """Bytes over seconds of the spans called ``span_name`` under the steady
    slices that carry ``bytes``, in MB/s (1e6 bytes); ``None`` where none
    does."""
    slices = steady()
    if not slices:
        return None
    moved = [
        (attrs["bytes"], seconds)
        for one in slices for name, seconds, attrs in one["under"]
        if name == span_name and "bytes" in attrs and seconds > 0
    ]
    if not moved:
        return None
    return sum(b for b, _ in moved) / sum(s for _, s in moved) / 1e6
