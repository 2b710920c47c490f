"""From a profiler trace (``*.xplane.pb``) to device numbers.

Reads the trace with ``jax.profiler.ProfileData`` alone. What it takes from
the program is the names the trace gives: XLA module names (``jit_<fn>``)
and XLA op names. Checked against a recorded trace in
``benchmarks/tests/test_trace_reduce.py``.

Planes: every plane whose name starts with ``/device:TPU:`` is a chip. On a
chip's plane the line ``XLA Modules`` holds one event per executed program
and ``XLA Ops`` one per executed operation; times are nanoseconds on the
trace's own clock. Host threads live on ``/host:CPU``; the harness's
``TraceAnnotation`` marks are found there by name.
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Tuple

DEVICE_PREFIX = "/device:TPU:"
MODULE_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"


def union_seconds(intervals: Iterable[Tuple[float, float]]) -> float:
    """Length of the union of ``(start, end)`` intervals."""
    total, edge = 0.0, None
    for start, end in sorted(intervals):
        if edge is None or start > edge:
            total += end - start
            edge = end
        elif end > edge:
            total += end - edge
            edge = end
    return total


def gaps(intervals: Iterable[Tuple[float, float]], lo: float, hi: float) -> List[Tuple[float, float]]:
    """The parts of ``[lo, hi]`` that no interval covers."""
    out, edge = [], lo
    for start, end in sorted(intervals):
        if start > edge:
            out.append((edge, min(start, hi)))
        edge = max(edge, end)
        if edge >= hi:
            break
    if edge < hi:
        out.append((edge, hi))
    return [(a, b) for a, b in out if b > a]


def short_op(name: str, width: int = 72) -> str:
    """The trace names an op by its whole HLO line (thousands of characters
    for a loop with many operands): keep the result's name and the start of
    its type, ``%while.68 = (s32[], f32[128,4,8], ...``, cut to ``width``."""
    return name if len(name) <= width else name[: width - 3] + "..."


def strip_module(name: str) -> str:
    """``jit_program(1234567)`` -> ``jit_program``."""
    return name.split("(", 1)[0]


def reduce_trace(path: str, marks_prefix: str = "bench:") -> Dict[str, object]:
    """All times in seconds on the trace's clock.

    ``devices``: per chip ``{"busy_s", "modules": {name: [(start, dur)]},
    "ops": {name: total_s}, "op_intervals": [(start, end)]}``.
    ``marks``: ``{name: [(start, dur)]}`` of host annotations whose name
    starts with ``marks_prefix``. ``span``: (first, last) device event edge.
    """
    from jax.profiler import ProfileData

    return reduce_data(ProfileData.from_file(path), marks_prefix)


def reduce_data(data, marks_prefix: str = "bench:") -> Dict[str, object]:
    """:func:`reduce_trace` of a trace already in memory (``ProfileData``)."""
    devices: Dict[str, Dict[str, object]] = {}
    marks: Dict[str, List[Tuple[float, float]]] = {}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            modules: Dict[str, List[Tuple[float, float]]] = {}
            ops: Dict[str, float] = {}
            op_intervals: List[Tuple[float, float]] = []
            for line in plane.lines:
                if line.name == MODULE_LINE:
                    for event in line.events:
                        modules.setdefault(strip_module(event.name), []).append(
                            (event.start_ns * 1e-9, event.duration_ns * 1e-9)
                        )
                elif line.name == OPS_LINE:
                    for event in line.events:
                        start = event.start_ns * 1e-9
                        dur = event.duration_ns * 1e-9
                        op = short_op(event.name)
                        ops[op] = ops.get(op, 0.0) + dur
                        op_intervals.append((start, start + dur))
            if not op_intervals:
                # a trace without the op line: programs stand for their ops
                op_intervals = [
                    (s, s + d) for runs in modules.values() for s, d in runs
                ]
            devices[plane.name] = {
                "modules": modules, "ops": ops, "op_intervals": op_intervals,
            }
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for event in line.events:
                    if event.name.startswith(marks_prefix):
                        marks.setdefault(event.name, []).append(
                            (event.start_ns * 1e-9, event.duration_ns * 1e-9)
                        )
    return {"devices": devices, "marks": marks}


def window_summary(reduced: Dict[str, object], lo: float, hi: float) -> Dict[str, object]:
    """Busy seconds (mean over chips), per-module whole runs and top ops
    inside ``[lo, hi]`` on the trace's clock."""
    devices = reduced["devices"]
    busy, per_module, ops_total = [], {}, {}
    all_gaps: List[Tuple[float, float]] = []
    for name, dev in devices.items():
        clipped = [
            (max(a, lo), min(b, hi)) for a, b in dev["op_intervals"]
            if b > lo and a < hi
        ]
        busy.append(union_seconds(clipped))
        all_gaps.extend(gaps(clipped, lo, hi))
        for module, runs in dev["modules"].items():
            whole = [d for s, d in runs if s >= lo and s + d <= hi]
            if whole:
                per_module.setdefault(module, []).extend(whole)
        for op, seconds in dev["ops"].items():
            ops_total[op] = ops_total.get(op, 0.0) + seconds
    n = max(len(devices), 1)
    return {
        "chips": len(devices),
        "window_s": hi - lo,
        "busy_s": sum(busy) / n if busy else 0.0,
        "modules": per_module,
        "top_ops": sorted(
            ((op, s / n) for op, s in ops_total.items()),
            key=lambda kv: -kv[1],
        )[:10],
        "gaps": sorted(all_gaps, key=lambda ab: ab[0] - ab[1]),
    }
