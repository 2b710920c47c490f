"""From a profiler trace (``*.xplane.pb``) to device numbers.

Reads the trace with ``jax.profiler.ProfileData`` alone. What it takes from
the program is the names the trace gives: XLA module names (``jit_<fn>``)
and XLA op names. Checked against a recorded trace in
``benchmarks/tests/test_trace_reduce.py``.

Planes: every plane whose name starts with ``/device:TPU:`` is a chip. On a
chip's plane the line ``XLA Modules`` holds one event per executed program
and ``XLA Ops`` one per executed operation; times are nanoseconds on the
trace's own clock. Host threads live on ``/host:CPU``; the harness's
``TraceAnnotation`` marks (``bench:``) and the program's own spans
(``fleet.*``, ``observability/spans.py``) are found there by name.

The device numbers are those of a **stretch** of a whole number of commit
periods (``window_summary``): a program's device seconds are what its runs
occupy inside the stretch, each run cut at the stretch's edges, so they do
not depend on where inside a period the runs begin and end.
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Tuple

DEVICE_PREFIX = "/device:TPU:"
MODULE_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
# host annotations kept: the harness's own marks and the program's spans
SPANS = "fleet."
MARKS = ("bench:", SPANS)
# a trace is read only where it holds this share of the seconds the host
# waited for the train program: the worst sound reading is 97.6% (ledger, PR
# 33), a trace that ended early held 85% of its steps (my chip runs, PR 25)
LEAST_DEVICE_SHARE = 0.9
# idle gaps shorter than this lie between a program's ops and take no name
NAMED_GAP_S = 1e-3


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


def reduce_trace(path: str) -> Dict[str, object]:
    """All times in seconds on the trace's clock.

    ``devices``: per chip ``{"modules": {name: [(start, dur)]},
    "ops": {name: total_s}, "op_intervals": [(start, end)]}``.
    ``marks``: ``{name: [(start, dur)]}`` of the host annotations, of any
    thread, whose name starts with one of ``MARKS``.
    """
    from jax.profiler import ProfileData

    return reduce_data(ProfileData.from_file(path))


def reduce_data(data) -> Dict[str, object]:
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
                    if event.name.startswith(MARKS):
                        marks.setdefault(event.name, []).append(
                            (event.start_ns * 1e-9, event.duration_ns * 1e-9)
                        )
    return {"devices": devices, "marks": marks}


def window_summary(
    reduced: Dict[str, object], lo: float, hi: float, periods: int = 1,
) -> Dict[str, object]:
    """The stretch ``[lo, hi]`` on the trace's clock, which the caller lays
    over ``periods`` whole commit periods: busy seconds (the union of the op
    intervals, mean over chips), ``module_s`` (per program the seconds its
    runs occupy inside the stretch, a run cut at the stretch's edges, mean
    over chips), the idle gaps, and the trace's top ops. ``modules`` keeps
    the runs that lie whole inside, for the log line: no metric reads it."""
    devices = reduced["devices"]
    busy, whole_runs, module_s, ops_total = [], {}, {}, {}
    all_gaps: List[Tuple[float, float]] = []
    for name, dev in devices.items():
        clipped = [
            (max(a, lo), min(b, hi)) for a, b in dev["op_intervals"]
            if b > lo and a < hi
        ]
        busy.append(union_seconds(clipped))
        all_gaps.extend(gaps(clipped, lo, hi))
        for module, runs in dev["modules"].items():
            inside = [min(s + d, hi) - max(s, lo) for s, d in runs if s + d > lo and s < hi]
            if inside:
                module_s[module] = module_s.get(module, 0.0) + sum(inside)
            whole = [d for s, d in runs if s >= lo and s + d <= hi]
            if whole:
                whole_runs.setdefault(module, []).extend(whole)
        for op, seconds in dev["ops"].items():
            ops_total[op] = ops_total.get(op, 0.0) + seconds
    n = max(len(devices), 1)
    return {
        "chips": len(devices),
        "window_s": hi - lo,
        "periods": int(periods),
        "busy_s": sum(busy) / n if busy else 0.0,
        "module_s": {module: s / n for module, s in module_s.items()},
        "modules": whole_runs,
        "top_ops": sorted(
            ((op, s / n) for op, s in ops_total.items()),
            key=lambda kv: -kv[1],
        )[:10],
        "gaps": sorted(all_gaps, key=lambda ab: ab[0] - ab[1]),
    }


def missing(summary: Dict[str, object], module: str, waited_s: Optional[float]) -> Optional[str]:
    """What a stretch lacks for the device metrics to be read off it, as one
    line of text; ``None`` where it holds them. ``waited_s``: the seconds a
    period's dispatch-until-ready took by the program's own span. A trace
    whose buffer filled, or that lacks a run at an edge, holds fewer device
    seconds a period than the host waited for them."""
    if summary["busy_s"] <= 0:
        return "no operation ran on the device inside the stretch"
    device_s = summary["module_s"].get(module)
    if not device_s:
        return f"no run of {module!r} inside the stretch (programs there: {sorted(summary['module_s'])})"
    device_s /= summary["periods"]
    if waited_s and device_s < LEAST_DEVICE_SHARE * waited_s:
        return f"trace ended early: device {device_s:.3f}s of {waited_s:.3f}s waited"
    return None


def name_gaps(
    gap_list: Iterable[Tuple[float, float]], marks: Dict[str, List[Tuple[float, float]]],
) -> Dict[str, float]:
    """Idle seconds by the program's span they fall in. A gap of ``NAMED_GAP_S``
    or more is named by the span, of any thread, whose interval matches it
    best among those that cover at least half of it: the overlap over the
    union of the two, so of the spans around a gap the innermost wins, and
    of those inside it the one that fills it. ``between-spans`` where none
    covers half; shorter gaps (between a program's ops) are summed under
    ``between-ops``."""
    spans = [
        (start, start + dur, name)
        for name, found in marks.items() if name.startswith(SPANS)
        for start, dur in found
    ]
    named: Dict[str, float] = {}
    for a, b in gap_list:
        label, best = "between-ops", 0.0
        if b - a >= NAMED_GAP_S:
            label = "between-spans"
            for start, end, name in spans:
                overlap = min(b, end) - max(a, start)
                if overlap < 0.5 * (b - a):
                    continue
                match = overlap / (max(b, end) - min(a, start))
                if match > best:
                    label, best = name, match
        named[label] = named.get(label, 0.0) + (b - a)
    return named
