"""The reduction from a profiler trace to device numbers, on a small trace
recorded on one TPU v5e chip (``fixture.xplane.pb``, made by
``benchmarks/tools/record_trace_fixture.py``: three runs of one tiny jitted
program, 50 ms apart) and on hand-made intervals.

Run by hand: ``python -m pytest benchmarks/tests -q`` (needs no device).
"""

import os

import pytest

from benchmarks import trace_reduce

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixture.xplane.pb")


def test_union_and_gaps():
    intervals = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.2, 3.4)]
    assert trace_reduce.union_seconds(intervals) == pytest.approx(3.0)
    assert trace_reduce.gaps(intervals, 0.0, 5.0) == [(2.0, 3.0), (4.0, 5.0)]
    assert trace_reduce.gaps(intervals, 0.25, 3.5) == [(2.0, 3.0)]
    assert trace_reduce.gaps([], 1.0, 2.0) == [(1.0, 2.0)]
    assert trace_reduce.strip_module("jit_program(123)") == "jit_program"


def test_window_summary_on_hand_made_planes():
    reduced = {"marks": {}, "devices": {
        "/device:TPU:0": {
            "modules": {"jit_program": [(1.0, 2.0), (5.0, 2.0), (9.0, 2.0)]},
            "ops": {"fusion.1": 4.0, "copy.2": 2.0},
            "op_intervals": [(1.0, 3.0), (5.0, 7.0), (9.0, 11.0)],
        },
    }}
    summary = trace_reduce.window_summary(reduced, 0.0, 10.0)
    assert summary["chips"] == 1 and summary["window_s"] == 10.0
    assert summary["busy_s"] == pytest.approx(5.0)  # the third run is clipped
    # a program's seconds inside the stretch, the run at the edge cut there
    assert summary["module_s"] == {"jit_program": pytest.approx(5.0)}
    assert summary["periods"] == 1
    assert summary["modules"] == {"jit_program": [2.0, 2.0]}  # for the log line
    assert trace_reduce.window_summary(reduced, 2.0, 10.0, 2)["module_s"] == {
        "jit_program": pytest.approx(4.0)
    }
    assert summary["top_ops"][0] == ("fusion.1", 4.0)
    assert summary["gaps"][0] == (3.0, 5.0)


@pytest.mark.skipif(not os.path.exists(FIXTURE), reason="no recorded trace")
def test_recorded_trace_from_the_chip():
    reduced = trace_reduce.reduce_trace(FIXTURE)
    assert list(reduced["devices"]) == ["/device:TPU:0"]
    assert "bench:sync" in reduced["marks"]
    device = reduced["devices"]["/device:TPU:0"]
    runs = device["modules"]["jit_fixture_program"]
    assert len(runs) == 3
    starts = [s for s, _ in runs]
    # the three runs are at least the 50 ms pause apart
    assert all(b - a > 0.05 for a, b in zip(starts, starts[1:]))
    lo, hi = starts[0] - 1e-3, starts[-1] + runs[-1][1] + 1e-3
    summary = trace_reduce.window_summary(reduced, lo, hi)
    module_s = sum(d for _, d in runs)
    # ops run only inside their programs, and fill most of them
    assert 0.5 * module_s < summary["busy_s"] <= module_s * 1.001
    assert summary["busy_s"] < 0.2 * summary["window_s"]  # mostly the pauses
    assert len(summary["modules"]["jit_fixture_program"]) == 3
    assert summary["module_s"]["jit_fixture_program"] == pytest.approx(module_s)
    # cut in the middle of the second run: half of it is inside
    middle = starts[1] + 0.5 * runs[1][1]
    half = trace_reduce.window_summary(reduced, lo, middle)
    assert half["module_s"]["jit_fixture_program"] == pytest.approx(runs[0][1] + 0.5 * runs[1][1])
    assert trace_reduce.missing(summary, "jit_fixture_program", None) is None
    assert len([g for g in summary["gaps"] if g[1] - g[0] > 0.04]) == 2
