"""The traced stretch reads the device per commit period, wherever the commits
fall against the train program's runs: the four trace metrics over hand-made
``reduced`` dictionaries. Pure Python: needs no device and no JAX.

Run by hand: ``python -m pytest benchmarks/tests/test_trace_stretch.py -q``.
"""

import pytest

from benchmarks import flops_bytes, trace_reduce
from benchmarks.layer_metrics import (
    device_idle_pct, fleet_train_roofline, train_device_s_per_slice, train_step_mfu,
)

MODULE = "jit_program"
PEAK = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
COUNTS = {"flops": 2.0e14, "bytes": 1.0e9}
# the ops leave a thousandth of each run idle (between-ops gaps)
OP_SHARE = 0.999


def periodic(program_s, period_s, count=6, dropped=()):
    """A chip whose train program runs ``program_s`` of every ``period_s``,
    from t = 0 on; ``dropped`` runs are left out of the trace."""
    starts = [k * period_s for k in range(count) if k not in dropped]
    return {"marks": {}, "devices": {"/device:TPU:0": {
        "modules": {
            MODULE: [(s, program_s) for s in starts],
            "jit__threefry_split": [(s - 0.001, 0.0005) for s in starts],
        },
        "ops": {"while.1": program_s * len(starts)},
        "op_intervals": [(s, s + OP_SHARE * program_s) for s in starts]
        + [(s - 0.001, s - 0.0005) for s in starts],
    }}}


def four(reduced, lo, periods, period_s, waited_s):
    """The four trace metrics of the stretch of ``periods`` commit periods
    from ``lo`` on, as the driver's ``trace_view`` hands them to the
    readers; all ``None`` where the stretch lacks something."""
    summary = trace_reduce.window_summary(reduced, lo, lo + periods * period_s, periods)
    lacks = trace_reduce.missing(summary, MODULE, waited_s)
    view = {
        "trace": None if lacks else summary, "peak": PEAK, "counts": COUNTS,
        "run": {"config": {"train_module": MODULE}},
    }
    readers = (train_device_s_per_slice, train_step_mfu, fleet_train_roofline, device_idle_pct)
    return tuple(reader.read(view) for reader in readers), lacks


# the commit 0.12 s in front of the run (today), a third of the way into
# it, two thirds in; a period that starts in the idle gap behind the run
PHASES = {"in-front": -0.12, "third-in": 1 / 3, "two-thirds-in": 2 / 3, "behind": 1.05}


@pytest.mark.parametrize("program_s, period_s", [(12.0, 17.0), (18.0, 22.0)])
@pytest.mark.parametrize("phase", list(PHASES))
@pytest.mark.parametrize("periods", [1, 2])
def test_the_four_numbers_do_not_depend_on_the_phase(program_s, period_s, phase, periods):
    reduced = periodic(program_s, period_s)
    at = PHASES[phase]
    lo = period_s + (at if at < 0 else at * program_s)
    aligned, lacks = four(reduced, period_s - 0.12, 1, period_s, program_s)
    assert lacks is None
    device_s, mfu, roofline, idle = aligned
    assert device_s == pytest.approx(program_s)
    assert mfu == pytest.approx(100 * COUNTS["flops"] / (period_s * PEAK["flops_per_s"]))
    least = flops_bytes.least_seconds(COUNTS, PEAK)["seconds"]
    assert roofline == pytest.approx(100 * least / program_s)
    assert idle == pytest.approx(100 * (1 - (OP_SHARE * program_s + 0.0005) / period_s))
    shifted, lacks = four(reduced, lo, periods, period_s, program_s)
    assert lacks is None
    assert shifted == pytest.approx(aligned, rel=1e-9)


@pytest.mark.parametrize("phase", ["third-in", "two-thirds-in"])
@pytest.mark.parametrize("edge", ["start", "stop"])
def test_a_run_missing_at_an_edge_reads_none(phase, edge):
    """The run in flight when the trace started, or the one in flight when it
    stopped, is not in it: fewer device seconds than the host waited."""
    program_s, period_s = 12.0, 17.0
    reduced = periodic(program_s, period_s, dropped=(1,) if edge == "start" else (2,))
    values, lacks = four(reduced, period_s + PHASES[phase] * program_s, 1, period_s, program_s)
    assert values == (None, None, None, None)
    assert lacks.startswith("trace ended early: device ") and lacks.endswith("s of 12.000s waited")


def test_a_trace_cut_short_against_the_waited_seconds_reads_none():
    """The trace's buffer filled at 85% of the run (my chip runs, PR 25: 1,050
    of 1,236 steps): read, it would say 20% idle of a chip that idled 3%."""
    reduced = periodic(18.0, 22.0)
    device = reduced["devices"]["/device:TPU:0"]
    device["modules"][MODULE] = [(s, 0.85 * d) for s, d in device["modules"][MODULE]]
    device["op_intervals"] = [(a, a + 0.85 * (b - a)) for a, b in device["op_intervals"]]
    values, lacks = four(reduced, 22.0 - 0.12, 1, 22.0, 18.075)
    assert values == (None, None, None, None)
    assert lacks == "trace ended early: device 15.300s of 18.075s waited"
    # the ledger's worst sound reading, 97.6% of the waited seconds, is read
    values, lacks = four(periodic(12.32, 17.0), 17.0 - 0.12, 1, 17.0, 12.624)
    assert lacks is None and values[0] == pytest.approx(12.32)


def test_a_stretch_without_the_program_or_without_an_op_says_so():
    reduced = periodic(12.0, 17.0)
    summary = trace_reduce.window_summary(reduced, 12.5, 16.5, 1)  # the idle gap alone
    assert trace_reduce.missing(summary, MODULE, 12.0).startswith("no operation ran")
    summary = trace_reduce.window_summary(reduced, 16.9, 33.9, 1)
    assert "no run of 'jit_other'" in trace_reduce.missing(summary, "jit_other", 12.0)


def test_gaps_are_named_by_the_programs_own_spans_of_any_thread():
    """The commit on a worker's thread, the build loop's spans on its own:
    each long gap takes the name of the span that matches it best."""
    marks = {
        "bench:sync": [(0.0, 1e-5)],
        # the build loop's thread
        "fleet.job": [(-50.0, 200.0)],
        "fleet.slice": [(0.1, 16.8), (17.0, 16.9)],
        "fleet.execute": [(0.2, 12.0), (17.2, 12.0)],
        "fleet.result_fetch": [(12.2, 0.4), (29.2, 0.4)],
        # a second thread: one commit a machine inside the loop's span, and
        # fetches of the next slice that lie across the gaps
        "fleet.commit_loop": [(12.65, 4.1)],
        "fleet.commit": [(12.66, 2.0), (14.67, 2.05)],
        "fleet.fetch": [(11.0, 3.9), (13.0, 2.6)],
        "fleet.prepare": [(0.3, 16.0)],
    }
    gaps = [(12.2, 17.2), (29.2, 29.62), (5.0, 5.0004), (6.0, 6.0003), (40.0, 40.5)]
    named = trace_reduce.name_gaps(gaps, marks)
    assert named == pytest.approx({
        "fleet.commit_loop": 5.0,  # not the slice around it, not a fetch inside it
        "fleet.result_fetch": 0.42,
        "between-ops": 0.0007,
        "fleet.job": 0.5,  # nothing nearer covers half of it
    })
    # one machine a slice: the commit fills its loop, and the loop is named
    # (the span that covers more of the gap)
    one = {"fleet.commit_loop": [(12.6, 3.6)], "fleet.commit": [(12.61, 3.58)]}
    assert list(trace_reduce.name_gaps([(12.5, 16.8)], one)) == ["fleet.commit_loop"]
    assert trace_reduce.name_gaps([(1.0, 2.0)], {"bench:window": [(0.0, 9.0)]}) == {"between-spans": 1.0}
