"""``correct`` has to be able to come out false, and true for sound work.

* The control (the plain reference computed in bfloat16, put in the program's
  place) fails at least one number's limit, at a size a test run holds; the
  sound stand-in (the reference at the configuration's own precision) fails
  none. On the chip at the cells' own size their readings are in ``PERF.md``.
* A whole run with the timed path broken underneath (``faulty_run.py``)
  reports ``correct: false``, once for each fault a build cell can have, and
  names the number that failed first: on stderr and in ``compared``.

Run by hand: ``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q``.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELLS = ["dense-ae-10tag.rehearsal-build", "lstm-ae-50tag.rehearsal-build"]


def faulty(fault, workload, seed=2147483777):
    env = dict(os.environ, JAX_PLATFORMS="cpu", GORDO_COMPILE_CACHE="off")
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "tests", "faulty_run.py"),
         fault, "--workload", workload, "--seed", str(seed), "--seconds", "1",
         "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    return json.loads(done.stdout.strip().splitlines()[-1]), done.stderr


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize(
    "fault", ["state_unchanged", "half_batch", "answer_altered", "score_altered"]
)
def test_a_broken_timed_path_is_not_correct(fault, workload):
    line, stderr = faulty(fault, workload)
    over = [
        k for k, v in line["compared"].items()
        if v["limit"] is not None and not v["value"] <= v["limit"]
    ]
    assert line["correct"] is False and over, line["compared"]
    # a record that keeps the start of the line, or the end of stderr, says
    # which number it was
    assert list(line["compared"])[: len(over)] == over, line["compared"]
    for name in over:
        assert f"FAILED NUMBER {name} = " in stderr
    assert stderr.strip().splitlines()[-1].endswith(", ".join(over))


def test_the_unbroken_path_is_correct():
    line, stderr = faulty("none", CELLS[0])
    assert line["correct"] is True
    assert "FAILED" not in stderr and "NOT CORRECT" not in stderr


def test_failed_numbers_come_first_and_keep_their_order():
    from benchmarks.reference import compare

    judged = compare.judge(
        {"a": 0.0, "b": 2.0, "c": 0.5, "d": float("nan"), "e": 9.0},
        {"a": 0, "b": 1.0, "c": 1.0, "d": 1.0},
    )
    assert list(compare.failed_first(judged)) == ["b", "d", "a", "c", "e"]
    assert judged == compare.failed_first(judged)  # the same entries


def test_the_worst_machine_and_the_median_one_have_limits_of_their_own():
    from benchmarks.reference import compare

    machines = [{"x": v, "y": v, "z": v} for v in (0.1, 0.2, 0.9, 0.3)]
    judged = compare.judge_sample(
        machines, {"limits": {"x": 0.5, "y": 1.0}, "median_limits": {"y": 0.2, "z": 0.3}}
    )
    assert {k: (v["value"], v["limit"], v["ok"]) for k, v in judged.items()} == {
        "x": (0.9, 0.5, False), "y": (0.9, 1.0, True), "z": (0.9, None, True),
        "y.median": (0.25, 0.2, False), "z.median": (0.25, 0.3, True),
    }
    # one machine that gives no number fails both forms
    machines[0]["y"] = float("nan")
    judged = compare.judge_sample(machines, {"limits": {"y": 1.0}, "median_limits": {"y": 1.0}})
    assert not judged["y"]["ok"] and not judged["y.median"]["ok"]


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize(
    "variant, sound", [("control_bf16", False), ("sound_default_precision", True)]
)
def test_a_stand_in_is_judged_as_what_it_is(variant, sound, workload):
    """The control fails a limit; the sound stand-in fails none."""
    from benchmarks import harness
    from benchmarks.drivers import build
    from benchmarks.reference import compare

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    loaded = harness.load_cell(workload)
    for seed in (5, 2147483999, 77):
        run = {**loaded, "seed": seed}
        sample, n_rows = [3, 9], 2048
        reference = build.reference_results(run, sample, n_rows)
        judged = compare.judge_sample(
            build.stand_in_numbers(run, sample, n_rows, reference, variant),
            build.correct_rules(run),
        )
        assert all(entry["ok"] for entry in judged.values()) is sound, judged


def test_an_unknown_stand_in_is_refused():
    from benchmarks.drivers import build

    with pytest.raises(SystemExit, match="no stand-in or fault"):
        build.stand_in_numbers({}, [], 0, [], "half_bach")
