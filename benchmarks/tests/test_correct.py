"""``correct`` has to be able to come out false.

* The control (the plain reference computed in bfloat16, put in the program's
  place) fails at least one number's limit, at a size a test run holds. On
  the chip at the cells' own size its readings are in ``PERF.md``.
* A whole run with the timed path broken underneath (``faulty_run.py``)
  reports ``correct: false``, once for each fault a build cell can have.

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
    return json.loads(done.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch", "answer_altered"])
def test_a_broken_timed_path_is_not_correct(fault, workload):
    line = faulty(fault, workload)
    over = {
        k: v for k, v in line["compared"].items()
        if v["limit"] is not None and not v["value"] <= v["limit"]
    }
    assert line["correct"] is False and over, line["compared"]


def test_the_unbroken_path_is_correct():
    assert faulty("none", CELLS[0])["correct"] is True


@pytest.mark.parametrize("workload", CELLS)
def test_the_bfloat16_control_fails_a_limit(workload):
    import jax.numpy as jnp

    from benchmarks import harness
    from benchmarks.drivers import build
    from benchmarks.reference import compare

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    loaded = harness.load_cell(workload)
    for seed in (5, 2147483999, 77):
        run = {**loaded, "seed": seed}
        sample, n_rows = [3, 9], 2048
        reference = build.reference_results(run, sample, n_rows)
        control = build.reference_results(
            run, sample, n_rows, dtype=jnp.bfloat16, precision=None
        )
        numbers = compare.worst_of([
            compare.machine_numbers(c, r) for c, r in zip(control, reference)
        ])
        judged = compare.judge(numbers, loaded["config"]["correct"]["limits"])
        assert not all(entry["ok"] for entry in judged.values()), judged
