"""The one command, rehearsed on the CPU at a tiny size: window open/close on
commit events, the result line, the early-job-end failure, the deadline, and
the refusal to print a device metric or to run where the program is not.

Run by hand: ``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q``.
"""

import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def bench(workload, seconds, trace=0, seed=2147483659, cwd=ROOT, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "benchmarks", "run.py"),
         "--workload", workload, "--seed", str(seed), "--seconds", str(seconds),
         "--trace", str(trace)],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=600,
    )


def test_result_line_and_no_device_metric_on_the_cpu():
    for trace in (0, 1):
        done = bench("dense-ae-10tag.rehearsal-build", 1, trace=trace)
        assert done.returncode == 0, done.stderr[-2000:]
        line = json.loads(done.stdout.strip().splitlines()[-1])
        assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
        assert list(line)[-1] == "compared"
        assert line["correct"] is True and line["failed"] == 0
        assert line["attempted"] > 0 and line["attempted"] % 4 == 0
        assert line["device"] == {"platform": "cpu", "kind": "cpu", "count": 1}
        # a CPU run reports its set-up and nothing that reads like a device
        # number: no rate, no share of a peak, no memory, no busy time
        assert set(line["metrics"]) <= {"setup_s"}
        assert "breakdown" not in line
        for name, entry in line["compared"].items():
            assert set(entry) == {"value", "limit"}, name
        assert "window " in done.stderr and "compiles inside the window" in done.stderr


def test_a_job_that_ends_inside_the_window_fails_the_run():
    done = bench("dense-ae-10tag.rehearsal-build", 3600)  # the fleet is 400 machines
    assert done.returncode == 6
    assert "no rate is reported over a short window" in done.stderr
    assert "fleet_machines" in done.stderr
    last = done.stdout.strip().splitlines()[-1] if done.stdout.strip() else ""
    assert '"correct"' not in last


def test_the_deadline_names_the_phase_and_prints_no_result():
    done = bench("dense-ae-10tag.rehearsal-deadline", 1)
    assert done.returncode == 4
    assert "DEADLINE setup_budget_s" in done.stderr and "in phase" in done.stderr
    assert done.stdout.strip() == ""


def test_an_unasked_for_cpu_and_an_unknown_cell_are_refused():
    done = bench("lstm-ae-50tag.build", 1, env_extra={"JAX_PLATFORMS": ""})
    assert done.returncode == 2 and done.stdout.strip() == ""
    done = bench("dense-ae-10tag.nosuch", 1)
    assert done.returncode != 0 and done.stdout.strip() == ""


def test_alone_with_the_benchmark_and_nothing_else(tmp_path):
    shutil.copytree(os.path.join(ROOT, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    done = bench("dense-ae-10tag.rehearsal-build", 1, cwd=str(tmp_path))
    assert done.returncode != 0 and done.stdout.strip() == ""
