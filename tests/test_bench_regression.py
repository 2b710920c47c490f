"""Execution-throughput regression gate (VERDICT r4 #6).

The driver's dense-fleet CPU exec number slid 6.5% across rounds 3→4 and
nothing noticed until the judge diffed artifacts. This gate fails the
suite BEFORE a large regression reaches a driver artifact:

- a **per-host measurement ring** (``tests/.anchors_local/``, gitignored)
  keeps the last 5 gate measurements on this box; the anchor is their
  MEDIAN, and the current run fails if it exceeds median x 1.5.
  Calibration (r5, this rig): raw exec seconds vary ±30% run-to-run
  with ambient load (0.41 idle .. 0.53 mid-suite .. 0.96 under
  concurrent drills for the identical code), so a tighter single-run
  bound false-positives — an earlier ratchet-to-minimum design locked
  in the luckiest idle run and failed the very next in-suite run at
  +30% on unchanged code. The 1.5x bound still catches the class that
  matters (a bad lowering or accidental O(n) regression is 2-100x).
  Because a rolling median could be WALKED upward by a sequence of
  just-under-tolerance regressions, a never-rising ``best_ever`` floor
  hard-caps cumulative drift at 2x per host; the 5-20% drift class is
  caught by diffing ``BENCH_HISTORY.jsonl`` across rounds.
- the **checked-in anchor** (``tests/anchors/dense_fleet_cpu.json``) is
  a x2.0 cross-host ceiling — loose on purpose; it catches the
  order-of-magnitude class even on a box the suite has never run on.

Reset a stale ring with GORDO_RESET_BENCH_ANCHOR=1 (e.g. after a
hardware change on a long-lived box).
"""

import hashlib
import json
import os
import platform
import subprocess
import sys
from pathlib import Path

import pytest

_REPO_ROOT = Path(__file__).resolve().parent.parent
_CHECKED_IN = Path(__file__).resolve().parent / "anchors" / "dense_fleet_cpu.json"
_LOCAL_DIR = Path(__file__).resolve().parent / ".anchors_local"

_GATE_ENV = {"BENCH_MACHINES": "32", "BENCH_EPOCHS": "5"}
_RING_KEEP = 5
_LOCAL_TOLERANCE = 1.5


def _measure_exec_s(tmp_path) -> float:
    import jax as _jax

    proc = subprocess.run(
        [sys.executable, "bench.py"],
        env={
            "PATH": "/usr/bin:/bin",
            "HOME": str(tmp_path),
            "BENCH_CONFIGS": "dense_ae_10tag",
            "BENCH_NO_SERVING": "1",
            "JAX_PLATFORMS": "cpu",
            # reuse the parent's persistent compile cache so the gate pays
            # execution time, not recompiles (cache empty => still correct)
            "JAX_COMPILATION_CACHE_DIR": (
                _jax.config.jax_compilation_cache_dir or ""
            ),
            # gate-shape rows must not pollute the checked-in history
            "GORDO_BENCH_HISTORY": os.devnull,
            **_GATE_ENV,
        },
        capture_output=True,
        text=True,
        timeout=560,
        cwd=str(_REPO_ROOT),
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    payload = json.loads(proc.stdout.strip().splitlines()[-1])
    exec_s = payload["configs"]["dense_ae_10tag"]["exec_s"]
    assert exec_s > 0
    return float(exec_s)


def _local_ring_path() -> Path:
    key = hashlib.sha256(
        f"{platform.node()}|{json.dumps(_GATE_ENV, sort_keys=True)}".encode()
    ).hexdigest()[:16]
    return _LOCAL_DIR / f"dense_fleet_cpu_{key}.json"


@pytest.mark.slow
def test_dense_fleet_exec_regression_gate(tmp_path):
    # best-of-2 damps transient load spikes within one gate run
    exec_s = min(_measure_exec_s(tmp_path), _measure_exec_s(tmp_path))

    ceiling = json.loads(_CHECKED_IN.read_text())["exec_s"] * 2.0
    assert exec_s <= ceiling, (
        f"dense-fleet exec_s {exec_s:.3f}s blew through the cross-host "
        f"ceiling {ceiling:.3f}s — an order-of-magnitude execution "
        "regression (see tests/anchors/dense_fleet_cpu.json)"
    )

    import statistics

    ring_path = _local_ring_path()
    ring: list = []
    best_ever = None
    if (
        os.environ.get("GORDO_RESET_BENCH_ANCHOR") != "1"
        and ring_path.exists()
    ):
        stored = json.loads(ring_path.read_text())
        # tolerate the pre-ring single-value format (r5 early): reseed
        ring = stored.get("ring", []) if isinstance(stored, dict) else []
        best_ever = stored.get("best_ever") if isinstance(stored, dict) else None
    if ring:
        anchor = statistics.median(ring)
        assert exec_s <= anchor * _LOCAL_TOLERANCE, (
            f"dense-fleet exec_s regressed >{_LOCAL_TOLERANCE}x on this "
            f"host: {exec_s:.3f}s vs median-of-recent {anchor:.3f}s "
            f"({ring_path}). If the slowdown is an intentional trade, "
            "reset with GORDO_RESET_BENCH_ANCHOR=1."
        )
    if best_ever is not None:
        # compounding backstop: the rolling median follows slow drift, so
        # a sequence of just-under-tolerance regressions could walk it
        # upward unflagged — but this floor NEVER rises (only the reset
        # knob clears it), so total drift on one host is hard-capped
        assert exec_s <= best_ever * 2.0, (
            f"dense-fleet exec_s {exec_s:.3f}s is >2x this host's best "
            f"ever ({best_ever:.3f}s, {ring_path}) — cumulative execution "
            "drift, even if each step stayed under the rolling-median "
            "gate. Reset with GORDO_RESET_BENCH_ANCHOR=1 if intentional."
        )
    _LOCAL_DIR.mkdir(exist_ok=True)
    ring = (ring + [exec_s])[-_RING_KEEP:]
    best_ever = exec_s if best_ever is None else min(best_ever, exec_s)
    ring_path.write_text(
        json.dumps({"ring": ring, "best_ever": best_ever, "env": _GATE_ENV})
    )


# -- cross-round history gate (fast tier) -------------------------------------
# The live gate above re-measures (slow tier, one host). This gate instead
# reads the CHECKED-IN ``BENCH_HISTORY.jsonl`` — the rows every bench round
# appended across rigs — and fails on SUSTAINED drift: the 5-25% class that
# slips under the 1.5x live tolerance but compounds across rounds. Raw
# exec seconds vary ±30% run-to-run with ambient load (the r5 calibration
# above), so each row is normalized by its own ``calib_matmul_ms`` rig
# probe, and one noisy round is never enough: only the last TWO rounds
# both exceeding the prior-median baseline by >25% fails.

_HISTORY = _REPO_ROOT / "BENCH_HISTORY.jsonl"
_DRIFT_TOLERANCE = 1.25


def _normalized_exec_history(path: Path) -> dict:
    """Per-config list of rig-normalized exec costs, round order kept.
    A row qualifies when it carries both the per-config ``exec_s`` block
    and the ``calib_matmul_ms`` rig probe measured in the same process —
    ``exec_s / calib_matmul_ms`` cancels the rig's scalar speed."""
    series: dict = {}
    for line in path.read_text().splitlines():
        line = line.strip()
        if not line:
            continue
        try:
            row = json.loads(line)
        except ValueError:
            continue  # a torn tail row must not fail the gate
        calib = row.get("calib_matmul_ms")
        configs = row.get("exec_s")
        if not isinstance(calib, (int, float)) or calib <= 0:
            continue
        if not isinstance(configs, dict):
            continue
        for config, block in configs.items():
            exec_s = (block or {}).get("exec_s")
            if isinstance(exec_s, (int, float)) and exec_s > 0:
                series.setdefault(config, []).append(exec_s / calib)
    return series


def _sustained_regression(values, tolerance=_DRIFT_TOLERANCE):
    """None, or (baseline, last_two) when BOTH of the newest two rounds
    exceed the median of all earlier rounds by ``tolerance``. A single
    bad round — however bad — is noise by calibration, not a verdict."""
    if len(values) < 3:
        return None
    import statistics

    baseline = statistics.median(values[:-2])
    last_two = values[-2:]
    if all(v > baseline * tolerance for v in last_two):
        return baseline, last_two
    return None


def test_bench_history_has_no_sustained_exec_drift():
    assert _HISTORY.exists(), "BENCH_HISTORY.jsonl missing from the repo"
    series = _normalized_exec_history(_HISTORY)
    assert series, (
        "no exec_s+calib_matmul_ms rows in BENCH_HISTORY.jsonl — the "
        "bench stopped recording the very numbers this gate watches"
    )
    for config, values in sorted(series.items()):
        verdict = _sustained_regression(values)
        assert verdict is None, (
            f"{config}: rig-normalized exec cost drifted "
            f">{(_DRIFT_TOLERANCE - 1) * 100:.0f}% for two consecutive "
            f"rounds (baseline {verdict[0]:.5f}, last two "
            f"{[round(v, 5) for v in verdict[1]]}) — a sustained "
            "execution regression reached the checked-in history"
        )


def test_sustained_drift_detector_tolerates_single_run_noise():
    # a ±30% one-round spike (the calibrated rig noise band) passes…
    assert _sustained_regression([1.0, 1.0, 1.0, 1.3, 1.0]) is None
    assert _sustained_regression([1.0, 1.0, 1.0, 1.0, 1.3]) is None
    # …and so does drift that stays inside the 25% tolerance
    assert _sustained_regression([1.0, 1.0, 1.0, 1.2, 1.24]) is None
    # but two consecutive rounds past it fail, spike-magnitude aside
    verdict = _sustained_regression([1.0, 1.0, 1.0, 1.3, 1.3])
    assert verdict is not None and verdict[0] == 1.0
    # short histories cannot render a verdict
    assert _sustained_regression([1.0, 2.0]) is None
