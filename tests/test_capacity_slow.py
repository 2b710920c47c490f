"""The full 100k-machine capacity sweep (docs/ARCHITECTURE.md §22),
behind the ``slow`` marker — ROADMAP item 5's "10–100k machines with
production-shaped load", end to end.

Fleet generation alone takes ~10 minutes at this rig's commit rate, so
tier-1 (``-m 'not slow'``) never runs this; ``make capacity-smoke``
gates the same properties at 2k machines in CI time. Scale down with
``GORDO_CAPACITY_SWEEP_MACHINES`` for a faster manual run."""

import os
import shutil
import tempfile

import pytest

pytestmark = pytest.mark.slow


def test_100k_machine_sweep():
    from tools import capacity_harness as ch

    machines = int(
        os.environ.get("GORDO_CAPACITY_SWEEP_MACHINES", "100000")
    )
    root = tempfile.mkdtemp(prefix="gordo-capacity-sweep-")
    try:
        report = ch.full_run(
            root,
            machines,
            seconds=8.0,
            workers=2,
            threads=8,
            # no full-scan boot comparison: at 100k the scan alone takes
            # ~25 minutes
            measure_scan_boot=False,
        )
        boot = report["boot"]
        assert boot["machines_visible"] == machines
        # O(index read): the lazy boot must stay seconds-flat even at
        # 100k machines — the whole point of the sidecar
        assert boot["lazy_s"] <= 30.0
        assert (report["spill"]["speedup_x"] or 0) >= 3.0
        assert report["traffic"]["failures"] == 0
        assert report["slo"]["breaches"] == 0
        metrics = report["metrics"]
        assert metrics["bounded"]
        assert metrics["exposition_bytes"] <= 1 << 20
        placement = report["placement"]
        assert placement["candidates_us_p99"] <= 1000.0
    finally:
        shutil.rmtree(root, ignore_errors=True)
