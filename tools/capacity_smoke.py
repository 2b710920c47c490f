#!/usr/bin/env python
"""Capacity smoke: the fleet-scale hot-path gates at a 2k-machine
synthetic fleet, fast mode (``make capacity-smoke``).

Checks (ISSUE 14 acceptance, scaled to CI):

- **lazy boot economics**: a FLEET_INDEX-sidecar boot of the whole
  fleet completes in bounded wall-clock AND ≥5x faster than the
  full-scan boot of the same tree (the §22 index gate).
- **spill-tier economy**: serving a demoted (host-cache-dropped) lazy
  machine end to end is ≥3x slower than serving it from the host-RAM
  spill tier — i.e. the hit is ≥3x faster, the §22 memcpy-vs-store gate.
- **placement lookups**: `Placement.candidates` p99 stays in the
  microsecond regime at a 64-worker ring (O(log v) bisect, no point-
  array rescans), and an incremental worker join beats a full rebuild.
- **router-tier baseline load + bounded scrape**: production-shaped
  traffic through 2 lazy workers finishes with ZERO failures and ZERO
  SLO breaches, and the Prometheus exposition stays size-bounded with
  machine-label cardinality ≤ top-K + `other` at any fleet size.

Fast mode: GORDO_CAPACITY_MACHINES (default 2000) and
GORDO_CAPACITY_SECONDS (default 4 here) shrink/grow the run; the full
10k+ sweep is `tools/capacity_harness.py full` and the `slow`-marked
test in tests/test_capacity_slow.py.

Exit codes: 0 = all checks passed, 1 = at least one failed.
"""

from __future__ import annotations

import os
import shutil
import sys
import tempfile

# runnable straight from a checkout (python tools/capacity_smoke.py)
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

_failures = []


def check(ok: bool, what: str) -> None:
    print(f"  {'ok' if ok else 'FAIL'}: {what}")
    if not ok:
        _failures.append(what)


def main() -> int:
    from tools import capacity_harness as ch

    machines = int(os.environ.get("GORDO_CAPACITY_MACHINES", "2000"))
    seconds = float(os.environ.get("GORDO_CAPACITY_SECONDS", "4"))
    print(
        f"capacity smoke: {machines}-machine synthetic fleet, "
        f"{seconds}s baseline load"
    )

    root = tempfile.mkdtemp(prefix="gordo-capacity-smoke-")
    try:
        report = ch.full_run(
            root,
            machines,
            seconds,
            workers=2,
            threads=6,
            spill_probes=8,
            measure_scan_boot=True,
        )

        print("\n[1/4] lazy boot economics (FLEET_INDEX sidecar)")
        boot = report["boot"]
        check(
            boot["machines_visible"] == machines,
            f"lazy boot sees the whole fleet ({boot['machines_visible']})",
        )
        check(
            boot["lazy_s"] <= 10.0,
            f"lazy boot bounded: {boot['lazy_s']}s <= 10s",
        )
        check(
            boot["speedup_x"] >= 5.0,
            f"index boot >=5x full scan: {boot['speedup_x']}x "
            f"({boot['scan_s']}s scan vs {boot['lazy_s']}s lazy)",
        )

        print("\n[2/4] spill-tier economy (host-RAM hit vs store path)")
        spill = report["spill"]
        check(
            (spill["speedup_x"] or 0) >= 3.0,
            f"spill hit serves a demoted machine >=3x faster: "
            f"{spill['speedup_x']}x ({spill['serve_store_ms_p50']}ms "
            f"store vs {spill['serve_hit_ms_p50']}ms hit)",
        )
        check(
            spill["host_cache"]["hits"] > 0
            and spill["host_cache"]["loads"] > 0,
            "host cache saw both hits and store loads",
        )

        print("\n[3/4] placement lookups at a 64-worker ring")
        placement = report["placement"]
        check(
            placement["candidates_us_p99"] <= 1000.0,
            f"candidates p99 {placement['candidates_us_p99']}us <= 1000us",
        )
        check(
            placement["join_incremental_ms"]
            < placement["join_full_rebuild_ms"],
            f"incremental join {placement['join_incremental_ms']}ms beats "
            f"full rebuild {placement['join_full_rebuild_ms']}ms",
        )

        print("\n[4/4] router-tier baseline load + bounded scrape")
        traffic = report["traffic"]
        check(
            traffic["failures"] == 0,
            f"zero failures over {traffic['requests']} shaped requests",
        )
        check(
            report["slo"]["breaches"] == 0,
            "zero SLO breaches at baseline load",
        )
        replay = report.get("replay")
        check(
            bool(replay) and replay["failures"] == 0,
            "flight-recorder replay ran with zero failures",
        )
        metrics = report["metrics"]
        check(
            metrics["bounded"],
            f"machine-label cardinality bounded: worst "
            f"{metrics['max_machine_values']} <= cap "
            f"{metrics['cardinality_cap']} + other",
        )
        check(
            metrics["exposition_bytes"] <= 1 << 20,
            f"exposition size {metrics['exposition_bytes']}B <= 1MiB "
            f"at {machines} machines",
        )
    finally:
        shutil.rmtree(root, ignore_errors=True)

    if _failures:
        print(f"\nCAPACITY SMOKE FAILED: {len(_failures)} check(s)",
              file=sys.stderr)
        for what in _failures:
            print(f"  - {what}", file=sys.stderr)
        return 1
    print(
        "\ncapacity smoke passed: index boot, spill-tier economy, "
        "O(log v) placement, bounded scrape, zero breaches"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
