#!/usr/bin/env python3
"""Readings for the limits of ``correct``: in ONE process (set-up is long),
for each seed, a short run of the cell through the driver, whose check also
puts the sound stand-in (the reference at the configuration's own precision),
the control (the reference in bfloat16) and the planted faults in the
program's place and judges each by the configuration's limits, as a run is
judged: ``fails`` names the numbers over their limit; a control or a fault
that fails none has not been caught, and a sound stand-in that fails one says
the limit sits inside what a sound rewrite reads. One JSON line per seed on
stdout, each variant with its machines' own numbers (``per_machine``), so
that another form of a number (worst of fewer, the median) can be read off
the same call.

    python benchmarks/tools/readings.py --workload <name> --seeds 1,2,3 \\
        --seconds 5 --variants sound_default_precision,control_bf16,half_batch

``--reference-only`` leaves the job out: the control and the faults are the
reference put in the program's place, so their readings need no build, only
the reference twice at the cell's own size; the program's own readings are
then those that the cell's runs print under ``compared``.

Not run by the benchmark's own runs. ``PERF.md`` holds what it read.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--seconds", type=float, default=5.0)
    parser.add_argument("--variants", default="sound_default_precision,control_bf16,half_batch")
    parser.add_argument("--slice-size", type=int, default=None,
                        help="try another slice size than the configuration's")
    parser.add_argument("--fleet-machines", type=int, default=None,
                        help="a fleet that ends with the window (twice the "
                             "slice size) saves the wait for the job's end")
    parser.add_argument("--reference-only", action="store_true")
    args = parser.parse_args()
    from benchmarks import harness

    loaded = harness.load_cell(args.workload)
    for key in ("slice_size", "fleet_machines"):
        if getattr(args, key) is not None:
            loaded["traffic"][key] = getattr(args, key)
    # readings are not runs: the run's own deadlines do not hold here
    loaded["traffic"]["setup_budget_s"] = loaded["traffic"]["run_budget_s"] = 3000.0
    device = harness.device_or_exit(int(loaded["cell"]["chips"]))
    if args.reference_only:
        return reference_only(loaded, args)
    meter = harness.CompileMeter()
    driver = importlib.import_module(f"benchmarks.drivers.{loaded['traffic']['kind']}")
    for seed in [int(s) for s in args.seeds.split(",")]:
        started = time.perf_counter()
        deadline = harness.Deadline(started)
        run = {
            **loaded, "seed": seed, "seconds": args.seconds, "trace": False,
            "started": started, "device": device, "deadline": deadline,
            "meter": meter,
            "variants": [v for v in args.variants.split(",") if v],
        }
        outcome = driver.run_cell(run)
        deadline.close()
        checked = outcome["checked"]
        print(json.dumps({
            "seed": seed, "workload": args.workload, "correct": outcome["correct"],
            "machines_per_hour": outcome["values"]["machines_per_hour"],
            "setup_s": outcome["values"]["setup_s"],
            "wall_s": time.perf_counter() - started,
            "check_s": checked["check_s"], "reference_s": checked["reference_s"],
            "memory_peak_bytes": outcome["device"].get("memory_peak_bytes"),
            "program": {k: v["value"] for k, v in checked["judged"].items()},
            "variants": {
                name: reading(judged, checked["variants_per_machine"][name])
                for name, judged in checked["variants"].items()
            },
            "window_s": outcome["window_s"],
            "per_machine": checked["per_machine"],
        }), flush=True)
    os._exit(0)


def reading(judged, per_machine):
    return {
        "fails": [k for k, v in judged.items() if not v["ok"]],
        "numbers": {k: v["value"] for k, v in judged.items()},
        "per_machine": per_machine,
    }


def reference_only(loaded, args) -> int:
    import pandas as pd

    from benchmarks.drivers import build
    from benchmarks.reference import compare
    from gordo_components_tpu.utils.backend import enable_persistent_compile_cache

    enable_persistent_compile_cache()
    traffic, rules = loaded["traffic"], build.correct_rules(loaded)
    rows = int(traffic["history_days"]) * 86_400 * 10**9 // int(pd.Timedelta(traffic["resolution"]).value)
    n_rows = -(-rows // 256) * 256
    for seed in [int(s) for s in args.seeds.split(",")]:
        started = time.perf_counter()
        run = {**loaded, "seed": seed}
        slice_size, _ = build.sizes(run)
        # a run's sample: machines of the window's slice, drawn from the seed
        sample = build.sample_of(run, list(range(slice_size, 2 * slice_size)))
        references = build.reference_results(run, sample, n_rows)
        reference_s = time.perf_counter() - started
        variants = {}
        for variant in [v for v in args.variants.split(",") if v]:
            per_machine = build.stand_in_numbers(run, sample, n_rows, references, variant)
            variants[variant] = reading(compare.judge_sample(per_machine, rules), per_machine)
        print(json.dumps({
            "seed": seed, "workload": args.workload, "sample": sample,
            "reference_s": reference_s, "wall_s": time.perf_counter() - started,
            "variants": variants,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
