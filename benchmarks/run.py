#!/usr/bin/env python3
"""The benchmark's one command.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of stdout is the result object; everything else goes to
stderr. ``JAX_PLATFORMS=cpu`` rehearses a ``rehearsal_only`` mix on the CPU
and never prints a device metric. See ``benchmarks/harness.py``.
"""

from __future__ import annotations

import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args()

    if not os.path.isdir(os.path.join(ROOT, "gordo_components_tpu")):
        sys.stderr.write("bench: the program is not in this directory; nothing to run\n")
        return 2
    from benchmarks import harness
    from benchmarks.reference import compare

    loaded = harness.load_cell(args.workload)
    device = harness.device_or_exit(int(loaded["cell"]["chips"]))
    meter = harness.CompileMeter()
    run = {
        **loaded, "seed": args.seed, "seconds": args.seconds,
        "trace": bool(args.trace), "started": STARTED, "device": device,
        "deadline": harness.Deadline(STARTED, meter), "meter": meter,
    }
    driver = importlib.import_module(f"benchmarks.drivers.{loaded['traffic']['kind']}")
    outcome = driver.run_cell(run)

    bench, name = loaded["bench"], loaded["cell"]["name"]
    on_chip = device["platform"] != "cpu"
    if args.trace:
        reports = harness.metric_names(bench, "end_to_end", name, [])
        names = harness.metric_names(bench, "per_layer", name, reports)
        values = harness.read_layer_metrics(names, outcome["view"]) if on_chip else {}
    else:
        values = dict(outcome["values"])
        if not on_chip:
            values = {"setup_s": values["setup_s"]}
    checked = outcome["checked"] or {"sample": []}
    # the numbers over their limit first, on stderr and in the line: a
    # record that keeps only part of either still says which it was
    judged = compare.failed_first(outcome["judged"])
    compared = {k: {"value": v["value"], "limit": v["limit"]} for k, v in judged.items()}
    harness.log(
        f"checked {checked['sample']} in {checked.get('check_s', 0):.1f}s "
        f"(reference {checked.get('reference_s', 0):.1f}s)"
    )
    for key, entry in judged.items():
        harness.log(
            f"{'compared' if entry['ok'] else 'FAILED NUMBER'} {key} = "
            f"{entry['value']:.6g} (limit {entry['limit']})"
        )
    over = [key for key, entry in judged.items() if not entry["ok"]]
    if over:
        harness.log(f"NOT CORRECT: over their limits: {', '.join(over)}")
    sys.stderr.flush()
    print(harness.result_line(
        bench, outcome["correct"], outcome["attempted"], outcome["failed"],
        values, outcome["device"], outcome["breakdown"] if on_chip else None,
        compared,
    ))
    sys.stdout.flush()
    # the job's worker threads are daemons and have ended; leave at once
    os._exit(0)


if __name__ == "__main__":
    sys.exit(main())
