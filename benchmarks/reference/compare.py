"""The numbers that decide ``correct``: program against plain reference.

Each is a gap, 0 when the two agree, read on every sampled machine. The
configuration's file gives the limits: ``correct.limits`` for a number's
worst machine, ``correct.median_limits`` for its median one; ``PERF.md``
gives the readings each limit was set from. A number without a limit is
printed and not judged.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np


def _rel(a, b, floor: float = 1e-12) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), floor)))


def flatten(tree, prefix="") -> Dict[str, np.ndarray]:
    """``{path: leaf}``, each leaf in the type it came in: a whole tree is
    never copied to float64 (16 GB of host memory at 680 M parameters)."""
    out = {}
    for key in sorted(tree):
        value = tree[key]
        path = f"{prefix}/{key}" if prefix else str(key)
        if isinstance(value, dict):
            out.update(flatten(value, path))
        else:
            out[path] = np.asarray(value)
    return out


def param_change_gap(program_params, ref_params, ref_params0) -> float:
    """Worst leaf of | ||dp_program|| - ||dp_reference|| | over the larger of
    the reference's norm for that leaf and its median leaf's: the gap between
    the norms of the change, not the norm of the difference. A leaf the
    program left where it started reads 1, one it moved twice as far reads
    1 too."""
    prog, ref, ref0 = flatten(program_params), flatten(ref_params), flatten(ref_params0)
    if sorted(prog) != sorted(ref):
        return float("inf")
    norms = {}  # leaf -> (the program's change, the reference's), in float64
    for key in ref:
        if prog[key].shape != ref[key].shape:
            return float("inf")
        # one leaf at a time is widened, and dropped before the next
        start = np.asarray(ref0[key], np.float64)
        norms[key] = (
            float(np.linalg.norm(np.asarray(prog[key], np.float64) - start)),
            float(np.linalg.norm(np.asarray(ref[key], np.float64) - start)),
        )
    median = float(np.median([theirs for _, theirs in norms.values()]))
    return max(
        (abs(ours - theirs) / max(theirs, median, 1e-30) for ours, theirs in norms.values()),
        default=0.0,
    )


def machine_numbers(program: Dict[str, object], reference: Dict[str, object]) -> Dict[str, float]:
    """``program``: what the timed job committed for one machine, read back
    from its artifact, and what the benchmark's dataset saw it fetch.
    ``reference``: the plain reference's result for the same machine.
    ``anomaly_gap`` is between two trainings' scores; ``anomaly_replay_gap``
    between the program's score and the reference's arithmetic on the
    program's own committed parameters (``program["anomaly_replayed"]``)."""
    numbers = {
        "rows_gap": abs(int(program["rows"]) - int(reference["rows"])),
        "x_sum_gap": _rel(program["x_sum"], reference["x_sum"], 1e-6),
        "scaler_gap": max(
            _rel(program["input_scale"], reference["input_scale"]),
            _rel(program["target_scale"], reference["input_scale"]),
            float(np.max(np.abs(
                np.asarray(program["input_offset"], np.float64)
                - np.asarray(reference["input_offset"], np.float64)
            ))),
        ),
        "loss_first_gap": _rel(program["loss_history"][0], reference["loss_history"][0]),
        "loss_last_gap": _rel(program["loss_history"][-1], reference["loss_history"][-1]),
        "param_change_gap": param_change_gap(
            program["params"], reference["params"], reference["params0"]
        ),
        "cv_mse_gap": _rel(program["cv_mse"], reference["cv_mse"]),
        "threshold_gap": _rel(program["total_threshold"], reference["total_threshold"]),
        "anomaly_gap": _rel(program["anomaly_mean"], reference["anomaly_mean"]),
        "anomaly_replay_gap": _rel(program["anomaly_mean"], program["anomaly_replayed"]),
    }
    if len(program["loss_history"]) != len(reference["loss_history"]):
        numbers["loss_last_gap"] = float("inf")
    return numbers


def judge_sample(per_machine: List[Dict[str, float]], rules: Dict[str, object]):
    """``judge`` of the sampled machines' numbers by the configuration's
    ``correct`` block: each number's WORST machine against ``limits``, and,
    for the numbers ``median_limits`` names, the MEDIAN machine against that
    limit too, as ``<name>.median``. A number that two trainings make (a
    loss, a change of the parameters, a threshold) has a tail from machine to
    machine that no limit can sit above and still catch a lost precision: its
    median is held tightly, and its worst machine only to what a machine
    gone wrong alone would read."""
    def across(names, pick):
        return {k: float(pick([float(m[k]) for m in per_machine])) for k in names}

    out = judge(across(per_machine[0], np.max), rules["limits"])
    medians = rules.get("median_limits", {})
    for name, entry in judge(across(medians, np.median), medians).items():
        out[f"{name}.median"] = entry
    return out


def judge(numbers: Dict[str, float], limits: Dict[str, float]) -> Dict[str, Dict[str, object]]:
    """``{name: {"value", "limit", "ok"}}``; a number with no limit has
    ``limit: None`` and is not judged. A value that is not finite fails."""
    out = {}
    for name, value in numbers.items():
        limit = limits.get(name)
        ok = True if limit is None else bool(np.isfinite(value) and value <= limit)
        out[name] = {"value": float(value), "limit": limit, "ok": ok}
    return out


def failed_first(judged: Dict[str, Dict[str, object]]) -> Dict[str, Dict[str, object]]:
    """The same entries, those over their limit first: a record that keeps
    only the start of the line then says which number it was."""
    return dict(sorted(judged.items(), key=lambda kv: bool(kv[1]["ok"])))
