#!/usr/bin/env python3
"""``run.py`` with the timed path broken underneath: plants one fault in the
PROGRAM (not in the reference), then drives a whole run. Used by
``test_correct.py``, which expects ``correct`` to come out false.

    python benchmarks/tests/faulty_run.py <fault>[,<fault>] --workload ... --seed ...

Faults that touch different answers (``answer_altered,score_altered``) can be
planted together, which reads both on the chip in one run.
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def plant(fault: str) -> None:
    import importlib

    # (the package exports a function of the same name as the module)
    build_fleet = importlib.import_module("gordo_components_tpu.parallel.build_fleet")
    fleet = importlib.import_module("gordo_components_tpu.parallel.fleet")
    train = importlib.import_module("gordo_components_tpu.models.train")

    if fault == "state_unchanged":
        # a fit that hands back the parameters it was given
        original = fleet.make_fit_fn

        def make_fit_fn(*args, **kwargs):
            fit = original(*args, **kwargs)

            def unchanged(params, X, y, w, key):
                result = fit(params, X, y, w, key)
                return train.FitResult(params=params, loss_history=result.loss_history)

            return unchanged

        fleet.make_fit_fn = make_fit_fn
    elif fault == "half_batch":
        # half of every batch left out, the mean taken over the rest
        original = train.make_loss_fn

        def make_loss_fn(*args, **kwargs):
            loss_fn = original(*args, **kwargs)

            def halved(params, x, y, w, dropout_key):
                half = x.shape[0] // 2
                return loss_fn(params, x[:half], y[:half], w[:half], dropout_key)

            return halved

        train.make_loss_fn = make_loss_fn
    elif fault == "answer_altered":
        # one answer changed where it is produced: every machine's total
        # threshold a quarter higher than the build found it
        original = build_fleet._install_result

        def install(model, *args, **kwargs):
            original(model, *args, **kwargs)
            detector = build_fleet._analyze_model(model).detector
            detector.total_threshold_ = 1.25 * detector.total_threshold_

        build_fleet._install_result = install
    elif fault == "score_altered":
        # the other answer, altered where the loaded model produces it: every
        # total anomaly score a quarter higher than its tags' scores give
        diff = importlib.import_module("gordo_components_tpu.models.anomaly.diff")
        original = diff.DiffBasedAnomalyDetector.anomaly

        def anomaly(self, X, y=None):
            frame = original(self, X, y)
            frame["total-anomaly-score"] = 1.25 * frame["total-anomaly-score"]
            return frame

        diff.DiffBasedAnomalyDetector.anomaly = anomaly
    elif fault != "none":
        raise SystemExit(f"unknown fault {fault!r}")


if __name__ == "__main__":
    for fault in sys.argv.pop(1).split(","):
        plant(fault)
    from benchmarks import run

    sys.exit(run.main())
