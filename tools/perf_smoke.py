#!/usr/bin/env python
"""Perf smoke: the serving data plane's parity + saturation gates on the
CPU backend (``make perf-smoke``).

Checks (ISSUE 4 acceptance, minus anything rig-dependent — deliberately NO
thresholds on absolute RPS, CI boxes vary):

- wire-format parity: an ``application/x-gordo-npz`` response decodes to
  arrays byte-identical to the JSON response's values (float32), over the
  real WSGI stack;
- pipeline parity: pipelined dispatch (``GORDO_DISPATCH_DEPTH=2``) is
  bit-identical to serial mode (depth 1) on the same engine inputs;
- saturation sanity: a short concurrent sweep (1/4/8 workers) over the
  engine completes with every request succeeding and the dispatch
  pipeline engaged, in BOTH replicated and shard mode. Per-rung RPS is
  printed for the log but deliberately not gated — 2-core CI boxes show
  ±2.5x run-to-run variance, and a flaky gate teaches people to ignore
  the battery (speed is measured by benchmarks/run.py on the chip).

Exit codes: 0 = all checks passed, 1 = at least one failed.
"""

from __future__ import annotations

import json
import os
import sys
from concurrent.futures import ThreadPoolExecutor

# runnable straight from a checkout (python tools/perf_smoke.py):
# sys.path[0] is tools/, the package lives one level up
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# 8 virtual devices so the shard-mode sweep exercises real partitioning
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

_failures = []


def check(ok: bool, what: str) -> None:
    print(f"  {'ok' if ok else 'FAIL'}: {what}")
    if not ok:
        _failures.append(what)


def _bits(result) -> tuple:
    import numpy as np

    return tuple(
        np.asarray(a).tobytes()
        for a in (result.model_input, result.model_output,
                  result.tag_anomaly_scores, result.total_anomaly_score)
    )


def _build_served_app(tmp: str):
    """One throwaway served model + WSGI test client, shared by the wire
    parity and flight-recorder overhead checks."""
    from werkzeug.test import Client as TestClient

    from gordo_components_tpu.builder import provide_saved_model
    from gordo_components_tpu.server import build_app

    data_config = {
        "type": "RandomDataset",
        "train_start_date": "2023-01-01T00:00:00+00:00",
        "train_end_date": "2023-01-04T00:00:00+00:00",
        "tag_list": ["t-a", "t-b", "t-c"],
    }
    model_config = {
        "DiffBasedAnomalyDetector": {
            "base_estimator": {
                "Pipeline": {
                    "steps": [
                        "MinMaxScaler",
                        {"DenseAutoEncoder": {"kind": "feedforward_symmetric",
                                              "dims": [4], "epochs": 1,
                                              "batch_size": 32}},
                    ]
                }
            }
        }
    }
    model_dir = provide_saved_model(
        "m-perf", model_config, data_config, os.path.join(tmp, "m-perf"),
        evaluation_config={"cv_mode": "build_only"},
    )
    return TestClient(build_app({"m-perf": model_dir}, project="proj"))


def wire_parity(client) -> None:
    """Two-format parity over the real WSGI stack."""
    import numpy as np

    from gordo_components_tpu import wire

    print("\n[1/4] wire-format parity (npz vs JSON, real WSGI stack)")
    X = (np.random.default_rng(0).normal(size=(96, 3)) * 2 + 4).tolist()
    body = json.dumps({"X": X})
    path = "/gordo/v0/proj/m-perf/anomaly/prediction"
    json_resp = client.post(path, data=body,
                            content_type="application/json")
    npz_resp = client.post(path, data=body,
                           content_type="application/json",
                           headers={"Accept": wire.NPZ_CONTENT_TYPE})
    check(json_resp.status_code == 200, "JSON response 200")
    check(npz_resp.status_code == 200, "npz response 200")
    check(npz_resp.content_type == wire.NPZ_CONTENT_TYPE,
          "npz content type negotiated")
    if json_resp.status_code == 200 and npz_resp.status_code == 200:
        json_data = json_resp.get_json()["data"]
        arrays, _ = wire.decode_npz(npz_resp.get_data())
        for name in wire.SCORE_FIELDS:
            same = (
                np.asarray(json_data[name], np.float32).tobytes()
                == arrays[name].tobytes()
            )
            check(same, f"{name}: npz byte-identical to JSON@float32")
        check(
            len(npz_resp.get_data()) < len(json_resp.get_data()),
            "npz payload smaller than JSON at 96 rows",
        )


def flightrec_overhead(client) -> None:
    """ISSUE 5 acceptance: throughput with the flight recorder enabled is
    within 3% of a run with it disabled.

    Measured as a PAIRED comparison with a noise floor (ISSUE 12
    satellite — the previous block-interleaved median flaked on this
    2-core rig, where even seed-vs-seed measured 0.79–1.15x): each
    iteration times one enabled and one disabled request back to back
    (order alternating per pair, so drift and order bias cancel), and
    the gate is the MEDIAN of the per-pair throughput ratios — adjacent
    requests share the same scheduler/GC weather, so the recorder's
    per-request cost (~40 µs against a ~2 ms request) is the only
    systematic difference a pair sees. A same-mode null comparison
    (enabled vs enabled, identically paired) measures what this rig
    calls "zero" right now; its deviation from 1.0 widens the 3% gate —
    the noise floor that keeps ``make smoke`` deterministic on noisy
    boxes while still catching a real regression."""
    import time

    import numpy as np

    from gordo_components_tpu.observability.flightrec import RECORDER

    print("\n[4/4] flight-recorder overhead (paired, noise-floored 3% gate)")
    X = (np.random.default_rng(3).normal(size=(64, 3)) * 2 + 4).tolist()
    body = json.dumps({"X": X})
    path = "/gordo/v0/proj/m-perf/anomaly/prediction"

    def timed_request() -> float:
        started = time.perf_counter()
        response = client.post(path, data=body,
                               content_type="application/json")
        assert response.status_code == 200
        return time.perf_counter() - started

    def paired_ratios(n_pairs: int, modes=(True, False)):
        """Median per-pair throughput ratio latency(slot b) / latency
        (slot a), slot a running ``modes[0]`` and slot b ``modes[1]``,
        execution order alternating per pair. Identical modes (the null
        comparison) measure pure pairing noise through the exact same
        structure."""
        ratios = []
        for i in range(n_pairs):
            slots = [("a", modes[0]), ("b", modes[1])]
            if i % 2:
                slots.reverse()
            sample = {}
            for slot, mode in slots:
                RECORDER.set_enabled(mode)
                sample[slot] = timed_request()
            if sample["a"] > 0:
                ratios.append(sample["b"] / sample["a"])
        return float(np.median(ratios))

    for _ in range(30):  # settle caches/compiles before timing
        timed_request()
    was_enabled = RECORDER.enabled
    try:
        # null comparison first: enabled-vs-enabled pairs — any
        # deviation from 1.0 is pure rig noise at this sample size
        null_ratio = paired_ratios(120, modes=(True, True))
        ratio = paired_ratios(240, modes=(True, False))
    finally:
        RECORDER.set_enabled(was_enabled)
    noise = abs(1.0 - null_ratio)
    floor = 0.97 - noise
    print(
        f"  median paired throughput ratio {ratio:.3f} "
        f"(null {null_ratio:.3f}, noise floor widens gate to "
        f">= {floor:.3f})"
    )
    check(
        ratio >= floor,
        f"flight recorder costs <= 3% throughput beyond rig noise "
        f"(ratio {ratio:.3f}, gate {floor:.3f})",
    )


def _build_engines():
    from gordo_components_tpu.models.synthetic_fleet import build_models

    models = build_models(8, 64, 4)
    return models


def pipeline_parity(models) -> None:
    import numpy as np

    from gordo_components_tpu.server.engine import ServingEngine

    print("\n[2/4] pipelined-vs-serial bit-identity")
    rng = np.random.default_rng(1)
    X = rng.normal(size=(64, 4)).astype(np.float32) * 2 + 4
    os.environ["GORDO_DISPATCH_DEPTH"] = "1"
    serial = ServingEngine(models)
    os.environ["GORDO_DISPATCH_DEPTH"] = "2"
    pipelined = ServingEngine(models)
    os.environ.pop("GORDO_DISPATCH_DEPTH", None)
    names = serial.machines()
    identical = all(
        _bits(serial.anomaly(n, X)) == _bits(pipelined.anomaly(n, X))
        for n in names
    )
    check(identical, "depth=2 bit-identical to depth=1 across the fleet")
    serial.close()
    pipelined.close()


def saturation_sweep(models, shard: bool) -> None:
    import time

    import numpy as np

    from gordo_components_tpu.server.engine import ServingEngine

    mode = "shard" if shard else "replicated"
    print(f"\n[3/4] saturation sweep ({mode} mode, no absolute thresholds)")
    mesh = None
    if shard:
        from gordo_components_tpu.parallel.mesh import fleet_mesh

        mesh = fleet_mesh(8)
    engine = ServingEngine(models, mesh=mesh)
    names = engine.machines()
    rng = np.random.default_rng(2)
    X = rng.normal(size=(64, 4)).astype(np.float32) * 2 + 4
    for _ in range(3):  # compiles + promotions + first hot dispatches
        for n in names:
            engine.anomaly(n, X)
        engine.quiesce()

    def one(i):
        engine.anomaly(names[i % len(names)], X)

    n_requests = 120
    rungs = {}
    ok = True
    for workers in (1, 4, 8):
        with ThreadPoolExecutor(max_workers=workers) as pool:
            list(pool.map(one, range(2 * workers)))  # settle threads
            started = time.perf_counter()
            try:
                list(pool.map(one, range(n_requests)))
            except Exception as exc:
                ok = False
                check(False, f"{mode} {workers}w: request failed: {exc}")
                break
            rungs[workers] = n_requests / (time.perf_counter() - started)
    if ok:
        check(True, f"all requests succeeded: " + ", ".join(
            f"{w}w={rps:.0f}rps" for w, rps in rungs.items()
        ))
        stats = engine.stats()
        check(stats["max_dispatch_batch"] >= 1 and stats["dispatches"] > 0,
              f"{mode} dispatch pipeline engaged "
              f"({stats['dispatches']} dispatches, "
              f"max batch {stats['max_dispatch_batch']})")
    engine.close()


def main() -> int:
    import tempfile

    print("perf smoke: wire parity + pipeline parity + saturation sanity "
          "+ flight-recorder overhead")
    with tempfile.TemporaryDirectory() as tmp:
        client = _build_served_app(tmp)
        wire_parity(client)
        models = _build_engines()
        pipeline_parity(models)
        saturation_sweep(models, shard=False)
        saturation_sweep(models, shard=True)
        flightrec_overhead(client)
    if _failures:
        print(f"\nPERF SMOKE FAILED: {len(_failures)} check(s)",
              file=sys.stderr)
        return 1
    print("\nperf smoke passed: both wire formats agree, pipelined == "
          "serial, saturation holds up, flight recorder is free")
    return 0


if __name__ == "__main__":
    sys.exit(main())
