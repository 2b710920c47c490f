#!/usr/bin/env python
"""Quant smoke: the precision ladder's parity + routing + boot gates on
the CPU backend (``make quant-smoke``, ARCHITECTURE §19).

Checks (ISSUE 11 acceptance, minus anything rig-dependent):

- **parity budgets** — a mixed-precision fleet (f32 + bf16 + int8 rungs
  of one architecture) scores within each rung's declared error budget
  of the all-f32 reference: f32 machines BIT-identical, bf16/int8 within
  ``precision.error_budget()`` on the normalized total-score ruler;
  anomaly-threshold flip rates across precisions are measured and
  REPORTED (never silently absorbed), with a loose catastrophic-break
  gate;
- **mixed-residency routing** — under 12-thread spread traffic the fused
  megabatch path engages per precision class and never mixes dtypes:
  every bucket's stacked tree (and therefore its resident stack, which
  aliases it) is dtype-homogeneous, fused dispatches happen, and the
  concurrent scores still meet the budgets;
- **boot economics** — a warm boot of the mixed-precision fleet against
  a seeded compile-cache store pays ZERO fresh XLA compiles (each rung's
  variants cache independently under their precision-carrying keys);
- **manifest pinning e2e** — a ``--precision bf16`` artifact serves
  through the real WSGI stack with its rung on the machine-scoped
  ``/healthz`` facet, and the cache store's entries surface per-entry
  precision.

Exit codes: 0 = all checks passed, 1 = at least one failed.
"""

from __future__ import annotations

import json
import os
import sys
from concurrent.futures import ThreadPoolExecutor

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

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


def _mixed_fleet():
    """6 same-architecture machines split 2/2/2 across the ladder."""
    from gordo_components_tpu.models.synthetic_fleet import build_models

    models = build_models(6, 64, 4)
    names = sorted(models)
    precisions = {}
    for i, name in enumerate(names):
        precisions[name] = ("f32", "bf16", "int8")[i // 2]
    return models, names, precisions


def parity_budgets(models, names, precisions, X):
    import numpy as np

    from gordo_components_tpu import precision as precision_mod
    from gordo_components_tpu.server.engine import ServingEngine

    print("\n[1/4] parity budgets: mixed fleet vs the all-f32 reference")
    reference = ServingEngine(models)
    ref = {n: reference.anomaly(n, X) for n in names}
    reference.close()
    mixed = ServingEngine(models, precisions=precisions)
    drift_report = {}
    for name in names:
        rung = precisions[name]
        scored = mixed.anomaly(name, X)
        if rung == "f32":
            check(_bits(scored) == _bits(ref[name]),
                  f"{name} (f32): bit-identical to the reference")
            continue
        budget = precision_mod.error_budget(rung)
        err = precision_mod.parity_error(
            ref[name].total_anomaly_score, scored.total_anomaly_score
        )
        check(err <= budget,
              f"{name} ({rung}): parity error {err:.2e} within "
              f"budget {budget:g}")
        # anomaly-threshold drift: how often the downgraded rung flips
        # the over/under-threshold call at the f32 p90 threshold —
        # measured and reported, not silently absorbed (§19)
        f32_total = ref[name].total_anomaly_score
        threshold = float(np.percentile(f32_total, 90))
        flips = float(np.mean(
            (scored.total_anomaly_score > threshold)
            != (f32_total > threshold)
        ))
        drift_report[f"{name}:{rung}"] = round(flips, 4)
        check(flips <= 0.2,
              f"{name} ({rung}): threshold flip rate {flips:.1%} below "
              "the catastrophic-break gate (20%)")
    print(f"  threshold-drift report (flip fraction at f32 p90): "
          f"{json.dumps(drift_report)}")
    return mixed, ref


def mixed_residency_routing(mixed, ref, names, precisions, X):
    import numpy as np

    from gordo_components_tpu import precision as precision_mod

    print("\n[2/4] mixed-residency routing: fused path never mixes dtypes")
    expected_dtype = {"f32": np.float32, "bf16": None, "int8": np.int8}
    try:
        import jax.numpy as jnp

        expected_dtype["bf16"] = jnp.bfloat16
    except Exception:  # lint: allow-swallow(backends without jnp.bfloat16 just skip the dtype pin)
        pass
    import jax

    buckets = mixed._buckets
    check(len(buckets) == 3,
          f"fleet partitions into one bucket per rung ({len(buckets)})")
    for bucket in buckets:
        dtypes = {
            np.asarray(a).dtype
            for a in jax.tree_util.tree_leaves(bucket.stacked["params"])
        }
        expected = np.dtype(expected_dtype[bucket.precision])
        check(dtypes == {expected},
              f"{bucket.precision} bucket: stacked weights homogeneous "
              f"{sorted(str(d) for d in dtypes)}")
        check(bucket._mega_full,
              f"{bucket.precision} bucket: fully megabatch-resident "
              "(resident stack aliases the stacked tree)")

    before = mixed.stats()["megabatch"]["dispatches"]

    def one(t: int):
        for i in range(20):
            mixed.anomaly(names[(t + i) % len(names)], X)

    with ThreadPoolExecutor(max_workers=12) as pool:
        list(pool.map(one, range(12)))
    mixed.quiesce()
    stats = mixed.stats()
    fused = stats["megabatch"]["dispatches"] - before
    check(fused > 0, f"fused dispatches under spread traffic ({fused})")
    # post-concurrency parity: the fused path served downgraded rungs
    # within their budgets, through the same resident stacks
    for name in names:
        rung = precisions[name]
        scored = mixed.anomaly(name, X)
        if rung == "f32":
            ok = _bits(scored) == _bits(ref[name])
            label = "bit-identical"
        else:
            err = precision_mod.parity_error(
                ref[name].total_anomaly_score, scored.total_anomaly_score
            )
            ok = err <= precision_mod.error_budget(rung)
            label = f"within budget (err {err:.2e})"
        check(ok, f"{name} ({rung}) after fused traffic: {label}")
    per_rung = stats["precision"]["requests"]
    check(set(per_rung) == {"f32", "bf16", "int8"} and
          all(v > 0 for v in per_rung.values()),
          f"per-precision request accounting engaged: {per_rung}")


def warm_boot(models, precisions, tmp):
    from gordo_components_tpu.compile_cache import CompileCacheStore
    from gordo_components_tpu.observability.registry import REGISTRY
    from gordo_components_tpu.server.engine import ServingEngine

    print("\n[3/4] warm boot of the quantized fleet: zero fresh compiles")

    def fresh_compiles() -> float:
        for metric in REGISTRY.metrics():
            if metric.name == "gordo_engine_compile_seconds":
                return sum(s["count"] for s in metric.stats().values())
        return 0

    root = os.path.join(tmp, "compile-cache")
    seed = ServingEngine(
        models, precisions=precisions,
        compile_cache=CompileCacheStore(root),
    )
    seed.warmup()
    seed.close()
    store = CompileCacheStore(root)
    entries = store.entries()
    rungs = {e["precision"] for e in entries}
    check(rungs == {"f32", "bf16", "int8"},
          f"cache entries span every rung (precision-carrying keys): "
          f"{sorted(rungs)}")
    warm = ServingEngine(models, precisions=precisions, compile_cache=store)
    before = fresh_compiles()
    warm.warmup()
    check(fresh_compiles() - before == 0,
          "warm boot paid zero fresh XLA compiles")
    check(store.counters["hit"] > 0 and store.counters["invalid"] == 0
          and store.counters["stale"] == 0,
          f"warm boot was all hits ({store.counters['hit']} hits)")
    warm.close()


def manifest_pinning(tmp):
    import numpy as np
    from werkzeug.test import Client as TestClient

    from gordo_components_tpu.builder import provide_saved_model
    from gordo_components_tpu.server import build_app

    print("\n[4/4] manifest pinning e2e: --precision bf16 artifact serves")
    data_config = {
        "type": "RandomDataset",
        "train_start_date": "2023-01-01T00:00:00+00:00",
        "train_end_date": "2023-01-03T00:00:00+00:00",
        "tag_list": ["q-a", "q-b", "q-c"],
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
        "m-bf16", model_config, data_config, os.path.join(tmp, "m-bf16"),
        evaluation_config={"cv_mode": "build_only"}, precision="bf16",
    )
    client = TestClient(build_app({"m-bf16": model_dir}, project="proj"))
    health = client.get("/gordo/v0/proj/m-bf16/healthz").get_json()
    check(health.get("precision") == "bf16",
          f"machine-scoped /healthz surfaces the rung ({health})")
    X = (np.random.default_rng(4).normal(size=(64, 3)) * 2 + 4).tolist()
    response = client.post(
        "/gordo/v0/proj/m-bf16/anomaly/prediction",
        data=json.dumps({"X": X}), content_type="application/json",
    )
    check(response.status_code == 200, "bf16 artifact scores over WSGI")
    metrics = client.get("/metrics").get_json()
    ladder = metrics["engine"]["precision"]
    check(ladder["machines"].get("bf16") == 1,
          f"engine stats carry the ladder ({ladder})")


def main() -> int:
    import tempfile

    import numpy as np

    print("quant smoke: precision-ladder parity + mixed routing + warm "
          "boot + manifest pinning")
    models, names, precisions = _mixed_fleet()
    X = np.random.default_rng(11).normal(size=(64, 4)).astype(np.float32) * 2 + 4
    mixed, ref = parity_budgets(models, names, precisions, X)
    mixed_residency_routing(mixed, ref, names, precisions, X)
    mixed.close()
    with tempfile.TemporaryDirectory() as tmp:
        warm_boot(models, precisions, tmp)
        manifest_pinning(tmp)
    if _failures:
        print(f"\nQUANT SMOKE FAILED: {len(_failures)} check(s)",
              file=sys.stderr)
        return 1
    print("\nquant smoke passed: every rung within budget, fused routing "
          "dtype-homogeneous, warm boots free, manifests pin precision")
    return 0


if __name__ == "__main__":
    sys.exit(main())
