"""Stacked serving engine: numerical parity with the host anomaly path,
O(buckets) compilation, machine-id dispatch, and request micro-batching
(VERDICT r1 #2: the serving half of the north star)."""

import threading

import jax

import numpy as np
import pytest

from gordo_components_tpu.models.anomaly.diff import DiffBasedAnomalyDetector
from gordo_components_tpu.serializer import pipeline_from_definition
from gordo_components_tpu.server.engine import ServingEngine


def _anomaly_config(epochs=2, extra=None):
    dense = {"kind": "feedforward_hourglass", "epochs": epochs, "batch_size": 32}
    dense.update(extra or {})
    return {
        "DiffBasedAnomalyDetector": {
            "base_estimator": {
                "TransformedTargetRegressor": {
                    "regressor": {
                        "Pipeline": {
                            "steps": ["MinMaxScaler", {"DenseAutoEncoder": dense}]
                        }
                    },
                    "transformer": "MinMaxScaler",
                }
            }
        }
    }


def test_synthetic_fleet_is_n_machines_that_score_differently():
    """The fixture the serving tests, the drills and the entry-point dry
    run share: ``n`` replicas of one fit, each with its own perturbed
    weights, so a dispatch that confuses two machines shows as a value."""
    from gordo_components_tpu.models.synthetic_fleet import build_models

    models = build_models(3, 64, 4)
    assert sorted(models) == ["machine-0000", "machine-0001", "machine-0002"]
    X = np.random.default_rng(1).normal(size=(64, 4)).astype(np.float32) * 2 + 4
    totals = [
        float(model.anomaly(X)["total-anomaly-score"].to_numpy().mean())
        for model in models.values()
    ]
    assert np.isfinite(totals).all() and len(set(totals)) == 3
    # and one stacked engine serves all of them, each with its own score
    engine = ServingEngine(models)
    served = [
        float(np.asarray(engine.anomaly(name, X).total_anomaly_score).mean())
        for name in sorted(models)
    ]
    np.testing.assert_allclose(served, totals, rtol=1e-4)


def _lstm_config():
    return {
        "DiffBasedAnomalyDetector": {
            "base_estimator": {
                "TransformedTargetRegressor": {
                    "regressor": {
                        "Pipeline": {
                            "steps": [
                                "MinMaxScaler",
                                {
                                    "LSTMAutoEncoder": {
                                        "kind": "lstm_symmetric",
                                        "lookback_window": 8,
                                        "dims": [8],
                                        "epochs": 1,
                                        "batch_size": 16,
                                    }
                                },
                            ]
                        }
                    },
                    "transformer": "MinMaxScaler",
                }
            }
        }
    }


def _fit(config, n_rows=160, n_tags=4, seed=0, cv=True):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n_rows, n_tags)).astype(np.float32) * 3 + 5
    model = pipeline_from_definition(config)
    if cv and isinstance(model, DiffBasedAnomalyDetector):
        model.cross_validate(X, n_splits=2)
    model.fit(X)
    return model, X


@pytest.fixture(scope="module")
def fitted_pair():
    m1, X1 = _fit(_anomaly_config(), seed=1)
    m2, X2 = _fit(_anomaly_config(), seed=2)
    return {"m1": (m1, X1), "m2": (m2, X2)}


def test_parity_with_host_anomaly_path(fitted_pair):
    models = {name: m for name, (m, _) in fitted_pair.items()}
    engine = ServingEngine(models)
    for name, (model, X) in fitted_pair.items():
        scored = engine.anomaly(name, X)
        frame = model.anomaly(X)
        np.testing.assert_allclose(
            scored.model_output, frame["model-output"].values, atol=1e-4
        )
        np.testing.assert_allclose(
            scored.tag_anomaly_scores,
            frame["tag-anomaly-scores"].values,
            atol=1e-4,
        )
        np.testing.assert_allclose(
            scored.total_anomaly_score,
            np.ravel(frame["total-anomaly-score"].values),
            atol=1e-3,
        )
        np.testing.assert_allclose(scored.model_input, X, atol=1e-6)


def test_same_architecture_shares_one_bucket_and_program(fitted_pair):
    models = {name: m for name, (m, _) in fitted_pair.items()}
    engine = ServingEngine(models)
    stats = engine.stats()
    assert stats["machines"] == 2
    assert stats["buckets"] == 1
    for name, (_, X) in fitted_pair.items():
        engine.anomaly(name, X)
    # same request shape through both machines → ONE compiled program
    assert engine.stats()["compiled_programs"] == 1


@pytest.mark.slow
def test_different_architectures_get_separate_buckets(fitted_pair):
    m1, _ = fitted_pair["m1"]
    m3, _ = _fit(_anomaly_config(extra={"compression_factor": 0.25}), seed=3)
    engine = ServingEngine({"m1": m1, "m3": m3})
    assert engine.stats()["buckets"] == 2


def test_machine_id_dispatch_differs(fitted_pair):
    """Two machines in one bucket must score with their OWN weights."""
    models = {name: m for name, (m, _) in fitted_pair.items()}
    engine = ServingEngine(models)
    _, X = fitted_pair["m1"]
    out1 = engine.anomaly("m1", X).model_output
    out2 = engine.anomaly("m2", X).model_output
    assert not np.allclose(out1, out2)


@pytest.mark.slow
def test_windowed_model_parity():
    model, X = _fit(_lstm_config(), n_rows=96, seed=4)
    engine = ServingEngine({"lstm": model})
    scored = engine.anomaly("lstm", X)
    frame = model.anomaly(X)
    assert len(scored.total_anomaly_score) == len(X) - 8 + 1
    np.testing.assert_allclose(
        scored.model_output, frame["model-output"].values, atol=1e-4
    )
    np.testing.assert_allclose(
        scored.total_anomaly_score,
        np.ravel(frame["total-anomaly-score"].values),
        atol=1e-3,
    )


def test_windowed_too_few_rows_raises_value_error():
    model, _ = _fit(_lstm_config(), n_rows=96, seed=5)
    engine = ServingEngine({"lstm": model})
    with pytest.raises(ValueError, match="lookback_window"):
        engine.anomaly("lstm", np.zeros((4, 4), np.float32))


def _forecast_config(horizon=2):
    return {
        "DiffBasedAnomalyDetector": {
            "base_estimator": {
                "TransformedTargetRegressor": {
                    "regressor": {
                        "Pipeline": {
                            "steps": [
                                "MinMaxScaler",
                                {
                                    "LSTMForecast": {
                                        "kind": "lstm_symmetric",
                                        "lookback_window": 8,
                                        "horizon": horizon,
                                        "dims": [8],
                                        "epochs": 1,
                                        "batch_size": 16,
                                    }
                                },
                            ]
                        }
                    },
                    "transformer": "MinMaxScaler",
                }
            }
        }
    }


@pytest.mark.slow
def test_forecast_horizon_parity():
    """VERDICT r2 #3: forecast configs (incl. multi-step horizon) serve
    through the stacked engine with host-path parity, not the slow path."""
    horizon = 2
    model, X = _fit(_forecast_config(horizon), n_rows=96, seed=9)
    engine = ServingEngine({"fc": model})
    assert engine.can_score("fc"), engine.stats()["host_path_machines"]
    scored = engine.anomaly("fc", X)
    frame = model.anomaly(X)
    assert len(scored.total_anomaly_score) == len(X) - 8 + 1 - horizon
    np.testing.assert_allclose(
        scored.model_output, frame["model-output"].values, atol=1e-4
    )
    np.testing.assert_allclose(
        scored.tag_anomaly_scores, frame["tag-anomaly-scores"].values, atol=1e-4
    )
    np.testing.assert_allclose(
        scored.total_anomaly_score,
        np.ravel(frame["total-anomaly-score"].values),
        atol=1e-3,
    )


_SUBSET_COLS = [1, 3]


@pytest.fixture(scope="module")
def fitted_subset():
    """A target_tag_list machine (targets = input cols 1,3 of 5) + its
    training data — shared by the host-parity and shard-parity tests."""
    rng = np.random.default_rng(10)
    X = rng.normal(size=(160, 5)).astype(np.float32) * 3 + 5
    model = pipeline_from_definition(_anomaly_config())
    model.cross_validate(X, X[:, _SUBSET_COLS], n_splits=2)
    model.fit(X, X[:, _SUBSET_COLS])
    return model, X


@pytest.mark.slow
def test_target_subset_parity(fitted_subset):
    """A target_tag_list machine (T-of-F subset targets) lifts into the
    engine when the target→input column mapping is provided, with exact
    host-path parity against anomaly(X, y=X[:, cols])."""
    cols = _SUBSET_COLS
    model, X = fitted_subset
    engine = ServingEngine({"sub": model}, target_cols={"sub": cols})
    assert engine.can_score("sub"), engine.stats()["host_path_machines"]
    scored = engine.anomaly("sub", X)
    frame = model.anomaly(X, y=X[:, cols])
    assert scored.model_output.shape == (160, 2)
    assert scored.model_input.shape == (160, 5)
    np.testing.assert_allclose(
        scored.model_output, frame["model-output"].values, atol=1e-4
    )
    np.testing.assert_allclose(
        scored.tag_anomaly_scores, frame["tag-anomaly-scores"].values, atol=1e-4
    )
    np.testing.assert_allclose(
        scored.total_anomaly_score,
        np.ravel(frame["total-anomaly-score"].values),
        atol=1e-3,
    )

    # same machine WITHOUT the mapping: host path, visible in stats
    blind = ServingEngine({"sub": model})
    assert not blind.can_score("sub")
    assert "sub" in blind.stats()["host_path_machines"]
    assert "subset" in blind.stats()["host_path_machines"]["sub"]


@pytest.mark.slow
def test_patchtst_machine_lifts_into_engine():
    """The transformer kind serves through the stacked engine like any zoo
    model — parity with its host anomaly path."""
    config = {
        "DiffBasedAnomalyDetector": {
            "base_estimator": {
                "TransformedTargetRegressor": {
                    "regressor": {
                        "PatchTSTAutoEncoder": {
                            "lookback_window": 16, "patch_length": 8,
                            "d_model": 16, "n_heads": 2, "n_layers": 1,
                            "epochs": 1, "batch_size": 16,
                        }
                    },
                    "transformer": "MinMaxScaler",
                }
            }
        }
    }
    model, X = _fit(config, n_rows=96, seed=13)
    engine = ServingEngine({"pt": model})
    assert engine.can_score("pt"), engine.stats()["host_path_machines"]
    scored = engine.anomaly("pt", X)
    frame = model.anomaly(X)
    assert len(scored.total_anomaly_score) == len(X) - 16 + 1
    np.testing.assert_allclose(
        scored.model_output, frame["model-output"].values, atol=1e-4
    )
    np.testing.assert_allclose(
        scored.total_anomaly_score,
        np.ravel(frame["total-anomaly-score"].values),
        atol=1e-3,
    )


@pytest.mark.slow
def test_mesh_sharded_engine_parity(fitted_pair):
    """Capacity mode: stacked params shard over the 8-device mesh (machine
    axis padded to a mesh multiple) and every score matches the
    single-device engine bit-for-bit-close — including a machine count that
    does NOT divide the mesh."""
    from gordo_components_tpu.parallel.mesh import fleet_mesh

    models = {name: m for name, (m, _) in fitted_pair.items()}  # 2 machines
    mesh = fleet_mesh(8)
    sharded = ServingEngine(models, mesh=mesh)
    plain = ServingEngine(models)
    for name, (_, X) in fitted_pair.items():
        a = sharded.anomaly(name, X)
        b = plain.anomaly(name, X)
        np.testing.assert_allclose(a.model_output, b.model_output, atol=1e-5)
        np.testing.assert_allclose(
            a.total_anomaly_score, b.total_anomaly_score, atol=1e-4
        )
    # the stacked pytree really is sharded over the mesh
    leaf = jax.tree_util.tree_leaves(sharded._buckets[0].stacked)[0]
    assert len(leaf.sharding.device_set) == 8


@pytest.mark.slow
def test_mesh_sharded_engine_concurrent_dispatch(fitted_pair):
    """Sharded executions carry collectives whose in-process rendezvous
    must never interleave: two buckets hammered from 12 threads through
    the shared dispatch lock must neither deadlock nor corrupt results
    (this scenario aborted the process before the lock existed)."""
    from gordo_components_tpu.parallel.mesh import fleet_mesh

    m1, X1 = fitted_pair["m1"]
    m3, _ = _fit(_anomaly_config(extra={"compression_factor": 0.25}), seed=31)
    engine = ServingEngine({"m1": m1, "m3": m3}, mesh=fleet_mesh(8))
    assert engine.stats()["buckets"] == 2  # cross-bucket concurrency
    expected = {
        "m1": engine.anomaly("m1", X1).total_anomaly_score,
        "m3": engine.anomaly("m3", X1).total_anomaly_score,
    }
    errors, results = [], {}

    def work(name, i):
        try:
            results[(name, i)] = engine.anomaly(name, X1).total_anomaly_score
        except Exception as exc:  # pragma: no cover
            errors.append(exc)

    threads = [
        threading.Thread(target=work, args=(name, i))
        for i in range(6)
        for name in ("m1", "m3")
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errors
    assert len(results) == 12
    for (name, _), total in results.items():
        np.testing.assert_allclose(total, expected[name], atol=1e-4)


def test_unsupported_model_is_skipped():
    class Opaque:
        def predict(self, X):
            return np.asarray(X)

    engine = ServingEngine({"weird": Opaque()})
    assert not engine.can_score("weird")
    assert engine.stats()["machines"] == 0


def test_unfitted_error_scaler_scores_raw_errors():
    """No cross_validate → unfitted error scaler → raw |residuals| (the
    DiffBasedAnomalyDetector fallback), not garbage."""
    model, X = _fit(_anomaly_config(), seed=6, cv=False)
    engine = ServingEngine({"m": model})
    scored = engine.anomaly("m", X)
    expected = np.abs(X - scored.model_output)
    np.testing.assert_allclose(scored.tag_anomaly_scores, expected, atol=1e-5)


def test_require_thresholds_unfitted_is_not_lifted():
    """require_thresholds + no cross_validate must keep the host path's
    refusal (HTTP 400), not engine-served raw errors."""
    config = _anomaly_config()
    config["DiffBasedAnomalyDetector"]["require_thresholds"] = True
    model, X = _fit(config, seed=7, cv=False)
    engine = ServingEngine({"m": model})
    assert not engine.can_score("m")


def test_non_affine_target_transformer_is_not_lifted():
    """A FunctionTransformer target scaler can't be stacked as an affine —
    the machine must fall back to the host path, not serve wrong numbers."""
    config = _anomaly_config()
    config["DiffBasedAnomalyDetector"]["base_estimator"][
        "TransformedTargetRegressor"
    ]["transformer"] = {
        "FunctionTransformer": {
            "func": "gordo_components_tpu.models.transformers.multiply",
            "kw_args": {"factor": 2.0},
        }
    }
    model, X = _fit(config, seed=8, cv=False)
    engine = ServingEngine({"m": model})
    assert not engine.can_score("m")


@pytest.mark.slow
def test_long_request_chunked_scoring_parity():
    """Requests beyond max_rows_dispatch score in overlapping chunks whose
    stitched result is identical to an unchunked dispatch (VERDICT r2 weak
    #6: no more unbounded power-of-two program growth on backfills)."""
    rng = np.random.default_rng(11)
    long_X = rng.normal(size=(300, 4)).astype(np.float32) * 3 + 5

    # windowed model (L=8): chunk overlap must stitch without gap/dup
    model, _ = _fit(_lstm_config(), n_rows=96, seed=11)
    chunky = ServingEngine({"m": model}, max_rows_dispatch=64,
                           min_rows_bucket=16)
    whole = ServingEngine({"m": model}, min_rows_bucket=16)
    a = chunky.anomaly("m", long_X)
    b = whole.anomaly("m", long_X)
    assert len(a.total_anomaly_score) == 300 - 8 + 1
    np.testing.assert_allclose(a.model_output, b.model_output, atol=1e-5)
    np.testing.assert_allclose(a.model_input, b.model_input, atol=1e-6)
    np.testing.assert_allclose(
        a.total_anomaly_score, b.total_anomaly_score, atol=1e-4
    )
    # the chunked engine never compiled a >64-row program (program keys
    # are (rows, k) for the cold path, ("mega"|"hot", rows, k) otherwise)
    assert all(
        key[-2] <= 64
        for bucket in chunky._buckets
        for key in bucket._programs
    )

    # flat model: zero overlap, plain row chunks
    dense_model, _ = _fit(_anomaly_config(), seed=12)
    chunky_d = ServingEngine({"d": dense_model}, max_rows_dispatch=64,
                             min_rows_bucket=16)
    whole_d = ServingEngine({"d": dense_model}, min_rows_bucket=16)
    a = chunky_d.anomaly("d", long_X)
    b = whole_d.anomaly("d", long_X)
    assert len(a.total_anomaly_score) == 300
    np.testing.assert_allclose(a.model_output, b.model_output, atol=1e-5)
    np.testing.assert_allclose(
        a.total_anomaly_score, b.total_anomaly_score, atol=1e-4
    )


def test_concurrent_requests_micro_batch(fitted_pair):
    models = {name: m for name, (m, _) in fitted_pair.items()}
    engine = ServingEngine(models)
    _, X = fitted_pair["m1"]
    # warm the program so worker threads pile up behind the busy lock
    engine.anomaly("m1", X)
    sequential = {
        name: engine.anomaly(name, fitted_pair[name][1]).total_anomaly_score
        for name in fitted_pair
    }
    results = {}
    errors = []

    def work(name, i):
        try:
            scored = engine.anomaly(name, fitted_pair[name][1])
            results[(name, i)] = scored.total_anomaly_score
        except Exception as exc:  # pragma: no cover
            errors.append(exc)

    threads = [
        threading.Thread(target=work, args=(name, i))
        for i in range(8)
        for name in fitted_pair
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not errors
    assert len(results) == 16
    for (name, _), total in results.items():
        np.testing.assert_allclose(total, sequential[name], atol=1e-4)


def test_engine_warmup_compiles_bucket_programs(fitted_pair):
    engine = ServingEngine({name: m for name, (m, _) in fitted_pair.items()})
    assert engine.stats()["compiled_programs"] == 0
    warmed = engine.warmup()
    assert warmed == engine.stats()["buckets"]
    assert engine.stats()["compiled_programs"] >= warmed
    # warm again: idempotent, no new programs for the same shapes
    before = engine.stats()["compiled_programs"]
    engine.warmup()
    assert engine.stats()["compiled_programs"] == before


def test_engine_warmup_failure_names_the_bucket(fitted_pair):
    """run_server logs a failed warm-up and serves on; the error has to say
    WHICH bucket could not warm, or the log line is useless on a fleet."""
    from gordo_components_tpu.resilience import faults

    engine = ServingEngine({name: m for name, (m, _) in fitted_pair.items()})
    faults.configure("engine-dispatch:*:error")
    try:
        with pytest.raises(RuntimeError) as exc:
            engine.warmup()
    finally:
        faults.configure("")
        engine.close()
    bucket = engine._buckets[0]
    assert f"warm-up failed for bucket {bucket.shape_key}" in str(exc.value)
    assert repr(bucket.names[0]) in str(exc.value)
    assert exc.value.__cause__ is not None


@pytest.mark.slow
def test_mesh_sharded_engine_forecast_and_target_subset_parity(fitted_subset):
    """Capacity mode x the non-reconstruction lifts: a multi-step forecast
    machine and a target_tag_list machine served from MESH-SHARDED stacked
    params must match their replicated-engine scores exactly — the
    per-machine gather must compose with the windowed forecast program and
    with the per-machine target-column gather, not just with the dense
    reconstruction path the existing shard-parity test covers."""
    from gordo_components_tpu.parallel.mesh import fleet_mesh

    horizon = 2
    fmodel, fX = _fit(_forecast_config(horizon), n_rows=96, seed=9)
    smodel, sX = fitted_subset

    models = {"fc": fmodel, "sub": smodel}
    target_cols = {"sub": _SUBSET_COLS}
    sharded = ServingEngine(models, mesh=fleet_mesh(8), target_cols=target_cols)
    plain = ServingEngine(models, target_cols=target_cols)
    assert sharded.can_score("fc") and sharded.can_score("sub"), (
        sharded.stats()["host_path_machines"]
    )
    # the lifts must really be running sharded, or parity is vacuous
    for bucket in sharded._buckets:
        leaf = jax.tree_util.tree_leaves(bucket.stacked)[0]
        assert len(leaf.sharding.device_set) == 8, bucket.names
    for name, X in (("fc", fX), ("sub", sX)):
        a = sharded.anomaly(name, X)
        b = plain.anomaly(name, X)
        np.testing.assert_allclose(a.model_output, b.model_output, atol=1e-5)
        np.testing.assert_allclose(
            a.total_anomaly_score, b.total_anomaly_score, atol=1e-4
        )


@pytest.mark.slow
def test_mesh_sharded_hot_cache_promotes_and_matches(fitted_pair, monkeypatch):
    """ROADMAP #3: shard-mode hot-machine cache. A machine's 2nd cold
    request promotes an unsharded device copy; later requests score
    through the replicated hot program with scores IDENTICAL to the
    sharded path, stats expose the cache, and a cap of 1 LRU-evicts."""
    from gordo_components_tpu.parallel.mesh import fleet_mesh
    from gordo_components_tpu.server.engine import _Bucket

    # freshness guard off: this test exercises the eviction mechanics
    # directly (test_mesh_sharded_hot_cache_freshness_guard covers the
    # guard itself)
    monkeypatch.setattr(_Bucket, "_HOT_EVICT_AFTER", 0)
    models = {name: m for name, (m, _) in fitted_pair.items()}  # 2 machines
    engine = ServingEngine(models, mesh=fleet_mesh(8), hot_cap=1)
    plain = ServingEngine(models)
    (n1, (_, X1)), (n2, (_, X2)) = sorted(fitted_pair.items())

    cold = engine.anomaly(n1, X1)  # hit 1: cold
    engine.quiesce()
    assert engine.stats()["hot_machines"] == 0
    engine.anomaly(n1, X1)  # hit 2: cold, then promoted
    engine.quiesce()  # promotion rides the fetch stage (pipelined dispatch)
    assert engine.stats()["hot_machines"] == 1
    hot = engine.anomaly(n1, X1)  # served from the hot copy
    stats = engine.stats()
    assert stats["hot_requests"] == 1
    np.testing.assert_allclose(
        hot.total_anomaly_score, cold.total_anomaly_score, atol=1e-6
    )
    np.testing.assert_allclose(
        hot.total_anomaly_score,
        plain.anomaly(n1, X1).total_anomaly_score,
        atol=1e-4,
    )

    # cap=1: promoting the second machine evicts the first (LRU)
    engine.anomaly(n2, X2)
    engine.anomaly(n2, X2)
    engine.quiesce()
    assert engine.stats()["hot_machines"] == 1
    engine.anomaly(n2, X2)
    assert engine.stats()["hot_requests"] == 2
    # the evicted machine re-earns promotion from zero hits
    engine.anomaly(n1, X1)
    engine.quiesce()
    assert engine.stats()["hot_machines"] == 1  # still only n2 hot
    engine.anomaly(n1, X1)  # 2nd post-eviction cold hit -> promoted again
    engine.quiesce()
    final = engine.anomaly(n1, X1)
    np.testing.assert_allclose(
        final.total_anomaly_score, cold.total_anomaly_score, atol=1e-6
    )
    assert engine.stats()["hot_requests"] == 3


@pytest.mark.slow
def test_mesh_sharded_hot_cache_freshness_guard(fitted_pair):
    """A full cache with a LIVE working set must not thrash: promoting a
    new machine would evict an entry that served a hot request within the
    freshness window, so the promotion is skipped — spread traffic over
    more machines than hot_cap pays zero promote/evict gather churn
    (measured ~15-30% concurrent-throughput cost without the guard)."""
    from gordo_components_tpu.parallel.mesh import fleet_mesh

    models = {name: m for name, (m, _) in fitted_pair.items()}  # 2 machines
    engine = ServingEngine(models, mesh=fleet_mesh(8), hot_cap=1)
    (n1, (_, X1)), (n2, (_, X2)) = sorted(fitted_pair.items())

    engine.anomaly(n1, X1)
    engine.anomaly(n1, X1)  # promoted
    engine.quiesce()  # promotion rides the fetch stage (pipelined dispatch)
    engine.anomaly(n1, X1)  # hot -> last_use fresh
    assert engine.stats()["hot_machines"] == 1
    # n2 earns promotion-by-hits, but n1's slot is freshly used: skipped
    for _ in range(4):
        engine.anomaly(n2, X2)
    engine.quiesce()
    stats = engine.stats()
    assert stats["hot_machines"] == 1
    # ... and n1 still serves hot (was never evicted)
    before = stats["hot_requests"]
    engine.anomaly(n1, X1)
    assert engine.stats()["hot_requests"] == before + 1


@pytest.mark.slow
def test_mesh_sharded_hot_cache_stable_under_uniform_spread():
    """The freshness window scales with the bucket's fleet size: uniform
    round-robin over M machines touches each hot entry only every ~M
    dispatches, so the old FIXED 64-dispatch window evicted live entries
    on every fleet cycle once M > 64 — promote/evict gather churn inside
    steady state. With the scaled window
    the working set must not rotate at all under uniform spread."""
    from gordo_components_tpu.models.synthetic_fleet import build_models
    from gordo_components_tpu.parallel.mesh import fleet_mesh
    from gordo_components_tpu.server.engine import ServingEngine

    machines = 72  # > the 64-dispatch base window: the churn regime
    models = build_models(machines, 64, 4)
    engine = ServingEngine(models, mesh=fleet_mesh(8), hot_cap=2)
    names = engine.machines()
    rng = np.random.default_rng(6)
    X = rng.normal(size=(64, 4)).astype(np.float32) * 2 + 4

    for _ in range(2):  # pass 2 promotes the first hot_cap machines
        for name in names:
            engine.anomaly(name, X)
    engine.quiesce()  # promotions ride the fetch stage
    bucket, _ = engine._by_name[names[0]]
    working_set = set(bucket._hot)
    assert len(working_set) == 2
    for _ in range(2):  # uniform spread: the set must hold, not rotate
        for name in names:
            engine.anomaly(name, X)
    engine.quiesce()
    assert set(bucket._hot) == working_set
    # ... and the hot machines really served hot through those passes
    assert engine.stats()["hot_requests"] >= 4


@pytest.mark.slow
def test_mesh_sharded_steady_state_tail_latency_bounded():
    """VERDICT r4 #4: steady-state sharded p99 must stay within a small
    multiple of p50 under concurrent mixed-machine traffic. The r4
    artifact's 540 ms p99 (170x the median) was first-dispatch compiles
    and hot-program compiles landing inside the percentile window — after
    a proper warmup (every machine served three times, every power-of-two
    batch program executed once), nothing in the steady-state path may
    cost compile-scale time."""
    import time
    from concurrent.futures import ThreadPoolExecutor

    from gordo_components_tpu.models.synthetic_fleet import build_models
    from gordo_components_tpu.parallel.mesh import fleet_mesh
    from gordo_components_tpu.server.engine import ServingEngine

    models = build_models(24, 64, 4)
    engine = ServingEngine(models, mesh=fleet_mesh(8), hot_cap=4)
    names = engine.machines()
    rng = np.random.default_rng(5)
    X = rng.normal(size=(64, 4)).astype(np.float32) * 2 + 4

    for _ in range(3):  # compiles, promotions, first hot dispatches
        for name in names:
            engine.anomaly(name, X)
    engine.quiesce()  # promotions ride the fetch stage
    # deterministically warm EVERY coalesced power-of-two batch program
    # (cold and hot variants): which sizes concurrent traffic produces is
    # timing-dependent, and one unwarmed size compiling mid-measurement
    # is a ~1 s outlier that IS the old flake this test exists to catch
    bucket, idx0 = engine._by_name[names[0]]
    x_padded, _ = engine._prepare(bucket, X)
    rows_padded = x_padded.shape[0]
    kb = 1
    while kb <= 8:  # max coalesced batch = worker count (8)
        xs_kb = jax.device_put(np.repeat(x_padded[None], kb, axis=0))
        idxs_kb = jax.device_put(np.full((kb,), idx0, np.int32))
        jax.block_until_ready(
            bucket._program(rows_padded, kb)(bucket.stacked, idxs_kb, xs_kb)
        )
        if bucket._hot:
            hot_idx = next(iter(bucket._hot))
            jax.block_until_ready(
                bucket._hot_program(rows_padded, kb)(
                    bucket._hot[hot_idx], np.asarray(xs_kb)
                )
            )
        kb *= 2

    def one(i: int) -> float:
        started = time.perf_counter()
        engine.anomaly(names[i % len(names)], X)
        return time.perf_counter() - started

    with ThreadPoolExecutor(max_workers=8) as pool:
        list(pool.map(one, range(64)))  # settle pool threads
        lats = list(pool.map(one, range(200)))
    lat_ms = np.asarray(lats) * 1000.0
    p50 = float(np.percentile(lat_ms, 50))
    p99 = float(np.percentile(lat_ms, 99))
    # 10x p50 with an absolute floor for scheduler noise on a shared CI
    # box; a compile (>150 ms measured) or promotion-thrash gather in the
    # window blows straight through either bound
    assert p99 <= max(10.0 * p50, 75.0), (p50, p99)


@pytest.mark.slow
def test_mesh_sharded_hot_cache_demotes_failing_entry(fitted_pair):
    """ADVICE r4: a failing hot copy must not permanently fail its
    machine's pure-hot batches. The engine demotes the entry on a hot
    dispatch error and scores the SAME request through the sharded cold
    path — the client sees a correct answer, not the hot path's
    exception — and the machine re-earns promotion afterwards."""
    from gordo_components_tpu.parallel.mesh import fleet_mesh

    models = {name: m for name, (m, _) in fitted_pair.items()}
    engine = ServingEngine(models, mesh=fleet_mesh(8), hot_cap=4)
    (n1, (_, X1)), _ = sorted(fitted_pair.items())

    cold = engine.anomaly(n1, X1)
    engine.anomaly(n1, X1)  # promoted
    engine.quiesce()  # promotion rides the fetch stage (pipelined dispatch)
    assert engine.stats()["hot_machines"] == 1
    bucket, _idx = engine._by_name[n1]

    def poisoned(rows, k):
        raise RuntimeError("injected hot-dispatch failure")

    bucket._hot_program = poisoned  # instance override, cold path untouched
    try:
        served = engine.anomaly(n1, X1)  # must fall back, not raise
    finally:
        del bucket._hot_program
    np.testing.assert_allclose(
        served.total_anomaly_score, cold.total_anomaly_score, atol=1e-6
    )
    assert engine.stats()["hot_machines"] == 0  # demoted
    # re-promotion backs off: one past demotion raises the hit threshold
    # 2 -> 16 so a deterministically failing hot program can't oscillate
    # promote->fail->demote on every other cold hit. The fallback cold
    # dispatch above already counted as hit 1.
    for _ in range(14):
        engine.anomaly(n1, X1)
    engine.quiesce()
    assert engine.stats()["hot_machines"] == 0  # still backing off
    engine.anomaly(n1, X1)  # hit 16 -> re-promoted (hot path repaired)
    engine.quiesce()
    assert engine.stats()["hot_machines"] == 1
    before = engine.stats()["hot_requests"]
    again = engine.anomaly(n1, X1)
    assert engine.stats()["hot_requests"] == before + 1
    np.testing.assert_allclose(
        again.total_anomaly_score, cold.total_anomaly_score, atol=1e-6
    )
