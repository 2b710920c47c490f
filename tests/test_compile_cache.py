"""Persistent compile cache: AOT-serialized executables in the model
store (ISSUE 6). Warm boots must be load-not-compile, every cache failure
mode must fall back to JIT with bit-identical scores, and the CLI verbs
must hold the operator contract."""

import json
import os

import numpy as np
import pytest
from click.testing import CliRunner

from gordo_components_tpu.compile_cache import (
    CompileCacheStore,
    backend_fingerprint,
    canonical,
    entry_name,
    full_key,
    resolve_store,
)
from gordo_components_tpu.compile_cache.store import (
    EXEC_FILE,
    KEY_FILE,
    STORE_ENV,
)
from gordo_components_tpu.observability.registry import REGISTRY
from gordo_components_tpu.serializer import pipeline_from_definition
from gordo_components_tpu.server.engine import ServingEngine


def _config():
    return {
        "DiffBasedAnomalyDetector": {
            "base_estimator": {
                "TransformedTargetRegressor": {
                    "regressor": {
                        "Pipeline": {
                            "steps": [
                                "MinMaxScaler",
                                {"DenseAutoEncoder": {
                                    "kind": "feedforward_hourglass",
                                    "epochs": 1, "batch_size": 32,
                                }},
                            ]
                        }
                    },
                    "transformer": "MinMaxScaler",
                }
            }
        }
    }


@pytest.fixture(scope="module")
def fitted_models():
    rng = np.random.default_rng(5)
    X = rng.normal(size=(160, 4)).astype(np.float32) * 3 + 5
    models = {}
    for i in range(2):
        model = pipeline_from_definition(_config())
        model.cross_validate(X, n_splits=2)
        model.fit(X)
        models[f"m{i}"] = model
    return models, X


def _bits(result):
    return tuple(
        np.asarray(a).tobytes()
        for a in (result.model_input, result.model_output,
                  result.tag_anomaly_scores, result.total_anomaly_score)
    )


def _fresh_compiles():
    for metric in REGISTRY.metrics():
        if metric.name == "gordo_engine_compile_seconds":
            return sum(s["count"] for s in metric.stats().values())
    return 0


# -- key / fingerprint ------------------------------------------------------
def test_fingerprint_names_toolchain_and_topology():
    fingerprint = backend_fingerprint()
    for field in ("jax", "jaxlib", "platform", "device_kind", "n_devices",
                  "machine"):
        assert field in fingerprint


def test_entry_name_is_stable_and_key_sensitive():
    key_a = full_key({"kind": "serving-cold", "rows": 64})
    key_b = full_key({"kind": "serving-cold", "rows": 128})
    assert entry_name(key_a) == entry_name(key_a)
    assert entry_name(key_a) != entry_name(key_b)
    assert entry_name(key_a).startswith("cc-")
    # canonical rendering is whitespace-free and deterministic
    assert canonical(key_a) == canonical(json.loads(canonical(key_a)))


def test_resolve_store_precedence(tmp_path, monkeypatch):
    monkeypatch.delenv(STORE_ENV, raising=False)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert resolve_store() is None
    assert resolve_store(models_root=str(tmp_path)).root == str(
        tmp_path / ".compile-cache"
    )
    # the default follows the operator's placement of JAX's own cache (a
    # models tree built into a fresh directory must still find the store);
    # without a models tree nothing anchors a store either way
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "placed"))
    assert resolve_store() is None
    assert resolve_store(models_root=str(tmp_path)).root == str(
        tmp_path / "placed" / "serving-aot"
    )
    monkeypatch.setenv(STORE_ENV, str(tmp_path / "env-root"))
    assert resolve_store(models_root=str(tmp_path)).root == str(
        tmp_path / "env-root"
    )
    assert resolve_store(
        explicit=str(tmp_path / "explicit"), models_root=str(tmp_path)
    ).root == str(tmp_path / "explicit")
    # "off" disables at any level
    assert resolve_store(explicit="off", models_root=str(tmp_path)) is None
    monkeypatch.setenv(STORE_ENV, "off")
    assert resolve_store(models_root=str(tmp_path)) is None


# -- store roundtrip through the engine -------------------------------------
def test_warm_boot_is_load_not_compile_and_bit_identical(
    fitted_models, tmp_path
):
    models, X = fitted_models
    plain = ServingEngine(models)
    ref = {n: _bits(plain.anomaly(n, X)) for n in sorted(models)}
    plain.close()

    store = CompileCacheStore(str(tmp_path / "cc"))
    cold = ServingEngine(models, compile_cache=store)
    before = _fresh_compiles()
    cold.warmup()
    assert _fresh_compiles() - before > 0  # cold boot pays the compile
    assert store.counters["write"] > 0
    assert {n: _bits(cold.anomaly(n, X)) for n in sorted(models)} == ref
    cold.close()

    store2 = CompileCacheStore(str(tmp_path / "cc"))
    warm = ServingEngine(models, compile_cache=store2)
    before = _fresh_compiles()
    warm.warmup()
    assert _fresh_compiles() - before == 0  # the acceptance gate
    assert store2.counters["hit"] > 0
    assert store2.counters["invalid"] == store2.counters["stale"] == 0
    assert {n: _bits(warm.anomaly(n, X)) for n in sorted(models)} == ref
    stats = warm.stats()
    assert stats["compile_cache"]["hit"] == store2.counters["hit"]
    warm.close()


def test_corrupt_entry_falls_back_and_self_heals(fitted_models, tmp_path):
    models, X = fitted_models
    root = str(tmp_path / "cc")
    seed = ServingEngine(models, compile_cache=CompileCacheStore(root))
    seed.warmup()
    ref = _bits(seed.anomaly("m0", X))
    seed.close()

    store = CompileCacheStore(root)
    # corrupt EVERY entry (not just the name-sorted first): which entry
    # hashes first shifts whenever the key schema grows a field, and the
    # fallback assertion needs a corrupted entry the warmup actually
    # looks up
    for entry in store.entries():
        target = os.path.join(root, entry["name"], EXEC_FILE)
        with open(target, "r+b") as fh:
            data = bytearray(fh.read())
            data[10] ^= 0xFF
            fh.seek(0)
            fh.write(data)
    fallback = ServingEngine(models, compile_cache=store)
    fallback.warmup()  # must not raise — never-fatal contract
    assert store.counters["invalid"] > 0
    assert _bits(fallback.anomaly("m0", X)) == ref
    fallback.close()
    # the write-back replaced the damaged entry whole
    assert all(e["verified"] for e in CompileCacheStore(root).entries())


def test_key_mismatch_reads_stale(fitted_models, tmp_path):
    from gordo_components_tpu.store.manifest import write_manifest

    models, X = fitted_models
    root = str(tmp_path / "cc")
    seed = ServingEngine(models, compile_cache=CompileCacheStore(root))
    seed.warmup()
    seed.close()
    store = CompileCacheStore(root)
    entry_dir = os.path.join(root, store.entries()[0]["name"])
    key_path = os.path.join(entry_dir, KEY_FILE)
    with open(key_path) as fh:
        stored = fh.read()
    with open(key_path, "w") as fh:
        fh.write(stored.replace('"jaxlib":"', '"jaxlib":"9.9.9-'))
    write_manifest(entry_dir)  # checksums pass; only the KEY disagrees
    store2 = CompileCacheStore(root)
    engine = ServingEngine(models, compile_cache=store2)
    engine.warmup()
    assert store2.counters["stale"] > 0
    engine.close()


def test_one_device_program_reloads_as_one_device_program(tmp_path):
    """On the 8-virtual-device platform jax's loader defaults to EVERY
    backend device and reloads a one-device executable as an 8-shard one
    no call can satisfy; the store loads it for the devices it was
    compiled for."""
    import jax
    import jax.numpy as jnp

    assert len(jax.devices()) == 8
    compiled = (
        jax.jit(lambda x: x * 2.0)
        .lower(jax.ShapeDtypeStruct((4,), jnp.float32))
        .compile()
    )
    store = CompileCacheStore(str(tmp_path / "cc"))
    key = {"kind": "serving-cold", "probe": "one-device"}
    assert store.put(key, compiled)
    loaded = store.get(key, jax.devices()[:1])
    assert loaded is not None and store.counters["hit"] == 1
    (arg_sharding,), _ = loaded.input_shardings
    assert arg_sharding.device_set == {jax.devices()[0]}
    np.testing.assert_array_equal(
        np.asarray(loaded(np.ones((4,), np.float32))), np.full((4,), 2.0)
    )


def test_put_never_raises_on_unserializable():
    store = CompileCacheStore("/nonexistent-root-never-created")
    assert store.put({"kind": "serving-cold"}, object()) is False
    assert store.counters["write_error"] == 1


def test_purge_and_entries(tmp_path, fitted_models):
    models, _ = fitted_models
    root = str(tmp_path / "cc")
    engine = ServingEngine(
        models, compile_cache=CompileCacheStore(root)
    )
    engine.warmup()
    engine.close()
    store = CompileCacheStore(root)
    entries = store.entries()
    assert entries and all(e["verified"] and e["current"] for e in entries)
    # replicated warmup routes through the megabatch program (ARCH §15),
    # so a warmed cache holds serving-mega entries (serving-cold appears
    # once the cold fallback path compiles)
    assert all(
        e["program"]["kind"] in ("serving-cold", "serving-mega")
        for e in entries
    )
    assert any(e["program"]["kind"] == "serving-mega" for e in entries)
    # stale-only purge keeps current entries; full purge clears
    assert store.purge(stale_only=True) == []
    removed = store.purge()
    assert sorted(removed) == sorted(e["name"] for e in entries)
    assert store.entries() == []


# -- precision key variants (§19) -------------------------------------------
def test_two_precisions_cache_as_two_entries(fitted_models, tmp_path):
    """One machine built at two rungs yields two independent cc-<sha>
    entries: the precision field partitions the key space."""
    models, X = fitted_models
    root = str(tmp_path / "cc")
    f32 = ServingEngine(models, compile_cache=CompileCacheStore(root))
    f32.warmup()
    f32.close()
    store = CompileCacheStore(root)
    f32_names = {e["name"] for e in store.entries()}
    assert all(e["precision"] == "f32" for e in store.entries())
    bf16 = ServingEngine(
        models, compile_cache=store,
        precisions={name: "bf16" for name in models},
    )
    bf16.warmup()
    bf16.close()
    entries = CompileCacheStore(root).entries()
    bf16_names = {e["name"] for e in entries if e["precision"] == "bf16"}
    assert bf16_names and not (bf16_names & f32_names)
    assert {e["precision"] for e in entries} == {"f32", "bf16"}


def test_precision_flip_is_clean_miss_never_stale_hit(fitted_models, tmp_path):
    """Flipping a machine's precision against an existing store is a
    clean MISS + JIT fallback — never a hit (or stale read) of the other
    variant's binary."""
    models, X = fitted_models
    root = str(tmp_path / "cc")
    seed = ServingEngine(models, compile_cache=CompileCacheStore(root))
    seed.warmup()
    ref = {n: _bits(seed.anomaly(n, X)) for n in sorted(models)}
    seed.close()

    store = CompileCacheStore(root)
    flipped = ServingEngine(
        models, compile_cache=store,
        precisions={name: "int8" for name in models},
    )
    before = _fresh_compiles()
    flipped.warmup()
    # the f32 entries never satisfied an int8 lookup: every int8 program
    # missed (then compiled + wrote back); nothing read stale or invalid
    assert store.counters["miss"] > 0
    assert store.counters["hit"] == 0
    assert store.counters["stale"] == store.counters["invalid"] == 0
    assert _fresh_compiles() - before > 0  # honest JIT/AOT fallback
    flipped.close()
    # and the f32 variant still hits untouched afterwards, bit-identical
    store2 = CompileCacheStore(root)
    back = ServingEngine(models, compile_cache=store2)
    before = _fresh_compiles()
    back.warmup()
    assert _fresh_compiles() - before == 0
    assert store2.counters["hit"] > 0
    assert {n: _bits(back.anomaly(n, X)) for n in sorted(models)} == ref
    back.close()


# -- server wiring ----------------------------------------------------------
def test_server_defaults_cache_on_models_root(tmp_path, monkeypatch):
    monkeypatch.delenv(STORE_ENV, raising=False)
    from gordo_components_tpu.builder import provide_saved_model
    from gordo_components_tpu.server import build_app

    data_config = {
        "type": "RandomDataset",
        "train_start_date": "2023-01-01T00:00:00+00:00",
        "train_end_date": "2023-01-03T00:00:00+00:00",
        "tag_list": ["a", "b", "c"],
    }
    model_config = {
        "DiffBasedAnomalyDetector": {
            "base_estimator": {
                "Pipeline": {
                    "steps": [
                        "MinMaxScaler",
                        {"DenseAutoEncoder": {
                            "kind": "feedforward_symmetric", "dims": [4],
                            "epochs": 1, "batch_size": 32,
                        }},
                    ]
                }
            }
        }
    }
    models_root = tmp_path / "models"
    model_dir = provide_saved_model(
        "m-a", model_config, data_config, str(models_root / "m-a"),
        evaluation_config={"cv_mode": "build_only"},
    )
    app = build_app({"m-a": str(models_root / "m-a")}, project="proj",
                    models_root=str(models_root))
    assert app.compile_cache is not None
    assert app.compile_cache.root == str(models_root / ".compile-cache")
    app.engine.warmup()
    assert app.compile_cache.counters["write"] > 0
    # second boot against the same tree loads instead of compiling
    app2 = build_app({"m-a": str(models_root / "m-a")}, project="proj",
                     models_root=str(models_root))
    before = _fresh_compiles()
    app2.engine.warmup()
    assert _fresh_compiles() - before == 0
    assert app2.compile_cache.counters["hit"] > 0
    # the hidden cache dir never scans as a machine
    from gordo_components_tpu.server.server import scan_models_root

    assert set(scan_models_root(str(models_root))) == {"m-a"}
    assert model_dir  # the generation dir exists


def test_server_cache_off_by_default_without_models_root(
    fitted_models, monkeypatch
):
    monkeypatch.delenv(STORE_ENV, raising=False)
    models, _ = fitted_models
    engine = ServingEngine(models)
    assert engine.compile_cache is None
    assert engine.stats()["compile_cache"] is None
    engine.close()


# -- CLI verbs --------------------------------------------------------------
def test_cli_cache_list_warm_purge(tmp_path, monkeypatch):
    monkeypatch.delenv(STORE_ENV, raising=False)
    from gordo_components_tpu.builder import provide_saved_model
    from gordo_components_tpu.cli.cli import gordo

    data_config = {
        "type": "RandomDataset",
        "train_start_date": "2023-01-01T00:00:00+00:00",
        "train_end_date": "2023-01-03T00:00:00+00:00",
        "tag_list": ["a", "b"],
    }
    model_config = {
        "DiffBasedAnomalyDetector": {
            "base_estimator": {
                "Pipeline": {
                    "steps": [
                        "MinMaxScaler",
                        {"DenseAutoEncoder": {
                            "kind": "feedforward_symmetric", "dims": [4],
                            "epochs": 1, "batch_size": 32,
                        }},
                    ]
                }
            }
        }
    }
    models_root = tmp_path / "models"
    provide_saved_model(
        "m-cli", model_config, data_config, str(models_root / "m-cli"),
        evaluation_config={"cv_mode": "build_only"},
    )
    runner = CliRunner()
    warm = runner.invoke(
        gordo, ["cache", "warm", "--models-dir", str(models_root)]
    )
    assert warm.exit_code == 0, warm.output
    summary = json.loads(warm.output[warm.output.index("{"):])
    assert summary["buckets"] == 1
    assert summary["cache"]["write"] > 0

    store_dir = str(models_root / ".compile-cache")
    listed = runner.invoke(gordo, ["cache", "list", "--store", store_dir])
    assert listed.exit_code == 0, listed.output
    payload = json.loads(listed.output[listed.output.index("{"):])
    assert payload["entries"] and all(
        e["verified"] and e["current"] for e in payload["entries"]
    )

    purged = runner.invoke(gordo, ["cache", "purge", "--store", store_dir])
    assert purged.exit_code == 0, purged.output
    removed = json.loads(purged.output[purged.output.index("{"):])
    assert len(removed["removed"]) == len(payload["entries"])


# -- satellite: engine accounting must not count unfilled results -----------
def test_fill_results_failure_does_not_inflate_accounting(fitted_models):
    models, X = fitted_models
    engine = ServingEngine(models)
    engine.anomaly("m0", X)
    engine.quiesce()
    bucket, _ = engine._by_name["m0"]
    before = (bucket.dispatch_count, bucket.request_count)

    original = bucket._fill_results
    bucket._fill_results = lambda *a, **k: (_ for _ in ()).throw(
        RuntimeError("fill boom")
    )
    try:
        with pytest.raises(RuntimeError, match="fill boom"):
            engine.anomaly("m0", X)
    finally:
        bucket._fill_results = original
    engine.quiesce()
    # the failed request errored its waiter and was NOT counted as served
    assert (bucket.dispatch_count, bucket.request_count) == before
    engine.anomaly("m0", X)  # engine still healthy
    engine.close()
