#!/usr/bin/env python
"""Cold-start smoke: the persistent compile cache's end-to-end gates on
the CPU backend (``make coldstart-smoke``).

Checks (ISSUE 6 acceptance):

- **warm boot is load-not-compile**: the second engine boot against a
  warmed cache pays ZERO fresh XLA compiles (compile-histogram delta 0,
  ``gordo_compile_cache_*`` hits > 0), and its scores are bit-identical
  to both the cold boot's and a cache-less engine's;
- **/reload and rollback pay no recompiles**: a served models tree that
  commits a new generation (and then rolls back) adopts each swap through
  ``POST /reload`` with zero fresh compiles;
- **corruption falls back to JIT**: a bitflipped executable payload and a
  truncated treedef file each read as *invalid*, boot succeeds, scores
  stay bit-identical, and the write-back self-heals the entry;
- **fingerprint mismatch falls back**: an entry whose stored KEY.json
  disagrees (the jaxlib-bump shape) reads as *stale* with the same
  fallback;
- **a torn cache write never wedges boot**: ``.staging-*`` debris and a
  manifest-less half-entry in the cache root are inert;
- **the stacked megabatch program round-trips** (ISSUE 7): a cold boot
  writes a ``serving-mega`` entry, the warm boot's zero-fresh-compiles
  gate covers it, and a fleet-build ``export_serving_cache`` produces a
  cache a fresh server boots against with zero compiles and mega hits.

Exit codes: 0 = all checks passed, 1 = at least one failed.
"""

from __future__ import annotations

import json
import os
import sys

# runnable straight from a checkout (python tools/coldstart_smoke.py)
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


def _fresh_compiles() -> int:
    from gordo_components_tpu.observability.registry import REGISTRY

    for metric in REGISTRY.metrics():
        if metric.name == "gordo_engine_compile_seconds":
            return int(sum(s["count"] for s in metric.stats().values()))
    return 0


def _entry_kinds(cache_root) -> set:
    """The program kinds stored in a cache root (from each entry's
    KEY.json) — how the smoke asserts WHICH executables round-tripped."""
    import glob

    from gordo_components_tpu.compile_cache.store import KEY_FILE

    kinds = set()
    for key_path in glob.glob(os.path.join(cache_root, "cc-*", KEY_FILE)):
        try:
            with open(key_path) as fh:
                kinds.add(json.load(fh)["program"]["kind"])
        except Exception:  # lint: allow-swallow(this smoke CREATES half-written cache debris; unreadable keys simply do not count as entries)
            pass
    return kinds


def warm_boot_zero_compiles(models, cache_root, X, ref_bits) -> None:
    from gordo_components_tpu.compile_cache import CompileCacheStore
    from gordo_components_tpu.server.engine import ServingEngine

    print("\n[1/6] warm boot is load-not-compile (and bit-identical)")
    names = sorted(models)
    # boot 1: cold cache — pays the compiles, writes executables back
    store = CompileCacheStore(cache_root)
    before = _fresh_compiles()
    engine = ServingEngine(models, compile_cache=store)
    engine.warmup()
    cold_compiles = _fresh_compiles() - before
    cold_bits = {n: _bits(engine.anomaly(n, X)) for n in names}
    engine.close()
    check(cold_compiles > 0, f"cold boot paid compiles ({cold_compiles})")
    check(store.counters["write"] > 0,
          f"cold boot wrote executables back ({store.counters['write']})")
    check(all(cold_bits[n] == ref_bits[n] for n in names),
          "cached-path scores bit-identical to the cache-less engine")
    # the fused megabatch program (ISSUE 7) joined the cache key schema:
    # replicated boots serve through it, so its executable must be here
    kinds = _entry_kinds(cache_root)
    check("serving-mega" in kinds,
          f"cold boot cached the stacked megabatch program ({sorted(kinds)})")

    # boot 2: warmed cache — the acceptance gate
    store = CompileCacheStore(cache_root)
    before = _fresh_compiles()
    engine = ServingEngine(models, compile_cache=store)
    engine.warmup()
    warm_compiles = _fresh_compiles() - before
    warm_bits = {n: _bits(engine.anomaly(n, X)) for n in names}
    engine.close()
    check(warm_compiles == 0,
          f"warm boot paid ZERO fresh XLA compiles (got {warm_compiles})")
    check(store.counters["hit"] > 0,
          f"warm boot loaded from the cache ({store.counters['hit']} hits)")
    check(store.counters["invalid"] == store.counters["stale"] == 0,
          "warm boot saw no invalid/stale entries")
    check(all(warm_bits[n] == ref_bits[n] for n in names),
          "warm-boot scores bit-identical to the cache-less engine")


def reload_and_rollback_no_recompiles(tmp) -> None:
    from werkzeug.test import Client as TestClient

    from gordo_components_tpu.builder import provide_saved_model
    from gordo_components_tpu.serializer import load, load_metadata
    from gordo_components_tpu.serializer.persistence import (
        write_artifact_files,
    )
    from gordo_components_tpu.server import build_app
    from gordo_components_tpu.store import (
        commit_generation,
        current_generation,
        rollback_generation,
    )

    print("\n[2/6] /reload and rollback pay no recompiles")
    models_root = os.path.join(tmp, "models")
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
    machine_dir = provide_saved_model(
        "m-cold", model_config, data_config,
        os.path.join(models_root, "m-cold"),
        evaluation_config={"cv_mode": "build_only"},
    )
    root_dir = os.path.join(models_root, "m-cold")

    # boot against the models tree: the compile cache defaults to
    # <models_root>/.compile-cache and the warm-up writes it
    app = build_app({"m-cold": root_dir}, project="proj",
                    models_root=models_root)
    check(app.compile_cache is not None,
          "models_root server defaults the compile cache on")
    app.engine.warmup()
    client = TestClient(app)

    # commit generation 2 (same model bytes re-committed — the shape
    # /reload sees after any rebuild that doesn't change architecture)
    model = load(root_dir)
    metadata = load_metadata(root_dir)
    commit_generation(
        root_dir,
        lambda staging: write_artifact_files(model, staging,
                                             metadata=metadata),
        name="m-cold",
    )
    before = _fresh_compiles()
    response = client.post("/reload")
    payload = response.get_json()
    check(response.status_code == 200 and "m-cold" in payload["refreshed"],
          f"reload adopted the new generation ({payload})")
    check(current_generation(root_dir) == "gen-0002",
          "CURRENT points at gen-0002")
    reload_compiles = _fresh_compiles() - before
    check(reload_compiles == 0,
          f"/reload paid ZERO fresh compiles (got {reload_compiles})")

    # rollback, adopted through the same path
    rollback_generation(root_dir)
    before = _fresh_compiles()
    response = client.post("/reload")
    payload = response.get_json()
    check(response.status_code == 200 and "m-cold" in payload["refreshed"],
          f"reload adopted the rollback ({payload})")
    rollback_compiles = _fresh_compiles() - before
    check(rollback_compiles == 0,
          f"rollback adoption paid ZERO fresh compiles "
          f"(got {rollback_compiles})")
    check(app.compile_cache.counters["hit"] >= 2,
          f"generation swaps served from the cache "
          f"({app.compile_cache.counters['hit']} hits)")
    # scoring still healthy after two swaps
    X = [[1.0, 2.0, 3.0]] * 16
    response = client.post(
        "/gordo/v0/proj/m-cold/anomaly/prediction",
        data=json.dumps({"X": X}), content_type="application/json",
    )
    check(response.status_code == 200, "scoring healthy after the swaps")


def corruption_falls_back(models, cache_root, X, ref_bits) -> None:
    from gordo_components_tpu.compile_cache import CompileCacheStore
    from gordo_components_tpu.compile_cache.store import EXEC_FILE, TREES_FILE
    from gordo_components_tpu.server.engine import ServingEngine

    print("\n[3/6] corrupt entries fall back to JIT, bit-identical, "
          "and self-heal")
    names = sorted(models)
    for fault, filename in (("bitflip", EXEC_FILE), ("truncate", TREES_FILE)):
        store = CompileCacheStore(cache_root)
        entries = [e for e in store.entries() if e["verified"]]
        check(bool(entries), f"{fault}: cache has entries to damage")
        if not entries:
            return
        target = os.path.join(store.root, entries[0]["name"], filename)
        if fault == "bitflip":
            with open(target, "r+b") as fh:
                data = bytearray(fh.read())
                data[len(data) // 2] ^= 0xFF
                fh.seek(0)
                fh.write(data)
        else:
            size = os.path.getsize(target)
            with open(target, "r+b") as fh:
                fh.truncate(max(0, size - 7))
        store = CompileCacheStore(cache_root)
        engine = ServingEngine(models, compile_cache=store)
        engine.warmup()
        bits = {n: _bits(engine.anomaly(n, X)) for n in names}
        engine.close()
        check(store.counters["invalid"] > 0,
              f"{fault} entry read as invalid (fell back to JIT)")
        check(all(bits[n] == ref_bits[n] for n in names),
              f"{fault} fallback scores bit-identical")
        check(store.counters["write"] > 0,
              f"{fault} entry self-healed (write-back replaced it)")
        healed = CompileCacheStore(cache_root)
        check(all(e["verified"] for e in healed.entries()),
              f"{fault}: every entry verifies again after self-heal")


def fingerprint_mismatch_falls_back(models, cache_root, X, ref_bits) -> None:
    from gordo_components_tpu.compile_cache import CompileCacheStore
    from gordo_components_tpu.compile_cache.store import KEY_FILE
    from gordo_components_tpu.server.engine import ServingEngine
    from gordo_components_tpu.store.manifest import write_manifest

    print("\n[4/6] fingerprint/key mismatch reads as stale, falls back")
    names = sorted(models)
    store = CompileCacheStore(cache_root)
    entries = [e for e in store.entries() if e["verified"]]
    check(bool(entries), "cache has entries to tamper")
    if not entries:
        return
    entry_dir = os.path.join(store.root, entries[0]["name"])
    key_path = os.path.join(entry_dir, KEY_FILE)
    with open(key_path) as fh:
        stored = fh.read()
    # the jaxlib-bump shape: the stored key names another toolchain. The
    # manifest is REWRITTEN so checksums pass — this isolates the key
    # comparison (a failing checksum would read as invalid, not stale)
    with open(key_path, "w") as fh:
        fh.write(stored.replace('"jaxlib":"', '"jaxlib":"0.0.0-'))
    write_manifest(entry_dir)
    store = CompileCacheStore(cache_root)
    engine = ServingEngine(models, compile_cache=store)
    engine.warmup()
    bits = {n: _bits(engine.anomaly(n, X)) for n in names}
    engine.close()
    check(store.counters["stale"] > 0, "tampered entry read as stale")
    check(all(bits[n] == ref_bits[n] for n in names),
          "stale fallback scores bit-identical")


def torn_writes_never_wedge(models, cache_root, X) -> None:
    from gordo_components_tpu.compile_cache import CompileCacheStore
    from gordo_components_tpu.server.engine import ServingEngine

    print("\n[5/6] torn cache writes never wedge boot")
    # crash debris: a staging dir the atomic commit never renamed in, and
    # a half-entry with no manifest (a hand-copied or torn dir)
    staging = os.path.join(cache_root, ".staging-cc-dead.beef1234")
    os.makedirs(staging, exist_ok=True)
    with open(os.path.join(staging, "executable.bin"), "wb") as fh:
        fh.write(b"\x00" * 64)
    half = os.path.join(cache_root, "cc-" + "f" * 32)
    os.makedirs(half, exist_ok=True)
    with open(os.path.join(half, "KEY.json"), "w") as fh:
        fh.write("{}")
    try:
        store = CompileCacheStore(cache_root)
        engine = ServingEngine(models, compile_cache=store)
        engine.warmup()
        scored = engine.anomaly(sorted(models)[0], X)
        engine.close()
        check(scored.total_anomaly_score.shape[0] > 0,
              "boot + scoring healthy beside crash debris")
    except Exception as exc:
        check(False, f"boot wedged on cache debris: {exc}")
        return
    records = {e["name"]: e for e in store.entries()}
    check(records.get("cc-" + "f" * 32, {}).get("verified") is False,
          "half-entry reports unverified in `gordo cache list`")
    removed = CompileCacheStore(cache_root).purge(stale_only=True)
    check(("cc-" + "f" * 32) in removed
          and any(name.startswith(".staging-") for name in removed),
          f"purge --stale removes the debris ({removed})")


def megabatch_export_roundtrip(models, tmp) -> None:
    """ISSUE 7 satellite: the stacked megabatch program's cache key
    round-trips through the fleet-build export into a server boot — a
    warmed export means the boot compiles ZERO fresh megabatch programs
    and serves its first fused dispatch from a loaded executable."""
    from gordo_components_tpu.compile_cache import CompileCacheStore
    from gordo_components_tpu.compile_cache.export import (
        export_serving_cache,
    )
    from gordo_components_tpu.serializer import dump, load
    from gordo_components_tpu.server.engine import ServingEngine

    print("\n[6/6] megabatch executable round-trips export -> boot")
    cache_root = os.path.join(tmp, "export-cache")
    # the export path works from SAVED model dirs (the fleet-build shape)
    model_dirs = {}
    for name in sorted(models)[:2]:
        model_dir = os.path.join(tmp, "export-models", name)
        os.makedirs(model_dir, exist_ok=True)
        dump(models[name], model_dir)
        model_dirs[name] = model_dir
    summary = export_serving_cache(model_dirs, cache_root)
    check(summary["cache"].get("write", 0) > 0,
          f"export wrote executables ({summary['cache']})")
    kinds = _entry_kinds(cache_root)
    check("serving-mega" in kinds,
          f"export produced a serving-mega entry ({sorted(kinds)})")

    # a fresh server boot against the exported cache: load, not compile
    store = CompileCacheStore(cache_root)
    before = _fresh_compiles()
    engine = ServingEngine(
        {name: load(path) for name, path in model_dirs.items()},
        compile_cache=store,
    )
    engine.warmup()
    boot_compiles = _fresh_compiles() - before
    check(boot_compiles == 0,
          f"boot against the export compiled ZERO fresh megabatch "
          f"programs (got {boot_compiles})")
    check(store.counters["hit"] > 0,
          f"boot loaded from the exported cache "
          f"({store.counters['hit']} hits)")
    check(engine.stats()["megabatch"]["enabled"],
          "megabatching live on the exported-cache boot")
    engine.close()


def main() -> int:
    import tempfile

    import numpy as np

    from gordo_components_tpu.models.synthetic_fleet import build_models
    from gordo_components_tpu.server.engine import ServingEngine

    print("cold-start smoke: warm boot O(load), reload/rollback zero "
          "recompiles, corrupt/stale/torn cache fallback")
    models = build_models(4, 64, 4)
    X = np.random.default_rng(11).normal(size=(64, 4)).astype(np.float32)
    # the parity reference: a cache-less engine (today's compile path)
    plain = ServingEngine(models)
    ref_bits = {n: _bits(plain.anomaly(n, X)) for n in sorted(models)}
    plain.close()
    with tempfile.TemporaryDirectory() as tmp:
        cache_root = os.path.join(tmp, "compile-cache")
        warm_boot_zero_compiles(models, cache_root, X, ref_bits)
        reload_and_rollback_no_recompiles(tmp)
        corruption_falls_back(models, cache_root, X, ref_bits)
        fingerprint_mismatch_falls_back(models, cache_root, X, ref_bits)
        torn_writes_never_wedge(models, cache_root, X)
        megabatch_export_roundtrip(models, tmp)
    if _failures:
        print(f"\nCOLDSTART SMOKE FAILED: {len(_failures)} check(s)",
              file=sys.stderr)
        return 1
    print("\ncoldstart smoke passed: warm boots load instead of compile, "
          "generation swaps are recompile-free, and every cache failure "
          "mode degrades to bit-identical JIT")
    return 0


if __name__ == "__main__":
    sys.exit(main())
