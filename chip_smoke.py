#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

``python chip_smoke.py`` drives the main path once, in ONE process (a chip
belongs to one process), through the entry points a user calls:

1. device      what JAX found; anything but a TPU is exit 2, nothing built
2. fleet_build ``gordo fleet-build``: the flagship dense-AE fleet (128
               machines x 864 rows x 10 tags, feedforward_hourglass, 10
               epochs, batch 64, 3-fold CV)
3. serve       ``gordo run-server`` on the built tree, real HTTP: a few
               sequential 144x10 anomaly requests, then concurrent bursts
               over distinct machines (the fused megabatch program and its
               donated buffers), scores checked against the host path
4. safety_nets no host-path machine, no demotion, no repair path fired
5. second_boot a second ``build_app`` on the same tree loads its programs
               from the AOT store and scores bit-identically
6. kernel      one PatchTST train step + forward with the Pallas flash
               kernel at 130 patches (> 128, not a multiple of 128: enters
               the kernel and its padding mask), against dense attention

Weights are random (seed 0) and every input is generated from the seed.
Stdout ends with two JSON lines; exit 0 only if every stage passed:

    {"report": {"rehearsal": ..., "stages": {...}, "compile": {...}, ...}}
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

The last line is the result and has exactly those keys. The report above it
states facts (what ran, what compiled, what was cached) — no rate,
utilization or latency percentile: this is not the benchmark.

``--rehearse`` runs the same stages at a tiny size on whatever backend
``JAX_PLATFORMS`` names (Pallas in interpret mode on the CPU) and marks the
report ``"rehearsal": true``.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import shutil
import socket
import sys
import threading
import time
import traceback
import urllib.request
from concurrent.futures import ThreadPoolExecutor

SEED = 0
HERE = os.path.dirname(os.path.abspath(__file__))
# build output (a models tree), wiped at start: a fixed path, because the
# serving AOT store defaults to <tree>/.compile-cache
WORK_DIR = os.path.join(HERE, ".chip_smoke")
PROJECT = "chip-smoke"

# ``train_end``/``rows``: what RandomDataset yields at 10-minute resolution
# from 2023-01-01T00:00 (checked against every built machine's metadata)
FULL = {
    "machines": 128, "train_end": "2023-01-07T00:10:00+00:00", "rows": 864,
    "tags": 10, "epochs": 10, "batch_size": 64,
    "request_rows": 144, "sequential": 6, "burst": 8,
    "flash": {"n_features": 10, "batch": 8, "d_model": 64, "n_heads": 4},
}
REHEARSAL = {
    "machines": 16, "train_end": "2023-01-02T00:00:00+00:00", "rows": 144,
    "tags": 4, "epochs": 2, "batch_size": 32,
    "request_rows": 48, "sequential": 3, "burst": 8,
    "flash": {"n_features": 2, "batch": 2, "d_model": 16, "n_heads": 2},
}
# 130 patches of 16 rows at stride 8
FLASH_WINDOW = {"lookback_window": 1048, "patch_length": 16, "stride": 8}

# Score parity between the serving engine and the host path
# (``serializer.load(dir).anomaly(X)``). Both run on the same device at
# JAX's default matmul precision — on the TPU that rounds f32 operands to
# bf16, but it rounds the SAME operands in both programs: measured on a v5e
# (PR 21) the largest difference was 9.5e-7 at scores up to 11.3, the same
# f32 rounding noise the CPU shows (1.4e-6). So one tolerance, 100x the
# measured noise: anything larger means the two paths compute different
# things, not that the device is less precise.
PARITY_TOL = 1e-4
# Flash vs dense PatchTST in bf16, same parameters: the kernel accumulates
# the softmax in f32 where the dense path rounds scores to bf16, so outputs
# differ by bf16 roundings (eps 2**-8 = 3.9e-3; measured on a v5e, PR 21:
# one ulp, 7.8e-3, on the forward). tests/test_flash_attention.py bounds one
# attention call at 2e-2; a whole layer and one optimizer step get 5e-2.
FLASH_TOL = 5e-2

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"


class StageFailed(Exception):
    pass


def check(condition, message: str) -> None:
    if not condition:
        raise StageFailed(message)


class CompileMeter:
    """XLA compiles of the whole process, from JAX's own monitoring events
    (a persistent-cache hit still fires the duration event, with the
    retrieval time)."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.programs = 0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kwargs):
        if event == _BACKEND_COMPILE:
            self.seconds += duration
            self.programs += 1

    def _event(self, event, **kwargs):
        if event == _CACHE_HIT:
            self.cache_hits += 1
        elif event == _CACHE_MISS:
            self.cache_misses += 1

    def summary(self):
        return {
            "seconds": round(self.seconds, 2),
            "programs": self.programs,
            "jax_cache_hits": self.cache_hits,
            "jax_cache_misses": self.cache_misses,
        }


def cache_entries(cache_dir: str) -> int:
    try:
        return sum(name.endswith("-cache") for name in os.listdir(cache_dir))
    except OSError:
        return 0


def series(name: str) -> dict:
    from gordo_components_tpu.observability.registry import REGISTRY

    return dict(REGISTRY.snapshot().get(name, {}).get("series", {}))


def counter(name: str, label: str = "") -> float:
    return float(series(name).get(label, 0))


def aot_store_activity() -> float:
    """Serving executables written to or loaded from the AOT store."""
    hits = sum(
        value
        for key, value in series("gordo_compile_cache_lookups_total").items()
        if 'outcome="hit"' in key
    )
    return hits + counter("gordo_compile_cache_writes_total", 'outcome="ok"')


# -- stage 2 ----------------------------------------------------------------
def fleet_config(sizes: dict) -> dict:
    model = {
        "DiffBasedAnomalyDetector": {
            "base_estimator": {
                "TransformedTargetRegressor": {
                    "regressor": {
                        "Pipeline": {
                            "steps": [
                                "MinMaxScaler",
                                {
                                    "DenseAutoEncoder": {
                                        "kind": "feedforward_hourglass",
                                        "epochs": sizes["epochs"],
                                        "batch_size": sizes["batch_size"],
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
    return {
        "project-name": PROJECT,
        "machines": [
            {
                "name": f"cs-{i:03d}",
                "dataset": {
                    "tag_list": [
                        f"cs-{i:03d}-t{j}" for j in range(sizes["tags"])
                    ]
                },
            }
            for i in range(sizes["machines"])
        ],
        "globals": {
            "model": model,
            "dataset": {
                "type": "RandomDataset",
                "train_start_date": "2023-01-01T00:00:00+00:00",
                "train_end_date": sizes["train_end"],
            },
        },
    }


def find_loss_history(node):
    if isinstance(node, dict):
        history = node.get("history")
        if isinstance(history, dict) and "loss" in history:
            return history["loss"]
        for value in node.values():
            found = find_loss_history(value)
            if found is not None:
                return found
    elif isinstance(node, list):
        for value in node:
            found = find_loss_history(value)
            if found is not None:
                return found
    return None


def stage_fleet_build(ctx: dict) -> dict:
    import jax
    import numpy as np
    import yaml

    from gordo_components_tpu.cli import gordo
    from gordo_components_tpu.serializer import load_metadata

    sizes = ctx["sizes"]
    config_path = os.path.join(WORK_DIR, "fleet.yaml")
    with open(config_path, "w") as fh:
        yaml.safe_dump(fleet_config(sizes), fh)
    tree = os.path.join(WORK_DIR, "models")
    aot_before = aot_store_activity()
    echoed = io.StringIO()
    try:
        with contextlib.redirect_stdout(echoed):
            gordo.main(
                ["fleet-build", "--machine-config", config_path,
                 "--output-dir", tree, "--n-splits", "3",
                 "--seed", str(SEED)],
                standalone_mode=False,
            )
    except SystemExit as exc:
        raise StageFailed(f"fleet-build exited {exc.code}") from exc
    built = json.loads(echoed.getvalue())
    names = [f"cs-{i:03d}" for i in range(sizes["machines"])]
    check(sorted(built) == names, f"fleet-build returned {len(built)} of "
          f"{len(names)} machines")
    with open(os.path.join(tree, "fleet_manifest.json")) as fh:
        manifest = json.load(fh)
    failed = [
        name for name, entry in manifest["machines"].items()
        if entry.get("status") != "completed"
    ]
    check(not failed and not manifest["pending"],
          f"manifest: failed {failed}, pending {manifest['pending']}")

    n_devices = len(jax.devices())
    shape = [sizes["rows"], sizes["tags"]]
    first, last = [], []
    for name in names:
        metadata = load_metadata(built[name])
        history = find_loss_history(metadata["model"])
        check(history and np.isfinite(history).all(),
              f"{name}: loss history missing or non-finite: {history}")
        first.append(history[0])
        last.append(history[-1])
        trained_on = metadata["model"]["fleet"]["devices"]
        check(trained_on == {
            "platform": ctx["device"]["platform"],
            "device_kind": ctx["device"]["kind"],
            "count": n_devices,
        }, f"{name}: trained on {trained_on}, JAX has {ctx['device']}")
        check(metadata["dataset"]["x_shape"] == shape,
              f"{name}: trained on {metadata['dataset']['x_shape']}, "
              f"not {shape}")
    check(np.mean(last) < np.mean(first),
          f"mean loss rose: {np.mean(first):.4f} -> {np.mean(last):.4f}")
    # the export either wrote the serving executables or, against a store
    # an earlier run filled, found them
    exported = aot_store_activity() - aot_before
    check(exported > 0,
          "fleet-build put no serving executable into the AOT store")
    ctx["tree"], ctx["built"], ctx["names"] = tree, built, names
    return {
        "machines": len(built),
        "rows_x_tags": shape,
        "mean_loss_first": round(float(np.mean(first)), 5),
        "mean_loss_last": round(float(np.mean(last)), 5),
        "trained_on": trained_on,
        "serving_executables_exported": int(exported),
    }


# -- stage 3 ----------------------------------------------------------------
def post_anomaly(base_url: str, name: str, X) -> dict:
    from gordo_components_tpu import wire

    request = urllib.request.Request(
        f"{base_url}/gordo/v0/{PROJECT}/{name}/anomaly/prediction",
        data=json.dumps({"X": X.tolist()}).encode(),
        headers={"Content-Type": "application/json",
                 "Accept": wire.NPZ_CONTENT_TYPE},
    )
    with urllib.request.urlopen(request, timeout=600) as response:
        check(response.status == 200, f"{name}: HTTP {response.status}")
        arrays, _ = wire.decode_npz(response.read())
    return arrays


def burst(base_url: str, names, inputs) -> dict:
    barrier = threading.Barrier(len(names))

    def one(name):
        barrier.wait(timeout=60)
        return post_anomaly(base_url, name, inputs[name])

    with ThreadPoolExecutor(len(names)) as pool:
        return dict(zip(names, pool.map(one, names)))


def engine_requests() -> float:
    return sum(series("gordo_engine_requests_total").values())


def stage_serve(ctx: dict) -> dict:
    import numpy as np

    from gordo_components_tpu.cli import gordo
    from gordo_components_tpu.serializer import load

    sizes, names = ctx["sizes"], ctx["names"]
    with socket.socket() as sock:
        sock.bind(("127.0.0.1", 0))
        port = sock.getsockname()[1]
    base_url = f"http://127.0.0.1:{port}"
    crashed = []

    def serve():
        try:
            gordo.main(
                ["run-server", "--models-dir", ctx["tree"],
                 "--host", "127.0.0.1", "--port", str(port),
                 "--project", PROJECT],
                standalone_mode=False,
            )
        except BaseException as exc:  # reported by the health poll below
            crashed.append(exc)
            raise

    server = threading.Thread(target=serve, name="chip-smoke-server",
                              daemon=True)
    server.start()
    deadline = time.monotonic() + 900
    while True:
        check(server.is_alive() and not crashed,
              f"run-server died at boot: {crashed}")
        check(time.monotonic() < deadline, "run-server not ready in 900s")
        try:
            with urllib.request.urlopen(f"{base_url}/healthz", timeout=5):
                break
        except OSError:
            time.sleep(0.5)
    check(counter("gordo_server_warmups_total", 'outcome="ok"') == 1
          and counter("gordo_server_warmups_total", 'outcome="error"') == 0,
          f"boot warm-up: {series('gordo_server_warmups_total')}")
    buckets = counter("gordo_engine_buckets")
    check(buckets == 1, f"expected one architecture bucket, got {buckets}")

    rng = np.random.default_rng(SEED)
    seq_names = names[: sizes["sequential"]]
    burst_names = names[sizes["sequential"]: sizes["sequential"] + sizes["burst"]]
    check(len(set(seq_names + burst_names))
          == sizes["sequential"] + sizes["burst"], "fleet too small")
    inputs = {
        name: rng.normal(size=(sizes["request_rows"], sizes["tags"]))
        .astype(np.float32)
        for name in seq_names + burst_names
    }
    meter = ctx["meter"]
    requests_before = engine_requests()
    sent = 0

    # warm the shapes the requests use: the sequential pass compiles the
    # single-request program; a burst coalesces into fused batches whose
    # sizes depend on thread timing, so bursts repeat until one compiles
    # nothing (a healthy engine runs out of batch sizes to compile)
    for name in seq_names:
        post_anomaly(base_url, name, inputs[name])
    sent += len(seq_names)
    warm_bursts = 0
    while True:
        programs = meter.programs
        burst(base_url, burst_names, inputs)
        sent += len(burst_names)
        warm_bursts += 1
        if meter.programs == programs:
            break
        check(warm_bursts < 8, "every burst compiles: programs are being "
              "recompiled per call")

    # the checked pass: same requests, nothing may compile for the
    # sequential ones, and their scores are the record of this boot
    programs = meter.programs
    scored = {
        name: post_anomaly(base_url, name, inputs[name]) for name in seq_names
    }
    sent += len(seq_names)
    second_pass_compiles = meter.programs - programs
    check(second_pass_compiles == 0, f"{second_pass_compiles} program(s) "
          "compiled on the second pass of the sequential requests")
    scored.update(burst(base_url, burst_names, inputs))
    sent += len(burst_names)
    counted = engine_requests() - requests_before
    check(counted == sent, f"engine counted {counted} requests, {sent} sent "
          "(the rest took a path the engine does not count)")
    fused = series("gordo_engine_megabatch_fused_machines")
    check(any(v["count"] > 0 and v["p99"] > 1 for v in fused.values()),
          f"no burst fused across machines: {fused}")

    # parity against the host path, on the same device
    tolerance = PARITY_TOL
    worst_abs = largest = 0.0
    for name, arrays in scored.items():
        reference = load(ctx["built"][name]).anomaly(inputs[name])
        for key in ("total-anomaly-score", "tag-anomaly-scores",
                    "model-output"):
            got = np.asarray(arrays[key], np.float32)
            want = np.asarray(reference[key].values, np.float32).reshape(
                got.shape
            )
            check(np.isfinite(got).all(), f"{name}/{key}: non-finite")
            worst_abs = max(worst_abs, float(np.abs(got - want).max()))
            largest = max(largest, float(np.abs(want).max()))
            check(np.allclose(got, want, rtol=tolerance, atol=tolerance),
                  f"{name}/{key}: engine and host path differ by "
                  f"{np.abs(got - want).max():.3e} (tolerance {tolerance})")
    ctx["inputs"], ctx["scored"], ctx["seq_names"] = inputs, scored, seq_names
    return {
        "requests": sent,
        "warm_bursts": warm_bursts,
        "second_pass_compiles": second_pass_compiles,
        "parity_tolerance": tolerance,
        "parity_max_abs_diff": worst_abs,
        "parity_largest_value": largest,
    }


# -- stage 4 ----------------------------------------------------------------
def stage_safety_nets(ctx: dict) -> dict:
    mega = series("gordo_engine_megabatch_events_total")
    hot = series("gordo_engine_hot_cache_events_total")
    fired = {
        "host_path_machines": counter("gordo_engine_host_path_machines"),
        **{
            f"megabatch_{event}": mega.get(f'event="{event}"', 0)
            for event in ("demote", "fallback_cold", "retry_isolated")
        },
        "hot_cache_demote": hot.get('event="demote"', 0),
    }
    check(not any(fired.values()), f"a safety net fired: {fired}")
    return {**fired, "requests_by_path": series("gordo_engine_requests_total")}


# -- stage 5 ----------------------------------------------------------------
def stage_second_boot(ctx: dict) -> dict:
    import numpy as np
    from werkzeug.test import Client

    from gordo_components_tpu import wire
    from gordo_components_tpu.server import build_app
    from gordo_components_tpu.server.server import scan_models_root

    app = build_app(
        scan_models_root(ctx["tree"]), project=PROJECT,
        models_root=ctx["tree"],
    )
    try:
        app.engine.warmup(ctx["sizes"]["request_rows"])
        client = Client(app)
        for name in ctx["seq_names"]:
            response = client.post(
                f"/gordo/v0/{PROJECT}/{name}/anomaly/prediction",
                data=json.dumps({"X": ctx["inputs"][name].tolist()}),
                headers={"Content-Type": "application/json",
                         "Accept": wire.NPZ_CONTENT_TYPE},
            )
            check(response.status_code == 200,
                  f"{name}: HTTP {response.status_code}")
            arrays, _ = wire.decode_npz(response.get_data())
            for key, first in ctx["scored"][name].items():
                check(np.array_equal(np.asarray(arrays[key]),
                                     np.asarray(first)),
                      f"{name}/{key}: second boot is not bit-identical")
        store = app.compile_cache
        check(store is not None, "second boot has no AOT store")
        counters = dict(store.counters)
    finally:
        app.engine.close()
    check(counters["hit"] > 0 and counters["invalid"] == 0
          and counters["stale"] == 0 and counters["write_error"] == 0,
          f"AOT store on the second boot: {counters}")
    return {"aot_store": {"root": store.root, **counters}}


# -- stage 6 ----------------------------------------------------------------
def stage_kernel(ctx: dict) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from gordo_components_tpu.models.register import get_factory
    from gordo_components_tpu.models.train import (
        make_batch_step,
        make_predict_fn,
    )

    cfg = ctx["sizes"]["flash"]
    n_features, batch = cfg["n_features"], cfg["batch"]

    def spec_for(attention_impl):
        return get_factory("patchtst")(
            n_features=n_features, d_model=cfg["d_model"],
            n_heads=cfg["n_heads"], n_layers=1, compute_dtype="bfloat16",
            attention_impl=attention_impl, **FLASH_WINDOW,
        )

    rng = np.random.default_rng(SEED)
    x = jnp.asarray(rng.normal(
        size=(batch, FLASH_WINDOW["lookback_window"], n_features)
    ), jnp.float32)
    y = jnp.asarray(rng.normal(size=(batch, n_features)), jnp.float32)
    w = jnp.ones((batch,), jnp.float32)
    key = jax.random.PRNGKey(SEED)
    # identical init key + identical architecture: identical parameters
    # (jitted: an eager flax init is some two hundred one-op compiles)
    params = jax.jit(
        lambda k, sample: spec_for("dense").module.init(
            k, sample, deterministic=True
        )["params"]
    )(key, x[:1])
    out = {}
    for impl in ("flash", "dense"):
        spec = spec_for(impl)
        step = jax.jit(make_batch_step(spec.module.apply, spec.optimizer,
                                       loss=spec.loss))
        carry = (params, spec.optimizer.init(params))
        args = (carry, (x, y, w, key))
        if impl == "flash":
            check("pallas_call" in str(jax.make_jaxpr(step)(*args)),
                  "the flash train step took the dense exit")
            if ctx["device"]["platform"] == "tpu":
                check("tpu_custom_call" in step.lower(*args).as_text(),
                      "no Mosaic custom call in the lowered train step")
        (new_params, _), (loss, *_) = step(*args)
        predict = jax.jit(make_predict_fn(spec.module.apply))
        out[impl] = jax.device_get(
            {"loss": loss, "predict": predict(params, x),
             "stepped": predict(new_params, x)}
        )
    for field in ("loss", "predict", "stepped"):
        got, want = out["flash"][field], out["dense"][field]
        check(np.isfinite(got).all(), f"flash {field}: non-finite")
        check(np.allclose(got, want, rtol=FLASH_TOL, atol=FLASH_TOL),
              f"flash {field} differs from dense by "
              f"{np.abs(got - want).max():.3e} (tolerance {FLASH_TOL})")
    return {
        "patches": 130,
        "mosaic": ctx["device"]["platform"] == "tpu",
        "loss_flash": float(out["flash"]["loss"]),
        "loss_dense": float(out["dense"]["loss"]),
        "forward_max_abs_diff": float(
            np.abs(out["flash"]["predict"] - out["dense"]["predict"]).max()
        ),
        "stepped_max_abs_diff": float(
            np.abs(out["flash"]["stepped"] - out["dense"]["stepped"]).max()
        ),
        "tolerance": FLASH_TOL,
    }


# -- driver -----------------------------------------------------------------
STAGES = (
    ("fleet_build", stage_fleet_build),
    ("serve", stage_serve),
    ("safety_nets", stage_safety_nets),
    ("second_boot", stage_second_boot),
    ("kernel", stage_kernel),
)


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--rehearse", action="store_true",
        help="tiny sizes, any backend; the report says rehearsal: true",
    )
    args = parser.parse_args()
    started = time.perf_counter()

    # -- stage 1: device ----------------------------------------------------
    import jax

    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    sys.stderr.write(f"chip_smoke: jax {jax.__version__}, {device}\n")
    if device["platform"] != "tpu" and not args.rehearse:
        sys.stderr.write(
            f"chip_smoke: JAX found no TPU (first device is "
            f"{device['platform']}:{device['kind']}); nothing was built. "
            "--rehearse runs the stages at a tiny size on this backend\n"
        )
        return 2
    try:
        from gordo_components_tpu.utils.backend import (
            enable_persistent_compile_cache,
        )
    except ImportError:
        sys.stderr.write(
            "chip_smoke: the gordo_components_tpu package is not beside "
            "this script; run it from the root of the repository\n"
        )
        return 2

    meter = CompileMeter()
    cache_dir = enable_persistent_compile_cache()
    entries_before = cache_entries(cache_dir)
    shutil.rmtree(WORK_DIR, ignore_errors=True)
    os.makedirs(WORK_DIR)
    ctx = {
        "sizes": REHEARSAL if args.rehearse else FULL,
        "device": device,
        "meter": meter,
    }
    stages = {"device": {"ok": True, "seconds": 0.0, "jax": jax.__version__}}
    for name, stage in STAGES:
        stage_started = time.perf_counter()
        compile_before = meter.seconds
        sys.stderr.write(f"chip_smoke: stage {name} ...\n")
        try:
            stages[name] = {"ok": True, **stage(ctx)}
        except Exception as exc:
            traceback.print_exc()
            stages[name] = {"ok": False,
                            "error": f"{type(exc).__name__}: {exc}"}
        stages[name]["seconds"] = round(
            time.perf_counter() - stage_started, 2
        )
        stages[name]["compile_seconds"] = round(
            meter.seconds - compile_before, 2
        )
        if not stages[name]["ok"]:
            break  # later stages need what this one builds

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    ok = len(stages) == 1 + len(STAGES) and all(
        s["ok"] for s in stages.values()
    )
    if ok and device["platform"] != "cpu" and not all(peaks):
        ok = False
        stages["device"] = {
            **stages["device"], "ok": False,
            "error": f"a device never held memory: peaks {peaks}",
        }
    if ok:
        shutil.rmtree(WORK_DIR, ignore_errors=True)
    report = {
        "rehearsal": args.rehearse,
        "stages": stages,
        "compile": meter.summary(),
        "jax_cache": {
            "dir": cache_dir,
            "entries_before": entries_before,
            "entries_after": cache_entries(cache_dir),
        },
        "peak_bytes_in_use": peaks,
        "wall_seconds": round(time.perf_counter() - started, 1),
    }
    # two lines: the report of what ran, then the result. The result is the
    # LAST line and has exactly these keys — whoever runs the script parses
    # it strictly, so every other fact belongs in the report above it
    print(json.dumps({"report": report}), flush=True)
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    code = main()
    sys.stderr.flush()
    # the server thread and the engines' collectors are daemons; should
    # interpreter shutdown wait on anything else, do not outlive the result
    watchdog = threading.Timer(60, os._exit, [code])
    watchdog.daemon = True
    watchdog.start()
    sys.exit(code)
