"""bench.py smoke: the driver runs it at round end — a broken bench means
a missing benchmark artifact, so its measurement core and JSON schema are
guarded here on a tiny CPU config."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

_REPO_ROOT = str(Path(__file__).resolve().parent.parent)

REQUIRED_CONFIG_KEYS = {
    "machines_per_hour",
    "machines_per_hour_serial",
    "vs_single_machine",
    "exec_s",
    "ingest_s",
    "ingest_mb",
    "compile_s",
    "single_machine_s",
    "mfu",
    "mfu_dtype",
    "peak_hbm_gb",
    "peak_hbm_owned_by_config",
}


@pytest.mark.slow
def test_bench_emits_valid_json_with_split_measurements(tmp_path):
    proc = subprocess.run(
        [sys.executable, "bench.py"],
        env={
            "PATH": "/usr/bin:/bin",
            "HOME": str(tmp_path),
            "BENCH_CONFIGS": "dense_ae_10tag",
            "BENCH_MACHINES": "2",
            "BENCH_EPOCHS": "2",
            "BENCH_SERVE_MACHINES": "4",
            "BENCH_SERVE_REQUESTS": "8",
            "JAX_PLATFORMS": "cpu",
            # smoke-shape rows must not pollute the checked-in history
            "GORDO_BENCH_HISTORY": os.devnull,
        },
        capture_output=True,
        text=True,
        timeout=420,
        cwd=_REPO_ROOT,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    # ONE JSON line on stdout (the driver contract)
    payload = json.loads(proc.stdout.strip().splitlines()[-1])
    assert payload["metric"] == "machines_trained_per_hour"
    assert payload["value"] > 0
    assert isinstance(payload["vs_baseline"], (int, float))
    cfg = payload["configs"]["dense_ae_10tag"]
    assert REQUIRED_CONFIG_KEYS <= set(cfg)
    assert cfg["exec_s"] > 0 and cfg["compile_s"] > 0
    # execution must be measured separately from ingest: the serial rate
    # can never exceed the execution-only rate
    assert cfg["machines_per_hour_serial"] <= cfg["machines_per_hour"]
    # the serving half of the north star rides the same artifact
    # (VERDICT r3 #2): replicated numbers inline, sharded capacity mode
    # from the 8-virtual-device subprocess leg on this 1-device CPU run
    serving = payload["serving"]
    assert serving["metric"] == "serving_p50_ms"
    assert serving["value"] > 0 and serving["end_to_end_p50_ms"] > 0
    # the serving 5 ms target is a TPU anchor: a CPU-measured run must
    # not carry a cross-device comparison (VERDICT r4 weak #6)
    assert serving["vs_baseline"] is None
    sharded = serving["sharded_cpu_8dev"]
    assert "error" not in sharded, sharded
    assert sharded["shard_mesh_devices"] == 8


def test_all_bench_configs_build_specs():
    """Every bench config (incl. the TPU-only plant shape, which no CPU run
    ever trains) must at least parse into a pipeline and a fleet spec —
    catching config typos long before a one-shot TPU run."""
    import sys

    sys.path.insert(0, _REPO_ROOT)
    import bench

    from gordo_components_tpu.parallel.build_fleet import (
        _analyze_model,
        _spec_for,
    )
    from gordo_components_tpu.serializer import pipeline_from_definition

    configs = bench._configs(full=False, epochs=2, machines=2)
    assert "plant_10ktag_bf16" in configs
    for name, cfg in configs.items():
        probe = pipeline_from_definition(cfg["model"])
        tags = cfg["tags"]
        spec = _spec_for(_analyze_model(probe), tags, tags, cfg["n_splits"])
        assert spec.lookback_window >= 1, name
    plant = configs["plant_10ktag_bf16"]
    assert plant["tags"] == 10_000 and plant.get("tpu_only")
    # the plant config asked for remat (memory-constrained): its derived
    # fold-execution mode must be the sequential scan, every other bench
    # config takes the vmapped (K+1)x parallel-CV path
    plant_spec = _spec_for(
        _analyze_model(pipeline_from_definition(plant["model"])),
        4, 4, plant["n_splits"],
    )
    assert plant_spec.cv_parallel is False
    assert plant_spec.fit_unroll == 1  # remat: no compile/footprint blowup
    assert plant_spec.widen_predict is False  # remat: keep predict narrow
    dense_spec = _spec_for(
        _analyze_model(
            pipeline_from_definition(configs["dense_ae_10tag"]["model"])
        ),
        10, 10, 3,
    )
    assert dense_spec.cv_parallel is True
    assert dense_spec.fit_unroll == 4
    # windowed models keep unroll=1: their batch step already carries an
    # inner time scan / attention stack, and inlining 4 copies blew the
    # XLA:TPU compile from 28.7 s to ~25 min (builder-measured, round 4)
    lstm_spec = _spec_for(
        _analyze_model(
            pipeline_from_definition(configs["lstm_ae_50tag"]["model"])
        ),
        50, 50, 2,
    )
    assert lstm_spec.cv_parallel is True
    assert lstm_spec.fit_unroll == 1
    # ... but keeps the forward-only predict-chunk widening (a memory
    # argument, not a compile-time one)
    assert lstm_spec.widen_predict is True


def test_bench_cv_parallel_env_pins_windowed_configs_only(monkeypatch):
    """The fold-execution knob, exercised through the same helper
    ``_bench_config`` calls: explicit BENCH_CV_PARALLEL=0|1 pins windowed
    configs (flat configs never touched); unset, windowed configs take
    the derived vmap default on CPU but the known-good scan default on a
    TPU backend, where only the canary's explicit =1 unlocks vmap."""
    import sys

    sys.path.insert(0, _REPO_ROOT)
    import bench

    from gordo_components_tpu.parallel.build_fleet import (
        _analyze_model,
        _spec_for,
    )
    from gordo_components_tpu.serializer import pipeline_from_definition

    configs = bench._configs(full=False, epochs=2, machines=2)

    def spec_of(name):
        cfg = configs[name]
        analyzed = _analyze_model(pipeline_from_definition(cfg["model"]))
        return _spec_for(
            analyzed,
            cfg["tags"],
            cfg["tags"],
            n_splits=cfg["n_splits"],
            cv_parallel=bench._cv_parallel_override(analyzed),
        )

    monkeypatch.delenv("BENCH_CV_PARALLEL", raising=False)
    assert spec_of("lstm_ae_50tag").cv_parallel is True  # CPU: derived
    monkeypatch.setenv("BENCH_CV_PARALLEL", "0")
    assert spec_of("dense_ae_10tag").cv_parallel is True  # flat: untouched
    assert spec_of("lstm_ae_50tag").cv_parallel is False  # windowed: pinned
    # unset on a TPU backend: windowed configs take the known-good scan
    # default — the driver's unattended bench must never gamble on the
    # unproven vmap-CV compile; only the canary's explicit =1 unlocks it
    monkeypatch.delenv("BENCH_CV_PARALLEL", raising=False)
    monkeypatch.setattr(bench.jax, "default_backend", lambda: "tpu")
    assert spec_of("lstm_ae_50tag").cv_parallel is False
    assert spec_of("dense_ae_10tag").cv_parallel is True  # flat: untouched
    monkeypatch.setenv("BENCH_CV_PARALLEL", "1")
    assert spec_of("lstm_ae_50tag").cv_parallel is True  # canary-proven


def test_fleet_flops_accounting_trip_adjustment():
    """MFU accounting: the trip-count-adjusted total must dominate the raw
    whole-program cost_analysis figure (which counts each scan body once)
    and scale linearly with epochs — pinning the adjustment the bench's
    MFU is computed from before a one-shot TPU run relies on it."""
    import sys

    sys.path.insert(0, _REPO_ROOT)
    import bench

    from gordo_components_tpu.parallel.build_fleet import (
        _analyze_model,
        _spec_for,
    )
    from gordo_components_tpu.parallel.fleet import (
        compiled_flops,
        fleet_executable,
        fleet_flops_accounting,
    )
    from gordo_components_tpu.serializer import pipeline_from_definition

    cfg = bench._configs(full=False, epochs=4, machines=2)["dense_ae_10tag"]
    probe = pipeline_from_definition(cfg["model"])
    spec = _spec_for(_analyze_model(probe), 10, 10, n_splits=2)
    acct = fleet_flops_accounting(spec, 2, 128, 10, 10)
    assert acct is not None
    # structure: 3 fits x 4 epochs x (128/64=2) steps
    assert acct["train_steps"] == 3 * spec.epochs * (128 // spec.batch_size)
    assert acct["predict_chunks"] == 3 * (128 // spec.batch_size)
    assert acct["total_flops"] > 0
    # doubling epochs doubles train steps, total grows accordingly
    acct2 = fleet_flops_accounting(
        spec._replace(epochs=2 * spec.epochs), 2, 128, 10, 10
    )
    assert acct2["train_steps"] == 2 * acct["train_steps"]
    assert acct2["total_flops"] > acct["total_flops"]
    # the adjusted total dominates the whole-program body-once figure
    compiled, _ = fleet_executable(spec, 2, 128, 10, 10)
    assert acct["total_flops"] >= compiled_flops(compiled)


def test_peak_for_dtype_matches_compute_dtype():
    """MFU denominators are per compute dtype (VERDICT r4 weak #1): f32
    configs divide by the f32 rate (half the bf16 MXU rate), bf16 configs
    by the published bf16 peak; unknown chips report no MFU at all."""
    import sys

    sys.path.insert(0, _REPO_ROOT)
    import bench

    assert bench._peak_for_dtype("TPU v5 lite", "bf16") == 197e12
    assert bench._peak_for_dtype("TPU v5 lite", "f32") == 98.5e12
    assert bench._peak_for_dtype("Colossal CPU", "f32") is None
    # every bench config declares its dtype so the denominator can't drift
    for name, cfg in bench._configs(full=False, epochs=2, machines=2).items():
        assert cfg.get("dtype") in ("f32", "bf16"), name


_FAKE_RESULT = {
    "machines_per_hour": 1000.0,
    "machines_per_hour_serial": 990.0,
    "vs_single_machine": 2.0,
    "shape": "2x864x10",
    "n_splits": 3,
    "exec_s": 0.01,
    "ingest_s": 0.001,
    "ingest_mb": 0.1,
    "ingest_mbps": 100.0,
    "compile_s": 1.0,
    "single_machine_s": 0.02,
    "program_tflops": 0.0,
    "mfu_vs_bf16_peak": None,
    "peak_hbm_gb": None,
}


def test_bench_cpu_backend_skips_mxu_configs(monkeypatch, capsys):
    """Any non-TPU backend skips the windowed MXU-workload configs unless
    BENCH_CONFIGS names them (r3: PatchTST-bf16 on CPU was killed after
    55 min) — and the artifact says exactly what was skipped."""
    import sys

    sys.path.insert(0, _REPO_ROOT)
    import bench

    monkeypatch.setattr(
        bench, "_bench_config", lambda name, cfg: dict(_FAKE_RESULT)
    )
    monkeypatch.setattr(bench, "_calibration_ms", lambda: 1.0)
    monkeypatch.setenv("BENCH_NO_SERVING", "1")
    monkeypatch.setenv("GORDO_BENCH_HISTORY", os.devnull)
    monkeypatch.delenv("BENCH_CONFIGS", raising=False)
    bench.main()
    payload = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert list(payload["configs"]) == ["dense_ae_10tag"]
    assert set(payload["skipped_cpu_configs"]) == {
        "lstm_ae_50tag", "lstm_forecast_100tag", "patchtst_bf16",
    }
    assert payload["device"] == "cpu" and "degraded" not in payload
    # explicit BENCH_CONFIGS overrides the skip (operator's budget)
    monkeypatch.setenv("BENCH_CONFIGS", "lstm_ae_50tag")
    bench.main()
    payload = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert list(payload["configs"]) == ["lstm_ae_50tag"]
    assert "skipped_cpu_configs" not in payload


def test_bench_failed_config_is_recorded_and_fails_the_run(monkeypatch, capsys):
    """A config that raises (plant-scale OOM on a small chip) must not cost
    the other configs their measurement: the artifact stays parseable with
    the headline intact and the error under the config's name — and the
    run exits non-zero, so a failed phase cannot pass as a green bench.
    (_bench_config is stubbed — this tests the error-isolation logic, not a
    real measurement, so it stays in the fast tier.)"""
    import sys

    sys.path.insert(0, _REPO_ROOT)
    import bench

    def stubbed(name, cfg):
        if name != "dense_ae_10tag":
            raise RuntimeError("synthetic OOM")
        return dict(_FAKE_RESULT)

    monkeypatch.setattr(bench, "_bench_config", stubbed)
    monkeypatch.setenv("BENCH_NO_SERVING", "1")
    monkeypatch.setenv("GORDO_BENCH_HISTORY", os.devnull)
    monkeypatch.setenv(
        "BENCH_CONFIGS", "dense_ae_10tag,lstm_ae_50tag"
    )
    with pytest.raises(SystemExit) as exc:
        bench.main()
    assert exc.value.code == 1
    captured = capsys.readouterr()
    payload = json.loads(captured.out.strip().splitlines()[-1])
    assert payload["value"] == 1000.0
    assert payload["configs"]["lstm_ae_50tag"] == {
        "error": "RuntimeError: synthetic OOM"
    }
    assert "lstm_ae_50tag" in captured.err


def test_bench_failed_headline_reports_zero_not_substitute(monkeypatch, capsys):
    """If the HEADLINE config fails, the artifact must say so with value=0 —
    never silently relabel another config's rate as the headline metric."""
    import sys

    sys.path.insert(0, _REPO_ROOT)
    import bench

    def stubbed(name, cfg):
        if name == "dense_ae_10tag":
            raise RuntimeError("synthetic headline OOM")
        return dict(_FAKE_RESULT)

    monkeypatch.setattr(bench, "_bench_config", stubbed)
    monkeypatch.setenv("BENCH_NO_SERVING", "1")
    monkeypatch.setenv("GORDO_BENCH_HISTORY", os.devnull)
    monkeypatch.setenv(
        "BENCH_CONFIGS", "dense_ae_10tag,lstm_ae_50tag"
    )
    with pytest.raises(SystemExit) as exc:
        bench.main()
    assert exc.value.code == 1
    payload = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert payload["value"] == 0
    assert "HEADLINE CONFIG FAILED" in payload["unit"]
    assert payload["configs"]["lstm_ae_50tag"]["machines_per_hour"] == 1000.0


def _run_without_platform(argv, tmp_path):
    """Run a repo script with JAX_PLATFORMS unset: on a chipless machine JAX
    logs the libtpu failure and hands back the CPU."""
    return subprocess.run(
        [sys.executable, *argv],
        env={
            "PATH": "/usr/bin:/bin",
            "HOME": str(tmp_path),
            "GORDO_BENCH_HISTORY": os.devnull,
        },
        capture_output=True,
        text=True,
        timeout=300,
        cwd=_REPO_ROOT,
    )


@pytest.mark.parametrize(
    "script", ["bench.py", "bench_serving.py", "__graft_entry__.py"]
)
def test_entry_points_refuse_a_cpu_nobody_asked_for(script, tmp_path):
    """No chip and no JAX_PLATFORMS=cpu: the script exits non-zero, names
    the platform it got and prints no artifact — it neither re-execs itself
    on the CPU nor measures there under a device metric's name."""
    proc = _run_without_platform([script], tmp_path)
    if proc.returncode == 0:
        pytest.skip("this machine has an accelerator")
    assert proc.returncode == 3, proc.stderr[-2000:]
    assert f"{script}: JAX found no accelerator" in proc.stderr
    assert "cpu" in proc.stderr and "JAX_PLATFORMS=cpu" in proc.stderr
    assert proc.stdout.strip() == ""


def test_bench_children_wait_for_their_own_process(monkeypatch):
    """On a chip the parent holds the device, so the legs that would boot
    processes needing it are reported by name as not run."""
    sys.path.insert(0, _REPO_ROOT)
    import bench
    import bench_serving

    class _Chip:
        platform = "tpu"
        device_kind = "TPU v5 lite"

    fake = {k: 1.0 for k in (
        "value", "end_to_end_p50_ms", "end_to_end_p99_ms", "warmup",
        "concurrent_rps", "saturation", "rps_at_p99_lt_5ms",
        "shard_mesh_devices", "hot_machine_p50_ms",
    )}
    monkeypatch.setattr(bench.jax, "devices", lambda: [_Chip()])
    monkeypatch.setattr(bench_serving, "build_models", lambda *a: {})
    monkeypatch.setattr(
        bench_serving, "measure", lambda **kwargs: dict(fake)
    )

    def no_children(*args, **kwargs):
        raise AssertionError("a chip-holding parent must start no child")

    monkeypatch.setattr(subprocess, "run", no_children)
    out = bench._measure_serving()
    assert out["sharded_cpu_8dev"] == "not run: needs its own process"
    assert "error" not in out["sharded_1dev_tpu"]


@pytest.mark.slow
def test_bench_cpu_run_is_headline_only_and_says_so(tmp_path):
    """JAX_PLATFORMS=cpu is the one way to ask for the CPU: the run measures
    the headline dense fleet, names the skipped MXU-workload configs, and
    labels the artifact with the device it ran on."""
    proc = subprocess.run(
        [sys.executable, "bench.py"],
        env={
            "PATH": "/usr/bin:/bin",
            "HOME": str(tmp_path),
            "BENCH_MACHINES": "2",
            "BENCH_EPOCHS": "2",
            "BENCH_SERVE_MACHINES": "4",
            "BENCH_SERVE_REQUESTS": "8",
            "JAX_PLATFORMS": "cpu",
            # smoke-shape rows must not pollute the checked-in history
            "GORDO_BENCH_HISTORY": os.devnull,
        },
        capture_output=True,
        text=True,
        timeout=420,
        cwd=_REPO_ROOT,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    payload = json.loads(proc.stdout.strip().splitlines()[-1])
    assert list(payload["configs"]) == ["dense_ae_10tag"]
    assert set(payload["skipped_cpu_configs"]) == {
        "lstm_ae_50tag", "lstm_forecast_100tag", "patchtst_bf16",
    }
    assert payload["device"] == "cpu" and "degraded" not in payload
    # the artifact still carries the serving half
    assert payload["serving"]["value"] > 0


@pytest.mark.slow
def test_bench_serving_emits_valid_json(tmp_path):
    proc = subprocess.run(
        [sys.executable, "bench_serving.py"],
        env={
            "PATH": "/usr/bin:/bin",
            "HOME": str(tmp_path),
            "BENCH_SERVE_MACHINES": "4",
            "BENCH_SERVE_REQUESTS": "8",
            "JAX_PLATFORMS": "cpu",
            # smoke-shape rows must not pollute the checked-in history
            "GORDO_BENCH_HISTORY": os.devnull,
        },
        capture_output=True,
        text=True,
        timeout=420,
        cwd=_REPO_ROOT,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    payload = json.loads(proc.stdout.strip().splitlines()[-1])
    assert payload["metric"] == "serving_p50_ms"
    assert payload["value"] > 0
    assert payload["end_to_end_p50_ms"] >= 0
    assert payload["compiled_programs"] >= 1
