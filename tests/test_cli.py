"""CLI tests via click's CliRunner (SURVEY.md §5)."""

import json
import os

import numpy as np
import pytest
import yaml
from click.testing import CliRunner

from gordo_components_tpu.cli import gordo
from gordo_components_tpu.serializer import load, load_metadata

DATA_CONFIG = {
    "type": "RandomDataset",
    "train_start_date": "2023-01-01T00:00:00+00:00",
    "train_end_date": "2023-01-03T00:00:00+00:00",
    "tag_list": ["cli-a", "cli-b"],
}

MODEL_CONFIG = {
    "Pipeline": {
        "steps": [
            "MinMaxScaler",
            {"DenseAutoEncoder": {"kind": "feedforward_symmetric", "dims": [4],
                                  "epochs": 1, "batch_size": 32}},
        ]
    }
}

FLEET_YAML = {
    "project-name": "cli-fleet",
    "machines": [
        {"name": "fm-1", "dataset": {"tag_list": ["f1-a", "f1-b"]}},
        {"name": "fm-2", "dataset": {"tag_list": ["f2-a", "f2-b"]}},
    ],
    "globals": {
        "model": {
            "DiffBasedAnomalyDetector": {
                "base_estimator": {
                    "TransformedTargetRegressor": {
                        "regressor": {
                            "Pipeline": {
                                "steps": [
                                    "MinMaxScaler",
                                    {"DenseAutoEncoder": {
                                        "kind": "feedforward_symmetric",
                                        "dims": [4], "epochs": 1,
                                        "batch_size": 32}},
                                ]
                            }
                        },
                        "transformer": "MinMaxScaler",
                    }
                }
            }
        },
        "dataset": {
            "type": "RandomDataset",
            "train_start_date": "2023-01-01T00:00:00+00:00",
            "train_end_date": "2023-01-03T00:00:00+00:00",
        },
    },
}


@pytest.fixture
def runner():
    return CliRunner()


def test_cli_help(runner):
    result = runner.invoke(gordo, ["--help"])
    assert result.exit_code == 0
    for command in ("build", "fleet-build", "run-server", "workflow", "client"):
        assert command in result.output


def test_cli_build_env_vars(runner, tmp_path):
    """Argo-style invocation: configs via env vars."""
    out = str(tmp_path / "model")
    result = runner.invoke(
        gordo,
        ["build", "cli-machine", "--cv-mode", "build_only"],
        env={
            "MODEL_CONFIG": json.dumps(MODEL_CONFIG),
            "DATA_CONFIG": json.dumps(DATA_CONFIG),
            "OUTPUT_DIR": out,
            "MODEL_REGISTER_DIR": str(tmp_path / "reg"),
        },
    )
    assert result.exit_code == 0, result.output
    assert out in result.output
    model = load(out)
    assert model.predict(np.zeros((3, 2), np.float32)).shape == (3, 2)
    assert load_metadata(out)["name"] == "cli-machine"


def test_cli_build_exit_codes(runner, tmp_path):
    # bad model config -> 64 (permanent config error)
    result = runner.invoke(
        gordo,
        ["build", "m", "--model-config", json.dumps({"NoSuchModel": {}}),
         "--data-config", json.dumps(DATA_CONFIG),
         "--output-dir", str(tmp_path / "x")],
    )
    assert result.exit_code == 64
    # insufficient data -> 66 (retryable)
    short_data = {**DATA_CONFIG, "row_threshold": 10_000_000}
    result = runner.invoke(
        gordo,
        ["build", "m", "--model-config", json.dumps(MODEL_CONFIG),
         "--data-config", json.dumps(short_data),
         "--output-dir", str(tmp_path / "y")],
    )
    assert result.exit_code == 66
    # missing config entirely -> 64
    result = runner.invoke(
        gordo, ["build", "m", "--output-dir", str(tmp_path / "z")], env={}
    )
    assert result.exit_code in (64, 2)


@pytest.mark.slow
def test_cli_fleet_build(runner, tmp_path):
    config_file = tmp_path / "fleet.yaml"
    config_file.write_text(yaml.safe_dump(FLEET_YAML))
    out = str(tmp_path / "models")
    result = runner.invoke(
        gordo,
        ["fleet-build", "--machine-config", str(config_file),
         "--output-dir", out, "--n-splits", "0", "--n-devices", "2"],
    )
    assert result.exit_code == 0, result.output
    dirs = json.loads(result.output)
    assert set(dirs) == {"fm-1", "fm-2"}
    for model_dir in dirs.values():
        assert os.path.isdir(model_dir)
        load(model_dir)


def test_cli_fleet_build_device_error_exit_codes(runner, tmp_path, monkeypatch):
    """ADVICE r4: JaxRuntimeError no longer maps wholesale to retryable
    75 — the generated Job Ignores 75, so a deterministic device failure
    (HBM OOM / invalid XLA program) would crash-loop on TPU quota forever.
    Those exit the permanent code (70, which the Job FailJobs on); genuine
    transport/collective failures keep the retryable contract."""
    from jax.errors import JaxRuntimeError

    from gordo_components_tpu import parallel as parallel_pkg

    config_file = tmp_path / "fleet.yaml"
    config_file.write_text(yaml.safe_dump(FLEET_YAML))
    args = ["fleet-build", "--machine-config", str(config_file),
            "--output-dir", str(tmp_path / "m")]

    def _raising(message):
        def fake_build_fleet(*a, **k):
            raise JaxRuntimeError(message)

        return fake_build_fleet

    for message, expected in (
        ("RESOURCE_EXHAUSTED: attempting to allocate 21.0G", 70),
        ("RESOURCE_EXHAUSTED: out of HBM on device 0", 70),
        ("INVALID_ARGUMENT: unsupported HLO", 70),
        # gRPC reuses RESOURCE_EXHAUSTED for transient flow-control on
        # cross-host transfers: without allocator wording it stays 75
        ("RESOURCE_EXHAUSTED: received trailing metadata size exceeds limit", 75),
        ("UNAVAILABLE: connection reset by peer in all-gather", 75),
        ("INTERNAL: something opaque the transport saw", 75),
    ):
        monkeypatch.setattr(parallel_pkg, "build_fleet", _raising(message))
        result = runner.invoke(gordo, args)
        assert result.exit_code == expected, (message, result.output)


def test_permanent_xla_classifier_is_anchored():
    """ADVICE r5: the permanent-failure classifier must match statuses at
    the START of the message — a transient failure whose wrapped error
    text merely EMBEDS a permanent-looking status must stay retryable."""
    from gordo_components_tpu.cli.cli import _is_permanent_xla_error

    # leading statuses classify (jax raises as "STATUS: detail")
    assert _is_permanent_xla_error("INVALID_ARGUMENT: unsupported HLO")
    assert _is_permanent_xla_error("  INVALID_ARGUMENT: after whitespace")
    assert _is_permanent_xla_error(
        "RESOURCE_EXHAUSTED: attempting to allocate 21.0G"
    )
    # embedded statuses do NOT: a dead-peer transport error quoting its
    # peer's INVALID_ARGUMENT must retry, not FailJob the build
    assert not _is_permanent_xla_error(
        "UNAVAILABLE: peer reported INVALID_ARGUMENT: bad collective"
    )
    assert not _is_permanent_xla_error(
        "INTERNAL: retrying after RESOURCE_EXHAUSTED: allocation failed"
    )
    # RESOURCE_EXHAUSTED without allocator wording stays retryable
    assert not _is_permanent_xla_error(
        "RESOURCE_EXHAUSTED: trailing metadata size exceeds limit"
    )


def _jax_cache_dir():
    import jax as _jax

    # empty string when the parent runs cacheless (children treat "" as
    # unset) — None would crash subprocess env construction
    return _jax.config.jax_compilation_cache_dir or ""


def test_cli_compiling_commands_follow_the_one_cache_rule(
    runner, tmp_path, monkeypatch
):
    """build / fleet-build / run-server all call the ONE cache helper
    before their first compile, and with JAX_COMPILATION_CACHE_DIR placed
    by the operator none of them sets a cache dir of its own (the old
    <output-dir>/.jax_compilation_cache default never hit from a fresh
    output dir). The commands get bad inputs so only the wiring runs."""
    import jax as _jax

    from gordo_components_tpu.utils import backend as backend_mod

    helper_calls = []
    real_helper = backend_mod.enable_persistent_compile_cache
    monkeypatch.setattr(
        backend_mod,
        "enable_persistent_compile_cache",
        lambda: helper_calls.append(1) or real_helper(),
    )
    cache_dir_updates = []
    real_update = _jax.config.update

    def spy(name, value):
        if name == "jax_compilation_cache_dir":
            cache_dir_updates.append(value)
        else:
            real_update(name, value)

    monkeypatch.setattr(_jax.config, "update", spy)
    monkeypatch.delenv("GORDO_COMPILE_CACHE", raising=False)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "placed"))
    out = str(tmp_path / "models")
    commands = [
        ["fleet-build", "--machine-config", "{not valid",
         "--output-dir", out],
        ["build", "m1", "--model-config", "{not valid",
         "--data-config", "{}", "--output-dir", out],
        ["run-server", "--model-dir", str(tmp_path / "no-such-model")],
    ]
    for argv in commands:
        helper_calls.clear()
        assert runner.invoke(gordo, argv).exit_code != 0, argv
        assert helper_calls == [1], argv
    assert cache_dir_updates == []
    assert not os.path.exists(os.path.join(out, ".jax_compilation_cache"))
    # --compile-cache-dir (the directory form of the knob) is gone
    result = runner.invoke(
        gordo, ["fleet-build", *commands[0][1:], "--compile-cache-dir", "x"]
    )
    assert result.exit_code == 2 and "No such option" in result.output


@pytest.mark.slow
def test_cli_fleet_build_multihost_flags(tmp_path):
    """--coordinator-address wires jax.distributed init + the global fleet
    mesh into fleet-build. Run as a 1-process 'cluster' in a subprocess
    (distributed init is process-global state pytest must not inherit)."""
    import socket
    import subprocess
    import sys

    config_file = tmp_path / "fleet.yaml"
    config_file.write_text(yaml.safe_dump(FLEET_YAML))
    out = str(tmp_path / "models")
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    proc = subprocess.run(
        [sys.executable, "-m", "gordo_components_tpu.cli", "fleet-build",
         "--machine-config", str(config_file), "--output-dir", out,
         "--n-splits", "0",
         "--coordinator-address", f"127.0.0.1:{port}",
         "--num-processes", "1", "--process-id", "0"],
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
             # subprocesses don't inherit conftest's jax.config cache setting
             "JAX_COMPILATION_CACHE_DIR": _jax_cache_dir()},
        capture_output=True,
        text=True,
        timeout=420,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    dirs = json.loads(proc.stdout)
    assert set(dirs) == {"fm-1", "fm-2"}
    for model_dir in dirs.values():
        load(model_dir)


def test_cli_workflow_generate(runner, tmp_path):
    config_file = tmp_path / "fleet.yaml"
    config_file.write_text(yaml.safe_dump(FLEET_YAML))
    result = runner.invoke(
        gordo, ["workflow", "generate", "--machine-config", str(config_file)]
    )
    assert result.exit_code == 0, result.output
    documents = [d for d in yaml.safe_load_all(result.output) if d]
    assert documents[0]["kind"] == "Workflow"

    out_file = str(tmp_path / "manifest.yaml")
    result = runner.invoke(
        gordo,
        ["workflow", "generate", "--machine-config", str(config_file),
         "--tpu", "--output-file", out_file],
    )
    assert result.exit_code == 0, result.output
    with open(out_file) as fh:
        documents = [d for d in yaml.safe_load_all(fh) if d]
    assert [d["kind"] for d in documents] == ["Job", "Deployment"]


def test_cli_module_entrypoint():
    """python -m gordo_components_tpu.cli --help must work (container
    command shape in the generated manifests)."""
    import subprocess
    import sys

    proc = subprocess.run(
        [sys.executable, "-m", "gordo_components_tpu.cli", "--help"],
        capture_output=True,
        text=True,
        cwd="/root/repo",
        timeout=120,
    )
    assert proc.returncode == 0
    assert "fleet-build" in proc.stdout


def test_debug_nans_flag():
    """--debug-nans flips jax_debug_nans (SURVEY.md §6.2 numeric sanitizer)."""
    import jax
    from click.testing import CliRunner

    from gordo_components_tpu.cli import gordo

    assert not jax.config.jax_debug_nans
    runner = CliRunner()
    result = runner.invoke(gordo, ["--debug-nans", "build", "--help"])
    try:
        assert result.exit_code == 0, result.output
        assert jax.config.jax_debug_nans
    finally:
        jax.config.update("jax_debug_nans", False)
