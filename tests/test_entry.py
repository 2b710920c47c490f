"""Driver entry-point coverage (VERDICT r1 #1: ``__graft_entry__`` shipped
untested and the multichip dryrun was red).

``entry()`` must jit + execute single-device; ``dryrun_multichip`` must work
both in-process (enough devices — the conftest provisions 8 virtual CPUs)
and via its self-provisioning subprocess path (more devices requested than
this process has)."""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

_REPO_ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(_REPO_ROOT))

import __graft_entry__ as graft_entry  # noqa: E402


def test_entry_jits_and_executes():
    fn, example_args = graft_entry.entry()
    recon, err, total = jax.jit(fn)(*example_args)
    jax.block_until_ready(total)
    x = example_args[1]
    assert recon.shape == x.shape
    assert err.shape == x.shape
    assert total.shape == (x.shape[0],)
    assert bool(jnp.isfinite(total).all())


def test_entry_scoring_semantics():
    """Scoring must respond to scale/offset independently of the model:
    zero scale+offset kills the score, doubling the scale doubles it."""
    fn, (params, x, scale, offset) = graft_entry.entry()
    _, _, total_zero = fn(params, x, jnp.zeros_like(scale), jnp.zeros_like(offset))
    assert jnp.allclose(total_zero, 0.0, atol=1e-6)
    _, err1, total1 = fn(params, x, scale, jnp.zeros_like(offset))
    _, err2, total2 = fn(params, x, 2.0 * scale, jnp.zeros_like(offset))
    assert jnp.allclose(err2, 2.0 * err1, atol=1e-5)
    assert jnp.allclose(total2, 2.0 * total1, atol=1e-4)


@pytest.mark.slow
def test_dryrun_multichip_in_process():
    assert jax.device_count() >= 8, "conftest must provision 8 virtual devices"
    graft_entry.dryrun_multichip(8)


@pytest.mark.slow
def test_dryrun_multichip_subprocess_self_provisions():
    """Request more devices than this process has → the subprocess path
    (the exact path the single-TPU driver host exercises)."""
    n = jax.device_count() * 2
    graft_entry.dryrun_multichip(n)


def test_graft_entry_refuses_a_cpu_nobody_asked_for(tmp_path):
    """No chip and no JAX_PLATFORMS=cpu: the script exits non-zero, names
    the platform it got and prints no artifact — it neither re-execs itself
    on the CPU nor measures there under a device metric's name. (With
    JAX_PLATFORMS unset, on a chipless machine JAX logs the libtpu failure
    and hands back the CPU.)"""
    import subprocess

    proc = subprocess.run(
        [sys.executable, "__graft_entry__.py"],
        env={"PATH": "/usr/bin:/bin", "HOME": str(tmp_path)},
        capture_output=True, text=True, timeout=300,
        cwd=str(_REPO_ROOT),
    )
    if proc.returncode == 0:
        pytest.skip("this machine has an accelerator")
    assert proc.returncode == 3, proc.stderr[-2000:]
    assert "__graft_entry__.py: JAX found no accelerator" in proc.stderr
    assert "cpu" in proc.stderr and "JAX_PLATFORMS=cpu" in proc.stderr
    assert proc.stdout.strip() == ""


# -- chip_smoke.py ----------------------------------------------------------


def _chip_smoke(argv, cwd, timeout):
    import os
    import subprocess

    env = dict(os.environ)  # conftest's JAX_PLATFORMS=cpu + 8 devices
    return subprocess.run(
        [sys.executable, "chip_smoke.py", *argv],
        env=env, cwd=str(cwd), capture_output=True, text=True,
        timeout=timeout,
    )


def test_chip_smoke_demands_the_chip():
    """No TPU and no --rehearse: non-zero within seconds, the platform
    named, nothing built and no result line."""
    import time

    started = time.monotonic()
    proc = _chip_smoke([], _REPO_ROOT, timeout=120)
    assert proc.returncode == 2, proc.stderr[-2000:]
    assert time.monotonic() - started < 60
    assert "JAX found no TPU" in proc.stderr and "cpu:cpu" in proc.stderr
    assert proc.stdout.strip() == ""
    assert "stage fleet_build" not in proc.stderr


def test_chip_smoke_alone_is_not_the_program(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the
    repo the script cannot pass, even past the device check."""
    import shutil

    shutil.copy(_REPO_ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    proc = _chip_smoke(["--rehearse"], tmp_path, timeout=120)
    assert proc.returncode == 2, proc.stderr[-2000:]
    assert "not beside this script" in proc.stderr
    assert proc.stdout.strip() == ""


@pytest.mark.slow
def test_chip_smoke_rehearsal_end_to_end():
    """The same stages at a tiny size on the CPU (8 virtual devices: the
    fleet trains sharded over all of them)."""
    import json

    proc = _chip_smoke(["--rehearse"], _REPO_ROOT, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    report_line, result_line = proc.stdout.strip().splitlines()[-2:]
    # the last line is the result, with exactly these keys: its reader
    # refuses anything more
    assert json.loads(result_line) == {
        "ok": True,
        "device": {
            "platform": "cpu", "kind": "cpu", "count": jax.device_count(),
        },
    }
    summary = json.loads(report_line)["report"]
    assert summary["rehearsal"] is True
    assert set(summary["stages"]) == {
        "device", "fleet_build", "serve", "safety_nets", "second_boot",
        "kernel",
    }
    assert all(stage["ok"] for stage in summary["stages"].values())
    assert summary["stages"]["fleet_build"]["trained_on"]["count"] == (
        jax.device_count()
    )
    assert summary["stages"]["second_boot"]["aot_store"]["hit"] > 0
