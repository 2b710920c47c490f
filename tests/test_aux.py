"""Auxiliary-subsystem tests: fault injection, phase timing/profiling, and
multi-host helpers (SURVEY.md §6)."""

import os

import numpy as np
import pytest

from gordo_components_tpu.dataset import GordoBaseDataset
from gordo_components_tpu.dataset.data_provider.base import GordoBaseDataProvider
from gordo_components_tpu.dataset.data_provider.providers import (
    FlakyDataProvider,
    RandomDataProvider,
)
from gordo_components_tpu.parallel import global_fleet_mesh, initialize_multihost
from gordo_components_tpu.utils.profiling import PhaseTimer, device_trace

DATA_CONFIG = {
    "type": "RandomDataset",
    "train_start_date": "2023-01-01T00:00:00+00:00",
    "train_end_date": "2023-01-03T00:00:00+00:00",
    "tag_list": ["fi-a", "fi-b", "fi-c"],
}


# ------------------------------------------------------------ fault injection
def test_flaky_provider_fails_then_recovers():
    """First load fails mid-stream; the retry succeeds — the reference's
    Argo-retry failure model, reproduced in-process."""
    dataset_config = {
        **DATA_CONFIG,
        "data_provider": {
            "type": "FlakyDataProvider",
            "fail_after": 1,
            "fail_times": 1,
            "provider": {"type": "RandomDataProvider", "min_size": 300,
                         "max_size": 400},
        },
    }
    dataset = GordoBaseDataset.from_dict(dataset_config)
    with pytest.raises(IOError, match="Injected provider failure"):
        dataset.get_data()
    # retry (same dataset object = same provider instance) succeeds
    X, y = dataset.get_data()
    assert X.shape[1] == 3


def test_flaky_provider_config_round_trip():
    provider = FlakyDataProvider(fail_after=2, fail_times=3, min_size=100)
    rebuilt = GordoBaseDataProvider.from_dict(provider.to_dict())
    assert isinstance(rebuilt, FlakyDataProvider)
    assert rebuilt.fail_after == 2
    assert isinstance(rebuilt.provider, RandomDataProvider)


def test_builder_data_failure_is_retryable_exit_code(tmp_path):
    """CLI build surfaces an injected provider failure as the retryable
    exit code, and an orchestrator retry completes."""
    import json

    from click.testing import CliRunner

    from gordo_components_tpu.cli import gordo

    model_config = {"Pipeline": {"steps": [
        "MinMaxScaler",
        {"DenseAutoEncoder": {"kind": "feedforward_symmetric", "dims": [4],
                              "epochs": 1, "batch_size": 32}}]}}
    flaky_data = {
        **DATA_CONFIG,
        "data_provider": {
            "type": "FlakyDataProvider",
            "fail_after": 1,
            "fail_times": 1,
        },
    }
    runner = CliRunner()
    args = ["build", "m", "--model-config", json.dumps(model_config),
            "--output-dir", str(tmp_path / "m"),
            "--cv-mode", "build_only"]
    # IOError propagates as exit code 1 (unexpected infra failure — Argo
    # treats nonzero as retryable); the cache makes the retry idempotent
    first = runner.invoke(gordo, args + ["--data-config", json.dumps(flaky_data)])
    assert first.exit_code != 0
    retry = runner.invoke(gordo, args + ["--data-config", json.dumps(DATA_CONFIG)])
    assert retry.exit_code == 0, retry.output


# ---------------------------------------------------------------- profiling
def test_phase_timer_accumulates():
    timer = PhaseTimer()
    with timer.phase("fetch"):
        pass
    with timer.phase("fetch"):
        pass
    with timer.phase("train"):
        pass
    report = timer.report()
    assert report["fetch"]["count"] == 2
    assert report["train"]["count"] == 1
    assert report["fetch"]["total_s"] >= 0
    import json

    json.dumps(report)


def test_phase_timer_records_on_exception():
    timer = PhaseTimer()
    with pytest.raises(RuntimeError):
        with timer.phase("boom"):
            raise RuntimeError("x")
    assert timer.report()["boom"]["count"] == 1


def test_device_trace_noop_and_real(tmp_path):
    with device_trace(None):  # no-op path
        pass
    import jax.numpy as jnp

    with device_trace(str(tmp_path / "trace")):
        jnp.ones((8, 8)).sum().block_until_ready()
    # jax wrote profile artifacts
    assert any((tmp_path / "trace").rglob("*"))


# ------------------------------------------------------------- distributed
def test_initialize_multihost_single_process_noop():
    # single-process env: must not raise, must leave jax usable
    initialize_multihost()
    import jax

    assert jax.process_count() == 1


def test_global_fleet_mesh_spans_devices():
    mesh = global_fleet_mesh()
    assert mesh.size == 8
    assert mesh.axis_names == ("fleet",)


def _run_multihost_children(extra_argv, timeout, extra_env=None, n_procs=2):
    """The multi-process mesh fixture (tests/fixtures/multiproc.py) —
    kept under its historical local name so this module's many call
    sites read unchanged. See the fixture for the spawn/rendezvous/
    teardown contract (port-race retry, fixed 4 virtual devices per
    process, inherited compilation cache, group kill on timeout)."""
    from fixtures.multiproc import run_mesh_children

    return run_mesh_children(
        extra_argv, timeout, extra_env=extra_env, n_procs=n_procs
    )


@pytest.mark.slow
def test_two_process_distributed_fleet_train():
    """Genuine multi-process training: two OS processes join one
    jax.distributed runtime (Gloo over localhost), span one fleet mesh, and
    run a sharded fleet train step where each process holds only its own
    machines' data (SURVEY.md §2.3 multi-host backend — exercised, not just
    single-process-tested)."""
    codes, outputs = _run_multihost_children([], timeout=120)
    if any(c != 0 for c in codes):  # possible port race — one retry
        codes, outputs = _run_multihost_children([], timeout=120)
    assert all(c == 0 for c in codes), f"children failed:\n" + "\n".join(outputs)
    assert any("trained 8 machines over 2 processes" in o for o in outputs)


@pytest.mark.slow
def test_two_process_build_fleet_sliced(tmp_path):
    """VERDICT r2 #9: the FULL build_fleet pipeline across two processes —
    sliced bucket, process-local streaming ingest (each process fetches only
    its machine shard through the prefetcher), global-batch assembly, and
    per-process artifact writes that union to the whole fleet."""
    import re

    def run_once(out_dir):
        return _run_multihost_children(["--build", out_dir], timeout=300)

    # a FRESH out_dir per attempt: a partially-completed first attempt
    # would otherwise satisfy the retry from the registry cache and break
    # the disjointness asserts below
    out_dir = str(tmp_path / "mhbuild")
    codes, outputs = run_once(out_dir)
    if any(c != 0 for c in codes):  # possible port race — one retry
        out_dir = str(tmp_path / "mhbuild-retry")
        codes, outputs = run_once(out_dir)
    assert all(c == 0 for c in codes), "children failed:\n" + "\n".join(outputs)

    # each process built a DISJOINT shard; the union is the whole fleet
    per_proc = {}
    for out in outputs:
        m = re.search(r"built@(\d+): (\S+)", out)
        assert m, out
        per_proc[int(m.group(1))] = set(m.group(2).split(","))
    all_names = {f"mh-{i:02d}" for i in range(16)}
    assert set.union(*per_proc.values()) == all_names
    assert per_proc[0] & per_proc[1] == set()
    # both slices contributed to both processes (streaming ingest ran
    # per-slice per-process: 16 machines / 2 slices / 2 procs = 4 each)
    assert all(len(names) == 8 for names in per_proc.values())

    # every artifact dir exists with the standard layout
    import json as _json

    for name in all_names:
        model_dir = os.path.join(out_dir, "models", name)
        assert os.path.isdir(model_dir), name
        meta = _json.load(
            open(os.path.join(model_dir, "metadata.json"))
        )
        assert meta["model"]["fleet"]["bucket_size"] == 16
    # per-process manifests: p0 writes the main file, p1 its own shard file
    assert os.path.exists(os.path.join(out_dir, "models", "fleet_manifest.json"))
    assert os.path.exists(
        os.path.join(out_dir, "models", "fleet_manifest.p1.json")
    )


@pytest.mark.slow
def test_two_process_kill_mid_build_restores_from_checkpoint(tmp_path):
    """Multi-host crash-resume end-to-end: every process dies right after
    the first slice's COLLECTIVE checkpoint lands (before any artifact);
    the re-run must restore that slice from the checkpoint instead of
    retraining, and still produce the whole fleet."""
    out_dir = str(tmp_path / "mhcrash")

    codes, outputs = _run_multihost_children(
        ["--build-crash", out_dir], timeout=300
    )
    if not all(c == 17 for c in codes):  # possible port race — one retry
        out_dir = str(tmp_path / "mhcrash-retry")
        codes, outputs = _run_multihost_children(
            ["--build-crash", out_dir], timeout=300
        )
    assert all(c == 17 for c in codes), "\n".join(outputs)
    assert all("crashed-after-checkpoint" in o for o in outputs)
    # nothing was built, but the slice checkpoint survived
    assert not os.path.isdir(os.path.join(out_dir, "models")) or not any(
        name.startswith("mh-")
        for name in os.listdir(os.path.join(out_dir, "models"))
    )
    ckpt_root = os.path.join(out_dir, "models", ".slice_checkpoints")
    assert os.path.isdir(ckpt_root) and os.listdir(ckpt_root)

    # resume: the normal build restores slice 0 and completes the fleet
    codes, outputs = _run_multihost_children(["--build", out_dir],
                                               timeout=300)
    assert all(c == 0 for c in codes), "\n".join(outputs)
    assert any("Restored slice checkpoint" in o for o in outputs)
    for i in range(16):
        assert os.path.isdir(os.path.join(out_dir, "models", f"mh-{i:02d}"))
    # steady state: checkpoints cleaned up after artifacts landed
    assert not os.listdir(ckpt_root) if os.path.isdir(ckpt_root) else True


@pytest.mark.slow
def test_two_process_asymmetric_peer_death_fails_fast_and_resumes(tmp_path):
    """ROADMAP #5 / VERDICT r3 weak #5: ASYMMETRIC multi-host failure. Only
    process 1 dies (at the start of slice 1, with slice 0's commit in flight
    on its commit worker). The survivor must FAIL FAST with a retryable outcome — on
    Gloo the transport detects the dead peer (connection reset ->
    JaxRuntimeError -> generic nonzero exit, which the CLI maps to the
    retryable code; only 64/66 mean permanent) — never complete a partial
    fleet silently and never hang past the drill timeout. The restart-all
    re-run (the reference's Argo/k8s retry model) must resume slice 0 from
    the registry and complete the fleet."""
    out_dir = str(tmp_path / "mhasym")
    env = {"GORDO_SLICE_TIMEOUT_S": "45"}

    codes, outputs = _run_multihost_children(
        ["--build-asym-crash", out_dir], timeout=300, extra_env=env
    )
    if 17 not in codes:  # possible port race — one retry
        out_dir = str(tmp_path / "mhasym-retry")
        codes, outputs = _run_multihost_children(
            ["--build-asym-crash", out_dir], timeout=300, extra_env=env
        )
    assert 17 in codes, (codes, "\n".join(outputs))
    victim_i = codes.index(17)
    survivor_code = codes[1 - victim_i]
    assert "peer-died-asymmetrically" in outputs[victim_i]
    # retryable failure: any POSITIVE nonzero except the permanent
    # config/data codes (75 = the watchdog beat the transport error to
    # it — also valid). Negative = SIGKILLed by the parent timeout = the
    # survivor hung, which is exactly what must not happen.
    assert survivor_code > 0 and survivor_code not in (64, 66), (
        codes, "\n".join(outputs)
    )
    # slice 0's artifacts survived the crash: the survivor's half whole (it
    # drains its commit worker before it lets the transport error out), the
    # victim's as far as its worker had come when it died — a kill loses the
    # slice in flight AND the slice committing beside it
    built_before = {
        name for name in os.listdir(os.path.join(out_dir, "models"))
        if name.startswith("mh-")
    }
    assert 4 <= len(built_before) <= 8, built_before

    # restart-all: a NORMAL re-run (same dirs) resumes and completes —
    # with a realistic watchdog budget (the tight 45s is for freeing
    # survivors in the death phase; the resume pays compile + rendezvous)
    codes, outputs = _run_multihost_children(
        ["--build", out_dir], timeout=300,
        extra_env={"GORDO_SLICE_TIMEOUT_S": "300"},
    )
    assert all(c == 0 for c in codes), "\n".join(outputs)
    for i in range(16):
        assert os.path.isdir(os.path.join(out_dir, "models", f"mh-{i:02d}"))
    # the re-run skipped the already-built slice machines (registry hits)
    assert any("cached" in o for o in outputs)


@pytest.mark.slow
def test_two_process_wedged_collective_watchdog_frees_both(tmp_path):
    """The failure mode the transport CANNOT detect: every peer is alive
    but the slice is wedged (simulated by both processes blocking at the
    start of slice 1, exactly where a stuck collective would hold them).
    No connection ever resets, so without the watchdog this hangs forever;
    with GORDO_SLICE_TIMEOUT_S set, BOTH processes must exit the RETRYABLE
    code 75 with the watchdog's CRITICAL line, and the restart-all re-run
    completes the fleet from the registry."""
    out_dir = str(tmp_path / "mhhang")
    env = {"GORDO_SLICE_TIMEOUT_S": "30"}

    codes, outputs = _run_multihost_children(
        ["--build-hang", out_dir], timeout=300, extra_env=env
    )
    if codes != [75, 75]:  # possible port race — one retry
        out_dir = str(tmp_path / "mhhang-retry")
        codes, outputs = _run_multihost_children(
            ["--build-hang", out_dir], timeout=300, extra_env=env
        )
    assert codes == [75, 75], (codes, "\n".join(outputs))
    for out in outputs:
        assert "wedged-in-slice" in out
        assert "Fleet slice watchdog" in out and "exiting 75" in out
    # slice 0 landed before the wedge
    assert len(os.listdir(os.path.join(out_dir, "models"))) >= 8

    # resume with a realistic watchdog budget (the drill's tight 30s is
    # for catching the wedge; the resume pays compile + rendezvous)
    codes, outputs = _run_multihost_children(
        ["--build", out_dir], timeout=300,
        extra_env={"GORDO_SLICE_TIMEOUT_S": "300"},
    )
    assert all(c == 0 for c in codes), "\n".join(outputs)
    for i in range(16):
        assert os.path.isdir(os.path.join(out_dir, "models", f"mh-{i:02d}"))


@pytest.mark.slow
def test_two_process_heterogeneous_kill_restores_from_checkpoint(tmp_path):
    """The remaining cell of the multi-host rehearsal matrix: kill-mid-
    build x HETEROGENEOUS buckets. Every process dies after the first
    slice's collective checkpoint lands (before any artifact); the normal
    re-run must RESTORE that slice from the checkpoint — whose sharded
    template now comes from the three-bucket fleet, not the homogeneous
    one — and complete all 20 machines across both processes."""
    out_dir = str(tmp_path / "mhhc")
    codes, outputs = _run_multihost_children(
        ["--build-hetero-crash", out_dir], timeout=300
    )
    if not all(c == 17 for c in codes):  # possible port race — one retry
        out_dir = str(tmp_path / "mhhc-retry")
        codes, outputs = _run_multihost_children(
            ["--build-hetero-crash", out_dir], timeout=300
        )
    assert all(c == 17 for c in codes), "\n".join(outputs)
    assert all("crashed-after-checkpoint" in o for o in outputs)
    # no artifact may land before the crash, or the resume run would skip
    # the checkpoint restore via registry hits and never exercise it
    models_dir = os.path.join(out_dir, "models")
    assert not any(
        name.startswith(("hn-", "hw-", "hz-"))
        for name in (os.listdir(models_dir) if os.path.isdir(models_dir) else [])
    )
    ckpt_root = os.path.join(models_dir, ".slice_checkpoints")
    assert os.path.isdir(ckpt_root) and os.listdir(ckpt_root)

    codes, outputs = _run_multihost_children(
        ["--build-hetero", out_dir], timeout=300
    )
    assert all(c == 0 for c in codes), "\n".join(outputs)
    assert any("Restored slice checkpoint" in o for o in outputs)
    for name in (
        [f"hn-{i:02d}" for i in range(10)]
        + [f"hw-{i:02d}" for i in range(6)]
        + [f"hz-{i:02d}" for i in range(4)]
    ):
        assert os.path.isdir(os.path.join(models_dir, name)), name
    # steady state: checkpoints cleaned up once artifacts landed
    assert not os.listdir(ckpt_root) if os.path.isdir(ckpt_root) else True


@pytest.mark.slow
def test_two_process_heterogeneous_buckets(tmp_path):
    """VERDICT r3 weak #5 extension: a HETEROGENEOUS fleet (three buckets —
    two tag widths plus a per-machine n_splits override, none a multiple
    of the 8-device global mesh) through one multi-host build_fleet call.
    Every bucket must shard across both processes disjointly, pad under
    multi-host, and union to the whole fleet."""
    import re

    def run_once(out_dir):
        return _run_multihost_children(
            ["--build-hetero", out_dir], timeout=300
        )

    out_dir = str(tmp_path / "mhhetero")
    codes, outputs = run_once(out_dir)
    if any(c != 0 for c in codes):  # possible port race — one retry
        out_dir = str(tmp_path / "mhhetero-retry")
        codes, outputs = run_once(out_dir)
    assert all(c == 0 for c in codes), "children failed:\n" + "\n".join(outputs)

    per_proc = {}
    for out in outputs:
        m = re.search(r"built@(\d+): (\S+)", out)
        assert m, out
        per_proc[int(m.group(1))] = set(m.group(2).split(","))
    all_names = (
        {f"hn-{i:02d}" for i in range(10)}
        | {f"hw-{i:02d}" for i in range(6)}
        | {f"hz-{i:02d}" for i in range(4)}
    )
    assert set.union(*per_proc.values()) == all_names
    assert per_proc[0] & per_proc[1] == set()
    # buckets larger than one process's device share (4 of the global 8)
    # must genuinely span both processes; the 4-machine hz bucket
    # legitimately collapses onto process 0 (positional machine shards +
    # mesh padding), which is itself worth pinning
    for prefix in ("hn", "hw"):
        for names in per_proc.values():
            assert any(n.startswith(prefix) for n in names), (
                f"bucket {prefix} missing from a process: {per_proc}"
            )

    import json as _json

    for name in all_names:
        meta = _json.load(
            open(os.path.join(out_dir, "models", name, "metadata.json"))
        )
        expected_splits = 0 if name.startswith("hz") else 2
        assert (
            meta["model"]["model_builder_metadata"]["cross_validation"][
                "n_splits"
            ]
            == expected_splits
        ), name


@pytest.mark.slow
def test_two_process_checkpoint_roundtrip(tmp_path):
    """Collective orbax slice checkpoints: two processes save a sharded
    tree, restore through the sharded template (each process its own
    shards, bit-exact), and finalize with the barrier+proc-0 delete."""
    out = str(tmp_path / "ckpt")
    codes, outputs = _run_multihost_children(
        ["--ckpt-roundtrip", out], timeout=180
    )
    if any(c != 0 for c in codes):  # possible port race — one retry
        codes, outputs = _run_multihost_children(
            ["--ckpt-roundtrip", str(tmp_path / "ckpt2")], timeout=180
        )
    assert all(c == 0 for c in codes), "children failed:\n" + "\n".join(outputs)
    assert any("ckpt-roundtrip@0 OK" in o for o in outputs)
    assert any("ckpt-roundtrip@1 OK" in o for o in outputs)


# ------------------------------------------------- 4-process drills (r5 #5)
# The v5e-16 north star is 4 hosts; 2-process symmetry hides the
# rendezvous/barrier bugs that 2->4 exposes (every collective path below
# crosses >2 processes, and the two-victim drill punches NON-ADJACENT
# holes in the ring). Same child modes as the 2-process drills — the
# child is process-count-agnostic by construction.


@pytest.mark.slow
def test_four_process_heterogeneous_buckets(tmp_path):
    """The three-bucket heterogeneous fleet through one build_fleet call
    across FOUR Gloo processes (16 global devices): disjoint per-process
    artifact shards unioning to the whole fleet, with the per-machine
    n_splits override intact."""
    import json as _json
    import re

    def run_once(out_dir):
        return _run_multihost_children(
            ["--build-hetero", out_dir], timeout=420, n_procs=4
        )

    out_dir = str(tmp_path / "mh4hetero")
    codes, outputs = run_once(out_dir)
    if any(c != 0 for c in codes):  # possible port race — one retry
        out_dir = str(tmp_path / "mh4hetero-retry")
        codes, outputs = run_once(out_dir)
    assert all(c == 0 for c in codes), "children failed:\n" + "\n".join(outputs)

    per_proc = {}
    for out in outputs:
        m = re.search(r"built@(\d+): (\S*)", out)
        assert m, out
        per_proc[int(m.group(1))] = {
            n for n in m.group(2).split(",") if n
        }
    all_names = (
        {f"hn-{i:02d}" for i in range(10)}
        | {f"hw-{i:02d}" for i in range(6)}
        | {f"hz-{i:02d}" for i in range(4)}
    )
    assert set.union(*per_proc.values()) == all_names
    for a in per_proc:
        for b in per_proc:
            if a < b:
                assert per_proc[a] & per_proc[b] == set(), (a, b, per_proc)
    for name in all_names:
        meta = _json.load(
            open(os.path.join(out_dir, "models", name, "metadata.json"))
        )
        expected_splits = 0 if name.startswith("hz") else 2
        assert (
            meta["model"]["model_builder_metadata"]["cross_validation"][
                "n_splits"
            ]
            == expected_splits
        ), name


@pytest.mark.slow
def test_four_process_checkpoint_roundtrip(tmp_path):
    """Collective orbax slice checkpoints at four processes: every process
    saves/restores ITS shards of the 16-device sharded tree bit-exact, and
    the finalize barrier holds with 4 participants."""
    out = str(tmp_path / "ckpt4")
    codes, outputs = _run_multihost_children(
        ["--ckpt-roundtrip", out], timeout=240, n_procs=4
    )
    if any(c != 0 for c in codes):  # possible port race — one retry
        codes, outputs = _run_multihost_children(
            ["--ckpt-roundtrip", str(tmp_path / "ckpt4b")],
            timeout=240,
            n_procs=4,
        )
    assert all(c == 0 for c in codes), "children failed:\n" + "\n".join(outputs)
    for pid in range(4):
        assert any(f"ckpt-roundtrip@{pid} OK" in o for o in outputs), pid


@pytest.mark.slow
def test_four_process_two_nonadjacent_peer_deaths_fail_fast_and_resume(
    tmp_path,
):
    """VERDICT r4 #5's named drill: ranks 1 and 3 (non-adjacent) die at the
    start of slice 1; survivors 0 and 2 each have a dead neighbor on some
    collective path and must fail fast RETRYABLY (transport error or
    watchdog 75 — never a clean exit, never a permanent code, never a
    hang). The restart-all re-run resumes slice 0 from the registry and
    completes the fleet."""
    out_dir = str(tmp_path / "mh4asym")
    env = {"GORDO_SLICE_TIMEOUT_S": "45"}

    codes, outputs = _run_multihost_children(
        ["--build-asym-crash2", out_dir], timeout=420, extra_env=env,
        n_procs=4,
    )
    if codes.count(17) != 2:  # possible port race — one retry
        out_dir = str(tmp_path / "mh4asym-retry")
        codes, outputs = _run_multihost_children(
            ["--build-asym-crash2", out_dir], timeout=420, extra_env=env,
            n_procs=4,
        )
    assert codes.count(17) == 2, (codes, "\n".join(outputs))
    assert codes[1] == 17 and codes[3] == 17, codes
    for victim in (1, 3):
        assert "peer-died-asymmetrically" in outputs[victim]
    for survivor in (0, 2):
        # positive nonzero only: a NEGATIVE code means the parent timeout
        # SIGKILLed a hung survivor — the exact regression this drill
        # hunts, which must fail the test, not slip past as "nonzero"
        assert codes[survivor] > 0 and codes[survivor] not in (17, 64, 66), (
            codes,
            outputs[survivor][-2000:],
        )
    # slice 0's artifacts survived the deaths: the two survivors' quarters
    # whole (each drains its commit worker before it exits), the victims' as
    # far as their workers had come when they died
    built_before = {
        name
        for name in os.listdir(os.path.join(out_dir, "models"))
        if name.startswith("mh-")
    }
    assert 4 <= len(built_before) <= 8, built_before

    # resume with a REALISTIC watchdog budget: the drill's tight 45s
    # exists to free the survivors quickly in the death phase; the
    # resume's remaining slice legitimately pays compile + 4-way Gloo
    # rendezvous + orbax barrier, which exceeds 45s on a loaded box
    codes, outputs = _run_multihost_children(
        ["--build", out_dir], timeout=420,
        extra_env={"GORDO_SLICE_TIMEOUT_S": "300"}, n_procs=4,
    )
    assert all(c == 0 for c in codes), "\n".join(outputs)
    for i in range(16):
        assert os.path.isdir(os.path.join(out_dir, "models", f"mh-{i:02d}"))
    assert any("cached" in o for o in outputs)


@pytest.mark.slow
def test_four_process_kill_mid_build_restores_from_checkpoint(tmp_path):
    """Kill/restore at four processes: all four die right after the first
    slice's collective checkpoint lands; the normal re-run must restore
    that slice (sharded over 16 devices across 4 processes) instead of
    retraining, and complete the fleet."""
    out_dir = str(tmp_path / "mh4crash")
    codes, outputs = _run_multihost_children(
        ["--build-crash", out_dir], timeout=420, n_procs=4
    )
    if not all(c == 17 for c in codes):  # possible port race — one retry
        out_dir = str(tmp_path / "mh4crash-retry")
        codes, outputs = _run_multihost_children(
            ["--build-crash", out_dir], timeout=420, n_procs=4
        )
    assert all(c == 17 for c in codes), (codes, "\n".join(outputs))
    assert all("crashed-after-checkpoint" in o for o in outputs)
    ckpt_root = os.path.join(out_dir, "models", ".slice_checkpoints")
    assert os.path.isdir(ckpt_root) and os.listdir(ckpt_root)

    codes, outputs = _run_multihost_children(
        ["--build", out_dir], timeout=420, n_procs=4
    )
    assert all(c == 0 for c in codes), "\n".join(outputs)
    assert any("Restored slice checkpoint" in o for o in outputs)
    for i in range(16):
        assert os.path.isdir(os.path.join(out_dir, "models", f"mh-{i:02d}"))


@pytest.mark.slow
def test_four_process_ring_attention_parity():
    """Ring attention across PROCESS boundaries (SURVEY §6.7 x §2.3): the
    sequence axis shards over all 16 global devices of 4 Gloo processes,
    so K/V ring hops traverse the inter-process transport — the CPU
    stand-in for multi-host ICI/DCN. Every process must get dense-parity
    output on its own shards."""
    codes, outputs = _run_multihost_children(
        ["--ring"], timeout=240, n_procs=4
    )
    if any(c != 0 for c in codes):  # possible port race — one retry
        codes, outputs = _run_multihost_children(
            ["--ring"], timeout=240, n_procs=4
        )
    assert all(c == 0 for c in codes), "children failed:\n" + "\n".join(outputs)
    for pid in range(4):
        assert any(
            f"ring-attention@{pid} OK over 16 devices (dense+flash hops)"
            in o
            for o in outputs
        ), pid


# ------------------------------------------------- backend start-up rules
def test_require_accelerator_accepts_requested_cpu(monkeypatch):
    from gordo_components_tpu.utils.backend import require_accelerator

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert require_accelerator("test-script").platform == "cpu"


def test_require_accelerator_refuses_unrequested_cpu(monkeypatch, capsys):
    """JAX falls back to the CPU without failing when it finds no chip; an
    entry point that did not ask for the CPU must exit non-zero and name
    the platform it got."""
    from gordo_components_tpu.utils.backend import require_accelerator

    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(SystemExit) as exc:
        require_accelerator("test-script")
    assert exc.value.code == 3
    err = capsys.readouterr().err
    assert "test-script" in err and "cpu" in err and "JAX_PLATFORMS" in err


@pytest.fixture
def config_updates(monkeypatch):
    """Every ``jax.config.update(name, value)`` the code under test makes,
    recorded instead of applied."""
    import jax as _jax

    updates = []
    monkeypatch.setattr(
        _jax.config, "update", lambda name, value: updates.append((name, value))
    )
    monkeypatch.delenv("GORDO_COMPILE_CACHE", raising=False)
    monkeypatch.delenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
                       raising=False)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    return updates


def test_compile_cache_placed_by_operator_is_left_alone(
    config_updates, monkeypatch, tmp_path
):
    from gordo_components_tpu.utils.backend import (
        enable_persistent_compile_cache,
    )

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_persistent_compile_cache() == str(tmp_path)
    assert config_updates == []


def test_compile_cache_defaults_to_fixed_checkout_path(
    config_updates, monkeypatch
):
    from gordo_components_tpu.utils.backend import (
        enable_persistent_compile_cache,
    )

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    expected = os.path.join(repo_root, ".jax_compilation_cache")
    assert enable_persistent_compile_cache() == expected
    assert config_updates == [("jax_compilation_cache_dir", expected)]


def test_compile_cache_off_leaves_operator_variable(
    config_updates, monkeypatch, tmp_path
):
    from gordo_components_tpu.utils.backend import (
        enable_persistent_compile_cache,
    )

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setenv("GORDO_COMPILE_CACHE", "off")
    assert enable_persistent_compile_cache() == ""
    assert config_updates == [("jax_compilation_cache_dir", None)]
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == str(tmp_path)


def test_compile_cache_keeps_every_program_off_the_cpu(
    config_updates, monkeypatch, tmp_path
):
    """On the chip most programs compile in under JAX's one-second bar and
    would never be stored; unless the CPU was asked for (or the operator
    set the bar), the helper keeps them all — and still sets no dir."""
    from gordo_components_tpu.utils.backend import (
        enable_persistent_compile_cache,
    )

    keep_all = ("jax_persistent_cache_min_compile_time_secs", 0.0)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.delenv("JAX_PLATFORMS")
    enable_persistent_compile_cache()
    assert config_updates == [keep_all]
    config_updates.clear()
    monkeypatch.setenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "5")
    enable_persistent_compile_cache()
    assert config_updates == []
