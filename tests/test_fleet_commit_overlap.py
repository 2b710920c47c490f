"""The fleet build loop's overlap (ARCHITECTURE §13): slice ``s`` commits on
the ``fleet-commit`` worker while slice ``s+1`` trains. What has to hold
beside it: one commit in flight; inside a slice's commit the parent's order
(artifact durable, registry key, manifest, only then the checkpoint's
deletion); ``build_fleet`` returns or raises only after the commit in flight
has ended; a commit that raises ends the job; a kill inside a commit finds the
slice's checkpoint; and a commit touches no device.

Small ``RandomDataset`` fleets on the CPU: six machines, three slices of two.
"""

import copy
import glob
import importlib
import json
import os
import threading
import time

import jax
import numpy as np
import pytest

from gordo_components_tpu.observability import flightrec
from gordo_components_tpu.parallel import (
    FleetMachineConfig,
    MachineBatch,
    build_fleet,
    train_fleet_arrays,
)
from gordo_components_tpu.serializer import load, pipeline_from_definition
from gordo_components_tpu.store import journal as store_journal
from gordo_components_tpu.utils import disk_registry

# the span tests' small fleet: six machines in slices of two, a dense model,
# and the dataset that ends a job from the third slice's first fetch on
from test_fleet_spans import DATASET, N_MACHINES, SLICE, StopJob
from test_fleet_spans import MODEL as DENSE

bf = importlib.import_module("gordo_components_tpu.parallel.build_fleet")

N_SLICES = N_MACHINES // SLICE
SLOW_S = 0.3  # a commit a machine made slow on purpose
LSTM = copy.deepcopy(DENSE)
LSTM["DiffBasedAnomalyDetector"]["base_estimator"]["TransformedTargetRegressor"][
    "regressor"]["Pipeline"]["steps"][1] = {"LSTMAutoEncoder": {
        "kind": "lstm_symmetric", "dims": [4], "lookback_window": 4,
        "epochs": 1, "batch_size": 32}}


def _machines(prefix, **data):
    return [
        FleetMachineConfig(
            name=f"{prefix}-{i}", model_config=DENSE,
            data_config={**DATASET, "tag_list": [f"{prefix}{i}-a", f"{prefix}{i}-b"],
                         **{k: (v(i) if callable(v) else v) for k, v in data.items()}},
        )
        for i in range(N_MACHINES)
    ]


def _names(prefix, sl):
    return [f"{prefix}-{i}" for i in range(sl * SLICE, (sl + 1) * SLICE)]


def _manifest(out):
    with open(os.path.join(out, bf.MANIFEST_FILE)) as fh:
        return json.load(fh)


def _slow_commits(monkeypatch, seconds=SLOW_S, dies_at=None, error=RuntimeError):
    """``_commit_machine`` made slow, and deadly from machine ``dies_at``
    on; returns the names it was asked to commit, in order."""
    real, asked = bf._commit_machine, []

    def commit(item, *args, **kwargs):
        name = item["machine"].name
        asked.append(name)
        time.sleep(seconds)
        if dies_at is not None and name == dies_at:
            raise error(f"killed inside the commit of {name}")
        return real(item, *args, **kwargs)

    monkeypatch.setattr(bf, "_commit_machine", commit)
    return asked


def _spans(timeline, name):
    return sorted(
        (s for s in timeline.spans if s.name == name), key=lambda s: s.start
    )


# ------------------------------------------------------------ (a) overlap --
def test_next_slice_trains_while_this_one_commits(tmp_path, monkeypatch):
    _slow_commits(monkeypatch)
    dirs = build_fleet(
        _machines("ov"), str(tmp_path / "out"), n_splits=1, slice_size=SLICE
    )
    assert sorted(dirs) == sorted(f"ov-{i}" for i in range(N_MACHINES))
    timeline = flightrec.RECORDER.latest(kind="fleet-build")
    slices = {s.id: s.attrs["slice"] for s in _spans(timeline, "fleet.slice")}
    loops = {slices[s.parent]: s for s in _spans(timeline, "fleet.commit_loop")}
    executes = {slices[s.parent]: s for s in _spans(timeline, "fleet.execute")}
    assert sorted(loops) == sorted(executes) == list(range(N_SLICES))
    for sl in range(N_SLICES - 1):
        commit, trains = loops[sl], executes[sl + 1]
        assert commit.duration >= SLICE * SLOW_S
        assert commit.thread.startswith("fleet-commit")
        assert trains.thread == "MainThread"
        # slice s+1's program was dispatched, and the loop's thread waited on
        # the device, before slice s's commit had ended: the two overlap
        assert trains.start < commit.start + commit.duration
        assert commit.start < trains.start + trains.duration
    # one commit in flight
    ordered = _spans(timeline, "fleet.commit_loop")
    for a, b in zip(ordered, ordered[1:]):
        assert a.start + a.duration <= b.start
    # the loop's thread waited for what of a slow commit a fast program did
    # not hide, slice by slice, and said for which slice
    waits = _spans(timeline, "fleet.commit_wait")
    assert [w.attrs["slice"] for w in waits] == list(range(N_SLICES))
    assert all(w.thread == "MainThread" for w in waits)
    for wait in waits:
        commit = loops[wait.attrs["slice"]]
        assert wait.start + wait.duration >= commit.start + commit.duration
    # a slice's fetch begins once the commit in flight when it was handed to
    # the prefetch worker has ended (a busy fetch pool starves the commit of
    # the interpreter lock), and says how long it was held
    prepares = {s.attrs["slice"]: s for s in _spans(timeline, "fleet.prepare")}
    assert prepares[0].attrs["held_s"] < 0.05 > prepares[1].attrs["held_s"]
    held = prepares[2]  # handed over at slice 1's start, beside slice 0's commit
    assert held.start >= loops[0].start + loops[0].duration
    assert held.attrs["held_s"] >= SLICE * SLOW_S - 0.1


# -------------------------------------------- (b) the order inside a commit --
def test_commit_order_artifact_registry_manifest_then_checkpoint(
    tmp_path, monkeypatch
):
    out, registry = str(tmp_path / "out"), str(tmp_path / "registry")
    machines = _machines("od")
    keys = {}  # machine -> its registry key, read off the journal's records
    events = []  # (what, when, detail) as each happens, on whichever thread

    real_manifest = bf._write_manifest

    def manifest(output_dir, completed, pending, journal_counts=None):
        real_manifest(output_dir, completed, pending, journal_counts=journal_counts)
        events.append(("manifest", time.time(), sorted(completed)))

    real_finalize = bf._SliceCheckpointer.finalize

    def finalize(self, key):
        # the save is joined first (as finalize does), so that the
        # checkpoint's directory is there to be looked at
        self._ckptr.wait_until_finished()
        events.append(("finalize", time.time(), os.path.isdir(self.path(key))))
        real_finalize(self, key)
        assert not os.path.isdir(self.path(key))

    monkeypatch.setattr(bf, "_write_manifest", manifest)
    monkeypatch.setattr(bf._SliceCheckpointer, "finalize", finalize)
    _slow_commits(monkeypatch, seconds=0.05)
    build_fleet(
        machines, out, model_register_dir=registry, n_splits=1, slice_size=SLICE
    )
    for name, record in store_journal.replay(out).items():
        assert record["event"] == store_journal.EVENT_COMMITTED
        keys[name] = record["cache_key"]
    slice_manifests = [e for e in events if e[0] == "manifest" and e[2]]
    finalizes = [e for e in events if e[0] == "finalize"]
    assert len(slice_manifests) == len(finalizes) == N_SLICES
    # the events alternate: a slice's manifest, then its checkpoint's end
    assert [e[0] for e in events if e[0] == "finalize" or e[2]] == (
        ["manifest", "finalize"] * N_SLICES
    )
    for sl in range(N_SLICES):
        _, manifest_at, completed = slice_manifests[sl]
        _, finalize_at, checkpoint_was_there = finalizes[sl]
        assert completed == sorted(
            name for done in range(sl + 1) for name in _names("od", done)
        )
        assert checkpoint_was_there, "dropped before its slice was durable"
        for name in _names("od", sl):
            current = os.stat(os.path.join(out, name, "CURRENT")).st_mtime
            key = os.stat(
                disk_registry._key_path(registry, keys[name])
            ).st_mtime
            # file times have the file system's grain: allow it
            assert current <= key + 0.01
            assert key <= manifest_at + 0.01
            assert manifest_at <= finalize_at
    assert not os.path.isdir(os.path.join(out, bf._CKPT_SUBDIR))


# ----------------------------------------------- (c) the drain on a stop ----
def test_stop_leaves_only_after_the_commit_in_flight(tmp_path, monkeypatch):
    asked = _slow_commits(monkeypatch)
    out = str(tmp_path / "out")
    machines = _machines(
        "st", type="test_fleet_spans.StoppingDataset", index=lambda i: i
    )
    with pytest.raises(StopJob):
        build_fleet(machines, out, n_splits=1, slice_size=SLICE)
    # what the harness reads as soon as the job's thread has ended
    manifest = _manifest(out)
    assert sorted(manifest["machines"]) == _names("st", 0) + _names("st", 1)
    assert all(m["status"] == "completed" for m in manifest["machines"].values())
    assert manifest["pending"] == _names("st", 2)
    assert asked == _names("st", 0) + _names("st", 1)
    for name in asked:
        load(os.path.join(out, name))
    timeline = flightrec.RECORDER.latest(kind="fleet-build")
    # the stop met slice 1's commit in flight: no slice was left to join it
    assert [w.attrs["slice"] for w in _spans(timeline, "fleet.commit_wait")] == [0]
    last = _spans(timeline, "fleet.commit_loop")[-1]
    assert last.start + last.duration <= timeline.finished - timeline.started
    assert not [t for t in threading.enumerate() if t.name.startswith("fleet-commit")]


def test_books_hold_under_a_short_switch_interval(tmp_path):
    """The job's books pass from the loop's thread to the worker and back by
    the hand-over and the join alone: with the interpreter switching threads
    every few bytecodes and a slice a machine, no record is lost or doubled."""
    import sys

    out, registry = str(tmp_path / "out"), str(tmp_path / "registry")
    before = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        dirs = build_fleet(
            _machines("sw"), out, model_register_dir=registry, n_splits=1,
            slice_size=1,
        )
    finally:
        sys.setswitchinterval(before)
    names = sorted(f"sw-{i}" for i in range(N_MACHINES))
    assert sorted(dirs) == names
    manifest = _manifest(out)
    assert sorted(manifest["machines"]) == names and manifest["pending"] == []
    assert manifest["journal"] == {"resumed": 0, "torn": 0, "rebuilt": N_MACHINES}
    assert [m["slice"] for _, m in sorted(manifest["machines"].items())] == list(
        range(N_MACHINES)
    )
    states = store_journal.replay(out)
    assert sorted(states) == names
    assert {r["event"] for r in states.values()} == {store_journal.EVENT_COMMITTED}
    timeline = flightrec.RECORDER.latest(kind="fleet-build")
    assert [w.attrs["slice"] for w in _spans(timeline, "fleet.commit_wait")] == list(
        range(N_MACHINES)
    )
    assert not [t for t in threading.enumerate() if t.name.startswith("fleet-commit")]


# ------------------------------------------ (d) a commit that raises --------
def test_commit_that_raises_ends_the_job_with_its_exception(tmp_path, monkeypatch):
    class DiskFull(Exception):
        pass

    asked = _slow_commits(monkeypatch, seconds=0.0, dies_at="cr-1", error=DiskFull)
    handed = []
    real_commit_slice = bf._commit_slice

    def commit_slice(seam, books, indexed_items, *rest):
        handed.append([item["machine"].name for _, item in indexed_items])
        return real_commit_slice(seam, books, indexed_items, *rest)

    monkeypatch.setattr(bf, "_commit_slice", commit_slice)
    trained = []
    real_train = bf.train_fleet_arrays
    monkeypatch.setattr(
        bf, "train_fleet_arrays",
        lambda *a, **k: trained.append(1) or real_train(*a, **k),
    )
    out = str(tmp_path / "out")
    with pytest.raises(DiskFull, match="cr-1"):
        build_fleet(_machines("cr"), out, n_splits=1, slice_size=SLICE)
    # raised at the loop's next join: slice 1 had trained beside the commit,
    # and neither it nor slice 2 was handed to the worker
    assert handed == [_names("cr", 0)]
    assert asked == _names("cr", 0)
    assert len(trained) == 2
    states = store_journal.replay(out)
    assert states["cr-0"]["event"] == store_journal.EVENT_COMMITTED
    assert set(states) == {"cr-0"}  # the deadly commit died before its record
    timeline = flightrec.RECORDER.latest(kind="fleet-build")
    assert timeline.status == "error" and timeline.error.startswith("DiskFull")
    (wait,) = _spans(timeline, "fleet.commit_wait")
    assert wait.attrs == {"slice": 0, "error": "DiskFull"}
    (loop,) = _spans(timeline, "fleet.commit_loop")
    assert loop.attrs["error"] == "DiskFull"


# -------------------------------- (e) a kill inside a commit, then a resume --
def test_kill_inside_a_commit_resumes_from_the_slices_checkpoint(
    tmp_path, monkeypatch
):
    out, registry = str(tmp_path / "out"), str(tmp_path / "registry")
    machines = _machines("kl")
    # slice 1's commit dies at its first machine, while slice 2 trains
    _slow_commits(monkeypatch, seconds=0.0, dies_at="kl-2")
    with pytest.raises(RuntimeError, match="killed inside the commit of kl-2"):
        build_fleet(
            machines, out, model_register_dir=registry, n_splits=1,
            slice_size=SLICE,
        )
    assert sorted(_manifest(out)["machines"]) == _names("kl", 0)
    # the committing slice's checkpoint outlived the kill (orbax finalizes by
    # an atomic rename: only a directory without the tmp suffix counts); the
    # slice that trained beside the commit saved none: its result is lost
    pattern = os.path.join(out, bf._CKPT_SUBDIR, "slice_*")

    def finalized():
        return [p for p in glob.glob(pattern) if "tmp" not in os.path.basename(p)]

    deadline = time.time() + 30
    while not finalized() and time.time() < deadline:
        time.sleep(0.2)
    assert len(finalized()) == 1

    monkeypatch.undo()
    trained = []
    real_train = bf.train_fleet_arrays
    monkeypatch.setattr(
        bf, "train_fleet_arrays",
        lambda *a, **k: trained.append(1) or real_train(*a, **k),
    )
    dirs = build_fleet(
        machines, out, model_register_dir=registry, n_splits=1, slice_size=SLICE
    )
    assert sorted(dirs) == sorted(m.name for m in machines)
    # slice 1 restored, not retrained; slice 2 trained again
    assert len(trained) == 1
    timeline = flightrec.RECORDER.latest(kind="fleet-build")
    assert [s.attrs["hit"] for s in _spans(timeline, "fleet.checkpoint_restore")] == [
        True, False
    ]
    assert _manifest(out)["journal"] == {"resumed": 2, "torn": 0, "rebuilt": 4}
    for model_dir in dirs.values():
        load(model_dir)
    assert not os.path.isdir(os.path.join(out, bf._CKPT_SUBDIR))


# ----------------------------------------- (f) the commit touches no device --
@pytest.mark.parametrize("config", [DENSE, LSTM], ids=["dense", "lstm"])
def test_commit_of_a_fetched_result_touches_no_device(tmp_path, config):
    """A commit that placed an array would queue its transfer behind the
    train program running beside it, and serialise itself again, silently."""
    n_features, n_splits, n_rows = 2, 1, 256
    rng = np.random.default_rng(0)
    spec = bf._spec_for(
        bf._analyze_model(pipeline_from_definition(config)),
        n_features, n_features, n_splits,
    )
    X = rng.normal(size=(SLICE, n_rows, n_features)).astype(np.float32)
    batch = MachineBatch(
        X=X, y=X.copy(), w=np.ones((SLICE, n_rows), np.float32),
        keys=jax.random.split(jax.random.PRNGKey(0), SLICE),
    )
    result = jax.device_get(train_fleet_arrays(spec, batch))
    assert not any(
        isinstance(leaf, jax.Array) for leaf in jax.tree_util.tree_leaves(result)
    )
    out = str(tmp_path / "out")
    journal = store_journal.BuildJournal(store_journal.journal_path(out, 0))
    provenance = {
        "bucket": 0, "bucket_size": SLICE, "slice": 0, "slice_size": SLICE,
        "slice_duration_s": 1.0, "cv_parallel": True, "devices": None,
    }
    before = {id(a) for a in jax.live_arrays()}
    with jax.transfer_guard("disallow"):
        for i in range(SLICE):
            item = {
                "machine": FleetMachineConfig(f"dv-{i}", config, {}),
                "cache_key": f"key-{i}", "dataset_metadata": {},
            }
            model_dir = bf._commit_machine(
                item, result, i, (n_features, n_features, n_splits),
                provenance, out, None, "f32", journal,
            )
            assert model_dir == os.path.join(out, f"dv-{i}")
    # no array was made on a device either, by a transfer the guard lets by
    assert {id(a) for a in jax.live_arrays()} <= before
    # outside the guard the artifact serves: the model places its state when
    # it is first asked to predict
    model = load(os.path.join(out, "dv-1"))
    frame = model.anomaly(X[1, -64:])
    assert np.isfinite(frame["total-anomaly-score"].values).all()
