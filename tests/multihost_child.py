"""Child process for the multi-process distributed fleet tests (test_aux.py).

Run as: python multihost_child.py <process_id> <num_processes> <port>
        python multihost_child.py <process_id> <num_processes> <port> --build <dir>

Each process joins the jax.distributed runtime (Gloo over localhost) and
spans a global fleet mesh over EVERY process's virtual CPU devices. The
default mode runs a sharded fleet train step where each process only holds
its own machines' data. ``--build`` runs the FULL ``build_fleet`` pipeline
multi-host: sliced buckets, process-local streaming ingest through the
prefetcher, global-batch assembly, and per-process artifact writes
(SURVEY.md §2.3: machine shards are process-local, collectives cross the
process boundary). Every mode is process-count-agnostic — the parents run
the drills at 2 AND at 4 processes (the v5e-16 host count; VERDICT r4 #5:
2-process symmetry hides rendezvous/barrier bugs that 2→4 exposes).
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np


DENSE_FLEET_MODEL = {
    "DiffBasedAnomalyDetector": {
        "base_estimator": {
            "Pipeline": {
                "steps": [
                    "MinMaxScaler",
                    {
                        "DenseAutoEncoder": {
                            "kind": "feedforward_hourglass",
                            "epochs": 1,
                            "batch_size": 16,
                        }
                    },
                ]
            }
        }
    }
}


def _verify_and_report(results, width_for=lambda name: 3) -> None:
    """Every artifact this process wrote must be loadable and score
    finitely; then print the built set in the ``built@N:`` format the
    parent tests regex for."""
    from gordo_components_tpu.serializer import load

    for name, model_dir in sorted(results.items()):
        model = load(model_dir)
        X = np.random.default_rng(3).normal(
            size=(24, width_for(name))
        ).astype(np.float32)
        frame = model.anomaly(X)
        assert np.isfinite(
            np.ravel(frame["total-anomaly-score"].values)
        ).all(), name
    print(
        f"built@{jax.process_index()}: {','.join(sorted(results))}",
        flush=True,
    )


def build_mode(output_dir: str) -> None:
    """Multi-host build_fleet: 16 machines, slice_size=8 → one bucket in two
    slices of 8 (each process ingests + trains + writes 4 machines per
    slice). Prints this process's built machine names for the parent to
    union-check."""
    from gordo_components_tpu.parallel import FleetMachineConfig, build_fleet
    from gordo_components_tpu.parallel.distributed import global_fleet_mesh

    mesh = global_fleet_mesh()
    machines = [
        FleetMachineConfig(
            name=f"mh-{i:02d}",
            model_config=DENSE_FLEET_MODEL,
            data_config={
                "type": "RandomDataset",
                "train_start_date": "2023-01-01T00:00:00+00:00",
                "train_end_date": "2023-01-03T00:00:00+00:00",
                "tag_list": [f"mh{i}-a", f"mh{i}-b", f"mh{i}-c"],
            },
        )
        for i in range(16)
    ]
    registry = os.path.join(output_dir, "registry")
    results = build_fleet(
        machines,
        os.path.join(output_dir, "models"),
        model_register_dir=registry,
        mesh=mesh,
        n_splits=1,
        slice_size=8,
    )
    _verify_and_report(results)


def build_hetero_mode(output_dir: str) -> None:
    """Heterogeneous multi-host build (VERDICT r3 weak #5 extension): one
    ``build_fleet`` call over THREE buckets — 10 dense 3-tag machines with
    2-fold CV, 6 dense 5-tag machines (different width => different
    bucket), and 4 dense 3-tag machines with per-machine
    ``evaluation.n_splits=0`` (same width, different CV depth => yet
    another bucket) — across two processes with process-local ingest.
    Bucket sizes (10/6/4) are deliberately not multiples of the 8-device
    global mesh, so the padding path runs under multi-host too. Prints the
    per-process built set for the parent's union/disjointness check."""
    from gordo_components_tpu.parallel import FleetMachineConfig, build_fleet
    from gordo_components_tpu.parallel.distributed import global_fleet_mesh

    mesh = global_fleet_mesh()

    def data(tags):
        return {
            "type": "RandomDataset",
            "train_start_date": "2023-01-01T00:00:00+00:00",
            "train_end_date": "2023-01-02T00:00:00+00:00",
            "tag_list": tags,
        }

    machines = [
        FleetMachineConfig(
            name=f"hn-{i:02d}",
            model_config=DENSE_FLEET_MODEL,
            data_config=data([f"hn{i}-a", f"hn{i}-b", f"hn{i}-c"]),
        )
        for i in range(10)
    ]
    machines += [
        FleetMachineConfig(
            name=f"hw-{i:02d}",
            model_config=DENSE_FLEET_MODEL,
            data_config=data([f"hw{i}-{t}" for t in range(5)]),
        )
        for i in range(6)
    ]
    machines += [
        FleetMachineConfig(
            name=f"hz-{i:02d}",
            model_config=DENSE_FLEET_MODEL,
            data_config=data([f"hz{i}-a", f"hz{i}-b", f"hz{i}-c"]),
            evaluation={"n_splits": 0},
        )
        for i in range(4)
    ]
    results = build_fleet(
        machines,
        os.path.join(output_dir, "models"),
        model_register_dir=os.path.join(output_dir, "registry"),
        mesh=mesh,
        n_splits=2,
        slice_size=8,
    )
    _verify_and_report(
        results, width_for=lambda name: 5 if name.startswith("hw") else 3
    )


def _install_crash_after_first_checkpoint() -> None:
    """Monkeypatch shared by the crash drills: every process dies (exit 17,
    sentinel printed) immediately after the FIRST slice's collective
    checkpoint save is durable — before any artifact lands. That is the
    crash window the restore-instead-of-retrain tests pin."""
    import importlib

    # NB: `from ..parallel import build_fleet` would bind the FUNCTION the
    # package re-exports, not the module
    bf = importlib.import_module("gordo_components_tpu.parallel.build_fleet")

    orig = bf._SliceCheckpointer.save_async

    def save_then_die(self, key, result):
        orig(self, key, result)
        self._ckptr.wait_until_finished()  # the ckpt must be durable
        print("crashed-after-checkpoint", flush=True)
        os._exit(17)

    bf._SliceCheckpointer.save_async = save_then_die


def build_crash_mode(output_dir: str) -> None:
    """build_mode under the crash-after-checkpoint drill: the follow-up
    normal build must RESTORE the checkpointed slice instead of retraining
    (kill-mid-build resume, multi-host edition)."""
    _install_crash_after_first_checkpoint()
    build_mode(output_dir)


def _install_die_at_slice1(victim_ranks) -> None:
    """Monkeypatch shared by the asymmetric drills: the given ranks die at
    the start of slice 1 (slice 0's commit is in flight on the commit
    worker then, and dies with the process); every other
    rank survives, stalls in the slice's collective assembly, and must be
    freed by the slice watchdog with the RETRYABLE exit code."""
    import importlib

    bf = importlib.import_module("gordo_components_tpu.parallel.build_fleet")

    orig = bf._SliceWatchdog.start

    def start_or_die(self, bucket, sl):
        if sl >= 1 and jax.process_index() in victim_ranks:
            print("peer-died-asymmetrically", flush=True)
            os._exit(17)
        orig(self, bucket, sl)

    bf._SliceWatchdog.start = start_or_die


def build_asym_crash_mode(output_dir: str) -> None:
    """ASYMMETRIC failure drill (ROADMAP #5 / VERDICT r3 weak #5): only
    process 1 dies — at the start of its second slice, with slice 0's
    commit in flight. The survivors stall in the slice's collective
    assembly (their peer is gone) and must be killed by the slice watchdog
    (``GORDO_SLICE_TIMEOUT_S``, set by the parent test) with the RETRYABLE
    exit code — never hang. The parent then re-runs a normal build, which
    must resume slice 0 from the registry and complete the fleet."""
    _install_die_at_slice1({1})
    build_mode(output_dir)


def build_asym_crash2_mode(output_dir: str) -> None:
    """TWO NON-ADJACENT ranks die (1 and 3, of 4): the failure shape
    VERDICT r4 #5 calls out — with two separated holes in the rendezvous
    ring, every survivor (0 and 2) has a dead neighbor on some collective
    path, a topology 2-process symmetry can never produce. Survivors must
    still fail fast via transport error or watchdog, retryably."""
    _install_die_at_slice1({1, 3})
    build_mode(output_dir)


def build_hetero_crash_mode(output_dir: str) -> None:
    """The crash-after-checkpoint drill composed with the THREE-bucket
    heterogeneous fleet — the restore path exercised against a checkpoint
    whose sharded template comes from a mixed bucket-shape fleet, not just
    the homogeneous one build_crash_mode covers."""
    _install_crash_after_first_checkpoint()
    build_hetero_mode(output_dir)


def build_hang_mode(output_dir: str) -> None:
    """Watchdog drill: BOTH processes wedge at the start of slice 1 (after
    arming the watchdog) — simulating a collective that blocks with every
    peer still alive, the case the transport layer cannot detect (no
    connection reset, no heartbeat failure). The slice watchdog must free
    both with the RETRYABLE exit code."""
    import importlib
    import time

    bf = importlib.import_module("gordo_components_tpu.parallel.build_fleet")

    orig = bf._SliceWatchdog.start

    def start_then_wedge(self, bucket, sl):
        orig(self, bucket, sl)
        if sl >= 1:
            print("wedged-in-slice", flush=True)
            while True:
                time.sleep(1)

    bf._SliceWatchdog.start = start_then_wedge
    build_mode(output_dir)


def ring_attention_mode() -> None:
    """Multi-PROCESS ring attention (SURVEY §6.7 x §2.3): the sequence
    axis shards over the GLOBAL mesh (every process's devices), so the
    ring's neighbor hops cross process boundaries over the Gloo
    transport — the CPU stand-in for ICI/DCN hops on a real pod. Each
    process holds only its seq shards; parity is checked per process
    against a locally-computed dense reference on the full arrays."""
    from jax.sharding import NamedSharding, PartitionSpec

    from gordo_components_tpu.ops.attention import (
        dense_attention,
        ring_attention,
    )
    from gordo_components_tpu.parallel.distributed import global_fleet_mesh

    mesh = global_fleet_mesh()
    n = mesh.size
    pid = jax.process_index()
    batch, seq, heads, head_dim = 2, 4 * n, 2, 8
    rng = np.random.default_rng(7)
    full = {
        name: rng.normal(size=(batch, seq, heads, head_dim)).astype(
            np.float32
        )
        for name in ("q", "k", "v")
    }
    sharding = NamedSharding(mesh, PartitionSpec(None, "fleet"))
    rows_per_proc = seq // jax.process_count()
    lo, hi = pid * rows_per_proc, (pid + 1) * rows_per_proc
    q, k, v = (
        jax.make_array_from_process_local_data(
            sharding, full[name][:, lo:hi]
        )
        for name in ("q", "k", "v")
    )
    reference = np.asarray(
        dense_attention(full["q"], full["k"], full["v"])
    )
    for block_impl in ("dense", "flash"):
        out = ring_attention(
            q, k, v, mesh=mesh, axis_name="fleet", block_impl=block_impl
        )
        jax.block_until_ready(out)
        for shard in out.addressable_shards:
            start = shard.index[1].start or 0
            np.testing.assert_allclose(
                np.asarray(shard.data),
                reference[:, start : start + shard.data.shape[1]],
                atol=1e-5,
                err_msg=block_impl,
            )
    print(
        f"ring-attention@{pid} OK over {n} devices (dense+flash hops)",
        flush=True,
    )


def ckpt_roundtrip_mode(ckpt_dir: str) -> None:
    """Collective slice-checkpoint round-trip: save a globally-sharded tree
    (plus a zero-size leaf), restore it through the sharded template, and
    verify every process gets ITS shards back bit-exact."""
    from jax.experimental import multihost_utils

    from gordo_components_tpu.parallel.build_fleet import _SliceCheckpointer
    from gordo_components_tpu.parallel.distributed import global_fleet_mesh
    from gordo_components_tpu.parallel.mesh import fleet_sharding

    mesh = global_fleet_mesh()
    sharding = fleet_sharding(mesh)
    n = mesh.size
    local = jax.local_device_count()
    pid = jax.process_index()
    full = (np.arange(n * 4, dtype=np.float32) * 2.5).reshape(n, 4)
    lo, hi = pid * local, (pid + 1) * local
    tree = {
        "real": jax.make_array_from_process_local_data(sharding, full[lo:hi]),
        "empty": np.zeros((n, 0, 4), np.float32),
    }
    ckpt = _SliceCheckpointer(ckpt_dir, mesh=mesh)
    key = "roundtrip"
    ckpt.save_async(key, tree)
    ckpt._ckptr.wait_until_finished()

    def abstract_fn():
        return {
            "real": jax.ShapeDtypeStruct((n, 4), np.float32),
            "empty": jax.ShapeDtypeStruct((n, 0, 4), np.float32),
        }

    restored = ckpt.try_restore(key, abstract_fn)
    assert restored is not None
    for shard in restored["real"].addressable_shards:
        start = shard.index[0].start or 0
        np.testing.assert_array_equal(
            np.asarray(shard.data), full[start : start + shard.data.shape[0]]
        )
    assert restored["empty"].shape == (n, 0, 4)
    ckpt.finalize(key)
    multihost_utils.sync_global_devices("roundtrip-finalized")
    assert not os.path.isdir(ckpt.path(key)), "finalize must drop the ckpt"
    # a missing checkpoint is agreed collectively -> both return None
    assert ckpt.try_restore("never-saved", abstract_fn) is None
    print(f"ckpt-roundtrip@{pid} OK", flush=True)


def serve_shard_mode() -> None:
    """SPMD mesh-serving drill (ARCHITECTURE §23): every process joins
    one ``global_fleet_mesh``, a bucket-shaped stacked tree shards its
    MACHINE axis across the processes (``shard_plan`` padding +
    ``NamedSharding`` — each process materializes only its own slice via
    ``make_array_from_process_local_data``), and every process enqueues
    the SAME gather-by-idx scoring program in lockstep — the cross-shard
    gather is the collective, and it lives ONLY inside the jitted
    program, exactly like the serving engine's sharded bucket. Requests
    deliberately index machines on BOTH processes' slices; the
    replicated output is parity-checked per process against a local
    dense reference."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    from gordo_components_tpu.parallel.distributed import global_fleet_mesh
    from gordo_components_tpu.parallel.mesh import pad_to_multiple
    from gordo_components_tpu.parallel.shard_plan import FleetShardPlan

    mesh = global_fleet_mesh()
    nproc = jax.process_count()
    pid = jax.process_index()
    plan = FleetShardPlan(nproc)
    n_machines = 6  # deliberately no multiple of anything: padding runs
    features, rows, k = 3, 8, 4
    # machine axis padded so it tiles the GLOBAL device mesh evenly (the
    # per-process slices are the plan's shard_bounds scaled to devices)
    height = pad_to_multiple(n_machines, mesh.size)
    rng = np.random.default_rng(0)
    stacked_full = {
        "w": rng.normal(size=(height, features, features)).astype(
            np.float32
        ),
        "b": rng.normal(size=(height, features)).astype(np.float32),
    }
    sharding = plan.global_sharding(mesh)
    per_proc = height // nproc
    lo, hi = pid * per_proc, (pid + 1) * per_proc

    def globalize(full):
        return jax.make_array_from_process_local_data(
            sharding, full[lo:hi]
        )

    stacked = {name: globalize(a) for name, a in stacked_full.items()}

    def score_one(tree, idx, x):
        machine = jax.tree_util.tree_map(lambda a: a[idx], tree)
        pred = x @ machine["w"] + machine["b"]
        return jnp.linalg.norm(jnp.abs(pred - x), axis=-1)

    replicated = NamedSharding(mesh, PartitionSpec())
    program = jax.jit(
        jax.vmap(score_one, in_axes=(None, 0, 0)),
        in_shardings=(sharding, replicated, replicated),
        out_shardings=replicated,
    )
    # every request targets a different machine, spanning both halves of
    # the padded axis — the gather crosses the process boundary
    idx = (np.arange(k, dtype=np.int32) * (n_machines // 2 + 1)) % n_machines
    xs = rng.normal(size=(k, rows, features)).astype(np.float32)
    out = np.asarray(
        jax.device_get(program(stacked, idx, xs))
    )
    reference = np.stack(
        [
            np.linalg.norm(
                np.abs(
                    xs[j] @ stacked_full["w"][idx[j]]
                    + stacked_full["b"][idx[j]]
                    - xs[j]
                ),
                axis=-1,
            )
            for j in range(k)
        ]
    )
    np.testing.assert_allclose(out, reference, atol=1e-5)
    print(
        f"serve-shard@{pid}: {k} requests gathered across "
        f"{nproc} process shards OK (height {height})",
        flush=True,
    )


def main() -> None:
    pid, nproc, port = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]

    from gordo_components_tpu.parallel.distributed import (
        global_fleet_mesh,
        initialize_multihost,
    )

    initialize_multihost(
        coordinator_address=f"127.0.0.1:{port}",
        num_processes=nproc,
        process_id=pid,
    )
    assert jax.process_count() == nproc

    import logging

    logging.basicConfig(level=logging.INFO)  # parents assert on INFO lines
    if len(sys.argv) >= 6 and sys.argv[4] == "--build":
        build_mode(sys.argv[5])
        return
    if len(sys.argv) >= 6 and sys.argv[4] == "--build-crash":
        build_crash_mode(sys.argv[5])
        return
    if len(sys.argv) >= 6 and sys.argv[4] == "--build-asym-crash":
        build_asym_crash_mode(sys.argv[5])
        return
    if len(sys.argv) >= 6 and sys.argv[4] == "--build-asym-crash2":
        build_asym_crash2_mode(sys.argv[5])
        return
    if len(sys.argv) >= 6 and sys.argv[4] == "--build-hang":
        build_hang_mode(sys.argv[5])
        return
    if len(sys.argv) >= 6 and sys.argv[4] == "--build-hetero-crash":
        build_hetero_crash_mode(sys.argv[5])
        return
    if len(sys.argv) >= 6 and sys.argv[4] == "--build-hetero":
        build_hetero_mode(sys.argv[5])
        return
    if len(sys.argv) >= 6 and sys.argv[4] == "--ckpt-roundtrip":
        ckpt_roundtrip_mode(sys.argv[5])
        return
    if len(sys.argv) >= 5 and sys.argv[4] == "--ring":
        ring_attention_mode()
        return
    if len(sys.argv) >= 5 and sys.argv[4] == "--serve-shard":
        serve_shard_mode()
        return

    from jax.sharding import NamedSharding, PartitionSpec

    from gordo_components_tpu.parallel import MachineBatch, train_fleet_arrays
    from gordo_components_tpu.parallel.build_fleet import _analyze_model, _spec_for
    from gordo_components_tpu.serializer import pipeline_from_definition

    mesh = global_fleet_mesh()
    n_machines = mesh.size  # one machine per global device
    local = jax.local_device_count()
    rows, tags = 64, 3

    model_config = {
        "DiffBasedAnomalyDetector": {
            "base_estimator": {
                "Pipeline": {
                    "steps": [
                        "MinMaxScaler",
                        {
                            "DenseAutoEncoder": {
                                "kind": "feedforward_hourglass",
                                "epochs": 2,
                                "batch_size": 16,
                            }
                        },
                    ]
                }
            }
        }
    }
    probe = pipeline_from_definition(model_config)
    spec = _spec_for(_analyze_model(probe), tags, tags, n_splits=2)

    # deterministic global batch; each process materializes ONLY its own
    # machines' rows on device (jax.make_array_from_process_local_data)
    rng = np.random.default_rng(0)
    X_full = rng.normal(size=(n_machines, rows, tags)).astype(np.float32)
    X_full += np.sin(np.linspace(0, 8, rows))[None, :, None]
    w_full = np.ones((n_machines, rows), np.float32)
    keys_full = np.asarray(jax.random.split(jax.random.PRNGKey(0), n_machines))

    lo, hi = pid * local, (pid + 1) * local

    def globalize(full, spec_axes):
        sharding = NamedSharding(mesh, PartitionSpec(*spec_axes))
        return jax.make_array_from_process_local_data(sharding, full[lo:hi])

    batch = MachineBatch(
        X=globalize(X_full, ("fleet", None, None)),
        y=globalize(X_full.copy(), ("fleet", None, None)),
        w=globalize(w_full, ("fleet", None)),
        keys=globalize(keys_full, ("fleet", None)),
    )
    result = train_fleet_arrays(spec, batch, mesh=mesh)
    jax.block_until_ready(result)

    # every process checks ITS machines' losses (addressable shards only)
    for shard in result.loss_history.addressable_shards:
        history = np.asarray(shard.data)
        assert np.isfinite(history).all(), "non-finite loss on local shard"
        assert history.shape[-1] == spec.epochs
    print(
        f"proc {pid}: trained {n_machines} machines over "
        f"{nproc} processes x {local} devices",
        flush=True,
    )


if __name__ == "__main__":
    main()
