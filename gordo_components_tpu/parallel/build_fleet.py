"""Fleet builder: N machine configs → one compiled program per bucket →
per-machine artifacts identical to the single-machine builder's.

The reference's workflow generator emits one Argo pod per machine running
``gordo build`` (SURVEY.md §4.4). ``build_fleet`` replaces that fan-out:
machines are grouped into compilation buckets (same model config + data
shape), each bucket trains as one ``vmap``-over-mesh program, and every
machine still gets its own serialized model dir + metadata + registry entry
— so the serving layer and the idempotency cache are shared verbatim with
the single-machine path, and a killed fleet build resumes by skipping
machines whose cache key is already registered (the reference's Argo-retry
semantics, per machine).

Supported model-config shapes (the reference's canonical anomaly configs):

1. ``DiffBasedAnomalyDetector(base_estimator=TransformedTargetRegressor(
   regressor=Pipeline([scaler, estimator]), transformer=scaler))``
2. ``DiffBasedAnomalyDetector(base_estimator=Pipeline([scaler, estimator]))``
3. ``Pipeline([scaler, estimator])`` / bare estimator

The estimator must be a zoo model (``BaseFlaxEstimator``); the scaler
``MinMaxScaler`` / ``StandardScaler`` or absent.
"""

from __future__ import annotations

import contextlib
import json
from concurrent.futures import Future, ThreadPoolExecutor
from concurrent.futures import wait as futures_wait
import logging
import os
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

import jax
import numpy as np

from .. import __version__
from .. import precision as precision_mod
from ..builder.build_model import (
    _dataset_from_config,
    cached_artifact_precision,
    calculate_model_key,
)
from ..models.analysis import Analyzed as _Analyzed
from ..models.analysis import analyze_model as _analyze_model
from ..models.transformers import MinMaxScaler, StandardScaler
from ..observability import spans
from ..observability.flightrec import build_timeline
from ..observability.registry import REGISTRY
from ..ops.scaling import ScalerParams
from ..resilience import faults
from ..serializer import pipeline_from_definition
from ..serializer.persistence import write_artifact_files
from ..store import (
    StoreError,
    commit_generation,
    resolve_artifact_dir,
    verify_artifact,
)
from ..store import journal as store_journal
from ..utils import disk_registry
from ..utils.profiling import device_trace
from .fleet import (
    FLEET_CV_METRICS,
    FleetSpec,
    MachineBatch,
    abstract_state,
    peek_fleet_executable,
    sequential_fits,
    train_fleet_arrays,
)
from .mesh import pad_to_multiple

logger = logging.getLogger(__name__)

_M_FLEET_MACHINES = REGISTRY.counter(
    "gordo_fleet_machines_total",
    "Fleet-build machines resolved, by outcome (completed / cached / failed)",
    labels=("outcome",),
)
_M_BUILD_FETCH = REGISTRY.counter(
    "gordo_resilience_build_fetch_total",
    "Fleet-build per-machine data-fetch outcomes (ok / retry / failed)",
    labels=("outcome",),
)

# sliced builds round the padded row axis up to a multiple of this, so
# heterogeneous-history slices collapse onto few compiled shapes
_ROW_QUANTUM = 256

MANIFEST_FILE = "fleet_manifest.json"

# exit code for a tripped multi-host watchdog: EX_TEMPFAIL — deliberately
# NOT the permanent-failure codes the CLI maps config/data errors to
# (64/66, which Argo/k8s must NOT retry); anything else is retryable under
# the reference's retry semantics, and 75 is the conventional "transient,
# try again" sysexits value
EXIT_RETRYABLE = 75

# env knob for the per-slice collective watchdog in multi-host builds
SLICE_TIMEOUT_ENV = "GORDO_SLICE_TIMEOUT_S"
_CKPT_SUBDIR = ".slice_checkpoints"

# per-machine data-fetch retry knobs (build-time resilience): transient
# lake hiccups get a few backed-off retries; a machine that STILL fails is
# isolated (built as zero-weight padding, recorded failed in the manifest)
# instead of killing the other N-1 machines' build
FETCH_RETRIES_ENV = "GORDO_BUILD_FETCH_RETRIES"
FETCH_BACKOFF_ENV = "GORDO_BUILD_FETCH_BACKOFF"


def _fetch_machine_data(item: dict, retries: int, backoff: float) -> Optional[str]:
    """Fetch one machine's training data into ``item`` (X/y/metadata),
    retrying transient provider failures with exponential backoff. Returns
    None on success, else the terminal error string — the caller decides
    isolation. Permanently-diagnosable failures (bad config, insufficient
    rows) skip the retry loop: re-reading the lake cannot grow history."""
    from ..dataset.dataset import InsufficientDataError

    name = item["machine"].name
    last_error: Optional[str] = None
    for attempt in range(max(0, retries) + 1):
        item["fetch_retries"] = attempt
        if attempt:
            _M_BUILD_FETCH.labels("retry").inc()
            time.sleep(backoff * 2 ** (attempt - 1))
        try:
            # chaos seam: `data-fetch:<machine>:error` stands in for a
            # dead lake / revoked credential for exactly one machine
            faults.inject("data-fetch", name)
            X_frame, y_frame = item["dataset"].get_data()
            item["X"] = np.asarray(
                getattr(X_frame, "values", X_frame), np.float32
            )
            item["y"] = np.asarray(
                getattr(y_frame, "values", y_frame), np.float32
            )
            item["dataset_metadata"] = item["dataset"].get_metadata()
            _M_BUILD_FETCH.labels("ok").inc()
            return None
        except (InsufficientDataError, ValueError) as exc:  # permanent
            last_error = f"{type(exc).__name__}: {exc}"
            break
        except Exception as exc:
            last_error = f"{type(exc).__name__}: {exc}"
            logger.warning(
                "Fleet fetch failed for %r (attempt %d/%d): %s",
                name, attempt + 1, max(0, retries) + 1, last_error,
            )
    _M_BUILD_FETCH.labels("failed").inc()
    return last_error


def _prepare_slice(
    slice_items: List[dict],
    n_padded: int,
    n_features: int,
    n_targets: int,
    quantize_rows: bool,
    span: Optional[Tuple[int, int]] = None,
    place: Optional[Tuple[Any, Any]] = None,
    fetch_retries: int = 2,
    fetch_backoff: float = 0.5,
):
    """Host-side ingest for one slice: provider fetch + padded stacked
    assembly. Runs on the prefetch worker so slice ``s+1``'s data-lake reads
    (the reference's I/O hot spot, SURVEY.md §4.1) overlap slice ``s``'s
    device training + artifact writes. Peak host memory is therefore TWO
    slices' data (double buffer), not one — still bounded and documented at
    the slice_size knob.

    ``span=(lo, hi)``: assemble only machine rows ``[lo, hi)`` of the padded
    slice — the multi-host streaming-ingest path, where each process
    fetches ONLY its own machines' data (the machine axis is sharded over
    processes) and the assembled block becomes this process's shard of the
    global batch. The default covers the whole slice (single-host). NOTE:
    the returned row count is the LOCAL maximum; multi-host callers must
    exchange it for the global maximum before building global arrays (done
    on the main thread — collectives must never run on the prefetch worker,
    or two processes could order them differently and deadlock).

    ``place=(spec, mesh)``: single-host transfer overlap. When the
    bucket's executable for this exact shape is ALREADY compiled
    (:func:`..fleet.peek_fleet_executable` — never compiles from this
    thread), the worker issues the layout-matched ``device_put`` of X/y/w
    here, so the NEXT slice's host→device transfer rides behind the
    current slice's training and artifact writes instead of serializing in
    front of its own training. ``jax.device_put`` dispatch
    is async, so the worker never blocks on the wire either. Skipped for
    memory-constrained (remat) buckets — callers pass ``place=None``.
    The peek typically first hits for slice 2 of a row shape: slice 1's
    prepare is submitted before slice 0 triggers the bucket's compile, so
    its peek usually races a still-running compile and stays host-side —
    i.e. a 2-slice bucket may see no overlap at all; the win scales with
    slice count, exactly where ingest wall-time does too. Multi-host
    callers must NOT pass ``place`` (their batch assembly is collective,
    main-thread-only).

    Every shape input is an explicit argument (not a closure over bucket-loop
    locals): the call runs on another thread, and late-bound locals would
    silently go stale if a future ever crossed a bucket boundary (ADVICE r2).

    Stages: ``fleet.fetch`` per machine (on the fetch pool's threads),
    ``fleet.assemble``, and ``fleet.place`` when the batch is device-placed
    here. Returns ``(X, y, w, n_rows)``.
    """
    lo, hi = span if span is not None else (0, n_padded)
    local_items = slice_items[lo:min(hi, len(slice_items))]
    # the fetch pool's threads inherit no context: each fetch binds this
    # one, so its span hangs under the stage open here (fleet.prepare)
    # and its log lines carry the job's trace id
    seam = spans.capture()

    def fetch_one(item: dict) -> None:
        # per-machine failure isolation: a machine whose fetch fails after
        # retries trains as zero-weight padding (fold masks already handle
        # empty machines) and is reported failed — it must not take the
        # other N-1 machines of the slice down with it
        with spans.bind(seam), spans.stage(
            "fleet.fetch", machine=item["machine"].name
        ) as fetched:
            error = _fetch_machine_data(item, fetch_retries, fetch_backoff)
            if error is not None:
                logger.error(
                    "Isolating machine %r from fleet build: %s",
                    item["machine"].name, error,
                )
                item["build_error"] = error
                item["X"] = np.zeros((0, n_features), np.float32)
                item["y"] = np.zeros((0, n_targets), np.float32)
                item["dataset_metadata"] = {}
            fetched["rows"] = len(item["X"])
            fetched["retries"] = item.pop("fetch_retries", 0)
            # "numpy" or "pandas": the path join_timeseries resampled by
            fetched["resample"] = getattr(item["dataset"], "resample_path", None)

    # items the width probe already fetched are skipped
    to_fetch = [item for item in local_items if "X" not in item]
    if len(to_fetch) > 1:
        # per-machine fetches are independent and (for real providers)
        # I/O-bound — the reference got this parallelism for free from its
        # pod-per-machine fan-out (SURVEY §4.1); a serial loop here would
        # make one slice's ingest wall-time the SUM of its machines' lake
        # reads. Bounded width: the point is overlapping network/disk
        # latency, not saturating the host CPU (this already runs on the
        # prefetch worker, itself overlapped behind device training).
        with ThreadPoolExecutor(
            max_workers=min(8, len(to_fetch)),
            thread_name_prefix="fleet-fetch",
        ) as pool:
            list(pool.map(fetch_one, to_fetch))
    else:
        for item in to_fetch:
            fetch_one(item)

    with spans.stage("fleet.assemble"):
        # max(…, 1): an all-isolated slice (every fetch failed) still needs
        # a nonzero row axis for the padded program
        n_rows = max(
            max((len(item["X"]) for item in local_items), default=1), 1
        )
        if quantize_rows:
            # quantize the row axis so slices with slightly different
            # history lengths share one (n_padded, n_rows, F) shape and the
            # bucket reuses a single compiled executable; padded rows are
            # zero-weight and masked everywhere (fold masks run on
            # real-sample ranks)
            n_rows = -(-n_rows // _ROW_QUANTUM) * _ROW_QUANTUM
        X = np.zeros((hi - lo, n_rows, n_features), np.float32)
        y = np.zeros((hi - lo, n_rows, n_targets), np.float32)
        w = np.zeros((hi - lo, n_rows), np.float32)
        for i, item in enumerate(local_items):
            rows = len(item["X"])
            # RIGHT-aligned by convention (rows end at the bucket's latest
            # timestamp). CV correctness does not depend on placement: fold
            # masks are computed on real-sample ranks
            # (fleet.timeseries_fold_masks), invariant to where padding sits
            X[i, n_rows - rows :] = item["X"]
            y[i, n_rows - rows :] = item["y"]
            w[i, n_rows - rows :] = 1.0
    if place is not None and span is None:
        spec, mesh = place
        hit = peek_fleet_executable(
            spec, n_padded, n_rows, n_features, n_targets, mesh=mesh
        )
        if hit is not None:
            with spans.stage("fleet.place"):
                X, y, w = (
                    jax.device_put(a, f)
                    for a, f in zip((X, y, w), hit[1][:3])
                )
    return X, y, w, n_rows


def _prepare_bound(
    seam: spans.SpanContext, bucket: int, sl: int,
    after: Optional[Future], *args,
):
    """The prefetch worker's side of the seam: :func:`_prepare_slice` as
    the stage ``fleet.prepare`` of the job's timeline, under the context
    the main thread captured when it submitted the slice.

    ``after``: the commit that was in flight then. The fetch begins once it
    has ended (``held_s`` on the stage: how long that was): a fetch pool
    busy in Python starves the commit worker of the interpreter lock — a
    commit of 2 s took 22 s beside eight such threads, and every artifact
    of the slice landed that much later (PERF.md §6, PR 35) — while a
    commit handed over with the slice's result ends before a fetch that
    needs a whole slice's time has to begin. What the commit raised is the
    loop's join's to raise, not this worker's."""
    held = time.perf_counter()
    if after is not None:
        futures_wait([after])
    held = time.perf_counter() - held
    with spans.bind(seam), spans.stage(
        "fleet.prepare", bucket=bucket, slice=sl, held_s=held
    ):
        return _prepare_slice(*args)


def _local_machine_span(mesh, n_padded: int) -> Tuple[int, int]:
    """Contiguous ``[lo, hi)`` of machine indices this process's devices own
    under :func:`~gordo_components_tpu.parallel.mesh.fleet_sharding` for a
    padded machine axis of ``n_padded`` — derived from the sharding itself,
    never from assumptions about device ordering."""
    from .mesh import fleet_sharding

    starts, stops = [], []
    for dev, idx in fleet_sharding(mesh).devices_indices_map(
        (n_padded,)
    ).items():
        if dev.process_index != jax.process_index():
            continue
        sl = idx[0]
        starts.append(0 if sl.start is None else sl.start)
        stops.append(n_padded if sl.stop is None else sl.stop)
    if not starts:
        raise ValueError(
            "This process owns no devices in the fleet mesh — every "
            "participating process must contribute devices"
        )
    lo, hi = min(starts), max(stops)
    owned = sum(stop - start for start, stop in zip(starts, stops))
    if owned != hi - lo:
        # interleaved per-process devices (a custom mesh not in
        # jax.devices() order) would make the min/max span cover OTHER
        # processes' machines — fail loudly instead of fetching and
        # assembling the wrong shard
        raise ValueError(
            "This process's fleet-mesh shards are not contiguous "
            f"(owns {owned} of span [{lo}, {hi})); build the mesh with "
            "parallel.distributed.global_fleet_mesh() so each process's "
            "devices are adjacent on the machine axis"
        )
    return lo, hi


def _gather_local_block(result):
    """Pull THIS process's contiguous machine block of a globally-sharded
    stacked result to host numpy (``jax.device_get`` on the whole tree
    would fault on non-addressable shards)."""

    def pull(a):
        if not hasattr(a, "addressable_shards"):
            return np.asarray(a)
        seen = {}
        for s in a.addressable_shards:
            start = s.index[0].start or 0
            if start not in seen:
                seen[start] = np.asarray(s.data)
        return np.concatenate([seen[k] for k in sorted(seen)], axis=0)

    return jax.tree_util.tree_map(pull, result)


def _abstract_result(spec, n_machines, n_rows, n_features, n_targets):
    """Shape/dtype skeleton of a stacked slice result, WITHOUT running the
    program — the restore template for orbax (types round-trip exactly)."""
    import jax.numpy as jnp

    from .fleet import fleet_program, prng_key_width

    avatars = (
        jax.ShapeDtypeStruct((n_machines, n_rows, n_features), jnp.float32),
        jax.ShapeDtypeStruct((n_machines, n_rows, n_targets), jnp.float32),
        jax.ShapeDtypeStruct((n_machines, n_rows), jnp.float32),
        jax.ShapeDtypeStruct((n_machines, prng_key_width()), jnp.uint32),
    )
    program = fleet_program(spec, n_rows, n_features, n_targets)
    if not sequential_fits(spec):
        return jax.eval_shape(program, *avatars)
    # such a program hands the optimizer's state back beside the result
    return jax.eval_shape(
        program, *avatars, abstract_state(spec, n_machines, n_features)
    )[0]


def _leaf_size(a) -> int:
    """Element count without materializing (np.asarray on a non-addressable
    global array would fail)."""
    size = getattr(a, "size", None)
    return int(size) if size is not None else int(np.asarray(a).size)


class _SliceCheckpointer:
    """Orbax-backed async checkpoint of each slice's stacked training result
    (SURVEY.md §6.4: async checkpoint of the stacked fleet pytree).

    The save overlaps the per-machine artifact loop (device→host transfer is
    already done; orbax writes in a background thread), closing the crash
    window between "training finished" and "every artifact + registry key
    durable": a resume restores the trained pytree instead of retraining the
    slice. Checkpoints are deleted once their slice's artifacts are all
    written — steady state leaves nothing behind.

    **Multi-host** (``mesh`` spanning processes): save/restore are orbax
    COLLECTIVES over the globally-sharded result — every process writes and
    reads its own shards (checkpoint dir on shared storage), the restore
    template carries fleet-axis ``NamedSharding``s, and deletion happens on
    process 0 only after a cross-process barrier confirms every process's
    slice artifacts are durable."""

    def __init__(self, output_dir: str, mesh=None):
        import orbax.checkpoint as ocp

        self._root = os.path.abspath(os.path.join(output_dir, _CKPT_SUBDIR))
        self._ckptr = ocp.AsyncCheckpointer(ocp.StandardCheckpointHandler())
        self._ocp = ocp
        self._mesh = mesh
        self._multihost = jax.process_count() > 1

    @staticmethod
    def slice_key(slice_items: List[dict]) -> str:
        """Content key for a slice: the machines' cache keys (which already
        hash name + model/data/evaluation configs). Positional (bucket,
        slice) indices would SHIFT across resumes — completed machines
        leave ``pending``, so the survivors re-slice differently, and a
        stale positional checkpoint could silently restore another slice's
        params for the wrong machines."""
        import hashlib

        digest = hashlib.md5(
            json.dumps([item["cache_key"] for item in slice_items]).encode()
        )
        return digest.hexdigest()

    def path(self, key: str) -> str:
        return os.path.join(self._root, f"slice_{key}")

    # orbax refuses zero-size arrays (e.g. cv_scores with CV off); stand in
    # a 1-element placeholder on save and rebuild the empty array on restore
    def _shrink(self, tree):
        if self._multihost and self._mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            repl = NamedSharding(self._mesh, PartitionSpec())

            def placeholder(a):
                # a GLOBAL replicated array, not host numpy: the collective
                # save expects every leaf to be a jax.Array whose shards
                # each process can write
                return jax.device_put(np.zeros((1,), a.dtype), repl)

        else:

            def placeholder(a):
                return np.zeros((1,), np.asarray(a).dtype)

        return jax.tree_util.tree_map(
            lambda a: placeholder(a) if _leaf_size(a) == 0 else a, tree
        )

    def _shrink_abstract(self, abstract):
        """Placeholder zero-size leaves, and — multi-host — attach the
        fleet-axis sharding to every real leaf (orbax restores each process's
        shards directly) and replicate the placeholders."""
        if self._mesh is None or not self._multihost:
            return jax.tree_util.tree_map(
                lambda s: (
                    jax.ShapeDtypeStruct((1,), s.dtype) if 0 in s.shape else s
                ),
                abstract,
            )
        from jax.sharding import NamedSharding, PartitionSpec

        from .mesh import FLEET_AXIS

        shard = NamedSharding(self._mesh, PartitionSpec(FLEET_AXIS))
        repl = NamedSharding(self._mesh, PartitionSpec())
        return jax.tree_util.tree_map(
            lambda s: (
                jax.ShapeDtypeStruct((1,), s.dtype, sharding=repl)
                if 0 in s.shape
                else jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=shard)
            ),
            abstract,
        )

    @staticmethod
    def _unshrink(abstract, restored):
        return jax.tree_util.tree_map(
            lambda s, r: (
                np.zeros(s.shape, s.dtype) if 0 in s.shape else r
            ),
            abstract,
            restored,
        )

    def try_restore(self, key: str, abstract_fn):
        """``abstract_fn`` is a thunk: building the restore template costs a
        full eval_shape trace of the training program, so it only runs when
        a finalized checkpoint actually exists.

        Multi-host: all processes must take the SAME branch (restore is a
        collective; one process retraining while others restore would
        deadlock the training collectives), so existence is agreed by
        allgather first, and a restore failure then raises instead of
        silently diverging — the job-level retry handles it."""
        path = self.path(key)
        exists = os.path.isdir(path)  # orbax finalizes via atomic rename, so
        # a crashed mid-save leaves only a *-tmp dir, never this path
        if self._multihost:
            from jax.experimental import multihost_utils

            exists = bool(
                multihost_utils.process_allgather(
                    np.asarray([exists])
                ).all()
            )
        if not exists:
            return None
        abstract = abstract_fn()
        try:
            result = self._unshrink(
                abstract,
                self._ckptr.restore(
                    path,
                    args=self._ocp.args.StandardRestore(
                        self._shrink_abstract(abstract)
                    ),
                ),
            )
            logger.info(
                "Restored slice checkpoint %s (skipping retrain)", key
            )
            return result
        except Exception as exc:
            if self._multihost:
                raise  # diverging (one process retrains, others restored)
                # would deadlock the fleet collectives — fail the job loudly
            logger.warning(
                "Slice checkpoint %s unreadable (%s); retraining", path, exc
            )
            return None

    def save_async(self, key: str, result) -> None:
        self._ckptr.save(
            self.path(key),
            args=self._ocp.args.StandardSave(self._shrink(result)),
            force=True,
        )

    def join(self) -> None:
        """Wait for any in-flight async save WITHOUT deleting anything —
        exception-path cleanup, so a failed build neither leaks the saver
        thread nor lets a still-writing save race an in-process resume (a
        REAL kill has no thread left to race; this covers the simulated
        kills tests and chaos runs use). Deferred save errors are logged,
        not raised: the original build exception must propagate, and the
        checkpoint is only a resume accelerator."""
        try:
            self._ckptr.wait_until_finished()
        except Exception:
            logger.warning(
                "Async slice-checkpoint save failed during build abort",
                exc_info=True,
            )

    def finalize(self, key: str) -> None:
        """Wait for the async save, then drop the checkpoint — the slice's
        artifacts are durable now, so the registry is the source of truth.
        Multi-host: a cross-process barrier first (every process's slice
        artifacts must be durable before ANY copy of the checkpoint dies),
        then process 0 alone deletes from the shared dir."""
        import shutil

        self._ckptr.wait_until_finished()
        if self._multihost:
            from jax.experimental import multihost_utils

            multihost_utils.sync_global_devices(f"slice-durable-{key}")
            if jax.process_index() != 0:
                return
        shutil.rmtree(self.path(key), ignore_errors=True)

    def close(self) -> None:
        import shutil

        self._ckptr.wait_until_finished()
        self._ckptr.close()
        if self._multihost and jax.process_index() != 0:
            return
        shutil.rmtree(self._root, ignore_errors=True)


def _device_summary(array) -> Dict[str, Any]:
    """Platform, kind and count of the devices a result array lives on."""
    devices = sorted(array.sharding.device_set, key=lambda d: d.id)
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "count": len(devices),
    }


def _write_manifest(
    output_dir: str,
    completed: Dict[str, Dict[str, Any]],
    pending: List[str],
    journal_counts: Optional[Dict[str, int]] = None,
) -> None:
    """Fleet completion bitmap (SURVEY.md §6.4): one JSON file in the output
    dir recording which machines are done, rewritten atomically after every
    slice — a monitor (or a resuming build) reads fleet progress without
    scanning the registry. Multi-host: each non-zero process writes its own
    ``fleet_manifest.p{i}.json`` (its machine shard) so concurrent writers
    on shared storage never clobber each other; a monitor unions the files.

    ``journal_counts``: the resume accounting from the build journal —
    how many machines were skipped because a previous run committed them
    (``resumed``), found torn and redone (``torn``), and actually built
    this run (``rebuilt``)."""
    import os
    import tempfile

    manifest_file = MANIFEST_FILE
    if jax.process_count() > 1 and jax.process_index() != 0:
        stem, ext = os.path.splitext(MANIFEST_FILE)
        manifest_file = f"{stem}.p{jax.process_index()}{ext}"
    os.makedirs(output_dir, exist_ok=True)
    payload = {
        "updated": time.strftime("%Y-%m-%d %H:%M:%S%z"),
        "n_completed": len(completed),
        "n_pending": len(pending),
        "machines": completed,
        "pending": sorted(pending),
    }
    if journal_counts is not None:
        payload["journal"] = dict(journal_counts)
    fd, tmp = tempfile.mkstemp(dir=output_dir, suffix=".manifest")
    try:
        with os.fdopen(fd, "w") as fh:
            json.dump(payload, fh, indent=2)
        os.replace(tmp, os.path.join(output_dir, manifest_file))
    except BaseException:
        if os.path.exists(tmp):
            os.unlink(tmp)
        raise


@dataclass
class FleetMachineConfig:
    name: str
    model_config: Dict[str, Any]
    data_config: Dict[str, Any]
    metadata: Dict[str, Any] = field(default_factory=dict)
    # per-machine evaluation overrides (the reference's Machine.evaluation):
    # ``n_splits`` here beats build_fleet's global — machines with different
    # CV depths land in different compilation buckets
    evaluation: Dict[str, Any] = field(default_factory=dict)


def _effective_splits(
    machine: "FleetMachineConfig", default: int
) -> Tuple[int, List[str]]:
    """Resolve the machine's CV depth: ``evaluation.n_splits`` beats the
    builder default (``None``/absent means "use the default"). Returns with
    it the keys the fleet builder does NOT honor (e.g. ``cv_mode`` — always
    ``"fleet"`` here) so the caller can surface them instead of silently
    dropping config."""
    evaluation = machine.evaluation or {}
    value = evaluation.get("n_splits")
    if value is None:
        eff = int(default)
    else:
        if isinstance(value, bool) or not isinstance(value, int):
            raise ValueError(
                f"Machine {machine.name!r}: evaluation.n_splits must be an "
                f"integer, got {value!r}"
            )
        if value < 0:
            raise ValueError(
                f"Machine {machine.name!r}: evaluation.n_splits must be >= 0, "
                f"got {value}"
            )
        eff = value
    ignored = sorted(k for k in evaluation if k != "n_splits")
    return eff, ignored


def _scaler_kind(
    scaler: Optional[Any],
) -> Tuple[str, Tuple[float, float], Tuple[bool, bool]]:
    if scaler is None:
        return "none", (0.0, 1.0), (True, True)
    if isinstance(scaler, MinMaxScaler):
        return "minmax", tuple(scaler.feature_range), (True, True)
    if isinstance(scaler, StandardScaler):
        return (
            "standard",
            (0.0, 1.0),
            (bool(scaler.with_mean), bool(scaler.with_std)),
        )
    raise ValueError(
        f"Fleet building supports MinMaxScaler/StandardScaler steps; got "
        f"{type(scaler).__name__}"
    )


def _spec_for(
    analyzed: _Analyzed,
    n_features: int,
    n_targets: int,
    n_splits: int,
) -> FleetSpec:
    est = analyzed.estimator
    if getattr(est, "joint_horizon", False):
        raise ValueError(
            "MultiStepForecast (joint horizon) is single-machine only: the "
            "fleet program's target/weight math assumes one target row per "
            "window — use LSTMForecast(horizon=k) for fleet builds"
        )
    model_spec = est._make_spec(n_features, n_targets)
    kind, feature_range, scaler_options = _scaler_kind(analyzed.input_scaler)
    t_kind, t_range, t_options = _scaler_kind(analyzed.target_scaler)
    if analyzed.detector is not None and not isinstance(
        analyzed.detector.scaler, MinMaxScaler
    ):
        # the compiled program computes minmax error-scaler params; writing
        # them into a different scaler class would silently change scoring
        raise ValueError(
            "Fleet building supports a MinMaxScaler anomaly error scaler; "
            f"got {type(analyzed.detector.scaler).__name__} — use the "
            "single-machine builder for this config"
        )
    dropout = float(model_spec.config.get("dropout", 0.0) or 0.0)
    return FleetSpec(
        module=model_spec.module,
        optimizer=model_spec.optimizer,
        loss=model_spec.loss,
        lookahead=est.lookahead,
        lookback_window=est.lookback_window,
        scaler=kind,
        feature_range=feature_range,
        batch_size=est.batch_size,
        epochs=est.epochs,
        n_splits=n_splits,
        use_dropout=dropout > 0.0,
        scale_targets=analyzed.target_scaler is not None,
        scaler_options=scaler_options,
        target_scaler=t_kind,
        target_feature_range=t_range,
        target_scaler_options=t_options,
        # a model that recomputes its activations is trading FLOPs for
        # memory: the one fact every execution choice follows (fleet.py)
        memory_constrained=bool(model_spec.config.get("remat", False)),
        rows_out=est.rows_out,
    )


def _slice_cap(spec: FleetSpec, n_features: int) -> Optional[int]:
    """The most machines of a memory-constrained spec a slice may hold, from
    the parameter count: weights, gradients and the optimizer's moments (the
    training state and a third of it again) within three quarters of the
    device's memory; at least one. ``None``: no cap (a spec that is not
    memory-constrained, or a device that does not say what it holds)."""
    if not spec.memory_constrained:
        return None
    limit = (jax.local_devices()[0].memory_stats() or {}).get("bytes_limit")
    if not limit:
        return None
    state = sum(
        leaf.size * leaf.dtype.itemsize
        for leaf in jax.tree_util.tree_leaves(abstract_state(spec, 1, n_features))
    )
    return max(1, int(0.75 * limit // (state * 4 / 3)))


def _slice_scaler(stacked: ScalerParams, i: int) -> ScalerParams:
    return ScalerParams(
        scale=np.asarray(stacked.scale[i]), offset=np.asarray(stacked.offset[i])
    )


def _install_result(
    model: Any, result, i: int, n_features: int, n_targets: int, n_splits: int
) -> None:
    """Write machine ``i``'s slice of the stacked bucket result into a fresh
    materialized model graph — producing the same fitted object the
    single-machine path would."""
    analyzed = _analyze_model(model)
    history = [float(v) for v in np.asarray(result.loss_history[i])]
    analyzed.estimator.set_state(
        {
            "params": jax.tree_util.tree_map(
                lambda leaf: np.asarray(leaf[i]), result.params
            ),
            "n_features": n_features,
            "n_features_out": n_targets,
            "history": history,
        }
    )
    if analyzed.input_scaler is not None:
        analyzed.input_scaler.params_ = _slice_scaler(result.input_scaler, i)
    if analyzed.target_scaler is not None:
        analyzed.target_scaler.params_ = _slice_scaler(result.target_scaler, i)
    if analyzed.detector is not None:
        det = analyzed.detector
        det.scaler.params_ = _slice_scaler(result.error_scaler, i)
        det.tag_thresholds_ = np.asarray(result.tag_thresholds[i])
        det.total_threshold_ = float(result.total_threshold[i])
        det.cross_validation_ = _cv_metadata(result, i, n_splits)


def _cv_metadata(result, i: int, n_splits: int) -> Dict[str, Any]:
    """Per-machine CV record with the same metric keys the single-machine
    builder emits (models.metrics.METRICS); NaN fold scores (fold had no
    real rows for this machine) are reported as null, never averaged in."""
    cv_scores = np.asarray(result.cv_scores[i])  # (n_splits, n_metrics)

    def val(s):
        return float(s) if np.isfinite(s) else None

    aggregates = {}
    for m, name in enumerate(FLEET_CV_METRICS):
        col = cv_scores[:, m]
        real = col[np.isfinite(col)]
        aggregates[name] = float(np.mean(real)) if len(real) else None
    return {
        "n_splits": n_splits,
        "splits": [
            {
                "fold": k,
                "scores": {
                    name: val(fold[m])
                    for m, name in enumerate(FLEET_CV_METRICS)
                },
            }
            for k, fold in enumerate(cv_scores)
        ],
        "scores": aggregates,
    }


class _SliceWatchdog:
    """Failure detection for multi-host slices (SURVEY §6.3 translation:
    the reference delegates hung-pod detection to k8s liveness + Argo
    retries; a multi-host ``build_fleet`` needs an in-process equivalent
    because a dead PEER process leaves the survivors blocked inside a
    collective — ``process_allgather``, the collective orbax save/restore,
    or a barrier — which no k8s probe can distinguish from slow training
    from the outside).

    With ``GORDO_SLICE_TIMEOUT_S`` set (CLI: ``fleet-build`` passes the
    env through), each slice iteration must finish inside the budget or
    the process logs CRITICAL and hard-exits :data:`EXIT_RETRYABLE` (75,
    EX_TEMPFAIL — retried under the reference's Argo semantics, unlike
    the permanent 64/66). A hard ``os._exit`` is deliberate: a thread
    blocked in a native collective cannot be interrupted from Python, so
    a cooperative exception would never fire. Restart-all-then-resume is
    exactly the reference's retry model — the re-run resolves finished
    machines from the registry and restores any checkpointed slice
    instead of retraining. Size the budget above the worst healthy slice
    wall time (it is a liveness bound, not a perf target); unset = no
    watchdog (single-host builds never arm it: a lone process cannot be
    stalled by a peer, and killing it would lose the in-flight slice for
    nothing).

    Pinned end-to-end by tests/test_aux.py's asymmetric-failure drill
    (peer killed mid-build -> survivor exits 75 -> rerun resumes).
    """

    def __init__(self, multihost: bool, timeout_s: Optional[float] = None):
        if timeout_s is None:
            raw = os.environ.get(SLICE_TIMEOUT_ENV, "")
            timeout_s = float(raw) if raw else 0.0
        self.armed = bool(multihost and timeout_s > 0)
        self.timeout_s = timeout_s
        self._timer: Optional[Any] = None
        self._where = ""

    def start(self, bucket: int, sl: int) -> None:
        """Arm the timer for one slice iteration (no-op when unarmed)."""
        if not self.armed:
            return
        import threading

        self.stop()
        self._where = f"bucket {bucket} slice {sl}"
        self._timer = threading.Timer(self.timeout_s, self._trip)
        self._timer.daemon = True
        self._timer.start()

    def stop(self) -> None:
        if self._timer is not None:
            self._timer.cancel()
            self._timer = None

    def _trip(self) -> None:
        try:
            # best-effort diagnostics only: ANY exception here (e.g. the
            # distributed runtime already torn down when process_index()
            # is evaluated) must still reach os._exit — a dead timer
            # thread would leave the process hung in the native
            # collective, the exact failure this watchdog exists to stop
            logger.critical(
                "Fleet slice watchdog: %s exceeded %.0fs on process %d — "
                "a peer process has likely died mid-collective; exiting "
                "%d (retryable) so the job layer restarts all processes "
                "and the re-run resumes from registry + slice checkpoints",
                self._where,
                self.timeout_s,
                jax.process_index(),
                EXIT_RETRYABLE,
            )
            logging.shutdown()  # the CRITICAL line must hit the stream
            # before os._exit skips every atexit/flush hook
        finally:
            os._exit(EXIT_RETRYABLE)

def build_fleet(
    machines: List[FleetMachineConfig],
    output_dir: str,
    model_register_dir: Optional[str] = None,
    mesh=None,
    seed: int = 0,
    n_splits: int = 3,
    profile_dir: Optional[str] = None,
    slice_size: Optional[int] = 256,
    fetch_retries: Optional[int] = None,
    fetch_backoff: Optional[float] = None,
    precision_default: Optional[str] = None,
    precision_map: Optional[Dict[str, str]] = None,
) -> Dict[str, str]:
    """Build every machine; returns ``{name: model_dir}``.

    **Precision ladder (§19)**: ``precision_map`` pins individual
    machines to a rung (f32/bf16/int8); everything else takes
    ``precision_default`` (flag → ``GORDO_PRECISION_DEFAULT`` → f32).
    Training always runs f32 — precision shapes each machine's SERVING
    artifact: the metadata pin, the int8 quantized sidecar, and the
    cache key (so re-precisioning a machine rebuilds its artifact
    rather than resurrecting the old rung's).

    **Per-machine failure isolation**: a machine whose data fetch fails
    (after ``fetch_retries`` backed-off retries — defaults from
    ``GORDO_BUILD_FETCH_RETRIES``/``GORDO_BUILD_FETCH_BACKOFF``, else 2 /
    0.5 s) is built as zero-weight padding and recorded ``failed`` in the
    fleet manifest instead of aborting the other machines' build; it is
    absent from the returned mapping and, being unregistered, retried by
    the next run. (Single-host only for the width-probe path — multi-host
    bucketing must stay process-identical, so probe failures there still
    abort.)

    Machines whose config hash is already registered — or whose build
    journal record says ``committed`` (``store/journal.py``; the WAL is
    the resume source when no registry is configured) — are skipped,
    but only after their artifact passes manifest VERIFICATION; a torn
    one is redone and counted under ``torn`` in the fleet manifest's
    ``journal`` block (alongside ``resumed``/``rebuilt``). Artifacts
    land as atomic ``gen-NNNN`` generations (``store/``), so a kill at
    any point leaves each machine either whole or absent — never torn.
    Remaining machines are bucketed by (model config, data shape)
    and each bucket trains as one compiled program, sharded over ``mesh``.
    ``profile_dir``: one ``jax.profiler`` session around one whole steady
    slice (the second of the first bucket, else the first) and its commit,
    host phases included, and the job's span timeline as
    ``fleet_build_timeline.json`` beside it.

    **Spans**: the job is one ``observability.spans.Timeline`` (``fleet.job``
    → ``fleet.preamble``, ``fleet.bucket`` → ``fleet.plan`` and
    ``fleet.slice`` and its phases on this thread, ``fleet.prepare`` and its
    fetches on the prefetch worker's, ``fleet.commit_loop`` and
    ``fleet.manifest`` on the commit worker's; docs/ARCHITECTURE.md §13),
    handed to the flight recorder (``meta`` ``kind="fleet-build"``) however
    the job ends (``flightrec.build_timeline``). ``gordo fleet-build`` begins
    it at its own entry, and the job records into that one.

    Buckets larger than ``slice_size`` train in slices: every slice is padded
    to the same machine count (so the compiled executable is reused across
    slices) and its artifacts + registry keys are written the moment its
    result is on the host, by a worker thread while the next slice trains
    (:class:`_SliceCommitter`: one commit in flight; this call returns, or
    lets an exception out, only after it has ended) — a killed build loses
    at most the slice in flight and the slice committing (whose checkpoint,
    where it has more than one machine, a resume restores), and the resume
    pass skips everything already registered. ``slice_size=None`` trains
    each bucket in a single program call (round-1 behavior).

    **Multi-host** (``jax.process_count() > 1`` with a
    :func:`~gordo_components_tpu.parallel.distributed.global_fleet_mesh`):
    every process runs the same deterministic bucketing, but fetches ONLY
    its own machines' data (the slice prefetcher assembles the process-local
    shard, overlapping the previous slice's training as on one host), the
    shards become one global batch via
    ``jax.make_array_from_process_local_data``, and after training each
    process writes only its own machines' artifacts + registry keys.
    Requires ``output_dir``/``model_register_dir`` on storage shared by all
    processes (the reference's shared-volume assumption) so resume scans
    agree; each process's return value covers cached + its own machines.
    Slice checkpoints are orbax COLLECTIVES over the sharded result (each
    process writes/reads its own shards), layered on the per-machine
    registry resume.
    """
    with build_timeline(profile_dir, machines=len(machines)):
        with spans.stage("fleet.job", machines=len(machines)):
            return _build_fleet(
                machines, output_dir, model_register_dir, mesh, seed,
                n_splits, profile_dir, slice_size, fetch_retries,
                fetch_backoff, precision_default, precision_map,
            )


def _build_fleet(
    machines: List[FleetMachineConfig],
    output_dir: str,
    model_register_dir: Optional[str],
    mesh,
    seed: int,
    n_splits: int,
    profile_dir: Optional[str],
    slice_size: Optional[int],
    fetch_retries: Optional[int],
    fetch_backoff: Optional[float],
    precision_default: Optional[str],
    precision_map: Optional[Dict[str, str]],
) -> Dict[str, str]:
    """The job itself, inside :func:`build_fleet`'s ``fleet.job`` stage."""
    if slice_size is not None and slice_size < 1:
        # validated BEFORE any dataset probing or cache scanning, so an
        # invalid value errors even on a fully-cached (no-op) build
        raise ValueError(
            f"slice_size must be a positive integer or None, got {slice_size!r}"
        )
    if fetch_retries is None:
        fetch_retries = int(os.environ.get(FETCH_RETRIES_ENV, "2"))
    if fetch_backoff is None:
        fetch_backoff = float(os.environ.get(FETCH_BACKOFF_ENV, "0.5"))
    # precision resolution: per-machine map beats the fleet default; every
    # value validated HERE (including map entries naming no machine in
    # this fleet — a typo'd name must fail the build, not silently build
    # that machine f32)
    fleet_precision = precision_mod.resolve_default(precision_default)
    precision_map = {
        name: precision_mod.validate(value)
        for name, value in (precision_map or {}).items()
    }
    known = {machine.name for machine in machines}
    unknown = sorted(set(precision_map) - known)
    if unknown:
        raise ValueError(
            f"--precision-map names machines not in this fleet: {unknown}"
        )

    def precision_of(name: str) -> str:
        return precision_map.get(name, fleet_precision)
    multihost = jax.process_count() > 1
    if multihost:
        if mesh is None:
            raise ValueError(
                "multi-host fleet builds need a global mesh "
                "(parallel.distributed.global_fleet_mesh())"
            )
        logger.info(
            "Multi-host fleet build: process %d/%d fetches and writes only "
            "its own machine shard; slice checkpoints are collective",
            jax.process_index(),
            jax.process_count(),
        )


    with spans.stage("fleet.preamble") as preamble:
        results: Dict[str, str] = {}
        pending: List[Tuple[FleetMachineConfig, str, int]] = []
        ignored_eval: Dict[str, List[str]] = {}
        # resumable-build WAL: one fsync'd record per machine lifecycle event
        # (started / committed / failed); a re-run replays it (unioned with any
        # multi-host siblings) so committed machines are skipped even when no
        # registry is configured, and torn ones are provably redone
        journal = store_journal.BuildJournal(
            store_journal.journal_path(output_dir, jax.process_index())
        )
        journal_states = store_journal.replay(output_dir)
        journal_counts = {"resumed": 0, "torn": 0, "rebuilt": 0}
        for machine in machines:
            eff_splits, ignored = _effective_splits(machine, n_splits)
            if ignored:
                ignored_eval[machine.name] = ignored
            evaluation_config = {"n_splits": eff_splits, "cv_mode": "fleet"}
            cache_key = calculate_model_key(
                machine.name,
                machine.model_config,
                machine.data_config,
                evaluation_config=evaluation_config,
                # §19: re-precisioning a machine is a cache miss — a cached
                # f32 artifact must not satisfy an int8 build (and vice
                # versa); f32 keeps every pre-ladder key valid
                precision=precision_of(machine.name),
            )
            cached: Optional[str] = None
            if model_register_dir:
                # dangling pointers already read as None inside get_value
                cached = disk_registry.get_value(model_register_dir, cache_key)
            if cached is None:
                # no registry (or no entry): the journal's committed record is
                # the fallback resume source — but only for the SAME config
                # (cache_key match), else a config change would resurrect a
                # stale artifact
                record = journal_states.get(machine.name)
                if (
                    record is not None
                    and record.get("event") == store_journal.EVENT_COMMITTED
                    and record.get("cache_key") == cache_key
                    and os.path.isdir(str(record.get("model_dir", "")))
                ):
                    cached = str(record["model_dir"])
            if cached is not None:
                # trust nothing unverified: a registered-but-torn artifact
                # (crash between artifact and registry durability) must
                # rebuild, not serve half a model later. Structural check
                # only (deep=False): a fully-cached thousand-machine resume
                # must stay O(stats) — the serving load() pays the hash pass
                try:
                    verify_artifact(resolve_artifact_dir(cached), deep=False)
                except StoreError as exc:
                    logger.warning(
                        "Fleet resume: artifact for %r fails verification "
                        "(%s); rebuilding", machine.name, exc,
                    )
                    journal_counts["torn"] += 1
                else:
                    cached_precision = cached_artifact_precision(cached)
                    if cached_precision != precision_of(machine.name):
                        # registry/journal values are the machine's SHARED
                        # output dir — a later re-precision build swapped
                        # CURRENT under this key's entry, so a hit alone
                        # must not resurrect the other rung (§19)
                        logger.warning(
                            "Fleet resume: artifact for %r serves precision "
                            "%s but this build pins %s; rebuilding",
                            machine.name, cached_precision,
                            precision_of(machine.name),
                        )
                    else:
                        logger.info(
                            "Fleet cache hit for %r -> %s", machine.name, cached
                        )
                        results[machine.name] = cached
                        journal_counts["resumed"] += 1
                        _M_FLEET_MACHINES.labels("cached").inc()
                        continue
            pending.append((machine, cache_key, eff_splits))
        if ignored_eval:
            sample = dict(list(ignored_eval.items())[:5])
            logger.warning(
                "Fleet builder ignores unsupported evaluation keys on %d "
                "machine(s) (cv_mode is always 'fleet' here): %s%s",
                len(ignored_eval),
                sample,
                " ..." if len(ignored_eval) > 5 else "",
            )

        manifest: Dict[str, Dict[str, Any]] = {
            name: {"status": "cached", "model_dir": path}
            for name, path in results.items()
        }
        _write_manifest(
            output_dir, manifest, [m.name for m, *_ in pending],
            journal_counts=journal_counts,
        )

        # ---- bucket by (model config, feature/target width) BEFORE fetching:
        # widths come from the dataset's declared columns, so peak host memory
        # is one bucket's data, not the whole fleet's ---------------------------
        buckets: Dict[str, List[dict]] = {}
        for machine, cache_key, eff_splits in pending:
            dataset = _dataset_from_config(machine.data_config)
            item: dict = {
                "machine": machine,
                "cache_key": cache_key,
                "dataset": dataset,
            }
            if hasattr(dataset, "_columns_for"):
                n_features = len(dataset._columns_for(dataset.tag_list))
                n_targets = len(dataset._columns_for(dataset.target_tag_list))
            elif multihost:  # non-TimeSeriesDataset: widths require a fetch —
                # and multi-host bucketing must stay identical on every
                # process, so a probe failure aborts (job-level retry) rather
                # than diverging the collective program
                X_probe, y_probe = dataset.get_data()
                n_features, n_targets = X_probe.shape[1], y_probe.shape[1]
                item["X"] = np.asarray(getattr(X_probe, "values", X_probe), np.float32)
                item["y"] = np.asarray(getattr(y_probe, "values", y_probe), np.float32)
                item["dataset_metadata"] = dataset.get_metadata()
            else:  # single-host width probe: fetch with retry, isolating a
                # terminally-failing machine BEFORE it ever buckets
                error = _fetch_machine_data(item, fetch_retries, fetch_backoff)
                if error is not None:
                    logger.error(
                        "Isolating machine %r from fleet build (width probe): %s",
                        machine.name, error,
                    )
                    manifest[machine.name] = {"status": "failed", "error": error}
                    journal.record(
                        machine.name, store_journal.EVENT_FAILED, error=error
                    )
                    _M_FLEET_MACHINES.labels("failed").inc()
                    continue
                n_features, n_targets = item["X"].shape[1], item["y"].shape[1]
            item["F"], item["T"] = n_features, n_targets
            item["n_splits"] = eff_splits
            sig = json.dumps(
                {
                    "model_config": machine.model_config,
                    "F": n_features,
                    "T": n_targets,
                    "n_splits": item["n_splits"],
                },
                sort_keys=True,
                default=str,
            )
            buckets.setdefault(sig, []).append(item)

        if any(
            entry.get("status") == "failed" for entry in manifest.values()
        ):
            # probe-isolated machines must land in the on-disk manifest even
            # when every remaining machine is cached (no slice write follows)
            _write_manifest(
                output_dir, manifest,
                [m.name for m, *_ in pending if m.name not in manifest],
                journal_counts=journal_counts,
            )

        master_key = jax.random.PRNGKey(seed)
        pending_names = [machine.name for machine, *_ in pending]
        checkpointer = _SliceCheckpointer(output_dir, mesh=mesh)
        watchdog = _SliceWatchdog(multihost)
        prefetcher = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="fleet-prefetch"
        )
        books = _JobBooks(
            output_dir, model_register_dir, precision_of, journal,
            journal_counts, manifest, results, pending_names,
        )
        committer = _SliceCommitter(checkpointer)
        session = contextlib.ExitStack()  # --trace-dir's profiler session
        preamble["cached"] = len(results)
        preamble["buckets"] = len(buckets)
    logger.info(
        "Fleet build: %d machines, %d cached, %d to build in %d bucket(s)",
        len(machines), len(results), len(pending), len(buckets),
    )

    try:
        for b, (sig, items) in enumerate(sorted(buckets.items())):
            bucket_started = time.perf_counter()
            with spans.stage(
                "fleet.bucket", bucket=b, machines=len(items)
            ) as bucket:
                # the bucket's plan: its spec, slices and padding, before
                # its first fetch is asked for
                with spans.stage("fleet.plan", bucket=b):
                    model_config = items[0]["machine"].model_config
                    probe = pipeline_from_definition(model_config)
                    analyzed = _analyze_model(probe)
                    n_features = items[0]["F"]
                    n_targets = items[0]["T"]
                    bucket_splits = items[0]["n_splits"]
                    spec = _spec_for(
                        analyzed, n_features, n_targets, bucket_splits
                    )

                    # ---- slice the bucket: each slice is an independent
                    # failure domain with its own data fetch, train call,
                    # and artifact writes. All slices share one padded
                    # machine count so the compiled executable is reused
                    # (fleet_program caches on spec+shape) ------------------
                    n_real = len(items)
                    eff = n_real if not slice_size else min(slice_size, n_real)
                    cap = _slice_cap(spec, n_features)
                    if cap is not None and cap < eff:
                        logger.info(
                            "Fleet bucket %d: slices of %d, not %d: the "
                            "training state of more would not fit the device",
                            b + 1, cap, eff,
                        )
                        eff = cap
                    n_padded = (
                        pad_to_multiple(eff, mesh.size)
                        if mesh is not None else eff
                    )
                    slices = [
                        items[s : s + eff] for s in range(0, n_real, eff)
                    ]
                    bucket["slices"] = len(slices)
                    logger.info(
                        "Fleet bucket %d/%d: %d machines in %d slice(s) of %d "
                        "(padded %d), F=%d",
                        b + 1,
                        len(buckets),
                        n_real,
                        len(slices),
                        eff,
                        n_padded,
                        n_features,
                    )
                    span = (
                        _local_machine_span(mesh, n_padded)
                        if multihost else None
                    )
                    # single-host transfer overlap (see _prepare_slice): the
                    # worker device-places a prepared slice when the
                    # bucket's executable already exists. Memory-constrained
                    # (remat) buckets keep the batch on host until their own
                    # turn — their peak-HBM budget has no room for a second
                    # slice's buffers
                    place = (
                        (spec, mesh)
                        if not (multihost or spec.memory_constrained)
                        else None
                    )
                # the prefetch worker inherits no context: it binds this
                # one, so every fleet.prepare hangs under this bucket's
                # stage (beside the slices it overlaps, not inside one)
                seam = spans.capture()

                def prefetch(s: int):
                    return prefetcher.submit(
                        _prepare_bound, seam, b, s, committer.in_flight(),
                        slices[s], n_padded, n_features, n_targets,
                        len(slices) > 1, span, place, fetch_retries,
                        fetch_backoff,
                    )

                prepared = prefetch(0)
                # --trace-dir: ONE profiler session per job, from one steady
                # slice's wait for the prefetch (the first slice of a job
                # holds the compile) until that slice's commit has been
                # joined — host phases and the idle gaps between them
                # included, and the commit beside the next slice's device ops
                traced_slice = min(1, len(slices) - 1) if b == 0 else None
                for s, slice_items in enumerate(slices):
                    # armed only multi-host + GORDO_SLICE_TIMEOUT_S: if THIS
                    # iteration stalls past the budget (dead peer -> blocked
                    # collective), the process exits EXIT_RETRYABLE for the
                    # job layer to restart; disarmed at iteration end below
                    # and in the outer finally
                    watchdog.start(b, s)
                    if s == traced_slice:
                        session.enter_context(device_trace(profile_dir))
                    with spans.stage(
                        "fleet.slice", bucket=b, slice=s,
                        machines=len(slice_items),
                    ) as sliced:
                        slice_started = time.perf_counter()
                        with spans.stage("fleet.prefetch_wait"):
                            X, y, w, n_rows = prepared.result()
                        if s + 1 < len(slices):
                            prepared = prefetch(s + 1)
                        with spans.stage(
                            "fleet.ingest", step="assemble",
                            placed_by_prefetch=isinstance(X, jax.Array),
                        ) as ingested:
                            keys = jax.random.split(
                                jax.random.fold_in(
                                    jax.random.fold_in(master_key, b), s
                                ),
                                n_padded,
                            )
                            if multihost:
                                batch, n_rows = _global_batch(
                                    X, y, w, n_rows, keys, mesh, span
                                )
                            else:
                                batch = MachineBatch(X=X, y=y, w=w, keys=keys)
                            ingested["bytes"] = X.nbytes + y.nbytes + w.nbytes
                        sliced["n_rows"] = n_rows

                        ckpt_key = checkpointer.slice_key(slice_items)
                        trained_on = None  # a restored slice trained in another run
                        with spans.stage("fleet.checkpoint_restore") as restore:
                            result = checkpointer.try_restore(
                                ckpt_key,
                                lambda: _abstract_result(
                                    spec, n_padded, n_rows, n_features, n_targets
                                ),
                            )
                            restore["hit"] = restored = result is not None
                        if not restored:
                            # stages fleet.program, fleet.ingest (the
                            # device_put) and fleet.execute, which ends when
                            # the result is ready on the device
                            result = train_fleet_arrays(spec, batch, mesh=mesh)
                            trained_on = _device_summary(result.loss_history)
                        # what the commit reads is on the host: the whole
                        # result, or this process's machine block of a
                        # globally sharded one (restored or trained), which
                        # the checkpoint keeps sharded
                        if multihost:
                            with spans.stage("fleet.result_fetch"):
                                on_host = _gather_local_block(result)
                        else:
                            if not restored:
                                with spans.stage("fleet.result_fetch") as fetched:
                                    result = jax.device_get(result)
                                    fetched["bytes"] = sum(
                                        leaf.nbytes for leaf in
                                        jax.tree_util.tree_leaves(result)
                                    )
                            on_host = result
                            # what the model's own loss counted over each
                            # machine's final fit (models.train: counters)
                            for name, counted in (result.counters or {}).items():
                                sliced[name] = np.asarray(counted).tolist()
                        slice_duration = time.perf_counter() - slice_started

                        # at most one commit in flight, and one thread for
                        # the checkpointer and its collectives: the slice
                        # before is durable and its checkpoint dropped
                        # before this one's is written
                        committer.join()
                        if not restored:
                            # async: orbax writes in the background while the
                            # worker commits (multi-host: a COLLECTIVE save
                            # of the sharded result); the join of this
                            # slice's commit finalizes it. A slice of one
                            # machine has nothing to save that its artifact,
                            # written next, does not hold
                            with spans.stage("fleet.checkpoint_save") as saved:
                                saved["skipped"] = n_padded == 1
                                if n_padded > 1:
                                    checkpointer.save_async(ckpt_key, result)

                        if multihost:
                            lo, hi = span
                            # this process's machines only; result rows are
                            # the local block, so indices shift by lo
                            indexed_items = [
                                (i - lo, item)
                                for i, item in enumerate(slice_items)
                                if lo <= i < hi
                            ]
                        else:
                            indexed_items = list(enumerate(slice_items))
                        provenance = {
                            "bucket": b,
                            "bucket_size": n_real,
                            "slice": s,
                            "slice_size": len(slice_items),
                            "slice_duration_s": slice_duration,
                            # fold-execution mode that trained this artifact
                            # (provenance; not in the cache key: both modes
                            # train the same models)
                            "cv_parallel": not spec.memory_constrained,
                            # where the trained arrays lived, read off their
                            # sharding — JAX hands back the CPU without
                            # failing when it cannot get the chip, and the
                            # artifact should say which one trained it
                            "devices": trained_on,
                        }

                        # ---- per-machine artifacts (same format as the
                        # single path), written by the commit worker while
                        # the next slice trains: a kill loses at most the
                        # slice in flight and the slice committing. A slice
                        # of several machines keeps its checkpoint until its
                        # commit is durable, so a resume restores it; a
                        # slice of one saved none and is retrained ----------
                        for item in slice_items:
                            # free before the next fetch: the batch holds
                            # the rows, and the commit reads none of them
                            item.pop("X", None)
                            item.pop("y", None)
                        committer.hand_over(
                            s, ckpt_key, books, indexed_items, on_host,
                            (n_features, n_targets, bucket_splits),
                            provenance,
                        )
                        # the worker's is the last hold on the fetched
                        # result: a large one (2 GB take a tenth of a
                        # second to unmap) is freed there, beside the next
                        # slice's run, not here between two runs
                        del result, on_host
                        if s + 1 == len(slices):
                            # no slice of this bucket is left to train
                            # beside the commit
                            committer.join()
                    if traced_slice is not None and (
                        s > traced_slice or s + 1 == len(slices)
                    ):
                        session.close()  # the traced slice has committed
                    watchdog.stop()  # this slice made liveness; next start()
                    # re-arms with a fresh budget
            logger.info(
                "Fleet bucket %d/%d done in %.1fs",
                b + 1, len(buckets), time.perf_counter() - bucket_started,
            )

    finally:
        watchdog.stop()
        prefetcher.shutdown(wait=True, cancel_futures=True)
        try:
            # where an exception is on its way out with a commit in flight:
            # the job ends only after that commit has, and with the commit's
            # own exception if it failed
            committer.drain()
        finally:
            session.close()
            checkpointer.join()
    checkpointer.close()
    return results


def _global_batch(X, y, w, n_rows: int, keys, mesh, span: Tuple[int, int]):
    """Multi-host, main thread only (see :func:`_prepare_slice`): agree on
    the global row width, then lift the process-local shards into one
    global batch — ingest stayed process-local and overlapped, only this
    assembly is synchronous. Returns ``(batch, n_rows)``."""
    from jax.experimental import multihost_utils

    from .mesh import fleet_sharding

    n_rows_global = int(
        multihost_utils.process_allgather(np.asarray([n_rows])).max()
    )
    if n_rows_global != n_rows:
        # leading pad keeps every machine right-aligned
        pad = ((0, 0), (n_rows_global - n_rows, 0))
        X = np.pad(X, pad + ((0, 0),))
        y = np.pad(y, pad + ((0, 0),))
        w = np.pad(w, pad)
        n_rows = n_rows_global
    sharding = fleet_sharding(mesh)
    lo, hi = span
    batch = MachineBatch(
        X=jax.make_array_from_process_local_data(sharding, X),
        y=jax.make_array_from_process_local_data(sharding, y),
        w=jax.make_array_from_process_local_data(sharding, w),
        keys=jax.make_array_from_process_local_data(
            sharding, np.asarray(keys)[lo:hi]
        ),
    )
    return batch, n_rows


class _JobBooks(NamedTuple):
    """What a job keeps of its machines' outcomes, and where it writes them.
    The preamble fills them on the loop's thread; from the first hand-over
    on, the commit worker alone writes them (one commit in flight, joined
    before the next is handed over and before ``build_fleet`` ends)."""

    output_dir: str
    model_register_dir: Optional[str]
    precision_of: Callable[[str], str]
    journal: Any
    journal_counts: Dict[str, int]
    manifest: Dict[str, Dict[str, Any]]
    results: Dict[str, str]
    pending_names: List[str]


class _SliceCommitter:
    """The artifact commit off the build loop's thread: slice ``s`` commits
    on the one ``fleet-commit`` worker while the loop's thread waits for
    slice ``s+1``'s prefetch, ingests it, dispatches its program and waits
    on the device. At most one commit is in flight: :meth:`join` comes
    before every :meth:`hand_over`.

    The checkpointer stays the loop's thread's (one thread touches it, and
    multi-host every process reaches its barrier at the same point of its
    loop): :meth:`join` drops a slice's checkpoint once that slice's commit
    is durable, never sooner, so a kill inside a commit finds the trained
    result on disk."""

    def __init__(self, checkpointer: "_SliceCheckpointer"):
        self._pool = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="fleet-commit"
        )
        self._checkpointer = checkpointer
        self._in_flight: Optional[Tuple[Future, int, str]] = None

    def hand_over(self, sl: int, ckpt_key: str, *commit_args) -> None:
        """Slice ``sl``'s commit (:func:`_commit_slice`) to the worker, its
        spans under the stage open here: the slice's own."""
        if self._in_flight is not None:
            raise RuntimeError("a commit is in flight: join() comes first")
        future = self._pool.submit(
            _commit_slice, spans.capture(), *commit_args
        )
        self._in_flight = (future, sl, ckpt_key)

    def in_flight(self) -> Optional[Future]:
        """The commit on the worker now, for what has to begin after it."""
        return self._in_flight[0] if self._in_flight is not None else None

    def join(self) -> None:
        """On the loop's thread, inside a ``fleet.slice`` stage: wait for
        the commit in flight (``fleet.commit_wait``: 0 where the commit hid
        whole behind the slice that trained beside it), raise what it
        raised, then join its slice's checkpoint save and drop the
        checkpoint (``fleet.checkpoint_wait``; multi-host: barrier, then
        process 0 deletes). Nothing in flight: nothing to do."""
        if self._in_flight is None:
            return
        (future, sl, ckpt_key), self._in_flight = self._in_flight, None
        with spans.stage("fleet.commit_wait", slice=sl):
            future.result()
        with spans.stage("fleet.checkpoint_wait"):
            self._checkpointer.finalize(ckpt_key)

    def drain(self) -> None:
        """The job's ending, whatever ends it: the commit in flight runs to
        its end and raises here what it raised; its checkpoint stays for the
        resume. No worker thread is left behind."""
        self._pool.shutdown(wait=True)
        if self._in_flight is not None:
            (future, _, _), self._in_flight = self._in_flight, None
            future.result()


def _commit_slice(
    seam: spans.SpanContext,
    books: _JobBooks,
    indexed_items: List[Tuple[int, dict]],
    result,
    shape: Tuple[int, int, int],
    provenance: Dict[str, Any],
) -> None:
    """The commit worker's side of the seam: every machine of a trained
    slice durable (:func:`_commit_machine`: artifact, then registry key and
    journal record), then the manifest's rewrite — ``fleet.commit_loop`` and
    ``fleet.manifest`` of the job's timeline, under the slice's stage (the
    context captured at hand-over).
    ``result`` is on the host; nothing here touches a device. Every input is
    an argument (the call runs on another thread: see
    :func:`_prepare_slice`)."""
    b, s = provenance["bucket"], provenance["slice"]
    with spans.bind(seam):
        with spans.stage("fleet.commit_loop"):
            for i, item in indexed_items:
                name = item["machine"].name
                with spans.stage("fleet.commit", machine=name) as commit:
                    if "build_error" in item:
                        # isolated at fetch: trained as zero-weight padding;
                        # no artifact, no registry key — the next run
                        # retries it from scratch
                        books.manifest[name] = {
                            "status": "failed",
                            "error": item["build_error"],
                            "bucket": b,
                            "slice": s,
                        }
                        books.journal.record(
                            name,
                            store_journal.EVENT_FAILED,
                            error=item["build_error"],
                        )
                        _M_FLEET_MACHINES.labels("failed").inc()
                        commit["outcome"] = "failed"
                        continue
                    model_dir = _commit_machine(
                        item, result, i, shape, provenance,
                        books.output_dir, books.model_register_dir,
                        books.precision_of(name), books.journal,
                    )
                    commit["bytes"] = sum(
                        leaf[i].nbytes for leaf in
                        jax.tree_util.tree_leaves(result)
                    )
                    books.journal_counts["rebuilt"] += 1
                    books.results[name] = model_dir
                    _M_FLEET_MACHINES.labels("completed").inc()
                    books.manifest[name] = {
                        "status": "completed",
                        "model_dir": model_dir,
                        "bucket": b,
                        "slice": s,
                    }
                    commit["outcome"] = "completed"
        with spans.stage("fleet.manifest"):
            _write_manifest(
                books.output_dir,
                books.manifest,
                [n for n in books.pending_names if n not in books.manifest],
                journal_counts=books.journal_counts,
            )


def _commit_machine(
    item: dict,
    result,
    i: int,
    shape: Tuple[int, int, int],
    provenance: Dict[str, Any],
    output_dir: str,
    model_register_dir: Optional[str],
    precision: str,
    journal,
) -> str:
    """Machine ``i`` of a trained slice → its artifact, durable: the model
    graph with the slice's result installed, metadata, WAL record, atomic
    generation commit, registry key. Returns the model dir."""
    machine = item["machine"]
    n_features, n_targets, n_splits = shape
    model = pipeline_from_definition(machine.model_config)
    _install_result(model, result, i, n_features, n_targets, n_splits)
    model_dir = os.path.join(output_dir, machine.name)
    # same metadata contract as the single-machine builder (consumers read
    # these keys uniformly off the shared registry); per-machine durations
    # are the slice's amortized share
    amortized = provenance["slice_duration_s"] / max(provenance["slice_size"], 1)
    metadata = {
        "name": machine.name,
        "gordo_components_tpu_version": __version__,
        "model": {
            "model_config": machine.model_config,
            "model_builder_metadata": (
                model.get_metadata() if hasattr(model, "get_metadata") else {}
            ),
            "cross_validation": _cv_metadata(result, i, n_splits),
            "model_training_duration_s": amortized,
            "model_creation_date": time.strftime("%Y-%m-%d %H:%M:%S%z"),
            "cache_key": item["cache_key"],
            "fleet": provenance,
        },
        "dataset": item["dataset_metadata"],
        "build_duration_s": amortized,
        "user_defined": dict(machine.metadata),
        # §19: the manifest pin the serving layers read
        "precision": precision,
    }
    # WAL first, then the atomic generation commit, then registry +
    # committed record: a crash at any point leaves either no trace (redo)
    # or a whole, verifiable artifact (skip) — never a torn dir a resume
    # would trust
    journal.record(
        machine.name,
        store_journal.EVENT_STARTED,
        cache_key=item["cache_key"],
        bucket=provenance["bucket"],
        slice=provenance["slice"],
    )
    commit_generation(
        model_dir,
        lambda staging: write_artifact_files(
            model, staging, metadata=metadata, precision=precision
        ),
        name=machine.name,
    )
    if model_register_dir:
        disk_registry.write_key(model_register_dir, item["cache_key"], model_dir)
    journal.record(
        machine.name,
        store_journal.EVENT_COMMITTED,
        cache_key=item["cache_key"],
        model_dir=model_dir,
    )
    return model_dir
