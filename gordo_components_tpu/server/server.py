"""REST model server.

Reference parity: ``gordo_components/server/server.py`` + ``views/``
[UNVERIFIED] — the per-model Flask app exposing:

- ``GET  /healthz``
- ``GET  /metadata``
- ``POST /prediction``
- ``POST /anomaly/prediction`` (anomaly models only; supports ``?start&end``
  server-side data fetch via the dataset config in build metadata)
- ``GET  /download-model`` (serialized model bytes)

plus the ingress path shape ``/gordo/v0/<project>/<machine>/<endpoint>``.

TPU redesign: where the reference runs ONE Flask app per model in its own
pod, this server hosts MANY machines' models in one process — models are
pure params + jitted apply fns, so a single TPU serves a whole fleet and
dispatch is just a dict lookup on the machine segment. Bare paths
(``/prediction``) work in single-model mode for drop-in parity. Flask is
replaced by a dependency-light werkzeug WSGI app (flask is not in this
image; werkzeug is its routing/WSGI core anyway).

Observability: request latencies and counts record into the process-wide
metrics registry (``observability.registry``), so ``GET /metrics`` serves
both the original JSON view (back-compat) and, with
``?format=prometheus``, the text exposition a scraper ingests — engine
compile/cache/dispatch series included, since every layer shares the one
registry. Each request adopts (or mints) an ``X-Gordo-Trace-Id``, echoes
it in the response, and binds it to the handler's context so every log
record emitted while serving the request — including engine dispatch
logs — carries the same id (SURVEY.md §6.5, grown into a real layer).

Resilience: serving a whole fleet from one process means one slow or
corrupt machine could take down every machine at once — so requests carry
deadlines (``X-Gordo-Deadline`` → 504 before the engine queues expired
work), a bounded admission gate sheds overload with 503 + ``Retry-After``
instead of convoying werkzeug threads, broken machines are QUARANTINED
per-machine (503 + probe-based recovery) while the fleet keeps serving,
and ``/healthz`` is tri-state (live/ready/degraded) naming the sick
machines. See ``resilience/`` and ARCHITECTURE.md §8.
"""

from __future__ import annotations

import json
import logging
import math
import os
import threading
import time
from typing import Any, Dict, List, Optional, Tuple, Union

import numpy as np
from werkzeug.exceptions import HTTPException, NotFound
from werkzeug.routing import Map, Rule
from werkzeug.wrappers import Request, Response

from .. import precision as precision_mod
from ..analysis import lockcheck
from ..autopilot import build_server_autopilot, disabled_snapshot
from ..models.anomaly.base import AnomalyDetectorBase
from ..observability import exposition, flightrec, spans, stitch, tracing
from ..observability import incidents as incidents_engine
from ..observability import ledger as ledger_engine
from ..observability import slo as slo_engine
from ..observability import telemetry as telemetry_engine
from ..observability.registry import REGISTRY
from ..resilience import deadline, faults, qos
from ..resilience.admission import (
    DRAINING_HEADER,
    AdmissionController,
    AdmissionRejected,
    QuotaExceeded,
)
from ..resilience.deadline import DeadlineExceeded
from ..resilience.quarantine import Quarantine
from ..serializer import dumps as serializer_dumps
from ..serializer import load, load_metadata
from ..store import generations as store_generations
from .. import wire
from .engine import ScoreResult, ServingEngine, SpillNotLiftable

logger = logging.getLogger(__name__)

_M_REQUEST_SECONDS = REGISTRY.histogram(
    "gordo_server_request_duration_seconds",
    "End-to-end HTTP request latency by endpoint",
    labels=("endpoint",),
)
_M_REQUESTS = REGISTRY.counter(
    "gordo_server_requests_total",
    "HTTP requests served, by endpoint and status code",
    labels=("endpoint", "status"),
)
# endpoints whose outcomes feed the per-tenant accounting counter (§25)
_SCORING_ENDPOINTS = ("prediction", "anomaly", "bulk-anomaly")

_M_WIRE_FORMAT = REGISTRY.counter(
    "gordo_server_wire_format_total",
    "Scoring responses by negotiated wire format (npz = binary "
    "application/x-gordo-npz, fast_json = the printf-rendered JSON "
    "fallback) — shows whether clients actually adopt the binary plane",
    labels=("format",),
)
_M_WARMUPS = REGISTRY.counter(
    "gordo_server_warmups_total",
    "Boot-time engine warm-ups by outcome (ok / error): an error boot "
    "still serves, and its first requests pay the compiles",
    labels=("outcome",),
)

_URL_MAP = Map(
    [
        Rule("/healthz", endpoint="healthz"),
        Rule("/metadata", endpoint="metadata"),
        Rule("/metrics", endpoint="metrics"),
        # host-RAM spill tier placement hint (§22): POST {"machines":
        # [...]} queues async host-cache loads for lazy machines
        Rule("/prefetch", endpoint="prefetch"),
        # layout plan application (§27): POST pins the committed plan's
        # residency set / cap / prefetch hints and records the plan
        # fingerprint this worker runs; GET reports it
        Rule("/layout", endpoint="layout"),
        Rule("/slo", endpoint="slo"),
        # fleet telemetry warehouse (§24): windowed rates / percentiles
        # from the durable history, traffic top-K, measured-cost ledger;
        # ?view=export renders the layout-input document
        Rule("/telemetry", endpoint="telemetry"),
        # fleet black box (§28): incident report index / one durable
        # report; ?view=ledger serves the raw control-ledger tail
        Rule("/incidents", endpoint="incidents"),
        Rule("/incidents/<incident_id>", endpoint="incident"),
        Rule("/models", endpoint="models"),
        Rule("/reload", endpoint="reload"),
        # closed-loop controller status + runtime kill switch (§20)
        Rule("/autopilot", endpoint="autopilot"),
        Rule("/autopilot/<action>", endpoint="autopilot-action"),
        # multi-tenant QoS (§25): declared tenant table, live bucket
        # levels, class watermarks at the current shed level
        Rule("/tenants", endpoint="tenants"),
        Rule("/prediction", endpoint="prediction"),
        Rule("/anomaly/prediction", endpoint="anomaly"),
        # bulk/offline scoring surface (§25): same anomaly scoring, but
        # the request is FORCED into the bulk priority class — its own
        # endpoint label keeps it outside the interactive latency SLO,
        # and large windows amortize through the engine's fused-batch
        # slicing + host-RAM spill tier like any lazy-fleet traffic
        Rule("/bulk/anomaly/prediction", endpoint="bulk-anomaly"),
        Rule("/download-model", endpoint="download-model"),
        # flight recorder: recent/slow/errored request timelines, and one
        # trace's full timeline (?format=chrome = Perfetto-loadable)
        Rule("/debug/requests", endpoint="debug-requests"),
        Rule("/debug/requests/<trace_id>", endpoint="debug-request"),
        Rule("/gordo/v0/<project>/<machine>/healthz", endpoint="healthz"),
        Rule("/gordo/v0/<project>/<machine>/metadata", endpoint="metadata"),
        Rule("/gordo/v0/<project>/<machine>/prediction", endpoint="prediction"),
        Rule(
            "/gordo/v0/<project>/<machine>/anomaly/prediction",
            endpoint="anomaly",
        ),
        Rule(
            "/gordo/v0/<project>/<machine>/bulk/anomaly/prediction",
            endpoint="bulk-anomaly",
        ),
        Rule(
            "/gordo/v0/<project>/<machine>/download-model",
            endpoint="download-model",
        ),
    ]
)


def _latency_view() -> Dict[str, Any]:
    """The original JSON ``/metrics`` latency block (count / p50_ms /
    p99_ms / mean_ms per endpoint), now read off the registry histogram
    that replaced the ad-hoc ``_Latency`` ring buffer — same shape, same
    bounded-window percentile semantics, one storage."""
    return {
        labelvalues[0]: {
            "count": stats["count"],
            "p50_ms": stats["p50"] * 1000,
            "p99_ms": stats["p99"] * 1000,
            "mean_ms": stats["mean"] * 1000,
        }
        for labelvalues, stats in _M_REQUEST_SECONDS.stats().items()
    }


class _Machine:
    def __init__(self, name: str, model_dir: str):
        # chaos seam: a `model-load:<name>:error` fault stands in for a
        # corrupt artifact dir without having to corrupt one on disk
        faults.inject("model-load", name)
        self.name = name
        self.model_dir = model_dir
        # mtime FIRST: if a rebuild lands between this stat and load(),
        # the stored mtime is older than the new artifacts and the next
        # reload refreshes — stat-after-load would pin the stale model
        self.mtime = _artifact_mtime(model_dir)
        # generation facet for /healthz and watchman: which gen-NNNN this
        # machine serves (None = flat pre-generation artifact). load()
        # below VERIFIES the manifest before deserializing, so a machine
        # that constructs at all is integrity-verified by definition —
        # torn/corrupt artifacts raise the store's typed errors and land
        # in quarantine instead
        self.generation = store_generations.current_generation(model_dir)
        self.model = load(model_dir)
        self.metadata = load_metadata(model_dir)
        # the precision ladder (§19): the artifact's manifest-pinned
        # precision, VALIDATED here — an unknown value raises, so the
        # machine quarantines instead of silently serving f32. int8
        # artifacts carry their quantized weights + scales as a
        # manifest-hashed sidecar; absent (e.g. hand-adopted artifact),
        # the engine quantizes on the fly with the identical formula.
        self.precision = precision_mod.of_metadata(self.metadata)
        self.quantized = None
        if self.precision == "int8":
            self.quantized = precision_mod.load_quantized(
                store_generations.resolve_artifact_dir(model_dir)
            )

    @property
    def tag_list(self) -> Optional[List[str]]:
        return self.metadata.get("dataset", {}).get("tag_list")

    @property
    def target_tag_list(self) -> Optional[List[str]]:
        return self.metadata.get("dataset", {}).get("target_tag_list")

    @property
    def target_columns(self) -> Optional[List[int]]:
        """Input-column index of each target tag, when the build metadata
        shows targets as a strict subset/permutation of input tags — how
        both scoring paths know which input columns a ``target_tag_list``
        machine's residuals compare against. ``None`` when targets equal
        inputs (the common reconstruction case) or can't be mapped."""
        tags, targets = self.tag_list, self.target_tag_list
        if not tags or not targets or targets == tags:
            return None
        try:
            return [tags.index(t) for t in targets]
        except ValueError:  # a target tag outside the inputs: unmappable
            return None


def scan_models_root(models_root: str) -> Dict[str, str]:
    """``{subdir_name: path}`` for every immediate subdir that passes the
    store's ``is_artifact_dir`` rule: a generation root (``CURRENT``
    pointer — the gen-NNNN layout) or a flat legacy dir
    (``definition.json``). The ONE scan rule, shared by CLI startup,
    ``/reload`` AND ``build_fleet_index`` (the predicate lives in the
    store layer) so none of the three can drift. Hidden dirs
    (``.staging-*`` crash debris, checkpoint dirs) never qualify."""
    import os

    seen: Dict[str, str] = {}
    for entry in sorted(os.listdir(models_root)):
        path = os.path.join(models_root, entry)
        if entry.startswith(".") or not os.path.isdir(path):
            continue
        if store_generations.is_artifact_dir(path):
            seen[entry] = path
    return seen


def _artifact_mtime(model_dir: str) -> float:
    """Newest mtime among the artifact files — the change signal reload
    uses to spot a rebuilt machine in the same directory."""
    import os

    newest = 0.0
    try:
        for entry in os.scandir(model_dir):
            if entry.is_file():
                newest = max(newest, entry.stat().st_mtime)
    except OSError:
        pass
    return newest


class _ServerState:
    """Everything a request needs, swapped as ONE reference on reload so a
    handler never sees machines and engine from different generations.

    Each request ``enter()``s the generation it snapshot and ``exit()``s
    when done; ``drain()`` lets a reload wait for the old generation's
    in-flight requests to finish BEFORE dropped machines (and their
    device-resident params) are released — without it, a reload racing a
    long request could free the very stacked tree that request is
    scoring against."""

    __slots__ = ("machines", "single", "engine", "lazy_names",
                 "_inflight", "_cond")

    def __init__(
        self,
        machines: Dict[str, _Machine],
        shard_fleet: bool = False,
        compile_cache=None,
        lazy_loaders: Optional[Dict[str, Any]] = None,
        mesh_shard: Optional[Tuple[int, int]] = None,
        mesh_remote: Optional[set] = None,
    ):
        self._inflight = 0
        self._cond = lockcheck.named_condition("server.state_cond")
        self.machines = machines
        # lazy fleet (§22): machines known from the FLEET_INDEX sidecar
        # but not materialized — the engine loads them through the
        # host-RAM spill tier on first touch
        lazy_loaders = lazy_loaders or {}
        self.lazy_names = frozenset(lazy_loaders)
        self.single = (
            next(iter(machines.values()))
            if len(machines) == 1 and not lazy_loaders
            else None
        )
        mesh = None
        if shard_fleet:
            # capacity mode: stacked params shard over every local device
            # (fleets whose weights exceed one chip's HBM) at the cost of
            # per-request gather hops — see engine._Bucket
            from ..parallel.mesh import fleet_mesh

            mesh = fleet_mesh()
        # stacked TPU scoring: machines sharing an architecture serve from
        # one device-resident pytree + one jitted program (engine.py);
        # anything the engine can't lift falls back to model.anomaly
        self.engine = ServingEngine(
            {name: machine.model for name, machine in machines.items()},
            target_cols={
                name: machine.target_columns
                for name, machine in machines.items()
            },
            # per-machine precision ladder (§19): the manifest-pinned
            # rung each machine serves at, plus any build-time int8
            # weights/scales loaded from its quant_int8.npz sidecar
            precisions={
                name: machine.precision
                for name, machine in machines.items()
            },
            quantized={
                name: machine.quantized
                for name, machine in machines.items()
                if machine.quantized is not None
            },
            mesh=mesh,
            # persistent compile cache: warmup (and every later program
            # build) loads AOT executables instead of compiling, so
            # adopting a generation — boot, /reload, rollback — is
            # O(load) against a warmed store (ARCHITECTURE §14)
            compile_cache=compile_cache,
            # host-RAM spill tier (§22): lazily-indexed machines load on
            # first touch through the byte-bounded host cache
            lazy=lazy_loaders,
            # multi-host mesh serving (§23): this process's (shard,
            # shards) identity — eager machines are the shard's owned
            # slice, and ``mesh_remote`` names the OTHER shards' machines
            # behind the spill fallback rung (owned-but-lazy machines
            # stay "owned" in the accounting)
            mesh_shard=mesh_shard,
            mesh_remote=mesh_remote,
        )
        if lazy_loaders:
            logger.info(
                "Lazy fleet boot: %d machine(s) eager, %d lazy behind "
                "the host-RAM spill tier (GORDO_HOST_CACHE_MB=%d)",
                len(machines), len(lazy_loaders),
                self.engine.host_cache_mb,
            )
        # cross-machine megabatching (ARCHITECTURE §15): env-resolved in
        # the engine (GORDO_MEGABATCH / GORDO_FILL_WINDOW_US /
        # GORDO_MEGABATCH_RESIDENCY); logged at boot so an operator can
        # tell from the log alone which dispatch mode a generation serves
        # with — the fill window bounds added latency under concurrency
        megabatch = self.engine.stats()["megabatch"]
        if megabatch["enabled"]:
            logger.info(
                "Cross-machine megabatching ON: fill window %d us, "
                "%d/%d machines resident in the stacked program(s)",
                megabatch["fill_window_us"],
                megabatch["resident_machines"],
                len(self.engine.machines()),
            )
        else:
            logger.info(
                "Cross-machine megabatching off (%s)",
                "shard mode" if shard_fleet else "disabled by config",
            )
        ladder = self.engine.stats()["precision"]["machines"]
        if set(ladder) - {"f32"}:
            # only mixed/downgraded fleets log the split — an all-f32
            # boot reads exactly as before the ladder existed
            logger.info(
                "Precision ladder: %s",
                ", ".join(f"{k}={v}" for k, v in sorted(ladder.items())),
            )

    def enter(self) -> None:
        with self._cond:
            lockcheck.assert_guard("server.state_cond")
            self._inflight += 1

    def exit(self) -> None:
        with self._cond:
            lockcheck.assert_guard("server.state_cond")
            self._inflight -= 1
            if self._inflight == 0:
                self._cond.notify_all()

    def drain(self, timeout: float) -> bool:
        """Wait until every request that entered this generation has
        exited (True), or ``timeout`` elapsed first (False)."""
        end = time.monotonic() + timeout
        with self._cond:
            while self._inflight > 0:
                left = end - time.monotonic()
                if left <= 0:
                    return False
                self._cond.wait(timeout=left)
        return True


class ModelServer:
    """WSGI app serving one or many built model dirs.

    ``model_dirs``: either a single dir (single-model mode: bare endpoint
    paths serve it) or ``{machine_name: dir}``.
    """

    def __init__(
        self,
        model_dirs: Union[str, Dict[str, str]],
        project: str = "project",
        models_root: Optional[str] = None,
        shard_fleet: bool = False,
        max_inflight: Optional[int] = None,
        quarantine_cooldown: float = 30.0,
        drain_timeout: float = 10.0,
        compile_cache_store: Optional[str] = None,
        worker_id: Optional[int] = None,
        lazy_boot: Optional[bool] = None,
        mesh_shards: Optional[int] = None,
        mesh_shard: Optional[int] = None,
    ):
        """``models_root``: optional directory whose immediate subdirs are
        model dirs; enables ``POST /reload`` so machines built AFTER server
        start (a fleet build appending to the same tree) become servable
        without a restart. ``shard_fleet``: shard every bucket's stacked
        params over all local devices (HBM capacity mode).

        ``max_inflight``: admission-gate bound on concurrently-scoring
        requests (default ``GORDO_MAX_INFLIGHT`` env or 64; see
        resilience.admission). ``quarantine_cooldown``: seconds a
        hard-failed machine waits before a recovery probe is allowed.
        ``drain_timeout``: how long a reload waits for the old
        generation's in-flight requests before releasing dropped models.

        ``compile_cache_store``: path of the persistent compile-cache
        root (AOT-serialized scoring executables; ``"off"`` disables).
        Default: the ``GORDO_COMPILE_CACHE_STORE`` env var, else — when
        a models_root is given — ``$JAX_COMPILATION_CACHE_DIR/serving-aot``
        or ``<models_root>/.compile-cache`` (``compile_cache.
        resolve_store``): the same root a fleet build exports into, so
        first boot is already warm. Single-dir servers without the env
        var run with the cache off (nothing anchors a sensible root).

        ``worker_id``: this process's slot in a horizontal fleet (see
        ``router/``). Default: the ``GORDO_WORKER_ID`` env var, else
        standalone. Workers stamp every response ``X-Gordo-Worker`` and
        report the id on ``/healthz`` so the router (and its smoke
        tests) can verify WHICH process answered.

        ``lazy_boot``: boot from ``models_root``'s ``FLEET_INDEX.json``
        sidecar (§22) — O(index read) instead of O(load the fleet); a
        small eager subset materializes, the rest serves through the
        host-RAM spill tier with artifact verification on first touch.
        Default: the ``GORDO_LAZY_BOOT`` env var, else off.

        ``mesh_shards`` / ``mesh_shard``: multi-host mesh serving (§23)
        — this process is shard ``mesh_shard`` of an
        ``mesh_shards``-process serving mesh. The deterministic shard
        plan (``parallel.shard_plan``) partitions the fleet's stacked
        machine axis by ring position: only the owned slice stacks
        eagerly; every other shard's machines stay reachable through the
        host-RAM spill tier (the fallback rung a dead shard degrades
        to). Defaults: ``GORDO_MESH_SHARDS`` / ``GORDO_MESH_SHARD``
        (shard falls back to ``worker_id mod shards``); 0/unset shards =
        single-host serving, exactly as before.
        """
        from ..compile_cache import resolve_store

        if worker_id is None:
            raw_worker = os.environ.get("GORDO_WORKER_ID")
            worker_id = int(raw_worker) if raw_worker else None
        self.worker_id = worker_id

        # multi-host mesh serving (§23): resolve this process's place in
        # the serving mesh. The plan itself is pure arithmetic over the
        # knob — router and every worker derive the identical layout.
        from ..parallel import shard_plan as shard_plan_mod

        if mesh_shards is None:
            mesh_shards = shard_plan_mod.mesh_shards_env()
        if mesh_shard is None:
            mesh_shard = shard_plan_mod.mesh_shard_env()
        self.mesh_shards = max(0, int(mesh_shards or 0))
        self.mesh_shard: Optional[int] = None
        self._mesh_plan = None
        # machines OTHER shards own (moved behind the spill tier by
        # _mesh_partition) — the engine's owned-vs-fallback accounting
        # boundary; empty when mesh serving is off or replicated
        self._mesh_remote: set = set()
        if (
            self.mesh_shards > 0
            and not isinstance(model_dirs, str)
            and models_root
        ):
            if mesh_shard is None and worker_id is not None:
                mesh_shard = shard_plan_mod.worker_shard(
                    worker_id, self.mesh_shards
                )
            if mesh_shard is None:
                logger.warning(
                    "GORDO_MESH_SHARDS=%d but neither GORDO_MESH_SHARD "
                    "nor a worker id names this process's shard; serving "
                    "single-host", self.mesh_shards,
                )
                self.mesh_shards = 0
            elif not 0 <= int(mesh_shard) < self.mesh_shards:
                logger.warning(
                    "GORDO_MESH_SHARD=%s outside the %d-shard mesh; "
                    "serving single-host", mesh_shard, self.mesh_shards,
                )
                self.mesh_shards = 0
            else:
                self.mesh_shard = int(mesh_shard)
                self._mesh_plan = shard_plan_mod.resolve_plan(
                    self.mesh_shards
                )
        elif self.mesh_shards > 0:
            # single-dir mode serves exactly one explicit model, and a
            # rootless boot (--model-dir only) registered EVERY machine
            # explicitly — registration overrides the layout, so there
            # is nothing to partition; demoting explicit machines behind
            # the spill tier would mislabel them as fallback traffic
            logger.warning(
                "Mesh serving needs --models-dir (a rescannable fleet "
                "root); explicitly-registered machines serve single-host"
            )
            self.mesh_shards = 0

        self.shard_fleet = shard_fleet
        self.compile_cache = resolve_store(
            explicit=compile_cache_store, models_root=models_root
        )
        if max_inflight is None:
            max_inflight = int(os.environ.get("GORDO_MAX_INFLIGHT", "64"))
        # multi-tenant QoS (§25): the declared tenant table (GORDO_TENANTS
        # / --tenants) — identity, priority classes, token-bucket quotas.
        # Undeclared deployments get the one default tenant and behave
        # exactly as before.
        self.tenants = qos.TenantTable.from_env()
        self.admission = AdmissionController(
            max_inflight=max_inflight,
            max_queue=int(os.environ.get("GORDO_MAX_QUEUE", "32")),
            queue_timeout=float(os.environ.get("GORDO_QUEUE_TIMEOUT", "1.0")),
            retry_after=1.0,
            tenants=self.tenants,
        )
        self.quarantine = Quarantine(cooldown=quarantine_cooldown)
        self.drain_timeout = drain_timeout
        # machines that failed to LOAD, by name -> dir: quarantined (not
        # served), retried on every /reload — the fleet analogue of the
        # reference's crash-looping pod that heals when its artifact is
        # rebuilt
        self._quarantined_dirs: Dict[str, str] = {}
        # lazy fleet boot (§22): with a FLEET_INDEX sidecar at
        # models_root, boot is O(read the index) — the index names the
        # fleet, a small eager subset (GORDO_BOOT_EAGER) materializes
        # now, and everything else loads through the host-RAM spill tier
        # on first touch, artifact verification included. Opt-in
        # (GORDO_LAZY_BOOT / --lazy-boot / lazy_boot=True): an eager boot
        # of a small fleet stays exactly as before.
        if lazy_boot is None:
            lazy_boot = os.environ.get(
                "GORDO_LAZY_BOOT", "0"
            ).strip().lower() in ("1", "true", "on", "yes")
        self.lazy_boot = bool(lazy_boot) and bool(models_root)
        lazy_dirs: Dict[str, str] = {}
        lazy_gens: Dict[str, Any] = {}
        if isinstance(model_dirs, str):
            # single-model mode: nothing to degrade to — a broken dir is
            # a startup error, exactly as before
            machine = _Machine("default", model_dirs)
            machine.name = machine.metadata.get("name", "default")
            machines = {machine.name: machine}
        else:
            model_dirs = dict(model_dirs)
            if self.lazy_boot:
                eager_dirs, lazy_dirs, lazy_gens = self._lazy_partition(
                    models_root
                )
                if eager_dirs is None:
                    # no (readable) index: fall back to the eager scan —
                    # the caller's resolved dirs, or a fresh scan when an
                    # index-driven boot passed none (a damaged index must
                    # never make a fleet unbootable)
                    self.lazy_boot = False
                    if not model_dirs:
                        model_dirs = scan_models_root(models_root)
                else:
                    for name, path in eager_dirs.items():
                        model_dirs.setdefault(name, path)
                    for name in model_dirs:
                        lazy_dirs.pop(name, None)
            # §23: machines other shards own never load here — they move
            # behind the spill tier (the fallback rung), loaders built
            # below like any lazy machine
            self._mesh_partition(
                model_dirs, lazy_dirs, lazy_gens, models_root
            )
            machines = {}
            for name, path in model_dirs.items():
                try:
                    machines[name] = _Machine(name, path)
                except Exception as exc:
                    # one corrupt artifact must not keep the whole fleet
                    # from serving: quarantine it, serve the rest
                    logger.exception("Failed to load machine %r", name)
                    self.quarantine.quarantine(
                        name, f"{type(exc).__name__}: {exc}", "load"
                    )
                    self._quarantined_dirs[name] = path
            if not machines and not lazy_dirs:
                raise ValueError(
                    "No machine loaded successfully; quarantined: "
                    f"{sorted(self._quarantined_dirs)}"
                )
        self.project = project
        self.models_root = models_root
        # the lazy half of the fleet: name -> model dir, re-read from the
        # index on reload; loaders are built fresh per state generation.
        # _lazy_gens remembers each lazy machine's index `generation` —
        # reload compares it against the fresh index and DROPS changed
        # machines from the host cache, so a rebuilt lazy artifact can
        # never keep serving its stale cached spill bundle (§22)
        self._lazy_dirs: Dict[str, str] = lazy_dirs
        self._lazy_gens: Dict[str, Any] = {
            name: lazy_gens.get(name) for name in lazy_dirs
        }
        # explicitly-registered machines survive every rescan, whatever
        # directory they live in (a reload must not drop --model-dir
        # machines that sit outside models_root, or rename ones registered
        # under their metadata name rather than their dir basename)
        self._pinned = dict(machines) if models_root else {}
        self._reload_lock = lockcheck.named_lock("server.reload")
        self._state = _ServerState(
            machines, shard_fleet=shard_fleet,
            compile_cache=self.compile_cache,
            lazy_loaders=self._lazy_loaders(),
            mesh_shard=self._mesh_tuple(),
            mesh_remote=set(self._mesh_remote),
        )
        # SLO engine (§18): declared objectives over the request
        # histograms this server already records, evaluated by
        # multi-window burn rate on the scrape path (/metrics and /slo
        # reads piggyback maybe_tick — no supervisor thread)
        self.slo = (
            slo_engine.SLOEvaluator(
                slo_engine.server_objectives()
                # per-class + per-declared-tenant burn rates over the
                # bounded tenant counter (§25)
                + slo_engine.tenant_objectives(self.tenants.specs())
            )
            if slo_engine.enabled()
            else None
        )
        # closed-loop autopilot (§20): observes the SLO engine + span
        # shares, tunes dispatch depth / fill window / admission /
        # residency through apply_tuning below. None under the hard kill
        # switch (GORDO_AUTOPILOT=0); constructed-but-frozen when unset.
        # Last-applied values survive reload generation swaps via
        # self._tuning.
        self._tuning: Dict[str, int] = {}
        # layout plan state (§27): the fingerprint + residency pins +
        # prefetch hints last applied via /layout. Survives reload swaps
        # the same way self._tuning does — a fresh generation re-pins
        # from here instead of reverting to pure LRU residency.
        self._layout: Dict[str, Any] = {}
        self.autopilot = build_server_autopilot(self)
        # fleet telemetry warehouse (§24): durable counter/gauge/histogram
        # history + traffic sketch + measured-cost ledger, snapshotted on
        # the scrape path (maybe_tick, no thread). The warehouse lives in
        # a dot-dir so the model rescan never mistakes it for an artifact.
        self.telemetry: Optional[telemetry_engine.TelemetryWarehouse] = None
        if telemetry_engine.enabled():
            warehouse_dir = os.environ.get("GORDO_TELEMETRY_DIR")
            if not warehouse_dir and models_root:
                warehouse_dir = os.path.join(
                    models_root,
                    ".telemetry",
                    f"worker-{worker_id if worker_id is not None else 0}",
                )
            self.telemetry = telemetry_engine.TelemetryWarehouse(
                directory=warehouse_dir or None,
                worker=(
                    str(worker_id) if worker_id is not None else ""
                ),
                cost_sampler=lambda: telemetry_engine.sample_costs(
                    self._state.engine, self.compile_cache
                ),
            )
        # fleet black box (§28): the shared control ledger every control
        # loop in this process emits into, durable next to the telemetry
        # warehouse, plus the breach-edge incident correlator
        ledger_dir = os.environ.get("GORDO_LEDGER_DIR")
        role_name = f"worker-{worker_id if worker_id is not None else 0}"
        if ledger_dir:
            # one GORDO_LEDGER_DIR serves the whole tier: each process
            # gets its own subtree (two writers in one segment dir would
            # interleave torn tails)
            ledger_dir = os.path.join(ledger_dir, role_name)
        elif models_root:
            ledger_dir = os.path.join(
                models_root, ".telemetry", f"ledger-{role_name}",
            )
        ledger_engine.configure(ledger_dir or None)
        self.incidents = incidents_engine.IncidentCorrelator(
            directory=(
                os.path.join(ledger_dir, "incidents") if ledger_dir
                else None
            ),
            warehouse=self.telemetry,
            layout_fingerprint=lambda: self._layout.get("fingerprint"),
            role=role_name,
        )
        if self.slo is not None:
            self.slo.breach_hook = self.incidents.on_breach
        # every record emitted while serving a request carries its trace id
        # (idempotent; composes with logsetup.configure_logging)
        tracing.install_log_record_factory()
        logger.info(
            "ModelServer serving %d model(s): %s",
            len(machines),
            sorted(machines),
        )

    # back-compat accessors (tests, metrics): always the CURRENT generation
    @property
    def machines(self) -> Dict[str, _Machine]:
        return self._state.machines

    @property
    def engine(self) -> ServingEngine:
        return self._state.engine

    @property
    def _single(self) -> Optional[_Machine]:
        return self._state.single

    def apply_tuning(
        self,
        dispatch_depth: Optional[int] = None,
        fill_window_us: Optional[int] = None,
        max_inflight: Optional[int] = None,
        megabatch_residency: Optional[int] = None,
        shed_level: Optional[int] = None,
    ) -> Dict[str, Any]:
        """The autopilot's actuation seam (§20): land new knob values on
        the LIVE serving state without a reload. Admission resizes under
        its own condition; engine values go through the engine's
        per-bucket setters. Applied values are remembered so a reload's
        fresh generation inherits them instead of re-reading the env."""
        applied: Dict[str, Any] = {}
        if max_inflight is not None:
            applied["max_inflight"] = self.admission.set_max_inflight(
                max_inflight
            )
            self._tuning["max_inflight"] = applied["max_inflight"]
        if shed_level is not None:
            # §25: the shed ladder — tightens ONLY the bulk class's
            # admission watermark; rung 0 = no shedding
            applied["shed_level"] = self.admission.set_shed_level(
                shed_level
            )
            self._tuning["shed_level"] = applied["shed_level"]
        engine_values = {
            "dispatch_depth": dispatch_depth,
            "fill_window_us": fill_window_us,
            "megabatch_residency": megabatch_residency,
        }
        engine_values = {
            key: value for key, value in engine_values.items()
            if value is not None
        }
        if engine_values:
            applied.update(self._state.engine.apply_tuning(**engine_values))
            for key, value in applied.items():
                if key != "max_inflight" and value is not None:
                    self._tuning[key] = value
        return applied

    def reload(self) -> Dict[str, Any]:
        """Rescan ``models_root`` and swap in the new fleet as ONE state
        reference: subdirs not yet served are loaded, vanished ones
        dropped, machines whose artifacts changed on disk re-loaded, and
        explicitly-registered (pinned) machines always kept. A directory
        that fails to load is SKIPPED and reported — one half-written
        artifact (a fleet build mid-write) must not abort the whole reload
        or unserve the healthy machines.

        Integrity gate: ``load()`` verifies the artifact's checksummed
        manifest before deserializing, so a reload REFUSES to adopt an
        unverified generation — the machine keeps serving its previous
        (verified) generation if it has one, else is quarantined with the
        typed store error (``ManifestMissing`` / ``ArtifactIncomplete`` /
        ``ArtifactCorrupt``) recorded for operators."""
        import os

        if not self.models_root:
            raise ValueError(
                "Server was not started with a models_root directory; "
                "reload has nothing to rescan"
            )
        with self._reload_lock:
            state = self._state
            new_lazy: Dict[str, str] = {}
            new_lazy_gens: Dict[str, Any] = {}
            if self.lazy_boot:
                eager_dirs, lazy_index, new_lazy_gens = (
                    self._lazy_partition(self.models_root)
                )
                if eager_dirs is None:
                    # the index vanished: this reload degrades to the full
                    # scan (and this server to an eager fleet) rather than
                    # failing — same never-unbootable rule as boot
                    logger.warning(
                        "Lazy reload: no readable FLEET_INDEX at %s; "
                        "degrading to a full scan", self.models_root,
                    )
                    self.lazy_boot = False
                    seen = scan_models_root(self.models_root)
                else:
                    # index-driven rescan, O(index + eager): machines
                    # already materialized stay eager (their mtime check
                    # below spots rebuilds); everything else stays behind
                    # the spill tier — first touch verifies
                    seen = {}
                    for name in state.machines:
                        if name in lazy_index:
                            seen[name] = lazy_index.pop(name)
                        elif name in eager_dirs:
                            seen[name] = eager_dirs.pop(name)
                    seen.update(eager_dirs)
                    new_lazy = lazy_index
            else:
                seen = scan_models_root(self.models_root)
            # §23: a rescan re-derives the SAME deterministic partition —
            # machines other shards own go back behind the spill tier
            # (their artifact mtime rides along as the staleness signal
            # that drops a rebuilt machine's cached spill bundle below)
            self._mesh_partition(
                seen, new_lazy, new_lazy_gens, self.models_root
            )
            pinned_paths = {
                os.path.realpath(m.model_dir) for m in self._pinned.values()
            }
            added, refreshed = [], []
            errors: Dict[str, str] = {}
            machines: Dict[str, _Machine] = {}
            for name, pinned in self._pinned.items():
                # pinned machines keep their NAME and DIR across rescans,
                # but not their bytes: a new generation (or rebuilt flat
                # artifact) in the same dir re-loads under the pinned name
                # — run-server --models-dir pins every startup machine, so
                # without this no CLI-started server would ever adopt a
                # fleet rebuild's generations. Same refusal rule as the
                # scan path: a torn rebuild keeps the old verified model.
                if name in self._mesh_remote and name in new_lazy:
                    # §23: the rescan's partition re-homed this in-root
                    # machine behind the spill tier (the fleet crossed
                    # the sharding threshold, or ownership moved on a
                    # reshard) — re-adding it eagerly would double-serve
                    # it and defeat the layout. Outside-root pins never
                    # enter the partition, so registration still wins.
                    continue
                current = state.machines.get(name, pinned)
                try:
                    if _artifact_mtime(current.model_dir) != current.mtime:
                        machines[name] = _Machine(name, current.model_dir)
                        refreshed.append(name)
                    else:
                        machines[name] = current
                except Exception as exc:
                    errors[name] = f"{type(exc).__name__}: {exc}"
                    machines[name] = current
            for name, path in seen.items():
                if os.path.realpath(path) in pinned_paths:
                    continue  # already served under its pinned name
                current = state.machines.get(name)
                try:
                    if current is None:
                        machines[name] = _Machine(name, path)
                        added.append(name)
                    elif (
                        current.model_dir != path
                        or _artifact_mtime(path) != current.mtime
                    ):
                        machines[name] = _Machine(name, path)
                        refreshed.append(name)
                    else:
                        machines[name] = current
                except Exception as exc:  # half-written or corrupt dir:
                    # keep the old generation if we have one, else skip
                    # AND quarantine — the machine exists but can't serve,
                    # which /healthz must say out loud
                    errors[name] = f"{type(exc).__name__}: {exc}"
                    if current is not None:
                        machines[name] = current
                    else:
                        self.quarantine.quarantine(name, errors[name], "load")
                        self._quarantined_dirs.setdefault(name, path)
            # retry load-quarantined machines living OUTSIDE models_root
            # (explicitly-registered dirs the scan can't see); in-root
            # dirs were already attempted by the scan above — retrying
            # them here would pay the load cost twice per reload
            for name, path in list(self._quarantined_dirs.items()):
                if name in machines or name in seen:
                    continue
                if not os.path.isdir(path):
                    # dir deleted = machine decommissioned: drop it the way
                    # a healthy vanished machine is dropped, else /healthz
                    # would report it degraded forever
                    self._quarantined_dirs.pop(name, None)
                    self.quarantine.recover(name)
                    continue
                try:
                    machines[name] = _Machine(name, path)
                    added.append(name)
                except Exception as exc:
                    errors[name] = f"{type(exc).__name__}: {exc}"
                    self.quarantine.quarantine(name, errors[name], "load")
            # a machine that (re)loaded in THIS generation is healthy by
            # construction: lift its quarantine and forget the failed dir
            for name in added + refreshed:
                self._quarantined_dirs.pop(name, None)
                self.quarantine.recover(name)
            removed = sorted(set(state.machines) - set(machines))
            # §22: lazy membership changes (index grew/shrank) also swap
            # the generation — names report in added/removed like eager
            # ones, total counts both halves of the fleet
            lazy_added = sorted(
                name for name in new_lazy
                if name not in state.lazy_names and name not in machines
            )
            lazy_removed = sorted(
                name for name in state.lazy_names
                if name not in new_lazy and name not in machines
            )
            added.extend(lazy_added)
            removed = sorted(set(removed) | set(lazy_removed))
            # §22 staleness: a lazy machine whose index `generation`
            # moved was REBUILT — its cached spill bundle (and parked
            # _Machine) hold the old generation's bytes. Dropping it
            # here makes the next touch pay the verified store path,
            # which resolves CURRENT fresh; O(index), no artifact I/O.
            # (The contract this rides: a fleet rebuild refreshes the
            # index — write_fleet_index — exactly like it bumps CURRENT.)
            lazy_refreshed = sorted(
                name for name in new_lazy
                if name in state.lazy_names
                and self._lazy_gens.get(name) != new_lazy_gens.get(name)
            )
            for name in lazy_refreshed:
                state.engine.host_cache.drop(name)
            self._lazy_gens = {
                name: new_lazy_gens.get(name) for name in new_lazy
            }
            if added or removed or refreshed:
                self._lazy_dirs = new_lazy
                # same compile cache as boot: the new generation's warm-up
                # below loads executables instead of compiling them, so a
                # reload (or a rollback adopted via reload) pays zero
                # fresh XLA compiles against a warmed store
                new_state = _ServerState(
                    machines, shard_fleet=self.shard_fleet,
                    compile_cache=self.compile_cache,
                    lazy_loaders=self._lazy_loaders(),
                    mesh_shard=self._mesh_tuple(),
                    mesh_remote=set(self._mesh_remote),
                )
                # warm new/changed bucket programs BEFORE publishing the
                # generation: the old state serves meanwhile, so no request
                # ever races the compile (the reload POST waits instead)
                self._warm_engine(new_state)
                # the autopilot's live-applied values survive the swap: a
                # fresh generation resolves knobs from env, which would
                # silently revert every adaptation on the next rollout
                engine_tuning = {
                    key: value for key, value in self._tuning.items()
                    if key != "max_inflight"
                }
                if engine_tuning:
                    new_state.engine.apply_tuning(**engine_tuning)
                # the applied layout plan survives the swap too (§27):
                # re-pin the declared resident set on the new engine —
                # machines gone from the new scan are reported by
                # pin_residency and simply skipped (plan degrades)
                if self._layout.get("resident"):
                    new_state.engine.pin_residency(
                        self._layout["resident"]
                    )
                self._state = new_state
                # drain the OLD generation before returning: dropped
                # machines' device-resident params must not be released
                # while a request is still scoring against them
                if not state.drain(self.drain_timeout):
                    logger.warning(
                        "Reload: old generation still has in-flight "
                        "requests after %.1fs drain; releasing anyway",
                        self.drain_timeout,
                    )
                # stop the old generation's collector threads (drains its
                # fetch queue first); without this every reload would leak
                # one idle thread per bucket until the weakref backstop
                # notices the bucket is gone
                state.engine.close()
                logger.info(
                    "Reload: +%d / -%d / refreshed %d -> %d machine(s)%s",
                    len(added),
                    len(removed),
                    len(refreshed),
                    len(machines),
                    f"; errors: {errors}" if errors else "",
                )
            return {
                "added": sorted(added),
                "removed": removed,
                # lazy generation moves report as refreshed too — they
                # changed what the next request serves, without costing
                # an engine swap (the host-cache drop is the refresh)
                "refreshed": sorted(set(refreshed) | set(lazy_refreshed)),
                "errors": errors,
                "total": len(machines) + len(new_lazy),
            }

    @staticmethod
    def _warm_engine(state: "_ServerState") -> None:
        try:
            state.engine.warmup()
        except Exception:  # warm-up is best-effort; scoring still compiles
            logger.error("Post-reload engine warm-up failed", exc_info=True)

    # -- multi-host mesh serving (§23) ----------------------------------------
    def _mesh_tuple(self) -> Optional[Tuple[int, int]]:
        """(shard, shards) when this server is one shard of a serving
        mesh, else None — the engine's accounting tag."""
        if self._mesh_plan is None or self.mesh_shard is None:
            return None
        return (self.mesh_shard, self.mesh_shards)

    def _mesh_partition(
        self,
        eager_dirs: Dict[str, str],
        lazy_dirs: Dict[str, str],
        lazy_gens: Dict[str, Any],
        models_root: Optional[str] = None,
    ) -> None:
        """Apply the shard plan to a resolved fleet: machines other
        shards own move from the eager set behind the host-RAM spill
        tier (the §23 fallback rung — still servable HERE if their
        owner dies, at spill cost instead of an error). The declared
        policy keeps small fleets replicated everywhere; the artifact
        mtime rides along as each moved machine's staleness signal so a
        reload drops rebuilt machines' cached spill bundles. Machines
        registered OUTSIDE ``models_root`` stay eager whatever shard
        owns them: explicit registration overrides the layout — a
        rescan cannot re-discover their dirs, so moving them behind the
        (rescan-rebuilt) lazy set would drop them on the first /reload.
        ``self._mesh_remote`` records the moved names — the engine's
        owned-vs-fallback accounting boundary."""
        self._mesh_remote = set()
        if self._mesh_plan is None or self.mesh_shard is None:
            return
        from ..parallel.shard_plan import POLICY_SHARDED

        fleet = sorted(set(eager_dirs) | set(lazy_dirs))
        if self._mesh_plan.policy(len(fleet)) != POLICY_SHARDED:
            logger.info(
                "Mesh serving: %d-machine fleet below the sharding "
                "threshold (%d) — replicated on every shard",
                len(fleet), self._mesh_plan.min_shard_machines,
            )
            return
        root_real = (
            os.path.realpath(models_root) + os.sep if models_root else None
        )
        moved = 0
        for name in sorted(eager_dirs):
            if self._mesh_plan.shard_of(name) == self.mesh_shard:
                continue
            path = eager_dirs[name]
            if root_real and not (
                os.path.realpath(path) + os.sep
            ).startswith(root_real):
                continue  # pinned outside the root: registration wins
            eager_dirs.pop(name)
            lazy_dirs[name] = path
            try:
                lazy_gens[name] = _artifact_mtime(path)
            except OSError:
                lazy_gens.setdefault(name, None)
            self._mesh_remote.add(name)
            moved += 1
        # lazy-registered machines other shards own (index boots) are
        # remote too — the accounting boundary is ownership, not tier
        self._mesh_remote.update(
            name for name in lazy_dirs
            if self._mesh_plan.shard_of(name) != self.mesh_shard
        )
        logger.info(
            "Mesh-sharded serving: shard %d/%d owns %d of %d machine(s); "
            "%d reachable via the spill fallback rung",
            self.mesh_shard, self.mesh_shards, len(eager_dirs),
            len(fleet), moved,
        )

    # -- lazy fleet boot + host-RAM spill tier (§22) --------------------------
    def _lazy_partition(self, models_root: str):
        """FLEET_INDEX-driven boot partition: ``(eager_dirs, lazy_dirs,
        lazy_gens)`` from the index sidecar, or ``(None, {}, {})`` when
        there is no readable index (callers fall back to the eager scan
        — a damaged or absent index must never make a fleet
        unbootable). The first ``GORDO_BOOT_EAGER`` machines (index
        order = sorted names) materialize now — they warm the common
        architecture's programs — and the rest serve lazily through the
        host-RAM spill tier, each artifact verified on its first touch
        instead of at boot. ``lazy_gens`` carries every index name's
        ``generation`` field — reload's O(index) staleness signal for
        the lazy half (eager machines get the mtime check instead)."""
        index = store_generations.read_fleet_index(models_root)
        if index is None:
            return None, {}, {}
        try:
            eager_n = int(os.environ.get("GORDO_BOOT_EAGER", "0"))
        except ValueError:
            eager_n = 0
        eager: Dict[str, str] = {}
        lazy: Dict[str, str] = {}
        gens: Dict[str, Any] = {}
        for name in sorted(index):
            entry = index[name] if isinstance(index[name], dict) else {}
            path = os.path.join(models_root, entry.get("path") or name)
            gens[name] = entry.get("generation")
            if len(eager) < eager_n:
                eager[name] = path
            else:
                lazy[name] = path
        return eager, lazy, gens

    def _lazy_loaders(self) -> Dict[str, Any]:
        """Fresh loader closures for the current lazy set — stateless, so
        each state generation gets its own dict (and its own host cache:
        a reload's new engine starts cold, which is exactly the staleness
        story — no dropped-generation bytes can be served)."""
        return {
            name: self._lazy_loader(name, path)
            for name, path in self._lazy_dirs.items()
        }

    @staticmethod
    def _lazy_loader(name: str, path: str):
        def load_lazy() -> Dict[str, Any]:
            # the store path the spill tier fronts: _Machine verifies the
            # manifest BEFORE deserializing (first-touch verification —
            # the lazy boot skipped it), then the engine lifts the model
            # into its host entry tree. The _Machine itself parks in the
            # bundle as opaque context so metadata endpoints serve
            # without a second deserialize; eviction drops both.
            machine = _Machine(name, path)
            nbytes = 0
            try:
                artifact = store_generations.resolve_artifact_dir(path)
                with os.scandir(artifact) as entries:
                    nbytes = sum(
                        e.stat().st_size for e in entries if e.is_file()
                    )
            except OSError:
                pass
            return {
                "model": machine.model,
                "target_cols": machine.target_columns,
                "precision": machine.precision,
                "quantized": machine.quantized,
                "context": machine,
                # footprint hint for host-only bundles (the engine
                # measures liftable ones off their stacked tree)
                "nbytes": nbytes,
            }

        return load_lazy

    def _materialize_lazy(self, name: str, state: _ServerState) -> _Machine:
        """A lazy machine's ``_Machine``, through the spill tier (host
        cache hit = free; miss = the verified store path). Load failures
        quarantine exactly like an eager boot failure would — with the
        same probe-based recovery, since the artifact may be rebuilt."""
        probing = False
        if self.quarantine.is_quarantined(name):
            if not self.quarantine.probe_allowed(name):
                self._abort_quarantined(name)
            probing = True
            logger.info("Quarantine recovery probe (lazy load) for %r", name)
        try:
            bundle = state.engine.spill_bundle(name)
        except HTTPException:
            raise
        except Exception as exc:
            logger.exception("Lazy materialization of %r failed", name)
            self.quarantine.quarantine(
                name, f"{type(exc).__name__}: {exc}", "load"
            )
            self._abort_quarantined(name)
        if probing:
            self.quarantine.recover(name)
            logger.info("Machine %r recovered from quarantine", name)
        return bundle["context"]

    def quiesce(self, drain_timeout: Optional[float] = None) -> bool:
        """Graceful-shutdown sequence (SIGTERM → here → exit): close the
        admission gate (new requests shed instantly, stamped with the
        draining marker so a router re-routes them), wait for every
        in-flight request to finish, then drain the engine's dispatch
        pipeline. After this returns, killing the process drops ZERO
        accepted requests. Returns False when the drain timed out (the
        process exits anyway; stragglers are logged)."""
        if drain_timeout is None:
            drain_timeout = self.drain_timeout
        self.admission.close("draining for shutdown")
        logger.info(
            "Draining: admission closed; waiting up to %.1fs for "
            "in-flight requests", drain_timeout,
        )
        state = self._state
        drained = state.drain(drain_timeout)
        if not drained:
            logger.warning(
                "Drain timed out after %.1fs with requests still in "
                "flight; shutting down anyway", drain_timeout,
            )
        try:
            state.engine.quiesce()
        except Exception:
            logger.warning("Engine quiesce failed during shutdown",
                           exc_info=True)
        logger.info("Drain complete (clean=%s)", drained)
        return drained

    # -- dispatch ------------------------------------------------------------
    def __call__(self, environ, start_response):
        request = Request(environ)
        started = time.perf_counter()
        # adopt the client's trace id or mint one; bound to this handler
        # thread's context for the whole request, so every log record down
        # through the engine carries it, and echoed in the response
        trace_id = request.headers.get(tracing.TRACE_HEADER) or tracing.new_trace_id()
        token = tracing.set_trace_id(trace_id)
        # the client's remaining patience rides the X-Gordo-Deadline header
        # (seconds); bound to this handler's context so every expensive
        # boundary below (admission queue, engine dispatch, data fetch)
        # can refuse work nobody is waiting for anymore
        budget = deadline.parse_header(
            request.headers.get(deadline.DEADLINE_HEADER)
        )
        deadline_token = (
            deadline.set_deadline(budget) if budget is not None else None
        )
        # tenant identity seam (§25): resolve X-Gordo-Tenant (name or
        # declared API key; absent/unknown → default tenant) and bind it
        # to this handler's context — the admission gate reads the class
        # watermark and quota bucket from it, the engine's fill window
        # reads the class at submit time
        tenant_spec = self.tenants.resolve(
            request.headers.get(qos.TENANT_HEADER)
        )
        qos_token = qos.set_current(tenant_spec)
        shed = False
        # per-request span timeline, bound to this handler's context; the
        # engine's leader/collector threads receive it via each item's
        # captured SpanContext (contextvars do not cross those threads)
        timeline = None
        timeline_token = None
        if flightrec.RECORDER.enabled:
            timeline, timeline_token = spans.begin(
                trace_id, method=request.method, path=request.path
            )
        adapter = _URL_MAP.bind_to_environ(environ)
        # ONE state snapshot per request: machines and engine must come from
        # the same generation even if a reload swaps mid-request
        state = self._state
        try:
            try:
                endpoint, args = adapter.match()
                response = self._dispatch(request, endpoint, args, state)
            except QuotaExceeded as exc:
                # quota, not overload: 429 tells THIS tenant to slow
                # down without claiming the fleet is hurting; the hint
                # is the bucket's actual refill time
                spans.event(
                    "quota_exceeded", tenant=exc.tenant,
                    retry_after=exc.retry_after,
                )
                response = _json(
                    {"error": f"quota exhausted: {exc}",
                     "tenant": exc.tenant},
                    status=429,
                )
                response.headers["Retry-After"] = _retry_after(exc.retry_after)
            except AdmissionRejected as exc:
                # load shed: tell the client WHEN to come back, not just
                # no — the hint derives from the gate's measured drain
                # rate, so backed-off clients converge on real capacity
                shed = True
                spans.event(
                    "admission_rejected", reason=str(exc),
                    retry_after=exc.retry_after,
                    tenant=tenant_spec.name,
                )
                response = _json({"error": f"overloaded: {exc}"}, status=503)
                response.headers["Retry-After"] = _retry_after(exc.retry_after)
            except DeadlineExceeded as exc:
                # Retry-After 1: the work itself is fine — the caller just
                # needs to come back with a fresh (or larger) budget
                response = _json(
                    {"error": str(exc)}, status=504,
                    headers={"Retry-After": _retry_after(1.0)},
                )
            except HTTPException as exc:
                if exc.response is not None:
                    response = exc.response
                else:
                    response = Response(
                        json.dumps({"error": exc.description}),
                        status=exc.code or 500,
                        mimetype="application/json",
                    )
                endpoint = "error"
            response.headers[tracing.TRACE_HEADER] = trace_id
            if self.worker_id is not None:
                # which fleet slot answered — the router's routing smoke
                # (and any operator curl) verifies placement with this
                response.headers["X-Gordo-Worker"] = str(self.worker_id)
            if self.mesh_shard is not None:
                # §23: which mesh shard answered — the owner in steady
                # state; a different shard than the plan's owner means
                # the spill fallback rung served this request
                response.headers["X-Gordo-Shard"] = str(self.mesh_shard)
            if self.admission.closed is not None:
                # draining marker on EVERYTHING this server still answers
                # (sheds and healthz alike): the router re-routes marked
                # 503s instead of erroring, and the control plane routes
                # around the drainer without ejecting it
                response.headers[DRAINING_HEADER] = "1"
            elapsed = time.perf_counter() - started
            _M_REQUEST_SECONDS.labels(endpoint).observe(elapsed)
            _M_REQUESTS.labels(endpoint, str(response.status_code)).inc()
            if endpoint in _SCORING_ENDPOINTS:
                # per-tenant accounting at the admission seam (§25):
                # tenant/class come from the closed table, outcome is a
                # closed enum — cardinality bounded by configuration
                status = response.status_code
                qos.note_request(
                    tenant_spec.name,
                    "bulk" if endpoint == "bulk-anomaly"
                    else tenant_spec.klass,
                    "quota" if status == 429
                    else "shed" if shed
                    else "ok" if status < 400
                    else "error",
                )
            if timeline is not None:
                status = response.status_code
                timeline.meta["endpoint"] = endpoint
                timeline.meta["tenant"] = tenant_spec.name
                if self.worker_id is not None:
                    timeline.meta["worker"] = self.worker_id
                if self.mesh_shard is not None:
                    # §23: the stitched router lane renders per-shard —
                    # the merge reads this off the remote timeline
                    timeline.meta["shard"] = self.mesh_shard
                timeline.finish(
                    status=str(status),
                    error=f"HTTP {status}" if status >= 500 else "",
                )
                # trace stitching (§18): ONLY when the caller negotiated
                # it (the router sends X-Gordo-Timeline: 1) — plain
                # clients never pay the header bytes. Past the size cap
                # the truncation marker tells the router to pull the
                # full timeline from /debug/requests/<trace_id> instead.
                if request.headers.get(stitch.TIMELINE_HEADER):
                    encoded, truncated = stitch.encode_timeline(timeline)
                    if encoded is not None:
                        response.headers[stitch.TIMELINE_HEADER] = encoded
                    else:
                        response.headers[
                            stitch.TIMELINE_TRUNCATED_HEADER
                        ] = str(truncated)
                # probe/scrape endpoints are excluded: a watchman polling
                # N machines would flush every scoring trace out of the
                # ring within one poll interval
                if endpoint not in (
                    "healthz", "metrics", "slo", "tenants",
                    "autopilot", "autopilot-action",
                    "debug-requests", "debug-request",
                ):
                    flightrec.RECORDER.record(timeline)
            # DEBUG for probe endpoints: a watchman polling N machines'
            # /healthz plus scrapers hitting /metrics would otherwise
            # double steady-state log volume (werkzeug's own access line
            # already covers them); real work logs at INFO with its trace
            logger.log(
                logging.DEBUG
                if endpoint in ("healthz", "metrics", "slo", "autopilot")
                else logging.INFO,
                "%s %s -> %d in %.1f ms [trace=%s]",
                request.method,
                request.path,
                response.status_code,
                elapsed * 1000,
                trace_id,
            )
        finally:
            if timeline_token is not None:
                spans.end(timeline_token)
            qos.reset(qos_token)
            if deadline_token is not None:
                deadline.reset(deadline_token)
            tracing.reset_trace_id(token)
        return response(environ, start_response)

    def _machine_for(self, args: Dict[str, Any], state: _ServerState) -> _Machine:
        name = args.get("machine")
        if name is None:
            if state.single is not None:
                return state.single
            raise NotFound(
                "Multiple models served; use "
                "/gordo/v0/<project>/<machine>/<endpoint>"
            )
        if args.get("project") not in (self.project, None):
            raise NotFound(f"Unknown project {args.get('project')!r}")
        try:
            return state.machines[name]
        except KeyError:
            if name in state.lazy_names:
                # spill tier (§22): known from the fleet index but not
                # materialized — first touch loads (and verifies) it
                # through the host cache
                return self._materialize_lazy(name, state)
            if self.quarantine.is_quarantined(name):
                # the machine EXISTS but failed to load: 503 (try later),
                # not 404 (never heard of it) — a watchman probing this
                # path must see a sick machine, not a vanished one
                self._abort_quarantined(name)
            raise NotFound(f"Unknown machine {name!r}") from None

    def _abort_quarantined(self, name: str) -> None:
        _abort(
            503,
            f"Machine {name!r} is quarantined: "
            f"{self.quarantine.last_error(name)}",
            headers={
                "Retry-After": _retry_after(self.quarantine.retry_after(name))
            },
        )

    def _dispatch(
        self, request: Request, endpoint: str, args, state: _ServerState
    ) -> Response:
        if endpoint == "healthz":
            if args.get("machine") is not None:
                # machine-scoped health: 404 if absent, 503 if quarantined
                name = args["machine"]
                if (
                    name in state.lazy_names
                    and name not in state.machines
                    and not self.quarantine.is_quarantined(name)
                ):
                    # spill tier (§22): a healthz probe must NOT force a
                    # store load (a watchman sweeping 100k machines would
                    # thrash the tier) — report off the host cache when
                    # the bundle is resident, else just "lazy"
                    bundle = state.engine.host_cache.peek(name)
                    if bundle is not None:
                        served = bundle["context"]
                        return _json(
                            {
                                "ok": True,
                                "status": "ok",
                                "lazy": True,
                                "resident": True,
                                "generation": served.generation,
                                "verified": True,
                                "precision": served.precision,
                            }
                        )
                    return _json(
                        {
                            "ok": True,
                            "status": "lazy",
                            "lazy": True,
                            "resident": False,
                            "generation": None,
                            # verified on first touch, not yet touched
                            "verified": None,
                            "precision": None,
                        }
                    )
                if self.quarantine.is_quarantined(name):
                    return _json(
                        {
                            "ok": False,
                            "status": "quarantined",
                            "error": self.quarantine.last_error(name),
                        },
                        status=503,
                        headers={
                            "Retry-After": _retry_after(
                                self.quarantine.retry_after(name)
                            )
                        },
                    )
                served = self._machine_for(args, state)
                # integrity facet: which generation serves, and that it
                # passed manifest verification at load (load() refuses
                # anything that doesn't — a served machine IS verified)
                return _json(
                    {
                        "ok": True,
                        "status": "ok",
                        "generation": served.generation,
                        "verified": True,
                        # §19: which rung of the precision ladder this
                        # machine's scores come from (manifest-pinned)
                        "precision": served.precision,
                    }
                )
            # fleet health is TRI-STATE: live (process answers), ready (at
            # least one machine servable), degraded (quarantined or
            # suspect machines named below) — k8s probes read live/ready,
            # operators read WHO is sick and why
            quarantined = self.quarantine.quarantined()
            suspects = self.quarantine.suspects()
            draining = self.admission.closed is not None
            ready = (
                len(state.machines) + len(state.lazy_names) > 0
                and not draining
            )
            degraded = bool(quarantined or suspects)
            return _json(
                {
                    "ok": ready and not degraded,
                    "status": (
                        "draining" if draining
                        else ("degraded" if degraded else "ok")
                    ),
                    "live": True,
                    "ready": ready,
                    "worker_id": self.worker_id,
                    # §23: this process's slice of the serving mesh —
                    # owned machines stack eagerly, the remainder serves
                    # via the spill fallback rung (null = single-host)
                    "mesh": (
                        {
                            "shard": self.mesh_shard,
                            "shards": self.mesh_shards,
                            "owned": len(state.machines),
                            "remote_or_lazy": len(state.lazy_names),
                        }
                        if self.mesh_shard is not None
                        else None
                    ),
                    "quarantined": quarantined,
                    "suspect": suspects,
                    # §27: the layout-plan fingerprint this worker has
                    # applied (null = no plan) — the reconciler's
                    # convergence signal for the layout class
                    "layout": self._layout.get("fingerprint"),
                    # artifact-integrity facet: every served machine passed
                    # manifest verification at load; dirs that DIDN'T are
                    # exactly the load-quarantined set above. generations
                    # name what would be rolled back by `gordo rollback`
                    "store": {
                        "verified": len(state.machines),
                        # §22: machines the index names that have not been
                        # touched (verification deferred to first touch)
                        "lazy": len(state.lazy_names),
                        "unverified": sorted(self._quarantined_dirs),
                        "generations": {
                            name: machine.generation
                            for name, machine in sorted(state.machines.items())
                        },
                        # §19: each machine's manifest-pinned precision —
                        # a mixed fleet is auditable from one curl
                        "precisions": {
                            name: machine.precision
                            for name, machine in sorted(state.machines.items())
                        },
                    },
                },
                status=200 if ready else 503,
            )
        if endpoint == "slo":
            if self.slo is None:
                return _json({"enabled": False})
            self.slo.maybe_tick()
            return _json(self.slo.snapshot(recorder=flightrec.RECORDER))
        if endpoint == "tenants":
            # §25: declared table + live bucket levels + top raw header
            # values, alongside the gate's class watermarks at the
            # current shed rung — one curl answers "who is declared,
            # who is spraying unknown names, who is being squeezed"
            snap = self.tenants.snapshot()
            snap["admission"] = self.admission.stats()
            return _json(snap)
        if endpoint == "telemetry":
            if self.telemetry is None:
                return _json({"enabled": False})
            # a telemetry read is also a snapshot tick (scrape-driven,
            # like /slo) — min-interval-gated inside maybe_tick
            self.telemetry.maybe_tick()
            # horizon forms accepted alongside bare seconds: ?window=1m
            # /10m/1h select the matching warehouse EWMA horizon (§27)
            window = telemetry_engine.parse_window(
                request.args.get("window")
            ) or 300.0
            view = self.telemetry.view(window=window)
            if request.args.get("view") == "export":
                return _json(
                    telemetry_engine.build_export(view, window=window)
                )
            return _json(view)
        if endpoint == "incidents":
            # §28: reading incidents is also an evaluation tick — a
            # breach that happened since the last scrape materializes
            # its report before this response renders
            if self.slo is not None:
                self.slo.maybe_tick()
            if request.args.get("view") == "ledger":
                window = telemetry_engine.parse_window(
                    request.args.get("window")
                )
                return _json({
                    "ledger": ledger_engine.LEDGER.snapshot(),
                    "events": ledger_engine.LEDGER.recent(
                        window=window,
                        limit=request.args.get("limit", type=int) or 200,
                    ),
                })
            return _json({
                "incidents": self.incidents.list(),
                "correlator": self.incidents.snapshot(),
            })
        if endpoint == "incident":
            report = self.incidents.get(str(args.get("incident_id")))
            if report is None:
                raise NotFound(
                    f"no incident {args.get('incident_id')!r} (rotated "
                    "out of GORDO_INCIDENT_KEEP, or never opened)"
                )
            return _json(report)
        if endpoint == "autopilot":
            if self.autopilot is None:
                return _json(disabled_snapshot())
            # a status read is also an evaluation tick (scrape-driven,
            # like /slo) — but the SLO engine must tick FIRST so the
            # burn rates the controller reads are fresh
            if self.slo is not None:
                self.slo.maybe_tick()
            self.autopilot.maybe_tick()
            return _json(self.autopilot.snapshot())
        if endpoint == "autopilot-action":
            return self._autopilot_action(request, args.get("action"))
        if endpoint == "metrics":
            # scrape-driven SLO evaluation: every scrape advances the
            # burn-rate windows (min-interval-gated), so gordo_slo_*
            # series below are fresh without a background thread
            if self.slo is not None:
                self.slo.maybe_tick()
            if self.autopilot is not None:
                self.autopilot.maybe_tick()
            if self.telemetry is not None:
                self.telemetry.maybe_tick()
            if request.args.get("format") == "prometheus":
                # &exemplars=1 opts into OpenMetrics-style exemplar
                # suffixes (gordo tooling / OpenMetrics ingesters); the
                # bare scrape stays strict v0.0.4 — the classic
                # Prometheus text parser rejects exemplar syntax
                return Response(
                    exposition.render_prometheus(
                        REGISTRY,
                        exemplars=request.args.get("exemplars")
                        in ("1", "true"),
                    ),
                    content_type=exposition.CONTENT_TYPE,
                )
            return _json(
                {
                    "latency": _latency_view(),
                    "engine": state.engine.stats(),
                    # gate occupancy + who is sick, for operators reading
                    # the JSON view (the prometheus twin carries the same
                    # as gordo_resilience_* series)
                    "resilience": {
                        "admission": self.admission.stats(),
                        "quarantined": self.quarantine.quarantined(),
                        "suspect": self.quarantine.suspects(),
                    },
                    # the full registry (engine, client, build series too):
                    # the JSON twin of ?format=prometheus
                    "registry": REGISTRY.snapshot(),
                }
            )
        if endpoint == "debug-requests":
            limit = request.args.get("limit", type=int)
            return _json(
                flightrec.RECORDER.summaries(limit=limit if limit else 50)
            )
        if endpoint == "debug-request":
            recorded = flightrec.RECORDER.get(args["trace_id"])
            if recorded is None:
                raise NotFound(
                    f"no recorded timeline for trace {args['trace_id']!r} "
                    "(rotated out of the flight recorder, or never seen)"
                )
            if request.args.get("format") == "chrome":
                return _json(recorded.to_chrome_trace())
            return _json(recorded.to_dict())
        if endpoint == "models":
            return _json(
                {
                    "project": self.project,
                    "models": sorted(
                        set(state.machines) | state.lazy_names
                    ),
                }
            )
        if endpoint == "prefetch":
            # placement hint (§22): queue async host-cache loads for lazy
            # machines the caller expects to land here. Advisory — the
            # response says what was queued, nothing blocks on the loads.
            if request.method != "POST":
                _abort(405, "POST required")
            try:
                payload = json.loads(request.get_data(as_text=True) or "{}")
            except json.JSONDecodeError:
                _abort(400, "Request body is not valid JSON")
            names = payload.get("machines")
            if not isinstance(names, list):
                _abort(400, 'Payload must contain "machines": [...]')
            return _json(state.engine.prefetch([str(n) for n in names]))
        if endpoint == "layout":
            # layout plan application seam (§27): the reconciler (or an
            # operator curl) lands this worker's slice of the committed
            # plan here — residency pins + optional cap + spill prefetch
            # hints — and the fingerprint recorded is what /healthz
            # reports back for convergence checks. POST {"clear": true}
            # reverts to pure LRU residency (rollback's direction).
            if request.method != "POST":
                return _json({
                    "fingerprint": self._layout.get("fingerprint"),
                    "resident": list(self._layout.get("resident") or ()),
                    "cap": self._layout.get("cap"),
                    "applied": self._layout.get("applied"),
                })
            try:
                payload = json.loads(request.get_data(as_text=True) or "{}")
            except json.JSONDecodeError:
                _abort(400, "Request body is not valid JSON")
            if payload.get("clear"):
                cleared = state.engine.pin_residency(())
                previous = self._layout.get("fingerprint")
                self._layout = {}
                # §28: plan reverts are control events too (rollback's
                # direction reads as clear-plan in the ledger)
                ledger_engine.emit(
                    actor="layout", action="clear-plan", target="worker",
                    before=previous,
                )
                return _json({"cleared": True, "residency": cleared})
            fingerprint = payload.get("fingerprint")
            if not isinstance(fingerprint, str) or not fingerprint:
                _abort(400, 'Payload must carry the plan "fingerprint"')
            resident = payload.get("resident") or []
            if not isinstance(resident, list):
                _abort(400, '"resident" must be a list of machine names')
            resident = [str(name) for name in resident]
            applied: Dict[str, Any] = {
                "residency": state.engine.pin_residency(resident),
            }
            cap = payload.get("cap")
            if cap is not None:
                applied["tuning"] = self.apply_tuning(
                    megabatch_residency=int(cap)
                )
            hints = payload.get("prefetch") or []
            if isinstance(hints, list) and hints:
                applied["prefetch"] = state.engine.prefetch(
                    [str(name) for name in hints]
                )
            previous = self._layout.get("fingerprint")
            self._layout = {
                "fingerprint": fingerprint,
                "resident": resident,
                "cap": int(cap) if cap is not None else None,
                "applied": applied,
            }
            ledger_engine.emit(
                actor="layout", action="apply-plan", target="worker",
                before=previous, after=fingerprint,
                reason=f"{len(resident)} pin(s), cap {cap}",
            )
            return _json({"fingerprint": fingerprint, "applied": applied})
        if endpoint == "reload":
            if request.method != "POST":
                _abort(405, "POST required")
            try:
                return _json(self.reload())
            except ValueError as exc:
                _abort(422, str(exc))
        machine = self._machine_for(args, state)
        if endpoint == "metadata":
            return _json({"name": machine.name, "metadata": machine.metadata})
        if endpoint == "download-model":
            return Response(
                serializer_dumps(machine.model),
                mimetype="application/octet-stream",
            )
        if endpoint in _SCORING_ENDPOINTS:
            # pin THIS generation while scoring: a concurrent reload
            # drains these before releasing dropped machines' params
            state.enter()
            try:
                return self._score_endpoint(request, endpoint, machine, state)
            finally:
                state.exit()
        raise NotFound(endpoint)

    def _autopilot_action(
        self, request: Request, action: Optional[str]
    ) -> Response:
        """``POST /autopilot/enable|disable`` — the runtime kill switch
        (``gordo autopilot enable|disable``). Under the HARD kill switch
        there is no controller to act on: 409."""
        if request.method != "POST":
            _abort(405, "POST required")
        if self.autopilot is None:
            return _json(
                {
                    **disabled_snapshot(),
                    "error": "hard kill switch active; runtime enable "
                             "is not possible",
                },
                status=409,
            )
        if action == "enable":
            self.autopilot.enable()
        elif action == "disable":
            self.autopilot.disable(reason="operator via /autopilot/disable")
        else:
            _abort(404, f"unknown autopilot action {action!r} "
                        "(enable | disable)")
        return _json(self.autopilot.snapshot())

    def _score_endpoint(
        self, request: Request, endpoint: str, machine: _Machine,
        state: _ServerState,
    ) -> Response:
        """Common resilience wrapper for the scoring endpoints: quarantine
        gate (with probe-based recovery), then the bounded admission gate,
        then the handler. Success clears the machine's health marks."""
        name = machine.name
        probing = False
        if self.quarantine.is_quarantined(name):
            if not self.quarantine.probe_allowed(name):
                self._abort_quarantined(name)
            # cooldown elapsed: this request is the recovery probe
            probing = True
            logger.info("Quarantine recovery probe for machine %r", name)
        # §25: the bulk surface forces the bulk priority class whatever
        # class the tenant declared — the quota identity (and bucket)
        # stays the tenant's own. Rebound here, not in __call__, so the
        # engine's fill window reads "bulk" at submit time too.
        bulk_token = None
        if endpoint == "bulk-anomaly":
            spec = qos.current() or self.tenants.default
            if spec.klass != "bulk":
                bulk_token = qos.set_current(qos.as_class(spec, "bulk"))
        try:
            # the admit() call itself is the gate wait (it returns the
            # release handle): staged so a queued request's timeline shows
            # WHERE the pre-engine time went
            with spans.stage("admission"):
                admitted = self.admission.admit()
            with admitted:
                if endpoint == "prediction":
                    response = self._predict(request, machine, state)
                else:
                    # anomaly and bulk-anomaly share the scoring path;
                    # they differ only in class and SLO accounting
                    response = self._anomaly(request, machine, state)
        except (AdmissionRejected, DeadlineExceeded):
            if probing:  # the model was never exercised: don't burn the
                # one-per-cooldown probe on a shed or an expired caller
                self.quarantine.release_probe(name)
            raise
        except HTTPException as exc:
            if (
                probing
                and exc.response is not None
                and exc.response.status_code < 500
            ):
                # client error (bad payload, 400): proves nothing about the
                # machine either way — leave the probe window open so a
                # well-formed request can still recover it immediately
                self.quarantine.release_probe(name)
            raise
        finally:
            if bulk_token is not None:
                qos.reset(bulk_token)
        if probing:
            self.quarantine.recover(name)
            logger.info("Machine %r recovered from quarantine", name)
        else:
            self.quarantine.clear_suspect(name)
        return response

    # -- payload handling ----------------------------------------------------
    _PARQUET_TYPES = (
        "application/octet-stream",
        "application/x-parquet",
        "application/vnd.apache.parquet",
    )

    def _parse_X(self, request: Request, machine: _Machine):
        """Request body → ``(array, timestamps-or-None)``. JSON ``{"X": …}``
        (records or nested lists) and parquet uploads (reference parity:
        ``server/views/base.py`` parquet payloads [UNVERIFIED]) are both
        accepted; a parquet DatetimeIndex flows into the response."""
        if request.method != "POST":
            raise HTTPException(
                response=Response(
                    json.dumps({"error": "POST required"}),
                    status=405,
                    mimetype="application/json",
                )
            )
        content_type = (request.content_type or "").split(";")[0].strip()
        if content_type in self._PARQUET_TYPES:
            # generic octet-stream only routes to parquet when the body
            # really is parquet (PAR1 magic) — clients that POST JSON under
            # that content type keep working
            if (
                content_type != "application/octet-stream"
                or request.get_data()[:4] == b"PAR1"
            ):
                return self._parse_parquet(request, machine)
        try:
            payload = json.loads(request.get_data(as_text=True) or "{}")
        except json.JSONDecodeError:
            _abort(400, "Request body is not valid JSON")
        X = payload.get("X")
        if X is None:
            _abort(400, 'Payload must contain "X"')
        if isinstance(X, list) and X and isinstance(X[0], dict):
            # list-of-records: column order from the build's tag list
            tags = machine.tag_list or sorted(X[0])
            try:
                X = [[row[tag] for tag in tags] for row in X]
            except KeyError as exc:
                _abort(400, f"Record missing tag {exc.args[0]!r}")
        try:
            arr = np.asarray(X, dtype=np.float32)
        except (ValueError, TypeError):
            _abort(400, '"X" must be a rectangular numeric array')
        if arr.ndim == 1:
            arr = arr[None, :]
        if arr.ndim != 2:
            _abort(400, f'"X" must be 2-D, got shape {list(arr.shape)}')
        return arr, None

    def _parse_parquet(self, request: Request, machine: _Machine):
        import io

        try:
            import pandas as pd

            frame = pd.read_parquet(io.BytesIO(request.get_data()))
        except Exception as exc:
            _abort(400, f"Request body is not a readable parquet table: {exc}")
        # same column-order rule as the JSON records path: build tag list,
        # else sorted columns — never the client's raw file order
        tags = machine.tag_list or sorted(frame.columns)
        missing = [t for t in tags if t not in frame.columns]
        if missing:
            _abort(400, f"Parquet payload missing tag columns {missing}")
        frame = frame[tags]
        try:
            arr = np.asarray(frame.values, dtype=np.float32)
        except (ValueError, TypeError):
            _abort(400, "Parquet payload must be all-numeric")
        timestamps = None
        if isinstance(frame.index, pd.DatetimeIndex):
            timestamps = [ts.isoformat() for ts in frame.index]
        return arr, timestamps

    def _predict(
        self, request: Request, machine: _Machine, state: _ServerState
    ) -> Response:
        X, _ = self._parse_X(request, machine)
        self._validate_X(X, machine)

        def run():
            with spans.stage("score", machine=machine.name):
                if state.engine.can_score(machine.name):
                    try:
                        return state.engine.predict(machine.name, X)
                    except SpillNotLiftable:
                        pass  # §22: host path, as an eager boot would
                deadline.check("server.predict")
                return machine.model.predict(X)

        output = self._guarded(machine, run, "Prediction failed")
        return self._scored_response(
            request,
            {"model-input": X, "model-output": np.asarray(output)},
        )

    def _anomaly(
        self, request: Request, machine: _Machine, state: _ServerState
    ) -> Response:
        model = machine.model
        if not isinstance(model, AnomalyDetectorBase):
            _abort(
                422,
                f"Model for machine {machine.name!r} is not an anomaly "
                "detector; use /prediction",
            )
        start = request.args.get("start")
        end = request.args.get("end")
        timestamps: Optional[List[str]] = None
        if start or end:
            X_frame = self._fetch_range(machine, start, end)
            timestamps_all = [ts.isoformat() for ts in X_frame.index]
            scored = self._score_guarded(machine, X_frame, state)
            timestamps = timestamps_all[
                len(timestamps_all) - len(scored.total_anomaly_score) :
            ]
        else:
            X, timestamps_all = self._parse_X(request, machine)
            self._validate_X(X, machine)
            scored = self._score_guarded(machine, X, state)
            if timestamps_all is not None:  # parquet DatetimeIndex
                timestamps = timestamps_all[
                    len(timestamps_all) - len(scored.total_anomaly_score) :
                ]
        arrays = {
            "model-input": scored.model_input,
            "model-output": scored.model_output,
            "tag-anomaly-scores": scored.tag_anomaly_scores,
            "total-anomaly-score": scored.total_anomaly_score,
        }
        thresholds = {}
        if getattr(model, "tag_thresholds_", None) is not None:
            thresholds = {
                "tag-thresholds": [float(v) for v in model.tag_thresholds_],
                "total-threshold": model.total_threshold_,
            }
        return self._scored_response(
            request, arrays, timestamps=timestamps, extras=thresholds
        )

    @staticmethod
    def _scored_response(
        request: Request,
        arrays: Dict[str, Any],
        timestamps: Optional[List[str]] = None,
        extras: Optional[Dict[str, Any]] = None,
    ) -> Response:
        """Scoring response with negotiated wire format: clients whose
        ``Accept`` lists ``application/x-gordo-npz`` get ONE binary blob
        (the arrays at native float32 + a JSON header); everyone else gets
        the schema-identical JSON body through the fast printf encoder —
        either way, no per-element ``.tolist()`` churn on the hot path
        (docs/ARCHITECTURE.md §12)."""
        arrays = {
            name: np.asarray(getattr(arr, "values", arr))
            for name, arr in arrays.items()
        }
        if wire.wants_npz(request.headers.get("Accept")):
            header = dict(extras or {})
            if timestamps is not None:
                header["timestamps"] = timestamps
            _M_WIRE_FORMAT.labels("npz").inc()
            with spans.stage("encode", format="npz"):
                body = wire.encode_npz(arrays, header)
            return Response(body, mimetype=wire.NPZ_CONTENT_TYPE)
        _M_WIRE_FORMAT.labels("fast_json").inc()
        with spans.stage("encode", format="fast_json"):
            body = wire.encode_scored_json(arrays, timestamps, extras)
        return Response(body, mimetype="application/json")

    def _score_guarded(self, machine: _Machine, X, state: _ServerState):
        return self._guarded(
            machine,
            lambda: self._score(machine, X, state),
            "Anomaly scoring failed",
        )

    def _guarded(self, machine: _Machine, fn, error_prefix: str):
        """ONE failure taxonomy for every scoring callable: bad input →
        400 (permanently-bad, e.g. too few rows for the lookback window —
        must be 4xx, not a retryable 500), expired deadline → 504 with the
        machine marked suspect, anything else → quarantine the machine and
        503 — never a bare 500 from inside a jitted program."""
        try:
            return fn()
        except ValueError as exc:
            _abort(400, f"{error_prefix}: {exc}")
        except DeadlineExceeded:
            # repeatedly missing its deadline makes a machine SUSPECT
            # (healthz names it) without refusing its future requests
            self.quarantine.mark_suspect(
                machine.name, "deadline expired at dispatch"
            )
            raise
        except HTTPException:
            raise
        except Exception as exc:
            self._quarantine_scoring_failure(machine, exc)

    def _quarantine_scoring_failure(self, machine: _Machine, exc: Exception):
        """An unexpected scoring exception (not a client error): isolate
        THIS machine — the rest of the fleet keeps serving — and answer
        503 with the recovery-probe horizon."""
        logger.exception("Scoring failed for machine %r; quarantining",
                         machine.name)
        self.quarantine.quarantine(
            machine.name, f"{type(exc).__name__}: {exc}", "score"
        )
        self._abort_quarantined(machine.name)

    @staticmethod
    def _validate_X(arr: np.ndarray, machine: _Machine) -> None:
        """Pre-dispatch payload validation: wrong width and non-finite
        values answer a STRUCTURED 400 naming the offending columns —
        never a 500 (or NaN scores) from inside a jitted program."""
        tags = machine.tag_list
        if tags and arr.shape[1] != len(tags):
            _abort(
                400,
                f"Machine {machine.name!r} expects {len(tags)} features, "
                f"got {arr.shape[1]}",
                expected_features=len(tags),
                got_features=int(arr.shape[1]),
            )
        finite = np.isfinite(arr)
        if not finite.all():
            bad = sorted(int(c) for c in np.unique(np.where(~finite)[1]))
            _abort(
                400,
                "Payload contains non-finite (NaN/Inf) values in "
                f"column(s) {bad}",
                non_finite_columns=bad,
            )

    def _score(self, machine: _Machine, X, state: _ServerState):
        """Anomaly arrays via the stacked TPU engine when the machine is
        lifted into it, else the host path (``model.anomaly``). Either way
        the whole call is the timeline's ``score`` stage (its engine
        children — queue_wait/dispatch/device_execute/fetch — nest inside
        it; a host-path machine shows a flat score span)."""
        with spans.stage("score", machine=machine.name):
            if state.engine.can_score(machine.name):
                try:
                    return state.engine.anomaly(machine.name, X)
                except SpillNotLiftable:
                    # lazy machine the engine can't lift (§22): score it
                    # through the same host path an eager boot would use
                    pass
            # host path: the engine's own pre-dispatch deadline check
            # doesn't cover these machines, so gate here before the slow
            # scoring
            deadline.check("server.anomaly_host")
            cols = machine.target_columns
            if cols is None:
                frame = machine.model.anomaly(X)
            elif hasattr(X, "iloc"):  # DataFrame from ?start&end fetch
                frame = machine.model.anomaly(X, y=X.iloc[:, cols])
            else:
                frame = machine.model.anomaly(X, y=np.asarray(X)[:, cols])
        return ScoreResult(
            model_input=frame["model-input"].values,
            model_output=frame["model-output"].values,
            tag_anomaly_scores=frame["tag-anomaly-scores"].values,
            total_anomaly_score=np.ravel(frame["total-anomaly-score"].values),
        )

    def _fetch_range(self, machine: _Machine, start, end):
        """?start&end server-side fetch: rebuild the dataset from the config
        embedded in build metadata with overridden dates. Deadline-checked
        BEFORE the provider round-trip: a lake read for an expired request
        is pure waste."""
        from ..dataset import GordoBaseDataset

        deadline.check("server.data_fetch")

        config = machine.metadata.get("dataset", {}).get("dataset_config")
        if not config:
            _abort(
                422,
                "Build metadata carries no dataset_config; "
                "POST data explicitly instead of using ?start&end",
            )
        if not (start and end):
            _abort(400, "Both ?start and ?end are required")
        config = dict(config)
        config["train_start_date"] = start
        config["train_end_date"] = end
        try:
            with spans.stage("data_fetch", machine=machine.name):
                faults.inject("data-fetch", machine.name)  # chaos: dead lake
                dataset = GordoBaseDataset.from_dict(config)
                X, _ = dataset.get_data()
        except Exception as exc:  # provider/parse errors → client error
            _abort(400, f"Data fetch failed: {exc}")
        return X


def _json(
    payload: Dict[str, Any],
    status: int = 200,
    headers: Optional[Dict[str, str]] = None,
) -> Response:
    response = Response(
        json.dumps(payload, default=str),
        status=status,
        mimetype="application/json",
    )
    for key, value in (headers or {}).items():
        response.headers[key] = value
    return response


def _retry_after(seconds: float) -> str:
    """HTTP ``Retry-After`` wants integer seconds; never advertise 0 (a
    zero invites an instant retry storm)."""
    return str(max(1, int(math.ceil(seconds))))


def _abort(
    code: int,
    message: str,
    headers: Optional[Dict[str, str]] = None,
    **extra: Any,
) -> None:
    """Raise an HTTP error with a JSON body; ``extra`` fields ride along
    (structured 400s name offending columns, 503s carry quarantine
    context) so clients can react programmatically, not by parsing prose."""
    raise HTTPException(
        response=_json(
            {"error": message, **extra}, status=code, headers=headers
        )
    )


def build_app(
    model_dirs: Union[str, Dict[str, str]],
    project: str = "project",
    models_root: Optional[str] = None,
    shard_fleet: bool = False,
    max_inflight: Optional[int] = None,
    quarantine_cooldown: float = 30.0,
    compile_cache_store: Optional[str] = None,
    worker_id: Optional[int] = None,
    lazy_boot: Optional[bool] = None,
    mesh_shards: Optional[int] = None,
    mesh_shard: Optional[int] = None,
) -> ModelServer:
    """App factory (reference: ``server.build_app``)."""
    return ModelServer(
        model_dirs, project=project, models_root=models_root,
        shard_fleet=shard_fleet, max_inflight=max_inflight,
        quarantine_cooldown=quarantine_cooldown,
        compile_cache_store=compile_cache_store,
        worker_id=worker_id,
        lazy_boot=lazy_boot,
        mesh_shards=mesh_shards,
        mesh_shard=mesh_shard,
    )


def run_server(
    model_dirs: Union[str, Dict[str, str]],
    host: str = "0.0.0.0",
    port: int = 5555,
    project: str = "project",
    models_root: Optional[str] = None,
    shard_fleet: bool = False,
    trace_dir: Optional[str] = None,
    max_inflight: Optional[int] = None,
    compile_cache_store: Optional[str] = None,
    worker_id: Optional[int] = None,
    lazy_boot: Optional[bool] = None,
) -> None:
    """Serve with werkzeug's multithreaded server.

    Production story: the reference fronted each per-model Flask app with
    gunicorn workers (SURVEY.md §4.2). Here the app is a plain WSGI callable
    (``build_app``), so any WSGI server works — ``gunicorn -w 1 --threads N
    "module:build_app(...)"`` is the intended deployment shape. One *process*
    per TPU: the serving engine owns device-resident stacked params, and
    forking workers would duplicate HBM and re-compile per worker; scale with
    threads (jax releases the GIL during device compute) and replicas behind
    the ingress, not preforked workers. The built-in werkzeug server below is
    threaded and suffices for the single-host case; it is not hardened for
    untrusted public traffic.

    ``trace_dir``: wrap the warm-up compiles in a ``jax.profiler`` device
    trace (the compile-heavy phase worth profiling; steady-state serving
    is better observed through ``/metrics``).

    Graceful shutdown: SIGTERM (what the router's supervisor — or k8s —
    sends) closes the admission gate, drains in-flight requests
    (``GORDO_DRAIN_TIMEOUT`` seconds, default 10), quiesces the engine's
    dispatch pipeline, and only then stops the listener — a
    router-initiated worker restart drops zero accepted requests.
    """
    import signal

    from werkzeug.serving import make_server

    from ..utils.profiling import device_trace

    import jax

    devices = jax.devices()
    # said once, at boot: JAX falls back to the CPU without failing when
    # it cannot get the chip, and a 200 does not say which device scored
    logger.info(
        "Serving on platform %s (%s), %d device(s)",
        devices[0].platform, devices[0].device_kind, len(devices),
    )
    app = build_app(
        model_dirs, project=project, models_root=models_root,
        shard_fleet=shard_fleet, max_inflight=max_inflight,
        compile_cache_store=compile_cache_store, worker_id=worker_id,
        lazy_boot=lazy_boot,
    )
    # warm each bucket's scoring program BEFORE accepting traffic: the
    # first request must pay dispatch (ms), not XLA compile (tens of s).
    # Against a warmed compile-cache store this is load-not-compile —
    # zero fresh XLA compiles at boot. Not fatal for a deployment — one
    # broken bucket must not keep the healthy machines from serving —
    # but an error (the engine names the bucket), and counted
    try:
        with device_trace(trace_dir):
            warmed = app.engine.warmup()
    except Exception:
        _M_WARMUPS.labels("error").inc()
        logger.error("Serving engine warm-up failed", exc_info=True)
    else:
        _M_WARMUPS.labels("ok").inc()
        if warmed:
            cache = app.compile_cache
            logger.info(
                "Serving engine warm: %d bucket(s)%s", warmed,
                (
                    f" (compile cache {cache.root}: "
                    f"{cache.counters.get('hit', 0)} hit(s), "
                    f"{cache.counters.get('write', 0)} write(s))"
                    if cache is not None
                    else " (compile cache off)"
                ),
            )
    server = make_server(host, port, app, threaded=True)
    drain_timeout = float(os.environ.get("GORDO_DRAIN_TIMEOUT", "10"))

    def _drain_and_stop() -> None:
        # ordering matters: close admission (new work sheds with the
        # draining marker, the router re-routes it) → drain in-flight →
        # quiesce the engine → stop the listener. shutdown() last so the
        # healthz endpoint keeps ANSWERING "draining" while we drain —
        # a silent socket would read as a dead worker and get ejected.
        app.quiesce(drain_timeout)
        server.shutdown()

    def _on_sigterm(signum, frame) -> None:
        logger.info("SIGTERM: beginning graceful drain")
        # a thread, not inline: the handler runs on the main thread,
        # which serve_forever() below owns — quiescing there would
        # deadlock against the very requests being drained
        threading.Thread(
            target=_drain_and_stop, name="gordo-drain", daemon=True
        ).start()

    try:
        signal.signal(signal.SIGTERM, _on_sigterm)
    except ValueError:
        # not the main thread (embedded run_server): graceful shutdown
        # is then the embedder's job via app.quiesce()
        logger.debug("SIGTERM handler not installed (non-main thread)")
    server.serve_forever()
    logger.info("Server stopped")
