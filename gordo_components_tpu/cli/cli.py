"""Command-line interface — the container entrypoints.

Reference parity: ``gordo_components/cli/cli.py`` [UNVERIFIED] — click group
``gordo`` with ``build`` (env-var backed: MODEL_CONFIG, DATA_CONFIG,
OUTPUT_DIR, MODEL_REGISTER_DIR — Argo injects these), ``run-server``,
``workflow generate``, ``client predict``; distinct exit codes so the
orchestrator can tell retryable data failures from permanent config errors.

TPU additions: ``fleet-build`` (the whole fleet in one process — what the
generated TPU Job runs), ``run-watchman``, and ``rollback`` (swap a model
dir's ``CURRENT`` pointer back to its previous verified generation).

Exit codes: 0 ok · 64 bad config (permanent) · 66 data unavailable/short
(retryable) · 1 unexpected.
"""

from __future__ import annotations

import contextlib
import json
import logging
import sys
from typing import Iterator, Optional

import click
import yaml

from ..precision import PRECISIONS as _PRECISIONS

EXIT_CONFIG = 64
EXIT_DATA = 66
# EX_SOFTWARE: a deterministic device-side failure (HBM OOM, invalid XLA
# program). The generated Job FailJobs on this code — restarting cannot
# help a program that is too big for the chip, and the retryable 75 path
# is Ignored by the podFailurePolicy so it must never absorb these.
EXIT_PERMANENT = 70

def _is_permanent_xla_error(message: str) -> bool:
    """Deterministic-failure classifier for JaxRuntimeError messages.

    Kept narrow on purpose: everything unrecognised (UNAVAILABLE,
    DEADLINE_EXCEEDED, INTERNAL from a dead collective peer, ...) stays
    retryable — wrongly marking a transient failure permanent kills a
    recoverable multi-host build, while wrongly retrying a permanent one
    only burns the Job's activeDeadlineSeconds bound. RESOURCE_EXHAUSTED
    alone is NOT enough: gRPC uses the same status for transient
    flow-control/overload on cross-host transfers, so it only counts as
    the deterministic device OOM when paired with allocator wording.

    Status matches are anchored to the START of the message (ADVICE r5):
    a transient multi-host failure whose wrapped/chained error text merely
    EMBEDS "INVALID_ARGUMENT" somewhere (e.g. an UNAVAILABLE transport
    error quoting a peer's status) must stay retryable — only a message
    that leads with the status (jax raises them as "STATUS: detail") is
    the deterministic device failure this classifier exists for.
    """
    lead = message.lstrip()
    if lead.startswith("INVALID_ARGUMENT"):
        return True
    if lead.startswith("RESOURCE_EXHAUSTED"):
        lowered = message.lower()
        return any(w in lowered for w in ("allocat", "hbm", "memory"))
    return False

logger = logging.getLogger(__name__)


def _load_config(value: Optional[str], kind: str) -> dict:
    """Accept inline YAML/JSON or a path to a YAML file."""
    if not value:
        raise click.UsageError(f"Missing {kind} (flag or env var)")
    import os

    if os.path.exists(value):
        with open(value) as fh:
            return yaml.safe_load(fh)
    parsed = yaml.safe_load(value)
    if not isinstance(parsed, dict):
        raise click.UsageError(f"{kind} must parse to a mapping")
    return parsed


@click.group("gordo")
@click.option("--log-level", default="INFO", envvar="GORDO_LOG_LEVEL",
              show_default=True)
@click.option("--log-format", default="text", envvar="GORDO_LOG_FORMAT",
              show_default=True, type=click.Choice(["text", "json"]),
              help="'json' emits one JSON object per record (trace/span ids "
                   "as fields) for log pipelines; 'text' keeps the classic "
                   "line format")
@click.option("--debug-nans/--no-debug-nans", default=False,
              envvar="GORDO_DEBUG_NANS", show_default=True,
              help="Enable jax_debug_nans: compiled programs re-run op-by-op "
                   "at the first NaN and raise with the producing op "
                   "(SURVEY.md §6.2 — the rebuild's numeric sanitizer; "
                   "large slowdown, diagnostics only).")
def gordo(log_level: str, log_format: str, debug_nans: bool):
    """gordo-components-tpu: fleet-scale TPU anomaly-model factory."""
    from ..observability import configure_logging

    configure_logging(log_level, log_format)
    if debug_nans:
        import jax

        jax.config.update("jax_debug_nans", True)
        logging.getLogger(__name__).warning(
            "jax_debug_nans enabled: training/scoring runs un-jitted "
            "re-checks on NaN and will be much slower"
        )


_TRACE_DIR_OPT = click.option(
    "--trace-dir",
    envvar="GORDO_TRACE_DIR",
    default=None,
    help="write a jax.profiler device trace (TensorBoard/perfetto-loadable) "
    "of the device work to this directory; fleet-build traces one whole "
    "steady slice, host phases included, and writes the job's span "
    "timeline beside it as fleet_build_timeline.json (Perfetto loads both)",
)


@gordo.command("build")
@click.argument("name")
@click.option("--model-config", envvar="MODEL_CONFIG",
              help="YAML/JSON string or file path")
@click.option("--data-config", envvar="DATA_CONFIG",
              help="YAML/JSON string or file path")
@click.option("--output-dir", envvar="OUTPUT_DIR", required=True)
@click.option("--model-register-dir", envvar="MODEL_REGISTER_DIR", default=None)
@click.option("--metadata", envvar="METADATA", default=None,
              help="extra user metadata (YAML/JSON string)")
@click.option("--cv-mode", default="full_build", show_default=True,
              type=click.Choice(["full_build", "cross_val_only", "build_only"]))
@click.option("--n-splits", default=3, show_default=True)
@click.option("--print-cv-scores", is_flag=True, default=False)
@click.option("--precision", default=None,
              type=click.Choice(list(_PRECISIONS)),
              help="this machine's rung on the serving precision ladder "
                   "(ARCHITECTURE §19): pinned into the artifact's build "
                   "metadata and validated on load; int8 also commits the "
                   "quantized weights + per-tensor scales beside state.npz. "
                   "Default: GORDO_PRECISION_DEFAULT, else f32")
@_TRACE_DIR_OPT
def build_cmd(name, model_config, data_config, output_dir, model_register_dir,
              metadata, cv_mode, n_splits, print_cv_scores, precision,
              trace_dir):
    """Build one machine's model (idempotent via the config-hash cache)."""
    from ..builder import provide_saved_model
    from ..dataset.dataset import InsufficientDataError
    from ..serializer import load_metadata
    from ..utils.backend import enable_persistent_compile_cache
    from ..utils.profiling import device_trace

    enable_persistent_compile_cache()
    try:
        model_cfg = _load_config(model_config, "MODEL_CONFIG")
        data_cfg = _load_config(data_config, "DATA_CONFIG")
        user_meta = yaml.safe_load(metadata) if metadata else {}
        with device_trace(trace_dir):
            model_dir = provide_saved_model(
                name,
                model_cfg,
                data_cfg,
                output_dir,
                metadata=user_meta,
                model_register_dir=model_register_dir,
                evaluation_config={"cv_mode": cv_mode, "n_splits": n_splits},
                precision=precision,
            )
    except InsufficientDataError as exc:
        logger.error("Data error building %r: %s", name, exc)
        sys.exit(EXIT_DATA)
    except (ValueError, click.UsageError) as exc:
        logger.error("Config error building %r: %s", name, exc)
        sys.exit(EXIT_CONFIG)
    click.echo(model_dir)
    if print_cv_scores:
        meta = load_metadata(model_dir)
        scores = meta.get("model", {}).get("cross_validation", {}).get("scores", {})
        click.echo(json.dumps(scores))


@contextlib.contextmanager
def _fleet_exit_codes() -> Iterator[None]:
    """The exit code of a ``fleet-build`` that fails, by what failed.
    Imports what it tells apart only where something did fail, so that the
    command's own span holds its imports."""
    try:
        yield
    except Exception as exc:
        from jax.errors import JaxRuntimeError

        from ..dataset.dataset import InsufficientDataError

        if isinstance(exc, InsufficientDataError):
            logger.error("Data error in fleet build: %s", exc)
            sys.exit(EXIT_DATA)
        if isinstance(exc, ValueError):
            logger.error("Config error in fleet build: %s", exc)
            sys.exit(EXIT_CONFIG)
        if not isinstance(exc, JaxRuntimeError):
            raise
        # Deterministic device failures (HBM OOM = RESOURCE_EXHAUSTED,
        # invalid XLA program = INVALID_ARGUMENT) exit the permanent code:
        # the Job's podFailurePolicy Ignores 75, so mapping these to 75
        # would crash-loop a build that can never succeed on TPU quota
        # forever without ever counting toward backoffLimit.
        if _is_permanent_xla_error(str(exc)):
            logger.error(
                "Deterministic device failure in fleet build: %s — "
                "exiting permanent code %d (restarts cannot help)",
                exc,
                EXIT_PERMANENT,
            )
            sys.exit(EXIT_PERMANENT)
        # Everything else is a device/collective runtime failure — in
        # multi-host builds most often a dead peer detected by the
        # transport (connection reset in an allgather). Deterministically
        # retryable: restart-all re-runs resume from the registry + slice
        # checkpoints, so map it to the explicit transient code (75,
        # EX_TEMPFAIL) rather than a generic crash. The in-process
        # watchdog (GORDO_SLICE_TIMEOUT_S) exits the same code for the
        # hangs the transport cannot see.
        from ..parallel.build_fleet import EXIT_RETRYABLE

        logger.error(
            "Runtime failure in fleet build (dead peer / device error?): "
            "%s — exiting retryable code %d; a restarted run resumes from "
            "the registry and slice checkpoints",
            exc,
            EXIT_RETRYABLE,
        )
        sys.exit(EXIT_RETRYABLE)


@gordo.command("fleet-build")
@click.option("--machine-config", required=True,
              help="fleet YAML (machines + globals) file path or string")
@click.option("--output-dir", envvar="OUTPUT_DIR", required=True)
@click.option("--model-register-dir", envvar="MODEL_REGISTER_DIR", default=None)
@click.option("--n-devices", default=None, type=int,
              help="mesh size (default: all available devices)")
@click.option("--n-splits", default=3, show_default=True,
              help="cross-validation folds for machines that do not set "
                   "their own evaluation.n_splits in the fleet YAML "
                   "(per-machine/globals evaluation takes precedence over "
                   "this flag, mirroring the reference's config hierarchy)")
@click.option("--seed", default=0, show_default=True)
@click.option("--slice-size", default=256, show_default=True, type=int,
              help="machines per checkpointed slice within a bucket: each "
                   "slice's artifacts + registry keys land while the next "
                   "slice trains, so a killed build loses at most the "
                   "slice training and the slice committing; 0 disables "
                   "slicing (whole bucket per program call)")
@click.option("--coordinator-address", envvar="GORDO_COORDINATOR", default=None,
              help="multi-host: jax.distributed coordinator host:port — run "
                   "the SAME command on every host; each fetches and writes "
                   "only its own machine shard (requires shared storage for "
                   "output/registry dirs). Omit for cluster autodetection "
                   "(TPU pod metadata) or single-host builds")
@click.option("--num-processes", envvar="GORDO_NUM_PROCESSES", default=None,
              type=int, help="multi-host: total process count")
@click.option("--process-id", envvar="GORDO_PROCESS_ID", default=None,
              type=int, help="multi-host: this host's process index")
@click.option("--precision", "precision_default", default=None,
              type=click.Choice(list(_PRECISIONS)),
              help="fleet-wide default rung on the serving precision "
                   "ladder (§19); per-machine overrides via "
                   "--precision-map. Default: GORDO_PRECISION_DEFAULT, "
                   "else f32")
@click.option("--precision-map", default=None,
              help="per-machine precision pins: 'name=prec,name=prec' "
                   "pairs or a YAML file mapping machine names to "
                   "f32/bf16/int8; unmapped machines take --precision. "
                   "Accuracy-sensitive machines stay f32 while the long "
                   "tail drops precision")
@click.option("--serving-cache/--no-serving-cache", default=True,
              show_default=True,
              help="after the build, export AOT-serialized SERVING "
                   "executables into the serving compile-cache store "
                   "(the root run-server --models-dir resolves to: "
                   "$JAX_COMPILATION_CACHE_DIR/serving-aot, else "
                   "<output-dir>/.compile-cache), so the first "
                   "server boot — and every /reload and rollback — loads "
                   "compiled programs instead of paying XLA compiles "
                   "(single-host builds only; best-effort)")
@_TRACE_DIR_OPT
def fleet_build_cmd(machine_config, output_dir, model_register_dir, n_devices,
                    n_splits, seed, slice_size, coordinator_address,
                    num_processes, process_id, precision_default,
                    precision_map, serving_cache, trace_dir):
    """Build an entire fleet: machines are bucketed and trained as vmapped
    programs sharded over the device mesh. With ``--coordinator-address``
    (or on a TPU pod with autodetectable cluster metadata plus explicit
    ``--num-processes``), the build runs multi-host — every process ingests
    and writes only its own machine shard."""
    from ..observability import flightrec, spans

    multihost = coordinator_address is not None or num_processes is not None
    # the job's timeline begins here: the command's own set-up (its imports,
    # the compile cache, the config, the mesh) is fleet.command on it, and
    # build_fleet records into the same timeline
    with _fleet_exit_codes(), flightrec.build_timeline(trace_dir):
        with spans.stage("fleet.command"):
            from ..parallel import FleetMachineConfig, build_fleet, fleet_mesh
            from ..precision import parse_precision_map
            from ..utils.backend import enable_persistent_compile_cache
            from ..workflow import NormalizedConfig

            enable_persistent_compile_cache()
            if process_id is not None and not multihost:
                # a bare process index would silently run a FULL single-host
                # build on every host — duplicated training and racing writes
                raise click.UsageError(
                    "--process-id requires --coordinator-address and/or "
                    "--num-processes"
                )
            if multihost:
                # must run BEFORE anything touches the XLA backend
                from ..parallel.distributed import (
                    global_fleet_mesh,
                    initialize_multihost,
                )

                initialize_multihost(
                    coordinator_address=coordinator_address,
                    num_processes=num_processes,
                    process_id=process_id,
                )
            with spans.stage("fleet.config") as configured:
                config = NormalizedConfig(
                    _load_config(machine_config, "machine-config")
                )
                machines = [
                    FleetMachineConfig(
                        name=machine.name,
                        model_config=machine.model,
                        data_config=machine.dataset,
                        metadata=machine.metadata,
                        evaluation=machine.evaluation,
                    )
                    for machine in config.machines
                ]
                configured["machines"] = len(machines)
            precisions = parse_precision_map(precision_map)
            if multihost and n_devices is not None:
                logger.warning(
                    "--n-devices is ignored in multi-host mode: the global "
                    "fleet mesh spans every device of every process"
                )
            with spans.stage("fleet.mesh"):
                # where the backend is first touched on one host
                mesh = global_fleet_mesh() if multihost else fleet_mesh(n_devices)
        results = build_fleet(
            machines,
            output_dir,
            model_register_dir=model_register_dir,
            mesh=mesh,
            seed=seed,
            n_splits=n_splits,
            profile_dir=trace_dir,
            slice_size=slice_size or None,
            precision_default=precision_default,
            precision_map=precisions,
        )
    if serving_cache and results and not multihost:
        # pay the SERVING compiles here, once, where the build already
        # owns the device — every later boot/reload/rollback against this
        # tree is then O(load). A failed export costs the first boot its
        # compiles, never the build its artifacts — but it is an error,
        # not a footnote: a chip run must not scroll past it
        from ..compile_cache import export_serving_cache, resolve_store

        store = resolve_store(models_root=output_dir)
        if store is not None:
            try:
                summary = export_serving_cache(results, store.root)
                logger.info("Serving compile-cache export: %s", summary)
            except Exception:
                logger.error(
                    "Serving compile-cache export into %s failed (builds "
                    "unaffected; the first server boot will compile "
                    "instead)", store.root, exc_info=True,
                )
    click.echo(json.dumps(results, indent=2))


@gordo.command("rollback")
@click.argument("model_dir")
@click.option("--list", "list_only", is_flag=True, default=False,
              help="print the model dir's generation status (current "
                   "generation, all generations, verify result) as JSON "
                   "without changing anything")
def rollback_cmd(model_dir, list_only):
    """Roll a model dir back to its previous verified generation.

    MODEL_DIR is a generation root (``gen-NNNN/`` dirs + ``CURRENT``
    pointer — what ``build``/``fleet-build`` write). The rollback is a
    single atomic ``CURRENT`` swap to the newest PREVIOUS generation that
    passes manifest verification; a serving process adopts it on its next
    ``POST /reload``. Exits 64 when there is nothing safe to roll back to.
    """
    from ..store import StoreError, artifact_status, rollback_generation

    if list_only:
        click.echo(json.dumps(artifact_status(model_dir), indent=2))
        return
    try:
        restored = rollback_generation(model_dir)
    except StoreError as exc:
        logger.error("Rollback failed: %s", exc)
        sys.exit(EXIT_CONFIG)
    click.echo(restored)


@gordo.group("cache")
def cache_group():
    """Persistent serving compile cache (AOT-serialized executables).

    The store that makes boot, /reload, and rollback O(load) instead of
    O(compile) — see docs/ARCHITECTURE.md §14 for the key schema,
    invalidation rules, and the never-fatal JIT fallback contract.
    """


@cache_group.command("list")
@click.option("--store", "store_dir", required=True,
              help="compile-cache root (e.g. <models-dir>/.compile-cache)")
def cache_list_cmd(store_dir):
    """List cache entries as JSON: program key, size, verification state,
    and whether each entry's backend fingerprint matches THIS process
    (``current: false`` entries are what ``purge --stale`` removes)."""
    from ..compile_cache import CompileCacheStore, backend_fingerprint

    store = CompileCacheStore(store_dir)
    click.echo(json.dumps(
        {
            "root": store.root,
            "backend": backend_fingerprint(),
            "entries": store.entries(),
        },
        indent=2,
    ))


@cache_group.command("warm")
@click.option("--models-dir", required=True,
              help="directory whose immediate subdirs are model dirs (the "
                   "tree run-server --models-dir serves)")
@click.option("--store", "store_dir", default=None,
              help="compile-cache root (default: the root run-server "
                   "resolves for this --models-dir)")
@click.option("--shard-fleet", is_flag=True, default=False,
              help="warm the mesh-sharded engine variant (must match how "
                   "the server will boot — sharding is part of the key)")
@click.option("--rows", default=None, type=int,
              help="warm the padded-row bucket real requests will hit "
                   "(default: each bucket's minimum scorable request)")
def cache_warm_cmd(models_dir, store_dir, shard_fleet, rows):
    """Pre-pay the serving compiles into the cache, off the serving path.

    Loads every model under MODELS-DIR, warms a throwaway serving engine
    wired to the store (the exact boot code path, so keys match by
    construction), and prints the summary. Run it wherever fleet-build's
    automatic export can't — after copying a models tree to a new rig, or
    after a jaxlib upgrade invalidated the old entries.
    """
    from ..compile_cache import export_serving_cache, resolve_store
    from ..server.server import scan_models_root
    from ..utils.backend import enable_persistent_compile_cache

    enable_persistent_compile_cache()
    model_dirs = scan_models_root(models_dir)
    if not model_dirs:
        raise click.UsageError(f"No model dirs found under {models_dir!r}")
    store = resolve_store(store_dir, models_root=models_dir)
    if store is None:
        raise click.UsageError("The serving compile cache is switched off")
    summary = export_serving_cache(
        model_dirs, store.root, rows=rows, shard_fleet=shard_fleet
    )
    click.echo(json.dumps(summary, indent=2))


@cache_group.command("purge")
@click.option("--store", "store_dir", required=True,
              help="compile-cache root")
@click.option("--stale", "stale_only", is_flag=True, default=False,
              help="remove only entries whose backend fingerprint no "
                   "longer matches this process (old jaxlib / device / "
                   "topology) or that fail verification; without it the "
                   "whole cache is cleared")
def cache_purge_cmd(store_dir, stale_only):
    """Delete cache entries (and sweep crash debris). Safe while servers
    run: entries are immutable and lookups that miss fall back to JIT."""
    from ..compile_cache import CompileCacheStore

    store = CompileCacheStore(store_dir)
    removed = store.purge(stale_only=stale_only)
    click.echo(json.dumps({"root": store.root, "removed": removed}, indent=2))


@gordo.command("run-server")
@click.option("--model-dir", "model_dirs", multiple=True,
              envvar="MODEL_LOCATION",
              help="model dir; repeat for multi-model serving")
@click.option("--models-dir", default=None,
              help="directory whose immediate subdirs are model dirs")
@click.option("--host", default="0.0.0.0", show_default=True)
@click.option("--port", default=5555, show_default=True)
@click.option("--project", default="project", show_default=True)
@click.option("--shard-fleet", is_flag=True, default=False,
              help="shard every bucket's stacked params over all local "
                   "devices (HBM capacity mode for fleets whose stacked "
                   "weights exceed one chip; adds per-request gather hops)")
@click.option("--max-inflight", default=None, type=int,
              envvar="GORDO_MAX_INFLIGHT",
              help="admission-gate bound on concurrently-scoring requests; "
                   "beyond it (plus a small queue) the server sheds with "
                   "503 + Retry-After instead of convoying threads "
                   "(default 64)")
@click.option("--tenants", default=None, envvar="GORDO_TENANTS",
              help="multi-tenant QoS table (§25): "
                   "'name:class[:rate[:burst[:key]]]' entries separated "
                   "by ';' — class interactive/standard/bulk, rate in "
                   "requests/s (0 = unmetered token bucket), key an "
                   "optional API key that maps to the tenant. Requests "
                   "pick their tenant via X-Gordo-Tenant; unknown names "
                   "fold into 'default'")
@click.option("--faults", default=None, envvar="GORDO_FAULTS",
              help="chaos-testing fault spec "
                   "'point:target:kind[:param][;...]' (points: model-load, "
                   "engine-dispatch, probe, data-fetch; kinds: error, "
                   "latency, corrupt) — injects failures at the named "
                   "boundaries; NEVER set in production")
@click.option("--compile-cache-store", default=None,
              envvar="GORDO_COMPILE_CACHE_STORE",
              help="persistent serving compile-cache root (AOT-serialized "
                   "scoring executables; 'off' disables). Default when "
                   "--models-dir is given: "
                   "$JAX_COMPILATION_CACHE_DIR/serving-aot, else "
                   "<models-dir>/.compile-cache "
                   "— the root fleet-build exports into, so boot, /reload "
                   "and rollback pay zero fresh XLA compiles against a "
                   "warmed store")
@click.option("--megabatch/--no-megabatch", default=None,
              help="cross-machine megabatching: concurrent requests for "
                   "different machines fuse into one stacked device "
                   "dispatch (default on; always off with --shard-fleet). "
                   "Overrides GORDO_MEGABATCH")
@click.option("--fill-window-us", default=None, type=int,
              envvar="GORDO_FILL_WINDOW_US",
              help="bounded megabatch fill window in microseconds: how "
                   "long a dispatch leader that observes concurrency "
                   "collects further requests before dispatching the "
                   "fused batch (core-aware default; 0 disables the "
                   "wait; idle requests never wait)")
@click.option("--worker-id", default=None, type=int,
              envvar="GORDO_WORKER_ID",
              help="fleet slot id when this server runs as one worker of "
                   "a run-fleet-server tier: responses carry "
                   "X-Gordo-Worker and /healthz reports the id so the "
                   "router can verify placement")
@click.option("--lazy-boot/--no-lazy-boot", default=None,
              help="boot from the models tree's FLEET_INDEX.json sidecar "
                   "— O(index read) instead of O(load the fleet); "
                   "non-eager machines serve through the host-RAM spill "
                   "tier (GORDO_HOST_CACHE_MB) with artifact verification "
                   "on first touch. Requires --models-dir. Overrides "
                   "GORDO_LAZY_BOOT")
@click.option("--mesh-shards", default=None, type=int,
              envvar="GORDO_MESH_SHARDS",
              help="multi-host mesh serving (§23): total shard count the "
                   "fleet's stacked machine axis partitions across by "
                   "ring position; this process stacks only its owned "
                   "slice and serves the rest via the spill fallback "
                   "rung. 0/unset = single-host serving")
@click.option("--mesh-shard", default=None, type=int,
              envvar="GORDO_MESH_SHARD",
              help="this process's shard id (0-based) in the "
                   "--mesh-shards mesh; defaults to worker-id mod shards")
@_TRACE_DIR_OPT
def run_server_cmd(model_dirs, models_dir, host, port, project, shard_fleet,
                   max_inflight, tenants, faults, compile_cache_store,
                   megabatch, fill_window_us, worker_id, lazy_boot,
                   mesh_shards, mesh_shard, trace_dir):
    """Serve built model(s) over REST."""
    import os

    from ..serializer import load_metadata
    from ..server import run_server
    from ..utils.backend import enable_persistent_compile_cache

    enable_persistent_compile_cache()
    # engine knobs resolve from env at construction: export the CLI's
    # answers so boot AND every /reload generation swap agree on them
    if megabatch is not None:
        os.environ["GORDO_MEGABATCH"] = "1" if megabatch else "0"
    if fill_window_us is not None:
        os.environ["GORDO_FILL_WINDOW_US"] = str(fill_window_us)
    # §23: exported so every /reload generation re-derives the SAME
    # shard partition this boot used
    if mesh_shards is not None:
        os.environ["GORDO_MESH_SHARDS"] = str(mesh_shards)
    if mesh_shard is not None:
        os.environ["GORDO_MESH_SHARD"] = str(mesh_shard)
    if lazy_boot is not None:
        os.environ["GORDO_LAZY_BOOT"] = "1" if lazy_boot else "0"
    if lazy_boot is None:
        lazy_boot = os.environ.get(
            "GORDO_LAZY_BOOT", "0"
        ).strip().lower() in ("1", "true", "on", "yes")
    if lazy_boot and not models_dir:
        raise click.UsageError("--lazy-boot requires --models-dir")

    if tenants is not None:
        from ..resilience import qos as qos_mod

        try:
            # validated HERE so a typo'd table fails the command loudly
            # instead of silently serving everyone as 'default'
            qos_mod.parse_tenants(tenants)
        except ValueError as exc:
            raise click.UsageError(f"Bad --tenants spec: {exc}")
        os.environ["GORDO_TENANTS"] = tenants

    if faults is not None:
        from ..resilience import faults as faults_mod

        try:
            # validated HERE so a typo'd spec fails the command loudly
            # instead of silently injecting nothing
            faults_mod.configure(faults)
        except ValueError as exc:
            raise click.UsageError(f"Bad --faults spec: {exc}")

    resolved: dict = {}
    for model_dir in model_dirs:
        name = load_metadata(model_dir).get("name") or os.path.basename(
            model_dir.rstrip("/")
        )
        resolved[name] = model_dir
    if models_dir and not lazy_boot:
        from ..server.server import scan_models_root

        # same scan rule as POST /reload (definition.json gate) so startup
        # and reload can never disagree about what counts as a model dir
        for entry, path in scan_models_root(models_dir).items():
            resolved.setdefault(entry, path)
    if not resolved and not lazy_boot:
        raise click.UsageError(
            "Provide --model-dir (or MODEL_LOCATION) or --models-dir"
        )
    if lazy_boot:
        # §22: the FLEET_INDEX sidecar names the fleet — no eager scan
        # here; explicit --model-dir machines stay eager, the server
        # partitions the rest behind the host-RAM spill tier (and falls
        # back to its own scan when the index is damaged or absent)
        run_server(resolved, host=host, port=port, project=project,
                   models_root=models_dir, shard_fleet=shard_fleet,
                   trace_dir=trace_dir, max_inflight=max_inflight,
                   compile_cache_store=compile_cache_store,
                   worker_id=worker_id, lazy_boot=True)
        return
    if len(resolved) == 1 and not models_dir:
        run_server(next(iter(resolved.values())), host=host, port=port,
                   project=project, shard_fleet=shard_fleet,
                   trace_dir=trace_dir, max_inflight=max_inflight,
                   compile_cache_store=compile_cache_store,
                   worker_id=worker_id)
    else:
        # models_dir servers stay reload-capable (POST /reload picks up
        # machines a fleet build adds to the tree after startup)
        run_server(resolved, host=host, port=port, project=project,
                   models_root=models_dir, shard_fleet=shard_fleet,
                   trace_dir=trace_dir, max_inflight=max_inflight,
                   compile_cache_store=compile_cache_store,
                   worker_id=worker_id)


@gordo.command("run-fleet-server")
@click.option("--models-dir", required=True,
              help="directory whose immediate subdirs are model dirs; "
                   "every worker serves this tree and shares its "
                   ".compile-cache store")
@click.option("--workers", default=2, show_default=True, type=int,
              help="worker server processes to spawn and supervise. More "
                   "than one is a CPU or multi-host topology: a chip "
                   "belongs to one process. Where the TPU runtime is "
                   "installed and JAX_PLATFORMS is unset, workers start "
                   "with JAX_PLATFORMS=tpu, so one that cannot get the "
                   "device dies at boot instead of serving from the CPU")
@click.option("--host", default="0.0.0.0", show_default=True,
              help="router listen address")
@click.option("--port", default=5555, show_default=True,
              help="router listen port")
@click.option("--worker-base-port", default=5600, show_default=True,
              type=int,
              help="worker i listens on worker-base-port + i (loopback)")
@click.option("--project", default="project", show_default=True)
@click.option("--replicas", default=2, show_default=True, type=int,
              help="distinct workers serving each HOT machine (cold "
                   "machines are pinned to exactly one, keeping its "
                   "megabatch residency and compile cache warm there)")
@click.option("--hot-rps", default=50.0, show_default=True, type=float,
              help="request rate at which a machine is replicated across "
                   "--replicas workers; 0 disables rate-based promotion")
@click.option("--probe-interval", default=2.0, show_default=True,
              type=float,
              help="control-plane health-probe interval in seconds "
                   "(each tick jittered ±10% so a large fleet never "
                   "probes in lockstep)")
@click.option("--megabatch/--no-megabatch", default=None,
              help="forwarded to every worker (see run-server)")
@click.option("--max-inflight", default=None, type=int,
              help="per-WORKER admission bound (see run-server)")
@click.option("--tenants", default=None, envvar="GORDO_TENANTS",
              help="multi-tenant QoS table (§25), exported as "
                   "GORDO_TENANTS so the router AND every spawned worker "
                   "load the same table (see run-server)")
@click.option("--mesh-shards", default=0, show_default=True, type=int,
              envvar="GORDO_MESH_SHARDS",
              help="multi-host mesh serving (§23): partition the fleet's "
                   "stacked machine axis across this many shards — "
                   "worker i serves shard i mod shards and the router "
                   "prefers each machine's owning shard (falls back to "
                   "any worker's spill tier if the owner dies). 0 = the "
                   "replicated tier exactly as before")
def run_fleet_server_cmd(models_dir, workers, host, port, worker_base_port,
                         project, replicas, hot_rps, probe_interval,
                         megabatch, max_inflight, tenants, mesh_shards):
    """Horizontal serving tier: spawn and supervise WORKERS server
    processes over one models tree, routing /prediction traffic by
    consistent-hash machine→worker placement. Worker health probes drive
    breaker/quarantine-based eject + respawn; POST /reload canaries one
    worker then sweeps the rest (rolling generation adoption), and POST
    /rollback swaps CURRENT fleet-wide before re-adopting."""
    import os

    from ..router import run_fleet_server

    worker_args = []
    if megabatch is not None:
        worker_args += ["--megabatch" if megabatch else "--no-megabatch"]
    if max_inflight is not None:
        worker_args += ["--max-inflight", str(max_inflight)]
    if tenants is not None:
        from ..resilience import qos as qos_mod

        try:
            qos_mod.parse_tenants(tenants)
        except ValueError as exc:
            raise click.UsageError(f"Bad --tenants spec: {exc}")
        # env, not worker_args: the router process reads the table too
        os.environ["GORDO_TENANTS"] = tenants
    if workers < 1:
        raise click.UsageError("--workers must be >= 1")
    if mesh_shards and mesh_shards > workers:
        raise click.UsageError(
            f"--mesh-shards ({mesh_shards}) needs at least that many "
            f"--workers to cover every shard (got {workers})"
        )
    run_fleet_server(
        models_dir,
        workers=workers,
        host=host,
        port=port,
        worker_base_port=worker_base_port,
        project=project,
        replicas=replicas,
        hot_rps=hot_rps,
        probe_interval=probe_interval,
        worker_args=worker_args,
        mesh_shards=max(0, mesh_shards),
    )


@gordo.command("run-watchman")
@click.option("--project", default=None)
@click.option("--machine", "machines", multiple=True)
@click.option("--target-url", default=None)
@click.option("--host", default="0.0.0.0", show_default=True)
@click.option("--port", default=5556, show_default=True)
@click.option("--manifest", default=None,
              help="path to a fleet build's fleet_manifest.json; GET / then "
                   "also reports build progress (completed/pending) from it "
                   "(multi-host sibling manifests are unioned)")
@click.option("--watch", is_flag=True, default=False,
              help="no HTTP: follow the fleet manifest(s), print one JSON "
                   "progress line per interval, exit 0 when every machine "
                   "is completed (the reference's CRD-status evolution of "
                   "watchman)")
@click.option("--interval", default=5.0, show_default=True,
              help="--watch poll interval in seconds")
def run_watchman_cmd(project, machines, target_url, host, port, manifest,
                     watch, interval):
    """Serve the fleet-health aggregator (or follow a build with --watch)."""
    if watch:
        if not manifest:
            raise click.UsageError("--watch requires --manifest")
        from ..watchman import watch_build_progress

        watch_build_progress(manifest, interval_s=interval)
        return
    if not (project and machines and target_url):
        raise click.UsageError(
            "--project, --machine, and --target-url are required "
            "(or use --watch --manifest)"
        )
    from ..watchman import run_watchman

    run_watchman(
        project,
        list(machines),
        target_url,
        host=host,
        port=port,
        manifest_path=manifest,
    )


@gordo.group("workflow")
def workflow_group():
    """Fleet-workflow manifest generation."""


@workflow_group.command("generate")
@click.option("--machine-config", required=True)
@click.option("--output-file", default=None)
@click.option("--image", default="gordo-components-tpu:latest", show_default=True)
@click.option("--parallelism", default=10, show_default=True)
@click.option("--tpu", "tpu_mode", is_flag=True, default=False,
              help="emit the single-Job TPU fleet spec instead of "
                   "pod-per-machine Argo")
@click.option("--tpu-chips", default=16, show_default=True)
@click.option("--tpu-hosts", default=1, show_default=True,
              help="(with --tpu) >1 emits the multi-host layout: an "
                   "Indexed Job (one pod per host) + headless coordinator "
                   "Service wiring fleet-build's jax.distributed flags")
@click.option("--slice-timeout-s", default=1800, show_default=True,
              type=click.IntRange(min=0),
              help="(with --tpu --tpu-hosts>1) GORDO_SLICE_TIMEOUT_S on the "
                   "build pods: the slice watchdog budget that turns a "
                   "wedged collective into retryable exit 75 (ignored by "
                   "the Job's podFailurePolicy, so restarts don't burn "
                   "backoffLimit); size above the worst healthy slice "
                   "time. 0 disables the watchdog — wedged pods then hang "
                   "until killed externally")
@click.option("--active-deadline-s", default=86400, show_default=True,
              type=click.IntRange(min=1),
              help="(with --tpu) Job activeDeadlineSeconds: the global "
                   "wall-clock bound on the build, and the only bound on "
                   "retryable (exit 75) crash loops since the "
                   "podFailurePolicy excludes 75 from backoffLimit; size "
                   "above the worst full-fleet build time")
def workflow_generate_cmd(machine_config, output_file, image, parallelism,
                          tpu_mode, tpu_chips, tpu_hosts, slice_timeout_s,
                          active_deadline_s):
    """Fleet YAML -> Argo Workflow (reference-compatible) or TPU Job spec."""
    from ..workflow import generate_argo_workflow, generate_tpu_job
    from ..workflow.workflow_generator import validate_generated

    try:
        config = _load_config(machine_config, "machine-config")
        if tpu_mode:
            manifest = generate_tpu_job(
                config, image=image, tpu_chips=tpu_chips, hosts=tpu_hosts,
                slice_timeout_s=slice_timeout_s,
                active_deadline_s=active_deadline_s,
            )
        else:
            manifest = generate_argo_workflow(
                config, image=image, parallelism=parallelism
            )
        validate_generated(manifest)
    except ValueError as exc:
        logger.error("Config error generating workflow: %s", exc)
        sys.exit(EXIT_CONFIG)
    if output_file:
        with open(output_file, "w") as fh:
            fh.write(manifest)
        click.echo(output_file)
    else:
        click.echo(manifest)


@gordo.group("trace")
def trace_group():
    """Flight-recorder timelines from a running model server."""


@trace_group.command("list")
@click.option("--base-url", required=True, help="model-server base URL")
@click.option("--limit", default=20, show_default=True,
              help="recent timelines to list")
def trace_list_cmd(base_url, limit):
    """List recorded request timelines (recent + slowest + errored)."""
    import requests

    url = f"{base_url.rstrip('/')}/debug/requests?limit={limit}"
    try:
        response = requests.get(url, timeout=10)
        response.raise_for_status()
    except requests.RequestException as exc:
        logger.error("Could not list traces from %s: %s", base_url, exc)
        sys.exit(1)
    click.echo(json.dumps(response.json(), indent=2))


@trace_group.command("dump")
@click.argument("trace_id")
@click.option("--base-url", required=True, help="model-server base URL")
@click.option("--output", "-o", default=None,
              help="write to this file instead of stdout")
@click.option("--format", "fmt", default="chrome", show_default=True,
              type=click.Choice(["chrome", "json"]),
              help="chrome = trace-event JSON (open at "
                   "https://ui.perfetto.dev or chrome://tracing); "
                   "json = the raw timeline with stage totals")
def trace_dump_cmd(trace_id, base_url, output, fmt):
    """Dump ONE trace's per-stage timeline.

    TRACE_ID is the ``X-Gordo-Trace-Id`` a response echoed (or a trace id
    from ``gordo trace list`` / watchman's slow-requests view). The
    default output is Chrome trace-event JSON — load it in Perfetto to
    see exactly which stage (queue wait, dispatch, device execution,
    fetch, encode) the request's time went to.
    """
    import requests

    url = f"{base_url.rstrip('/')}/debug/requests/{trace_id}"
    if fmt == "chrome":
        url += "?format=chrome"
    try:
        response = requests.get(url, timeout=10)
    except requests.RequestException as exc:
        logger.error("Could not fetch trace from %s: %s", base_url, exc)
        sys.exit(1)
    if response.status_code == 404:
        logger.error(
            "Trace %s is not in the flight recorder (rotated out, or "
            "never seen by this server)", trace_id,
        )
        sys.exit(1)
    try:
        response.raise_for_status()
    except requests.RequestException as exc:
        logger.error("Trace fetch failed: %s", exc)
        sys.exit(1)
    body = json.dumps(response.json(), indent=2)
    if output:
        with open(output, "w") as fh:
            fh.write(body)
        click.echo(output)
    else:
        click.echo(body)


@gordo.command("slo")
@click.option("--base-url", required=True,
              help="router or model-server base URL")
def slo_cmd(base_url):
    """Objective attainment + burn rates from a live server's ``/slo``.

    The SLO engine (ARCHITECTURE §18) evaluates declared latency and
    availability objectives by multi-window burn rate over the
    already-collected histograms; this verb is the operator view —
    attainment per objective, fast/slow-window burn, breach counts, and
    which span stage is eating the budget.
    """
    import requests

    url = f"{base_url.rstrip('/')}/slo"
    try:
        response = requests.get(url, timeout=10)
        response.raise_for_status()
    except requests.RequestException as exc:
        logger.error("Could not read /slo from %s: %s", base_url, exc)
        sys.exit(1)
    click.echo(json.dumps(response.json(), indent=2))


@gordo.command("tenants")
@click.option("--base-url", required=True,
              help="router or model-server base URL")
def tenants_cmd(base_url):
    """The QoS control surface (ARCHITECTURE §25) from a live ``/tenants``:
    the declared tenant table (name, class, token-bucket rate/burst and
    current fill), the admission gate's per-class limits and shed ladder
    rung (model-server only), and the raw-header heavy-hitter sketch —
    which unmapped principals are folding into 'default' and how hard."""
    import requests

    url = f"{base_url.rstrip('/')}/tenants"
    try:
        response = requests.get(url, timeout=10)
        response.raise_for_status()
    except requests.RequestException as exc:
        logger.error("Could not read /tenants from %s: %s", base_url, exc)
        sys.exit(1)
    click.echo(json.dumps(response.json(), indent=2))


@gordo.group("autopilot")
def autopilot_group():
    """The closed-loop controller (ARCHITECTURE §20): SLO-driven knob
    tuning on servers, elastic worker scaling on the router.

    ``status`` dumps the /autopilot body (enablement, per-actuator
    values/bounds/cooldowns, the decision journal, the last
    observation); ``enable``/``disable`` are the runtime kill switch.
    The HARD kill switch is ``GORDO_AUTOPILOT=0`` at process start —
    under it no controller exists and ``enable`` answers 409.
    """


def _autopilot_request(base_url: str, path: str, method: str = "GET"):
    import requests

    url = f"{base_url.rstrip('/')}{path}"
    try:
        response = requests.request(method, url, timeout=10)
    except requests.RequestException as exc:
        logger.error("Could not reach %s: %s", url, exc)
        sys.exit(1)
    try:
        body = response.json()
    except ValueError:
        logger.error("Non-JSON answer from %s (HTTP %d)", url,
                     response.status_code)
        sys.exit(1)
    if response.status_code >= 400:
        logger.error("%s answered HTTP %d: %s", url, response.status_code,
                     body.get("error", body))
        sys.exit(1)
    return body


@autopilot_group.command("status")
@click.option("--base-url", required=True,
              help="router or model-server base URL")
def autopilot_status_cmd(base_url):
    """Controller status from a live server's ``/autopilot``."""
    click.echo(json.dumps(_autopilot_request(base_url, "/autopilot"),
                          indent=2))


@autopilot_group.command("enable")
@click.option("--base-url", required=True,
              help="router or model-server base URL")
def autopilot_enable_cmd(base_url):
    """Start (or resume) adapting: ``POST /autopilot/enable``."""
    body = _autopilot_request(base_url, "/autopilot/enable", method="POST")
    click.echo(json.dumps(body, indent=2))


@autopilot_group.command("disable")
@click.option("--base-url", required=True,
              help="router or model-server base URL")
def autopilot_disable_cmd(base_url):
    """The runtime kill switch: freeze all adaptation NOW
    (``POST /autopilot/disable``); status stays readable."""
    body = _autopilot_request(base_url, "/autopilot/disable", method="POST")
    click.echo(json.dumps(body, indent=2))


@gordo.group("fleet")
def fleet_group():
    """The declarative fleet reconciler (ARCHITECTURE §26): journaled
    desired-state specs the router continuously converges the fleet
    toward.

    ``apply`` commits a JSON spec file as a new journal revision;
    ``diff`` shows spec-vs-observed divergences without repairing;
    ``status`` dumps the /fleet body (revision, divergence counts,
    repair ring, frozen/cooling classes); ``rollback`` re-applies the
    previous revision as a new one. The HARD kill switch is
    ``GORDO_FLEET=0`` at router start — under it no reconciler exists
    and every verb answers 409.
    """


def _fleet_request(base_url: str, path: str, method: str = "GET",
                   payload=None):
    import requests

    url = f"{base_url.rstrip('/')}{path}"
    try:
        response = requests.request(
            method, url, timeout=30,
            json=payload if payload is not None else None,
        )
    except requests.RequestException as exc:
        logger.error("Could not reach %s: %s", url, exc)
        sys.exit(1)
    try:
        body = response.json()
    except ValueError:
        logger.error("Non-JSON answer from %s (HTTP %d)", url,
                     response.status_code)
        sys.exit(1)
    if response.status_code >= 400:
        logger.error("%s answered HTTP %d: %s", url, response.status_code,
                     body.get("error", body))
        sys.exit(1)
    return body


@fleet_group.command("apply")
@click.argument("spec_file", type=click.Path(exists=True))
@click.option("--base-url", required=True, help="router base URL")
def fleet_apply_cmd(spec_file, base_url):
    """Commit SPEC_FILE (a JSON fleet spec) as a new revision:
    ``POST /fleet/apply``. Parsing is loud — an unknown machine,
    precision rung, or key is a 422, never a silent no-op."""
    with open(spec_file) as fh:
        try:
            payload = json.load(fh)
        except ValueError as exc:
            logger.error("%s is not JSON: %s", spec_file, exc)
            sys.exit(1)
    body = _fleet_request(base_url, "/fleet/apply", method="POST",
                          payload=payload)
    click.echo(json.dumps(body, indent=2))


@fleet_group.command("diff")
@click.option("--base-url", required=True, help="router base URL")
def fleet_diff_cmd(base_url):
    """Spec-vs-observed divergences, read-only: ``GET /fleet/diff``
    (no repairs run, no budget spent)."""
    click.echo(json.dumps(_fleet_request(base_url, "/fleet/diff"),
                          indent=2))


@fleet_group.command("status")
@click.option("--base-url", required=True, help="router base URL")
def fleet_status_cmd(base_url):
    """Reconciler status from a live router's ``/fleet``."""
    click.echo(json.dumps(_fleet_request(base_url, "/fleet"), indent=2))


@fleet_group.command("rollback")
@click.option("--base-url", required=True, help="router base URL")
def fleet_rollback_cmd(base_url):
    """Re-apply the previous spec revision as a NEW journaled revision:
    ``POST /fleet/rollback`` (422 with fewer than two revisions)."""
    body = _fleet_request(base_url, "/fleet/rollback", method="POST")
    click.echo(json.dumps(body, indent=2))


@gordo.group("telemetry")
def telemetry_group():
    """The fleet telemetry warehouse (ARCHITECTURE §24): durable metric
    history, per-machine traffic accounting, and the measured-cost
    ledger, read from a live ``/telemetry`` endpoint.

    ``traffic`` shows the top-K heavy hitters with multi-horizon EWMA
    rates; ``costs`` shows the per-rung device/host byte and latency
    ledger; ``export`` emits the versioned layout-input document
    (machines x observed rate x bytes x latency per rung) that layout
    planning consumes. Point ``--base-url`` at a router to read the
    whole fleet merged, or at one worker for its slice.
    """


def _telemetry_request(base_url: str, window: Optional[float] = None,
                       view: Optional[str] = None):
    import requests

    url = f"{base_url.rstrip('/')}/telemetry"
    params = {}
    if window is not None:
        params["window"] = window
    if view is not None:
        params["view"] = view
    try:
        response = requests.get(url, params=params, timeout=10)
        response.raise_for_status()
        body = response.json()
    except requests.RequestException as exc:
        logger.error("Could not read /telemetry from %s: %s", base_url, exc)
        sys.exit(1)
    except ValueError:
        logger.error("Non-JSON answer from %s", url)
        sys.exit(1)
    if not body.get("enabled", True) and "schema" not in body:
        logger.error(
            "Telemetry is disabled on %s (GORDO_TELEMETRY=0)", base_url
        )
        sys.exit(1)
    return body


@telemetry_group.command("traffic")
@click.option("--base-url", required=True,
              help="router or model-server base URL")
@click.option("--window", default=300.0, show_default=True,
              help="history window in seconds for rates/percentiles")
def telemetry_traffic_cmd(base_url, window):
    """Per-machine traffic accounting: the top-K heavy-hitter sketch
    with 1m/10m/1h EWMA rates, plus shape-bucket x precision groups."""
    body = _telemetry_request(base_url, window=window)
    click.echo(json.dumps(
        {
            "now": body.get("now"),
            "workers": body.get("workers", [body.get("worker")]),
            "traffic": body.get("traffic"),
            "window": body.get("window"),
        },
        indent=2,
    ))


@telemetry_group.command("costs")
@click.option("--base-url", required=True,
              help="router or model-server base URL")
@click.option("--window", default=300.0, show_default=True,
              help="history window in seconds")
def telemetry_costs_cmd(base_url, window):
    """The measured-cost ledger: per-rung stacked-tree device bytes,
    dispatch seconds, host-cache tier bytes + hit/load latency EWMAs,
    spill-path accounting, and per-key compile seconds."""
    body = _telemetry_request(base_url, window=window)
    click.echo(json.dumps(
        {
            "now": body.get("now"),
            "workers": body.get("workers", [body.get("worker")]),
            "costs": body.get("costs"),
            "warehouse": body.get("warehouse"),
        },
        indent=2,
    ))


@telemetry_group.command("export")
@click.option("--base-url", required=True,
              help="router or model-server base URL")
@click.option("--window", default="5m", show_default=True,
              help="rate horizon: seconds or 1m/10m/1h forms")
@click.option("--output", "-o", default=None,
              help="write the document here instead of stdout")
def telemetry_export_cmd(base_url, window, output):
    """Emit the versioned layout-input document from ``?view=export``.

    ``--window`` takes the warehouse horizon forms (``1m``/``10m``/
    ``1h``) or bare seconds; the document's per-machine ``rate`` field
    snaps to the nearest tracked EWMA horizon. The document (schema
    ``gordo-layout-input/v1``) is validated client-side before it is
    printed — a malformed answer exits nonzero rather than handing
    layout planning a broken contract.
    """
    from ..observability import telemetry as telemetry_engine

    seconds = telemetry_engine.parse_window(window)
    if seconds is None:
        logger.error("--window %r is not a duration (try 90, 10m, 1h)",
                     window)
        sys.exit(1)
    body = _telemetry_request(base_url, window=seconds, view="export")
    problems = telemetry_engine.validate_layout_input(body)
    if problems:
        for problem in problems:
            logger.error("layout-input validation: %s", problem)
        sys.exit(1)
    rendered = json.dumps(body, indent=2)
    if output:
        with open(output, "w") as fh:
            fh.write(rendered + "\n")
        click.echo(output)
    else:
        click.echo(rendered)


@gordo.group("incidents")
def incidents_group():
    """The fleet black box (ARCHITECTURE §28): the unified control
    ledger every control loop emits into, and the incident reports the
    breach-edge correlator snapshots from it.

    ``list`` shows newest-first incident summaries (router answers with
    the whole fleet merged; a worker answers for itself); ``show``
    renders one full report — trigger, lookback ledger events, metric
    deltas, spec/layout revisions, and the ranked root-cause candidate
    list; ``ledger`` tails the raw control-event journal.
    """


def _incidents_request(base_url: str, path: str, params=None):
    import requests

    url = f"{base_url.rstrip('/')}{path}"
    try:
        response = requests.get(url, params=params or {}, timeout=30)
    except requests.RequestException as exc:
        logger.error("Could not reach %s: %s", url, exc)
        sys.exit(1)
    try:
        body = response.json()
    except ValueError:
        logger.error("Non-JSON answer from %s (HTTP %d)", url,
                     response.status_code)
        sys.exit(1)
    if response.status_code >= 400:
        logger.error("%s answered HTTP %d: %s", url, response.status_code,
                     body.get("error", body))
        sys.exit(1)
    return body


@incidents_group.command("list")
@click.option("--base-url", required=True,
              help="router or model-server base URL")
def incidents_list_cmd(base_url):
    """Newest-first incident summaries from ``GET /incidents``."""
    click.echo(json.dumps(_incidents_request(base_url, "/incidents"),
                          indent=2))


@incidents_group.command("show")
@click.argument("incident_id")
@click.option("--base-url", required=True,
              help="router or model-server base URL")
def incidents_show_cmd(incident_id, base_url):
    """One full incident report: ``GET /incidents/<id>`` (the router
    also searches its workers for the id)."""
    click.echo(json.dumps(
        _incidents_request(base_url, f"/incidents/{incident_id}"),
        indent=2,
    ))


@incidents_group.command("ledger")
@click.option("--base-url", required=True,
              help="router or model-server base URL")
@click.option("--window", default=None,
              help="only events in this trailing window: seconds or "
                   "1m/10m/1h forms (default: all retained)")
@click.option("--limit", default=200, show_default=True,
              help="newest events kept")
def incidents_ledger_cmd(base_url, window, limit):
    """Tail the raw control ledger: ``GET /incidents?view=ledger``."""
    params = {"view": "ledger", "limit": limit}
    if window is not None:
        params["window"] = window
    click.echo(json.dumps(
        _incidents_request(base_url, "/incidents", params=params),
        indent=2,
    ))


@gordo.group("layout")
def layout_group():
    """The fleet layout compiler (ARCHITECTURE §27): measured-cost
    placement plans computed from the telemetry warehouse's layout-input
    document, replacing hand-set placement/residency/precision knobs.

    ``plan`` compiles a versioned ``gordo-layout-plan/v1`` artifact from
    a live ``/telemetry?view=export`` feed or a saved document;
    ``explain`` renders the decisions and why each machine moved;
    ``apply`` commits a plan into the fleet spec journal, where the
    reconciler drives it onto the running fleet (and ``gordo fleet
    rollback`` reverts it).
    """


def _read_plan_file(plan_file: str):
    from ..layout import plan as layout_plan

    with open(plan_file) as fh:
        try:
            plan = json.load(fh)
        except ValueError as exc:
            logger.error("%s is not JSON: %s", plan_file, exc)
            sys.exit(1)
    problems = layout_plan.validate_layout_plan(plan)
    if problems:
        for problem in problems:
            logger.error("layout-plan validation: %s", problem)
        sys.exit(1)
    return plan


@layout_group.command("plan")
@click.option("--base-url", default=None,
              help="router base URL to pull /telemetry?view=export from")
@click.option("--input", "input_file", default=None,
              type=click.Path(exists=True),
              help="saved layout-input document instead of a live fleet")
@click.option("--window", default="10m", show_default=True,
              help="rate horizon: seconds or 1m/10m/1h forms")
@click.option("--cap", type=int, default=None,
              help="per-worker residency cap override")
@click.option("--parity-budget", type=float, default=None,
              help="traffic-weighted parity budget for precision "
                   "downgrades (0 disables them)")
@click.option("--output", "-o", default=None,
              help="write the plan here instead of stdout")
def layout_plan_cmd(base_url, input_file, window, cap, parity_budget,
                    output):
    """Compile a ``gordo-layout-plan/v1`` from measured costs.

    Exactly one of ``--base-url`` (live export) or ``--input`` (saved
    document) chooses the evidence. The plan is deterministic: the same
    document compiles to the same bytes and the same fingerprint.
    """
    from ..layout import compiler as layout_compiler
    from ..observability import telemetry as telemetry_engine

    if (base_url is None) == (input_file is None):
        logger.error("pass exactly one of --base-url or --input")
        sys.exit(1)
    if base_url is not None:
        seconds = telemetry_engine.parse_window(window)
        if seconds is None:
            logger.error("--window %r is not a duration (try 90, 10m, 1h)",
                         window)
            sys.exit(1)
        doc = _telemetry_request(base_url, window=seconds, view="export")
    else:
        with open(input_file) as fh:
            try:
                doc = json.load(fh)
            except ValueError as exc:
                logger.error("%s is not JSON: %s", input_file, exc)
                sys.exit(1)
    try:
        plan = layout_compiler.compile_plan(
            doc, residency_cap=cap, parity_budget=parity_budget,
        )
    except ValueError as exc:
        logger.error("layout plan does not compile: %s", exc)
        sys.exit(1)
    rendered = json.dumps(plan, indent=2, sort_keys=True)
    if output:
        with open(output, "w") as fh:
            fh.write(rendered + "\n")
        click.echo(output)
    else:
        click.echo(rendered)


@layout_group.command("explain")
@click.argument("plan_file", required=False,
                type=click.Path(exists=True))
@click.option("--base-url", default=None,
              help="read the committed spec's plan from a live router")
def layout_explain_cmd(plan_file, base_url):
    """Render a plan's decisions: cost before/after, per-worker weights
    and resident sets, precision downgrades, and why each machine moved.
    Reads PLAN_FILE, or with ``--base-url`` the plan committed in the
    live fleet spec."""
    from ..layout import plan as layout_plan

    if (plan_file is None) == (base_url is None):
        logger.error("pass exactly one of PLAN_FILE or --base-url")
        sys.exit(1)
    if plan_file is not None:
        plan = _read_plan_file(plan_file)
    else:
        body = _fleet_request(base_url, "/fleet/diff")
        plan = (body.get("spec") or {}).get("layout")
        if plan is None:
            logger.error("the committed fleet spec carries no layout plan")
            sys.exit(1)
    click.echo(layout_plan.explain_plan(plan))


@layout_group.command("apply")
@click.argument("plan_file", type=click.Path(exists=True))
@click.option("--base-url", required=True, help="router base URL")
def layout_apply_cmd(plan_file, base_url):
    """Commit PLAN_FILE into the fleet spec journal: the current spec
    is fetched, ``layout`` is replaced, and the merged spec lands as a
    new revision via ``POST /fleet/apply`` — journaled, diffable, and
    revertible with ``gordo fleet rollback``."""
    plan = _read_plan_file(plan_file)
    body = _fleet_request(base_url, "/fleet/diff")
    spec = dict(body.get("spec") or {})
    spec["layout"] = plan
    reply = _fleet_request(base_url, "/fleet/apply", method="POST",
                           payload=spec)
    click.echo(json.dumps(reply, indent=2))


@gordo.group("client")
def client_group():
    """Bulk prediction against running servers."""


@client_group.command("predict")
@click.argument("start")
@click.argument("end")
@click.option("--base-url", required=True, help="model-server base URL")
@click.option("--project", default="project", show_default=True)
@click.option("--machine", "machines", multiple=True,
              help="subset of machines (default: discover via /models)")
@click.option("--max-interval", default="1D", show_default=True)
@click.option("--parallelism", default=10, show_default=True)
@click.option("--output-dir", default=None,
              help="write per-machine score CSVs here")
def client_predict_cmd(start, end, base_url, project, machines, max_interval,
                       parallelism, output_dir):
    """Score [START, END) for every machine and print row counts."""
    from ..client import Client, ClientError, CsvForwarder

    forwarders = [CsvForwarder(output_dir)] if output_dir else []
    client = Client(
        base_url,
        project=project,
        machines=list(machines) or None,
        max_interval=max_interval,
        parallelism=parallelism,
        forwarders=forwarders,
    )
    try:
        frames = client.predict(start, end)
    except ClientError as exc:
        logger.error("Prediction failed: %s", exc)
        sys.exit(1)
    click.echo(
        json.dumps({machine: len(frame) for machine, frame in frames.items()})
    )


@gordo.command(
    "lint",
    context_settings={"ignore_unknown_options": True},
    add_help_option=False,
)
@click.argument("args", nargs=-1, type=click.UNPROCESSED)
def lint_cmd(args):
    """Run the invariant linter (lock discipline, span seams, metric
    conventions, knob registry — docs/ARCHITECTURE.md §17). Delegates to
    ``python -m gordo_components_tpu.analysis``; ``make lint`` is the
    jax-free fast path."""
    from ..analysis.runner import main as lint_main

    sys.exit(lint_main(list(args)))


if __name__ == "__main__":
    gordo()
