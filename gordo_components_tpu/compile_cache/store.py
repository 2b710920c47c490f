"""Store-backed persistent compilation cache: AOT-serialized executables.

Every server boot, ``/reload``, generation swap, and ``gordo rollback``
otherwise re-pays full XLA compilation for every (architecture ×
row-bucket × batch-size) scoring program — warmup hides it from the first
request but not from the boot clock. This store persists the compiled
executables themselves (``jax.experimental.serialize_executable`` — the
loaded binary, not re-lowerable IR), so adopting a generation is O(load):
deserialize, one probe dispatch, serve.

Layout — one entry per executable, committed through the model store's
atomic machinery so cache entries inherit its guarantees (a torn write is
invisible; a damaged entry FAILS VERIFICATION instead of loading)::

    <root>/
      cc-<sha256(key)[:32]>/
        KEY.json         # full key: program identity + backend fingerprint
        executable.bin   # serialize_executable payload
        treedefs.pkl     # pickled (in_tree, out_tree)
        MANIFEST.json    # per-file SHA-256 + size (store/atomic.py)

The fallback contract (the load path is NEVER fatal):

- entry absent → **miss** (caller JIT-compiles, writes back);
- manifest fails, payload unreadable, deserialization raises, or the
  caller's probe dispatch fails → **invalid** (caller JIT-compiles and
  the write-back overwrites the bad entry — self-healing);
- stored ``KEY.json`` disagrees with the expected key (fingerprint
  tamper, hash collision) → **stale** (same JIT fallback);
- a crash mid-write leaves only ``.staging-*`` debris the atomic-commit
  rename never published — the next boot misses cleanly.

Scores from a fallen-back JIT path are bit-identical to the cached path
(same lowering → same executable; gated end-to-end by
``tools/coldstart_smoke.py``).

Security note: ``treedefs.pkl`` and the executable payload are pickle
(jax's serialization format). The manifest's SHA-256 pass runs BEFORE any
unpickling — same trust model as the serializer's model artifacts — so a
flipped bit fails typed, but the cache root must be as trusted as the
model store it lives beside.
"""

from __future__ import annotations

import logging
import os
import pickle
import shutil
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

from ..observability.registry import REGISTRY
from ..store import StoreError, atomic_commit, sweep_leftovers, verify_artifact
from ..utils.backend import CACHE_DIR_ENV
from . import fingerprint as fp

logger = logging.getLogger(__name__)

KEY_FILE = "KEY.json"
EXEC_FILE = "executable.bin"
TREES_FILE = "treedefs.pkl"
# sidecar measurements (NOT part of the cache key): the measured XLA
# compile seconds this entry saved, read back by the §24 cost ledger.
# Pre-ledger entries simply lack the file — `entries()` reports None.
META_FILE = "META.json"

# env knob read by the server/CLI wiring (a path, or "off" to disable the
# cache even when a models_root would default one on)
STORE_ENV = "GORDO_COMPILE_CACHE_STORE"

_M_LOOKUPS = REGISTRY.counter(
    "gordo_compile_cache_lookups_total",
    "Persistent compile-cache lookups by program kind and outcome: hit "
    "(executable loaded, no XLA compile), miss (no entry), stale (entry's "
    "stored key disagrees — e.g. jaxlib fingerprint mismatch), invalid "
    "(corrupt/unreadable/failed-probe entry). Everything but 'hit' falls "
    "back to JIT and is never fatal",
    labels=("kind", "outcome"),
)
_M_WRITES = REGISTRY.counter(
    "gordo_compile_cache_writes_total",
    "Persistent compile-cache write-backs, by outcome (ok / error / "
    "unserializable / compile_error: the AOT compile itself failed and "
    "the program serves via lazy JIT)",
    labels=("outcome",),
)
_M_LOAD_SECONDS = REGISTRY.histogram(
    "gordo_compile_cache_load_seconds",
    "Duration of a successful cache-entry load (verify + deserialize) — "
    "the O(load) cost that replaces an O(compile) one",
)


class CompileCacheStore:
    """One cache root; thread-safe (entries are immutable once committed,
    commits are atomic renames, concurrent writers of one key last-win).

    Instance ``counters`` track THIS store object's lookups (a fresh boot
    diff, next to the process-cumulative registry series).
    """

    def __init__(self, root: str):
        self.root = os.path.abspath(root)
        self.counters: Dict[str, int] = {
            "hit": 0, "miss": 0, "stale": 0, "invalid": 0,
            "write": 0, "write_error": 0,
        }

    # -- lookup --------------------------------------------------------------
    def get(
        self,
        program_key: Dict[str, Any],
        devices: Sequence[Any],
        probe: Optional[Callable[[Any], None]] = None,
    ) -> Optional[Any]:
        """The loaded executable for ``program_key``, or ``None`` (miss /
        stale / invalid — the caller JIT-compiles either way).

        ``devices``: the devices the program was compiled for, in its
        device-assignment order (one device for a replicated scoring
        program, the mesh's for a sharded one). jax's loader otherwise
        assumes EVERY device of the backend and reloads a one-device
        program as an N-shard one that no call can satisfy.

        ``probe``: optional callable run with the loaded executable before
        it is adopted (the engine dispatches a zeros batch through it) — a
        binary that verifies on disk but cannot execute on THIS host
        (moved cache dir, ISA drift inside one fingerprint) downgrades to
        *invalid* here instead of failing live requests later."""
        kind = str(program_key.get("kind", "unknown"))
        key = fp.full_key(program_key)
        path = os.path.join(self.root, fp.entry_name(key))
        if not os.path.isdir(path):
            self._count(kind, "miss")
            return None
        started = time.perf_counter()
        try:
            verify_artifact(path, deep=True)
        except StoreError as exc:
            logger.warning(
                "Compile-cache entry %s fails verification (%s); falling "
                "back to JIT", path, exc,
            )
            self._count(kind, "invalid")
            return None
        try:
            with open(os.path.join(path, KEY_FILE)) as fh:
                stored = fh.read()
            if stored.strip() != fp.canonical(key):
                logger.warning(
                    "Compile-cache entry %s key mismatch (stale fingerprint "
                    "or collision); falling back to JIT", path,
                )
                self._count(kind, "stale")
                return None
            loaded = self._load_entry(path, devices)
            if probe is not None:
                probe(loaded)
        except Exception as exc:
            logger.warning(
                "Compile-cache entry %s unloadable (%s: %s); falling back "
                "to JIT", path, type(exc).__name__, exc,
            )
            self._count(kind, "invalid")
            return None
        _M_LOAD_SECONDS.observe(time.perf_counter() - started)
        self._count(kind, "hit")
        return loaded

    @staticmethod
    def _load_entry(path: str, devices: Sequence[Any]):
        from jax.experimental.serialize_executable import deserialize_and_load

        with open(os.path.join(path, EXEC_FILE), "rb") as fh:
            payload = fh.read()
        with open(os.path.join(path, TREES_FILE), "rb") as fh:
            in_tree, out_tree = pickle.load(fh)
        return deserialize_and_load(
            payload, in_tree, out_tree, execution_devices=list(devices)
        )

    # -- write-back ----------------------------------------------------------
    def put(
        self,
        program_key: Dict[str, Any],
        compiled: Any,
        compile_seconds: Optional[float] = None,
    ) -> bool:
        """Serialize ``compiled`` and commit it under ``program_key``
        (atomic; an existing entry — e.g. one that just read invalid — is
        replaced whole). Never raises: a cache that cannot write degrades
        to compile-every-boot, not to a failed build or request.

        ``compile_seconds``: the measured XLA compile duration this entry
        amortizes, persisted as sidecar meta — the §24 cost ledger's
        per-key compile cost, recorded once at the only moment it is
        actually known."""
        key = fp.full_key(program_key)
        path = os.path.join(self.root, fp.entry_name(key))
        try:
            from jax.experimental.serialize_executable import serialize

            payload, in_tree, out_tree = serialize(compiled)
            trees = pickle.dumps((in_tree, out_tree))
        except Exception as exc:
            # sharded/exotic executables some backends cannot serialize:
            # a known, logged degradation — the program still serves
            logger.warning(
                "Compile-cache: executable for %s is not serializable "
                "(%s: %s); this program will recompile every boot",
                program_key, type(exc).__name__, exc,
            )
            self.counters["write_error"] += 1
            _M_WRITES.labels("unserializable").inc()
            return False
        try:
            os.makedirs(self.root, exist_ok=True)
            import json

            with atomic_commit(path, name=os.path.basename(path)) as staging:
                with open(os.path.join(staging, KEY_FILE), "w") as fh:
                    fh.write(fp.canonical(key) + "\n")
                with open(os.path.join(staging, EXEC_FILE), "wb") as fh:
                    fh.write(payload)
                with open(os.path.join(staging, TREES_FILE), "wb") as fh:
                    fh.write(trees)
                with open(os.path.join(staging, META_FILE), "w") as fh:
                    json.dump(
                        {
                            "compile_seconds": compile_seconds,
                            "created": time.time(),
                        },
                        fh,
                    )
        except Exception as exc:
            logger.warning(
                "Compile-cache write-back failed for %s (%s: %s)",
                program_key, type(exc).__name__, exc,
            )
            self.counters["write_error"] += 1
            _M_WRITES.labels("error").inc()
            return False
        self.counters["write"] += 1
        _M_WRITES.labels("ok").inc()
        return True

    def count_compile_failure(self) -> None:
        """The caller's AOT compile for this store failed and it fell back
        to lazy JIT: nothing was written, and the program recompiles every
        boot — the same degradation as a failed write, counted as one."""
        self.counters["write_error"] += 1
        _M_WRITES.labels("compile_error").inc()

    # -- maintenance (the `gordo cache` verbs) -------------------------------
    def entries(self) -> List[Dict[str, Any]]:
        """One record per entry dir: its stored key, byte size, whether it
        verifies, and whether its backend fingerprint matches THIS process
        (``current`` False = candidate for ``purge --stale``)."""
        import json

        out: List[Dict[str, Any]] = []
        try:
            names = sorted(os.listdir(self.root))
        except OSError:
            return out
        current_backend = fp.backend_fingerprint()
        for name in names:
            path = os.path.join(self.root, name)
            if not name.startswith(fp.ENTRY_PREFIX) or not os.path.isdir(path):
                continue
            record: Dict[str, Any] = {"name": name, "bytes": _dir_bytes(path)}
            try:
                # deep (hashing) verification: `cache list` must report a
                # size-preserving bitflip as unverified, and `purge
                # --stale` promises to remove entries that fail
                # verification — entries are small, so the hash pass is
                # cheap at operator-CLI cadence
                verify_artifact(path, deep=True)
                record["verified"] = True
            except StoreError as exc:
                record["verified"] = False
                record["error"] = f"{type(exc).__name__}: {exc}"
            try:
                with open(os.path.join(path, KEY_FILE)) as fh:
                    key = json.load(fh)
                record["program"] = key.get("program")
                record["backend"] = key.get("backend")
                record["current"] = key.get("backend") == current_backend
                # §19: the precision rung this executable was compiled
                # for, surfaced top-level so `gordo cache list` makes a
                # mixed-precision cache auditable at a glance (pre-ladder
                # entries carry no field and read f32)
                record["precision"] = (key.get("program") or {}).get(
                    "precision", "f32"
                )
            except Exception:
                record.setdefault("error", "KEY.json unreadable")
                record["current"] = False
            try:
                with open(os.path.join(path, META_FILE)) as fh:
                    meta = json.load(fh)
                record["compile_seconds"] = meta.get("compile_seconds")
                record["created"] = meta.get("created")
            except Exception:  # lint: allow-swallow(pre-ledger entries have no META.json sidecar by design; absence is the signal, recorded as compile_seconds=None)
                record["compile_seconds"] = None
            out.append(record)
        return out

    def purge(self, stale_only: bool = False) -> List[str]:
        """Delete entries (all, or — ``stale_only`` — those whose backend
        fingerprint no longer matches or that fail verification) and sweep
        crash debris (``.staging-*``). Returns the removed names."""
        removed: List[str] = []
        for record in self.entries():
            if stale_only and record.get("current") and record.get("verified"):
                continue
            shutil.rmtree(
                os.path.join(self.root, record["name"]), ignore_errors=True
            )
            removed.append(record["name"])
        removed.extend(sweep_leftovers(self.root))
        return removed

    def _count(self, kind: str, outcome: str) -> None:
        self.counters[outcome] = self.counters.get(outcome, 0) + 1
        _M_LOOKUPS.labels(kind, outcome).inc()


def _dir_bytes(path: str) -> int:
    total = 0
    try:
        for entry in os.scandir(path):
            if entry.is_file():
                total += entry.stat().st_size
    except OSError:
        pass
    return total


def resolve_store(
    explicit: Optional[str] = None, models_root: Optional[str] = None
) -> Optional[CompileCacheStore]:
    """The ONE resolution rule for where the serving compile cache lives,
    shared by the server, the CLI, and the builder export so they can
    never warm different roots: explicit path beats the
    ``GORDO_COMPILE_CACHE_STORE`` env var beats the default. The default
    (only with a ``models_root``, i.e. a models-tree server or build)
    follows JAX's own cache placement: ``$JAX_COMPILATION_CACHE_DIR/
    serving-aot`` when the operator placed that cache, else
    ``<models_root>/.compile-cache`` (hidden, so the model scan rule never
    mistakes it for a machine). ``"off"`` at any level disables; no path
    resolvable → ``None`` (cache off, compile-on-boot)."""
    root = explicit
    if root is None:
        root = os.environ.get(STORE_ENV) or None
    if root is None and models_root:
        placed = os.environ.get(CACHE_DIR_ENV)
        root = (
            os.path.join(placed, "serving-aot")
            if placed
            else os.path.join(models_root, ".compile-cache")
        )
    if not root or root == "off":
        return None
    return CompileCacheStore(root)
