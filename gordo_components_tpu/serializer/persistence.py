"""Disk persistence for fitted pipelines.

Reference parity: ``gordo_components/serializer/__init__.py`` dump/load —
the reference persists a dir tree of per-step pickles + keras HDF5
[UNVERIFIED]. Here the artifact is pure-state and pickle-free on the load
path:

```
model_dir/
  definition.json       # into_definition output (class graph + kwargs)
  state.npz             # every fitted array, flattened "step/sub/key" paths
  state_meta.json       # non-array fitted state (history, shapes, …)
  metadata.json         # caller-provided build metadata (optional)
  MANIFEST.json         # per-file SHA-256 + size + format version (store/)
```

Crash-safety contract (``store/``): ``dump`` stages into a hidden sibling
dir, fsyncs everything, writes the checksummed manifest, and renames into
place — a crash leaves the destination untouched. ``load`` VERIFIES the
manifest before deserializing anything and raises the store's typed
errors (``ManifestMissing`` / ``ArtifactIncomplete`` / ``ArtifactCorrupt``)
on any disagreement — a torn artifact is an exception, never a silently
half-loaded pipeline. ``load``/``load_metadata`` also resolve generation
roots (``CURRENT`` → ``gen-NNNN/``), so callers can hold one path per
machine whichever layout it uses.

``dumps``/``loads`` wrap the same format in an in-memory tar for the
``/download-model`` endpoint and client-side reloads. ``dumps`` is
byte-deterministic (zeroed tar/gzip/zip timestamps and ownership, sorted
members), so the same artifact always produces an identical blob and a
downloaded model's manifest hashes match the server's. ``loads`` bounds
extraction (member count, total decompressed bytes, duplicate names) so
a spoofed server cannot decompression-bomb the client.
"""

from __future__ import annotations

import io
import json
import os
import tarfile
import zipfile
from typing import Any, Dict, Optional, Tuple

import numpy as np

from ..store.atomic import atomic_commit
from ..store.generations import resolve_artifact_dir
from ..store.manifest import verify_artifact
from .from_definition import pipeline_from_definition
from .into_definition import pipeline_into_definition

METADATA_FILE = "metadata.json"
DEFINITION_FILE = "definition.json"
STATE_FILE = "state.npz"
STATE_META_FILE = "state_meta.json"
_SEP = "/"

# tar-extraction bounds for loads(): an artifact is ≤ 5 files, so a blob
# claiming hundreds of members or absurd decompressed sizes is an attack
# (or corruption), not a model. Total-bytes ceiling is env-tunable for
# genuinely huge plant fleets.
MAX_TAR_MEMBERS = 128
MAX_TAR_TOTAL_BYTES_ENV = "GORDO_MAX_ARTIFACT_BYTES"
DEFAULT_MAX_TAR_TOTAL_BYTES = 2 << 30  # 2 GiB

# fixed zip timestamp (the ZIP epoch) for deterministic state.npz bytes
_ZIP_EPOCH = (1980, 1, 1, 0, 0, 0)


def _flatten_state(
    state: Dict[str, Any], prefix: str = ""
) -> Tuple[Dict[str, np.ndarray], Dict[str, Any]]:
    arrays: Dict[str, np.ndarray] = {}
    scalars: Dict[str, Any] = {}
    for key, value in state.items():
        if _SEP in str(key):
            raise ValueError(f"State key {key!r} must not contain {_SEP!r}")
        path = f"{prefix}{_SEP}{key}" if prefix else str(key)
        if isinstance(value, dict):
            sub_arrays, sub_scalars = _flatten_state(value, path)
            arrays.update(sub_arrays)
            scalars.update(sub_scalars)
        elif hasattr(value, "__array__") and not isinstance(value, (int, float, bool)):
            arrays[path] = np.asarray(value)
        else:
            scalars[path] = value
    return arrays, scalars


def _unflatten_state(
    arrays: Dict[str, np.ndarray], scalars: Dict[str, Any]
) -> Dict[str, Any]:
    state: Dict[str, Any] = {}
    for path, value in list(arrays.items()) + list(scalars.items()):
        parts = path.split(_SEP)
        node = state
        for part in parts[:-1]:
            node = node.setdefault(part, {})
        node[parts[-1]] = value
    return state


def _write_state_npz(path: str, arrays: Dict[str, np.ndarray]) -> None:
    """``np.savez`` twin with DETERMINISTIC bytes: numpy stamps each zip
    member with the wall clock, so two saves of identical arrays differ —
    which would break manifest-hash comparison between a server's artifact
    and its ``/download-model`` blob. Same format (``np.load`` reads it),
    fixed ZIP-epoch timestamps, sorted member order. Streamed: a leaf goes
    into its member in numpy's own chunks, so a model of gigabytes is
    written without a second copy of any leaf on the host."""
    from numpy.lib import format as npformat

    with zipfile.ZipFile(path, "w", zipfile.ZIP_STORED, allowZip64=True) as zf:
        for name in sorted(arrays):
            array = np.asarray(arrays[name])
            info = zipfile.ZipInfo(name + ".npy", date_time=_ZIP_EPOCH)
            info.external_attr = 0o644 << 16
            # a member's size is not known to the archive until it is
            # written: only a leaf that may pass 2 GiB gets the wider header,
            # so every smaller model's file is byte for byte what one
            # ``writestr`` of the whole member gave
            with zf.open(info, "w", force_zip64=array.nbytes >= 2**31 - 2**16) as member:
                npformat.write_array(member, array, allow_pickle=False)


def write_artifact_files(
    obj: Any,
    dest_dir: str,
    metadata: Optional[Dict[str, Any]] = None,
    precision: Optional[str] = None,
) -> None:
    """Write the raw artifact files (NO atomicity, NO manifest) into an
    existing directory — the writer the store's staged commits wrap. Only
    :func:`dump` and ``store.commit_generation`` callers should use this
    directly.

    ``precision``: the machine's rung on the precision ladder (§19).
    ``"int8"`` additionally writes ``quant_int8.npz`` — the per-tensor
    quantized weights + scales — beside ``state.npz``, through the same
    staged commit, so the manifest hashes it like every other artifact
    file. The f32 state file is always written untouched (the host path
    and any future re-precision build read it)."""
    from .. import precision as precision_mod

    definition = pipeline_into_definition(obj)
    with open(os.path.join(dest_dir, DEFINITION_FILE), "w") as fh:
        json.dump(definition, fh, indent=2)
    state = obj.get_state() if hasattr(obj, "get_state") else {}
    arrays, scalars = _flatten_state(state)
    _write_state_npz(os.path.join(dest_dir, STATE_FILE), arrays)
    with open(os.path.join(dest_dir, STATE_META_FILE), "w") as fh:
        json.dump(scalars, fh, indent=2, sort_keys=True)
    if precision_mod.validate(precision) == "int8":
        quant = precision_mod.quantized_arrays_for(obj)
        if quant is not None:
            _write_state_npz(
                os.path.join(dest_dir, precision_mod.QUANT_INT8_FILE), quant
            )
    if metadata is not None:
        with open(os.path.join(dest_dir, METADATA_FILE), "w") as fh:
            json.dump(metadata, fh, indent=2, default=str)


def dump(obj: Any, dest_dir: str, metadata: Optional[Dict[str, Any]] = None) -> str:
    """Persist a fitted pipeline/estimator to ``dest_dir``; returns the dir.

    All-or-nothing: files are staged in a hidden sibling dir, fsync'd,
    manifested (per-file SHA-256 — see ``store/``), and renamed into
    place. A crash mid-dump leaves any previous ``dest_dir`` content
    untouched and serving."""
    with atomic_commit(dest_dir, name=os.path.basename(dest_dir)) as staging:
        write_artifact_files(obj, staging, metadata=metadata)
    return dest_dir


def load(source_dir: str, *, allow_external: bool = False) -> Any:
    """Rebuild the fitted pipeline persisted by :func:`dump`.

    Integrity first: the artifact's manifest is verified (every file
    present, sizes and SHA-256 matching) BEFORE anything is deserialized;
    a torn or tampered artifact raises the store's typed errors
    (``ManifestMissing`` / ``ArtifactIncomplete`` / ``ArtifactCorrupt`` —
    all ``StoreError``), which the server maps to quarantine rather than
    a 500. Generation roots resolve through their ``CURRENT`` pointer.

    The artifact's definition is treated as *data*, not config: by default
    class/function resolution is restricted to this package, so a tampered
    ``definition.json`` (e.g. fetched from a spoofed server via
    ``/download-model``) cannot instantiate arbitrary importables.
    Artifacts that legitimately reference an external plugin class load
    with ``allow_external=True`` (an explicit trust statement about the
    artifact), or after appending the plugin's package prefix to
    ``from_definition._TRUSTED_PREFIXES`` once at startup.
    """
    source_dir = resolve_artifact_dir(source_dir)
    verify_artifact(source_dir)
    with open(os.path.join(source_dir, DEFINITION_FILE)) as fh:
        definition = json.load(fh)
    obj = pipeline_from_definition(definition, allow_external=allow_external)
    with np.load(os.path.join(source_dir, STATE_FILE)) as npz:
        arrays = {key: npz[key] for key in npz.files}
    scalars: Dict[str, Any] = {}
    meta_path = os.path.join(source_dir, STATE_META_FILE)
    if os.path.exists(meta_path):
        with open(meta_path) as fh:
            scalars = json.load(fh)
    state = _unflatten_state(arrays, scalars)
    if hasattr(obj, "set_state"):
        obj.set_state(state)
    return obj


def load_metadata(source_dir: str) -> Dict[str, Any]:
    try:
        source_dir = resolve_artifact_dir(source_dir)
    except Exception:  # lint: allow-swallow(torn generation root: metadata is best-effort context; verified load is the loud path)
        return {}
    path = os.path.join(source_dir, METADATA_FILE)
    if not os.path.exists(path):
        return {}
    with open(path) as fh:
        return json.load(fh)


def dumps(obj: Any, metadata: Optional[Dict[str, Any]] = None) -> bytes:
    """Single-blob form of :func:`dump` (in-memory tar) — the payload of the
    server's ``GET /download-model``.

    Byte-deterministic: tar headers carry zeroed mtime/uid/gid/ownership,
    members are sorted, the gzip wrapper's mtime is zeroed, and the inner
    ``state.npz`` uses fixed zip timestamps — so the same fitted object
    always produces an identical blob, and its per-file manifest hashes
    match the server's on-disk artifact."""
    import gzip
    import tempfile

    buffer = io.BytesIO()
    with tempfile.TemporaryDirectory() as tmp:
        dump(obj, tmp, metadata=metadata)
        with gzip.GzipFile(fileobj=buffer, mode="wb", mtime=0) as gz:
            with tarfile.open(fileobj=gz, mode="w") as tar:
                for name in sorted(os.listdir(tmp)):
                    path = os.path.join(tmp, name)
                    info = tar.gettarinfo(path, arcname=name)
                    info.mtime = 0
                    info.uid = info.gid = 0
                    info.uname = info.gname = ""
                    info.mode = 0o644
                    with open(path, "rb") as fh:
                        tar.addfile(info, fh)
    return buffer.getvalue()


def _max_tar_total_bytes() -> int:
    raw = os.environ.get(MAX_TAR_TOTAL_BYTES_ENV, "")
    return int(raw) if raw else DEFAULT_MAX_TAR_TOTAL_BYTES


def _check_tar_bounds(tar: tarfile.TarFile) -> None:
    """Pre-extraction guard rails: a spoofed ``/download-model`` response
    must not be able to decompression-bomb the client. Header-declared
    sizes are authoritative for extraction (tarfile reads exactly
    ``member.size`` bytes per member), so checking headers bounds the
    bytes written. Duplicate member names are rejected outright — the
    last-wins overwrite they imply is only ever an attack.

    Streams member headers one at a time and bails at the FIRST violation
    — ``getmembers()`` up front would itself be bombable (a few-MB gzip
    blob can declare millions of zero-size members, and materializing a
    ``TarInfo`` per header OOMs the guard before any limit is checked)."""
    limit = _max_tar_total_bytes()
    count = 0
    total = 0
    seen = set()
    while True:
        member = tar.next()
        if member is None:
            break
        count += 1
        if count > MAX_TAR_MEMBERS:
            raise ValueError(
                f"Artifact tar has over {MAX_TAR_MEMBERS} members; a model "
                "artifact has at most a handful — refusing to extract"
            )
        total += max(0, member.size)
        if total > limit:
            raise ValueError(
                f"Artifact tar declares over {limit} decompressed bytes "
                f"({MAX_TAR_TOTAL_BYTES_ENV} to raise) — refusing to extract"
            )
        name = os.path.normpath(member.name)
        if name in seen:
            raise ValueError(
                f"Artifact tar repeats member {member.name!r} — refusing "
                "to extract (duplicate names imply overwrite games)"
            )
        seen.add(name)


def loads(blob: bytes, *, allow_external: bool = False) -> Any:
    """Inverse of :func:`dumps` (same trust gate as :func:`load`)."""
    import tempfile

    with tempfile.TemporaryDirectory() as tmp:
        with tarfile.open(fileobj=io.BytesIO(blob), mode="r:gz") as tar:
            _check_tar_bounds(tar)
            try:
                tar.extractall(tmp, filter="data")
            except TypeError:
                # Python < 3.10.12/3.11.4 lacks extractall(filter=); apply
                # the same path-traversal guard manually rather than
                # extracting unfiltered
                _safe_extract(tar, tmp)
        return load(tmp, allow_external=allow_external)


def _safe_extract(tar: tarfile.TarFile, dest: str) -> None:
    """Manual equivalent of ``filter="data"``: plain files/dirs only, no
    absolute paths, no ``..`` escapes, no links."""
    dest_real = os.path.realpath(dest)
    for member in tar.getmembers():
        if not (member.isfile() or member.isdir()):
            raise ValueError(
                f"Refusing to extract non-regular member {member.name!r}"
            )
        target = os.path.realpath(os.path.join(dest, member.name))
        if not (target == dest_real or target.startswith(dest_real + os.sep)):
            raise ValueError(
                f"Refusing to extract {member.name!r} outside target dir"
            )
    tar.extractall(dest)
