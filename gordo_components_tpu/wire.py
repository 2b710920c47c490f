"""Scoring wire formats: negotiated binary (npz) + fast-JSON encoding.

The serving data plane's transport half (docs/ARCHITECTURE.md §12). The
original response path serialized every score via ``.tolist()`` +
``json.dumps`` — one Python float object per array element, which came
to dominate host time once device dispatch was fast. Two
fixes, negotiated per request:

- ``application/x-gordo-npz`` (``Accept`` request header / response
  ``Content-Type``): ONE ``np.savez`` blob carrying the four
  :class:`~.server.engine.ScoreResult` arrays at native float32 plus a
  small JSON header (timestamps, thresholds). ~5x smaller and ~5x cheaper
  to encode than JSON at bench shapes, and the decoder hands back numpy
  arrays directly — no per-element churn on either side.
- fast-JSON fallback (the default ``application/json`` path): the array
  blocks are rendered row-at-a-time with a ``%.17g`` printf format and
  spliced into the payload template, skipping the generic encoder's
  per-element object walk (~2-3x at bench shapes). 17 significant digits
  round-trip float64 exactly, so consumers parse the same values the
  legacy ``.tolist()`` + ``json.dumps`` path produced, and decoded values
  cast to float32 are byte-identical to the npz path — the parity gate
  both formats are tested against.

This module is deliberately dependency-light (numpy + stdlib only): the
client imports it without dragging in jax or the server stack.
"""

from __future__ import annotations

import io
import json
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

NPZ_CONTENT_TYPE = "application/x-gordo-npz"

# the ScoreResult payload fields, in response order
SCORE_FIELDS = (
    "model-input",
    "model-output",
    "tag-anomaly-scores",
    "total-anomaly-score",
)

# npz member carrying the JSON header (timestamps, thresholds, ...) as
# utf-8 bytes; everything else in the archive is a payload array
_HEADER_MEMBER = "__header__"


def content_type_of(header: Optional[str]) -> str:
    """Normalized media type of a ``Content-Type`` header value (lowercase,
    parameters stripped) — the one parse both client transports dispatch
    npz-vs-JSON responses on."""
    return (header or "").split(";")[0].strip().lower()


def wants_npz(accept: Optional[str]) -> bool:
    """Does the request's ``Accept`` header ask for the binary format?
    Minimal negotiation on purpose: any listed ``application/x-gordo-npz``
    media type opts in (q-values are ignored — a client that lists the
    format at all speaks it); everything else keeps the JSON default."""
    if not accept:
        return False
    for part in accept.split(","):
        if part.split(";")[0].strip().lower() == NPZ_CONTENT_TYPE:
            return True
    return False


# -- binary format -----------------------------------------------------------
def encode_npz(
    arrays: Dict[str, np.ndarray], header: Optional[Dict[str, Any]] = None
) -> bytes:
    """One ``np.savez`` blob: each array at its native dtype plus the JSON
    ``header`` riding along as a uint8 member. Uncompressed — scores are
    high-entropy floats, and the format exists to cut encode CPU, not to
    trade it back for deflate."""
    buf = io.BytesIO()
    members: Dict[str, np.ndarray] = {
        name: np.ascontiguousarray(arr) for name, arr in arrays.items()
    }
    members[_HEADER_MEMBER] = np.frombuffer(
        json.dumps(header or {}, default=str).encode("utf-8"), dtype=np.uint8
    )
    np.savez(buf, **members)
    return buf.getvalue()


def decode_npz(blob: bytes) -> Tuple[Dict[str, np.ndarray], Dict[str, Any]]:
    """``encode_npz`` inverse → ``(arrays, header)``. ``allow_pickle`` stays
    False (the default): the wire must never deserialize objects. Any
    decode failure (truncated blob, bad zip, garbage header) normalizes to
    ``ValueError`` so transports can treat it like any other bad body."""
    try:
        with np.load(io.BytesIO(blob)) as archive:
            header: Dict[str, Any] = {}
            if _HEADER_MEMBER in archive.files:
                header = json.loads(
                    archive[_HEADER_MEMBER].tobytes().decode("utf-8")
                )
            arrays = {
                name: archive[name]
                for name in archive.files
                if name != _HEADER_MEMBER
            }
    except ValueError:
        raise
    except Exception as exc:
        raise ValueError(f"not a readable npz payload: {exc}") from exc
    return arrays, header


def payload_from_npz(blob: bytes) -> Dict[str, Any]:
    """Decode an npz response into the SAME payload shape the JSON wire
    carries — ``{"data": {<arrays>, "timestamps": [...]}, <extras>}`` —
    so one downstream consumer (the client's frame builder) serves both
    formats. Array values stay numpy arrays (that is the point)."""
    arrays, header = decode_npz(blob)
    data: Dict[str, Any] = dict(arrays)
    extras = {}
    for key, value in header.items():
        if key == "timestamps":
            data["timestamps"] = value
        else:
            extras[key] = value
    return {"data": data, **extras}


# -- fast JSON ---------------------------------------------------------------
def format_float_array(arr: np.ndarray) -> str:
    """A numeric array as a JSON array literal, rendered row-at-a-time with
    printf formatting instead of per-element Python float objects.
    ``%.17g`` round-trips float64 exactly, and ``.tolist()`` widens every
    dtype to float64 first, so a JSON consumer parses the EXACT values the
    legacy ``json.dumps(arr.tolist())`` encoder produced — float32 engine
    scores included (their float64 widening is preserved bit-for-bit; only
    the textual form may differ, e.g. ``5`` vs ``5.0`` or a non-shortest
    digit string). Non-finite values fall back to the generic encoder —
    ``%g`` would print bare ``nan``/``inf``, which is not JSON (the
    stdlib's ``NaN``/``Infinity`` extension at least round-trips through
    every consumer this repo ships)."""
    arr = np.asarray(arr)
    if not np.isfinite(arr).all():
        return json.dumps(arr.tolist())
    if arr.ndim == 1:
        if arr.size == 0:
            return "[]"
        fmt = ",".join(["%.17g"] * arr.shape[0])
        return "[" + fmt % tuple(arr.tolist()) + "]"
    if arr.ndim != 2:
        return json.dumps(arr.tolist())
    if arr.shape[0] == 0:
        return "[]"
    fmt = ",".join(["%.17g"] * arr.shape[1])
    rows = (fmt % tuple(row) for row in arr.tolist())
    return "[[" + "],[".join(rows) + "]]"


def encode_scored_json(
    arrays: Dict[str, np.ndarray],
    timestamps: Optional[List[str]] = None,
    extras: Optional[Dict[str, Any]] = None,
) -> str:
    """The scoring response body — schema-identical to the historical
    ``json.dumps({"data": {...}})`` path — with the array blocks rendered
    by :func:`format_float_array` and spliced into the template."""
    parts = ["{\"data\":{"]
    first = True
    for name, arr in arrays.items():
        if not first:
            parts.append(",")
        first = False
        parts.append(json.dumps(name))
        parts.append(":")
        parts.append(format_float_array(arr))
    if timestamps is not None:
        parts.append(",\"timestamps\":")
        parts.append(json.dumps(timestamps, default=str))
    parts.append("}")
    for key, value in (extras or {}).items():
        parts.append(",")
        parts.append(json.dumps(key))
        parts.append(":")
        parts.append(json.dumps(value, default=str))
    parts.append("}")
    return "".join(parts)
