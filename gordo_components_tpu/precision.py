"""The per-machine precision ladder: f32 / bf16 / int8 scoring.

A machine's numeric precision is a FIRST-CLASS artifact property, chosen
at build time (``gordo build --precision``, fleet ``--precision-map``),
recorded in the artifact's build metadata, validated on load, and carried
through every serving layer (docs/ARCHITECTURE.md §19):

- **f32** — the default; the scoring path is bit-identical to a build
  that never heard of this module.
- **bf16** — weights are stored (host and device) as bfloat16 and the
  network forward pass computes in bf16; everything around it — scaler
  affines, residuals, error scaling, the L2 — stays f32, and every
  output array is f32. Halves the stacked tree's device bytes.
- **int8** — weights are quantized per-tensor (symmetric, scale =
  max|w|/127) and stay int8 ON DEVICE; the jitted closure dequantizes
  into f32 and accumulates in f32. Quarters the stacked tree's weight
  bytes. The quantized arrays + scales are committed INTO the artifact
  (``quant_int8.npz``, hashed by the manifest like every other file) so
  serve-time quantization is a load, not a recompute — and the f32
  ``state.npz`` stays untouched for the host path and for rebuilding at
  another precision.

Downgraded precisions trade accuracy for speed and residency; the trade
is GATED, not assumed: the parity budgets below bound how far bf16/int8
total anomaly scores may drift from the f32 reference (normalized to the
f32 score scale — raw relative error explodes where residuals cancel to
~0), and ``tools/quant_smoke.py`` measures them on every run.
Anomaly-threshold flip rates across precisions are measured and reported
there too, never silently absorbed.
"""

from __future__ import annotations

import logging
import os
from typing import Any, Dict, Optional, Tuple

import numpy as np

logger = logging.getLogger(__name__)

#: the ladder, in descending width; also the `--precision` CLI choices
PRECISIONS = ("f32", "bf16", "int8")
DEFAULT_PRECISION = "f32"

#: artifact file holding the int8-quantized weights + per-tensor scales
#: (committed beside state.npz through the same atomic path, so the
#: manifest hashes it and a torn/tampered copy fails verification)
QUANT_INT8_FILE = "quant_int8.npz"

# parity error budgets: max |downgraded - f32| of total_anomaly_score,
# normalized by the mean f32 total score over the comparison set (see
# parity_error). Raw rtol is the wrong ruler here — residuals that
# cancel toward zero make per-element relative error unbounded while the
# actual anomaly signal is unaffected. Defaults hold with margin on the
# smoke's shapes (measured in tools/quant_smoke.py); GORDO_PARITY_RTOL_*
# override for fleets whose models are more (or less) sensitive.
_DEFAULT_BUDGETS = {"f32": 0.0, "bf16": 0.02, "int8": 0.08}
_BUDGET_ENV = {
    "bf16": "GORDO_PARITY_RTOL_BF16",
    "int8": "GORDO_PARITY_RTOL_INT8",
}


def validate(value: Optional[str]) -> str:
    """Normalize + validate a precision value (None/"" → f32). Raises
    ``ValueError`` on anything outside the ladder — the load path turns
    that into a quarantined machine, never a silently-f32 one."""
    if value in (None, ""):
        return DEFAULT_PRECISION
    normalized = str(value).strip().lower()
    if normalized not in PRECISIONS:
        raise ValueError(
            f"unknown precision {value!r} (expected one of {PRECISIONS})"
        )
    return normalized


def resolve_default(explicit: Optional[str] = None) -> str:
    """Build-time precision resolution: explicit flag beats the
    ``GORDO_PRECISION_DEFAULT`` env default beats f32. A bad env value
    fails loudly here — at build time, where it is cheap — rather than
    producing a fleet of mislabeled artifacts."""
    if explicit:
        return validate(explicit)
    return validate(os.environ.get("GORDO_PRECISION_DEFAULT"))


def of_metadata(metadata: Dict[str, Any]) -> str:
    """The validated precision an artifact's build metadata pins
    (absent → f32, so every pre-ladder artifact keeps serving f32)."""
    return validate((metadata or {}).get("precision"))


def error_budget(precision: str) -> float:
    """The declared parity budget for a precision (see module docstring
    for the normalization), env-overridable per rung."""
    precision = validate(precision)
    env = _BUDGET_ENV.get(precision)
    if env:
        raw = os.environ.get(env)
        if raw:
            try:
                return max(0.0, float(raw))
            except ValueError:
                logger.warning(
                    "%s=%r is not a float; using the default %s budget",
                    env, raw, precision,
                )
    return _DEFAULT_BUDGETS[precision]


def parity_error(reference: np.ndarray, candidate: np.ndarray) -> float:
    """Normalized parity error between two total-anomaly-score arrays:
    ``max|candidate - reference| / mean|reference|``. The one ruler the
    smoke harness and the tests both measure with."""
    reference = np.asarray(reference, np.float64)
    candidate = np.asarray(candidate, np.float64)
    scale = float(np.mean(np.abs(reference)))
    if scale == 0.0:
        scale = 1.0
    return float(np.max(np.abs(candidate - reference))) / scale


# -- int8 quantization -------------------------------------------------------
def quantize_array_int8(array: np.ndarray) -> Tuple[np.ndarray, np.float32]:
    """Symmetric per-tensor int8 quantization: ``q = round(w / scale)``
    with ``scale = max|w| / 127``. Deterministic (pure numpy, no RNG), so
    build-time and serve-time quantization of the same weights are
    byte-identical — which is what lets the stored sidecar and an
    on-the-fly fallback serve the same scores."""
    array = np.asarray(array, np.float32)
    peak = float(np.max(np.abs(array))) if array.size else 0.0
    scale = peak / 127.0 if peak > 0.0 else 1.0
    q = np.clip(np.round(array / scale), -127, 127).astype(np.int8)
    return q, np.float32(scale)


def quantize_tree_int8(params: Any) -> Tuple[Any, Any]:
    """Quantize every leaf of a params pytree; returns ``(q_tree,
    scale_tree)`` with the SAME treedef (the engine stacks and gathers
    them in lockstep with the scales)."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(params)
    pairs = [quantize_array_int8(leaf) for leaf in leaves]
    qs = [q for q, _ in pairs]
    scales = [s for _, s in pairs]
    return (
        jax.tree_util.tree_unflatten(treedef, qs),
        jax.tree_util.tree_unflatten(treedef, scales),
    )


def dequantize_tree_int8(q_tree: Any, scale_tree: Any) -> Any:
    """Host-side inverse (tests, drift analysis); the serving closure
    does the same math in-program with jnp."""
    import jax

    return jax.tree_util.tree_map(
        lambda q, s: np.asarray(q, np.float32) * np.float32(s),
        q_tree, scale_tree,
    )


def quantized_arrays_for(model: Any) -> Optional[Dict[str, np.ndarray]]:
    """Flattened ``{"q/<path>": int8, "s/<path>": f32-scale}`` arrays for
    an anomaly pipeline's estimator params — the ``quant_int8.npz``
    payload. ``None`` when the model has no liftable estimator (the
    engine would skip it to the host path anyway, which always serves
    f32)."""
    from .models.analysis import analyze_model
    from .serializer.persistence import _flatten_state

    try:
        est = analyze_model(model).estimator
        params = est.params_
        if params is None:
            return None
        import jax

        params = jax.device_get(params)
    except (ValueError, AttributeError, TypeError):
        return None
    q_tree, scale_tree = quantize_tree_int8(params)
    arrays, _ = _flatten_state({"q": q_tree, "s": scale_tree})
    return arrays


def load_quantized(artifact_dir: str) -> Optional[Tuple[Any, Any]]:
    """The ``(q_tree, scale_tree)`` pair stored in an artifact's
    ``quant_int8.npz``, or ``None`` when the artifact carries none (the
    engine then quantizes the f32 params on the fly — same formula, same
    bytes). Callers pass a RESOLVED artifact dir; integrity is the
    manifest's job (``load``/``verify_artifact`` already hashed this file
    before anything trusts the directory)."""
    from .serializer.persistence import _unflatten_state

    path = os.path.join(artifact_dir, QUANT_INT8_FILE)
    if not os.path.isfile(path):
        return None
    with np.load(path) as npz:
        arrays = {key: npz[key] for key in npz.files}
    tree = _unflatten_state(arrays, {})
    q_tree, scale_tree = tree.get("q"), tree.get("s")
    if q_tree is None or scale_tree is None:
        raise ValueError(
            f"{path}: malformed quantized sidecar (missing q/ or s/ trees)"
        )
    return q_tree, scale_tree


def parse_precision_map(spec: Optional[str]) -> Dict[str, str]:
    """``--precision-map`` parser: ``name=precision`` pairs (comma- or
    semicolon-separated), or a path to a YAML file mapping names to
    precisions. Every value is validated here so a typo fails the build
    command, not a fleet of artifacts later."""
    if not spec:
        return {}
    mapping: Dict[str, str] = {}
    if os.path.exists(spec):
        import yaml

        with open(spec) as fh:
            loaded = yaml.safe_load(fh)
        if not isinstance(loaded, dict):
            raise ValueError(
                f"--precision-map file {spec!r} must parse to a mapping"
            )
        items = loaded.items()
    else:
        items = []
        for pair in spec.replace(";", ",").split(","):
            pair = pair.strip()
            if not pair:
                continue
            if "=" not in pair:
                raise ValueError(
                    f"--precision-map entry {pair!r} is not name=precision"
                )
            name, _, value = pair.partition("=")
            items.append((name.strip(), value.strip()))
    for name, value in items:
        if not name:
            raise ValueError("--precision-map entry has an empty name")
        mapping[str(name)] = validate(str(value))
    return mapping
