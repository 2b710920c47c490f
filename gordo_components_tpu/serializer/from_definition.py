"""Definition dict/YAML → live pipeline.

Reference parity: ``gordo_components/serializer/from_definition.py``
[UNVERIFIED]. A definition node is either

- a dotted path string (instantiated with no kwargs),
- ``{dotted.path.Class: {kwargs}}`` (single-key mapping), or
- inside kwargs, lists/dicts recursed into (``steps`` lists, nested
  regressors, FunctionTransformer funcs).

Ported gordo configs name ``sklearn.*`` and ``gordo_components.*`` classes;
an alias table rewrites those onto this package's TPU-native equivalents so
reference fleet YAML loads unchanged.
"""

from __future__ import annotations

from typing import Any, Dict, Union

import yaml

from ..utils.config import resolve_dotted_path

# reference-world dotted paths → TPU-native equivalents
_ALIASES: Dict[str, str] = {
    # sklearn surface the reference's configs use
    "sklearn.pipeline.Pipeline": "gordo_components_tpu.models.pipeline.Pipeline",
    "sklearn.pipeline.FeatureUnion": (
        "gordo_components_tpu.models.pipeline.FeatureUnion"
    ),
    "sklearn.compose.TransformedTargetRegressor": (
        "gordo_components_tpu.models.pipeline.TransformedTargetRegressor"
    ),
    "sklearn.preprocessing.MinMaxScaler": (
        "gordo_components_tpu.models.transformers.MinMaxScaler"
    ),
    "sklearn.preprocessing.data.MinMaxScaler": (
        "gordo_components_tpu.models.transformers.MinMaxScaler"
    ),
    "sklearn.preprocessing.StandardScaler": (
        "gordo_components_tpu.models.transformers.StandardScaler"
    ),
    "sklearn.preprocessing.data.StandardScaler": (
        "gordo_components_tpu.models.transformers.StandardScaler"
    ),
    "sklearn.preprocessing.FunctionTransformer": (
        "gordo_components_tpu.models.transformers.FunctionTransformer"
    ),
    # the reference's own package paths
    "gordo_components.model.models.KerasAutoEncoder": (
        "gordo_components_tpu.models.models.DenseAutoEncoder"
    ),
    "gordo_components.model.models.KerasLSTMAutoEncoder": (
        "gordo_components_tpu.models.models.LSTMAutoEncoder"
    ),
    "gordo_components.model.models.KerasLSTMForecast": (
        "gordo_components_tpu.models.models.LSTMForecast"
    ),
    "gordo_components.model.anomaly.diff.DiffBasedAnomalyDetector": (
        "gordo_components_tpu.models.anomaly.diff.DiffBasedAnomalyDetector"
    ),
    "gordo_components.model.transformer_funcs.general.multiply": (
        "gordo_components_tpu.models.transformers.multiply"
    ),
    "gordo_components.model.transformers.imputer.InfImputer": (
        "gordo_components_tpu.models.transformers.InfImputer"
    ),
}
# short names for the local zoo, so hand-written configs stay terse
_SHORT_NAMES: Dict[str, str] = {
    name: f"gordo_components_tpu.models.models.{name}"
    for name in (
        "DenseAutoEncoder",
        "LSTMAutoEncoder",
        "LSTMForecast",
        "MultiStepForecast",
        "PatchTSTAutoEncoder",
        "PatchTSTForecast",
        "MoEMLAForecast",
        "MoEGQAForecast",
        "AfMoEForecast",
        "KerasAutoEncoder",
        "KerasLSTMAutoEncoder",
        "KerasLSTMForecast",
    )
}
_SHORT_NAMES.update(
    {
        "Pipeline": "gordo_components_tpu.models.pipeline.Pipeline",
        "FeatureUnion": "gordo_components_tpu.models.pipeline.FeatureUnion",
        "TransformedTargetRegressor": (
            "gordo_components_tpu.models.pipeline.TransformedTargetRegressor"
        ),
        "MinMaxScaler": "gordo_components_tpu.models.transformers.MinMaxScaler",
        "StandardScaler": "gordo_components_tpu.models.transformers.StandardScaler",
        "InfImputer": "gordo_components_tpu.models.transformers.InfImputer",
        "FunctionTransformer": (
            "gordo_components_tpu.models.transformers.FunctionTransformer"
        ),
        "DiffBasedAnomalyDetector": (
            "gordo_components_tpu.models.anomaly.diff.DiffBasedAnomalyDetector"
        ),
    }
)


# prefixes an *untrusted* definition (one loaded from an artifact rather
# than authored by the operator) is allowed to resolve into; operators
# deploying their own plugin package may append its prefix here once at
# startup (that is an explicit trust decision, like installing the plugin)
_TRUSTED_PREFIXES: list = ["gordo_components_tpu."]


def resolve_class_path(path: str, *, allow_external: bool = True) -> Any:
    """Alias- and short-name-aware dotted-path resolution (also used by
    FunctionTransformer to resolve ``func`` strings lazily).

    ``allow_external=False`` is the artifact-load mode: resolution is
    restricted to this package (every alias/short name lands there), so a
    definition.json from a spoofed server cannot instantiate arbitrary
    importables (e.g. ``os.system``) with attacker kwargs.
    """
    path = _ALIASES.get(path, path)
    path = _SHORT_NAMES.get(path, path)
    if "." not in path:
        raise ValueError(
            f"Unknown class short name {path!r}; known: {sorted(_SHORT_NAMES)}"
        )
    if not allow_external and not path.startswith(tuple(_TRUSTED_PREFIXES)):
        raise ValueError(
            f"Refusing to resolve external dotted path {path!r} while "
            "loading an artifact: artifact definitions may only reference "
            "gordo_components_tpu classes (or their sklearn/"
            "gordo_components aliases). Rebuild the model locally, or load "
            "its definition yourself via pipeline_from_definition(...) if "
            "you authored and trust it."
        )
    return resolve_dotted_path(path)


def _is_class_definition(node: Any) -> bool:
    """A single-key mapping whose key looks like a class reference."""
    if isinstance(node, dict) and len(node) == 1:
        key = next(iter(node))
        return isinstance(key, str) and (
            key in _SHORT_NAMES or key in _ALIASES or "." in key
        )
    return False


def _build_string(s: str, allow_external: bool) -> Any:
    """Instantiate strings that resolve to classes (bare steps like
    ``sklearn.preprocessing.data.MinMaxScaler``); keep everything else —
    including function dotted paths like FunctionTransformer's ``func``,
    which resolve lazily — as plain strings."""
    if not (s in _SHORT_NAMES or s in _ALIASES or "." in s):
        return s
    try:
        target = resolve_class_path(s, allow_external=allow_external)
    except ValueError:
        if not allow_external and (s in _SHORT_NAMES or s in _ALIASES):
            raise  # a known name refused by the trust gate must not degrade
            # into a silently-passed-through string
        return s
    return target() if isinstance(target, type) else s


def _build(node: Any, allow_external: bool = True) -> Any:
    if isinstance(node, str):
        return _build_string(node, allow_external)
    if _is_class_definition(node):
        path, kwargs = next(iter(node.items()))
        target = resolve_class_path(path, allow_external=allow_external)
        if not isinstance(target, type):
            raise ValueError(f"{path!r} resolves to a non-class; cannot take kwargs")
        if kwargs is None:
            kwargs = {}
        if not isinstance(kwargs, dict):
            raise ValueError(
                f"Definition for {path!r} must map to kwargs, got {type(kwargs)}"
            )
        built_kwargs = {
            k: (
                _build_steps(v, allow_external)
                if k in ("steps", "transformer_list") and isinstance(v, list)
                else _build_value(v, allow_external)
            )
            for k, v in kwargs.items()
        }
        instance = target(**built_kwargs)
        if not allow_external:
            # lazily-resolved function strings (FunctionTransformer.func)
            # must inherit the trust gate, or 'os.system' would execute on
            # the first transform() of a loaded artifact
            try:
                instance._allow_external_funcs = False
            except AttributeError:
                pass
        return instance
    return node


def _build_steps(value: list, allow_external: bool) -> list:
    """Steps / transformer lists: a ``[name, definition]`` 2-list element is
    a NAMED step pair (into_definition writes these) — the name must stay a
    plain string even when it collides with a class short name like
    ``"MinMaxScaler"``, or the pair would degenerate into a broken bare
    step. Everything else is an ordinary (unnamed) step definition."""
    out = []
    for el in value:
        if (
            isinstance(el, list)
            and len(el) == 2
            and isinstance(el[0], str)
            and (_is_class_definition(el[1]) or isinstance(el[1], str))
        ):
            out.append((el[0], _build_value(el[1], allow_external)))
        else:
            out.append(_build_value(el, allow_external))
    return out


def _build_value(value: Any, allow_external: bool = True) -> Any:
    """Recurse into kwarg values: lists of definitions (steps lists), nested
    definitions (regressor/base_estimator), plain data otherwise."""
    if isinstance(value, str):
        return _build_string(value, allow_external)
    if _is_class_definition(value):
        return _build(value, allow_external)
    if isinstance(value, list):
        return [_build_value(v, allow_external) for v in value]
    if isinstance(value, dict):
        return {k: _build_value(v, allow_external) for k, v in value.items()}
    return value


def pipeline_from_definition(
    definition: Union[str, Dict[str, Any]], *, allow_external: bool = True
) -> Any:
    """Materialize a model definition (dict, or YAML string) into a live
    (unfitted) pipeline/estimator graph.

    ``allow_external=True`` (default) is the *build* path: the operator
    authored the config, so dotted paths outside this package are a plugin
    feature. ``allow_external=False`` is the *artifact-load* path
    (``serializer.load``/``loads``): definitions are data from disk or a
    remote server and may only reference this package's classes.
    """
    if isinstance(definition, str):
        definition = yaml.safe_load(definition)
    built = _build(definition, allow_external)
    if isinstance(built, (str, dict)) or built is definition:
        raise ValueError(
            "Model definition must be a single-key {dotted.path: kwargs} "
            f"mapping or a class dotted-path string; got: {definition!r}"
        )
    return built


# reference-era alias
from_definition = pipeline_from_definition
