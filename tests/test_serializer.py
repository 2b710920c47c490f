"""Serializer tests: definition ⇄ pipeline round-trips (including reference
``gordo_components.*`` / ``sklearn.*`` dotted paths via the alias table),
dump/load dir-tree persistence, dumps/loads blobs, and transformer/pipeline
behavior."""

import json
import os

import numpy as np
import pytest

from gordo_components_tpu.models.models import DenseAutoEncoder
from gordo_components_tpu.models.pipeline import (
    Pipeline,
    TransformedTargetRegressor,
    clone_pipeline,
)
from gordo_components_tpu.models.transformers import (
    FunctionTransformer,
    InfImputer,
    MinMaxScaler,
    StandardScaler,
    multiply,
)
from gordo_components_tpu import serializer
from gordo_components_tpu.serializer import (
    dump,
    dumps,
    load,
    load_metadata,
    loads,
    pipeline_from_definition,
    pipeline_into_definition,
)


@pytest.fixture(scope="module")
def X():
    return np.random.default_rng(3).normal(size=(150, 4)).astype(np.float32) * 5 + 2


# ------------------------------------------------------------- transformers
def test_minmax_scaler_sklearn_parity(X):
    import sklearn.preprocessing as skp

    ours = MinMaxScaler(feature_range=(0, 1)).fit(X)
    theirs = skp.MinMaxScaler().fit(X)
    np.testing.assert_allclose(ours.transform(X), theirs.transform(X), atol=1e-5)
    np.testing.assert_allclose(ours.inverse_transform(ours.transform(X)), X, atol=1e-4)


def test_scaler_width_mismatch_raises(X):
    """sklearn parity: transform/inverse_transform validate the feature
    count — a narrower input must raise, not broadcast against (F,) params."""
    for scaler in (MinMaxScaler().fit(X), StandardScaler().fit(X)):
        for bad in (np.ones((4, 1), np.float32), np.ones((4, X.shape[1] + 1))):
            with pytest.raises(ValueError, match="features"):
                scaler.transform(bad)
            with pytest.raises(ValueError, match="features"):
                scaler.inverse_transform(bad)


def test_standard_scaler_sklearn_parity(X):
    import sklearn.preprocessing as skp

    ours = StandardScaler().fit(X)
    theirs = skp.StandardScaler().fit(X)
    np.testing.assert_allclose(ours.transform(X), theirs.transform(X), atol=1e-4)
    partial = StandardScaler(with_std=False).fit(X)
    np.testing.assert_allclose(
        partial.transform(X), X - X.mean(axis=0), atol=1e-4
    )


def test_inf_imputer(X):
    Xi = X.copy()
    Xi[0, 0] = np.inf
    Xi[1, 1] = -np.inf
    out = InfImputer().fit_transform(Xi)
    assert np.isfinite(out).all()
    filled = InfImputer(inf_fill_value=99.0).fit_transform(Xi)
    assert filled[0, 0] == 99.0


def test_function_transformer_multiply(X):
    ft = FunctionTransformer(
        func="gordo_components.model.transformer_funcs.general.multiply",
        kw_args={"factor": 2.0},
    )
    np.testing.assert_allclose(ft.fit_transform(X), multiply(X, 2.0))


# ------------------------------------------------------------------ pipeline
def test_pipeline_fit_predict_score(X):
    pipe = Pipeline(
        [
            ("scaler", MinMaxScaler()),
            ("model", DenseAutoEncoder(kind="feedforward_hourglass", epochs=3,
                                       batch_size=32)),
        ]
    )
    pipe.fit(X)
    assert pipe.predict(X).shape == X.shape
    # scaling should make the AE learn far better than the unscaled smoke runs
    assert pipe.score(X) > -1.0
    assert pipe["scaler"] is pipe[0]


def test_transformed_target_regressor(X):
    ttr = TransformedTargetRegressor(
        regressor=DenseAutoEncoder(kind="feedforward_symmetric", dims=(8,),
                                   epochs=2, batch_size=32),
        transformer=MinMaxScaler(),
    )
    ttr.fit(X)
    pred = ttr.predict(X)
    assert pred.shape == X.shape
    # contract: predict = transformer.inverse_transform(regressor.predict(X))
    np.testing.assert_allclose(
        pred,
        ttr.transformer.inverse_transform(ttr.regressor.predict(X)),
        rtol=1e-5,
    )


# -------------------------------------------------------- from/into definition
REFERENCE_STYLE_DEFINITION = """
sklearn.pipeline.Pipeline:
  steps:
    - sklearn.preprocessing.data.MinMaxScaler
    - gordo_components.model.models.KerasAutoEncoder:
        kind: feedforward_hourglass
        compression_factor: 0.5
        epochs: 2
        batch_size: 32
"""


def test_from_definition_reference_yaml(X):
    pipe = pipeline_from_definition(REFERENCE_STYLE_DEFINITION)
    assert isinstance(pipe, Pipeline)
    assert isinstance(pipe[0], MinMaxScaler)
    assert isinstance(pipe[1], DenseAutoEncoder)
    assert pipe[1].factory_kwargs["compression_factor"] == 0.5
    pipe.fit(X)
    assert pipe.predict(X).shape == X.shape


def test_from_definition_short_names():
    pipe = pipeline_from_definition(
        {"Pipeline": {"steps": ["MinMaxScaler", {"DenseAutoEncoder": {"epochs": 1}}]}}
    )
    assert isinstance(pipe[0], MinMaxScaler)
    assert isinstance(pipe[1], DenseAutoEncoder)


def test_from_definition_nested_ttr():
    obj = pipeline_from_definition(
        {
            "TransformedTargetRegressor": {
                "regressor": {"DenseAutoEncoder": {"epochs": 1}},
                "transformer": "MinMaxScaler",
            }
        }
    )
    assert isinstance(obj, TransformedTargetRegressor)
    assert isinstance(obj.transformer, MinMaxScaler)


def test_from_definition_rejects_garbage():
    with pytest.raises(ValueError):
        pipeline_from_definition({"not a definition": 1, "two keys": 2})
    with pytest.raises(ValueError):
        pipeline_from_definition("no_such_short_name")


def test_round_trip_definition(X):
    pipe = pipeline_from_definition(REFERENCE_STYLE_DEFINITION)
    definition = pipeline_into_definition(pipe)
    rebuilt = pipeline_from_definition(definition)
    assert isinstance(rebuilt[0], MinMaxScaler)
    assert rebuilt[1].get_params() == pipe[1].get_params()
    json.dumps(definition)  # definition must be JSON-able


# ------------------------------------------------------------- dump / load
def test_dump_load_round_trip(X, tmp_path):
    pipe = pipeline_from_definition(REFERENCE_STYLE_DEFINITION)
    pipe.fit(X)
    expected = pipe.predict(X)
    out = str(tmp_path / "model")
    dump(pipe, out, metadata={"name": "machine-1", "user": {"a": 1}})
    assert os.path.exists(os.path.join(out, "definition.json"))
    loaded = load(out)
    np.testing.assert_allclose(loaded.predict(X), expected, rtol=1e-5)
    meta = load_metadata(out)
    assert meta["name"] == "machine-1"
    assert load_metadata(str(tmp_path)) == {}  # missing metadata → empty


def test_dumps_loads_round_trip(X):
    pipe = Pipeline([MinMaxScaler(), DenseAutoEncoder(
        kind="feedforward_symmetric", dims=(6,), epochs=1, batch_size=32)])
    pipe.fit(X)
    blob = dumps(pipe)
    assert isinstance(blob, bytes) and len(blob) > 0
    loaded = loads(blob)
    np.testing.assert_allclose(loaded.predict(X), pipe.predict(X), rtol=1e-5)


def test_dump_load_custom_step_names(X, tmp_path):
    """Custom step names round-trip as [name, definition] pairs, and fitted
    state round-trips independently because it is keyed positionally."""
    pipe = Pipeline([("my_scaler", MinMaxScaler()),
                     ("my_model", DenseAutoEncoder(kind="feedforward_symmetric",
                                                   dims=(6,), epochs=1,
                                                   batch_size=32))])
    pipe.fit(X)
    out = str(tmp_path / "named")
    dump(pipe, out)
    loaded = load(out)
    np.testing.assert_allclose(loaded.predict(X), pipe.predict(X), rtol=1e-5)


def test_clone_pipeline_is_unfitted(X):
    pipe = Pipeline([MinMaxScaler(), DenseAutoEncoder(
        kind="feedforward_symmetric", dims=(6,), epochs=1, batch_size=32)])
    pipe.fit(X)
    fresh = clone_pipeline(pipe)
    assert fresh[0].params_ is None
    assert fresh[1].params_ is None
    fresh.fit(X)  # must be fittable again


# ---------------------------------------------------------------------------
# FeatureUnion (VERDICT r1 #6 / SURVEY §3 serializer row: nested FeatureUnion)
# ---------------------------------------------------------------------------
def test_feature_union_materializes_from_sklearn_path():
    from gordo_components_tpu.models.pipeline import FeatureUnion

    definition = {
        "sklearn.pipeline.FeatureUnion": {
            "transformer_list": [
                "sklearn.preprocessing.MinMaxScaler",
                {"sklearn.preprocessing.StandardScaler": {"with_mean": True}},
            ]
        }
    }
    union = pipeline_from_definition(definition)
    assert isinstance(union, FeatureUnion)
    X = np.random.default_rng(0).normal(size=(50, 3)).astype(np.float32)
    out = union.fit_transform(X)
    assert out.shape == (50, 6)  # both blocks concatenated
    # first block is minmax-scaled to [0, 1]
    assert out[:, :3].min() >= -1e-6 and out[:, :3].max() <= 1 + 1e-6


def test_feature_union_inside_pipeline_round_trips():
    from gordo_components_tpu.models.pipeline import FeatureUnion, Pipeline

    definition = {
        "Pipeline": {
            "steps": [
                {
                    "FeatureUnion": {
                        "transformer_list": ["MinMaxScaler", "StandardScaler"],
                        "transformer_weights": None,
                    }
                },
                {"DenseAutoEncoder": {"kind": "feedforward_hourglass",
                                      "epochs": 1, "batch_size": 16}},
            ]
        }
    }
    pipe = pipeline_from_definition(definition)
    assert isinstance(pipe, Pipeline)
    assert isinstance(pipe.steps[0][1], FeatureUnion)
    # round-trip: into_definition → from_definition → same shape
    rebuilt = pipeline_from_definition(pipeline_into_definition(pipe))
    assert isinstance(rebuilt.steps[0][1], FeatureUnion)
    X = np.random.default_rng(1).normal(size=(64, 4)).astype(np.float32)
    pipe.fit(X)
    pred = pipe.predict(X)
    # the AE's input is the unioned 8-wide feature block, and with y=None an
    # autoencoder reconstructs its own input
    assert pred.shape == (64, 8)


def test_feature_union_weights_scale_blocks():
    from gordo_components_tpu.models.pipeline import FeatureUnion
    from gordo_components_tpu.models.transformers import MinMaxScaler

    union = FeatureUnion(
        [("a", MinMaxScaler()), ("b", MinMaxScaler())],
        transformer_weights={"b": 2.0},
    )
    X = np.random.default_rng(2).normal(size=(20, 2)).astype(np.float32)
    out = union.fit_transform(X)
    np.testing.assert_allclose(out[:, 2:], out[:, :2] * 2.0, atol=1e-6)


def test_feature_union_clone_and_state_round_trip(tmp_path):
    from gordo_components_tpu.models.pipeline import FeatureUnion, clone_pipeline
    from gordo_components_tpu.models.transformers import MinMaxScaler

    union = FeatureUnion([("a", MinMaxScaler())])
    X = np.random.default_rng(3).normal(size=(20, 2)).astype(np.float32)
    union.fit(X)
    fresh = clone_pipeline(union)
    assert fresh.transformer_list[0][1].params_ is None  # unfitted clone
    restored = FeatureUnion([("a", MinMaxScaler())]).set_state(union.get_state())
    np.testing.assert_allclose(restored.transform(X), union.transform(X))


def test_feature_union_weights_survive_round_trip():
    """Names must survive into_definition → from_definition, or
    name-keyed transformer_weights silently stop applying."""
    from gordo_components_tpu.models.pipeline import FeatureUnion
    from gordo_components_tpu.models.transformers import MinMaxScaler

    union = FeatureUnion(
        [("a", MinMaxScaler()), ("b", MinMaxScaler())],
        transformer_weights={"b": 2.0},
    )
    rebuilt = pipeline_from_definition(pipeline_into_definition(union))
    X = np.random.default_rng(5).normal(size=(20, 2)).astype(np.float32)
    np.testing.assert_allclose(
        rebuilt.fit_transform(X), union.fit_transform(X), atol=1e-6
    )


def test_feature_union_unknown_weight_key_rejected():
    from gordo_components_tpu.models.pipeline import FeatureUnion
    from gordo_components_tpu.models.transformers import MinMaxScaler

    with pytest.raises(ValueError, match="match no transformer"):
        FeatureUnion(
            [("a", MinMaxScaler())], transformer_weights={"scaler": 2.0}
        )


# -- artifact-load trust gate (load path treats definitions as data) ---------


def test_load_path_refuses_external_dotted_class(tmp_path):
    """A tampered definition.json naming an arbitrary importable must not
    instantiate it (ADVICE r1: artifact load is not a code-loading API)."""
    import json as _json
    import os as _os

    pipe = Pipeline(steps=[MinMaxScaler()])
    X = np.random.default_rng(0).normal(size=(16, 3)).astype(np.float32)
    pipe.fit(X)
    model_dir = str(tmp_path / "model")
    dump(pipe, model_dir)
    definition_path = _os.path.join(model_dir, "definition.json")
    with open(definition_path) as fh:
        definition = _json.load(fh)
    definition = {"subprocess.Popen": {"args": ["true"]}}
    with open(definition_path, "w") as fh:
        _json.dump(definition, fh)
    # an attacker who can rewrite files can recompute the (unsigned)
    # manifest too — re-sign so the test reaches the TRUST gate, which
    # must hold even for integrity-clean artifacts
    from gordo_components_tpu.store import write_manifest

    write_manifest(model_dir)
    with pytest.raises(ValueError, match="external dotted path"):
        load(model_dir)


def test_load_path_refuses_external_function_transformer_func(tmp_path):
    """FunctionTransformer.func resolves lazily — the trust gate must still
    apply at transform() time for artifacts loaded from disk."""
    import json as _json
    import os as _os

    pipe = Pipeline(
        steps=[FunctionTransformer(func="gordo_components_tpu.models.transformers.multiply")]
    )
    X = np.random.default_rng(0).normal(size=(8, 3)).astype(np.float32)
    pipe.fit(X)
    model_dir = str(tmp_path / "model")
    dump(pipe, model_dir)
    definition_path = _os.path.join(model_dir, "definition.json")
    with open(definition_path) as fh:
        definition = _json.load(fh)
    text = _json.dumps(definition).replace(
        "gordo_components_tpu.models.transformers.multiply", "os.system"
    )
    with open(definition_path, "w") as fh:
        fh.write(text)
    # re-sign the manifest (see test above): the lazy-resolution trust
    # gate is the defense under test, not the integrity check
    from gordo_components_tpu.store import write_manifest

    write_manifest(model_dir)
    loaded = load(model_dir)  # builds fine: func is lazy
    with pytest.raises(ValueError, match="external dotted path"):
        loaded.transform(X)


def test_build_path_still_allows_external_plugins():
    """The operator-authored build path keeps dotted-path plugins working."""
    built = pipeline_from_definition(
        {"fractions.Fraction": {"numerator": 3, "denominator": 4}}
    )
    from fractions import Fraction

    assert built == Fraction(3, 4)


def test_load_path_allows_reference_aliases(tmp_path):
    """sklearn/gordo_components alias spellings land inside the package and
    must keep loading under the trust gate."""
    pipe = pipeline_from_definition(
        {
            "sklearn.pipeline.Pipeline": {
                "steps": ["sklearn.preprocessing.MinMaxScaler"]
            }
        }
    )
    X = np.random.default_rng(0).normal(size=(16, 3)).astype(np.float32)
    pipe.fit(X)
    model_dir = str(tmp_path / "model")
    dump(pipe, model_dir)
    loaded = load(model_dir)
    np.testing.assert_allclose(loaded.transform(X), pipe.transform(X), rtol=1e-6)


def test_named_step_colliding_with_short_name_round_trips(tmp_path):
    """A step literally named "MinMaxScaler" must survive dump/load as a
    NAME, not get materialized into an extra bare step (the [name, def]
    pair and a 2-element bare-steps list are distinguished by element
    shape)."""
    pipe = Pipeline(
        steps=[
            ("MinMaxScaler", MinMaxScaler()),
            ("model", DenseAutoEncoder(kind="feedforward_hourglass",
                                       epochs=2, batch_size=16)),
        ]
    )
    X = np.random.default_rng(0).normal(size=(64, 3)).astype(np.float32)
    pipe.fit(X)
    model_dir = str(tmp_path / "model")
    dump(pipe, model_dir)
    loaded = load(model_dir)
    assert [name for name, _ in loaded.steps] == ["MinMaxScaler", "model"]
    np.testing.assert_allclose(
        loaded.predict(X), pipe.predict(X), rtol=1e-5, atol=1e-5
    )


def test_two_element_bare_steps_list_still_works():
    """steps: [bare_string, definition] is a 2-step pipeline, not a named
    pair — the pair detection must key on the ELEMENT being a 2-list."""
    pipe = pipeline_from_definition(
        {
            "Pipeline": {
                "steps": [
                    "MinMaxScaler",
                    {"DenseAutoEncoder": {"kind": "feedforward_hourglass",
                                          "epochs": 2, "batch_size": 16}},
                ]
            }
        }
    )
    assert len(pipe.steps) == 2
    assert isinstance(pipe.steps[0][1], MinMaxScaler)


def test_load_external_plugin_opt_in(tmp_path):
    """Artifacts that legitimately reference external functions load with
    allow_external=True (an explicit trust statement); the default stays
    locked down."""
    pipe = Pipeline(steps=[FunctionTransformer(func="numpy.abs")])
    X = np.random.default_rng(0).normal(size=(8, 3)).astype(np.float32)
    pipe.fit(X)
    model_dir = str(tmp_path / "model")
    dump(pipe, model_dir)

    locked = load(model_dir)
    with pytest.raises(ValueError, match="external dotted path"):
        locked.transform(X)

    trusted = load(model_dir, allow_external=True)
    np.testing.assert_allclose(trusted.transform(X), np.abs(X), rtol=1e-6)


def test_state_npz_is_byte_for_byte_what_one_write_a_member_gave(tmp_path):
    """The streamed ``state.npz`` (a leaf goes into its member in chunks, so
    a model of gigabytes has no second copy on the host) is the same file,
    byte for byte, as a whole member written at once: the manifest hash of a
    small model's artifact does not depend on which of the two wrote it."""
    import io
    import zipfile

    from numpy.lib import format as npformat

    from gordo_components_tpu.serializer import persistence

    rng = np.random.default_rng(1)
    arrays = {
        "b/kernel": rng.normal(size=(37, 5)).astype(np.float32),
        "a/scale": rng.normal(size=(4,)),
        "scalar": np.float32(2.5),
        "ids": np.arange(6, dtype=np.int32).reshape(2, 3),
    }
    path = str(tmp_path / "state.npz")
    persistence._write_state_npz(path, arrays)

    whole = io.BytesIO()
    with zipfile.ZipFile(whole, "w", zipfile.ZIP_STORED, allowZip64=True) as zf:
        for name in sorted(arrays):
            buffer = io.BytesIO()
            npformat.write_array(buffer, np.asarray(arrays[name]), allow_pickle=False)
            info = zipfile.ZipInfo(name + ".npy", date_time=persistence._ZIP_EPOCH)
            info.external_attr = 0o644 << 16
            zf.writestr(info, buffer.getvalue())
    with open(path, "rb") as fh:
        assert fh.read() == whole.getvalue()
    with np.load(path) as loaded:
        assert sorted(loaded.files) == sorted(arrays)
        for name, value in arrays.items():
            np.testing.assert_array_equal(loaded[name], value)


def test_a_loaded_models_first_predict_from_many_threads_at_once(X, tmp_path):
    """A loaded estimator keeps its parameters on the host until it first
    predicts. Threads that all meet that first call together (a server's
    first requests) each get the fitted model's prediction, and the
    parameters end up placed once for the calls that follow."""
    import threading

    import jax

    pipe = pipeline_from_definition(REFERENCE_STYLE_DEFINITION)
    pipe.fit(X)
    expected = pipe.predict(X)
    out = str(tmp_path / "model")
    dump(pipe, out)
    loaded = load(out)
    estimator = loaded.steps[-1][1]
    assert isinstance(jax.tree_util.tree_leaves(estimator.params_)[0], np.ndarray)

    gate, results, errors = threading.Barrier(8), [None] * 8, []

    def first_call(i):
        try:
            gate.wait()
            results[i] = loaded.predict(X)
        except Exception as exc:  # noqa: BLE001 (handed to the assertion below)
            errors.append(exc)

    threads = [threading.Thread(target=first_call, args=(i,)) for i in range(8)]
    for thread in threads:
        thread.start()
    for thread in threads:
        thread.join()
    assert not errors, errors
    for got in results:
        np.testing.assert_allclose(got, expected, rtol=1e-5)
    assert isinstance(jax.tree_util.tree_leaves(estimator.params_)[0], jax.Array)
    np.testing.assert_allclose(loaded.predict(X), expected, rtol=1e-5)
