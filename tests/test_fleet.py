"""Fleet-engine tests on the 8-virtual-device CPU mesh (conftest forces
--xla_force_host_platform_device_count=8): stacked training correctness,
mesh sharding, padding masks, artifact parity with the single-machine path,
and idempotent resume."""

import os

import jax
import numpy as np
import pytest

from gordo_components_tpu.models.anomaly import DiffBasedAnomalyDetector
from gordo_components_tpu.parallel import (
    FleetMachineConfig,
    MachineBatch,
    build_fleet,
    fleet_mesh,
    train_fleet_arrays,
)
from gordo_components_tpu.parallel.fleet import MachineResult
from gordo_components_tpu.parallel.build_fleet import _analyze_model, _spec_for
from gordo_components_tpu.serializer import load, load_metadata, pipeline_from_definition


def _definition(estimator, **kwargs):
    return {
        "DiffBasedAnomalyDetector": {
            "base_estimator": {
                "TransformedTargetRegressor": {
                    "regressor": {
                        "Pipeline": {"steps": ["MinMaxScaler", {estimator: kwargs}]}
                    },
                    "transformer": "MinMaxScaler",
                }
            }
        }
    }


MODEL_CONFIG = _definition(
    "DenseAutoEncoder", kind="feedforward_hourglass", epochs=4, batch_size=32
)


def _data_config(tags):
    return {
        "type": "RandomDataset",
        "train_start_date": "2023-01-01T00:00:00+00:00",
        "train_end_date": "2023-01-04T00:00:00+00:00",
        "tag_list": list(tags),
    }


def _make_spec_and_batch(n_machines, n_rows=256, n_features=3, seed=0,
                         model_config=MODEL_CONFIG, n_splits=2):
    rng = np.random.default_rng(seed)
    probe = pipeline_from_definition(model_config)
    spec = _spec_for(_analyze_model(probe), n_features, n_features, n_splits)
    X = rng.normal(size=(n_machines, n_rows, n_features)).astype(np.float32)
    X += np.sin(np.linspace(0, 12, n_rows))[None, :, None] * 2
    batch = MachineBatch(
        X=X,
        y=X.copy(),
        w=np.ones((n_machines, n_rows), np.float32),
        keys=jax.random.split(jax.random.PRNGKey(0), n_machines),
    )
    return spec, batch


def test_devices_available():
    assert jax.device_count() == 8, "conftest must provide 8 virtual devices"


@pytest.mark.slow
def test_fleet_trains_stacked_machines():
    spec, batch = _make_spec_and_batch(4)
    result = train_fleet_arrays(spec, batch)
    # stacked shapes: leading machine axis everywhere
    assert result.loss_history.shape == (4, spec.epochs)
    assert result.cv_scores.shape == (4, 2, 4)  # machines, folds, metrics
    assert result.input_scaler.scale.shape == (4, 3)
    assert result.error_scaler.scale.shape == (4, 3)
    leaves = jax.tree_util.tree_leaves(result.params)
    assert all(leaf.shape[0] == 4 for leaf in leaves)
    hist = np.asarray(result.loss_history)
    assert np.isfinite(hist).all()
    # every machine's loss decreased
    assert (hist[:, -1] < hist[:, 0]).all()
    # different data -> different trained params
    k0 = np.asarray(leaves[0][0])
    k1 = np.asarray(leaves[0][1])
    assert not np.allclose(k0, k1)


def test_cv_parallel_evaluation_override():
    """Nobody sets the fold-execution mode: an ``evaluation.cv_parallel``
    key is reported among the ignored keys beside ``cv_mode``, whatever its
    value, and the spec follows the model's own remat request alone
    (``test_spec_derives_its_execution_from_the_remat_request``)."""
    from gordo_components_tpu.parallel.build_fleet import _effective_splits
    from gordo_components_tpu.parallel.fleet import sequential_fits

    m = FleetMachineConfig(
        name="m", model_config={}, data_config={},
        evaluation={"n_splits": 1, "cv_parallel": False, "cv_mode": "full"},
    )
    assert _effective_splits(m, 3) == (1, ["cv_mode", "cv_parallel"])
    m_default = FleetMachineConfig(
        name="m2", model_config={}, data_config={}, evaluation={}
    )
    assert _effective_splits(m_default, 3) == (3, [])
    odd = FleetMachineConfig(
        name="m3", model_config={}, data_config={},
        evaluation={"cv_parallel": "yes"},
    )
    assert _effective_splits(odd, 3) == (3, ["cv_parallel"])
    spec = _spec_for(_analyze_model(pipeline_from_definition(MODEL_CONFIG)), 3, 3, 2)
    assert not spec.memory_constrained and not sequential_fits(spec)
    with pytest.raises(TypeError):
        _spec_for(_analyze_model(pipeline_from_definition(MODEL_CONFIG)),
                  3, 3, 2, cv_parallel=False)


def test_machines_that_differ_in_an_ignored_evaluation_key_share_a_bucket(
    tmp_path, caplog
):
    """The bucket signature is the model config, the widths and the CV
    depth: a machine that still carries ``evaluation.cv_parallel`` trains in
    the same program, in the same slice, as its twin without the key."""
    import logging

    from gordo_components_tpu.observability.flightrec import RECORDER

    machines = [
        FleetMachineConfig(
            name=name,
            model_config=MODEL_CONFIG,
            data_config=_data_config(["a", "b", "c"]),
            evaluation=evaluation,
        )
        for name, evaluation in (("plain", {}), ("keyed", {"cv_parallel": False}))
    ]
    with caplog.at_level(logging.WARNING):
        results = build_fleet(machines, str(tmp_path / "out"), n_splits=1)
    assert "ignores unsupported evaluation keys" in caplog.text
    assert "'keyed': ['cv_parallel']" in caplog.text
    buckets = [
        span for span in RECORDER.latest(kind="fleet-build").spans
        if span.name == "fleet.bucket"
    ]
    assert [span.attrs["machines"] for span in buckets] == [2]
    for name in ("plain", "keyed"):
        assert load_metadata(results[name])["model"]["fleet"]["cv_parallel"] is True


def test_cv_parallel_matches_scan():
    """The vmapped fold path must train the SAME models as the sequential
    scan path (a memory-constrained spec's): per-fit keys are identical by
    construction, so every MachineResult field agrees up to XLA
    reduction-order float noise. This pins the (K+1)x sequential-depth
    optimization as a pure execution-strategy change, not a semantic one."""
    spec, batch = _make_spec_and_batch(3, n_rows=128, n_splits=2)
    assert not spec.memory_constrained  # no remat asked: vmapped folds
    fast = train_fleet_arrays(spec, batch)
    slow = train_fleet_arrays(spec._replace(memory_constrained=True), batch)
    for name in MachineResult._fields:
        a, b = getattr(fast, name), getattr(slow, name)
        for la, lb in zip(
            jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
        ):
            np.testing.assert_allclose(
                np.asarray(la), np.asarray(lb), rtol=2e-4, atol=1e-5,
                err_msg=f"cv_parallel vs scan mismatch in {name}",
            )


LSTM_CONFIG = _definition(
    "LSTMAutoEncoder", kind="lstm_symmetric", lookback_window=8, dims=[8],
    epochs=2, batch_size=16,
)


def test_cv_parallel_windowed_matches_scan():
    """Same parity through the windowed (LSTM) path, whose predict side
    runs lax.map chunks under the fold vmap."""
    spec, batch = _make_spec_and_batch(
        2, n_rows=96, model_config=LSTM_CONFIG, n_splits=2
    )
    assert not spec.memory_constrained
    fast = train_fleet_arrays(spec, batch)
    slow = train_fleet_arrays(spec._replace(memory_constrained=True), batch)
    for name in MachineResult._fields:
        for la, lb in zip(
            jax.tree_util.tree_leaves(getattr(fast, name)),
            jax.tree_util.tree_leaves(getattr(slow, name)),
        ):
            np.testing.assert_allclose(
                np.asarray(la), np.asarray(lb), rtol=2e-4, atol=1e-5,
                err_msg=f"cv_parallel vs scan mismatch in {name}",
            )


RESULT_READS = ("cv_scores", "error_scaler", "tag_thresholds", "total_threshold")


def _windowed_case(case, n_splits=2, model_config=LSTM_CONFIG):
    """(spec, batch) of LSTM machines of 96 rows (89 windows of 8, padded to
    96 samples; 16 a batch). ``fallback``: a second machine with
    ``n_splits + 7`` real rows, ``n_splits`` real windows, fewer than
    ``n_splits + 1``; ``holes``: rows 80-81 of the first machine weigh
    nothing, so the 9 windows over them drop out of the last fold's test
    region and cut it in two (80 real samples)."""
    n_machines = 1 if case == "one_machine" else 2
    spec, batch = _make_spec_and_batch(
        n_machines, n_rows=96, model_config=model_config, n_splits=n_splits
    )
    w = batch.w.copy()
    if case == "fallback":
        w[1, : 89 - n_splits] = 0.0
    if case in ("holes", "counted"):
        w[0, 80:82] = 0.0
    if case == "counted":
        w[1, :87] = 0.0
    return spec, batch._replace(w=w)


@pytest.mark.parametrize("case", ["one_machine", "fallback", "holes"])
def test_sequential_fits_read_what_the_vmapped_fits_read(case):
    """Both fold modes predict only what their result reads (a fold its
    test samples; the final fit nothing while a fold covers the machine,
    every real sample where none does), the sequential one in a loop whose
    trip count is traced, the vmapped one in a loop of a static count, and
    their results agree: on a slice of ONE machine (the unbatched predict
    loop), beside a machine that falls back to the final fit's residuals
    (the traced loop under a vmap, to the longer machine's trip count), and
    with a test region that is not contiguous in index space."""
    spec, batch = _windowed_case(case)
    assert not spec.memory_constrained
    fast = train_fleet_arrays(spec, batch)
    slow = train_fleet_arrays(spec._replace(memory_constrained=True), batch)
    for name in RESULT_READS:
        for la, lb in zip(
            jax.tree_util.tree_leaves(getattr(fast, name)),
            jax.tree_util.tree_leaves(getattr(slow, name)),
        ):
            np.testing.assert_allclose(
                np.asarray(la), np.asarray(lb), rtol=2e-4, atol=1e-5,
                err_msg=f"vmapped vs sequential mismatch in {name} ({case})",
            )
    if case == "fallback":
        assert not np.isfinite(np.asarray(slow.cv_scores[1])).any()
        assert np.isfinite(np.asarray(slow.tag_thresholds[1])).all()
        assert float(slow.total_threshold[1]) > 0


LSTM_BATCH_4 = _definition(
    "LSTMAutoEncoder", kind="lstm_symmetric", lookback_window=8, dims=[8],
    epochs=2, batch_size=4,
)


@pytest.mark.parametrize("n_splits", [1, 2, 3])
@pytest.mark.parametrize("case", ["padding", "holes", "fallback"])
def test_vmapped_fits_predict_within_the_static_bound(case, n_splits):
    """The vmapped fold mode predicts a fit's wanted samples in a loop of
    ``fold_predict_chunks`` chunks, a count fixed at trace time: every fit
    wants at most that many chunks' samples (a fold its test samples, the
    final fit where it falls back, with the trailing padding of 89 samples
    to 92, holes, or a machine of ``n_splits`` real samples), and the
    result is the sequential mode's. Four samples a batch make the bound
    several chunks (23 steps: chunks of one batch)."""
    from gordo_components_tpu.ops import windowing
    from gordo_components_tpu.parallel.fleet import (
        fold_predict_chunks,
        predict_width,
        timeseries_fold_masks,
    )

    spec, batch = _windowed_case(case, n_splits, model_config=LSTM_BATCH_4)
    assert not spec.memory_constrained
    L, la, n_rows = spec.lookback_window, spec.lookahead, batch.X.shape[1]
    starts = windowing.window_starts(n_rows, L, la)
    target = windowing.window_output_index(n_rows, L, la)
    padded = 92
    width = predict_width(spec, padded)
    n_chunks = fold_predict_chunks(padded, n_splits, width)
    assert (width, n_chunks) == (4, -(-max(padded // (n_splits + 1), n_splits) // 4))
    counted = []
    for w in batch.w:
        real = (w[starts[:, None] + np.arange(L)].min(axis=1) > 0) & (w[target] > 0)
        wt = np.pad(real.astype(np.float32), (0, padded - real.size))
        tested = [test.sum() for _, test in timeseries_fold_masks(wt, n_splits)]
        wanted = tested + [wt.sum() if sum(tested) == 0 else 0.0]
        assert max(wanted) <= n_chunks * width
        counted.append(int(sum(wanted)))
    fast = train_fleet_arrays(spec, batch)
    slow = train_fleet_arrays(spec._replace(memory_constrained=True), batch)
    assert np.asarray(fast.counters["predicted_samples"]).tolist() == counted
    for name in RESULT_READS:
        for la_, lb in zip(
            jax.tree_util.tree_leaves(getattr(fast, name)),
            jax.tree_util.tree_leaves(getattr(slow, name)),
        ):
            np.testing.assert_allclose(
                np.asarray(la_), np.asarray(lb), rtol=2e-4, atol=1e-5,
                err_msg=f"vmapped vs sequential mismatch in {name} ({case})",
            )


def test_fold_predict_chunks_refuses_a_rule_that_asks_more(monkeypatch):
    """The static bound is held against the fold rule itself: a rule whose
    folds test more than ``padded // (K+1)`` samples is refused at trace
    time, where a loop of the old bound's chunks would drop predictions."""
    from gordo_components_tpu.parallel import fleet

    def every_sample_tested(wt, n_splits):
        return [(wt * 0, wt) for _ in range(n_splits)]

    assert fleet.fold_predict_chunks(96, 2, 48) == 1
    monkeypatch.setattr(fleet, "timeseries_fold_masks", every_sample_tested)
    with pytest.raises(ValueError, match="over the 32"):
        fleet.fold_predict_chunks(96, 2, 48)


def test_sequential_fits_count_the_samples_they_predict():
    """``predicted_samples``: a covered machine's folds each predict their
    test samples, ``n_real // (K+1)``, and its final fit none; a machine no
    fold covers predicts its real samples in the final fit alone.
    ``predictable_samples``: (K+1) x padded samples. The vmapped mode counts
    the same."""
    spec, batch = _windowed_case("counted")
    for constrained in (True, False):
        result = train_fleet_arrays(
            spec._replace(memory_constrained=constrained), batch
        )
        # machine 0: 80 real samples, 2 folds of 80 // 3; machine 1: 2 real
        assert np.asarray(result.counters["predicted_samples"]).tolist() == [
            2 * (80 // 3), 2
        ]
        assert np.asarray(result.counters["predictable_samples"]).tolist() == [
            3 * 96, 3 * 96
        ]


@pytest.mark.parametrize("constrained", [False, True])
def test_sequential_fits_without_folds_predict_every_real_sample(constrained):
    """``n_splits`` 0: the final fit predicts every real sample, and its
    residuals set the error scaler and the thresholds as a plain forward of
    the machine's own parameters over all of its windows reads them."""
    from gordo_components_tpu.ops import windowing

    spec, batch = _windowed_case("holes", n_splits=0)
    spec = spec._replace(memory_constrained=constrained)
    result = train_fleet_arrays(spec, batch)
    L, la, n_rows = spec.lookback_window, spec.lookahead, batch.X.shape[1]
    sx, sy = (
        jax.tree_util.tree_map(lambda a: np.asarray(a[0]), s)
        for s in (result.input_scaler, result.target_scaler)
    )
    starts = windowing.window_starts(n_rows, L, la)
    windows = (batch.X[0] * sx.scale + sx.offset)[starts[:, None] + np.arange(L)]
    params = jax.tree_util.tree_map(lambda a: a[0], result.params)
    pred = np.asarray(
        spec.module.apply({"params": params}, windows, deterministic=True)
    )
    target = windowing.window_output_index(n_rows, L, la)
    real = batch.w[0][starts[:, None] + np.arange(L)].min(axis=1) > 0
    real &= batch.w[0][target] > 0
    err = np.abs(batch.y[0][target] - (pred - sy.offset) / sy.scale)[real]
    lo, hi = err.min(axis=0), err.max(axis=0)
    scaled = (err - lo) / (hi - lo)
    expected = {
        "error_scaler": (1 / (hi - lo), -lo / (hi - lo)),
        "tag_thresholds": np.percentile(scaled, 99, axis=0),
        "total_threshold": np.percentile(np.linalg.norm(scaled, axis=1), 99),
    }
    # every real sample, once: the machine with holes, and its whole twin
    assert np.asarray(result.counters["predicted_samples"]).tolist() == [80, 89]
    for name, want in expected.items():
        for got, one in zip(
            jax.tree_util.tree_leaves(getattr(result, name)),
            jax.tree_util.tree_leaves(want),
        ):
            np.testing.assert_allclose(
                np.asarray(got)[0], one, rtol=1e-3, atol=1e-5, err_msg=name
            )


def _decoder_definition():
    return _definition(
        "MoEMLAForecast", kind="moe_mla_decoder", lookback_window=16,
        vocab_size=64, hidden_size=64, n_layers=2, n_dense_layers=1,
        intermediate_size=96, moe_intermediate_size=32, n_routed_experts=8,
        experts_held=[1, 5], experts_per_token=2, n_shared_experts=1,
        routed_scaling_factor=2.5, q_lora_rank=48, kv_lora_rank=32, n_heads=4,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, remat=True,
        batch_size=2, epochs=1,
    )


def _plant_definition():
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "tools"))
    from plant_memory_sweep import plant_model

    return plant_model(batch_size=16)


@pytest.mark.parametrize(
    "definition, constrained, sequential, unroll",
    [
        (lambda: MODEL_CONFIG, False, False, 4),
        (
            lambda: _definition(
                "LSTMAutoEncoder", kind="lstm_symmetric", lookback_window=24,
                epochs=1, batch_size=64,
            ),
            False, False, 1,
        ),
        (_plant_definition, True, True, 1),
        (_decoder_definition, True, True, 1),
    ],
    ids=["flat", "windowed", "remat", "moe_mla_decoder"],
)
def test_spec_derives_its_execution_from_the_remat_request(
    definition, constrained, sequential, unroll
):
    """What ``_spec_for`` derives for a flat, a windowed, a remat and the
    decoder definition: one stored fact, the module's remat request, and
    from it (with the input kind) the fold mode and the scan unroll. Each
    definition must also parse into a pipeline at all — a config typo shows
    here, not in a chip run."""
    from gordo_components_tpu.parallel.fleet import fit_unroll, sequential_fits

    spec = _spec_for(_analyze_model(pipeline_from_definition(definition())), 4, 4, 2)
    assert spec.lookback_window >= 1
    assert spec.memory_constrained is constrained
    assert sequential_fits(spec) is sequential
    assert fit_unroll(spec) == unroll
    # no folds: nothing to vmap, so the fits run in sequence either way
    assert sequential_fits(spec._replace(n_splits=0))


@pytest.mark.parametrize(
    "definition, bytes_limit, states_that_fit",
    [
        (lambda: MODEL_CONFIG, 16 * 2**30, None),  # not memory-constrained
        (_decoder_definition, None, None),  # the device does not say
        (_decoder_definition, 16 * 2**30, "from the state"),
        (_decoder_definition, 1, 1),  # at least one machine a slice
    ],
    ids=["unconstrained", "no-limit", "capped", "at-least-one"],
)
def test_slice_cap_follows_the_memory_constrained_fact(
    monkeypatch, definition, bytes_limit, states_that_fit
):
    """A slice of a memory-constrained spec holds as many machines as their
    training state (and a third of it again) fits into three quarters of
    what the device says it has; any other spec, and a device that says
    nothing, is not capped."""
    import importlib

    from gordo_components_tpu.parallel.fleet import abstract_state

    bf = importlib.import_module("gordo_components_tpu.parallel.build_fleet")

    class _Device:
        def memory_stats(self):
            return None if bytes_limit is None else {"bytes_limit": bytes_limit}

    spec = _spec_for(_analyze_model(pipeline_from_definition(definition())), 4, 4, 2)
    if states_that_fit == "from the state":
        state = sum(
            leaf.size * leaf.dtype.itemsize
            for leaf in jax.tree_util.tree_leaves(abstract_state(spec, 1, 4))
        )
        states_that_fit = int(0.75 * bytes_limit // (state * 4 / 3))
        assert states_that_fit > 1
    monkeypatch.setattr(bf.jax, "local_devices", lambda: [_Device()])
    assert bf._slice_cap(spec, 4) == states_that_fit


def test_fleet_flops_accounting_trip_adjustment():
    """MFU accounting: the trip-count-adjusted total must dominate the raw
    whole-program cost_analysis figure (which counts each scan body once)
    and scale linearly with epochs."""
    from gordo_components_tpu.parallel.fleet import (
        compiled_flops,
        fleet_executable,
        fleet_flops_accounting,
    )

    probe = pipeline_from_definition(
        _definition(
            "DenseAutoEncoder", kind="feedforward_hourglass", epochs=4,
            batch_size=64,
        )
    )
    spec = _spec_for(_analyze_model(probe), 10, 10, n_splits=2)
    acct = fleet_flops_accounting(spec, 2, 128, 10, 10)
    assert acct is not None
    # structure: 3 fits x 4 epochs x (128/64=2) steps
    assert acct["train_steps"] == 3 * spec.epochs * (128 // spec.batch_size)
    assert acct["predict_chunks"] == 3 * (128 // spec.batch_size)
    assert acct["total_flops"] > 0
    # doubling epochs doubles train steps, total grows accordingly
    acct2 = fleet_flops_accounting(
        spec._replace(epochs=2 * spec.epochs), 2, 128, 10, 10
    )
    assert acct2["train_steps"] == 2 * acct["train_steps"]
    assert acct2["total_flops"] > acct["total_flops"]
    # the adjusted total dominates the whole-program body-once figure
    compiled, _ = fleet_executable(spec, 2, 128, 10, 10)
    assert acct["total_flops"] >= compiled_flops(compiled)


@pytest.mark.slow
def test_fleet_on_mesh_sharded():
    mesh = fleet_mesh()
    assert mesh.size == 8
    spec, batch = _make_spec_and_batch(8)
    result = train_fleet_arrays(spec, batch, mesh=mesh)
    hist = np.asarray(result.loss_history)
    assert hist.shape[0] == 8
    assert np.isfinite(hist).all()
    # sharded run must agree with unsharded run (same program, same keys)
    plain = train_fleet_arrays(spec, batch)
    np.testing.assert_allclose(
        hist, np.asarray(plain.loss_history), rtol=1e-4, atol=1e-5
    )


def test_fleet_program_has_nothing_to_donate():
    """The batch buffers are not donated because XLA could alias them only
    to an output of the same shape and dtype, and the fleet program has
    none (on a v5e the donation came back "not usable" for all four
    inputs). If a result ever grows a batch-shaped leaf, donation is worth
    another look — and compiling emits no donation warning meanwhile."""
    import warnings

    from gordo_components_tpu.parallel.fleet import fleet_program

    spec, batch = _make_spec_and_batch(2)
    n_rows, n_features = batch.X.shape[1], batch.X.shape[2]
    program = fleet_program(spec, n_rows, n_features, batch.y.shape[2])
    args = (batch.X, batch.y, batch.w, np.asarray(batch.keys))
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        out = jax.eval_shape(program, *args)
        program.lower(*args)
    assert not [w for w in caught if "donated" in str(w.message)]
    inputs = {(a.shape, a.dtype) for a in args}
    outputs = {
        (leaf.shape, leaf.dtype) for leaf in jax.tree_util.tree_leaves(out)
    }
    assert not inputs & outputs


def test_fleet_executable_matches_train_fleet_arrays():
    """The AOT executable fed layout-matched arguments by hand (what the
    slice loop does) is the program train_fleet_arrays runs."""
    from gordo_components_tpu.parallel.fleet import (
        fleet_executable,
        put_fleet_batch,
    )

    spec, batch = _make_spec_and_batch(2)
    plain = train_fleet_arrays(spec, batch)
    n_rows, n_features = batch.X.shape[1], batch.X.shape[2]
    compiled, formats = fleet_executable(
        spec, 2, n_rows, n_features, batch.y.shape[2]
    )
    placed = put_fleet_batch(batch, formats)
    direct = compiled(placed.X, placed.y, placed.w, placed.keys)
    np.testing.assert_allclose(
        np.asarray(direct.loss_history), np.asarray(plain.loss_history),
        rtol=1e-5,
    )
    np.testing.assert_allclose(
        np.asarray(direct.total_threshold), np.asarray(plain.total_threshold),
        rtol=1e-5,
    )


def test_fleet_mesh_divisibility_enforced():
    mesh = fleet_mesh()
    spec, batch = _make_spec_and_batch(3)
    with pytest.raises(ValueError, match="divide evenly"):
        train_fleet_arrays(spec, batch, mesh=mesh)


@pytest.mark.slow
def test_zero_weight_padding_machine_is_finite():
    """A fully-padded (weight-0) machine must not poison the bucket with
    NaNs — this is what makes machine-axis padding safe."""
    spec, batch = _make_spec_and_batch(2)
    w = batch.w.copy()
    w[1] = 0.0
    result = train_fleet_arrays(spec, batch._replace(w=w))
    assert np.isfinite(np.asarray(result.loss_history)).all()
    assert np.isfinite(np.asarray(result.input_scaler.scale)).all()
    assert np.isfinite(np.asarray(result.error_scaler.scale)).all()


def test_row_padding_masks():
    """Machines with fewer real rows than the bucket width train correctly:
    the scaler must reflect only real rows."""
    spec, batch = _make_spec_and_batch(2, n_rows=256)
    X = batch.X.copy()
    w = batch.w.copy()
    # machine 1: only 200 real rows; padding is huge garbage that masks
    # must exclude
    X[1, 200:] = 1e9
    w[1, 200:] = 0.0
    result = train_fleet_arrays(spec, batch._replace(X=X, y=X.copy(), w=w))
    scale = np.asarray(result.input_scaler.scale[1])
    # minmax scale over real rows only: 1/(max-min) of N(0,1)+2sin data,
    # nowhere near 1/1e9
    assert (scale > 1e-3).all()
    assert np.isfinite(np.asarray(result.loss_history)).all()


@pytest.mark.slow
def test_lstm_fleet_bucket():
    lstm_config = {
        "DiffBasedAnomalyDetector": {
            "base_estimator": {
                "TransformedTargetRegressor": {
                    "regressor": {
                        "Pipeline": {
                            "steps": [
                                "MinMaxScaler",
                                {"LSTMAutoEncoder": {"kind": "lstm_symmetric",
                                                     "lookback_window": 6,
                                                     "dims": [8],
                                                     "epochs": 1,
                                                     "batch_size": 32}},
                            ]
                        }
                    },
                    "transformer": "MinMaxScaler",
                }
            }
        }
    }
    spec, batch = _make_spec_and_batch(2, n_rows=128,
                                       model_config=lstm_config, n_splits=2)
    assert spec.lookahead == 0 and spec.lookback_window == 6
    result = train_fleet_arrays(spec, batch)
    assert np.isfinite(np.asarray(result.loss_history)).all()


@pytest.mark.slow
def test_multi_step_forecast_fleet_bucket():
    """A horizon=2 LSTMForecast fleet trains through the same compiled
    program: spec.lookahead carries the horizon and window weights mask the
    2-step-shifted targets (BASELINE config 3 inside the fleet path)."""
    forecast_config = {
        "DiffBasedAnomalyDetector": {
            "base_estimator": {
                "TransformedTargetRegressor": {
                    "regressor": {
                        "Pipeline": {
                            "steps": [
                                "MinMaxScaler",
                                {"LSTMForecast": {"kind": "lstm_symmetric",
                                                  "lookback_window": 6,
                                                  "horizon": 2,
                                                  "dims": [8],
                                                  "epochs": 1,
                                                  "batch_size": 32}},
                            ]
                        }
                    },
                    "transformer": "MinMaxScaler",
                }
            }
        }
    }
    spec, batch = _make_spec_and_batch(2, n_rows=128,
                                       model_config=forecast_config,
                                       n_splits=2)
    assert spec.lookahead == 2 and spec.lookback_window == 6
    result = train_fleet_arrays(spec, batch)
    assert np.isfinite(np.asarray(result.loss_history)).all()
    assert np.isfinite(np.asarray(result.cv_scores)).all()


@pytest.mark.slow
def test_build_fleet_end_to_end(tmp_path):
    mesh = fleet_mesh()
    machines = [
        FleetMachineConfig(
            name=f"machine-{i}",
            model_config=MODEL_CONFIG,
            data_config=_data_config([f"m{i}-a", f"m{i}-b", f"m{i}-c"]),
            metadata={"idx": i},
        )
        for i in range(3)
    ]
    out = str(tmp_path / "fleet")
    registry = str(tmp_path / "registry")
    dirs = build_fleet(machines, out, model_register_dir=registry, mesh=mesh,
                       n_splits=2)
    assert set(dirs) == {"machine-0", "machine-1", "machine-2"}

    # each artifact is a fully-functional anomaly model, same format as the
    # single-machine builder's
    for i, (name, model_dir) in enumerate(sorted(dirs.items())):
        model = load(model_dir)
        assert isinstance(model, DiffBasedAnomalyDetector)
        X = np.random.default_rng(i).normal(size=(40, 3)).astype(np.float32)
        frame = model.anomaly(X)
        assert len(frame) == 40
        assert np.isfinite(
            np.ravel(frame["total-anomaly-score"].values)
        ).all()
        meta = load_metadata(model_dir)
        assert meta["name"] == name
        assert meta["model"]["fleet"]["bucket_size"] == 3
        assert meta["model"]["model_builder_metadata"]["cross_validation"][
            "n_splits"
        ] == 2

    # resume: second call is pure cache hits (no rebuild -> same dirs)
    dirs2 = build_fleet(machines, str(tmp_path / "other"),
                        model_register_dir=registry, mesh=mesh, n_splits=2)
    assert dirs2 == dirs


@pytest.mark.slow
def test_fleet_pipeline_shape_predicts_raw_space(tmp_path):
    """Config WITHOUT TransformedTargetRegressor: the fleet must train
    against raw targets (Pipeline.fit passes y through untransformed), so
    the served artifact predicts in raw units."""
    config = {
        "DiffBasedAnomalyDetector": {
            "base_estimator": {
                "Pipeline": {
                    "steps": [
                        "MinMaxScaler",
                        {"DenseAutoEncoder": {"kind": "feedforward_symmetric",
                                              "dims": [8], "epochs": 6,
                                              "batch_size": 32}},
                    ]
                }
            }
        }
    }
    probe = pipeline_from_definition(config)
    spec = _spec_for(_analyze_model(probe), 3, 3, 2)
    assert spec.scale_targets is False
    _, batch = _make_spec_and_batch(2, model_config=config)
    result = train_fleet_arrays(spec, batch)
    # no TTR -> target scaler is exactly identity: the model trains against
    # raw targets and the error scaler sees true raw residuals
    np.testing.assert_array_equal(np.asarray(result.target_scaler.scale), 1.0)
    np.testing.assert_array_equal(np.asarray(result.target_scaler.offset), 0.0)

    # and the artifact built from it serves without a target transform
    machines = [FleetMachineConfig("raw-m", config,
                                   _data_config(["r-a", "r-b", "r-c"]))]
    dirs = build_fleet(machines, str(tmp_path / "out"), n_splits=2)
    model = load(dirs["raw-m"])
    X = np.random.default_rng(0).normal(size=(60, 3)).astype(np.float32)
    frame = model.anomaly(X)
    assert np.isfinite(np.ravel(frame["total-anomaly-score"].values)).all()


@pytest.mark.slow
def test_fleet_short_machine_gets_real_thresholds():
    """A machine much shorter than the bucket must still get finite nonzero
    thresholds and honest per-machine CV: fold boundaries are computed on
    EACH machine's real samples (timeseries_fold_masks), so every fold of a
    short machine trains and tests on its own data — no empty folds, no
    fake scores."""
    spec, batch = _make_spec_and_batch(2, n_rows=256, n_splits=3)
    X = batch.X.copy()
    w = batch.w.copy()
    # machine 1: 128 real rows, RIGHT-aligned (leading padding)
    X[1, :128] = 0.0
    w[1, :128] = 0.0
    result = train_fleet_arrays(spec, batch._replace(X=X, y=X.copy(), w=w))
    thresholds = np.asarray(result.tag_thresholds[1])
    assert np.isfinite(thresholds).all()
    assert (thresholds > 0).any(), "short machine must get usable thresholds"
    # every fold covers the short machine's real data (sklearn
    # TimeSeriesSplit on its 128 real rows), so all scores are real numbers
    cv = np.asarray(result.cv_scores[1])
    assert np.isfinite(cv).all()


def test_fleet_cache_key_includes_eval_config():
    from gordo_components_tpu.builder import calculate_model_key

    base = calculate_model_key("m", MODEL_CONFIG, _data_config(["a"]))
    fleet = calculate_model_key(
        "m", MODEL_CONFIG, _data_config(["a"]),
        evaluation_config={"n_splits": 2, "cv_mode": "fleet"},
    )
    assert base != fleet


@pytest.mark.slow
def test_fleet_standard_scaler_options_honored():
    config = {
        "Pipeline": {
            "steps": [
                {"StandardScaler": {"with_mean": False}},
                {"DenseAutoEncoder": {"kind": "feedforward_symmetric",
                                      "dims": [4], "epochs": 1,
                                      "batch_size": 32}},
            ]
        }
    }
    probe = pipeline_from_definition(config)
    spec = _spec_for(_analyze_model(probe), 3, 3, 0)
    assert spec.scaler == "standard"
    assert spec.scaler_options == (False, True)
    assert spec.scale_targets is False
    _, batch = _make_spec_and_batch(2)
    result = train_fleet_arrays(spec, batch)
    # with_mean=False -> offsets are exactly zero
    np.testing.assert_array_equal(
        np.asarray(result.input_scaler.offset), 0.0
    )


@pytest.mark.slow
def test_fleet_target_scaler_independent_of_input_scaler():
    """TTR transformer with NO input scaler: targets must still be
    minmax-scaled (the target scaler kind comes from the transformer, not
    the pipeline's input scaler)."""
    config = {
        "DiffBasedAnomalyDetector": {
            "base_estimator": {
                "TransformedTargetRegressor": {
                    "regressor": {"DenseAutoEncoder": {
                        "kind": "feedforward_symmetric", "dims": [4],
                        "epochs": 1, "batch_size": 32}},
                    "transformer": "MinMaxScaler",
                }
            }
        }
    }
    probe = pipeline_from_definition(config)
    spec = _spec_for(_analyze_model(probe), 3, 3, 0)
    assert spec.scaler == "none"
    assert spec.scale_targets is True
    assert spec.target_scaler == "minmax"
    _, batch = _make_spec_and_batch(2)
    result = train_fleet_arrays(spec, batch)
    # target scaler actually fitted (real minmax, not identity)
    assert not np.allclose(np.asarray(result.target_scaler.scale), 1.0)


def test_fleet_rejects_non_minmax_error_scaler():
    config = {
        "DiffBasedAnomalyDetector": {
            "scaler": "StandardScaler",
            "base_estimator": {"DenseAutoEncoder": {"epochs": 1}},
        }
    }
    probe = pipeline_from_definition(config)
    with pytest.raises(ValueError, match="error scaler"):
        _spec_for(_analyze_model(probe), 3, 3, 0)


def test_fleet_untrainable_folds_fall_back_to_final_residuals():
    """A machine with fewer real samples than n_splits+1 has TimeSeriesSplit
    test_size == 0 — every fold is empty — and must get thresholds from
    final-model residuals, not an untrained network."""
    spec, batch = _make_spec_and_batch(2, n_rows=256, n_splits=3)
    X = batch.X.copy()
    w = batch.w.copy()
    # machine 1: only 3 real rows (< n_splits+1 = 4) -> all folds empty
    X[1, :253] = 0.0
    w[1, :253] = 0.0
    result = train_fleet_arrays(spec, batch._replace(X=X, y=X.copy(), w=w))
    thresholds = np.asarray(result.tag_thresholds[1])
    assert np.isfinite(thresholds).all()
    assert (thresholds > 0).any()
    assert float(result.total_threshold[1]) > 0
    # CV scores for that machine are all-NaN (no honest folds), not fake
    assert not np.isfinite(np.asarray(result.cv_scores[1])).any()
    # the normal machine still gets real CV scores
    assert np.isfinite(np.asarray(result.cv_scores[0])).all()


def test_provide_saved_model_rejects_cross_val_only(tmp_path):
    from gordo_components_tpu.builder import provide_saved_model

    with pytest.raises(ValueError, match="cross_val_only"):
        provide_saved_model(
            "m", MODEL_CONFIG, _data_config(["a"]), str(tmp_path / "x"),
            evaluation_config={"cv_mode": "cross_val_only"},
        )


@pytest.mark.slow
def test_fleet_heterogeneous_buckets(tmp_path):
    """Machines with different tag counts land in different buckets but one
    build_fleet call handles all of them."""
    machines = [
        FleetMachineConfig("narrow", MODEL_CONFIG, _data_config(["a", "b"])),
        FleetMachineConfig("wide", MODEL_CONFIG,
                           _data_config(["a", "b", "c", "d"])),
    ]
    dirs = build_fleet(machines, str(tmp_path / "out"), n_splits=0)
    narrow = load(dirs["narrow"])
    wide = load(dirs["wide"])
    assert narrow.predict(np.zeros((4, 2), np.float32)).shape == (4, 2)
    assert wide.predict(np.zeros((4, 4), np.float32)).shape == (4, 4)


@pytest.mark.slow
def test_fleet_slice_checkpoint_resume(tmp_path, monkeypatch):
    """A build killed mid-bucket loses only the in-flight slice: completed
    slices' artifacts + registry keys are already on disk, and the resume
    pass retrains only the remainder (SURVEY.md §6.4 sub-bucket resume)."""
    import importlib

    bf = importlib.import_module("gordo_components_tpu.parallel.build_fleet")

    mesh = fleet_mesh()
    machines = [
        FleetMachineConfig(
            name=f"sl-{i}",
            model_config=MODEL_CONFIG,
            data_config=_data_config([f"s{i}-a", f"s{i}-b", f"s{i}-c"]),
        )
        for i in range(6)
    ]
    out = str(tmp_path / "fleet")
    registry = str(tmp_path / "registry")

    real_train = bf.train_fleet_arrays
    calls = {"n": 0}

    def dying_train(*args, **kwargs):
        calls["n"] += 1
        if calls["n"] == 2:  # slice 0 completes, slice 1 dies mid-train
            raise RuntimeError("simulated kill mid-build")
        return real_train(*args, **kwargs)

    monkeypatch.setattr(bf, "train_fleet_arrays", dying_train)
    with pytest.raises(RuntimeError, match="simulated kill"):
        build_fleet(machines, out, model_register_dir=registry, mesh=mesh,
                    n_splits=2, slice_size=2)

    # slice 0 (first two machines) survived the kill: artifacts + registry
    for name in ("sl-0", "sl-1"):
        model_dir = os.path.join(out, name)
        assert os.path.isdir(model_dir)
        assert isinstance(load(model_dir), DiffBasedAnomalyDetector)
    assert not os.path.isdir(os.path.join(out, "sl-2"))

    # resume: only the 2 remaining slices train; slice 0 is a cache hit
    resumed_calls = {"n": 0}

    def counting_train(*args, **kwargs):
        resumed_calls["n"] += 1
        return real_train(*args, **kwargs)

    monkeypatch.setattr(bf, "train_fleet_arrays", counting_train)
    dirs = build_fleet(machines, out, model_register_dir=registry, mesh=mesh,
                       n_splits=2, slice_size=2)
    assert set(dirs) == {f"sl-{i}" for i in range(6)}
    assert resumed_calls["n"] == 2
    for name, model_dir in dirs.items():
        meta = load_metadata(model_dir)
        assert meta["model"]["fleet"]["slice_size"] == 2


def test_fleet_manifest_tracks_progress(tmp_path, monkeypatch):
    """The fleet completion bitmap (fleet_manifest.json) is rewritten after
    every slice: a kill leaves it reflecting exactly the finished slices."""
    import importlib
    import json

    bf = importlib.import_module("gordo_components_tpu.parallel.build_fleet")
    mesh = fleet_mesh()
    machines = [
        FleetMachineConfig(
            name=f"mf-{i}",
            model_config=MODEL_CONFIG,
            data_config=_data_config([f"f{i}-a", f"f{i}-b", f"f{i}-c"]),
        )
        for i in range(4)
    ]
    out = str(tmp_path / "fleet")

    real_train = bf.train_fleet_arrays
    calls = {"n": 0}

    def dying_train(*args, **kwargs):
        calls["n"] += 1
        if calls["n"] == 2:
            raise RuntimeError("kill")
        return real_train(*args, **kwargs)

    monkeypatch.setattr(bf, "train_fleet_arrays", dying_train)
    with pytest.raises(RuntimeError):
        build_fleet(machines, out, mesh=mesh, n_splits=2, slice_size=2)

    manifest = json.load(open(os.path.join(out, bf.MANIFEST_FILE)))
    assert manifest["n_completed"] == 2
    assert sorted(manifest["machines"]) == ["mf-0", "mf-1"]
    assert manifest["pending"] == ["mf-2", "mf-3"]
    assert all(
        m["status"] == "completed" and os.path.isdir(m["model_dir"])
        for m in manifest["machines"].values()
    )


def test_slice_checkpoint_restores_instead_of_retraining(tmp_path, monkeypatch):
    """A crash AFTER a slice trains but BEFORE its artifacts land must not
    lose the training: the async orbax checkpoint of the stacked result
    restores on resume and only the untrained slices run (SURVEY.md §6.4
    async checkpoint of the stacked fleet pytree)."""
    import importlib
    import time as _time

    bf = importlib.import_module("gordo_components_tpu.parallel.build_fleet")
    mesh = fleet_mesh()
    machines = [
        FleetMachineConfig(
            name=f"ck-{i}",
            model_config=MODEL_CONFIG,
            data_config=_data_config([f"k{i}-a", f"k{i}-b", f"k{i}-c"]),
        )
        for i in range(4)
    ]
    out = str(tmp_path / "fleet")
    registry = str(tmp_path / "reg")

    # the artifact-commit boundary is store.commit_generation now (atomic
    # generation commits) — kill there, after training succeeded
    real_commit = bf.commit_generation

    def dying_commit(*args, **kwargs):
        raise RuntimeError("killed before artifacts")

    monkeypatch.setattr(bf, "commit_generation", dying_commit)
    with pytest.raises(RuntimeError, match="killed before artifacts"):
        build_fleet(machines, out, model_register_dir=registry, mesh=mesh,
                    n_splits=2, slice_size=2)

    # wait for the in-flight async save to FINALIZE: orbax writes into a
    # "*.orbax-checkpoint-tmp" dir and renames atomically, so only a match
    # without the tmp suffix counts (matching the tmp dir would race the
    # rename and flakily retrain instead of restoring)
    import glob as _glob

    pattern = os.path.join(out, ".slice_checkpoints", "slice_*")

    def finalized():
        return [p for p in _glob.glob(pattern) if "tmp" not in os.path.basename(p)]

    deadline = _time.time() + 30
    while not finalized() and _time.time() < deadline:
        _time.sleep(0.2)
    assert finalized(), "slice checkpoint never finalized"

    monkeypatch.setattr(bf, "commit_generation", real_commit)
    real_train = bf.train_fleet_arrays
    trains = {"n": 0}

    def counting_train(*args, **kwargs):
        trains["n"] += 1
        return real_train(*args, **kwargs)

    monkeypatch.setattr(bf, "train_fleet_arrays", counting_train)
    dirs = build_fleet(machines, out, model_register_dir=registry, mesh=mesh,
                       n_splits=2, slice_size=2)
    assert set(dirs) == {f"ck-{i}" for i in range(4)}
    assert trains["n"] == 1, "slice 0 must restore from checkpoint, not retrain"
    for model_dir in dirs.values():
        assert isinstance(load(model_dir), DiffBasedAnomalyDetector)
    # steady state leaves no checkpoint residue
    assert not os.path.isdir(os.path.join(out, ".slice_checkpoints"))


def test_negative_slice_size_rejected(tmp_path):
    machines = [FleetMachineConfig(
        name="neg", model_config=MODEL_CONFIG,
        data_config=_data_config(["n-a", "n-b", "n-c"]))]
    with pytest.raises(ValueError, match="slice_size"):
        build_fleet(machines, str(tmp_path / "o"), n_splits=2, slice_size=-1)


@pytest.mark.slow
def test_fleet_executable_formats_and_placement():
    """fleet_executable AOT-compiles once per (spec, shape, mesh) and
    put_fleet_batch coerces host dtypes (float64 data, typed PRNG keys)
    before placement — AOT executables are strict where jit would coerce."""
    from gordo_components_tpu.parallel.fleet import (
        fleet_executable,
        put_fleet_batch,
    )

    spec, batch = _make_spec_and_batch(4, n_rows=128)
    compiled, formats = fleet_executable(spec, 4, 128, 3, 3)
    again, _ = fleet_executable(spec, 4, 128, 3, 3)
    assert compiled is again, "executable cache must hit on identical key"

    sloppy = MachineBatch(
        X=np.asarray(batch.X, np.float64),  # float64 data (raw pandas .values)
        y=np.asarray(batch.y, np.float64),
        w=np.asarray(batch.w, np.float64),
        keys=jax.random.split(jax.random.key(0), 4),  # typed keys
    )
    placed = put_fleet_batch(sloppy, formats)
    assert placed.X.dtype == np.float32
    assert placed.keys.dtype == np.uint32
    result = compiled(placed.X, placed.y, placed.w, placed.keys)
    assert np.isfinite(np.asarray(result.loss_history)).all()

    # formats=None fallback (backends without the layout API) still executes
    placed2 = put_fleet_batch(batch, None)
    result2 = compiled(placed2.X, placed2.y, placed2.w, placed2.keys)
    assert np.isfinite(np.asarray(result2.loss_history)).all()


@pytest.mark.slow
def test_per_machine_evaluation_n_splits(tmp_path):
    """A machine's ``evaluation: {n_splits: N}`` (reference Machine
    semantics) overrides build_fleet's global — machines with different CV
    depths land in different buckets and their metadata records their own
    fold count."""
    machines = [
        FleetMachineConfig(
            name="deep-cv",
            model_config=MODEL_CONFIG,
            data_config=_data_config(["a", "b", "c"]),
            evaluation={"n_splits": 4},
        ),
        FleetMachineConfig(
            name="default-cv",
            model_config=MODEL_CONFIG,
            data_config=_data_config(["a", "b", "c"]),
        ),
    ]
    results = build_fleet(
        machines, str(tmp_path / "out"), mesh=None, n_splits=2
    )
    deep = load_metadata(results["deep-cv"])
    default = load_metadata(results["default-cv"])
    assert deep["model"]["cross_validation"]["n_splits"] == 4
    assert len(deep["model"]["cross_validation"]["splits"]) == 4
    assert default["model"]["cross_validation"]["n_splits"] == 2
    assert len(default["model"]["cross_validation"]["splits"]) == 2


def test_evaluation_n_splits_validation(tmp_path):
    """Non-integer evaluation.n_splits is a config error (ValueError -> the
    CLI's EXIT_CONFIG path), not a raw TypeError; None means 'use default';
    unsupported evaluation keys are surfaced, not silently dropped."""
    def machine(name, evaluation):
        return FleetMachineConfig(
            name=name,
            model_config=MODEL_CONFIG,
            data_config=_data_config(["a", "b", "c"]),
            evaluation=evaluation,
        )

    with pytest.raises(ValueError, match="n_splits must be an integer"):
        build_fleet([machine("bad", {"n_splits": "three"})], str(tmp_path / "o1"))
    with pytest.raises(ValueError, match="n_splits must be an integer"):
        build_fleet([machine("badf", {"n_splits": 2.5})], str(tmp_path / "o2"))
    with pytest.raises(ValueError, match="n_splits must be >= 0"):
        build_fleet([machine("neg", {"n_splits": -1})], str(tmp_path / "o3"))

    # None -> builder default; unsupported keys warn but build proceeds
    results = build_fleet(
        [machine("null-splits", {"n_splits": None, "cv_mode": "cross_val_only"})],
        str(tmp_path / "o4"),
        n_splits=2,
    )
    meta = load_metadata(results["null-splits"])
    assert meta["model"]["cross_validation"]["n_splits"] == 2


def test_prepare_slice_places_on_device_when_executable_cached():
    """Transfer overlap: once a bucket's executable exists, the prefetch
    worker's _prepare_slice must return DEVICE-placed X/y/w (layout-matched
    via the cached formats) so the next slice's host->device transfer rides
    behind training — and must stay on host before the first compile (no
    formats to borrow) and when no placement is requested."""
    from gordo_components_tpu.parallel.build_fleet import _prepare_slice
    from gordo_components_tpu.parallel.fleet import (
        fleet_executable,
        peek_fleet_executable,
    )

    probe = pipeline_from_definition(MODEL_CONFIG)
    spec = _spec_for(_analyze_model(probe), 3, 3, n_splits=1)
    rng = np.random.default_rng(0)
    items = [
        {
            "X": rng.normal(size=(48, 3)).astype(np.float32),
            "y": rng.normal(size=(48, 3)).astype(np.float32),
            "dataset_metadata": {},
        }
        for _ in range(2)
    ]
    place = (spec, None)

    def is_device(a):
        return isinstance(a, jax.Array)

    # fresh shape, nothing compiled -> stays host-side even with place
    X, y, w, n_rows = _prepare_slice(
        [dict(i) for i in items], 2, 3, 3, False, None, place
    )
    if peek_fleet_executable(spec, 2, n_rows, 3, 3) is None:
        assert not is_device(X)

    # compile the executable, then the SAME call must come back placed
    # (unless this backend exposes no input formats — then it stays host)
    compiled, formats = fleet_executable(spec, 2, n_rows, 3, 3)
    X2, y2, w2, n_rows2 = _prepare_slice(
        [dict(i) for i in items], 2, 3, 3, False, None, place
    )
    assert n_rows2 == n_rows
    if formats is not None:
        assert is_device(X2) and is_device(y2) and is_device(w2)
        # placed data is bit-identical to the host assembly
        np.testing.assert_array_equal(np.asarray(X2), X)
    # and no placement without the request
    X3, *_ = _prepare_slice([dict(i) for i in items], 2, 3, 3, False, None)
    assert not is_device(X3)


def test_prepare_slice_fetches_machines_concurrently():
    """One slice's per-machine provider reads run concurrently (the
    reference's pod-per-machine fan-out gave it this for free): 4 fake
    datasets each sleeping 0.2s must fetch in well under the 0.8s serial
    sum and land in item order. A provider exception no longer kills the
    slice: the failing machine is ISOLATED (zero-weight padding +
    build_error) while its neighbors' data lands intact (the resilience
    layer's per-machine failure-containment contract)."""
    import time as _time
    from types import SimpleNamespace

    from gordo_components_tpu.parallel.build_fleet import _prepare_slice

    class SlowDataset:
        def __init__(self, value):
            self.value = value

        def get_data(self):
            _time.sleep(0.2)
            X = np.full((8, 3), self.value, np.float32)
            return X, X.copy()

        def get_metadata(self):
            return {"v": self.value}

    def _item(dataset, name):
        return {"dataset": dataset, "machine": SimpleNamespace(name=name)}

    items = [_item(SlowDataset(float(i)), f"c-{i}") for i in range(4)]
    started = _time.perf_counter()
    X, y, w, n_rows = _prepare_slice(items, 4, 3, 3, False)
    wall = _time.perf_counter() - started
    assert wall < 0.6, f"serial fetch? {wall:.2f}s"
    for i in range(4):
        assert np.all(np.asarray(X)[i, -8:] == float(i))
        assert items[i]["dataset_metadata"] == {"v": i}

    class BoomDataset(SlowDataset):
        def get_data(self):
            raise RuntimeError("lake exploded")

    items = [_item(SlowDataset(7.0), "ok-m"), _item(BoomDataset(1.0), "boom-m")]
    X, y, w, n_rows = _prepare_slice(
        items, 2, 3, 3, False, None, None, 0,  # fetch_retries=0: no backoff
    )
    assert "build_error" not in items[0]
    assert "lake exploded" in items[1]["build_error"]
    assert np.all(np.asarray(X)[0, -8:] == 7.0)
    assert np.all(np.asarray(w)[1] == 0.0)  # isolated = zero-weight padding
