"""The latent-attention / expert-layer decoder kind (``moe_mla_decoder``,
``MoEMLAForecast``) at small widths on the CPU: hidden 64, 8 experts of which
2 are held, vocabulary 64, lookback 16.

The program's module against the benchmark's plain reference
(``benchmarks/reference/models/moe_mla.py``: the same equations, a dense pass
of every held expert, no sort, no kernel) on seeded weights; the share test
(what all the shares of a layer give, the shared expert counted once, is the
uncut layer); the grouped product against a per-expert loop under a skewed
router; the layout against the reference's; and one machine through
``fleet-build``'s slice loop, store and serializer to ``anomaly()``.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

SMALL = {
    "kind": "moe_mla", "hidden_size": 64, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "intermediate_size": 96,
    "moe_intermediate_size": 32, "n_routed_experts": 8, "experts_held": [1, 5],
    "num_experts_per_tok": 2, "n_shared_experts": 1,
    "routed_scaling_factor": 2.5, "q_lora_rank": 48, "kv_lora_rank": 32,
    "num_attention_heads": 4, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "rope_theta": 32000000.0, "rms_norm_eps": 1e-6,
    "vocab_size": 64, "mtp_loss_weight": 0.3, "lookback": 16, "epochs": 1,
    "batch_size": 2, "n_splits": 2, "learning_rate": 1e-3,
}
TAGS = 3


def estimator_kwargs(model=SMALL, **more):
    """The reference's dictionary as the program's estimator takes it."""
    return dict(
        kind="moe_mla_decoder", lookback_window=model["lookback"],
        vocab_size=model["vocab_size"], hidden_size=model["hidden_size"],
        n_layers=model["num_hidden_layers"],
        n_dense_layers=model["first_k_dense_replace"],
        intermediate_size=model["intermediate_size"],
        moe_intermediate_size=model["moe_intermediate_size"],
        n_routed_experts=model["n_routed_experts"],
        experts_held=list(model["experts_held"]),
        experts_per_token=model["num_experts_per_tok"],
        n_shared_experts=model["n_shared_experts"],
        routed_scaling_factor=model["routed_scaling_factor"],
        q_lora_rank=model["q_lora_rank"], kv_lora_rank=model["kv_lora_rank"],
        n_heads=model["num_attention_heads"],
        qk_nope_head_dim=model["qk_nope_head_dim"],
        qk_rope_head_dim=model["qk_rope_head_dim"],
        v_head_dim=model["v_head_dim"], rope_theta=model["rope_theta"],
        rms_norm_eps=model["rms_norm_eps"], **more,
    )


def flat(tree):
    return {
        "/".join(str(getattr(k, "key", k)) for k in path): leaf
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]
    }


@pytest.fixture(scope="module")
def both():
    """The program's module and the reference's kind on the same seed."""
    from benchmarks.reference.models import moe_mla as kind
    from gordo_components_tpu.models.register import get_factory

    kwargs = estimator_kwargs(remat=True)
    kwargs.pop("kind")
    module = get_factory("moe_mla_decoder")(n_features=TAGS, **kwargs).module
    key = jax.random.PRNGKey(0)
    x = jax.random.uniform(jax.random.PRNGKey(1), (2, 16, TAGS))
    # the rows that follow each row: the window a row on, and one row more
    y = jnp.concatenate(
        [x[:, 1:], jax.random.uniform(jax.random.PRNGKey(2), (2, 1, TAGS))], axis=1
    )
    ours = module.init(key, x[:1], deterministic=True)["params"]
    theirs = kind.init(SMALL, key, TAGS, TAGS)
    return module, kind, ours, theirs, x, y


def test_the_same_seed_draws_the_same_weights(both):
    _, _, ours, theirs, _, _ = both
    ours, theirs = flat(ours), flat(theirs)
    assert sorted(ours) == sorted(theirs)
    for name in ours:
        assert np.array_equal(ours[name], theirs[name]), name


def test_prediction_and_both_loss_terms_are_the_references(both):
    module, kind, ours, theirs, x, y = both
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(
            module.apply({"params": ours}, x), kind.apply(SMALL, theirs, x),
            rtol=0, atol=2e-6,
        )
        nxt, after, counted = module.apply({"params": ours}, x, y, method="loss_terms")
        ref_nxt, ref_after = kind.loss_terms(SMALL, theirs, x, y)
    np.testing.assert_allclose(nxt, ref_nxt, rtol=2e-6)
    np.testing.assert_allclose(after, ref_after, rtol=2e-6)
    # two expert layers and the prediction module's; every token-slot that
    # fell on a held expert is counted, and nothing else
    assert counted.shape == (3, 2) and counted.dtype == jnp.int32
    assert 0 < int(counted.sum()) <= 3 * 2 * 16 * TAGS * 2


def test_the_gradient_of_every_leaf_is_the_references(both):
    module, kind, ours, theirs, x, y = both
    with jax.default_matmul_precision("highest"):
        mine = jax.grad(
            lambda p: module.apply({"params": p}, x, y, method="sample_losses")[0].sum()
        )(ours)
        ref = jax.grad(lambda p: kind.loss(SMALL, p, x, y).sum())(theirs)
    mine, ref = flat(mine), flat(ref)
    assert sorted(mine) == sorted(ref)
    for name, theirs_leaf in ref.items():
        scale = float(jnp.abs(theirs_leaf).max())
        if name.endswith("router_bias"):  # selects, and takes no gradient
            assert scale == 0.0 and not np.any(mine[name])
            continue
        assert scale > 0, name
        assert float(jnp.abs(mine[name] - theirs_leaf).max()) <= 2e-5 * scale, name


def _layer_inputs(n_tokens=48, seed=3):
    from gordo_components_tpu.models.factories import decoder as moe_mla

    D, I, E = 64, 32, 8
    keys = jax.random.split(jax.random.PRNGKey(seed), 8)
    x = jax.random.normal(keys[0], (n_tokens, D))
    weights = {
        "router": 0.3 * jax.random.normal(keys[1], (D, E)),
        "gate": 0.1 * jax.random.normal(keys[2], (E, D, I)),
        "up": 0.1 * jax.random.normal(keys[3], (E, D, I)),
        "down": 0.1 * jax.random.normal(keys[4], (E, I, D)),
        "shared": [0.1 * jax.random.normal(k, s) for k, s in
                   zip(keys[5:], [(D, I), (D, I), (I, D)])],
    }
    return moe_mla, x, weights


def _held_part(moe_mla, x, weights, chosen, gates, held):
    held = list(held)
    return moe_mla.grouped_experts(
        x, chosen, gates, held, 8,
        weights["gate"][jnp.asarray(held)], weights["up"][jnp.asarray(held)],
        weights["down"][jnp.asarray(held)],
    )


def test_the_shares_of_a_layer_add_up_to_the_uncut_layer():
    """Four chips hold two experts each. What each gives for its own experts,
    and the shared expert counted ONCE, is what a chip that holds all eight
    gives: the router, its top-k and its weights are over all experts on
    every chip alike."""
    moe_mla, x, weights = _layer_inputs()
    with jax.default_matmul_precision("highest"):
        chosen, gates = moe_mla.route(x, weights["router"], jnp.zeros(8), 2, 2.5)
        shared = moe_mla.swiglu(x, *weights["shared"])
        whole, whole_counts = _held_part(moe_mla, x, weights, chosen, gates, range(8))
        parts = [
            _held_part(moe_mla, x, weights, chosen, gates, share)
            for share in ([0, 1], [2, 3], [4, 5], [6, 7])
        ]
    together = shared + sum(part for part, _ in parts)
    np.testing.assert_allclose(together, shared + whole, rtol=0, atol=1e-5)
    # every (token, choice) slot is some chip's, once
    counts = np.concatenate([np.asarray(c) for _, c in parts])
    assert np.array_equal(counts, np.asarray(whole_counts))
    assert counts.sum() == 48 * 2
    # and the uncut layer is the reference's, which loops over its experts
    from benchmarks.reference.models import moe_mla as kind

    uncut = {**SMALL, "experts_held": list(range(8))}
    p = {
        "router": weights["router"], "router_bias": jnp.zeros(8),
        "shared_gate": weights["shared"][0], "shared_up": weights["shared"][1],
        "shared_down": weights["shared"][2], "experts_gate": weights["gate"],
        "experts_up": weights["up"], "experts_down": weights["down"],
    }
    with jax.default_matmul_precision("highest"):
        theirs = kind._experts(uncut, p, x[None])[0]
    np.testing.assert_allclose(shared + whole, theirs, rtol=0, atol=1e-5)


def test_the_grouped_product_is_the_per_expert_loop_under_a_skewed_router():
    """One held expert takes half the tokens, one none, the third a few: the
    sorted rows overflow the usual chunk, and none is dropped."""
    moe_mla, x, weights = _layer_inputs(n_tokens=64)
    held = [2, 6, 7]
    chosen = np.stack([np.arange(64) % 2 * 2, 3 + np.arange(64) % 3], axis=1)
    chosen[::9, 1] = 7  # expert 2: every other token; 7: a few; 6: none
    chosen = jnp.asarray(chosen, jnp.int32)
    gates = jax.random.uniform(jax.random.PRNGKey(9), (64, 2), minval=0.2)

    def grouped(x, w):
        return _held_part(moe_mla, x, w, chosen, gates, held)[0]

    def looped(x, w):
        out = jnp.zeros_like(x)
        for expert in held:
            share = jnp.sum(jnp.where(chosen == expert, gates, 0.0), axis=-1)
            out = out + share[:, None] * moe_mla.swiglu(
                x, w["gate"][expert], w["up"][expert], w["down"][expert]
            )
        return out

    with jax.default_matmul_precision("highest"):
        ours, counts = _held_part(moe_mla, x, weights, chosen, gates, held)
        np.testing.assert_allclose(ours, looped(x, weights), rtol=0, atol=1e-5)
        assert np.asarray(counts).tolist() == [32, 0, 8]
        # the gradient too, through the chunks that are skipped and the one
        # that is not
        mine = jax.grad(lambda x, w: jnp.sum(grouped(x, w) ** 2), argnums=(0, 1))(x, weights)
        ref = jax.grad(lambda x, w: jnp.sum(looped(x, w) ** 2), argnums=(0, 1))(x, weights)
    for a, b in zip(jax.tree_util.tree_leaves(mine), jax.tree_util.tree_leaves(ref)):
        np.testing.assert_allclose(a, b, rtol=0, atol=2e-5 * max(float(jnp.abs(b).max()), 1.0))


@pytest.mark.parametrize("n_rows, lookback", [(160, 16), (8960, 1024), (77, 16), (33, 16)])
def test_samples_lie_where_the_references_layout_lays_them(n_rows, lookback):
    """``prepare``'s windows and targets (``ops.windowing``) against
    ``reference/models.Layout``: every row from the first sample's first
    target on is predicted exactly once, the last row last."""
    from benchmarks.reference.models import Layout
    from gordo_components_tpu.ops import windowing

    lay = Layout(lookback, lookback, lookback)
    starts = windowing.window_starts(n_rows, lookback, 1, lookback)
    assert len(starts) == windowing.n_windows(n_rows, lookback, 1, lookback) == lay.n_samples(n_rows)
    assert starts[0] == lay.lead(n_rows)
    predicted = windowing.window_output_index(n_rows, lookback, 1, lookback)
    assert np.array_equal(predicted, np.arange(starts[0] + 1, n_rows))
    rows = np.arange(n_rows, dtype=np.float32)[:, None]
    windows = np.asarray(windowing.sliding_windows(rows, lookback, 1, lookback))
    targets = np.asarray(windowing.sample_targets(rows, lookback, 1, lookback))
    assert windows.shape == (len(starts), lookback, 1) == targets.shape
    assert np.array_equal(targets, windows + 1)  # the row after each row read


MODEL = {
    "DiffBasedAnomalyDetector": {"base_estimator": {"TransformedTargetRegressor": {
        "regressor": {"Pipeline": {"steps": ["MinMaxScaler", {"MoEMLAForecast": dict(
            estimator_kwargs({**SMALL, "num_hidden_layers": 2}, remat=True),
            batch_size=2, epochs=1,
        )}]}},
        "transformer": "MinMaxScaler",
    }}}
}


def test_one_machine_a_slice_through_fleet_build_store_and_serializer(tmp_path):
    """The new kind from a machine config through the slice loop (a slice of
    one machine, its folds in sequence on one donated training state), the
    commit, the store's ``CURRENT`` pointer and the serializer, to the loaded
    model's ``anomaly()``."""
    from gordo_components_tpu import serializer
    from gordo_components_tpu.models.analysis import analyze_model
    from gordo_components_tpu.observability.flightrec import RECORDER
    from gordo_components_tpu.parallel.build_fleet import (
        FleetMachineConfig, build_fleet,
    )
    from gordo_components_tpu.store import CURRENT_FILE

    machines = [
        FleetMachineConfig(name=f"m{i}", model_config=MODEL, data_config={
            "type": "RandomDataset", "resolution": "10min",
            "train_start_date": "2023-01-01T00:00:00+00:00",
            "train_end_date": "2023-01-02T12:00:00+00:00",
            "tag_list": [f"m{i}-t{j}" for j in range(TAGS)],
        })
        for i in range(2)
    ]
    built = build_fleet(machines, str(tmp_path), seed=3, n_splits=2, slice_size=1)
    assert sorted(built) == ["m0", "m1"]
    assert (tmp_path / "m0" / CURRENT_FILE).exists()

    model = serializer.load(built["m0"])
    parts = analyze_model(model)
    assert parts.estimator.rows_out == 16
    assert len(parts.estimator.history_) == 1
    assert np.isfinite(parts.estimator.history_[0])
    # it is kept on the host until it first predicts
    assert isinstance(jax.tree_util.tree_leaves(parts.estimator.params_)[0], np.ndarray)
    cv = parts.detector.cross_validation_
    assert cv["n_splits"] == 2 and len(cv["splits"]) == 2
    assert np.isfinite(parts.detector.total_threshold_)

    probe = np.random.default_rng(0).uniform(size=(40, TAGS)).astype(np.float32)
    frame = model.anomaly(probe)
    # two samples of sixteen rows each, the last row last
    assert len(frame) == 32
    assert np.all(np.isfinite(frame["total-anomaly-score"].values))
    pred = parts.estimator.predict(parts.input_scaler.transform(probe))
    assert pred.shape == (32, TAGS) and np.all((pred > 0) & (pred < 1))

    timeline = RECORDER.latest(kind="fleet-build")
    slices = [s for s in timeline.spans if s.name == "fleet.slice"]
    assert [s.attrs["machines"] for s in slices] == [1, 1]
    for one in slices:
        # a machine's counts: two layers with experts (one, and the
        # prediction module's), two held experts each
        counted = np.asarray(one.attrs["expert_tokens"])
        assert counted.shape == (1, 2, 2) and counted.sum() > 0
    saved = [s for s in timeline.spans if s.name == "fleet.checkpoint_save"]
    assert all(s.attrs["skipped"] for s in saved)
    for name in ("fleet.result_fetch", "fleet.commit"):
        moved = [s.attrs["bytes"] for s in timeline.spans if s.name == name]
        assert len(moved) == 2 and min(moved) > 100_000


def test_the_spec_of_a_memory_constrained_model_runs_its_fits_in_sequence():
    from gordo_components_tpu.models.analysis import analyze_model
    from gordo_components_tpu.parallel import fleet
    from gordo_components_tpu.parallel.build_fleet import _spec_for
    from gordo_components_tpu.serializer import pipeline_from_definition

    spec = _spec_for(analyze_model(pipeline_from_definition(MODEL)), TAGS, TAGS, 2)
    assert spec.memory_constrained and fleet.sequential_fits(spec)
    assert (spec.rows_out, spec.loss, spec.lookahead) == (16, "module", 1)
    # its training state is an argument of its own, handed back in place
    state = fleet.abstract_state(spec, 1, TAGS)
    program = fleet.fleet_program(spec, 160, TAGS, TAGS)
    shapes = [
        jax.ShapeDtypeStruct(s, d) for s, d in (
            ((1, 160, TAGS), jnp.float32), ((1, 160, TAGS), jnp.float32),
            ((1, 160), jnp.float32), ((1, fleet.prng_key_width()), jnp.uint32),
        )
    ]
    result, handed_back = jax.eval_shape(program, *shapes, state)
    assert jax.tree_util.tree_structure(handed_back) == jax.tree_util.tree_structure(state[1])
    assert jax.tree_util.tree_map(lambda a: a.shape, result.params) == \
        jax.tree_util.tree_map(lambda a: a.shape, state[0])
    assert result.counters["expert_tokens"].shape == (1, 2, 2)
