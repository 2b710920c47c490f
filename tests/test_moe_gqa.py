"""The grouped-query-attention / expert-layer decoder kind
(``moe_gqa_decoder``, ``MoEGQAForecast``) at small widths on the CPU: hidden
64, 8 query heads over 2 key heads of 16, a window of 6 over sequences of
16, two periods of (sliding, sliding, full), 8 experts of which 2 are held,
vocabulary 64.

The program's module against the benchmark's plain reference
(``benchmarks/reference/models/moe_gqa.py``: the same equations, masked dense
attention over all keys, a dense pass of every held expert, no kernel) on
seeded weights; the rotary tables against the published formula written out
here; the softmax router; the share test (what the eight shares of a layer
give is the uncut layer: there is no shared expert, nothing is counted once);
the period-stacked layout; and one machine through ``fleet-build``'s slice
loop, store and serializer to ``anomaly()``.
"""

import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from test_moe_mla import flat

SLIDING, FULL = "sliding_attention", "full_attention"
ROPES = {
    SLIDING: {"rope_type": "default", "rope_theta": 500000.0},
    FULL: {"rope_type": "yarn", "rope_theta": 500000.0, "factor": 16.0,
           "original_max_position_embeddings": 8, "beta_fast": 32.0,
           "beta_slow": 1.0, "attention_factor": 1.2772588722239782},
}
SMALL = {
    "kind": "moe_gqa", "hidden_size": 64,
    "layer_types": [SLIDING, SLIDING, FULL] * 2, "sliding_window": 6,
    "rope_parameters": ROPES, "num_attention_heads": 8,
    "num_key_value_heads": 2, "head_dim": 16, "moe_intermediate_size": 32,
    "num_experts": 8, "experts_held": [1, 5], "num_experts_per_tok": 2,
    "rms_norm_eps": 1e-6, "vocab_size": 64, "lookback": 16, "query_block": 8,
    "epochs": 1, "batch_size": 2, "n_splits": 2, "learning_rate": 1e-3,
}
TAGS = 3


def estimator_kwargs(model=SMALL, **more):
    """The reference's dictionary as the program's estimator takes it."""
    return dict(
        lookback_window=model["lookback"],
        vocab_size=model["vocab_size"], hidden_size=model["hidden_size"],
        layer_types=list(model["layer_types"]),
        sliding_window=model["sliding_window"],
        rope_parameters=model["rope_parameters"],
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"], head_dim=model["head_dim"],
        moe_intermediate_size=model["moe_intermediate_size"],
        n_routed_experts=model["num_experts"],
        experts_held=list(model["experts_held"]),
        experts_per_token=model["num_experts_per_tok"],
        rms_norm_eps=model["rms_norm_eps"], **more,
    )


@pytest.fixture(scope="module")
def both():
    """The program's module and the reference's kind on the same seed."""
    from benchmarks.reference.models import moe_gqa as kind
    from gordo_components_tpu.models.register import get_factory

    kwargs = estimator_kwargs(remat=True)
    module = get_factory("moe_gqa_decoder")(n_features=TAGS, **kwargs).module
    key = jax.random.PRNGKey(0)
    x = jax.random.uniform(jax.random.PRNGKey(1), (2, 16, TAGS))
    y = jnp.concatenate(
        [x[:, 1:], jax.random.uniform(jax.random.PRNGKey(2), (2, 1, TAGS))], axis=1
    )
    ours = module.init(key, x[:1], deterministic=True)["params"]
    theirs = kind.init(SMALL, key, TAGS, TAGS)
    return module, kind, ours, theirs, x, y


def test_the_same_seed_draws_the_same_weights_stacked_by_period(both):
    _, _, ours, theirs, _, _ = both
    ours, theirs = flat(ours), flat(theirs)
    assert sorted(ours) == sorted(theirs)
    for name in ours:
        assert np.array_equal(ours[name], theirs[name]), name
    # two periods; in each a stack of two sliding layers, then the full one
    assert ours[f"periods/0_{SLIDING}/wq"].shape == (2, 2, 64, 8 * 16)
    assert ours[f"periods/1_{FULL}/wk"].shape == (2, 1, 64, 2 * 16)
    assert ours[f"periods/0_{SLIDING}/experts_gate"].shape == (2, 2, 2, 64, 32)
    assert not any("shared" in name or "mtp" in name or "bias" in name for name in ours)


def test_prediction_and_loss_are_the_references_and_the_counters_count(both):
    module, kind, ours, theirs, x, y = both
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(
            module.apply({"params": ours}, x), kind.apply(SMALL, theirs, x),
            rtol=0, atol=2e-6,
        )
        losses, counted = module.apply({"params": ours}, x, y, method="sample_losses")
        np.testing.assert_allclose(losses, kind.loss(SMALL, theirs, x, y), rtol=2e-6)
    # six layers, two held experts each; every token-slot that fell on a held
    # expert is counted, and nothing else
    tokens = counted["expert_tokens"]
    assert tokens.shape == (6, 2) and tokens.dtype == jnp.int32
    assert 0 < int(tokens.sum()) <= 6 * 2 * 16 * TAGS * 2
    # one block of 16 rows a sequence: a tile a head, a layer, a sequence
    # forward, two backward, window or not
    visits = np.asarray(counted["attention_key_blocks"])
    assert visits.shape == (2, 2, 2)
    assert visits[0].tolist() == [[4 * 8 * 6, 4 * 8 * 6], [2 * 4 * 8 * 6, 2 * 4 * 8 * 6]]
    assert visits[1].tolist() == [[2 * 8 * 6, 2 * 8 * 6], [2 * 2 * 8 * 6, 2 * 2 * 8 * 6]]


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
        assert scale > 0, name
        assert float(jnp.abs(mine[name] - theirs_leaf).max()) <= 2e-5 * scale, name


def test_the_visit_counter_of_a_long_sequence_follows_the_band():
    """At 1,024 rows in blocks of 512 with a window of 256, a sliding layer's
    grids visit 3 of the 3 tiles causal attention does... and at 2,048 rows,
    7 of 10: the counter is the band's."""
    from gordo_components_tpu.models.register import get_factory

    kwargs = estimator_kwargs(
        {**SMALL, "lookback": 2048, "sliding_window": 256, "layer_types": [SLIDING, FULL]}
    )
    module = get_factory("moe_gqa_decoder")(n_features=TAGS, **kwargs).module
    sliding, full = np.asarray(module.attention_key_blocks(2048, 3))
    heads_and_sequences = 8 * 3
    # four blocks of 512: causal 1 + 2 + 3 + 4; the band 1 + 2 + 2 + 2
    assert full.tolist() == [[10 * heads_and_sequences] * 2, [20 * heads_and_sequences] * 2]
    assert sliding.tolist() == [
        [7 * heads_and_sequences, 10 * heads_and_sequences],
        [14 * heads_and_sequences, 20 * heads_and_sequences],
    ]


def test_yarn_frequencies_and_attention_factor_are_the_published_formula():
    """Mellum2's own ``rope_parameters`` at head_dim 128, written out: the
    default frequencies ``theta^(-2i/d)``; the full layers' blended between
    them and a sixteenth of them by the ramp between the dimensions that
    turn 32 times and once over 8,192 positions; cosine and sine times
    ``0.1 ln 16 + 1``."""
    from benchmarks.reference.models import moe_gqa as kind
    from gordo_components_tpu.models.factories.moe_gqa import (
        rotary_frequencies, rotary_halves,
    )

    d, theta = 128, 500000.0
    yarn = {**ROPES[FULL], "original_max_position_embeddings": 8192}
    published = np.array([theta ** (-2.0 * i / d) for i in range(d // 2)])
    low = math.floor(d * math.log(8192 / (32 * 2 * math.pi)) / (2 * math.log(theta)))
    high = math.ceil(d * math.log(8192 / (1 * 2 * math.pi)) / (2 * math.log(theta)))
    assert (low, high) == (18, 35)
    expected = np.empty(d // 2)
    for i in range(d // 2):
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        expected[i] = published[i] * (1.0 - ramp) + published[i] / 16.0 * ramp
    for table in (
        lambda rope: rotary_frequencies(d, rope),
        lambda rope: kind.rope_table({"head_dim": d, "rope_parameters": {FULL: rope}}, FULL),
    ):
        inv, factor = table(ROPES[SLIDING])
        np.testing.assert_allclose(inv, published, rtol=1e-12)
        assert factor == 1.0
        inv, factor = table(yarn)
        np.testing.assert_allclose(inv, expected, rtol=1e-12)
        assert factor == 1.2772588722239782 == 0.1 * math.log(16.0) + 1.0
        # without the key the factor is the formula's
        inv, factor = table({k: v for k, v in yarn.items() if k != "attention_factor"})
        assert factor == 0.1 * math.log(16.0) + 1.0
    # the fast dimensions keep their frequency, the slow ones a sixteenth
    np.testing.assert_allclose(expected[:19], published[:19])
    np.testing.assert_allclose(expected[35:], published[35:] / 16.0)
    # dimension i turns with dimension i + d/2, both times the factor
    x = jnp.zeros((3, 1, d)).at[:, 0, 5].set(1.0)
    turned = np.asarray(rotary_halves(x, expected, 1.25))
    for t in range(3):
        assert turned[t, 0, 5] == pytest.approx(1.25 * math.cos(t * expected[5]), abs=1e-6)
        assert turned[t, 0, 5 + 64] == pytest.approx(1.25 * math.sin(t * expected[5]), abs=1e-6)
        assert np.count_nonzero(turned[t, 0]) <= 2


def test_the_softmax_router_is_a_softmax_over_the_chosen_logits():
    from gordo_components_tpu.models.factories.decoder import route

    keys = jax.random.split(jax.random.PRNGKey(4), 2)
    x = jax.random.normal(keys[0], (48, 64))
    router = 0.3 * jax.random.normal(keys[1], (64, 8))
    logits = np.asarray(x, np.float64) @ np.asarray(router, np.float64)
    chosen, weights = route(x, router, None, 3, 1.0, "softmax")
    top = np.argsort(-logits, axis=-1)[:, :3]
    assert np.array_equal(np.sort(np.asarray(chosen), axis=-1), np.sort(top, axis=-1))
    picked = np.take_along_axis(logits, np.asarray(chosen), axis=-1)
    expected = np.exp(picked) / np.exp(picked).sum(axis=-1, keepdims=True)
    np.testing.assert_allclose(weights, expected, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(weights).sum(axis=-1), 1.0, rtol=1e-6)
    # the sigmoid form is untouched by the softmax form's arguments
    with pytest.raises(ValueError, match="scoring_func"):
        route(x, router, None, 3, 1.0, "tanh")


def test_the_eight_shares_of_a_layer_add_up_to_the_uncut_layer():
    """Eight chips hold one expert each of a layer's eight. What each gives
    for its own expert, summed, is what a chip that holds all eight gives:
    there is no shared expert, so nothing is counted once; the router, its
    top-k and its weights are over all experts on every chip alike. And the
    uncut layer is the reference's, which loops over its experts."""
    from benchmarks.reference.models import moe_gqa as kind
    from gordo_components_tpu.models.factories.decoder import grouped_experts, route

    D, I, E = 64, 32, 8
    keys = jax.random.split(jax.random.PRNGKey(3), 5)
    x = jax.random.normal(keys[0], (48, D))
    p = {
        "router": 0.3 * jax.random.normal(keys[1], (D, E)),
        "experts_gate": 0.1 * jax.random.normal(keys[2], (E, D, I)),
        "experts_up": 0.1 * jax.random.normal(keys[3], (E, D, I)),
        "experts_down": 0.1 * jax.random.normal(keys[4], (E, I, D)),
    }

    def held_part(held):
        at = jnp.asarray(list(held))
        return grouped_experts(
            x, chosen, gates, list(held), E,
            p["experts_gate"][at], p["experts_up"][at], p["experts_down"][at],
        )

    with jax.default_matmul_precision("highest"):
        chosen, gates = route(x, p["router"], None, 2, 1.0, "softmax")
        whole, whole_counts = held_part(range(E))
        parts = [held_part([e]) for e in range(E)]
        theirs = kind._experts({**SMALL, "experts_held": list(range(E))}, p, x)
    np.testing.assert_allclose(sum(part for part, _ in parts), whole, rtol=0, atol=1e-5)
    counts = np.concatenate([np.asarray(c) for _, c in parts])
    assert np.array_equal(counts, np.asarray(whole_counts))
    assert counts.sum() == 48 * 2  # every (token, choice) slot is some chip's, once
    np.testing.assert_allclose(whole, theirs, rtol=0, atol=1e-5)


def test_a_pattern_is_cut_into_its_shortest_period_and_runs():
    from gordo_components_tpu.models.factories.moe_gqa import period_runs

    mellum = ([SLIDING] * 3 + [FULL]) * 7
    assert period_runs(mellum) == (7, [(SLIDING, 3), (FULL, 1)])
    assert period_runs(mellum[:4]) == (1, [(SLIDING, 3), (FULL, 1)])
    assert period_runs([FULL] * 5) == (5, [(FULL, 1)])
    assert period_runs([SLIDING, FULL, FULL]) == (1, [(SLIDING, 1), (FULL, 2)])


MODEL = {
    "DiffBasedAnomalyDetector": {"base_estimator": {"TransformedTargetRegressor": {
        "regressor": {"Pipeline": {"steps": ["MinMaxScaler", {"MoEGQAForecast": dict(
            estimator_kwargs({**SMALL, "layer_types": [SLIDING, FULL]}, remat=True),
            # as the 8k cell's configuration asks: the kernel's operands in
            # bfloat16, through the estimator, the factory and the slice loop
            attention_operand_dtype="bfloat16", batch_size=2, epochs=1,
        )}]}},
        "transformer": "MinMaxScaler",
    }}}
}


def test_one_machine_a_slice_through_fleet_build_store_and_serializer(tmp_path):
    """The kind from a machine config through the slice loop (a slice of one
    machine, its folds in sequence on one donated training state), the
    commit, the store's ``CURRENT`` pointer and the serializer, to the loaded
    model's ``anomaly()``; its counters on the slice's span."""
    from gordo_components_tpu import serializer
    from gordo_components_tpu.models.analysis import analyze_model
    from gordo_components_tpu.models.models import MoEGQAForecast
    from gordo_components_tpu.observability.flightrec import RECORDER
    from gordo_components_tpu.parallel import fleet
    from gordo_components_tpu.parallel.build_fleet import (
        FleetMachineConfig, _spec_for, build_fleet,
    )
    from gordo_components_tpu.serializer import pipeline_from_definition
    from gordo_components_tpu.store import CURRENT_FILE

    spec = _spec_for(analyze_model(pipeline_from_definition(MODEL)), TAGS, TAGS, 2)
    # the spec keys the fleet program's memo: unhashable, every slice would
    # trace, lower and load its program again (21 s a slice on the chip)
    assert hash(spec) == hash(
        _spec_for(analyze_model(pipeline_from_definition(MODEL)), TAGS, TAGS, 2)
    )
    assert spec.memory_constrained and fleet.sequential_fits(spec)
    assert (spec.rows_out, spec.loss, spec.lookahead) == (16, "module", 1)

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
    assert isinstance(parts.estimator, MoEGQAForecast)
    assert parts.estimator.kind == "moe_gqa_decoder"  # the class's, not the config's
    assert spec.module.attention_operand_dtype == "bfloat16"
    assert parts.estimator.rows_out == 16
    assert len(parts.estimator.history_) == 1 and np.isfinite(parts.estimator.history_[0])
    assert np.isfinite(parts.detector.total_threshold_)
    probe = np.random.default_rng(0).uniform(size=(40, TAGS)).astype(np.float32)
    frame = model.anomaly(probe)
    assert len(frame) == 32  # two samples of sixteen rows each, the last row last
    assert np.all(np.isfinite(frame["total-anomaly-score"].values))

    timeline = RECORDER.latest(kind="fleet-build")
    slices = [s for s in timeline.spans if s.name == "fleet.slice"]
    assert [s.attrs["machines"] for s in slices] == [1, 1]
    programs = [s for s in timeline.spans if s.name == "fleet.program"]
    assert [s.attrs["memo_hit"] for s in programs] == [False, True]
    for one in slices:
        counted = np.asarray(one.attrs["expert_tokens"])
        assert counted.shape == (1, 2, 2) and counted.sum() > 0
        visits = np.asarray(one.attrs["attention_key_blocks"])
        assert visits.shape == (1, 2, 2, 2) and np.all(visits > 0)
        # summed over the final fit's steps: the same share whatever their count
        assert np.array_equal(visits[0, :, :, 0], visits[0, :, :, 1])
    # the benchmark's reader takes it off the steady slice's span: a sequence
    # of 16 rows is one tile, so the window hides none of causal's
    from benchmarks.layer_metrics import attention_key_blocks_visited_pct

    assert attention_key_blocks_visited_pct.read({}) == 100.0
    # the folds predict their test samples, the final fit none: the reader
    # takes the share off the steady (second) slice's span
    from benchmarks.layer_metrics import predicted_samples_pct

    steady = slices[1].attrs
    predicted = steady["predicted_samples"][0]
    assert 0 < predicted < steady["predictable_samples"][0]
    assert predicted_samples_pct.read({}) == pytest.approx(
        100.0 * predicted / steady["predictable_samples"][0]
    )
