"""The gated grouped-query-attention / expert-layer decoder kind
(``afmoe_decoder``, ``AfMoEForecast``) at small widths on the CPU: hidden 64,
8 query heads over 2 key heads of 16, a window of 6 over sequences of 16, one
dense layer then one period of (sliding, sliding, sliding, full) expert
layers, 8 experts of which 2 are held beside one shared expert, vocabulary 64.

The program's module against the benchmark's plain reference
(``benchmarks/reference/models/afmoe.py``: the same equations, masked dense
attention over all keys, a dense pass of every held expert, no kernel) on
seeded weights: predictions, loss, counters, every gradient, and the fits of
``fleet_program`` in sequence; one planted fault per mechanism, each of which
the comparison catches; the share test (the shares of a layer, the shared
expert counted once, are the uncut layer); the configuration's parameter
count; and one machine through ``fleet-build``'s slice loop, store and
serializer to ``anomaly()``. Last, the other two decoder kinds read, bit for
bit, what they read before this kind's mechanisms joined the scaffold.
"""

import dataclasses
import hashlib
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from test_moe_gqa import estimator_kwargs as gqa_kwargs
from test_moe_mla import estimator_kwargs as mla_kwargs
from test_moe_mla import flat

SLIDING, FULL = "sliding_attention", "full_attention"
ROPES = {SLIDING: {"rope_type": "default", "rope_theta": 10000.0}}
SMALL = {
    "kind": "afmoe", "hidden_size": 64,
    "layer_types": [SLIDING, SLIDING, SLIDING, SLIDING, FULL],
    "num_dense_layers": 1, "intermediate_size": 96, "sliding_window": 6,
    "rope_parameters": ROPES, "num_attention_heads": 8,
    "num_key_value_heads": 2, "head_dim": 16, "moe_intermediate_size": 32,
    "num_experts": 8, "experts_held": [1, 5], "num_experts_per_tok": 2,
    "num_shared_experts": 1, "route_scale": 2.826,
    "mup_enabled": True, "rms_norm_eps": 1e-5, "vocab_size": 64, "lookback": 16,
    "query_block": 8, "logit_block": 8, "epochs": 1, "batch_size": 2,
    "n_splits": 2, "learning_rate": 1e-3,
}
TAGS = 3
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def estimator_kwargs(model=SMALL, **more):
    """The reference's dictionary as the program's estimator takes it."""
    return dict(
        lookback_window=model["lookback"], vocab_size=model["vocab_size"],
        hidden_size=model["hidden_size"], layer_types=list(model["layer_types"]),
        n_dense_layers=model["num_dense_layers"],
        intermediate_size=model["intermediate_size"],
        sliding_window=model["sliding_window"],
        rope_parameters=model["rope_parameters"],
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"], head_dim=model["head_dim"],
        moe_intermediate_size=model["moe_intermediate_size"],
        n_routed_experts=model["num_experts"],
        experts_held=list(model["experts_held"]),
        experts_per_token=model["num_experts_per_tok"],
        n_shared_experts=model["num_shared_experts"],
        route_scale=model["route_scale"],
        mup_enabled=model["mup_enabled"], rms_norm_eps=model["rms_norm_eps"], **more,
    )


def model_config(**more):
    """A machine config of the kind, as a fleet-build job reads it."""
    return {
        "DiffBasedAnomalyDetector": {"base_estimator": {"TransformedTargetRegressor": {
            "regressor": {"Pipeline": {"steps": ["MinMaxScaler", {"AfMoEForecast": dict(
                estimator_kwargs(remat=True), epochs=1, **more,
            )}]}},
            "transformer": "MinMaxScaler",
        }}}
    }


@pytest.fixture(scope="module")
def both():
    """The program's module and the reference's kind on the same seed."""
    from benchmarks.reference.models import afmoe as kind
    from gordo_components_tpu.models.register import get_factory

    module = get_factory("afmoe_decoder")(
        n_features=TAGS, **estimator_kwargs(remat=True)
    ).module
    key = jax.random.PRNGKey(0)
    x = jax.random.uniform(jax.random.PRNGKey(1), (2, 16, TAGS))
    y = jnp.concatenate(
        [x[:, 1:], jax.random.uniform(jax.random.PRNGKey(2), (2, 1, TAGS))], axis=1
    )
    ours = module.init(key, x[:1], deterministic=True)["params"]
    theirs = kind.init(SMALL, key, TAGS, TAGS)
    return module, kind, ours, theirs, x, y


def test_the_same_seed_draws_the_same_weights_dense_layer_then_periods(both):
    _, _, ours, theirs, _, _ = both
    ours, theirs = flat(ours), flat(theirs)
    assert sorted(ours) == sorted(theirs)
    for name in ours:
        assert np.array_equal(ours[name], theirs[name]), name
    # one dense layer (sliding), then one period: a stack of three sliding
    # layers and the full one
    assert ours[f"dense_layers/0_{SLIDING}/w_gate"].shape == (1, 1, 64, 96)
    assert ours[f"periods/0_{SLIDING}/experts_gate"].shape == (1, 3, 2, 64, 32)
    assert ours[f"periods/1_{FULL}/attn_gate"].shape == (1, 1, 64, 8 * 16)
    assert ours[f"periods/1_{FULL}/q_norm"].shape == (1, 1, 16)
    assert ours[f"periods/1_{FULL}/shared_gate"].shape == (1, 1, 64, 32)
    assert not np.any(ours[f"periods/1_{FULL}/router_bias"])
    assert not any("router" in name for name in ours if name.startswith("dense"))


def test_prediction_loss_and_counters_are_the_references(both):
    module, kind, ours, theirs, x, y = both
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(
            module.apply({"params": ours}, x), kind.apply(SMALL, theirs, x),
            rtol=0, atol=2e-6,
        )
        losses, counted = module.apply({"params": ours}, x, y, method="sample_losses")
        np.testing.assert_allclose(losses, kind.loss(SMALL, theirs, x, y), rtol=2e-6)
    # four expert layers; every token's 2 choices among all 8 experts are
    # counted, and the held experts' columns are the slots that fell here
    routed, held = np.asarray(counted["routed_tokens"]), np.asarray(counted["expert_tokens"])
    assert routed.shape == (4, 8) and routed.dtype == np.int32
    assert np.all(routed.sum(axis=-1) == 2 * 16 * TAGS * 2)
    assert np.array_equal(routed[:, [1, 5]], held)
    assert np.asarray(counted["attention_key_blocks"]).shape == (2, 2, 2)


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
        if name.endswith("router_bias"):  # no gradient: its update is a recipe
            assert scale == 0 and not np.any(mine[name]), name
            continue
        assert scale > 0, name
        assert float(jnp.abs(mine[name] - theirs_leaf).max()) <= 2e-5 * scale, name


def _without(names):
    """The kind with the leaves ``names`` left out of every layer: what a
    layer does not hold, it does not run."""
    from gordo_components_tpu.models.factories.afmoe import AfMoEDecoder

    class Planted(AfMoEDecoder):
        def _attention_shapes(self):
            return {k: v for k, v in super()._attention_shapes().items() if k not in names}

    return Planted


def _softmax_routed():
    """The kind with its router's scores a softmax in place of the sigmoid."""
    from gordo_components_tpu.models.factories.afmoe import AfMoEDecoder
    from gordo_components_tpu.models.factories.decoder import route

    class Planted(AfMoEDecoder):
        def _route(self, p, tokens):
            return route(tokens, p["router"], p["router_bias"], self.experts_per_token,
                         self.route_scale, "softmax")

    return Planted


def _stripped(tree, names):
    if isinstance(tree, dict):
        return {k: _stripped(v, names) for k, v in tree.items() if k not in names}
    return tree


FAULTS = {
    "gate removed": ("attn_gate",),
    "query/key norm removed": ("q_norm", "k_norm"),
    "post-norms removed": ("post_attn_norm", "post_ffn_norm"),
    "rotary on the full layer": {"rope_parameters": {**ROPES, FULL: ROPES[SLIDING]}},
    "softmax in place of sigmoid": _softmax_routed,
    "route scale dropped": {"route_scale": 1.0},
    "shared expert dropped": {"n_shared_experts": 0},
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_fails_the_comparison(both, fault):
    """Each mechanism, taken out or changed in the program alone, moves its
    prediction more than a hundred times the sound comparison's tolerance
    away from the reference's on the same weights."""
    from gordo_components_tpu.models.register import get_factory

    module, kind, _, theirs, x, _ = both
    planted = FAULTS[fault]
    params = theirs
    if not isinstance(planted, dict):
        fields = {f.name: getattr(module, f.name) for f in dataclasses.fields(module)
                  if f.name not in ("parent", "name")}
        if callable(planted):
            module = planted()(**fields)
        else:
            module = _without(planted)(**fields)
            params = _stripped(theirs, planted)
    else:
        module = get_factory("afmoe_decoder")(
            n_features=TAGS, **{**estimator_kwargs(remat=True), **planted}
        ).module
        if "n_shared_experts" in planted:
            params = _stripped(theirs, ("shared_gate", "shared_up", "shared_down"))
    with jax.default_matmul_precision("highest"):
        apart = float(jnp.abs(
            module.apply({"params": params}, x) - kind.apply(SMALL, theirs, x)
        ).max())
    assert apart > 100 * 2e-6, (fault, apart)


@pytest.mark.parametrize("chips", [2, 4])
def test_the_shares_of_a_layer_add_up_to_the_uncut_layer(chips):
    """``chips`` chips hold 8 / chips experts each of a layer's eight. What
    each gives for its own experts, summed, with the shared expert counted
    once, is what one chip that holds all eight gives; the router, its
    selection bias, its top-k and its scaled weights are over all experts on
    every chip alike. And the uncut layer is the reference's, which loops
    over its experts."""
    from benchmarks.reference.models import afmoe as kind
    from gordo_components_tpu.models.factories.decoder import (
        grouped_experts, route, swiglu,
    )

    D, I, E = 64, 32, 8
    keys = jax.random.split(jax.random.PRNGKey(3), 9)
    x = jax.random.normal(keys[0], (48, D))
    p = {
        "router": 0.3 * jax.random.normal(keys[1], (D, E)),
        "router_bias": 0.1 * jax.random.normal(keys[8], (E,)),
        "experts_gate": 0.1 * jax.random.normal(keys[2], (E, D, I)),
        "experts_up": 0.1 * jax.random.normal(keys[3], (E, D, I)),
        "experts_down": 0.1 * jax.random.normal(keys[4], (E, I, D)),
        "shared_gate": 0.1 * jax.random.normal(keys[5], (D, I)),
        "shared_up": 0.1 * jax.random.normal(keys[6], (D, I)),
        "shared_down": 0.1 * jax.random.normal(keys[7], (I, D)),
    }

    def held_part(held):
        at = jnp.asarray(list(held))
        return grouped_experts(
            x, chosen, weights, list(held), E,
            p["experts_gate"][at], p["experts_up"][at], p["experts_down"][at],
        )

    with jax.default_matmul_precision("highest"):
        chosen, weights = route(x, p["router"], p["router_bias"], 2, 2.826, "sigmoid")
        shared = swiglu(x, p["shared_gate"], p["shared_up"], p["shared_down"])
        parts = [held_part(range(c * E // chips, (c + 1) * E // chips)) for c in range(chips)]
        whole, whole_counts = held_part(range(E))
        theirs = kind._experts({**SMALL, "experts_held": list(range(E))}, p, x)
    np.testing.assert_allclose(
        shared + sum(part for part, _ in parts), shared + whole, rtol=0, atol=1e-5
    )
    np.testing.assert_allclose(shared + whole, theirs, rtol=0, atol=1e-5)
    counts = np.concatenate([np.asarray(c) for _, c in parts])
    assert np.array_equal(counts, np.asarray(whole_counts))
    assert counts.sum() == 48 * 2  # every (token, choice) slot is some chip's, once


def test_the_fits_in_sequence_are_the_references_fits():
    """``fleet_program``'s sequential fits (a fold, then the final fit, each
    one Adam step of two samples on one donated training state) against the
    plain reference's build on the same rows and key: the final fit's loss
    and every leaf's change from where it started."""
    from benchmarks.reference import build as ref_build
    from gordo_components_tpu.models.analysis import analyze_model
    from gordo_components_tpu.parallel import fleet
    from gordo_components_tpu.parallel.build_fleet import _spec_for
    from gordo_components_tpu.serializer import pipeline_from_definition

    n_rows = 2 * 16 + 1  # two samples: one optimizer step a fit at batch 2
    spec = _spec_for(
        analyze_model(pipeline_from_definition(model_config(batch_size=2))), TAGS, TAGS, 1
    )
    assert fleet.sequential_fits(spec)
    rng = np.random.default_rng(7)
    X = np.cumsum(rng.normal(size=(1, n_rows, TAGS)), axis=1).astype(np.float32)
    w = np.ones((1, n_rows), np.float32)
    keys = np.asarray(jax.random.split(jax.random.PRNGKey(9), 1))
    with jax.default_matmul_precision("highest"):
        state = fleet.fleet_state(spec, 1, TAGS)(keys)
        result, _ = fleet.fleet_program(spec, n_rows, TAGS, TAGS)(X, X, w, keys, state)
        build, _, initial = ref_build.make_build(
            {**SMALL, "n_splits": 1}, n_rows, TAGS
        )
        ref = jax.jit(build)(X[0], w[0], keys[0])
        start = initial(keys[0])
    np.testing.assert_allclose(result.loss_history[0], ref["loss_history"], rtol=2e-6)
    mine = flat(jax.tree_util.tree_map(lambda a: a[0], result.params))
    theirs, start = flat(ref["params"]), flat(start)
    assert sorted(mine) == sorted(theirs)
    for name, leaf in theirs.items():
        moved = float(jnp.linalg.norm(leaf - start[name]))
        if name.endswith("router_bias"):
            assert moved == 0 and np.array_equal(mine[name], start[name]), name
            continue
        assert moved > 0, name
        assert float(jnp.linalg.norm(mine[name] - leaf)) <= 1e-3 * moved, name


def test_the_configuration_states_the_count_the_reference_trains():
    """``trinity-mini.json``'s parameter count is the reference's count of
    the tree it draws, 16 bytes of training state a parameter is the 8.07 GB
    it states, and the optimizer step reads and writes 28 a parameter."""
    from benchmarks.reference import models

    with open(os.path.join(ROOT, "benchmarks", "configs", "trinity-mini.json")) as fh:
        config = json.load(fh)
    model = config["reference_model"]
    kind = models.for_kind(model)
    assert kind.n_parameters(model) == config["parameters"] == 504_147_712
    assert models.state_bytes(model, config["tags"]) == 28.0 * config["parameters"]
    assert round(16 * config["parameters"] / 1e9, 2) == 8.07
    # the program's estimator and the reference describe one block
    est = config["model"]["DiffBasedAnomalyDetector"]["base_estimator"][
        "TransformedTargetRegressor"]["regressor"]["Pipeline"]["steps"][1]["AfMoEForecast"]
    assert est["layer_types"] == model["layer_types"] == config["layer_types"][:1] + config["layer_types"][4:8]
    assert est["experts_held"] == model["experts_held"] == list(range(config["num_experts"]))
    assert (est["n_routed_experts"], config["published"]["num_experts"]) == (128, 128)


def test_one_machine_a_slice_through_fleet_build_store_and_serializer(tmp_path):
    """The kind from a machine config through the slice loop (a slice of one
    machine, its folds in sequence on one donated training state), the
    commit, the store's ``CURRENT`` pointer and the serializer, to the loaded
    model's ``anomaly()``; the program memoised across slices; its counters
    on the slice's span, and the benchmark's reader of the router's load."""
    from gordo_components_tpu import serializer
    from gordo_components_tpu.models.analysis import analyze_model
    from gordo_components_tpu.models.models import AfMoEForecast
    from gordo_components_tpu.observability.flightrec import RECORDER
    from gordo_components_tpu.parallel import fleet
    from gordo_components_tpu.parallel.build_fleet import (
        FleetMachineConfig, _spec_for, build_fleet,
    )
    from gordo_components_tpu.serializer import pipeline_from_definition

    config = model_config(attention_operand_dtype="bfloat16", batch_size=2)
    spec = _spec_for(analyze_model(pipeline_from_definition(config)), TAGS, TAGS, 2)
    # the spec keys the fleet program's memo
    assert hash(spec) == hash(
        _spec_for(analyze_model(pipeline_from_definition(config)), TAGS, TAGS, 2)
    )
    assert spec.memory_constrained and fleet.sequential_fits(spec)

    machines = [
        FleetMachineConfig(name=f"m{i}", model_config=config, data_config={
            "type": "RandomDataset", "resolution": "10min",
            "train_start_date": "2023-01-01T00:00:00+00:00",
            "train_end_date": "2023-01-02T12:00:00+00:00",
            "tag_list": [f"m{i}-t{j}" for j in range(TAGS)],
        })
        for i in range(3)
    ]
    built = build_fleet(machines, str(tmp_path), seed=3, n_splits=2, slice_size=1)
    assert sorted(built) == ["m0", "m1", "m2"]
    model = serializer.load(built["m0"])
    parts = analyze_model(model)
    assert isinstance(parts.estimator, AfMoEForecast)
    assert parts.estimator.kind == "afmoe_decoder"
    assert parts.estimator.rows_out == 16
    assert np.isfinite(parts.estimator.history_[0])
    frame = model.anomaly(np.random.default_rng(0).uniform(size=(40, TAGS)).astype(np.float32))
    assert len(frame) == 32 and np.all(np.isfinite(frame["total-anomaly-score"].values))

    timeline = RECORDER.latest(kind="fleet-build")
    slices = [s for s in timeline.spans if s.name == "fleet.slice"]
    programs = [s for s in timeline.spans if s.name == "fleet.program"]
    assert [s.attrs["memo_hit"] for s in programs] == [False, True, True]
    for one in slices:
        routed = np.asarray(one.attrs["routed_tokens"])
        assert routed.shape == (1, 4, 8)
        assert np.array_equal(routed[0][:, [1, 5]], np.asarray(one.attrs["expert_tokens"])[0])
    from benchmarks.layer_metrics import router_load_max_over_mean

    steady = np.asarray(slices[1].attrs["routed_tokens"], np.float64)[0]
    view = {"run": {"config": {"reference_model": SMALL}}}
    assert router_load_max_over_mean.read(view) == pytest.approx(
        float((steady.max(axis=-1) / steady.mean(axis=-1)).max())
    )


def _fingerprint(kind, kwargs):
    from gordo_components_tpu.models.register import get_factory

    module = get_factory(kind)(n_features=TAGS, **kwargs).module
    x = jax.random.uniform(jax.random.PRNGKey(1), (2, 16, TAGS))
    y = jnp.concatenate([x[:, 1:], jax.random.uniform(jax.random.PRNGKey(2), (2, 1, TAGS))], axis=1)
    p = module.init(jax.random.PRNGKey(0), x[:1], deterministic=True)["params"]
    losses, counted = module.apply({"params": p}, x, y, method="sample_losses")
    grads = jax.grad(
        lambda p: module.apply({"params": p}, x, y, method="sample_losses")[0].sum()
    )(p)
    digest = hashlib.sha256()
    for leaf in ([module.apply({"params": p}, x), losses] + jax.tree_util.tree_leaves(counted)
                 + jax.tree_util.tree_leaves(grads)):
        digest.update(np.asarray(leaf).tobytes())
    return digest.hexdigest()[:16]


@pytest.mark.parametrize("kind,kwargs,before", [
    ("moe_gqa_decoder", lambda: gqa_kwargs(remat=True), "d1683c92a977c8ff"),
    ("moe_mla_decoder",
     lambda: {k: v for k, v in mla_kwargs(remat=True).items() if k != "kind"},
     "cd26d2e9f8a01e48"),
])
def test_the_other_decoders_read_what_they_read_before(kind, kwargs, before):
    """Predictions, losses, counters and every gradient of ``moe_gqa`` and
    ``moe_mla`` at their tests' small sizes, bit for bit on the CPU, as the
    scaffold read them before it learnt sub-block output norms, a dense
    stack in front of the period scan and counters beyond the held experts'
    slots: those run only where a layer's parameters hold them."""
    assert _fingerprint(kind, kwargs()) == before
