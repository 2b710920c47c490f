"""Tier-1's hold on the benchmark's plain reference and its model kinds
(``benchmarks/reference/``), which ``benchmarks/tests`` holds in full by hand:

* the golden comparison for ``lstm`` (the reference reads, for the kind of the
  guarded cell, what it read before kinds could bring a loss, a layout and
  blocks of their own);
* the kinds' contract, run whole on a kind that brings every optional
  function: the stand-in ``tokens`` kind that lives with the benchmark's
  tests, and ``moe_mla``, ``moe_gqa`` and ``afmoe`` at small widths. Through ``make_build``, ``anomaly``,
  ``slice_counts`` and ``compare.machine_numbers``, with a planted fault read
  as one.
"""

import importlib
import sys

import numpy as np
import pytest

from test_afmoe import SMALL as SMALL_AF
from test_moe_gqa import SMALL as SMALL_GQA
from test_moe_mla import SMALL

TOKENS = {
    "kind": "tokens", "vocab": 64, "width": 16, "blocks": 1, "lookback": 16,
    "aux_weight": 0.3, "epochs": 1, "batch_size": 4, "micro_batch": 2,
    "n_splits": 1, "learning_rate": 3e-3,
}
KINDS = {
    "tokens": TOKENS, "moe_mla": {**SMALL, "n_splits": 1},
    "moe_gqa": {**SMALL_GQA, "n_splits": 1}, "afmoe": {**SMALL_AF, "n_splits": 1},
}
TAGS, N_ROWS, N_REAL = 3, 160, 150


@pytest.mark.parametrize("kind", ["lstm"])
def test_the_reference_reads_its_golden_numbers(kind):
    from benchmarks.tests import test_reference_golden as golden

    golden.test_the_reference_reads_what_it_read_before_kinds_could_bring_more(kind)


def _built(model, fault=None):
    import jax

    from benchmarks.reference import build as ref_build

    rng = np.random.default_rng(11)
    walk = np.cumsum(rng.normal(size=(N_REAL, TAGS)), axis=0)
    raw = (walk + 3.0 * np.sin(np.arange(N_REAL) / 9.0)[:, None]).astype(np.float32)
    X = np.zeros((N_ROWS, TAGS), np.float32)
    w = np.zeros((N_ROWS,), np.float32)
    X[N_ROWS - N_REAL:], w[N_ROWS - N_REAL:] = raw, 1.0
    build, anomaly, initial = ref_build.make_build(model, N_ROWS, TAGS, fault=fault)

    def one(X, w, key, probe):
        result = build(X, w, key)
        result["anomaly_mean"] = anomaly(result, probe)
        result["params0"] = initial(key)
        return result

    with jax.default_matmul_precision("highest"):
        out = jax.device_get(jax.jit(one)(X, w, jax.random.PRNGKey(5), raw[-40:]))
    # what ``compare.machine_numbers`` reads of either side
    out.update(rows=N_REAL, x_sum=float(raw.astype(np.float64).sum()),
               target_scale=out["input_scale"], anomaly_replayed=out["anomaly_mean"])
    return out


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_a_kind_that_brings_everything_goes_through_the_reference_whole(kind, monkeypatch):
    from benchmarks import flops_bytes
    from benchmarks.reference import compare, models

    if kind == "tokens":  # the stand-in lives with the benchmark's tests
        monkeypatch.setitem(
            sys.modules, "benchmarks.reference.models.tokens",
            importlib.import_module("benchmarks.tests.kinds.tokens"),
        )
    model = KINDS[kind]
    module = models.for_kind(model)
    assert all(hasattr(module, name) for name in models.REQUIRED + models.OPTIONAL)
    lay = models.layout(model)
    assert lay == (16, 16, 16) and lay.n_samples(N_ROWS) == 9 and lay.lead(N_ROWS) == 15

    sound = _built(model)
    for key in ("total_threshold", "tag_thresholds", "cv_mse", "error_scale",
                "anomaly_mean", "loss_history"):
        assert np.all(np.isfinite(sound[key])), key
    assert sound["tag_thresholds"].shape == (TAGS,) and sound["cv_mse"].shape == (1,)
    # near the cross-entropy of a uniform guess on both heads at the start
    assert 0.5 * np.log(64) < sound["loss_history"][0] < 1.35 * np.log(64)
    numbers = compare.machine_numbers(sound, sound)
    assert all(value == 0 for value in numbers.values()), numbers
    unchanged = compare.machine_numbers(_built(model, fault="state_unchanged"), sound)
    assert unchanged["param_change_gap"] == 1.0

    counts = flops_bytes.slice_counts(model, 2, N_ROWS, TAGS)
    batch = model["batch_size"]
    steps = -(-lay.n_samples(N_ROWS) // batch)
    assert counts["train_steps"] == 2 * steps
    assert counts["flops"] == 2 * 2 * steps * batch * (
        module.train_flops(model, TAGS) + module.forward_flops(model, TAGS)["total"]
    )
    rows = 2 * 2 * 2 * N_ROWS * 4.0 * TAGS
    assert counts["bytes"] == rows + 2 * counts["train_steps"] * module.state_bytes(model, TAGS)
    assert module.state_bytes(model, TAGS) == 28.0 * sum(
        leaf.size for leaf in _leaves(sound["params"])
    )


def _leaves(tree):
    if isinstance(tree, dict):
        for value in tree.values():
            yield from _leaves(value)
    else:
        yield np.asarray(tree)


def test_moe_mla_counts_no_more_than_its_forward_pass_multiplies():
    """Its count takes attention at its causal mean and the experts at their
    expected slots, where its plain forward pass multiplies whole score
    matrices and every held expert over every token: under the jaxpr's
    products, never over (a count over them would flatter a share of peak)."""
    from benchmarks.reference import models
    from benchmarks.tests.test_flops_bytes import product_flops_a_sample

    model = KINDS["moe_mla"]
    kind = models.for_kind(model)
    multiplied = product_flops_a_sample(kind, model, TAGS)
    total = kind.forward_flops(model, TAGS)["total"]
    assert 0.5 * multiplied <= total <= multiplied, (total, multiplied)
    # the routed part: 2 of 8 experts held and 2 chosen a token make half a
    # slot a token expected here, where the plain pass runs both held experts
    # over every token: a quarter of a layer's dense passes
    tokens = 16 * TAGS
    dense_passes = 2 * (2.0 * tokens * 3 * 64 * 32)
    assert kind.expert_ffn_flops(model, tokens) == dense_passes / 4
    assert models.train_flops(model, TAGS) > 3.0 * total


def test_moe_gqa_counts_the_pairs_inside_the_band_and_no_more_than_it_multiplies():
    """Its count takes a window layer's attention at the pairs the window
    leaves (row ``i`` sees ``min(i + 1, W)`` keys), a full layer's at its
    causal mean, and the experts at their expected slots, where its plain
    forward pass multiplies whole blocks of scores over all keys and every
    held expert over every token: under the jaxpr's products, never over."""
    from benchmarks.reference import models
    from benchmarks.tests.test_flops_bytes import product_flops_a_sample

    model = KINDS["moe_gqa"]
    kind = models.for_kind(model)
    multiplied = product_flops_a_sample(kind, model, TAGS)
    total = kind.forward_flops(model, TAGS)["total"]
    assert 0.4 * multiplied <= total <= multiplied, (total, multiplied)
    # a window of 6 over 16 rows: 1 + 2 + ... + 6, then ten rows of 6
    assert kind.attention_pairs(model, "sliding_attention") == 21 + 60
    assert kind.attention_pairs(model, "full_attention") == 16 * 17 / 2
    a_pair = 4.0 * 8 * 16  # scores and mixing, 8 query heads of 16
    assert kind.attention_flops(model, TAGS) == TAGS * a_pair * (4 * 81 + 2 * 136)
    # q and the output a query head, k and v a key head, float32, six layers
    assert kind.attention_bytes(model, TAGS) == TAGS * 6 * 4.0 * (2 * 8 + 2 * 2) * 16 * 16
    # 2 of 8 experts held and 2 chosen a token: half a slot a token expected
    tokens = 16 * TAGS
    assert kind.expert_ffn_flops(model, tokens) == 0.5 * (2.0 * tokens * 3 * 64 * 32)
    assert models.train_flops(model, TAGS) == 3.0 * total


def test_afmoe_counts_the_gate_the_dense_layer_and_the_band():
    """Every layer's projections with the gate (five products, four of them
    a query head wide), the dense layer's feed-forward, the expert layers'
    router, shared expert and expected slots, attention's pairs inside the
    band: under the jaxpr's products, never over."""
    from benchmarks.reference import models
    from benchmarks.tests.test_flops_bytes import product_flops_a_sample

    model = KINDS["afmoe"]
    kind = models.for_kind(model)
    multiplied = product_flops_a_sample(kind, model, TAGS)
    total = kind.forward_flops(model, TAGS)["total"]
    assert 0.4 * multiplied <= total <= multiplied, (total, multiplied)
    tokens = 16 * TAGS
    projections = 5 * 2.0 * tokens * 64 * (3 * 8 + 2 * 2) * 16
    dense = 2.0 * tokens * 3 * 64 * 96
    experts = 4 * (2.0 * tokens * (64 * 8 + 3 * 64 * 32) + 0.5 * 2.0 * tokens * 3 * 64 * 32)
    attention = TAGS * 4.0 * 8 * 16 * (4 * (21 + 60) + 16 * 17 / 2)
    head = 2.0 * tokens * 64 * 64
    assert total == pytest.approx(projections + dense + experts + attention + head, rel=1e-12)
    assert models.train_flops(model, TAGS) == 3.0 * total
