"""What a model kind may bring beside the four functions it has to: a layout
with several predicted rows a sample (and the samples that far apart), a loss of its own, a gradient taken
in blocks, its own count of a training sample's operations and of a step's
least bytes. The stand-in kind ``kinds/tokens.py`` brings all of them and goes
through ``make_build``, ``anomaly``, ``slice_counts`` and
``compare.machine_numbers`` here; and the reference keeps one fitted model,
however many folds it fits.

Run by hand: ``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q``.
"""

import importlib
import sys

import numpy as np
import pytest

TOKENS = {
    "kind": "tokens", "vocab": 256, "width": 32, "blocks": 2, "lookback": 32,
    "aux_weight": 0.3, "epochs": 2, "batch_size": 8, "micro_batch": 2,
    "n_splits": 1, "learning_rate": 3e-3,
}
TAGS, N_ROWS, N_REAL = 3, 1024, 1000


@pytest.fixture
def kinds(monkeypatch):
    """The tests' kinds, put where ``models.for_kind`` looks."""
    for name in ("tokens", "one_leaf"):
        monkeypatch.setitem(
            sys.modules, f"benchmarks.reference.models.{name}",
            importlib.import_module(f"benchmarks.tests.kinds.{name}"),
        )


def machine(seed=11):
    rng = np.random.default_rng(seed)
    walk = np.cumsum(rng.normal(size=(N_REAL, TAGS)), axis=0)
    raw = (walk + 3.0 * np.sin(np.arange(N_REAL) / 9.0)[:, None]).astype(np.float32)
    X = np.zeros((N_ROWS, TAGS), np.float32)
    w = np.zeros((N_ROWS,), np.float32)
    X[N_ROWS - N_REAL:], w[N_ROWS - N_REAL:] = raw, 1.0
    return X, w, raw


def built(model, fault=None):
    import jax

    from benchmarks.reference import build as ref_build

    X, w, raw = machine()
    build, anomaly, initial = ref_build.make_build(model, N_ROWS, TAGS, fault=fault)

    def one(X, w, key, probe):
        result = build(X, w, key)
        result["anomaly_mean"] = anomaly(result, probe)
        result["params0"] = initial(key)
        return result

    with jax.default_matmul_precision("highest"):
        out = jax.device_get(jax.jit(one)(X, w, jax.random.PRNGKey(5), raw[-200:]))
    # what ``compare.machine_numbers`` reads of either side
    out.update(rows=N_REAL, x_sum=float(raw.astype(np.float64).sum()),
               target_scale=out["input_scale"], anomaly_replayed=out["anomaly_mean"])
    return out


@pytest.fixture
def sound(kinds):
    return built(TOKENS)


def test_the_stand_in_kind_is_built_whole(sound):
    from benchmarks import flops_bytes
    from benchmarks.reference import models

    lay = models.layout(TOKENS)
    assert lay == (32, 32, 32)
    # every row after the first sample's first is predicted exactly once
    first = lay.lead(N_ROWS) + np.arange(lay.n_samples(N_ROWS)) * lay.rows_out
    targets = (first[:, None] + lay.target_offset - (lay.rows_out - 1)
               + np.arange(lay.rows_out)[None, :])
    assert targets[0, 0] == lay.lead(N_ROWS) + 1 == 32
    assert np.array_equal(targets.ravel(), np.arange(32, N_ROWS))
    assert first[-1] + lay.lookback - 1 == N_ROWS - 2
    # thresholds and scores are over predicted rows, and finite
    for key in ("total_threshold", "tag_thresholds", "cv_mse", "error_scale",
                "anomaly_mean", "loss_history"):
        assert np.all(np.isfinite(sound[key])), key
    assert sound["tag_thresholds"].shape == (TAGS,) and sound["cv_mse"].shape == (1,)
    # it learns: under the cross-entropy of a uniform guess on both heads
    assert sound["loss_history"][-1] < sound["loss_history"][0] < 1.3 * np.log(256)
    counts = flops_bytes.slice_counts(TOKENS, 2, N_ROWS, TAGS)
    kind = models.for_kind(TOKENS)
    steps = -(-lay.n_samples(N_ROWS) // 8)
    assert counts["train_steps"] == 2 * 2 * steps
    rows = 2 * 2 * (2 + 1) * N_ROWS * 4.0 * TAGS
    assert counts["bytes"] == rows + 2 * counts["train_steps"] * kind.state_bytes(TOKENS, TAGS)
    assert counts["flops"] == 2 * 2 * steps * 8 * (
        2 * kind.train_flops(TOKENS, TAGS) + kind.forward_flops(TOKENS, TAGS)["total"]
    )


def test_a_kinds_own_count_claims_no_more_than_its_forward_pass_multiplies(kinds):
    """The stand-in counts the causal half of its attention, which its plain
    forward pass computes whole: less than the jaxpr's products, never more
    (a count over them would flatter a share of the peak)."""
    from benchmarks.reference import models
    from benchmarks.tests.test_flops_bytes import product_flops_a_sample

    kind = models.for_kind(TOKENS)
    multiplied = product_flops_a_sample(kind, TOKENS, TAGS)
    total = kind.forward_flops(TOKENS, TAGS)["total"]
    assert 0.8 * multiplied <= total <= multiplied, (total, multiplied)
    assert models.train_flops(TOKENS, TAGS) == 3.0 * total


def test_a_gradient_taken_in_blocks_is_the_batchs_gradient(sound, kinds):
    from benchmarks.reference import compare

    whole = built({**TOKENS, "micro_batch": TOKENS["batch_size"]})
    numbers = compare.machine_numbers(sound, whole)
    assert numbers["loss_first_gap"] < 1e-6 and numbers["loss_last_gap"] < 1e-6
    # Adam divides a gradient by the root of its own square while the second
    # moment is young, so where an element's gradient is nought to rounding
    # (a bin no value fell into) the rounding IS the step. After eight steps
    # of 3e-3, which move a parameter by 0.024 at most: a leaf's norm of
    # change reads 1.2e-6 apart, 999 parameters in a thousand under 3e-6, and
    # one row of the embedding 5.9e-5.
    assert numbers["param_change_gap"] < 1e-5, numbers
    ours, theirs = compare.flatten(sound["params"]), compare.flatten(whole["params"])
    for key, start in compare.flatten(whole["params0"]).items():
        apart = np.abs(ours[key] - theirs[key])
        assert np.quantile(apart, 0.999) < 1e-5, key
        assert apart.max() < 5e-3 * np.abs(theirs[key] - start).max(), key


@pytest.mark.parametrize("fault", ["half_batch", "state_unchanged"])
def test_a_planted_fault_reads_as_one_for_a_kind_with_its_own_loss(fault, sound, kinds):
    from benchmarks.reference import compare

    numbers = compare.machine_numbers(built(TOKENS, fault=fault), sound)
    if fault == "state_unchanged":
        assert numbers["param_change_gap"] == 1.0
    else:  # blocks and a whole batch agree to 1e-6 (above): this is no rounding
        assert numbers["loss_first_gap"] > 1e-3 and numbers["param_change_gap"] > 1e-2, numbers


# a row predicted twice, or one left out between two samples, cannot be
# stated: samples lie ``rows_out`` apart by construction
@pytest.mark.parametrize("layout, said", [
    ((32, 8, 16), "in front of its first row"),
    ((32, 32, 0), "no such layout"),
])
def test_a_layout_that_no_sample_can_have_is_refused(layout, said, kinds, monkeypatch):
    from benchmarks.reference import build as ref_build
    from benchmarks.tests.kinds import tokens

    monkeypatch.setattr(tokens, "layout", lambda model: layout)
    with pytest.raises(ValueError, match=said):
        ref_build.make_build(TOKENS, N_ROWS, TAGS)


def test_a_micro_batch_that_does_not_divide_the_batch_is_refused(kinds):
    with pytest.raises(ValueError, match="does not divide"):
        built({**TOKENS, "micro_batch": 3})


# ``output + temp`` of the compiled build over the bytes of one copy of the
# parameters, one leaf, n_splits 4. The tree before this test (6736d58) kept
# every fit's parameters stacked, n_splits + 6 copies: 10.23 on the CPU at 4 M
# parameters, 11.14 for the described v5e at 64 M. Now 8.27 on the CPU (the
# fitted model, what the fits start from, a fit's current one with its two
# moments and its gradient, and the final fit's two moments, which XLA's CPU
# scheduler zeroes ahead of the fold loop) and 3.14 for the chip, whose
# compiler folds the gradient into Adam's update and whose figure is the one
# that decides what fits (``tools/size_probe.py`` reads a model of many
# leaves, its gradient taken in blocks: 5.15).
COPIES = {"cpu": (2000, 8.5), "v5e": (8000, 7.5)}


@pytest.mark.parametrize("backend", sorted(COPIES))
def test_the_reference_keeps_one_fitted_model(backend, kinds):
    import jax
    import jax.numpy as jnp

    from benchmarks.reference import build as ref_build

    tags, limit = COPIES[backend]
    sharding = None
    if backend == "v5e":
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        try:
            topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as exc:
            pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
        sharding = SingleDeviceSharding(topo.devices[0])
    model = {"kind": "one_leaf", "epochs": 1, "batch_size": 8, "n_splits": 4,
             "learning_rate": 1e-3}
    n_rows = 64
    build, _, _ = ref_build.make_build(model, n_rows, tags)
    shapes = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
        for shape, dtype in (((n_rows, tags), jnp.float32), ((n_rows,), jnp.float32),
                             ((2,), jnp.uint32))
    ]
    memory = jax.jit(build).lower(*shapes).compile().memory_analysis()
    copies = (memory.output_size_in_bytes + memory.temp_size_in_bytes) / (4.0 * tags * tags)
    assert 2.0 <= copies < limit, (backend, copies)
