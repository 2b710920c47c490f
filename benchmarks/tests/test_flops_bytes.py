"""The benchmark's shape arithmetic against the program's XLA-read
accounting (``fleet_flops_accounting``) at one small shape, on the CPU.

Run by hand: ``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q``.
"""

import json
import os

import pytest

from benchmarks import flops_bytes

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _spec(config, n_features):
    from gordo_components_tpu.models.analysis import analyze_model
    from gordo_components_tpu.parallel.build_fleet import _spec_for
    from gordo_components_tpu.serializer import pipeline_from_definition

    analyzed = analyze_model(pipeline_from_definition(config["model"]))
    return _spec_for(analyzed, n_features, n_features, config["n_splits"])


@pytest.mark.parametrize("name", ["dense-ae-10tag", "lstm-ae-50tag"])
def test_flops_agree_with_the_programs_accounting(name):
    from gordo_components_tpu.parallel.fleet import fleet_flops_accounting

    with open(os.path.join(HERE, "configs", f"{name}.json")) as fh:
        config = json.load(fh)
    machines, rows, tags = 4, 512, config["tags"]
    theirs = fleet_flops_accounting(_spec(config, tags), machines, rows, tags, tags)
    ours = flops_bytes.slice_counts(config["reference_model"], machines, rows, tags)
    assert theirs is not None
    assert ours["train_steps"] == theirs["train_steps"]
    # ours counts matrix products alone and is the floor; XLA's count adds
    # the elementwise work (activations, Adam, the loss). XLA counts a scan's
    # body once whatever its trip count: the program's accounting corrects
    # that for the training loop but not for the LSTM's scan over the window,
    # so for a windowed model its figure is low by about the window length
    # (PERF.md, Open questions); the recurrent layers do run every time step.
    inner = config["reference_model"]["lookback"]
    assert ours["flops"] <= inner * theirs["total_flops"]
    assert ours["flops"] >= 0.6 * inner * theirs["total_flops"]


def test_least_seconds_names_its_bound():
    peak = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops_bytes.least_seconds({"flops": 1000.0, "bytes": 10.0}, peak) == {
        "seconds": 10.0, "bound": "flops", "by_flops_s": 10.0, "by_bytes_s": 1.0,
    }
    assert flops_bytes.least_seconds({"flops": 10.0, "bytes": 100.0}, peak)["bound"] == "bytes"


def _kinds():
    folder = os.path.join(HERE, "reference", "models")
    return sorted(f[:-3] for f in os.listdir(folder) if f.endswith(".py") and f != "__init__.py")


@pytest.mark.parametrize("kind", _kinds())
def test_every_model_kind_is_found_by_name_and_is_whole(kind):
    """A kind is one file under ``reference/models``: the reference and the
    flops arithmetic reach it through ``reference_model["kind"]`` alone."""
    import jax
    import jax.numpy as jnp

    from benchmarks.reference import models

    model = {"kind": kind, "widths": [6, 4], "funcs": ["tanh", "tanh"],
             "out_func": "linear", "lookback": 5, "epochs": 1, "batch_size": 8,
             "n_splits": 2, "learning_rate": 1e-3}
    module = models.for_kind(model)
    lookback, target_offset = module.layout(model)
    assert lookback >= 1 and 0 <= target_offset
    params = module.init(model, jax.random.PRNGKey(0), 7, 7)
    out = module.apply(model, params, jnp.ones((3, lookback, 7), jnp.float32))
    assert out.shape == (3, 7)
    flops = module.forward_flops(model, 7)
    assert 0 < flops["first_layer"] < flops["total"]
    counts = flops_bytes.slice_counts(model, 2, 64, 7)
    assert counts["flops"] > 0 and counts["bytes"] > 0
