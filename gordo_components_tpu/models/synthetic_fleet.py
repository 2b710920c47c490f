"""A synthetic fleet of fitted machines for serving tests, drills and the
chip smoke: nothing here measures anything."""

from __future__ import annotations

import copy

import numpy as np


def build_models(n_machines: int, rows: int, tags: int):
    """One quick real fit, then ``n_machines`` weight-perturbed replicas:
    serving behaviour depends on stacked shapes, not on training quality."""
    import jax

    from ..serializer import pipeline_from_definition

    config = {
        "DiffBasedAnomalyDetector": {
            "base_estimator": {
                "TransformedTargetRegressor": {
                    "regressor": {
                        "Pipeline": {
                            "steps": [
                                "MinMaxScaler",
                                {
                                    "DenseAutoEncoder": {
                                        "kind": "feedforward_hourglass",
                                        "epochs": 2,
                                        "batch_size": 64,
                                    }
                                },
                            ]
                        }
                    },
                    "transformer": "MinMaxScaler",
                }
            }
        }
    }
    rng = np.random.default_rng(0)
    X = rng.normal(size=(max(rows, 256), tags)).astype(np.float32) * 2 + 4
    proto = pipeline_from_definition(config)
    proto.cross_validate(X, n_splits=2)
    proto.fit(X)

    models = {}
    for i in range(n_machines):
        model = copy.deepcopy(proto)
        est = model.base_estimator.regressor.steps[-1][1]
        key = jax.random.PRNGKey(i)
        est.params_ = jax.tree_util.tree_map(
            lambda p: p * (1.0 + 0.01 * float(jax.random.uniform(key, ()))),
            est.params_,
        )
        models[f"machine-{i:04d}"] = model
    return models
