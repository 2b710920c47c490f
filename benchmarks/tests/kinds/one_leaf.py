"""A kind of one leaf, for the tests of the reference's memory: one square
matrix over the tags, a row in and the same row out. Not a model anyone
runs; it lives with the tests, which put it where ``for_kind`` looks."""

from __future__ import annotations

import jax


def layout(model):
    return 1, 0


def init(model, key, n_features: int, n_out: int):
    return {"w": 0.01 * jax.random.normal(key, (n_features, n_out))}


def apply(model, params, windows):
    return windows[:, 0, :] @ params["w"]


def forward_flops(model, n_features: int):
    total = 2.0 * n_features * n_features
    return {"total": total, "first_layer": total}
