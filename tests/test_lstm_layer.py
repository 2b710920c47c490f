"""The LSTM layer's own differentiation rule (``models/modules.py::
lstm_sequence``) against autodiff through ``nn.RNN(nn.OptimizedLSTMCell)``,
which stays HERE as the oracle: gradients, the parameter tree and its draw,
a model the parent tree serialized, and the shape of the reverse loop."""

import os
from typing import Any, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gordo_components_tpu import serializer
from gordo_components_tpu.models.modules import (
    _ACTIVATIONS,
    LSTMModule,
    activation,
    resolve_dtype,
)


class OracleLSTM(nn.Module):
    """``LSTMModule`` as the parent tree had it: Flax's scan, JAX's transpose."""

    units: Sequence[int]
    n_features_out: int
    funcs: Sequence[str]
    dropout: float = 0.0
    compute_dtype: Any = "float32"

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        dtype = resolve_dtype(self.compute_dtype)
        h = x.astype(dtype)
        for n_units, func in zip(self.units, self.funcs):
            cell = nn.OptimizedLSTMCell(
                n_units, activation_fn=activation(func), dtype=dtype
            )
            h = nn.RNN(cell)(h)
            if self.dropout > 0.0:
                h = nn.Dropout(rate=self.dropout)(h, deterministic=deterministic)
        out = nn.Dense(self.n_features_out, dtype=dtype)(h[:, -1, :])
        return out.astype(jnp.float32)


def _pair(units, funcs, dtype="float32", dropout=0.0):
    kw = dict(
        units=units, n_features_out=units[-1], funcs=funcs,
        compute_dtype=dtype, dropout=dropout,
    )
    return LSTMModule(**kw), OracleLSTM(**kw)


def _data(n_in, n_out, lookback, batch=8, lead=()):
    kx, ky = jax.random.split(jax.random.key(26))
    x = jax.random.normal(kx, lead + (batch, lookback, n_in))
    y = jax.random.normal(ky, lead + (batch, n_out))
    return x, y


def _loss(module, x, y):
    return lambda params: jnp.mean((module.apply(params, x) - y) ** 2)


def _init(module, x, key=0):
    return jax.jit(module.init)(jax.random.key(key), x)


def _losses_and_grads(modules, x, y, params):
    """``[(loss, gradient)]`` a module, one program for all of them (one
    compile a case: the file is most of a tier-1 worker's time)."""
    return jax.jit(
        lambda p: [jax.value_and_grad(_loss(m, x, y))(p) for m in modules]
    )(params)


def _same_forward(new, old, params, x, **kw):
    """The same products in the same order: equal to float32 rounding (XLA
    may pick another dot routine for a transposed operand)."""
    got, want = jax.jit(lambda p: [m.apply(p, x, **kw) for m in (new, old)])(params)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


def _worst_relative(got, want):
    gaps = jax.tree_util.tree_map(
        lambda a, b: float(
            jnp.linalg.norm((a - b).astype(jnp.float32))
            / (jnp.linalg.norm(b.astype(jnp.float32)) + 1e-30)
        ),
        got, want,
    )
    return max(jax.tree_util.tree_leaves(gaps))


# ------------------------------------------------------------ (a) gradients
# every (n_in, units) pair of the cell lstm-ae-50tag: 50 tags, 128-64-64-128
WIDTHS = [(50, 128), (128, 64), (64, 64), (64, 128)]


@pytest.mark.parametrize("func", ["tanh", "relu"])
@pytest.mark.parametrize("lookback", [1, 24])
@pytest.mark.parametrize("n_in,n_units", WIDTHS)
def test_gradients_match_autodiff_float32(n_in, n_units, lookback, func):
    new, old = _pair((n_units,), (func,))
    x, y = _data(n_in, n_units, lookback)
    params = _init(old, x)
    (loss, got), (loss_old, want) = _losses_and_grads((new, old), x, y, params)
    np.testing.assert_allclose(loss, loss_old, rtol=1e-6)  # the forward pass
    assert _worst_relative(got, want) <= 1e-5


@pytest.mark.parametrize("func", ["tanh", "relu"])
@pytest.mark.parametrize("lookback", [1, 24])
@pytest.mark.parametrize("n_in,n_units", WIDTHS)
def test_gradients_match_autodiff_bfloat16(n_in, n_units, lookback, func):
    """bfloat16's own tolerance: the oracle sums bf16-rounded per-step
    products, the rule one float32-accumulated product over the window, so
    they differ by bf16 rounding (2**-8) and agree far inside it with the
    float32 gradient, which is the better of the two to be near."""
    new, old = _pair((n_units,), (func,), dtype="bfloat16")
    exact, _ = _pair((n_units,), (func,))
    x, y = _data(n_in, n_units, lookback)
    params = _init(old, x)
    (_, got), (_, want), (_, truth) = _losses_and_grads((new, old, exact), x, y, params)
    assert all(leaf.dtype == jnp.float32 for leaf in jax.tree_util.tree_leaves(got))
    assert _worst_relative(got, want) <= 3e-2
    assert _worst_relative(got, truth) <= 1.5 * _worst_relative(want, truth) + 1e-3


@pytest.mark.parametrize("dtype,limit", [("float32", 1e-5), ("bfloat16", 3e-2)])
@pytest.mark.parametrize("lookback", [1, 24])
def test_gradients_match_under_vmap_over_models(lookback, dtype, limit):
    """The fleet's shape: a leading model axis over parameters AND data, the
    whole stack (input gradients between layers, two activations)."""
    new, old = _pair((16, 8, 16), ("tanh", "relu", "tanh"), dtype=dtype)
    x, y = _data(5, 16, lookback, lead=(3,))
    params = jax.jit(jax.vmap(lambda k: old.init(k, x[0])))(
        jax.random.split(jax.random.key(1), 3)
    )
    got, want = jax.jit(
        jax.vmap(lambda p, xm, ym: [jax.grad(_loss(m, xm, ym))(p) for m in (new, old)])
    )(params, x, y)
    assert _worst_relative(got, want) <= limit


@pytest.mark.parametrize(
    "func", sorted(set(_ACTIVATIONS) - {"tanh", "relu"})
)
def test_gradients_match_for_every_other_activation(func):
    """``act`` is differentiated where it is applied (``jax.vjp`` on the
    pre-activation), not from a formula on its output: softmax, which is
    not elementwise, included."""
    new, old = _pair((8, 8), (func, func))
    x, y = _data(4, 8, 5)
    params = _init(old, x)
    (_, got), (_, want) = _losses_and_grads((new, old), x, y, params)
    assert _worst_relative(got, want) <= 1e-5


def test_dropout_between_layers_draws_the_same_masks():
    new, old = _pair((8, 8), ("tanh", "tanh"), dropout=0.5)
    x, _ = _data(4, 8, 5)
    params = _init(old, x)
    _same_forward(
        new, old, params, x, deterministic=False, rngs={"dropout": jax.random.key(9)}
    )


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("lookback", [1, 24])
def test_forward_of_the_cell_stack_equals_the_parent_module(lookback, dtype):
    """Serving and the build's predicts: no gradient, so the time loops
    emit ``h`` alone (the residual streams are not computed, not just dead)."""
    new, old = _pair((128, 64, 64, 128), ("tanh",) * 4, dtype=dtype)
    x, _ = _data(50, 128, lookback)
    params = _init(old, x)
    _same_forward(new, old, params, x)
    scans = list(_scans(jax.make_jaxpr(lambda p: new.apply(p, x))(params).jaxpr))
    assert len(scans) == 4 and not any(s.params["reverse"] for s in scans)
    for scan in scans:
        assert len(scan.outvars) == scan.params["num_carry"] + 1  # (c, h) and hs


# ---------------------------------------------- (b) parameters and artifacts
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("lookback", [1, 24])
def test_parameter_tree_and_draw_are_flax_own(lookback, dtype):
    new, old = _pair((128, 64, 64, 128), ("tanh",) * 4, dtype=dtype)
    x, _ = _data(50, 128, lookback, batch=1)
    got, want = (_init(m, x, key=4) for m in (new, old))
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = jax.tree_util.tree_leaves_with_path(want)
    assert [jax.tree_util.keystr(p) for p, _ in flat_got] == [
        jax.tree_util.keystr(p) for p, _ in flat_want
    ]
    for (path, a), (_, b) in zip(flat_got, flat_want):
        assert a.shape == b.shape and a.dtype == b.dtype, path
        np.testing.assert_array_equal(a, b, err_msg=jax.tree_util.keystr(path))
    cell = got["params"]["OptimizedLSTMCell_1"]
    assert sorted(cell) == ["hf", "hg", "hi", "ho", "if", "ig", "ii", "io"]
    assert sorted(cell["hi"]) == ["bias", "kernel"] and sorted(cell["ii"]) == ["kernel"]


def test_model_serialized_by_parent_tree_scores_identically():
    """``tests/fixtures/lstm_parent_tree``: gordo's stock anomaly wrapper over
    an ``LSTMAutoEncoder`` (12-7-7-12, tanh and relu, lookback 6) fitted and
    ``serializer.dumps``-ed by commit ae46e89, the tree before this layer had
    its own rule, with what it predicted and scored there."""
    here = os.path.join(os.path.dirname(__file__), "fixtures", "lstm_parent_tree")
    with open(os.path.join(here, "model.bin"), "rb") as fh:
        model = serializer.loads(fh.read())
    expected = np.load(os.path.join(here, "expected.npz"))
    np.testing.assert_allclose(
        np.asarray(model.predict(expected["X"])), expected["predicted"],
        rtol=1e-6, atol=1e-6,
    )
    scores = model.anomaly(expected["X"])["total-anomaly-score"].to_numpy().ravel()
    np.testing.assert_allclose(scores, expected["total_anomaly"], rtol=1e-6, atol=1e-6)


# -------------------------------------------------- (c) the reverse loop
def _scans(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _scans(sub)


@pytest.mark.parametrize("vmapped", [False, True], ids=["plain", "vmap"])
def test_reverse_scans_carry_the_recurrence_alone(vmapped):
    units, n_in, batch, lookback = (128, 64, 64, 128), 50, 40, 24  # 40: no width
    new, _ = _pair(units, ("tanh",) * 4)
    x, y = _data(n_in, units[-1], lookback, batch=batch)
    params = _init(new, x)
    grad = jax.grad(_loss(new, x, y))
    if vmapped:
        grad = jax.vmap(grad)
        params = jax.tree_util.tree_map(lambda a: jnp.stack([a, a]), params)
    scans = list(_scans(jax.make_jaxpr(grad)(params).jaxpr))
    reverse = [s for s in scans if s.params["reverse"]]
    forward = [s for s in scans if not s.params["reverse"]]
    assert len(reverse) == len(units) and len(forward) == len(units)
    lead = (2,) if vmapped else ()
    kernel_shapes = set()
    for n_from, n_units in zip((n_in,) + units[:-1], units):
        for rows in (n_from, n_units):
            kernel_shapes |= {lead + (rows, n_units), lead + (rows, 4 * n_units)}
        kernel_shapes |= {lead + (n_units,), lead + (4 * n_units,)}
    for scan in reverse:
        n_consts, n_carry = scan.params["num_consts"], scan.params["num_carry"]
        carry = [v.aval.shape for v in scan.invars[n_consts : n_consts + n_carry]]
        assert scan.params["length"] == lookback
        assert len(carry) == 2, carry  # (dh, dc)
        n_units = carry[0][-1]
        assert carry == [lead + (batch, n_units)] * 2
        assert not kernel_shapes & set(carry)
    widths = [s.invars[s.params["num_consts"]].aval.shape[-1] for s in reverse]
    assert sorted(widths) == sorted(units)  # one reverse loop a layer
