"""A decoder of gated grouped-query-attention and expert layers over sensor
values read as tokens: Arcee's Trinity block (``model_type`` ``afmoe``) as ONE
of the chips that share each layer holds it.

Every tag is a sequence of its own. A scaled value in [0, 1] is binned into
the ``vocab_size`` ids of this chip's slice of the vocabulary; the model
predicts the NEXT row's bin at every position; ``apply`` hands back the
expected bin centre. A sample reads rows ``i .. i+L-1`` and is judged against
rows ``i+1 .. i+L``.

The equations (``config.json`` and the ``afmoe`` modelling code, which applies
the gate, the query/key norms, rotary in the window layers alone, the four
norms a layer and the embedding's scale whatever the config says; each
departure is in the configuration's ``assumed``), RMSNorm with weights
throughout:

* ``h = E[id] * sqrt(hidden_size)`` where ``mup_enabled``;
* every layer: ``a = norm_in(h)``; ``h += norm_post_attn(Attn(a))``;
  ``b = norm_pre_mlp(h)``; ``h += norm_post_mlp(FFN(b))``; a final norm; an
  untied head;
* attention: ``q = norm_q((a W_q)_head)``, ``k = norm_k((a W_k)_head)`` (each
  head normed over ``head_dim``), ``v = a W_v``; query head ``i`` reads key
  head ``i // group``; rotary embedding in pairs ``(i, i + head_dim/2)`` on
  ``q`` and ``k`` of a layer kind that ``rope_parameters`` names (the window
  layers; the full layers are not turned); scores ``q k / sqrt(head_dim)``,
  position ``i`` sees ``j <= i``, and in a ``sliding_attention`` layer only
  ``i - j < sliding_window``; output ``(o * sigmoid(a W_g)) W_o``;
* FFN of the first ``num_dense_layers`` layers: SwiGLU of
  ``intermediate_size``; of the others: ``num_shared_experts`` shared experts
  (one SwiGLU of ``moe_intermediate_size`` times that) plus the routed part:
  ``s = sigmoid(W_r b)`` in float32 at ``highest`` over ALL ``num_experts``
  (the published ``score_func``), the
  ``num_experts_per_tok`` largest of ``s + bias`` (``n_group`` 1: no group
  limit), weights ``s`` of the chosen over their sum (``route_norm``) times
  ``route_scale``. The bias starts at zero and takes no gradient. This chip
  holds ``experts_held`` only and adds only what they give: a partial result,
  which is what goes on.

Plain ``jax.numpy``: a dense pass of every held expert over every token (no
sort, no grouped product), masked dense attention over ALL keys in window
layers and full ones alike, a block of ``query_block`` queries at a time. So
that one block of the gradient fits beside the training state, a layer runs a
sequence at a time, and the activations of one sequence of a layer, of one
held expert, of the shared expert, of the dense feed-forward, of one block of
scores and of one block of ``logit_block`` positions' logits are made again in
the backward pass (``jax.checkpoint`` around each, ``lax.map`` over them);
nothing else is saved.

The initial weights are drawn through Flax, as the program draws them:
normal(0.02) a leading index at a time, norms one, the selection bias zero.
The names are declared in one order, which is part of that contract:
``embed``, ``head``, ``final_norm``, ``dense_layers`` (where there are any),
``periods``; each of the last two the pattern of its layers' kinds cut into
its shortest period and the period into runs of one kind (``moe_gqa``'s
tree: run ``j`` of kind ``k`` the group ``"<j>_<k>"``, leaves stacked
``(periods, layers of the run, ...)``).
"""

from __future__ import annotations

import math

import flax.linen as nn
import jax
import jax.numpy as jnp

from benchmarks.reference.models.moe_gqa import (
    _cross_entropy, _matrix, _mix, _over_vocabulary, _rms, _rope, _runs,
    _sequences, _swiglu, attention_pairs, rope_table,
)

HIGHEST = jax.lax.Precision.HIGHEST
SLIDING, FULL = "sliding_attention", "full_attention"


# ---------------------------------------------------------------- sizes ----
# what a dictionary that leaves a size out gets: toy sizes, for callers that
# know a kind by its name alone (the benchmark's test of every kind)
SMALL = {
    "hidden_size": 32, "layer_types": (SLIDING, SLIDING, FULL),
    "num_dense_layers": 1, "intermediate_size": 48, "sliding_window": 4,
    "rope_parameters": {SLIDING: {"rope_type": "default", "rope_theta": 10000.0}},
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
    "moe_intermediate_size": 16, "num_experts": 4, "experts_held": (0, 1),
    "num_experts_per_tok": 2, "num_shared_experts": 1, "route_scale": 2.826,
    "mup_enabled": True, "rms_norm_eps": 1e-5,
    "vocab_size": 32, "query_block": 256, "logit_block": 2048,
}


def _whole(model):
    return {**SMALL, **model}


def layout(model):
    L = int(model["lookback"])
    return L, L, L  # lookback, target_offset, rows_out


def _parts(m):
    """The layer kinds of the dense layers and of the expert layers."""
    n = int(m["num_dense_layers"])
    return list(m["layer_types"])[:n], list(m["layer_types"])[n:]


def _layer_shapes(m, dense: bool):
    D, d = int(m["hidden_size"]), int(m["head_dim"])
    H, Hkv = int(m["num_attention_heads"]), int(m["num_key_value_heads"])
    shapes = {
        "attn_norm": (D,), "wq": (D, H * d), "wk": (D, Hkv * d), "wv": (D, Hkv * d),
        "wo": (H * d, D), "attn_gate": (D, H * d), "q_norm": (d,), "k_norm": (d,),
        "post_attn_norm": (D,), "ffn_norm": (D,), "post_ffn_norm": (D,),
    }
    if dense:
        I = int(m["intermediate_size"])
        shapes.update(w_gate=(D, I), w_up=(D, I), w_down=(I, D))
        return shapes
    E, I = int(m["num_experts"]), int(m["moe_intermediate_size"])
    held = len(m["experts_held"])
    shapes.update(
        router=(D, E), router_bias=(E,),
        experts_gate=(held, D, I), experts_up=(held, D, I), experts_down=(held, I, D),
    )
    shared = I * int(m["num_shared_experts"])
    if shared:
        shapes.update(shared_gate=(D, shared), shared_up=(D, shared), shared_down=(shared, D))
    return shapes


def _draw(key, shapes):
    """A layer's leaves from one key: leaf ``j`` of the sorted names from
    ``fold_in(key, j)``; norms one, the selection bias zero, else normal(0.02)
    a leading index at a time."""
    out = {}
    for j, (name, shape) in enumerate(sorted(shapes.items())):
        if name.endswith("norm"):
            out[name] = jnp.ones(shape, jnp.float32)
        elif name == "router_bias":
            out[name] = jnp.zeros(shape, jnp.float32)
        else:
            out[name] = _matrix(jax.random.fold_in(key, j), shape)
    return out


def _periods(key, shapes, n_periods, runs):
    """Period ``i`` from ``split(key, P)[i]``, its run ``j`` from
    ``fold_in(that, j)``, the run's layer ``l`` from ``split(that, n)[l]``."""
    def period(k):
        return {
            f"{j}_{kind}": jax.lax.map(
                lambda kk: _draw(kk, shapes), jax.random.split(jax.random.fold_in(k, j), n)
            )
            for j, (kind, n) in enumerate(runs)
        }

    return jax.lax.map(period, jax.random.split(key, n_periods))


class _Init(nn.Module):
    """The parameter tree and nothing else, declared in the one order."""

    model: dict

    @nn.compact
    def __call__(self):
        m = self.model
        D, V = int(m["hidden_size"]), int(m["vocab_size"])
        self.param("embed", _matrix, (V, D))
        self.param("head", _matrix, (D, V))
        self.param("final_norm", nn.initializers.ones, (D,))
        dense, expert = _parts(m)
        if dense:
            self.param("dense_layers", _periods, _layer_shapes(m, True),
                       *_runs({"layer_types": dense}))
        self.param("periods", _periods, _layer_shapes(m, False),
                   *_runs({"layer_types": expert}))


def init(model, key, n_features: int, n_out: int):
    return _Init(_whole(model)).init(key)["params"]


# ----------------------------------------------------------- one sequence ----
def _bins(model, values):
    V = int(model["vocab_size"])
    return jnp.clip(jnp.floor(values.astype(jnp.float32) * V), 0, V - 1).astype(jnp.int32)


def _attention(m, p, a, kind: str):
    """One sequence ``a (L, D)``, already normed."""
    L = a.shape[0]
    H, Hkv, d = (int(m[k]) for k in ("num_attention_heads", "num_key_value_heads", "head_dim"))
    eps = float(m["rms_norm_eps"])
    q = _rms((a @ p["wq"]).reshape(1, L, H, d), p["q_norm"], eps)
    k = _rms((a @ p["wk"]).reshape(1, L, Hkv, d), p["k_norm"], eps)
    if kind in m["rope_parameters"]:  # the full layers carry no position
        inv_freq, factor = rope_table(m, kind)
        q, k = _rope(q, inv_freq, factor), _rope(k, inv_freq, factor)
    v = (a @ p["wv"]).reshape(L, Hkv, d)
    qb = min(L, int(m["query_block"]))
    if L % qb:
        qb = L
    window = int(m["sliding_window"]) if kind == SLIDING else None
    mix = jax.checkpoint(_mix, static_argnums=(0, 1))  # a block's scores are made again backward
    mixed = jax.lax.map(
        lambda b: mix(d ** -0.5, window, b[0], b[1], k[0], v),
        (qb * jnp.arange(L // qb), q[0].reshape(L // qb, qb, Hkv, H // Hkv, d)),
    ).reshape(L, H * d)
    return (mixed * jax.nn.sigmoid(a @ p["attn_gate"])) @ p["wo"]


def route(m, p, x):
    """``(chosen experts (T, k), their weights (T, k))`` over ALL experts."""
    logits = jnp.dot(x.astype(jnp.float32), p["router"].astype(jnp.float32), precision=HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, chosen = jax.lax.top_k(
        scores + jax.lax.stop_gradient(p["router_bias"].astype(jnp.float32)),
        int(m["num_experts_per_tok"]),
    )
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    return chosen, weights * float(m["route_scale"])


def _experts(m, p, x):
    """The shared experts, plus what THIS chip's experts give for tokens ``x
    (T, D)``: every held expert passes over every token and counts where the
    token chose it."""
    chosen, weights = route(m, p, x)

    @jax.checkpoint  # the expert's own activations are made again backward
    def given(x, share, gate, up, down):
        return share[:, None].astype(x.dtype) * _swiglu(x, gate, up, down)

    def held(out, expert):
        which, gate, up, down = expert
        share = jnp.sum(jnp.where(chosen == which, weights, 0.0), axis=-1)
        return out + given(x, share, gate, up, down), None

    out = jnp.zeros_like(x)
    if "shared_gate" in p:
        out = jax.checkpoint(_swiglu)(x, p["shared_gate"], p["shared_up"], p["shared_down"])
    out, _ = jax.lax.scan(held, out, (
        jnp.asarray(list(m["experts_held"]), jnp.int32),
        p["experts_gate"], p["experts_up"], p["experts_down"],
    ))
    return out


def _layer(m, p, x, kind: str):
    """``x (S, L, D)``, a sequence at a time (attention mixes within one, and
    every other product is a token's own), each sequence's activations made
    again in the backward pass."""
    eps = float(m["rms_norm_eps"])

    @jax.checkpoint
    def sequence(h):
        h = h + _rms(_attention(m, p, _rms(h, p["attn_norm"], eps), kind), p["post_attn_norm"], eps)
        b = _rms(h, p["ffn_norm"], eps)
        if "w_gate" in p:
            out = jax.checkpoint(_swiglu)(b, p["w_gate"], p["w_up"], p["w_down"])
        else:
            out = _experts(m, p, b)
        return h + _rms(out, p["post_ffn_norm"], eps)

    return jax.lax.map(sequence, x)


def _trunk(m, params, ids):
    """``ids (S, L)`` -> the last layer's output ``(S, L, D)``, before its
    norm: the dense layers, then the expert layers. What a layer keeps for
    the backward pass is its sequences' inputs (``_layer``): a second
    recomputation around the whole layer reads 1.73 GB more on a described
    v5e's ``memory_analysis()``, which the run's memory check judges by."""
    x = params["embed"][ids]
    if m["mup_enabled"]:
        x = x * math.sqrt(int(m["hidden_size"]))
    for name, kinds in zip(("dense_layers", "periods"), _parts(m)):
        if not kinds:
            continue
        _, runs = _runs({"layer_types": kinds})

        def period(x, stacks, runs=runs):
            for j, (kind, _) in enumerate(runs):
                x, _ = jax.lax.scan(
                    lambda x, p, kind=kind: (_layer(m, p, x, kind), None),
                    x, stacks[f"{j}_{kind}"],
                )
            return x, None

        x = jax.lax.scan(period, x, params[name])[0]
    return x


# ------------------------------------------------------------- the kind ----
def apply(model, params, windows):
    model = _whole(model)
    V = int(model["vocab_size"])
    centres = (jnp.arange(V, dtype=jnp.float32) + 0.5) / V

    def one(window):  # a sample at a time: (L, F) -> (F, L)
        h = _trunk(model, params, _sequences(_bins(model, window[None])))
        return _over_vocabulary(
            model, params, lambda logits: jax.nn.softmax(logits, axis=-1) @ centres, h
        )

    expected = jax.lax.map(one, windows)  # (B, F, L)
    return jnp.swapaxes(expected, 1, 2).astype(windows.dtype)


def loss(model, params, windows, targets):
    """Per sample: the mean over tags and positions of the next row's
    cross-entropy. All of a batch's samples go through the layers together."""
    model = _whole(model)
    B, L, F = windows.shape
    ids, ids_next = _sequences(_bins(model, windows)), _sequences(_bins(model, targets))
    nxt = _over_vocabulary(model, params, _cross_entropy, _trunk(model, params, ids), ids_next)
    # an empty batch too
    return jnp.mean(nxt.reshape(B, F, L), axis=(1, 2)).astype(windows.dtype)


# ------------------------------------------------- operations and bytes ----
def n_parameters(model) -> int:
    m = _whole(model)
    D, V = int(m["hidden_size"]), int(m["vocab_size"])
    dense, expert = _parts(m)
    return 2 * V * D + D + sum(
        len(kinds) * sum(math.prod(shape) for shape in _layer_shapes(m, is_dense).values())
        for kinds, is_dense in ((dense, True), (expert, False))
    )


def attention_flops(m, n_sequences: float = 1.0) -> float:
    """Scores and mixing (two products a pair, ``head_dim`` deep, a query
    head) of every layer's attention over ``n_sequences``, FORWARD, inside
    the band alone: the same work whatever computes it."""
    m = _whole(m)
    a_pair = 4.0 * int(m["num_attention_heads"]) * int(m["head_dim"])
    return n_sequences * a_pair * sum(attention_pairs(m, kind) for kind in m["layer_types"])


def expert_ffn_flops(m, n_tokens: float) -> float:
    """Products of one layer's routed part HERE: the expected token-slots that
    fall on the held experts, ``k * held / all`` a token."""
    m = _whole(m)
    slots = int(m["num_experts_per_tok"]) * len(m["experts_held"]) / int(m["num_experts"])
    return 2.0 * n_tokens * slots * 3 * int(m["hidden_size"]) * int(m["moe_intermediate_size"])


def forward_flops(model, n_features: int):
    """What ``apply`` multiplies for one sample: every layer's projections
    (queries, keys, values, gate, output), the dense layers' feed-forward,
    the expert layers' router, shared experts and expected expert slots,
    attention's pairs inside the band, and the head. The embedding is a
    look-up, so no first product goes without a gradient."""
    m, tokens = _whole(model), float(int(model["lookback"]) * n_features)
    D, d = int(m["hidden_size"]), int(m["head_dim"])
    H, Hkv = int(m["num_attention_heads"]), int(m["num_key_value_heads"])
    dense, expert = _parts(m)
    projections = 2.0 * tokens * D * (3 * H + 2 * Hkv) * d
    dense_ffn = 2.0 * tokens * 3 * D * int(m["intermediate_size"])
    shared = 3 * D * int(m["moe_intermediate_size"]) * int(m["num_shared_experts"])
    expert_ffn = (2.0 * tokens * (D * int(m["num_experts"]) + shared)
                  + expert_ffn_flops(m, tokens))
    total = (len(m["layer_types"]) * projections + len(dense) * dense_ffn
             + len(expert) * expert_ffn + attention_flops(m, n_features))
    return {"total": total + 2.0 * tokens * D * int(m["vocab_size"]), "first_layer": 0.0}


def train_flops(model, n_features: int):
    """Forward and twice that backward; experts at their expected slots;
    nothing recomputed is counted."""
    return 3.0 * forward_flops(model, n_features)["total"]


def state_bytes(model, n_features: int):
    # float32 weights, gradients and Adam's two moments: an optimizer step
    # reads 16 bytes a parameter and writes 12 (weights and both moments)
    return 28.0 * n_parameters(model)
