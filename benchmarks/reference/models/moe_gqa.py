"""A decoder of grouped-query-attention and expert layers over sensor values
read as tokens: Mellum2-12B-A2.5B's block as ONE of the chips that share each
layer holds it.

Every tag is a sequence of its own. A scaled value in [0, 1] is binned into
the ``vocab_size`` ids of this chip's slice of the vocabulary; the model
predicts the NEXT row's bin at every position; ``apply`` hands back the
expected bin centre. A sample reads rows ``i .. i+L-1`` and is judged against
rows ``i+1 .. i+L``.

The equations, as published (``config.json``; each departure is in the
configuration's ``assumed``):

* every layer: ``h = x + Attn(norm(x)); y = h + MoE(norm(h))``, RMSNorm with
  weights; a final norm; an untied head. No leading dense layer
  (``mlp_layer_types`` is ``sparse`` throughout: ``intermediate_size``
  belongs to no layer), no shared expert, no prediction module (the config
  has no key for one);
* attention: ``q = x·W_q`` (``num_attention_heads`` of ``head_dim``), ``k =
  x·W_k``, ``v = x·W_v`` (``num_key_value_heads``), no bias, no query/key
  norm; query head ``i`` reads key/value head ``i // group``; rotary
  embedding on ``q`` and ``k`` in pairs ``(i, i + head_dim/2)``; scores
  ``q·k / sqrt(head_dim)``; position ``i`` sees ``j <= i``, and in a
  ``sliding_attention`` layer only ``i - j < sliding_window``;
* rotary frequencies by layer kind (``rope_parameters``): ``default``:
  ``theta^(-2i/d)``; ``yarn``: those and those divided by ``factor``, blended
  by the linear ramp between the correction dimensions of ``beta_fast`` and
  ``beta_slow`` at ``original_max_position_embeddings`` (floor and ceiling,
  clipped to the dimensions there are), cosine and sine times
  ``attention_factor``;
* experts: scores ``softmax(W_r·x)`` in float32 at ``highest`` over ALL
  ``num_experts``, the ``num_experts_per_tok`` largest, weights renormalised
  over the chosen (``norm_topk_prob``); expert ``e``: ``(silu(h·G_e) * (h·U_e))
  ·D_e``. This chip holds ``experts_held`` only and adds only what they give:
  a partial result, which is what goes on.

Plain ``jax.numpy``: a dense pass of every held expert over every token (no
sort, no grouped product), masked dense attention over ALL keys in window
layers and full ones alike (no band is skipped), a block of ``query_block``
queries at a time (a whole sequence's scores at 8,192 rows are 8.6 GB). So
that one block of the gradient fits beside the training state, a layer runs
a sequence at a time, and the activations of a layer, of one sequence of it,
of one held expert, of one block of scores and of one block of
``logit_block`` positions' logits are made again in the backward pass
(``jax.checkpoint`` around each, ``lax.map`` over them); nothing else is
saved.

The initial weights are drawn through Flax, as the program draws them (the
configuration's "same seed, same weights" is Flax's per-name key folding):
normal(0.02) a leading index at a time, norms one. The names are declared in
one order, which is part of that contract: ``embed``, ``head``,
``final_norm``, ``periods``: the layer pattern's shortest period cut into
runs of one kind, run ``j`` of kind ``k`` the group ``"<j>_<k>"`` whose
leaves are stacked ``(periods, layers of the run, ...)``; period ``i`` from
``split(key, P)[i]``, its run ``j`` from ``fold_in(that, j)``, the run's
layer ``l`` from ``split(that, n)[l]``, leaf ``m`` of the layer's sorted names
from ``fold_in(that, m)``.
"""

from __future__ import annotations

import math

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
SLIDING, FULL = "sliding_attention", "full_attention"


# ---------------------------------------------------------------- sizes ----
# what a dictionary that leaves a size out gets: toy sizes, for callers that
# know a kind by its name alone (the benchmark's test of every kind)
SMALL = {
    "hidden_size": 32, "layer_types": (SLIDING, FULL), "sliding_window": 4,
    "rope_parameters": {
        SLIDING: {"rope_type": "default", "rope_theta": 10000.0},
        FULL: {"rope_type": "yarn", "rope_theta": 10000.0, "factor": 4.0,
               "original_max_position_embeddings": 8, "beta_fast": 4.0,
               "beta_slow": 1.0},
    },
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
    "moe_intermediate_size": 16, "num_experts": 4, "experts_held": (0, 1),
    "num_experts_per_tok": 2, "rms_norm_eps": 1e-6, "vocab_size": 32,
    "query_block": 256, "logit_block": 2048,
}


def _whole(model):
    return {**SMALL, **model}


def layout(model):
    L = int(model["lookback"])
    return L, L, L  # lookback, target_offset, rows_out


def _runs(m):
    """``(periods, [(kind, layers), ...])``: the pattern's shortest period,
    cut into runs of one kind."""
    kinds = list(m["layer_types"])
    n = len(kinds)
    period = next(p for p in range(1, n + 1) if n % p == 0 and kinds == kinds[:p] * (n // p))
    runs = []
    for kind in kinds[:period]:
        if runs and runs[-1][0] == kind:
            runs[-1][1] += 1
        else:
            runs.append([kind, 1])
    return n // period, [(kind, count) for kind, count in runs]


def _layer_shapes(m):
    D, d = int(m["hidden_size"]), int(m["head_dim"])
    H, Hkv = int(m["num_attention_heads"]), int(m["num_key_value_heads"])
    I, E = int(m["moe_intermediate_size"]), len(m["experts_held"])
    return {
        "attn_norm": (D,), "wq": (D, H * d), "wk": (D, Hkv * d), "wv": (D, Hkv * d),
        "wo": (H * d, D), "ffn_norm": (D,), "router": (D, int(m["num_experts"])),
        "experts_gate": (E, D, I), "experts_up": (E, D, I), "experts_down": (E, I, D),
    }


def _matrix(key, shape):
    """normal(0.02), a leading index at a time: index ``i`` from
    ``split(key, n)[i]`` (no random bits of the leaf's size beside it)."""
    return jax.lax.map(lambda k: 0.02 * jax.random.normal(k, shape[1:], jnp.float32),
                       jax.random.split(key, shape[0]))


def _draw(key, shapes):
    """A layer's leaves from one key: leaf ``j`` of the sorted names from
    ``fold_in(key, j)``; norms one, else ``_matrix``."""
    return {
        name: jnp.ones(shape, jnp.float32) if name.endswith("norm")
        else _matrix(jax.random.fold_in(key, j), shape)
        for j, (name, shape) in enumerate(sorted(shapes.items()))
    }


def _periods(key, shapes, n_periods, runs):
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
        self.param("periods", _periods, _layer_shapes(m), *_runs(m))


def init(model, key, n_features: int, n_out: int):
    return _Init(_whole(model)).init(key)["params"]


# ----------------------------------------------------------- one sequence ----
def _bins(model, values):
    V = int(model["vocab_size"])
    return jnp.clip(jnp.floor(values.astype(jnp.float32) * V), 0, V - 1).astype(jnp.int32)


def _rms(x, weight, eps):
    x32 = x.astype(jnp.float32)
    normed = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (normed * weight.astype(jnp.float32)).astype(x.dtype)


def rope_table(m, kind: str):
    """``(inv_freq (d/2,) float64, the factor on cosine and sine)`` of a layer
    kind, as ``rope_parameters`` states them."""
    rope, d = m["rope_parameters"][kind], int(m["head_dim"])
    theta = float(rope["rope_theta"])
    inv = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    if rope.get("rope_type", "default") == "default":
        return inv, 1.0
    factor = float(rope["factor"])
    reach = float(rope["original_max_position_embeddings"])
    # the dimension that turns ``n`` times over ``reach`` positions
    turns = lambda n: d * math.log(reach / (n * 2.0 * math.pi)) / (2.0 * math.log(theta))  # noqa: E731
    low = max(math.floor(turns(float(rope["beta_fast"]))), 0)
    high = min(math.ceil(turns(float(rope["beta_slow"]))), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2, dtype=np.float64) - low) / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp  # 1: the published frequency; 0: the stretched one
    mixed = (inv / factor) * (1.0 - keep) + inv * keep
    return mixed, float(rope.get("attention_factor", 0.1 * math.log(factor) + 1.0))


def _rope(x, inv_freq, factor):
    """``(S, L, heads, d)``: dimension ``i`` turned with dimension ``i + d/2``
    by ``t * inv_freq[i]`` at position ``t`` (axis 1)."""
    L, half = x.shape[1], x.shape[-1] // 2
    angle = jnp.arange(L, dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq, jnp.float32)[None, :]
    cos = (factor * jnp.cos(angle))[None, :, None, :]
    sin = (factor * jnp.sin(angle))[None, :, None, :]
    a, b = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1).astype(x.dtype)


def _mix(scale, window, first, q, k, v):
    """A block of one sequence's queries ``(qb, Hkv, G, d)``, the first at
    position ``first``, over ALL its keys ``(L, Hkv, d)``: the whole ``(Hkv,
    G, qb, L)`` block of scores, masked."""
    qb, L = q.shape[0], k.shape[0]
    scores = jnp.einsum("qhgd,khd->hgqk", q, k).astype(jnp.float32) * scale
    i = first + jnp.arange(qb)[:, None]
    j = jnp.arange(L)[None, :]
    seen = j <= i
    if window is not None:
        seen = seen & (i - j < window)
    scores = jnp.where(seen, scores, -1e30)
    return jnp.einsum("hgqk,khd->qhgd", jax.nn.softmax(scores, axis=-1).astype(v.dtype), v)


def _attention(m, p, x, kind: str):
    """One sequence ``(L, D)``, already normed."""
    L = x.shape[0]
    H, Hkv, d = (int(m[k]) for k in ("num_attention_heads", "num_key_value_heads", "head_dim"))
    inv_freq, factor = rope_table(m, kind)
    q = _rope((x @ p["wq"]).reshape(1, L, H, d), inv_freq, factor)[0]
    k = _rope((x @ p["wk"]).reshape(1, L, Hkv, d), inv_freq, factor)[0]
    v = (x @ p["wv"]).reshape(L, Hkv, d)
    qb = min(L, int(m["query_block"]))
    if L % qb:
        qb = L
    window = int(m["sliding_window"]) if kind == SLIDING else None
    mix = jax.checkpoint(_mix, static_argnums=(0, 1))  # a block's scores are made again backward
    mixed = jax.lax.map(
        lambda b: mix(d ** -0.5, window, b[0], b[1], k, v),
        (qb * jnp.arange(L // qb), q.reshape(L // qb, qb, Hkv, H // Hkv, d)),
    )
    return mixed.reshape(L, H * d) @ p["wo"]


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def route(m, p, x):
    """``(chosen experts (T, k), their weights (T, k))`` over ALL experts: a
    softmax over the router's logits, the k largest, renormalised over the
    chosen (``norm_topk_prob``)."""
    scores = jax.nn.softmax(jnp.dot(
        x.astype(jnp.float32), p["router"].astype(jnp.float32), precision=HIGHEST
    ), axis=-1)
    weights, chosen = jax.lax.top_k(scores, int(m["num_experts_per_tok"]))
    return chosen, weights / jnp.sum(weights, axis=-1, keepdims=True)


def _experts(m, p, x):
    """What THIS chip's experts give for tokens ``x (T, D)``: every held
    expert passes over every token and counts where the token chose it."""
    chosen, weights = route(m, p, x)

    @jax.checkpoint  # the expert's own activations are made again backward
    def given(x, share, gate, up, down):
        return share[:, None].astype(x.dtype) * _swiglu(x, gate, up, down)

    def held(out, expert):
        which, gate, up, down = expert
        share = jnp.sum(jnp.where(chosen == which, weights, 0.0), axis=-1)
        return out + given(x, share, gate, up, down), None

    out, _ = jax.lax.scan(held, jnp.zeros_like(x), (
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
    def sequence(x_s):
        x_s = x_s + _attention(m, p, _rms(x_s, p["attn_norm"], eps), kind)
        return x_s + _experts(m, p, _rms(x_s, p["ffn_norm"], eps))

    return jax.lax.map(sequence, x)


def _trunk(m, params, ids):
    """``ids (S, L)`` -> the last layer's output ``(S, L, D)``, before its
    norm. A layer's activations are made again in the backward pass."""
    _, runs = _runs(m)

    def period(x, stacks):
        for j, (kind, _) in enumerate(runs):
            layer = jax.checkpoint(lambda x, p, kind=kind: (_layer(m, p, x, kind), None))
            x, _ = jax.lax.scan(layer, x, stacks[f"{j}_{kind}"])
        return x, None

    return jax.lax.scan(period, params["embed"][ids], params["periods"])[0]


def _over_vocabulary(m, params, read, h, *rest):
    """``read(logits (rows, V) float32, ...)`` of ``logit_block`` positions
    of a sequence at a time (``h (S, L, D)``, ``rest (S, L)``; ``read`` is a
    position's own): one block's logits are the most that is held, and they
    are made again in the backward pass."""
    S, L = h.shape[:2]
    rows = min(L, int(m["logit_block"]))
    if L % rows:
        rows = L
    blocks = [a.reshape((S * L // rows, rows) + a.shape[2:]) for a in (h,) + rest]

    def one(args):
        logits = _rms(args[0], params["final_norm"], float(m["rms_norm_eps"])) @ params["head"]
        return read(logits.astype(jnp.float32), *args[1:])

    return jax.lax.map(jax.checkpoint(one), tuple(blocks)).reshape(S, L)


def _cross_entropy(logits, ids):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, ids[:, None], axis=-1)[:, 0]


def _sequences(windows):
    """``(B, L, F) -> (B * F, L)``: every tag a sequence."""
    B, L, F = windows.shape
    return jnp.swapaxes(windows, 1, 2).reshape(B * F, L)


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
    layer = sum(math.prod(shape) for shape in _layer_shapes(m).values())
    return 2 * V * D + D + len(m["layer_types"]) * layer


def attention_pairs(m, kind: str) -> float:
    """(query, key) pairs inside the band of one sequence of ``lookback``:
    row ``i`` sees ``min(i + 1, window)`` keys in a window layer, ``i + 1``
    in a full one."""
    m = _whole(m)
    L, W = int(m["lookback"]), int(m["sliding_window"])
    if kind == SLIDING and W < L:
        return W * (W + 1) / 2.0 + (L - W) * float(W)
    return L * (L + 1) / 2.0


def attention_flops(m, n_sequences: float = 1.0) -> float:
    """Scores and mixing (two products a pair, ``head_dim`` deep, a query
    head) of every layer's attention over ``n_sequences``, FORWARD, inside
    the band alone: the same work whatever computes it. Backward is twice
    this (``dv``, ``dp``, ``dq``, ``dk``; the scores made again are not
    counted)."""
    m = _whole(m)
    a_pair = 4.0 * int(m["num_attention_heads"]) * int(m["head_dim"])
    return n_sequences * a_pair * sum(attention_pairs(m, kind) for kind in m["layer_types"])


def attention_bytes(m, n_sequences: float = 1.0) -> float:
    """The least bytes every layer's attention moves over ``n_sequences``,
    FORWARD: queries read and output written a query head, keys and values
    read a key head, once each, float32. Backward is twice this (``q``, ``k``,
    ``v``, the output and its cotangent read; ``dq``, ``dk``, ``dv``
    written)."""
    m = _whole(m)
    heads = 2 * int(m["num_attention_heads"]) + 2 * int(m["num_key_value_heads"])
    a_layer = 4.0 * heads * int(m["head_dim"]) * int(m["lookback"])
    return n_sequences * len(m["layer_types"]) * a_layer


def expert_ffn_flops(m, n_tokens: float) -> float:
    """Products of one layer's routed part HERE: the expected token-slots that
    fall on the held experts, ``k * held / all`` a token."""
    m = _whole(m)
    slots = int(m["num_experts_per_tok"]) * len(m["experts_held"]) / int(m["num_experts"])
    return 2.0 * n_tokens * slots * 3 * int(m["hidden_size"]) * int(m["moe_intermediate_size"])


def forward_flops(model, n_features: int):
    """What ``apply`` multiplies for one sample: the layers' projections,
    router and expected expert slots, attention's pairs inside the band, and
    the head. The embedding is a look-up, so no first product goes without a
    gradient."""
    m, tokens = _whole(model), float(int(model["lookback"]) * n_features)
    D, d = int(m["hidden_size"]), int(m["head_dim"])
    H, Hkv = int(m["num_attention_heads"]), int(m["num_key_value_heads"])
    projections = D * (2 * H + 2 * Hkv) * d + D * int(m["num_experts"])
    a_layer = 2.0 * tokens * projections + expert_ffn_flops(m, tokens)
    total = len(m["layer_types"]) * a_layer + attention_flops(m, n_features)
    return {"total": total + 2.0 * tokens * D * int(m["vocab_size"]), "first_layer": 0.0}


def train_flops(model, n_features: int):
    """Forward and twice that backward; experts at their expected slots;
    nothing recomputed is counted."""
    return 3.0 * forward_flops(model, n_features)["total"]


def state_bytes(model, n_features: int):
    # float32 weights, gradients and Adam's two moments: an optimizer step
    # reads 16 bytes a parameter and writes 12 (weights and both moments)
    return 28.0 * n_parameters(model)
