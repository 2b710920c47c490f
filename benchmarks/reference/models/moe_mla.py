"""A decoder of latent-attention and expert layers over sensor values read as
tokens: JoyAI-LLM-Flash's block (DeepSeek-V3's with ``n_group`` 1) as ONE of
the chips that share each layer holds it.

Every tag is a sequence of its own. A scaled value in [0, 1] is binned into
the ``vocab_size`` ids of this chip's slice of the vocabulary; the model
predicts the NEXT row's bin at every position, and its multi-token-prediction
module the row after; ``apply`` hands back the expected bin centre. A sample
reads rows ``i .. i+L-1`` and is judged against rows ``i+1 .. i+L``.

The equations, as published (each departure is in the configuration's
``assumed``):

* layer: ``x += MLA(norm(x)); x += FFN(norm(x))``, RMSNorm with weights;
* MLA: ``q = W_qb·norm(W_qa·x)``; ``[c_kv; k_rope] = W_kva·x``;
  ``[k_nope; v] = W_kvb·norm(c_kv)``; rotary embedding on the ``rope``
  dims in interleaved pairs ``(2i, 2i+1)``, the one rotary key shared by all
  heads; scores scaled by ``(nope + rope)^-0.5``, causal;
* FFN of the leading ``first_k_dense_replace`` layers: SwiGLU of
  ``intermediate_size``; of the others: one shared expert plus the routed
  part: ``s = sigmoid(W_r·x)`` (float32, at ``highest``), the
  ``num_experts_per_tok`` largest of ``s + b`` over ALL ``n_routed_experts``,
  weights ``s`` of the chosen over their sum over all chosen, times
  ``routed_scaling_factor``. This chip holds ``experts_held`` only and adds
  only what they give: a partial result, which is what goes on;
* prediction module (``num_nextn_predict_layers`` 1, the one count this kind
  builds): ``W_p·[norm(h); norm(emb(t+1))]``, one expert layer, a norm of its
  own, the shared embedding and head.

Plain ``jax.numpy``: a dense pass of every held expert over every token (no
sort, no grouped product), the whole score matrix of a sequence. So that one
block of the gradient fits beside the training state, the activations of a
layer, of one held expert, of one sequence's scores and of one sequence's
logits are made again in the backward pass (``jax.checkpoint``), scores and
logits a sequence at a time (``lax.map``); nothing else is saved.

The initial weights are drawn through Flax, as the program draws them (the
configuration's "same seed, same weights" is Flax's per-name key folding):
normal(0.02) a leading index at a time, norms one, the selection bias zero.
The names are declared in one order, which is part of that contract: ``embed``,
``head``, ``final_norm``, ``dense_layers``, ``expert_layers`` (each kind's
layers stacked on a leading axis and scanned over), ``mtp``.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------- sizes ----
# what a dictionary that leaves a size out gets: toy sizes, for callers that
# know a kind by its name alone (the benchmark's test of every kind)
SMALL = {
    "hidden_size": 32, "num_hidden_layers": 2, "first_k_dense_replace": 1,
    "intermediate_size": 48, "moe_intermediate_size": 16, "n_routed_experts": 4,
    "experts_held": (0, 1), "num_experts_per_tok": 2, "n_shared_experts": 1,
    "routed_scaling_factor": 2.5, "q_lora_rank": 16, "kv_lora_rank": 16,
    "num_attention_heads": 2, "qk_nope_head_dim": 8, "qk_rope_head_dim": 4,
    "v_head_dim": 8, "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
    "vocab_size": 32, "mtp_loss_weight": 0.3, "num_nextn_predict_layers": 1,
}


def _whole(model):
    whole = {**SMALL, **model}
    if int(whole["num_nextn_predict_layers"]) != 1:
        raise ValueError(
            "this kind builds the one prediction module that is published; got "
            f"num_nextn_predict_layers {whole['num_nextn_predict_layers']}"
        )
    return whole


def layout(model):
    L = int(model["lookback"])
    return L, L, L  # lookback, target_offset, rows_out


def _attention_shapes(m):
    D, H = int(m["hidden_size"]), int(m["num_attention_heads"])
    nope, rope, dv = (int(m[k]) for k in ("qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))
    rq, rkv = int(m["q_lora_rank"]), int(m["kv_lora_rank"])
    return {
        "attn_norm": (D,), "wq_a": (D, rq), "q_norm": (rq,),
        "wq_b": (rq, H * (nope + rope)), "wkv_a": (D, rkv + rope),
        "kv_norm": (rkv,), "wkv_b": (rkv, H * (nope + dv)), "wo": (H * dv, D),
        "ffn_norm": (D,),
    }


def _layer_shapes(m, dense: bool):
    D = int(m["hidden_size"])
    shapes = _attention_shapes(m)
    if dense:
        I = int(m["intermediate_size"])
        shapes.update(w_gate=(D, I), w_up=(D, I), w_down=(I, D))
        return shapes
    I, E = int(m["moe_intermediate_size"]), len(m["experts_held"])
    S = I * int(m["n_shared_experts"])
    shapes.update(
        router=(D, int(m["n_routed_experts"])), router_bias=(int(m["n_routed_experts"]),),
        shared_gate=(D, S), shared_up=(D, S), shared_down=(S, D),
        experts_gate=(E, D, I), experts_up=(E, D, I), experts_down=(E, I, D),
    )
    return shapes


def _mtp_shapes(m):
    D = int(m["hidden_size"])
    return {"h_norm": (D,), "e_norm": (D,), "proj": (2 * D, D), "out_norm": (D,),
            **_layer_shapes(m, dense=False)}


def _matrix(key, shape):
    """normal(0.02), a leading index at a time: index ``i`` from
    ``split(key, n)[i]`` (no random bits of the leaf's size beside it)."""
    return jax.lax.map(lambda k: 0.02 * jax.random.normal(k, shape[1:], jnp.float32),
                       jax.random.split(key, shape[0]))


def _draw(key, shapes):
    """A group of leaves from one key: leaf ``j`` of the sorted names from
    ``fold_in(key, j)``; norms one, the selection bias zero, else ``_matrix``."""
    out = {}
    for j, (name, shape) in enumerate(sorted(shapes.items())):
        if name.endswith("norm"):
            out[name] = jnp.ones(shape, jnp.float32)
        elif name == "router_bias":
            out[name] = jnp.zeros(shape, jnp.float32)
        else:
            out[name] = _matrix(jax.random.fold_in(key, j), shape)
    return out


def _stack(key, shapes, n):
    """``n`` layers of one kind, stacked on a leading axis: layer ``i`` from
    ``split(key, n)[i]``."""
    return jax.lax.map(lambda k: _draw(k, shapes), jax.random.split(key, n))


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
        n_dense = int(m["first_k_dense_replace"])
        if n_dense:
            self.param("dense_layers", _stack, _layer_shapes(m, True), n_dense)
        if int(m["num_hidden_layers"]) > n_dense:
            self.param("expert_layers", _stack, _layer_shapes(m, False),
                       int(m["num_hidden_layers"]) - n_dense)
        self.param("mtp", _draw, _mtp_shapes(m))


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


def _rope(x, theta: float):
    """``(S, L, ..., rope)``: pair ``(2i, 2i+1)`` turned by ``t * theta^(-2i/rope)``
    at position ``t`` (axis 1); the turned pairs are laid first halves, then
    second halves."""
    L, rope = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, rope, 2, dtype=jnp.float32) / rope)
    angle = jnp.arange(L, dtype=jnp.float32)[:, None] * inv[None, :]  # (L, rope/2)
    angle = angle.reshape((1, L) + (1,) * (x.ndim - 3) + (rope // 2,))
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[..., 0::2].astype(jnp.float32), x[..., 1::2].astype(jnp.float32)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1).astype(x.dtype)


def _mix(scale, q_nope, q_rope, k_nope, k_rope, v):
    """One sequence's attention: the whole ``(H, L, L)`` score matrix."""
    L = q_nope.shape[0]
    scores = (
        jnp.einsum("qhd,khd->hqk", q_nope, k_nope) + jnp.einsum("qhd,kd->hqk", q_rope, k_rope)
    ).astype(jnp.float32) * scale
    scores = jnp.where(jnp.tril(jnp.ones((L, L), bool)), scores, -1e30)
    return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1).astype(v.dtype), v)


def _attention(m, p, x):
    S, L, _ = x.shape
    H = int(m["num_attention_heads"])
    nope, rope, dv = (int(m[k]) for k in ("qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))
    rkv, eps = int(m["kv_lora_rank"]), float(m["rms_norm_eps"])
    q = (_rms(x @ p["wq_a"], p["q_norm"], eps) @ p["wq_b"]).reshape(S, L, H, nope + rope)
    kv_a = x @ p["wkv_a"]
    kv = (_rms(kv_a[..., :rkv], p["kv_norm"], eps) @ p["wkv_b"]).reshape(S, L, H, nope + dv)
    q_rope = _rope(q[..., nope:], float(m["rope_theta"]))
    k_rope = _rope(kv_a[..., rkv:], float(m["rope_theta"]))  # (S, L, rope): one key, all heads
    # a sequence at a time, its scores made again in the backward pass
    mixed = jax.lax.map(
        lambda one: jax.checkpoint(_mix, static_argnums=0)((nope + rope) ** -0.5, *one),
        (q[..., :nope], q_rope, kv[..., :nope], k_rope, kv[..., nope:]),
    )
    return mixed.reshape(S, L, H * dv) @ p["wo"]


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def route(m, p, x):
    """``(chosen experts (T, k), their weights (T, k))`` over ALL experts."""
    scores = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), p["router"].astype(jnp.float32), precision=HIGHEST
    ))
    _, chosen = jax.lax.top_k(scores + p["router_bias"].astype(jnp.float32),
                              int(m["num_experts_per_tok"]))
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    return chosen, weights * float(m["routed_scaling_factor"])


def _experts(m, p, x):
    """The shared expert, plus what THIS chip's experts give: every held
    expert passes over every token and counts where the token chose it."""
    S, L, D = x.shape
    x = x.reshape(S * L, D)
    chosen, weights = route(m, p, x)

    @jax.checkpoint  # the expert's own activations are made again backward
    def given(x, share, gate, up, down):
        return share[:, None].astype(x.dtype) * _swiglu(x, gate, up, down)

    def held(out, expert):
        which, gate, up, down = expert
        share = jnp.sum(jnp.where(chosen == which, weights, 0.0), axis=-1)
        return out + given(x, share, gate, up, down), None

    out = _swiglu(x, p["shared_gate"], p["shared_up"], p["shared_down"])
    out, _ = jax.lax.scan(held, out, (
        jnp.asarray(list(m["experts_held"]), jnp.int32),
        p["experts_gate"], p["experts_up"], p["experts_down"],
    ))
    return out.reshape(S, L, D)


def _layer(m, p, x):
    eps = float(m["rms_norm_eps"])
    x = x + _attention(m, p, _rms(x, p["attn_norm"], eps))
    h = _rms(x, p["ffn_norm"], eps)
    if "w_gate" in p:  # its wide activations are made again backward, as an expert's
        return x + jax.checkpoint(_swiglu)(h, p["w_gate"], p["w_up"], p["w_down"])
    return x + _experts(m, p, h)


def _trunk(m, params, ids):
    """``ids (S, L)`` -> the last layer's output ``(S, L, D)``, before its
    norm. A layer's activations are made again in the backward pass."""
    x = params["embed"][ids]
    layer = jax.checkpoint(lambda x, p: (_layer(m, p, x), None))
    for stack in ("dense_layers", "expert_layers"):  # each kind's layers, stacked
        if stack in params:
            x, _ = jax.lax.scan(layer, x, params[stack])
    return x


def _after(m, params, h, ids_next):
    """The prediction module: from the trunk's ``h_t`` and the NEXT token's
    embedding, the hidden state that predicts the token after it."""
    p, eps = params["mtp"], float(m["rms_norm_eps"])
    joined = jnp.concatenate(
        [_rms(h, p["h_norm"], eps), _rms(params["embed"][ids_next], p["e_norm"], eps)], axis=-1
    )
    return jax.checkpoint(lambda p, x: _layer(m, p, x))(p, joined @ p["proj"])


def _over_vocabulary(m, params, norm, read, h, *rest):
    """``read(logits (L, V) float32, ...)`` of each sequence in turn: one
    sequence's logits are the most that is held, and they are made again in
    the backward pass."""
    def one(args):
        logits = _rms(args[0], norm, float(m["rms_norm_eps"])) @ params["head"]
        return read(logits.astype(jnp.float32), *args[1:])

    return jax.lax.map(jax.checkpoint(one), (h,) + rest)


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
            model, params, params["final_norm"],
            lambda logits: jax.nn.softmax(logits, axis=-1) @ centres, h,
        )

    expected = jax.lax.map(one, windows)  # (B, F, L)
    return jnp.swapaxes(expected, 1, 2).astype(windows.dtype)


def loss_terms(model, params, windows, targets):
    """Per sample ``(next, after)``: the mean over tags and positions of the
    next row's cross-entropy, and of the prediction module's on the row after
    it (which the last position has no target for). All of a batch's samples
    go through the layers together."""
    model = _whole(model)
    B, L, F = windows.shape
    per_sample = lambda a: jnp.mean(a.reshape(B, F, a.shape[-1]), axis=(1, 2))  # noqa: E731 (an empty batch too)
    ids, ids_next = _sequences(_bins(model, windows)), _sequences(_bins(model, targets))
    h = _trunk(model, params, ids)
    nxt = _over_vocabulary(
        model, params, params["final_norm"], _cross_entropy, h, ids_next
    )
    after = _over_vocabulary(
        model, params, params["mtp"]["out_norm"],
        lambda logits, ids: _cross_entropy(logits[:-1], ids[1:]),
        _after(model, params, h, ids_next), ids_next,
    )
    return per_sample(nxt), per_sample(after)


def loss(model, params, windows, targets):
    nxt, after = loss_terms(model, params, windows, targets)
    return (nxt + float(_whole(model)["mtp_loss_weight"]) * after).astype(windows.dtype)


# ------------------------------------------------- operations and bytes ----
def n_parameters(model) -> int:
    import math

    m = _whole(model)
    groups = [{"embed": (int(m["vocab_size"]), int(m["hidden_size"])),
               "head": (int(m["hidden_size"]), int(m["vocab_size"])),
               "final_norm": (int(m["hidden_size"]),)}]
    groups += [_mtp_shapes(m)]
    groups += [_layer_shapes(m, i < int(m["first_k_dense_replace"]))
               for i in range(int(m["num_hidden_layers"]))]  # stacked by kind
    return sum(math.prod(shape) for group in groups for shape in group.values())


def attention_flops(m, n_tokens: float) -> float:
    """Products of one layer's attention over sequences of ``lookback``:
    the projections, and scores and mix at their causal mean."""
    m = _whole(m)
    D, H, L = int(m["hidden_size"]), int(m["num_attention_heads"]), int(m["lookback"])
    nope, rope, dv = (int(m[k]) for k in ("qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))
    rq, rkv = int(m["q_lora_rank"]), int(m["kv_lora_rank"])
    weights = D * rq + rq * H * (nope + rope) + D * (rkv + rope) + rkv * H * (nope + dv) + H * dv * D
    return 2.0 * n_tokens * (weights + H * (nope + rope + dv) * (L + 1) / 2)


def expert_ffn_flops(m, n_tokens: float) -> float:
    """Products of one layer's routed part HERE: the expected token-slots that
    fall on the held experts, ``k * held / all`` a token."""
    m = _whole(m)
    slots = int(m["num_experts_per_tok"]) * len(m["experts_held"]) / int(m["n_routed_experts"])
    return 2.0 * n_tokens * slots * 3 * int(m["hidden_size"]) * int(m["moe_intermediate_size"])


def _expert_layer_flops(m, n_tokens: float) -> float:
    m = _whole(m)
    D = int(m["hidden_size"])
    shared = 3 * D * int(m["moe_intermediate_size"]) * int(m["n_shared_experts"])
    return (attention_flops(m, n_tokens) + expert_ffn_flops(m, n_tokens)
            + 2.0 * n_tokens * (D * int(m["n_routed_experts"]) + shared))


def forward_flops(model, n_features: int):
    """What ``apply`` multiplies for one sample: trunk and head. The
    embedding is a look-up, so no first product goes without a gradient."""
    m, tokens = _whole(model), float(int(model["lookback"]) * n_features)
    D, n_dense = int(m["hidden_size"]), int(m["first_k_dense_replace"])
    dense = attention_flops(m, tokens) + 2.0 * tokens * 3 * D * int(m["intermediate_size"])
    total = n_dense * dense + (int(m["num_hidden_layers"]) - n_dense) * _expert_layer_flops(m, tokens)
    return {"total": total + 2.0 * tokens * D * int(m["vocab_size"]), "first_layer": 0.0}


def train_flops(model, n_features: int):
    """Forward and twice that backward, of the trunk, the head and the
    prediction module (one position fewer a sequence); experts at their
    expected slots; nothing recomputed is counted."""
    m = _whole(model)
    D = int(m["hidden_size"])
    after = float((int(m["lookback"]) - 1) * n_features)
    module = _expert_layer_flops(m, after) + 2.0 * after * (2 * D * D + D * int(m["vocab_size"]))
    return 3.0 * (forward_flops(m, n_features)["total"] + module)


def state_bytes(model, n_features: int):
    # float32 weights, gradients and Adam's two moments: an optimizer step
    # reads 16 bytes a parameter and writes 12 (weights and both moments)
    return 28.0 * n_parameters(model)
