"""Latent-attention / expert-layer decoder factory: a per-machine sequence
model over sensor values read as tokens.

No reference counterpart. The block is DeepSeek-V3's (multi-head latent
attention, a router over all experts with a selection bias, one shared
expert, one multi-token-prediction module), published with these sizes as
JoyAI-LLM-Flash; the factory's defaults are small. Each tag is a sequence
of its own, as the ``patchtst`` kind is channel-independent: a scaled value
in [0, 1] is binned into ``vocab_size`` ids, the model predicts the NEXT
row's bin at every position and the prediction module the row after, and
the module's output is the expected bin centre, so residuals, error
scaler and thresholds stay the stock ones.

**The expert layer's contract.** The layer is told which experts it holds
(``experts_held``, ids among ``n_routed_experts``: one chip's share when a
layer is divided over chips; all of them by default). It routes over ALL
experts (``s = sigmoid(W_r·x)`` in float32, the ``experts_per_token``
largest of ``s + b``, weights ``s`` over their sum over all chosen, times
``routed_scaling_factor``) and adds only what its own experts give: the
partial result goes on, and on one chip there is no exchange. No token is
dropped: the (token, choice) slots are sorted by held expert, the slots of
experts held elsewhere last, and one grouped product
(``jax.lax.ragged_dot``) a weight matrix runs over the sorted rows, a chunk
at a time and only as far as slots of held experts reach. The selection bias
``b`` takes no gradient (its update is a training recipe).

The module brings its own loss (``sample_losses``: cross-entropy at every
position plus ``MTP_LOSS_WEIGHT`` times the prediction module's), which
``models.train.make_loss_fn`` calls for the loss named ``"module"``, and
counts the token-slots each held expert received (``expert_tokens``).

The parameter tree is drawn group by group in ONE declared order (Flax
folds a name's position into its key): ``embed``, ``head``, ``final_norm``,
``dense_layers`` and ``expert_layers`` (each kind's layers stacked on a
leading axis, layer ``i`` from ``split(key, n)[i]``: the trunk scans over
them, so the compiler sees a layer once), ``mtp``; inside a group leaf ``j``
of the sorted names comes
from ``fold_in(key, j)``: normal(0.02) a leading index at a time (index ``i``
from ``split(key, n)[i]``), norms one, the selection bias zero.
The benchmark's plain reference draws the same tree the same way.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ...ops.attention import dense_attention
from ...ops.flash_attention import flash_attention
from ..register import register_model_factory
from ..train import MODULE_LOSS
from .feedforward import _reject_unknown
from .spec import ModelSpec, make_optimizer

_INIT_STD = 0.02
# the prediction module's share of the loss (DeepSeek-V3's first-phase weight;
# the published config.json does not carry one)
MTP_LOSS_WEIGHT = 0.3


def _draw_matrix(key, shape: Tuple[int, ...]):
    """normal(0.02), a leading index at a time (index ``i`` from ``split(key,
    n)[i]``): a leaf of gigabytes is drawn without random bits of its size
    beside it."""
    keys = jax.random.split(key, shape[0])
    return jax.lax.map(
        lambda k: _INIT_STD * jax.random.normal(k, shape[1:], jnp.float32), keys
    )


def _draw_stack(key, shapes: Dict[str, Tuple[int, ...]], n: int):
    """``n`` layers of one kind, stacked on a leading axis (layer ``i`` from
    ``split(key, n)[i]``): the trunk scans over them, so a layer is traced,
    compiled and kept as code once however many there are."""
    return jax.lax.map(lambda k: _draw_group(k, shapes), jax.random.split(key, n))


def _draw_group(key, shapes: Dict[str, Tuple[int, ...]]):
    out = {}
    for j, (name, shape) in enumerate(sorted(shapes.items())):
        if name.endswith("norm"):
            out[name] = jnp.ones(shape, jnp.float32)
        elif name == "router_bias":
            out[name] = jnp.zeros(shape, jnp.float32)
        else:
            out[name] = _draw_matrix(jax.random.fold_in(key, j), shape)
    return out


def rms_norm(x, weight, eps: float):
    x32 = x.astype(jnp.float32)
    normed = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (normed * weight).astype(x.dtype)


def rotary(x, theta: float):
    """Interleaved rotary embedding over axis 1 (positions) of ``(S, L, ...,
    rope)``: the pair ``(2i, 2i+1)`` turns by ``t·theta^(-2i/rope)``; the
    turned pairs come out first halves, then second halves (queries and keys
    alike, so their products are the published ones)."""
    length, rope = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, rope, 2, dtype=jnp.float32) / rope)
    angle = jnp.arange(length, dtype=jnp.float32)[:, None] * inv[None, :]
    angle = angle.reshape((1, length) + (1,) * (x.ndim - 3) + (rope // 2,))
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def route(x, router, router_bias, k: int, scaling: float):
    """``(chosen (T, k), weights (T, k))`` over ALL experts, as published:
    scores in float32 at ``highest`` whatever the model computes in."""
    scores = jax.nn.sigmoid(
        jnp.dot(
            x.astype(jnp.float32), router.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        )
    )
    _, chosen = jax.lax.top_k(scores + jax.lax.stop_gradient(router_bias), k)
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    return chosen, weights * scaling


def grouped_experts(x, chosen, weights, held: Sequence[int], n_experts: int,
                    gate, up, down):
    """What the held experts give for tokens ``x (T, D)``: ``(partial (T, D),
    token-slots a held expert (E,))``. The ``T·k`` (token, choice) slots are
    sorted by held expert (a stable sort), those of experts held elsewhere
    last, outside every group, and the grouped products (``ragged_dot``)
    run over the sorted rows a chunk at a time: a chunk is twice what a
    uniform router sends here, so one chunk is the usual case; chunks past
    the last slot of a held expert are skipped, and however many slots fall
    here, none is dropped. (Under ``vmap`` the skip becomes a select: every
    chunk is computed. A slice of one machine is not vmapped.)

    The backward pass is written out (``custom_vjp``): it walks the same
    chunks, makes a chunk's rows and activations again and skips the same
    ones. Left to the transposition of the loop, every chunk, skipped or not,
    would keep the tokens and the three weight matrices it was handed: as
    many copies of them as there are chunks."""
    n_tokens, k = chosen.shape
    n_held, n_slots = len(held), n_tokens * k
    # expert id -> its place among the held ones, or n_held: held elsewhere
    place = np.full((n_experts,), n_held, np.int32)
    place[list(held)] = np.arange(n_held, dtype=np.int32)
    slot_place = jnp.asarray(place)[chosen.reshape(-1)]  # (T * k,)
    chunk = min(n_slots, -(-2 * n_slots * n_held // n_experts // 8) * 8)
    n_chunks = -(-n_slots // chunk)
    firsts = chunk * np.arange(n_chunks, dtype=np.int32)
    order = jnp.pad(
        jnp.argsort(slot_place, stable=True), (0, n_chunks * chunk - n_slots)
    )
    sizes = jnp.bincount(slot_place, length=n_held + 1)[:n_held].astype(jnp.int32)

    def given_by(x, gate, up, down, slot_weights, order, sizes, first):
        """What the chunk of sorted slots that starts at ``first`` gives, a
        row a slot."""
        ends = jnp.cumsum(sizes)
        slots = jax.lax.dynamic_slice(order, (first,), (chunk,))
        # rows past the last group belong to no expert here
        here = (first + jnp.arange(chunk) < ends[-1])[:, None]
        # the part of each expert's group that lies in this chunk
        inside = jnp.clip(ends - first, 0, chunk) - jnp.clip(ends - sizes - first, 0, chunk)

        def grouped(rows, weights):
            # ragged_dot leaves the rows outside every group unwritten
            # (whatever the buffer held, on the TPU): they are zeroed going
            # in and coming out, so neither they nor, in the backward pass,
            # their gradients reach a token
            rows = jnp.where(here, rows, 0.0)
            return jnp.where(here, jax.lax.ragged_dot(rows, weights, inside), 0.0)

        rows = x[slots // k]  # sorted by held expert
        with jax.named_scope("expert_ffn"):
            hidden = jax.nn.silu(grouped(rows, gate)) * grouped(rows, up)
            given = grouped(hidden, down)
        return given * slot_weights[slots][:, None]

    def over_chunks(start, step, order, sizes):
        """``step(carry, first)`` over the chunks that hold a slot of a held
        expert; the others leave ``carry`` as it is."""
        n_here = jnp.sum(sizes)
        return jax.lax.scan(
            lambda carry, first: (
                jax.lax.cond(first < n_here, step, lambda c, _: c, carry, first),
                None,
            ),
            start, firsts,
        )[0]

    @jax.custom_vjp
    def partial_result(x, gate, up, down, slot_weights, order, sizes):
        def add(partial, first):
            slots = jax.lax.dynamic_slice(order, (first,), (chunk,))
            return partial.at[slots // k].add(
                given_by(x, gate, up, down, slot_weights, order, sizes, first)
            )

        return over_chunks(jnp.zeros_like(x), add, order, sizes)

    def forward(*args):
        return partial_result(*args), args

    def backward(args, g):
        floats, (order, sizes) = args[:5], args[5:]

        def add(grads, first):
            slots = jax.lax.dynamic_slice(order, (first,), (chunk,))
            _, pull = jax.vjp(
                lambda *floats: given_by(*floats, order, sizes, first), *floats
            )
            return jax.tree_util.tree_map(jnp.add, grads, pull(g[slots // k]))

        grads = over_chunks(
            jax.tree_util.tree_map(jnp.zeros_like, floats), add, order, sizes
        )
        return (*grads, None, None)

    partial_result.defvjp(forward, backward)
    partial = partial_result(
        x, gate, up, down, weights.reshape(-1).astype(x.dtype), order, sizes
    )
    return partial, sizes


class MoEMLADecoder(nn.Module):
    """``(batch, L, F)`` scaled values → ``(batch, L, F)``: the expected bin
    centre of the row after each position, every tag a sequence."""

    vocab_size: int
    hidden_size: int
    n_layers: int
    n_dense_layers: int
    intermediate_size: int
    moe_intermediate_size: int
    n_routed_experts: int
    experts_held: Tuple[int, ...]
    experts_per_token: int
    n_shared_experts: int
    routed_scaling_factor: float
    q_lora_rank: int
    kv_lora_rank: int
    n_heads: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float
    rms_norm_eps: float
    attention_impl: str = "flash"
    # recompute a layer's activations on the backward pass (the same trade
    # as the patchtst kind's): fleet builds read it as "memory-constrained"
    remat: bool = False

    # -- the parameter tree, in the one order --------------------------------
    def _layer_shapes(self, dense: bool) -> Dict[str, Tuple[int, ...]]:
        D, H = self.hidden_size, self.n_heads
        nope, rope, dv = self.qk_nope_head_dim, self.qk_rope_head_dim, self.v_head_dim
        shapes = {
            "attn_norm": (D,), "wq_a": (D, self.q_lora_rank),
            "q_norm": (self.q_lora_rank,),
            "wq_b": (self.q_lora_rank, H * (nope + rope)),
            "wkv_a": (D, self.kv_lora_rank + rope),
            "kv_norm": (self.kv_lora_rank,),
            "wkv_b": (self.kv_lora_rank, H * (nope + dv)),
            "wo": (H * dv, D), "ffn_norm": (D,),
        }
        if dense:
            I = self.intermediate_size
            shapes.update(w_gate=(D, I), w_up=(D, I), w_down=(I, D))
        else:
            I, E = self.moe_intermediate_size, len(self.experts_held)
            S = I * self.n_shared_experts
            shapes.update(
                router=(D, self.n_routed_experts),
                router_bias=(self.n_routed_experts,),
                shared_gate=(D, S), shared_up=(D, S), shared_down=(S, D),
                experts_gate=(E, D, I), experts_up=(E, D, I),
                experts_down=(E, I, D),
            )
        return shapes

    def setup(self):
        D, V = self.hidden_size, self.vocab_size
        self.embed = self.param("embed", _draw_matrix, (V, D))
        self.head = self.param("head", _draw_matrix, (D, V))
        self.final_norm = self.param("final_norm", nn.initializers.ones, (D,))
        n_dense, n_expert = self.n_dense_layers, self.n_layers - self.n_dense_layers
        self.stacks = [
            self.param(name, _draw_stack, self._layer_shapes(dense=dense), n)
            for name, dense, n in (
                ("dense_layers", True, n_dense), ("expert_layers", False, n_expert),
            ) if n
        ]
        self.mtp = self.param(
            "mtp", _draw_group,
            {"h_norm": (D,), "e_norm": (D,), "proj": (2 * D, D),
             "out_norm": (D,), **self._layer_shapes(dense=False)},
        )

    # -- layers ---------------------------------------------------------------
    def _recomputed(self, fn):
        """``fn``, its activations made again on the backward pass where the
        model recomputes (``remat``): what goes into ``fn`` is all that is
        kept of it."""
        return jax.checkpoint(fn) if self.remat else fn

    def _per_sequence(self, fn, x):
        """``fn (L, D) → (L, D)`` of each sequence of ``x (S, L, D)`` in turn:
        attention mixes within a sequence and every other product is a
        token's own, so one sequence's activations are the most a block of
        the layer holds, forward or (``remat``) backward."""
        return jax.lax.map(self._recomputed(fn), x)

    def _attention(self, p, x):
        """One sequence ``(L, D)``, its norm first."""
        L = x.shape[0]
        H = self.n_heads
        nope, rope, dv = self.qk_nope_head_dim, self.qk_rope_head_dim, self.v_head_dim
        eps = self.rms_norm_eps
        with jax.named_scope("mla_attention"):
            x = rms_norm(x, p["attn_norm"], eps)
            q = rms_norm(x @ p["wq_a"], p["q_norm"], eps) @ p["wq_b"]
            q = q.reshape(L, H, nope + rope)
            kv_a = x @ p["wkv_a"]
            kv = rms_norm(kv_a[..., : self.kv_lora_rank], p["kv_norm"], eps) @ p["wkv_b"]
            kv = kv.reshape(L, H, nope + dv)
            q = jnp.concatenate(
                [q[..., :nope], rotary(q[None, ..., nope:], self.rope_theta)[0]],
                axis=-1,
            )
            # the one rotary key, shared by all heads
            k_rope = rotary(kv_a[None, ..., self.kv_lora_rank:], self.rope_theta)[0]
            k = jnp.concatenate(
                [kv[..., :nope], jnp.broadcast_to(k_rope[:, None, :], (L, H, rope))],
                axis=-1,
            )
            attend = flash_attention if self.attention_impl == "flash" else dense_attention
            mixed = attend(
                q, k, kv[..., nope:], scale=(nope + rope) ** -0.5, causal=True
            )
            return mixed.reshape(L, H * dv) @ p["wo"]

    def _experts(self, p, x):
        """All the tokens ``(S, L, D)`` together (the slots are sorted over
        them), their norm first: ``(given, token-slots a held expert)``."""
        S, L, D = x.shape
        tokens = rms_norm(x, p["ffn_norm"], self.rms_norm_eps).reshape(S * L, D)
        with jax.named_scope("shared_expert"):
            out = swiglu(tokens, p["shared_gate"], p["shared_up"], p["shared_down"])
        with jax.named_scope("expert_route"):
            chosen, weights = route(
                tokens, p["router"], p["router_bias"], self.experts_per_token,
                self.routed_scaling_factor,
            )
        partial, sizes = grouped_experts(
            tokens, chosen, weights, self.experts_held, self.n_routed_experts,
            p["experts_gate"], p["experts_up"], p["experts_down"],
        )
        return (out + partial).reshape(S, L, D), sizes

    def _layer(self, p, x):
        """``x (S, L, D) → (x, token-slots a held expert (E,))``. Where the
        model recomputes, the layer's input and its attention's output are
        what the backward pass keeps of it."""
        x = x + self._per_sequence(lambda x_s: self._attention(p, x_s), x)
        if "w_gate" in p:
            return x + self._per_sequence(
                lambda x_s: swiglu(
                    rms_norm(x_s, p["ffn_norm"], self.rms_norm_eps),
                    p["w_gate"], p["w_up"], p["w_down"],
                ), x,
            ), None
        given, sizes = self._recomputed(self._experts)(p, x)
        return x + given, sizes

    def _trunk(self, ids):
        """``ids (S, L)`` → the last layer's output, before its norm, and
        the expert layers' token-slot counts."""
        x = self.embed[ids]
        counts = []
        for stack in self.stacks:  # the leading dense layers, then the others
            x, sizes = jax.lax.scan(
                lambda x, p: self._layer(p, x), x, stack
            )
            if sizes is not None:
                counts.append(sizes)  # (layers, E)
        return x, counts

    def _over_vocabulary(self, norm, read, h, *rest):
        """``read(logits (L, V), ...)`` of each sequence in turn: one
        sequence's logits are the most that is held, made again in the
        backward pass where the model recomputes (``remat``)."""
        def one(head, norm, h_s, *rest_s):
            logits = rms_norm(h_s, norm, self.rms_norm_eps) @ head
            return read(logits.astype(jnp.float32), *rest_s)

        one = self._recomputed(one)
        return jax.lax.map(lambda args: one(self.head, norm, *args), (h,) + rest)

    def _bins(self, values):
        V = self.vocab_size
        return jnp.clip(jnp.floor(values * V), 0, V - 1).astype(jnp.int32)

    def _sequences(self, windows):
        B, L, F = windows.shape
        return jnp.swapaxes(self._bins(windows), 1, 2).reshape(B * F, L)

    # -- what the estimator and the train step call ---------------------------
    def __call__(self, x: jnp.ndarray, deterministic: bool = True) -> jnp.ndarray:
        B, L, F = x.shape
        h, _ = self._trunk(self._sequences(x))
        V = self.vocab_size
        centres = (jnp.arange(V, dtype=jnp.float32) + 0.5) / V
        expected = self._over_vocabulary(
            self.final_norm,
            lambda logits: jax.nn.softmax(logits, axis=-1) @ centres, h,
        )
        return jnp.swapaxes(expected.reshape(B, F, L), 1, 2)

    def loss_terms(self, x, targets):
        """Per sample ``(next, after, expert_tokens)``: the mean over tags and
        positions of the next row's cross-entropy; of the prediction module's
        on the row after it (the last position has no target); and the
        token-slots each held expert received, a row an expert layer (the
        prediction module's last)."""
        B, L, F = x.shape
        ids, ids_next = self._sequences(x), self._sequences(targets)
        h, counts = self._trunk(ids)
        per_sample = lambda a: jnp.mean(a.reshape(B, F, a.shape[-1]), axis=(1, 2))  # noqa: E731
        with jax.named_scope("token_loss"):
            nxt = self._over_vocabulary(
                self.final_norm, _cross_entropy, h, ids_next
            )
        with jax.named_scope("mtp_head"):
            p = self.mtp
            joined = jnp.concatenate(
                [rms_norm(h, p["h_norm"], self.rms_norm_eps),
                 rms_norm(self.embed[ids_next], p["e_norm"], self.rms_norm_eps)],
                axis=-1,
            )
            h_after, sizes = self._layer(p, joined @ p["proj"])
            after = self._over_vocabulary(
                p["out_norm"],
                lambda logits, ids: _cross_entropy(logits[:-1], ids[1:]),
                h_after, ids_next,
            )
        return per_sample(nxt), per_sample(after), jnp.concatenate(
            counts + [sizes[None]]
        )

    def sample_losses(self, x, targets, deterministic: bool = True):
        """``((B,) losses, counters)``: what ``make_loss_fn`` weights and
        sums, and what the fit sums beside it."""
        nxt, after, expert_tokens = self.loss_terms(x, targets)
        return nxt + MTP_LOSS_WEIGHT * after, {"expert_tokens": expert_tokens}


def _cross_entropy(logits, ids):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, ids[..., None], axis=-1)[..., 0]


@register_model_factory("moe_mla_decoder")
def moe_mla_decoder(
    n_features: int,
    n_features_out: Optional[int] = None,
    lookback_window: int = 64,
    vocab_size: int = 256,
    hidden_size: int = 64,
    n_layers: int = 2,
    n_dense_layers: int = 1,
    intermediate_size: int = 128,
    moe_intermediate_size: int = 32,
    n_routed_experts: int = 8,
    experts_held: Optional[Sequence[int]] = None,
    experts_per_token: int = 2,
    n_shared_experts: int = 1,
    routed_scaling_factor: float = 2.5,
    q_lora_rank: int = 48,
    kv_lora_rank: int = 32,
    n_heads: int = 4,
    qk_nope_head_dim: int = 16,
    qk_rope_head_dim: int = 8,
    v_head_dim: int = 16,
    rope_theta: float = 10000.0,
    rms_norm_eps: float = 1e-6,
    optimizer: str = "Adam",
    optimizer_kwargs: Optional[Dict[str, Any]] = None,
    attention_impl: str = "flash",
    remat: bool = False,
    **unknown: Any,
) -> ModelSpec:
    _reject_unknown("moe_mla_decoder", unknown)
    # a YAML machine config reads "1e-06" as text
    routed_scaling_factor, rope_theta, rms_norm_eps = (
        float(v) for v in (routed_scaling_factor, rope_theta, rms_norm_eps)
    )
    if n_features_out not in (None, n_features):
        raise ValueError(
            "moe_mla_decoder predicts each tag's own next rows: "
            f"{n_features_out} targets for {n_features} tags"
        )
    held = tuple(
        int(e) for e in (range(n_routed_experts) if experts_held is None else experts_held)
    )
    if len(set(held)) != len(held) or not all(0 <= e < n_routed_experts for e in held):
        raise ValueError(
            f"experts_held must be distinct ids below n_routed_experts "
            f"({n_routed_experts}); got {list(held)}"
        )
    if not 0 <= n_dense_layers <= n_layers:
        raise ValueError(
            f"n_dense_layers ({n_dense_layers}) must lie in 0..n_layers ({n_layers})"
        )
    if qk_rope_head_dim % 2:
        raise ValueError(f"qk_rope_head_dim must be even, got {qk_rope_head_dim}")
    if attention_impl not in ("dense", "flash"):
        raise ValueError(
            f"Unknown attention_impl {attention_impl!r}; use 'dense' or 'flash'"
        )
    config = {
        "n_features": n_features, "lookback_window": lookback_window,
        "vocab_size": vocab_size, "hidden_size": hidden_size,
        "n_layers": n_layers, "n_dense_layers": n_dense_layers,
        "intermediate_size": intermediate_size,
        "moe_intermediate_size": moe_intermediate_size,
        "n_routed_experts": n_routed_experts, "experts_held": list(held),
        "experts_per_token": experts_per_token,
        "n_shared_experts": n_shared_experts,
        "routed_scaling_factor": routed_scaling_factor,
        "q_lora_rank": q_lora_rank, "kv_lora_rank": kv_lora_rank,
        "n_heads": n_heads, "qk_nope_head_dim": qk_nope_head_dim,
        "qk_rope_head_dim": qk_rope_head_dim, "v_head_dim": v_head_dim,
        "rope_theta": rope_theta, "rms_norm_eps": rms_norm_eps,
        "optimizer": optimizer,
        "optimizer_kwargs": dict(optimizer_kwargs or {}),
        "loss": MODULE_LOSS, "attention_impl": attention_impl, "remat": remat,
    }
    module = MoEMLADecoder(
        vocab_size=vocab_size, hidden_size=hidden_size, n_layers=n_layers,
        n_dense_layers=n_dense_layers, intermediate_size=intermediate_size,
        moe_intermediate_size=moe_intermediate_size,
        n_routed_experts=n_routed_experts, experts_held=held,
        experts_per_token=experts_per_token, n_shared_experts=n_shared_experts,
        routed_scaling_factor=routed_scaling_factor, q_lora_rank=q_lora_rank,
        kv_lora_rank=kv_lora_rank, n_heads=n_heads,
        qk_nope_head_dim=qk_nope_head_dim, qk_rope_head_dim=qk_rope_head_dim,
        v_head_dim=v_head_dim, rope_theta=rope_theta, rms_norm_eps=rms_norm_eps,
        attention_impl=attention_impl, remat=remat,
    )
    return ModelSpec(
        module=module,
        optimizer=make_optimizer(optimizer, optimizer_kwargs),
        loss=MODULE_LOSS,
        input_kind="window",
        config=config,
    )
