"""Latent-attention / expert-layer decoder factory: a per-machine sequence
model over sensor values read as tokens, on the decoder scaffold
(``decoder.py``: tokens, layers, the expert layer's contract, the loss).

No reference counterpart. The block is DeepSeek-V3's (multi-head latent
attention, a router over all experts with a selection bias, one shared
expert, one multi-token-prediction module), published with these sizes as
JoyAI-LLM-Flash; the factory's defaults are small. The model predicts the
NEXT row's bin at every position and the prediction module the row after.

**Its scoring function**: ``s = sigmoid(W_r·x)`` in float32, the
``experts_per_token`` largest of ``s + b``, weights ``s`` over their sum over
all chosen, times ``routed_scaling_factor``. The selection bias ``b`` takes
no gradient (its update is a training recipe).

Its loss (``sample_losses``): cross-entropy at every position plus
``MTP_LOSS_WEIGHT`` times the prediction module's.

The parameter tree, in the scaffold's one order: ``embed``, ``head``,
``final_norm``, ``dense_layers`` and ``expert_layers`` (each kind's layers
stacked on a leading axis and scanned over), ``mtp``.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ...ops.attention import dense_attention
from ...ops.flash_attention import flash_attention
from ..register import register_model_factory
from ..train import MODULE_LOSS
from .decoder import (
    TokenDecoder, _cross_entropy, _draw_group, _draw_stack, per_sample, rms_norm,
    route,
)
from .feedforward import _reject_unknown
from .spec import ModelSpec, make_optimizer

# the prediction module's share of the loss (DeepSeek-V3's first-phase weight;
# the published config.json does not carry one)
MTP_LOSS_WEIGHT = 0.3


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


class MoEMLADecoder(TokenDecoder):
    """The scaffold with latent attention, sigmoid scores, leading dense
    layers, a shared expert and one prediction module."""

    n_layers: int
    n_dense_layers: int
    intermediate_size: int
    n_shared_experts: int
    routed_scaling_factor: float
    q_lora_rank: int
    kv_lora_rank: int
    n_heads: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float
    attention_impl: str

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
            S = self.moe_intermediate_size * self.n_shared_experts
            shapes.update(
                **self._expert_shapes(),
                router_bias=(self.n_routed_experts,),
                shared_gate=(D, S), shared_up=(D, S), shared_down=(S, D),
            )
        return shapes

    def setup(self):
        D = self.hidden_size
        self._token_ends()
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

    # -- its blocks -------------------------------------------------------------
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

    def _route(self, p, tokens):
        return route(
            tokens, p["router"], p["router_bias"], self.experts_per_token,
            self.routed_scaling_factor,
        )

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

    # -- what the train step calls ----------------------------------------------
    def loss_terms(self, x, targets):
        """Per sample ``(next, after, expert_tokens)``: the mean over tags and
        positions of the next row's cross-entropy; of the prediction module's
        on the row after it (the last position has no target); and the
        token-slots each held expert received, a row an expert layer (the
        prediction module's last)."""
        ids_next, h, counts, nxt = self._next_row(x, targets)
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
        B, _, F = x.shape
        return per_sample(nxt, B, F), per_sample(after, B, F), jnp.concatenate(
            counts + [sizes[None]]
        )

    def sample_losses(self, x, targets, deterministic: bool = True):
        """``((B,) losses, counters)``: what ``make_loss_fn`` weights and
        sums, and what the fit sums beside it."""
        nxt, after, expert_tokens = self.loss_terms(x, targets)
        return nxt + MTP_LOSS_WEIGHT * after, {"expert_tokens": expert_tokens}


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
