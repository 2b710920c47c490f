"""Gated grouped-query-attention / expert-layer decoder factory: a per-machine
sequence model over sensor values read as tokens, on ``moe_gqa.py``'s
attention and period stacks and the decoder scaffold (``decoder.py``).

No reference counterpart. The block is the one published as Arcee's Trinity
(``model_type`` ``afmoe``); the factory's defaults are small. Beside
``moe_gqa.py``'s, every layer holds (and so runs, by the scaffold's and the
attention block's rule "where the layer's parameters hold it"):

* an output gate: the heads' output times ``sigmoid(x W_g)`` (``attn_gate``,
  ``x`` the normed input), then ``W_o``;
* per-head RMS norms of queries and keys over ``head_dim`` (``q_norm``,
  ``k_norm``), before rotary;
* a norm of each sub-block's output before its residual (``post_attn_norm``,
  ``post_ffn_norm``): ``h += norm(Attn(norm(h))); h += norm(FFN(norm(h)))``.

Rotary embedding turns a layer kind only where ``rope_parameters`` names it:
by default the window layers alone, so the full layers carry no position
signal. The first ``n_dense_layers`` of ``layer_types`` are dense (a SwiGLU
of ``intermediate_size``), the others expert layers: sigmoid scores (the
published router's) over ALL experts, the ``experts_per_token`` largest of
scores plus a selection bias (zero, no gradient: its update is a training
recipe), weights the chosen scores over their sum times ``route_scale``, and
``n_shared_experts`` shared experts beside them. With ``mup_enabled`` the
embedding is multiplied by ``sqrt(hidden_size)``.

The parameter tree, in the scaffold's one order: ``embed``, ``head``,
``final_norm``, ``dense_layers`` (where there are any) and ``periods``, each
``_draw_periods``' tree of its part of the pattern. Its loss counts, beside
the held experts' token-slots and the attention tiles, the token-slots the
router hands each of ALL experts (``routed_tokens``, an expert layer a row).
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Sequence, Tuple

import jax.numpy as jnp

from ..register import register_model_factory
from .decoder import per_sample, route
from .feedforward import _reject_unknown
from .moe_gqa import (
    FULL, SLIDING, MoEGQADecoder, _draw_periods, by_layer, decoder_spec,
    gqa_arguments, period_runs,
)
from .spec import ModelSpec


class AfMoEDecoder(MoEGQADecoder):
    """``MoEGQADecoder`` with gated, query/key-normed attention, sandwich
    norms, leading dense layers and sigmoid scores beside shared experts."""

    n_dense_layers: int = 0
    intermediate_size: int = 0
    n_shared_experts: int = 0
    route_scale: float = 1.0
    mup_enabled: bool = False

    _SCOPE = "afmoe_attention"

    def _attention_shapes(self) -> Dict[str, Tuple[int, ...]]:
        D, d = self.hidden_size, self.head_dim
        return {
            **super()._attention_shapes(), "attn_gate": (D, self.n_heads * d),
            "q_norm": (d,), "k_norm": (d,),
            "post_attn_norm": (D,), "post_ffn_norm": (D,),
        }

    def _layer_shapes(self, dense: bool = False) -> Dict[str, Tuple[int, ...]]:
        D = self.hidden_size
        if dense:
            I = self.intermediate_size
            return {**self._attention_shapes(),
                    "w_gate": (D, I), "w_up": (D, I), "w_down": (I, D)}
        shapes = {**super()._layer_shapes(), "router_bias": (self.n_routed_experts,)}
        if self.n_shared_experts:
            S = self.moe_intermediate_size * self.n_shared_experts
            shapes.update(shared_gate=(D, S), shared_up=(D, S), shared_down=(S, D))
        return shapes

    def _parts(self) -> Tuple[Tuple[str, ...], Tuple[str, ...]]:
        """The layer kinds of the dense layers and of the expert layers."""
        return self.layer_types[: self.n_dense_layers], self.layer_types[self.n_dense_layers:]

    def setup(self):
        self._token_ends()
        dense, expert = self._parts()
        if dense:
            self.dense_layers = self.param(
                "dense_layers", _draw_periods, self._layer_shapes(dense=True),
                *period_runs(dense),
            )
        self.periods = self.param(
            "periods", _draw_periods, self._layer_shapes(), *period_runs(expert)
        )

    def _route(self, p, tokens):
        return route(
            tokens, p["router"], p["router_bias"], self.experts_per_token,
            self.route_scale, "sigmoid",
        )

    def _counted(self, chosen, sizes):
        # ``expert_tokens`` (the held experts' columns of ``routed_tokens``)
        # is the counter every decoder kind lays on the slice's span
        return {
            "expert_tokens": sizes,
            "routed_tokens": jnp.bincount(
                chosen.reshape(-1), length=self.n_routed_experts
            ).astype(jnp.int32),
        }

    def _trunk(self, ids):
        """``ids (S, L)`` → the last layer's output, before its norm, and the
        expert layers' counts (``(layers, E)`` a counter), in their order."""
        x = self.embed[ids]
        if self.mup_enabled:
            x = x * math.sqrt(self.hidden_size)
        dense, expert = self._parts()
        if dense:
            x, _ = self._through(x, self.dense_layers, period_runs(dense)[1])
        x, counts = self._through(x, self.periods, period_runs(expert)[1])
        return x, by_layer(counts)

    def sample_losses(self, x, targets, deterministic: bool = True):
        """``((B,) losses, counters)``: what ``make_loss_fn`` weights and
        sums, and what the fit sums beside it."""
        B, L, F = x.shape
        _, _, counts, nxt = self._next_row(x, targets)
        return per_sample(nxt, B, F), {
            **counts, "attention_key_blocks": self.attention_key_blocks(L, B * F),
        }


@register_model_factory("afmoe_decoder")
def afmoe_decoder(
    n_features: int,
    n_features_out: Optional[int] = None,
    lookback_window: int = 64,
    vocab_size: int = 256,
    hidden_size: int = 64,
    layer_types: Sequence[str] = (SLIDING, SLIDING, FULL),
    n_dense_layers: int = 1,
    intermediate_size: int = 96,
    sliding_window: int = 16,
    rope_parameters: Optional[Dict[str, Dict[str, Any]]] = None,
    n_heads: int = 4,
    n_kv_heads: int = 2,
    head_dim: int = 16,
    moe_intermediate_size: int = 32,
    n_routed_experts: int = 8,
    experts_held: Optional[Sequence[int]] = None,
    experts_per_token: int = 2,
    n_shared_experts: int = 1,
    route_scale: float = 2.826,
    mup_enabled: bool = True,
    rms_norm_eps: float = 1e-5,
    attention_operand_dtype: Optional[str] = None,
    optimizer: str = "Adam",
    optimizer_kwargs: Optional[Dict[str, Any]] = None,
    remat: bool = False,
    **unknown: Any,
) -> ModelSpec:
    _reject_unknown("afmoe_decoder", unknown)
    if not 0 <= n_dense_layers < len(layer_types):
        raise ValueError(
            f"n_dense_layers ({n_dense_layers}) must leave an expert layer of "
            f"the {len(layer_types)} layer_types"
        )
    # the published block turns the window layers alone
    ropes = {
        kind: dict(rope) for kind, rope in (
            rope_parameters or {SLIDING: {"rope_type": "default", "rope_theta": 10000.0}}
        ).items()
    }
    fields, config = gqa_arguments(
        "afmoe_decoder", n_features, n_features_out, lookback_window, vocab_size,
        hidden_size, layer_types, sliding_window, ropes, n_heads, n_kv_heads,
        head_dim, moe_intermediate_size, n_routed_experts, experts_held,
        experts_per_token, rms_norm_eps, attention_operand_dtype,
    )
    more = dict(
        n_dense_layers=int(n_dense_layers), intermediate_size=int(intermediate_size),
        n_shared_experts=int(n_shared_experts), route_scale=float(route_scale),
        mup_enabled=bool(mup_enabled),
    )
    return decoder_spec(
        AfMoEDecoder(**fields, **more, remat=remat), {**config, **more},
        optimizer, optimizer_kwargs,
    )
