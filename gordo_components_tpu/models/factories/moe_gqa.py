"""Grouped-query-attention / expert-layer decoder factory: a per-machine
sequence model over sensor values read as tokens, on the decoder scaffold
(``decoder.py``: tokens, layers, the expert layer's contract, the loss).

No reference counterpart. The block is the one published as
Mellum2-12B-A2.5B: every layer ``h = x + Attn(norm(x)); y = h +
MoE(norm(h))``, no leading dense layer, no shared expert, no prediction
module; the factory's defaults are small.

**Its attention**: ``n_heads`` query heads over ``n_kv_heads`` key/value heads
of ``head_dim`` (query head ``i`` reads key head ``i // group``), no bias,
rotary embedding on queries and keys in pairs ``(i, i + head_dim/2)``,
causal, and by the layer's kind (``layer_types``) either full or limited to
the last ``sliding_window`` positions. Both through the banded Pallas kernel
(``ops/flash_attention.py``): key heads addressed, not repeated; key blocks
the window hides neither computed nor fetched, forward or backward.
``attention_operand_dtype`` is the model's word on the kernel's precision:
the type its operands go to the MXU in (``"bfloat16"``: what an XLA dot of
float32 operands does on the TPU at JAX's default precision, one pass with
float32 accumulation; ``None``: float32 as they are). The
rotary frequencies are the layer kind's own (``rope_parameters``):
``default`` (``theta^(-2i/d)``) or ``yarn`` (those and those divided by
``factor``, blended by a linear ramp between two correction dimensions,
cosine and sine times ``attention_factor``).

**Its scoring function**: a softmax over the router's logits in float32, the
``experts_per_token`` largest, weights renormalised over the chosen; no
selection bias, no scaling.

**The layer pattern is data**: ``layer_types`` is cut into its shortest
period and the period into runs of one kind. The parameter tree, in the
scaffold's one order: ``embed``, ``head``, ``final_norm``, ``periods``: a
run's layers stacked on a leading axis (a scanned stack), the periods stacked
in front of that (scanned too), so the executable holds one layer body a run
of the period however deep the model is. Period ``i`` is drawn from
``split(key, P)[i]``, its run ``j`` from ``fold_in(that, j)``.

Its loss (``sample_losses``) is the next row's cross-entropy; beside the
experts' token-slots it counts the score tiles its attention kernels visit
(``attention_key_blocks``, a layer kind: what the band's grids compute,
forward and backward, beside what causal attention over the whole sequence
would).
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...ops.flash_attention import flash_attention, visited_blocks
from ..register import register_model_factory
from ..train import MODULE_LOSS
from .decoder import TokenDecoder, _draw_stack, per_sample, rms_norm, route
from .feedforward import _reject_unknown
from .spec import ModelSpec, make_optimizer

SLIDING, FULL = "sliding_attention", "full_attention"
# the kernel's tile: a long sequence is walked in blocks of this many rows
_BLOCK = 512


def rotary_frequencies(head_dim: int, rope: Dict[str, Any]) -> Tuple[np.ndarray, float]:
    """``(inv_freq (head_dim / 2,), what cosine and sine are multiplied
    by)`` for one layer kind's ``rope_parameters`` entry."""
    theta = float(rope["rope_theta"])
    inv = theta ** (-np.arange(0, head_dim, 2, dtype=np.float64) / head_dim)
    kind = rope.get("rope_type", "default")
    if kind == "default":
        return inv, 1.0
    if kind != "yarn":
        raise ValueError(f"Unknown rope_type {kind!r}; use 'default' or 'yarn'")
    factor, reach = float(rope["factor"]), float(rope["original_max_position_embeddings"])

    def correction_dim(rotations: float) -> float:
        # the dimension whose wavelength fits ``rotations`` times into ``reach``
        return head_dim * math.log(reach / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(float(rope["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(rope["beta_slow"]))), head_dim - 1)
    ramp = np.clip(
        (np.arange(head_dim // 2, dtype=np.float64) - low) / max(high - low, 0.001),
        0.0, 1.0,
    )
    # below ``low`` the published frequencies, above ``high`` those of the
    # stretched positions, a linear blend between
    blended = inv / factor * ramp + inv * (1.0 - ramp)
    return blended, float(rope.get("attention_factor", 0.1 * math.log(factor) + 1.0))


def rotary_halves(x, inv_freq: np.ndarray, factor: float):
    """Rotary embedding over axis 0 (positions) of ``(L, heads, head_dim)``:
    dimension ``i`` turns with dimension ``i + head_dim / 2``."""
    length, half = x.shape[0], x.shape[-1] // 2
    angle = jnp.arange(length, dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq, jnp.float32)
    cos = (factor * jnp.cos(angle))[:, None, :]
    sin = (factor * jnp.sin(angle))[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def period_runs(layer_types: Sequence[str]) -> Tuple[int, List[Tuple[str, int]]]:
    """``(periods, [(kind, layers), ...])``: the shortest period of the
    pattern and its runs of one kind."""
    n = len(layer_types)
    length = next(
        p for p in range(1, n + 1)
        if n % p == 0 and list(layer_types) == list(layer_types[:p]) * (n // p)
    )
    runs: List[Tuple[str, int]] = []
    for kind in layer_types[:length]:
        if runs and runs[-1][0] == kind:
            runs[-1] = (kind, runs[-1][1] + 1)
        else:
            runs.append((kind, 1))
    return n // length, runs


def by_layer(counts):
    """What periods counted, ``(periods, layers of a period, ...)`` a leaf,
    as ``(layers, ...)`` in the layers' order."""
    return jax.tree_util.tree_map(lambda c: c.reshape(-1, c.shape[-1]), counts)


def _draw_periods(key, shapes, n_periods: int, runs):
    return jax.lax.map(
        lambda k: {
            f"{j}_{kind}": _draw_stack(jax.random.fold_in(k, j), shapes, n)
            for j, (kind, n) in enumerate(runs)
        },
        jax.random.split(key, n_periods),
    )


class MoEGQADecoder(TokenDecoder):
    """The scaffold with grouped-query attention, window and full layers
    mixed by period, and softmax scores."""

    layer_types: Tuple[str, ...]
    sliding_window: int
    # ``rope_parameters`` as ((kind, ((key, value), ...)), ...): the module is
    # part of the fleet program's memo key, so every field is hashable; a
    # layer kind it leaves out is not turned
    rope_parameters: Tuple[Tuple[str, Tuple[Tuple[str, Any], ...]], ...]
    n_heads: int
    n_kv_heads: int
    head_dim: int
    attention_operand_dtype: Optional[str] = None

    # a layer's attention block runs under ``<_SCOPE>/sliding`` or ``/full``
    _SCOPE = "gqa_attention"

    def _attention_shapes(self) -> Dict[str, Tuple[int, ...]]:
        D, d = self.hidden_size, self.head_dim
        return {
            "attn_norm": (D,), "wq": (D, self.n_heads * d),
            "wk": (D, self.n_kv_heads * d), "wv": (D, self.n_kv_heads * d),
            "wo": (self.n_heads * d, D), "ffn_norm": (D,),
        }

    def _layer_shapes(self) -> Dict[str, Tuple[int, ...]]:
        return {**self._attention_shapes(), **self._expert_shapes()}

    def setup(self):
        self._token_ends()
        n_periods, runs = period_runs(self.layer_types)
        self.periods = self.param(
            "periods", _draw_periods, self._layer_shapes(), n_periods, runs
        )

    def _blocks(self, length: int) -> int:
        return min(_BLOCK, -(-length // 128) * 128) if length > 128 else -(-length // 8) * 8

    def _heads(self, p, name: str, x, n_heads: int, kind: str):
        """Queries (``name`` "q") or keys ("k") ``(L, n_heads, head_dim)`` of
        the normed sequence ``x``: each head normed where the layer holds
        ``<name>_norm``, turned where the layer kind has rotary parameters."""
        d = self.head_dim
        heads = (x @ p[f"w{name}"]).reshape(x.shape[0], n_heads, d)
        if f"{name}_norm" in p:
            with jax.named_scope("qk_norm"):
                heads = rms_norm(heads, p[f"{name}_norm"], self.rms_norm_eps)
        rope = dict(self.rope_parameters).get(kind)
        if rope is None:
            return heads
        return rotary_halves(heads, *rotary_frequencies(d, dict(rope)))

    def _attention(self, p, x, kind: str):
        """One sequence ``(L, D)``, its norm first; the heads' output gated
        by ``sigmoid(x W_g)`` where the layer holds ``attn_gate``."""
        L, d = x.shape[0], self.head_dim
        block = self._blocks(L)
        with jax.named_scope(f"{self._SCOPE}/{kind.split('_')[0]}"):
            x = rms_norm(x, p["attn_norm"], self.rms_norm_eps)
            q = self._heads(p, "q", x, self.n_heads, kind)
            k = self._heads(p, "k", x, self.n_kv_heads, kind)
            v = (x @ p["wv"]).reshape(L, self.n_kv_heads, d)
            mixed = flash_attention(
                q, k, v, scale=d ** -0.5, block_q=block, block_k=block, causal=True,
                window=self.sliding_window if kind == SLIDING else None,
                operand_dtype=self.attention_operand_dtype,
            ).reshape(L, self.n_heads * d)
            if "attn_gate" in p:
                with jax.named_scope("attention_gate"):
                    mixed = mixed * jax.nn.sigmoid(x @ p["attn_gate"])
            return mixed @ p["wo"]

    def _route(self, p, tokens):
        return route(
            tokens, p["router"], None, self.experts_per_token, 1.0, "softmax"
        )

    def _through(self, x, periods, runs):
        """``x`` through stacked periods (``_draw_periods``' tree, cut into
        ``runs``): ``(x, what the layers counted, (periods, layers of a
        period, ...); None where no layer counts)``."""
        def period(x, stacks):
            counted = []
            for j, (kind, _) in enumerate(runs):
                x, sizes = jax.lax.scan(
                    lambda x, p: self._layer(p, x, kind=kind), x, stacks[f"{j}_{kind}"]
                )
                counted.append(sizes)
            return x, jax.tree_util.tree_map(lambda *c: jnp.concatenate(c), *counted)

        return jax.lax.scan(period, x, periods)

    def _trunk(self, ids):
        """``ids (S, L)`` → the last layer's output, before its norm, and the
        layers' token-slot counts ``(layers, E)``, in the layers' order."""
        x, counts = self._through(
            self.embed[ids], self.periods, period_runs(self.layer_types)[1]
        )
        return x, by_layer(counts)

    def attention_key_blocks(self, length: int, n_sequences: int):
        """``(2, 2, 2)``: a layer kind (sliding, full) x (forward, backward)
        x (score tiles the kernels' grids visit, tiles causal attention over
        the whole sequence would visit), over all heads, layers of the kind
        and ``n_sequences``."""
        block = self._blocks(length)
        whole = visited_blocks(length, block, block, None)
        out = np.zeros((2, 2, 2), np.int32)
        for row, kind in enumerate((SLIDING, FULL)):
            window = self.sliding_window if kind == SLIDING else None
            calls = self.layer_types.count(kind) * self.n_heads * n_sequences
            visited = visited_blocks(length, block, block, window)
            out[row] = calls * np.stack([visited, whole], axis=1)
        return jnp.asarray(out)

    def sample_losses(self, x, targets, deterministic: bool = True):
        """``((B,) losses, counters)``: what ``make_loss_fn`` weights and
        sums, and what the fit sums beside it."""
        B, L, F = x.shape
        _, _, counts, nxt = self._next_row(x, targets)
        return per_sample(nxt, B, F), {
            "expert_tokens": counts,
            "attention_key_blocks": self.attention_key_blocks(L, B * F),
        }


@register_model_factory("moe_gqa_decoder")
def moe_gqa_decoder(
    n_features: int,
    n_features_out: Optional[int] = None,
    lookback_window: int = 64,
    vocab_size: int = 256,
    hidden_size: int = 64,
    layer_types: Sequence[str] = (SLIDING, FULL),
    sliding_window: int = 16,
    rope_parameters: Optional[Dict[str, Dict[str, Any]]] = None,
    n_heads: int = 4,
    n_kv_heads: int = 2,
    head_dim: int = 16,
    moe_intermediate_size: int = 32,
    n_routed_experts: int = 8,
    experts_held: Optional[Sequence[int]] = None,
    experts_per_token: int = 2,
    rms_norm_eps: float = 1e-6,
    attention_operand_dtype: Optional[str] = None,
    optimizer: str = "Adam",
    optimizer_kwargs: Optional[Dict[str, Any]] = None,
    remat: bool = False,
    **unknown: Any,
) -> ModelSpec:
    _reject_unknown("moe_gqa_decoder", unknown)
    default_rope = {"rope_type": "default", "rope_theta": 10000.0}
    ropes = {
        kind: dict((rope_parameters or {}).get(kind, default_rope))
        for kind in (SLIDING, FULL)
    }
    fields, config = gqa_arguments(
        "moe_gqa_decoder", n_features, n_features_out, lookback_window, vocab_size,
        hidden_size, layer_types, sliding_window, ropes, n_heads, n_kv_heads,
        head_dim, moe_intermediate_size, n_routed_experts, experts_held,
        experts_per_token, rms_norm_eps, attention_operand_dtype,
    )
    return decoder_spec(
        MoEGQADecoder(**fields, remat=remat), config, optimizer, optimizer_kwargs
    )


def gqa_arguments(
    name: str, n_features: int, n_features_out: Optional[int], lookback_window: int,
    vocab_size: int, hidden_size: int, layer_types: Sequence[str],
    sliding_window: int, ropes: Dict[str, Dict[str, Any]], n_heads: int,
    n_kv_heads: int, head_dim: int, moe_intermediate_size: int,
    n_routed_experts: int, experts_held: Optional[Sequence[int]],
    experts_per_token: int, rms_norm_eps: float,
    attention_operand_dtype: Optional[str],
) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """``(the module's fields, the spec's config)`` of the arguments every
    grouped-query kind takes, checked: the factory ``name`` refuses what no
    module of it can be."""
    rms_norm_eps = float(rms_norm_eps)  # a YAML machine config reads "1e-06" as text
    if n_features_out not in (None, n_features):
        raise ValueError(
            f"{name} predicts each tag's own next rows: "
            f"{n_features_out} targets for {n_features} tags"
        )
    layer_types = tuple(str(kind) for kind in layer_types)
    if not layer_types or set(layer_types) - {SLIDING, FULL}:
        raise ValueError(
            f"layer_types must name {SLIDING!r} or {FULL!r} for every layer; "
            f"got {list(layer_types)}"
        )
    held = tuple(
        int(e) for e in (range(n_routed_experts) if experts_held is None else experts_held)
    )
    if len(set(held)) != len(held) or not all(0 <= e < n_routed_experts for e in held):
        raise ValueError(
            f"experts_held must be distinct ids below n_routed_experts "
            f"({n_routed_experts}); got {list(held)}"
        )
    if n_heads % n_kv_heads or head_dim % 2 or sliding_window < 1:
        raise ValueError(
            f"n_kv_heads ({n_kv_heads}) must divide n_heads ({n_heads}), head_dim "
            f"({head_dim}) be even and sliding_window ({sliding_window}) at least 1"
        )
    if attention_operand_dtype is not None:
        attention_operand_dtype = jnp.dtype(attention_operand_dtype).name
    for rope in ropes.values():
        rotary_frequencies(head_dim, rope)  # an unknown rope_type is refused here
    config = {
        "n_features": n_features, "lookback_window": lookback_window,
        "vocab_size": vocab_size, "hidden_size": hidden_size,
        "layer_types": list(layer_types), "sliding_window": sliding_window,
        "rope_parameters": ropes, "n_heads": n_heads, "n_kv_heads": n_kv_heads,
        "head_dim": head_dim, "moe_intermediate_size": moe_intermediate_size,
        "n_routed_experts": n_routed_experts, "experts_held": list(held),
        "experts_per_token": experts_per_token, "rms_norm_eps": rms_norm_eps,
        "attention_operand_dtype": attention_operand_dtype,
    }
    fields = dict(
        vocab_size=vocab_size, hidden_size=hidden_size,
        moe_intermediate_size=moe_intermediate_size,
        n_routed_experts=n_routed_experts, experts_held=held,
        experts_per_token=experts_per_token, rms_norm_eps=rms_norm_eps,
        layer_types=layer_types, sliding_window=sliding_window,
        rope_parameters=tuple(
            (kind, tuple(sorted(rope.items()))) for kind, rope in sorted(ropes.items())
        ),
        n_heads=n_heads, n_kv_heads=n_kv_heads, head_dim=head_dim,
        attention_operand_dtype=attention_operand_dtype,
    )
    return fields, config


def decoder_spec(module, config: Dict[str, Any], optimizer: str,
                 optimizer_kwargs: Optional[Dict[str, Any]]) -> ModelSpec:
    """The spec of a decoder ``module`` that brings its own loss."""
    return ModelSpec(
        module=module,
        optimizer=make_optimizer(optimizer, optimizer_kwargs),
        loss=MODULE_LOSS,
        input_kind="window",
        config={
            **config, "optimizer": optimizer,
            "optimizer_kwargs": dict(optimizer_kwargs or {}),
            "loss": MODULE_LOSS, "remat": module.remat,
        },
    )
