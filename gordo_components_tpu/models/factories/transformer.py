"""PatchTST transformer factory — the rebuild's new model kind.

No reference counterpart (the reference zoo stops at LSTM); this covers
BASELINE.json ``configs`` entry 5 ("Transformer/PatchTST anomaly head on a 10k-tag
plant"). Architecture follows PatchTST (Nie et al., ICLR 2023, public):
channel-independent patching — each tag's lookback window is split into
patches, embedded, and run through a shared transformer encoder; a linear
head per channel emits the reconstruction/forecast. TPU notes: patching is
a static gather; attention over ≤dozens of patches lowers to MXU matmuls
that XLA flash-fuses; for very long windows the sequence axis can shard
over a mesh with :func:`gordo_components_tpu.ops.attention.ring_attention`.

The ``patchtst`` kind plugs into the standard window estimators
(``input_kind="window"``), so ``PatchTSTAutoEncoder`` / ``PatchTSTForecast``
inherit the exact windowing contracts — and the fleet engine buckets
transformer machines like any other kind.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from ...ops.attention import dense_attention, ring_attention
from ...ops.flash_attention import flash_attention
from ..modules import activation, resolve_dtype
from ..register import register_model_factory
from .feedforward import _reject_unknown
from .spec import ModelSpec, make_optimizer


class MultiHeadSelfAttention(nn.Module):
    """q/k/v/out projections around a swappable attention core.

    ``attention_impl``:

    - ``"dense"`` — :func:`ops.attention.dense_attention` (XLA fuses it
      well for patch counts in the dozens);
    - ``"flash"`` — :func:`ops.flash_attention.flash_attention`: the
      Pallas blockwise kernel — scores stay in VMEM tiles, never O(P²)
      HBM; the single-device long-window path. Exact; parity pinned by
      tests/test_flash_attention.py;
    - ``"ring"`` — :func:`ops.attention.ring_attention`: the sequence
      (patch) axis shards over a 1-D mesh of all local devices and K/V
      blocks rotate via ICI neighbor hops (SURVEY.md §6.7 long-context
      path). Same parameters, exact same math — pinned by
      tests/test_transformer.py.
    - ``"ring_flash"`` — ring across devices with the Pallas blockwise
      kernel as each hop's local update: per-hop scores stay in VMEM too,
      so the sharded long-context path never materializes scores in HBM
      at any level. Exact; parity pinned alongside ring.

    Attention-weight dropout applies on the dense path (weights are
    materialized there); the flash and ring paths cannot drop weights they
    never materialize, so they train with residual dropout only.
    """

    d_model: int
    n_heads: int
    compute_dtype: Any
    attention_impl: str = "dense"
    ring_axis: str = "seq"
    dropout_rate: float = 0.0

    @nn.compact
    def __call__(self, x: jnp.ndarray, deterministic: bool = True) -> jnp.ndarray:
        if self.d_model % self.n_heads != 0:
            raise ValueError(
                f"d_model ({self.d_model}) must be divisible by n_heads "
                f"({self.n_heads})"
            )
        dtype = resolve_dtype(self.compute_dtype)
        head_dim = self.d_model // self.n_heads
        # fused q/k/v projection: one (d_model -> 3*d_model) matmul instead
        # of three d_model-wide ones — at the zoo's small d_model a single
        # 3x-wide contraction wastes fewer MXU tile lanes and gives XLA one
        # op to schedule. DenseGeneral's kernel init draws per output
        # feature with fan_in = d_model either way, so statistics match the
        # separate projections. DELIBERATE pre-1.0 param-tree change
        # (query/key/value -> qkv): artifacts serialized before this do not
        # load into the new tree — unlike the remat knob below (a runtime
        # toggle that must keep the tree stable), this is a versioned
        # architecture change with no compatibility shim.
        qkv = nn.DenseGeneral(
            (3, self.n_heads, head_dim), dtype=dtype, name="qkv"
        )(x)
        q, k, v = (qkv[..., 0, :, :], qkv[..., 1, :, :], qkv[..., 2, :, :])
        if self.attention_impl in ("ring", "ring_flash"):
            mesh = Mesh(np.asarray(jax.devices()), (self.ring_axis,))
            out = ring_attention(
                q, k, v, mesh=mesh, axis_name=self.ring_axis,
                block_impl="flash" if self.attention_impl == "ring_flash"
                else "dense",
            )
        elif self.attention_impl == "flash":
            out = flash_attention(q, k, v)
        elif self.attention_impl == "dense":
            if self.dropout_rate > 0.0 and not deterministic:
                # materialized-weights path so dropout can hit the weights
                # (same math as ops.attention.dense_attention)
                scale = head_dim**-0.5
                logits = jnp.einsum("...qhd,...khd->...hqk", q, k) * scale
                weights = jax.nn.softmax(logits, axis=-1)
                weights = nn.Dropout(self.dropout_rate)(
                    weights, deterministic=False
                )
                out = jnp.einsum("...hqk,...khd->...qhd", weights, v)
            else:
                out = dense_attention(q, k, v)
        else:
            raise ValueError(
                f"Unknown attention_impl {self.attention_impl!r}; "
                "use 'dense', 'flash', 'ring', or 'ring_flash'"
            )
        return nn.DenseGeneral(
            self.d_model, axis=(-2, -1), dtype=dtype, name="out"
        )(out)


class TransformerEncoderLayer(nn.Module):
    d_model: int
    n_heads: int
    ff_dim: int
    dropout: float
    compute_dtype: Any
    attention_impl: str = "dense"

    @nn.compact
    def __call__(self, x: jnp.ndarray, deterministic: bool = True) -> jnp.ndarray:
        dtype = resolve_dtype(self.compute_dtype)
        h = nn.LayerNorm(dtype=dtype)(x)
        h = MultiHeadSelfAttention(
            d_model=self.d_model,
            n_heads=self.n_heads,
            compute_dtype=self.compute_dtype,
            attention_impl=self.attention_impl,
            dropout_rate=self.dropout,
        )(h, deterministic=deterministic)
        x = x + nn.Dropout(self.dropout)(h, deterministic=deterministic)
        h = nn.LayerNorm(dtype=dtype)(x)
        h = nn.Dense(self.ff_dim, dtype=dtype)(h)
        h = nn.gelu(h)
        h = nn.Dense(self.d_model, dtype=dtype)(h)
        return x + nn.Dropout(self.dropout)(h, deterministic=deterministic)


class PatchTSTModule(nn.Module):
    """``(batch, L, F) → (batch, F_out)`` channel-independent PatchTST."""

    n_features_out: int
    patch_length: int
    stride: int
    d_model: int
    n_heads: int
    n_layers: int
    ff_dim: int
    dropout: float = 0.0
    out_func: str = "linear"
    compute_dtype: Any = "float32"
    attention_impl: str = "dense"
    # rematerialize encoder layers on the backward pass: activations are
    # recomputed instead of stored, trading ~1 extra forward of FLOPs for
    # O(n_layers) less HBM — the standard lever for plant-scale configs
    # (10k tags x long windows) whose activations otherwise exceed HBM
    remat: bool = False

    @nn.compact
    def __call__(self, x: jnp.ndarray, deterministic: bool = True) -> jnp.ndarray:
        batch, window, n_features = x.shape
        if window < self.patch_length:
            raise ValueError(
                f"PatchTST input window ({window}) is shorter than "
                f"patch_length ({self.patch_length}); set the estimator's "
                "lookback_window >= patch_length"
            )
        dtype = resolve_dtype(self.compute_dtype)
        channels = jnp.swapaxes(x.astype(dtype), 1, 2)  # (B, F, L)
        starts = np.arange(0, window - self.patch_length + 1, self.stride)
        # patching as P static contiguous slices + stack, not an
        # advanced-index gather: slice/concat is XLA:TPU's fast layout
        # path, while a (P, patch_len) index-matrix gather addresses
        # every element through the scalar core — this runs every
        # training step on the (B, F, L) tensor, so the lowering matters
        patches = jnp.stack(
            [
                jax.lax.slice_in_dim(channels, s, s + self.patch_length, axis=2)
                for s in starts
            ],
            axis=2,
        )  # (B, F, P, patch_len)
        n_patches = len(starts)
        h = patches.reshape(batch * n_features, n_patches, self.patch_length)
        h = nn.Dense(self.d_model, dtype=dtype)(h)
        pos = self.param(
            "pos_embedding",
            nn.initializers.normal(0.02),
            (n_patches, self.d_model),
        )
        h = h + pos.astype(dtype)
        h = nn.Dropout(self.dropout)(h, deterministic=deterministic)
        layer_cls = (
            nn.remat(TransformerEncoderLayer, static_argnums=(2,))
            if self.remat
            else TransformerEncoderLayer
        )
        for i in range(self.n_layers):
            # explicit names pin the param tree: nn.remat renames the class
            # (Checkpoint...), and auto-scoping would give remat=True a
            # different tree than remat=False — breaking artifact loads
            # that flip the flag (remat is a memory knob, not a new model)
            h = layer_cls(
                d_model=self.d_model,
                n_heads=self.n_heads,
                ff_dim=self.ff_dim,
                dropout=self.dropout,
                compute_dtype=self.compute_dtype,
                attention_impl=self.attention_impl,
                name=f"TransformerEncoderLayer_{i}",
            )(h, deterministic)
        h = nn.LayerNorm(dtype=dtype)(h)
        flat = h.reshape(batch, n_features, n_patches * self.d_model)
        out = nn.Dense(1, dtype=dtype)(flat)[..., 0]  # per-channel head (B, F)
        if self.n_features_out != n_features:
            out = nn.Dense(self.n_features_out, dtype=dtype)(out)
        return activation(self.out_func)(out).astype(jnp.float32)


@register_model_factory("patchtst")
def patchtst(
    n_features: int,
    n_features_out: Optional[int] = None,
    lookback_window: int = 32,
    patch_length: int = 8,
    stride: Optional[int] = None,
    d_model: int = 64,
    n_heads: int = 4,
    n_layers: int = 2,
    ff_dim: Optional[int] = None,
    dropout: float = 0.0,
    out_func: str = "linear",
    optimizer: str = "Adam",
    optimizer_kwargs: Optional[Dict[str, Any]] = None,
    loss: str = "mse",
    compute_dtype: str = "float32",
    attention_impl: str = "dense",
    remat: bool = False,
    **unknown: Any,
) -> ModelSpec:
    _reject_unknown("patchtst", unknown)
    if lookback_window < patch_length:
        raise ValueError(
            f"lookback_window ({lookback_window}) must be >= patch_length "
            f"({patch_length})"
        )
    stride = stride or max(1, patch_length // 2)
    ff_dim = ff_dim or 2 * d_model
    n_features_out = n_features_out or n_features
    if attention_impl not in ("dense", "flash", "ring", "ring_flash"):
        raise ValueError(
            f"Unknown attention_impl {attention_impl!r}; "
            "use 'dense', 'flash', 'ring', or 'ring_flash'"
        )
    if d_model % n_heads != 0:
        raise ValueError(
            f"d_model ({d_model}) must be divisible by n_heads ({n_heads})"
        )
    if attention_impl in ("ring", "ring_flash"):
        n_patches = (lookback_window - patch_length) // stride + 1
        n_devices = jax.device_count()
        if n_patches % n_devices != 0:
            raise ValueError(
                f"attention_impl={attention_impl!r} shards the patch axis "
                f"over {n_devices} device(s), but {n_patches} patches do "
                "not divide evenly; pick lookback_window/patch_length/"
                "stride so (lookback_window - patch_length)//stride + 1 is "
                "a multiple of the device count"
            )
    module = PatchTSTModule(
        n_features_out=n_features_out,
        patch_length=patch_length,
        stride=stride,
        d_model=d_model,
        n_heads=n_heads,
        n_layers=n_layers,
        ff_dim=ff_dim,
        dropout=dropout,
        out_func=out_func,
        compute_dtype=compute_dtype,
        attention_impl=attention_impl,
        remat=remat,
    )
    config = {
        "n_features": n_features,
        "n_features_out": n_features_out,
        "lookback_window": lookback_window,
        "patch_length": patch_length,
        "stride": stride,
        "d_model": d_model,
        "n_heads": n_heads,
        "n_layers": n_layers,
        "ff_dim": ff_dim,
        "dropout": dropout,
        "out_func": out_func,
        "optimizer": optimizer,
        "optimizer_kwargs": dict(optimizer_kwargs or {}),
        "loss": loss,
        "compute_dtype": compute_dtype,
        "attention_impl": attention_impl,
        "remat": remat,
    }
    return ModelSpec(
        module=module,
        optimizer=make_optimizer(optimizer, optimizer_kwargs),
        loss=loss,
        input_kind="window",
        config=config,
    )
