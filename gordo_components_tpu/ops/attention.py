"""Attention primitives: dense scaled-dot-product and ring attention.

The reference has no attention anywhere (its models are MLP/LSTM
autoencoders — SURVEY.md §6.7), but the rebuild's Transformer/PatchTST
model kind (BASELINE.json ``configs`` entry 5) needs it, and long lookback windows on
10k-tag plants motivate sequence sharding.

``ring_attention`` is the ICI-native long-context path: Q stays sharded
over the mesh's sequence axis while K/V blocks rotate around the ring via
``lax.ppermute``; each step folds one block into a numerically-stable
running softmax (flash-attention style: running max ``m``, normalizer
``l``, accumulator ``acc``). After ``n_devices`` hops every query block has
attended to every key block — memory per device is O(seq/n_devices), and
the only communication is neighbor-to-neighbor ring hops that map exactly
onto TPU ICI links. Exact (not approximate): pinned against dense attention
in tests/test_transformer.py.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec


def dense_attention(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
    scale: Optional[float] = None, causal: bool = False,
) -> jnp.ndarray:
    """Reference scaled-dot-product attention.

    Shapes: q/k ``(..., seq, heads, head_dim)``, v ``(..., seq, heads,
    value_dim)`` → ``(..., seq, heads, value_dim)`` (the flax convention,
    so modules can swap implementations). ``causal``: a query sees the keys
    up to its own position.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("...qhd,...khd->...hqk", q, k) * scale
    if causal:
        seq = q.shape[-3]
        logits = jnp.where(
            jnp.tril(jnp.ones((seq, seq), bool)), logits, -jnp.inf
        )
    weights = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("...hqk,...khd->...qhd", weights, v)


def _ring_attention_sharded(
    q, k, v, *, axis_name: str, scale: float, block_impl: str = "dense"
):
    """Per-shard body: q/k/v are this device's sequence block
    ``(batch, block, heads, head_dim)``.

    ``block_impl`` picks the per-hop update:

    - ``"dense"`` — einsum scores for the local (q_block, k_block) pair
      (materialized per hop, O(block²) HBM);
    - ``"flash"`` — the Pallas blockwise kernel
      (:func:`~gordo_components_tpu.ops.flash_attention.flash_block_with_lse`):
      the hop's scores stay in VMEM tiles and only its ``(out, lse)`` pair
      enters the ring merge, so the sharded long-context path is
      HBM-score-free end to end. Both merges are the same exact
      online-softmax fold; parity is pinned in tests/test_transformer.py.
    """
    n_devices = jax.lax.psum(1, axis_name)

    def hop_dense(k_blk, v_blk, m, l, acc):
        logits = jnp.einsum("...qhd,...khd->...hqk", q, k_blk) * scale
        blk_max = jnp.max(logits, axis=-1)  # (..., h, q)
        new_m = jnp.maximum(m, blk_max)
        correction = jnp.exp(m - new_m)
        p = jnp.exp(logits - new_m[..., None])  # (..., h, q, k)
        l = l * correction + jnp.sum(p, axis=-1)
        # correction/l carry (..., heads, q); acc carries (..., q, heads, d)
        acc = (
            acc * jnp.swapaxes(correction, -1, -2)[..., None]
            + jnp.einsum("...hqk,...khd->...qhd", p, v_blk)
        )
        return new_m, l, acc

    def hop_flash(k_blk, v_blk, m, l, acc):
        from .flash_attention import flash_block_with_lse

        *batch_shape, q_len, heads, head_dim = q.shape
        bh = heads
        for dim in batch_shape:
            bh *= int(dim)

        def to3d(a):
            return jnp.moveaxis(a, -2, -3).reshape(bh, a.shape[-3], head_dim)

        out3, lse3 = flash_block_with_lse(
            to3d(q), to3d(k_blk), to3d(v_blk), scale, 128, 128,
            frozenset((axis_name,)),
        )
        # hop result folds into the carry as one pre-reduced block whose
        # "max" is its lse and whose normalizer mass is exp(lse - new_m):
        # out3 is normalized, so its unnormalized sum is out3 * exp(lse)
        hop_out = jnp.moveaxis(
            out3.reshape(*batch_shape, heads, q_len, head_dim), -3, -2
        )  # (..., q, h, d)
        hop_lse = lse3.reshape(*batch_shape, heads, q_len)  # (..., h, q)
        new_m = jnp.maximum(m, hop_lse)
        correction = jnp.exp(m - new_m)
        hop_w = jnp.exp(hop_lse - new_m)  # (..., h, q)
        l = l * correction + hop_w
        acc = (
            acc * jnp.swapaxes(correction, -1, -2)[..., None]
            + hop_out * jnp.swapaxes(hop_w, -1, -2)[..., None]
        )
        return new_m, l, acc

    hop = hop_flash if block_impl == "flash" else hop_dense

    def fold(carry, _):
        acc, m, l, k_blk, v_blk = carry
        new_m, l, acc = hop(k_blk, v_blk, m, l, acc)
        # rotate K/V one hop around the ring
        perm = [(i, (i + 1) % n_devices) for i in range(n_devices)]
        k_nxt = jax.lax.ppermute(k_blk, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_blk, axis_name, perm)
        return (acc, new_m, l, k_nxt, v_nxt), None

    heads, q_len = q.shape[-2], q.shape[-3]
    batch_shape = q.shape[:-3]
    # mark the fresh accumulators as varying over the ring axis so the scan
    # carry type stays consistent once device-varying K/V fold in
    m0 = jax.lax.pcast(
        jnp.full((*batch_shape, heads, q_len), -jnp.inf, q.dtype),
        axis_name,
        to="varying",
    )
    l0 = jax.lax.pcast(
        jnp.zeros((*batch_shape, heads, q_len), q.dtype), axis_name, to="varying"
    )
    acc0 = jnp.zeros_like(q)
    (acc, _, l, _, _), _ = jax.lax.scan(
        fold, (acc0, m0, l0, k, v), None, length=n_devices
    )
    return acc / jnp.swapaxes(l, -1, -2)[..., None]


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh,
    axis_name: Optional[str] = None,
    scale: Optional[float] = None,
    block_impl: str = "dense",
) -> jnp.ndarray:
    """Exact attention with the sequence axis sharded over ``mesh``.

    q/k/v: ``(batch, seq, heads, head_dim)`` with ``seq`` divisible by the
    mesh size. Communication is ``n_devices − 1`` neighbor hops of one K/V
    block each — the ring pattern that rides ICI links on TPU topologies.

    ``block_impl="flash"`` runs each hop's local attention as the Pallas
    blockwise kernel, so per-hop scores never materialize in HBM either —
    the fully HBM-score-free long-context path (ring across devices, flash
    within each device).
    """
    if axis_name is None:
        axis_name = mesh.axis_names[0]
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if block_impl not in ("dense", "flash"):
        raise ValueError(
            f"Unknown block_impl {block_impl!r}; use 'dense' or 'flash'"
        )
    n = mesh.shape[axis_name]
    if q.shape[1] % n != 0:
        raise ValueError(
            f"Sequence length {q.shape[1]} must divide over mesh axis "
            f"{axis_name!r} of size {n}"
        )
    spec = PartitionSpec(None, axis_name)  # shard seq axis; replicate batch
    sharded = jax.shard_map(
        partial(
            _ring_attention_sharded,
            axis_name=axis_name,
            scale=scale,
            block_impl=block_impl,
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        # pallas_call inside a shard_map body trips the vma checker's
        # interpreter (mixed varying axes in its internal dynamic_slice);
        # correctness of the flash composition is pinned by parity tests
        check_vma=block_impl != "flash",
    )
    return sharded(q, k, v)
