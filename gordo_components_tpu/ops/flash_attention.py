"""Blockwise (flash) attention as a Pallas TPU kernel.

The reference has no attention at all (SURVEY.md §6.7); this backs the
rebuild's long-window PatchTST path. ``dense_attention`` materializes the
``(seq, seq)`` score matrix — fine for patch counts in the dozens, but a
long-window config (thousands of patches) pays O(S²) HBM for scores that
exist only to be softmaxed and contracted away. This kernel computes
attention blockwise in VMEM with the online-softmax recurrence (running
max ``m``, normalizer ``l``, accumulator ``acc`` — the same fold
:func:`gordo_components_tpu.ops.attention.ring_attention` runs across ICI
hops, here run across VMEM tiles): per-core live memory is
O(block_q x block_k), the two contractions per tile are
``lax.dot_general`` calls that land on the MXU, and scores never touch
HBM.

Exactness and autodiff:

- forward is exact (not approximate); parity vs ``dense_attention`` is
  pinned by tests/test_flash_attention.py, including an odd sequence
  length that exercises the padding mask;
- backward is a ``jax.custom_vjp`` implemented as a blockwise
  ``lax.scan`` over key blocks using the saved per-row logsumexp — the
  standard flash backward recurrence — so gradients are exact and peak
  memory stays O(S x block_k), never O(S²).

On the CPU the kernel runs in Pallas interpret mode, so CPU tests execute
the same kernel body the TPU lowers.

Scope: self-attention, bidirectional (the PatchTST encoder) or ``causal``
(the decoder kind: key blocks past a query block's last row are neither
computed nor fetched again), with a value width of its own (latent
attention's keys are wider than its values). Attention-weight dropout is not
representable (weights are never materialized) — callers fall back to the
dense path for that, as with ring attention.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _interpret_mode() -> bool:
    """Whether to run the Pallas kernel in interpret mode: on the CPU only,
    where it is the test path. The kernel is written for Mosaic, so any
    other non-TPU backend raises instead of crawling through the
    interpreter — use ``attention_impl='dense'`` there."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise NotImplementedError(
        f"flash_attention is a Pallas TPU kernel; the {backend!r} backend "
        "can only interpret it — use attention_impl='dense'"
    )


# finite stand-in for -inf in the masked-score/online-max recurrence:
# genuine -inf turns the first block's ``exp(s - m)`` into exp(-inf + inf)
# = NaN when a tile is fully masked; exp(-1e30 - x) just underflows to 0
_MASK = -1e30

_LANES = 128
_DEF_BLOCK_Q = 128
_DEF_BLOCK_K = 128


def _pad_to(n: int, multiple: int) -> int:
    return -(-n // multiple) * multiple


def _fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
    *, scale: float, seq_len: int, block_q: int, block_k: int, n_k: int,
    masked: bool, causal: bool
):
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _MASK)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _fold():
        q = q_ref[0].astype(jnp.float32)  # (bq, D)
        k = k_ref[0].astype(jnp.float32)  # (bk, D)
        v = v_ref[0].astype(jnp.float32)  # (bk, Dv)
        s = (
            jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            * scale
        )  # (bq, bk) — scores live in VMEM only
        kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        if masked:  # the padded tail (from EITHER block size) carries
            # phantom keys — mask any key position at or beyond the true
            # sequence length
            s = jnp.where(kpos < seq_len, s, _MASK)
        if causal:
            qpos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0
            )
            s = jnp.where(kpos <= qpos, s, _MASK)

        m_prev = m_scr[...][:, :1]  # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_scr[...][:, :1] * corr + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        acc_scr[...] = acc_scr[...] * corr + pv
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    if causal:
        # a key block that starts past this query block's last row is all
        # mask: its fold is skipped (its fetch too: see the index maps)
        pl.when(ki * block_k <= qi * block_q + block_q - 1)(_fold)
    else:
        _fold()

    @pl.when(ki == n_k - 1)
    def _finish():
        l = l_scr[...][:, :1]
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)
        lse = m_scr[...][:, :1] + jnp.log(l)  # (bq, 1)
        lse_ref[0] = jnp.broadcast_to(lse.T, lse_ref.shape[1:])


def _flash_fwd_3d(
    q3, k3, v3, scale: float, block_q: int, block_k: int, vma=None,
    causal: bool = False,
):
    """q3/k3: ``(BH, S, D)``, v3: ``(BH, S, Dv)`` → ``(out (BH, S, Dv),
    lse (BH, S))``.

    ``vma``: mesh axes the operands vary over, required when the kernel
    runs inside a ``shard_map`` body (the ring composition) — pallas_call
    must declare its outputs' varying axes there."""
    bh, seq, d = q3.shape
    dv = v3.shape[-1]
    # a common multiple of BOTH block sizes: padding to max() alone leaves
    # trailing key blocks unvisited when block_k does not divide it
    # (n_k floor-divides), silently dropping real keys from the softmax
    s_pad = _pad_to(seq, math.lcm(block_q, block_k))
    d_pad, dv_pad = _pad_to(d, _LANES), _pad_to(dv, _LANES)
    pad = [(0, 0), (0, s_pad - seq), (0, d_pad - d)]
    q3, k3 = (jnp.pad(a, pad) for a in (q3, k3))
    v3 = jnp.pad(v3, pad[:2] + [(0, dv_pad - dv)])
    n_q, n_k = s_pad // block_q, s_pad // block_k
    kernel = functools.partial(
        _fwd_kernel,
        scale=scale,
        seq_len=seq,
        block_q=block_q,
        block_k=block_k,
        n_k=n_k,
        masked=s_pad != seq,
        causal=causal,
    )
    if causal:
        # a skipped key block names the last block this query block needs:
        # an index that does not change is not fetched again
        def kv_index(b, qi, ki):
            return (b, jnp.minimum(ki, (qi * block_q + block_q - 1) // block_k), 0)
    else:
        def kv_index(b, qi, ki):
            return (b, ki, 0)
    out, lse8 = pl.pallas_call(
        kernel,
        grid=(bh, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d_pad), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_k, d_pad), kv_index),
            pl.BlockSpec((1, block_k, dv_pad), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, dv_pad), lambda b, qi, ki: (b, qi, 0)),
            # lse per q row, broadcast over 8 sublanes to satisfy tiling
            pl.BlockSpec((1, 8, block_q), lambda b, qi, ki: (b, 0, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s_pad, dv_pad), q3.dtype, vma=vma),
            jax.ShapeDtypeStruct((bh, 8, s_pad), jnp.float32, vma=vma),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, dv_pad), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=_interpret_mode(),
    )(q3, k3, v3)
    return out[:, :seq, :dv], lse8[:, 0, :seq]


def _bwd_3d(scale, block_k, res, do, dlse=None, causal=False):
    """Blockwise flash backward (pure JAX, exact): scan over key blocks
    using the saved logsumexp; peak memory O(S x block_k).

    ``dlse``: optional cotangent of the logsumexp output (the ring
    composition differentiates through per-hop lse values in its merge);
    its score-gradient contribution is ``p * dlse`` (since
    ``∂lse_i/∂s_ij = p_ij``), and it never touches ``dv``."""
    q3, k3, v3, out, lse = res
    bh, seq, d = q3.shape
    dv = v3.shape[-1]
    qf = q3.astype(jnp.float32)
    dof = do.astype(jnp.float32)
    s_pad = _pad_to(seq, block_k)
    padk = [(0, 0), (0, s_pad - seq), (0, 0)]
    kp = jnp.pad(k3.astype(jnp.float32), padk)
    vp = jnp.pad(v3.astype(jnp.float32), padk)
    kpos = jnp.arange(s_pad)
    valid = kpos < seq
    if causal:  # (S, S_pad): a query sees the keys up to its own row
        valid = valid[None, :] & (kpos[None, :] <= jnp.arange(seq)[:, None])
    else:
        valid = valid[None, :]
    k_blocks = kp.reshape(bh, s_pad // block_k, block_k, d).swapaxes(0, 1)
    v_blocks = vp.reshape(bh, s_pad // block_k, block_k, dv).swapaxes(0, 1)
    m_blocks = (
        valid.astype(jnp.float32)
        .reshape(-1, s_pad // block_k, block_k)
        .swapaxes(0, 1)[:, None]
    )  # (n_k, 1, S or 1, bk)
    d_i = jnp.sum(dof * out.astype(jnp.float32), axis=-1)  # (BH, S)

    def step(dq_acc, blk):
        k_b, v_b, mask = blk  # (BH, bk, D), (BH, bk, Dv), (1, S or 1, bk)
        s = jnp.einsum("bqd,bkd->bqk", qf, k_b) * scale
        # masked before the exponential: a key no query may see can score
        # far above the row's logsumexp, and inf * 0 is not 0
        p = jnp.exp(jnp.where(mask > 0, s - lse[..., None], _MASK))  # (BH, S, bk)
        dv_b = jnp.einsum("bqk,bqd->bkd", p, dof)
        dp = jnp.einsum("bqd,bkd->bqk", dof, v_b)
        dresid = dp - d_i[..., None]
        if dlse is not None:
            dresid = dresid + dlse[..., None]
        ds = p * dresid * scale
        dq_acc = dq_acc + jnp.einsum("bqk,bkd->bqd", ds, k_b)
        dk_b = jnp.einsum("bqk,bqd->bkd", ds, qf)
        return dq_acc, (dk_b, dv_b)

    dq, (dk_s, dv_s) = jax.lax.scan(
        step, jnp.zeros_like(qf), (k_blocks, v_blocks, m_blocks)
    )
    dk = dk_s.swapaxes(0, 1).reshape(bh, s_pad, d)[:, :seq]
    dv_ = dv_s.swapaxes(0, 1).reshape(bh, s_pad, dv)[:, :seq]
    return dq.astype(q3.dtype), dk.astype(k3.dtype), dv_.astype(v3.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_3d(q3, k3, v3, scale, block_q, block_k, causal):
    out, _ = _flash_fwd_3d(q3, k3, v3, scale, block_q, block_k, causal=causal)
    return out


def _flash_3d_fwd(q3, k3, v3, scale, block_q, block_k, causal):
    out, lse = _flash_fwd_3d(
        q3, k3, v3, scale, block_q, block_k, causal=causal
    )
    return out, (q3, k3, v3, out, lse)


def _flash_3d_bwd(scale, block_q, block_k, causal, res, do):
    return _bwd_3d(scale, block_k, res, do, causal=causal)


_flash_3d.defvjp(_flash_3d_fwd, _flash_3d_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_block_with_lse(q3, k3, v3, scale, block_q, block_k, vma=None):
    """``(BH, S, D)`` q/k/v → ``(out (BH, S, D), lse (BH, S))`` — the Pallas
    forward with the per-row logsumexp exposed, differentiable in BOTH
    outputs. This is the per-hop update for
    :func:`gordo_components_tpu.ops.attention.ring_attention`'s flash
    composition: the ring merge needs each hop's lse to fold partial
    softmaxes exactly, and gradients must flow through that merge.
    ``vma``: the shard_map mesh axes the operands vary over (see
    :func:`_flash_fwd_3d`)."""
    return _flash_fwd_3d(q3, k3, v3, scale, block_q, block_k, vma=vma)


def _flash_lse_fwd(q3, k3, v3, scale, block_q, block_k, vma=None):
    out, lse = _flash_fwd_3d(q3, k3, v3, scale, block_q, block_k, vma=vma)
    return (out, lse), (q3, k3, v3, out, lse)


def _flash_lse_bwd(scale, block_q, block_k, vma, res, cotangents):
    do, dlse = cotangents
    return _bwd_3d(scale, block_k, res, do, dlse=dlse)


flash_block_with_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    scale: Optional[float] = None,
    block_q: int = _DEF_BLOCK_Q,
    block_k: int = _DEF_BLOCK_K,
    causal: bool = False,
) -> jnp.ndarray:
    """Exact blockwise attention; drop-in for :func:`dense_attention`.

    Shapes follow the flax convention: q/k ``(..., seq, heads, head_dim)``
    and v ``(..., seq, heads, value_dim)`` → ``(..., seq, heads,
    value_dim)``. Worth using when the patch/sequence axis is long (the
    score matrix would be large). ``causal``: a query sees the keys up to
    its own position.

    **Short sequences fall back to** :func:`~gordo_components_tpu.ops.
    attention.dense_attention`: when the whole sequence fits in one
    q-block AND one k-block (``seq <= min(block_q, block_k)``) the kernel
    degenerates to dense attention
    computed on tile-padded operands — same arithmetic, strictly more
    HBM. The padding is not a rounding error: each operand is padded to
    ``(lcm(block_q, block_k), 128)`` regardless of true size, so a
    many-machine short-window config (e.g. PatchTST at plant scale: 7
    patches x 16-wide heads over batch x tags x heads = 640k rows)
    materializes ~146x its real footprint: ``bf16[640000,128,128]`` is
    21 GB against 16 GiB of v5e HBM, a compile-time OOM. Dense attention
    at those shapes keeps the score matrix trivially small. The crossover
    rule is structural (single-tile => dense), not a tuned threshold.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    *batch, seq, heads, head_dim = q.shape
    if seq <= min(block_q, block_k):
        from .attention import dense_attention  # lazy: avoids an import
        # cycle (attention.py imports this module inside its flash hop)

        return dense_attention(q, k, v, scale, causal=causal)
    bh = heads
    for dim in batch:  # python shape math — jnp would trace it
        bh *= int(dim)

    def to3d(a):
        moved = jnp.moveaxis(a, -2, -3)  # (..., heads, seq, head_dim)
        return moved.reshape(bh, seq, a.shape[-1])

    out3 = _flash_3d(
        to3d(q), to3d(k), to3d(v), float(scale), block_q, block_k, bool(causal)
    )
    out = out3.reshape(*batch, heads, seq, v.shape[-1])
    return jnp.moveaxis(out, -3, -2)
