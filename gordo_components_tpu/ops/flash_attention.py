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
(the decoder kinds: key blocks past a query block's last row are neither
computed nor fetched again), with a value width of its own (latent
attention's keys are wider than its values). Attention-weight dropout is not
representable (weights are never materialized) — callers fall back to the
dense path for that, as with ring attention.

**Grouped heads and a window** (``window=W``, and ``k``, ``v`` of fewer
heads than ``q``): query head ``h`` reads key/value head ``h // group``
through the block index maps, so no repeated copy of ``k`` or ``v`` is
made in HBM, and position ``i`` sees ``i - W < j <= i``. The grid's third
axis walks a query block's **band** alone: the key blocks from the one
that holds ``i_first - W + 1`` to the one that holds the block's last row
(``_key_band``); steps past the band name its last block again (an index
that does not change is not fetched) and skip their fold. The work is
O(S·(W + block)) whatever S. The backward pass of this form is two Pallas
kernels over the same band, from the saved logsumexp: ``dq`` a query block
over its key band (the forward's grid), and ``dk``, ``dv`` a key block over
the query blocks that can see it (``_query_band``), its accumulators
summed over the group's query heads inside the kernel, so a key head's
gradient leaves it whole. ``visited_blocks`` counts the tiles both grids
compute, from the same band arithmetic. ``operand_dtype`` is the caller's
word on precision (the model owns it; this module reads no global): the
type ``q``, ``k``, ``v``, the cotangent and the tiles' ``p``, ``ds`` go to
the MXU in, cast once outside the kernels (half the bytes a tile at
bfloat16), accumulation always float32; ``None`` leaves the operands as
they come. It is a non-differentiable argument of the ``custom_vjp``, so
forward and backward cannot disagree, and interpret mode runs the same
casts (tests/test_flash_attention.py holds the bfloat16 bodies against
masked dense attention). ``window=None`` with equal head counts is the
form above, forward kernel and scanned backward as they were.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

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


def _key_band(qi, block_q: int, block_k: int, window: Optional[int]):
    """``(first, last)`` key blocks that query block ``qi`` of a causal
    layer sees: its last row's block, back to the block of the first row's
    oldest visible key (block 0 without a window). On Python ints or traced
    indices alike: the kernels' grids and ``visited_blocks`` read it."""
    last = (qi * block_q + block_q - 1) // block_k
    if window is None:
        return 0 * last, last
    lowest = qi * block_q - (window - 1)
    lowest = max(lowest, 0) if isinstance(qi, int) else jnp.maximum(lowest, 0)
    return lowest // block_k, last


def _query_band(ki, block_q: int, block_k: int, window: Optional[int], n_q: int):
    """``(first, last)`` query blocks of a causal layer that see key block
    ``ki``: from the block of its first key's own row to the block of the
    last row its last key is visible to (the last block without a window)."""
    first = (ki * block_k) // block_q
    if window is None:
        return first, 0 * first + (n_q - 1)
    last = (ki * block_k + block_k - 1 + window - 1) // block_q
    return first, min(last, n_q - 1) if isinstance(ki, int) else jnp.minimum(last, n_q - 1)


def _band_block(band, index, step):
    """The block that ``step`` along block ``index``'s band stands for; a
    step past the band names its last block again (an index that does not
    change is not fetched)."""
    first, last = band(index)
    return jnp.minimum(first + step, last)


def _band_steps(band, n: int) -> int:
    """The longest band among blocks ``0..n-1``: the grid's band axis."""
    return max(last - first + 1 for first, last in map(band, range(n)))


def visited_blocks(
    seq: int, block_q: int = 128, block_k: int = 128,
    window: Optional[int] = None,
) -> Tuple[int, int]:
    """``(forward, backward)`` score tiles one query head's kernels compute
    for a causal sequence of ``seq``: the forward's and ``dq``'s walk over
    every query block's key band, ``dk/dv``'s over every key block's query
    band (the same tiles, met from the other side)."""
    s_pad = _pad_to(seq, math.lcm(block_q, block_k))
    n_q, n_k = s_pad // block_q, s_pad // block_k
    by_query = sum(
        last - first + 1
        for first, last in (_key_band(qi, block_q, block_k, window) for qi in range(n_q))
    )
    by_key = sum(
        last - first + 1
        for first, last in (
            _query_band(ki, block_q, block_k, window, n_q) for ki in range(n_k)
        )
    )
    return by_query, by_query + by_key


def _visible(kpos, qpos, seq_len: int, masked: bool, window: Optional[int]):
    """Which (key, query) pairs of a causal tile count."""
    seen = kpos <= qpos
    if window is not None:
        seen &= kpos > qpos - window
    if masked:
        seen &= kpos < seq_len
    return seen


def _fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
    *, scale: float, seq_len: int, block_q: int, block_k: int, n_k: int,
    masked: bool, causal: bool, banded: bool = False,
    window: Optional[int] = None, mxu=jnp.float32
):
    """``banded``: the grid's third axis is a step along the query block's
    key band (``n_k`` steps), not a key block; else it is the key block."""
    qi, ki = pl.program_id(1), pl.program_id(2)
    if banded:
        first, last = _key_band(qi, block_q, block_k, window)
        kb = first + ki
    else:
        kb = ki

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _MASK)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _fold():
        q = q_ref[0].astype(mxu)  # (bq, D)
        k = k_ref[0].astype(mxu)  # (bk, D)
        v = v_ref[0].astype(mxu)  # (bk, Dv)
        s = (
            jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            * scale
        )  # (bq, bk) — scores live in VMEM only
        kpos = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        if banded:
            qpos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0
            )
            s = jnp.where(_visible(kpos, qpos, seq_len, masked, window), s, _MASK)
        else:
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
        if mxu != jnp.float32:
            p = p.astype(mxu)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        acc_scr[...] = acc_scr[...] * corr + pv
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    if banded:
        # a step past the band is all mask: its fold is skipped (its fetch
        # too: see the index maps)
        pl.when(kb <= last)(_fold)
    elif causal:
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
    causal: bool = False, banded: bool = False, window: Optional[int] = None,
    operand_dtype=None,
):
    """q3: ``(BH, S, D)``, k3: ``(BHk, S, D)``, v3: ``(BHk, S, Dv)`` →
    ``(out (BH, S, Dv), lse (BH, S))``; ``BHk`` is ``BH`` unless ``banded``
    (the grouped, windowed form: causal, ``BH // BHk`` query heads a key
    head; ``operand_dtype``: what its tiles go to the MXU as).

    ``vma``: mesh axes the operands vary over, required when the kernel
    runs inside a ``shard_map`` body (the ring composition) — pallas_call
    must declare its outputs' varying axes there."""
    bh, seq, d = q3.shape
    dv = v3.shape[-1]
    out_dtype = q3.dtype
    if operand_dtype is not None:
        # once, before the tiles are fetched: half the bytes a tile at bfloat16
        q3, k3, v3 = (a.astype(operand_dtype) for a in (q3, k3, v3))
    # a common multiple of BOTH block sizes: padding to max() alone leaves
    # trailing key blocks unvisited when block_k does not divide it
    # (n_k floor-divides), silently dropping real keys from the softmax
    s_pad = _pad_to(seq, math.lcm(block_q, block_k))
    d_pad, dv_pad = _pad_to(d, _LANES), _pad_to(dv, _LANES)
    pad = [(0, 0), (0, s_pad - seq), (0, d_pad - d)]
    q3, k3 = (jnp.pad(a, pad) for a in (q3, k3))
    v3 = jnp.pad(v3, pad[:2] + [(0, dv_pad - dv)])
    n_q, n_k = s_pad // block_q, s_pad // block_k
    more = {}
    if banded:
        group = bh // k3.shape[0]
        band = functools.partial(
            _key_band, block_q=block_q, block_k=block_k, window=window
        )
        n_k = _band_steps(band, n_q)
        more = dict(banded=True, window=window, mxu=q3.dtype)

        def kv_index(b, qi, ki):
            return (b // group, _band_block(band, qi, ki), 0)
    elif causal:
        # a skipped key block names the last block this query block needs:
        # an index that does not change is not fetched again
        def kv_index(b, qi, ki):
            return (b, jnp.minimum(ki, (qi * block_q + block_q - 1) // block_k), 0)
    else:
        def kv_index(b, qi, ki):
            return (b, ki, 0)
    kernel = functools.partial(
        _fwd_kernel,
        scale=scale,
        seq_len=seq,
        block_q=block_q,
        block_k=block_k,
        n_k=n_k,
        masked=s_pad != seq,
        causal=causal,
        **more,
    )
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
            jax.ShapeDtypeStruct((bh, s_pad, dv_pad), out_dtype, vma=vma),
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
        **({"name": "window_attention_fwd"} if banded else {}),
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


# -- the banded form's backward: two kernels over the band --------------------
def _tile_grads(q, k, v, do, lse, delta, seen, scale: float):
    """One tile, keys down and queries across (so the per-row ``lse`` and
    ``delta`` lie along lanes as they are stored): ``(p, ds)``, both ``(bk,
    bq)`` in the operands' type (the MXU's: the caller cast them), from
    which ``dv += p·do``, ``dk += ds·q``, ``dq += dsᵀ·k``."""
    s = jax.lax.dot_general(
        k, q, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    # masked before the exponential: a key no query may see can score far
    # above the row's logsumexp, and inf * 0 is not 0
    p = jnp.exp(jnp.where(seen, s, _MASK) - lse)
    dp = jax.lax.dot_general(
        v, do, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    ds = p * (dp - delta) * scale
    return p.astype(q.dtype), ds.astype(q.dtype)


def _tile_positions(kb, qb, block_q: int, block_k: int):
    shape = (block_k, block_q)
    kpos = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    qpos = qb * block_q + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return kpos, qpos


def _dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, acc_scr,
    *, scale: float, seq_len: int, block_q: int, block_k: int, n_steps: int,
    masked: bool, window: Optional[int]
):
    """A query block over its key band (the forward's grid)."""
    qi, step = pl.program_id(1), pl.program_id(2)
    first, last = _key_band(qi, block_q, block_k, window)
    kb = first + step

    @pl.when(step == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(kb <= last)
    def _fold():
        k = k_ref[0]
        kpos, qpos = _tile_positions(kb, qi, block_q, block_k)
        _, ds = _tile_grads(
            q_ref[0], k, v_ref[0], do_ref[0], lse_ref[0][:1], delta_ref[0][:1],
            _visible(kpos, qpos, seq_len, masked, window), scale,
        )
        acc_scr[...] += jax.lax.dot_general(
            ds, k, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(step == n_steps - 1)
    def _finish():
        dq_ref[0] = acc_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_scr, dv_scr,
    *, scale: float, seq_len: int, block_q: int, block_k: int, n_q: int,
    n_steps: int, group: int, masked: bool, window: Optional[int]
):
    """A key block over the query blocks that see it, of every query head
    of its group in turn: the accumulators are summed over both, so the key
    head's gradient is written once, whole."""
    ki, g, step = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    first, last = _query_band(ki, block_q, block_k, window, n_q)
    qb = first + step

    @pl.when((g == 0) & (step == 0))
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    @pl.when(qb <= last)
    def _fold():
        q, do = q_ref[0], do_ref[0]
        kpos, qpos = _tile_positions(ki, qb, block_q, block_k)
        p, ds = _tile_grads(
            q, k_ref[0], v_ref[0], do, lse_ref[0][:1], delta_ref[0][:1],
            _visible(kpos, qpos, seq_len, masked, window), scale,
        )
        dv_scr[...] += jax.lax.dot_general(
            p, do, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dk_scr[...] += jax.lax.dot_general(
            ds, q, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when((g == group - 1) & (step == n_steps - 1))
    def _finish():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _banded_bwd_3d(scale, block_q, block_k, window, operand_dtype, res, do):
    """``(dq, dk, dv)`` of the banded form; ``dk``, ``dv`` have the key
    heads' count."""
    q3, k3, v3, out, lse = res
    (bh, seq, d), (bhk, _, dv) = q3.shape, v3.shape
    group = bh // bhk
    s_pad = _pad_to(seq, math.lcm(block_q, block_k))
    d_pad, dv_pad = _pad_to(d, _LANES), _pad_to(dv, _LANES)
    n_q, n_k = s_pad // block_q, s_pad // block_k
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)

    def rows(a, width):  # (BH, S, w) -> the padded tiles' array
        return jnp.pad(a, [(0, 0), (0, s_pad - seq), (0, width - a.shape[-1])])

    def lanes(a):  # (BH, S) per-row numbers along lanes, over 8 sublanes
        return jnp.broadcast_to(
            jnp.pad(a, [(0, 0), (0, s_pad - seq)])[:, None, :], (bh, 8, s_pad)
        )

    # once, before the tiles are fetched: what the forward sent to the MXU
    mxu = q3.dtype if operand_dtype is None else operand_dtype
    operands = (
        rows(q3.astype(mxu), d_pad), rows(k3.astype(mxu), d_pad),
        rows(v3.astype(mxu), dv_pad), rows(do.astype(mxu), dv_pad),
        lanes(lse), lanes(delta),
    )
    static = dict(
        scale=scale, seq_len=seq, block_q=block_q, block_k=block_k,
        masked=s_pad != seq, window=window,
    )
    key_band = functools.partial(
        _key_band, block_q=block_q, block_k=block_k, window=window
    )
    query_band = functools.partial(
        _query_band, block_q=block_q, block_k=block_k, window=window, n_q=n_q
    )

    def in_specs(q_row, kv_row, q_lane):  # q, k, v, do, lse, delta
        return [
            pl.BlockSpec((1, block_q, d_pad), q_row),
            pl.BlockSpec((1, block_k, d_pad), kv_row),
            pl.BlockSpec((1, block_k, dv_pad), kv_row),
            pl.BlockSpec((1, block_q, dv_pad), q_row),
            pl.BlockSpec((1, 8, block_q), q_lane),
            pl.BlockSpec((1, 8, block_q), q_lane),
        ]

    n_steps = _band_steps(key_band, n_q)
    q_row = lambda b, qi, step: (b, qi, 0)  # noqa: E731
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, n_steps=n_steps, **static),
        grid=(bh, n_q, n_steps),
        in_specs=in_specs(
            q_row,
            lambda b, qi, step: (b // group, _band_block(key_band, qi, step), 0),
            lambda b, qi, step: (b, 0, qi),
        ),
        out_specs=pl.BlockSpec((1, block_q, d_pad), q_row),
        out_shape=jax.ShapeDtypeStruct((bh, s_pad, d_pad), q3.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d_pad), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=_interpret_mode(),
        name="window_attention_bwd_dq",
    )(*operands)

    n_steps = _band_steps(query_band, n_k)
    kv_row = lambda b, ki, g, step: (b, ki, 0)  # noqa: E731
    dk, dv_ = pl.pallas_call(
        functools.partial(
            _dkv_kernel, n_q=n_q, n_steps=n_steps, group=group, **static
        ),
        grid=(bhk, n_k, group, n_steps),
        in_specs=in_specs(
            lambda b, ki, g, step: (b * group + g, _band_block(query_band, ki, step), 0),
            kv_row,
            lambda b, ki, g, step: (b * group + g, 0, _band_block(query_band, ki, step)),
        ),
        out_specs=[
            pl.BlockSpec((1, block_k, d_pad), kv_row),
            pl.BlockSpec((1, block_k, dv_pad), kv_row),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bhk, s_pad, d_pad), k3.dtype),
            jax.ShapeDtypeStruct((bhk, s_pad, dv_pad), v3.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d_pad), jnp.float32),
            pltpu.VMEM((block_k, dv_pad), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary", "arbitrary")
        ),
        interpret=_interpret_mode(),
        name="window_attention_bwd_dkv",
    )(*operands)
    return dq[:, :seq, :d], dk[:, :seq, :d], dv_[:, :seq, :dv]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _banded_3d(q3, k3, v3, scale, block_q, block_k, window, operand_dtype):
    return _banded_3d_fwd(
        q3, k3, v3, scale, block_q, block_k, window, operand_dtype
    )[0]


def _banded_3d_fwd(q3, k3, v3, scale, block_q, block_k, window, operand_dtype):
    out, lse = _flash_fwd_3d(
        q3, k3, v3, scale, block_q, block_k, causal=True, banded=True,
        window=window, operand_dtype=operand_dtype,
    )
    return out, (q3, k3, v3, out, lse)


_banded_3d.defvjp(_banded_3d_fwd, _banded_bwd_3d)


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
    window: Optional[int] = None,
    operand_dtype=None,
) -> jnp.ndarray:
    """Exact blockwise attention; drop-in for :func:`dense_attention`.

    Shapes follow the flax convention: q ``(..., seq, heads, head_dim)``,
    k ``(..., seq, kv_heads, head_dim)`` and v ``(..., seq, kv_heads,
    value_dim)`` → ``(..., seq, heads, value_dim)``. Worth using when the
    patch/sequence axis is long (the score matrix would be large).
    ``causal``: a query sees the keys up to its own position; ``window``
    (causal only): and no further back than ``window - 1`` rows. Fewer
    ``kv_heads`` than ``heads`` (a divisor; causal only): query head ``h``
    reads key/value head ``h // (heads // kv_heads)``. Either takes the
    banded form (module docstring), which always runs the kernel, and
    alone takes ``operand_dtype``: the type its operands go to the MXU in
    (``"bfloat16"``: one pass, float32 accumulation), forward and backward;
    ``None``: as they come.

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
    kv_heads = k.shape[-2]
    banded = window is not None or kv_heads != heads
    if banded and (not causal or heads % kv_heads or (window or 1) < 1):
        raise ValueError(
            "a window or grouped key/value heads need causal=True, a window "
            f"of at least 1 and a head count the key heads divide; got causal="
            f"{causal}, window={window}, {heads} heads over {kv_heads}"
        )
    if operand_dtype is not None and not banded:
        raise ValueError("operand_dtype is the banded form's (a window or grouped heads)")
    if not banded and seq <= min(block_q, block_k):
        from .attention import dense_attention  # lazy: avoids an import
        # cycle (attention.py imports this module inside its flash hop)

        return dense_attention(q, k, v, scale, causal=causal)
    n_batch = 1
    for dim in batch:  # python shape math — jnp would trace it
        n_batch *= int(dim)

    def to3d(a):
        moved = jnp.moveaxis(a, -2, -3)  # (..., heads, seq, head_dim)
        return moved.reshape(n_batch * a.shape[-2], seq, a.shape[-1])

    if banded:
        out3 = _banded_3d(
            to3d(q), to3d(k), to3d(v), float(scale), block_q, block_k,
            None if window is None else int(window),
            None if operand_dtype is None else jnp.dtype(operand_dtype),
        )
    else:
        out3 = _flash_3d(
            to3d(q), to3d(k), to3d(v), float(scale), block_q, block_k, bool(causal)
        )
    out = out3.reshape(*batch, heads, seq, v.shape[-1])
    return jnp.moveaxis(out, -3, -2)
