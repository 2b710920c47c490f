"""Flash-attention kernel parity (forward, gradients, padding, dtypes).

Off-TPU the kernel runs in Pallas interpret mode — these tests execute the
same kernel body the TPU lowers (tiling/padding behavior included)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gordo_components_tpu.ops.attention import dense_attention
from gordo_components_tpu.ops.flash_attention import flash_attention


def _qkv(shape, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    return tuple(
        jnp.asarray(rng.normal(scale=0.5, size=shape), dtype) for _ in range(3)
    )


@pytest.mark.parametrize(
    "shape,blocks",
    [
        # short-seq cases pass explicit small blocks so seq spans multiple
        # tiles and the KERNEL runs (default 128-blocks would now take the
        # single-tile dense fallback and test dense against itself)
        ((2, 16, 2, 8), dict(block_q=8, block_k=8)),  # small head_dim
        ((1, 37, 1, 4), dict(block_q=8, block_k=8)),  # odd seq — padded-key mask
        ((2, 160, 2, 8), {}),  # seq > one k block with default block=128
    ],
)
def test_flash_matches_dense_forward(shape, blocks):
    q, k, v = _qkv(shape)
    ours = flash_attention(q, k, v, **blocks)
    ref = dense_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(ours), np.asarray(ref), atol=2e-5)


def test_flash_short_seq_falls_back_to_dense():
    """A sequence that fits in one q block AND one k block must route to
    dense_attention: the kernel would compute the same thing on operands
    tile-padded to (lcm(block_q, block_k), 128) — at plant scale (7
    patches, 16-wide heads, 640k batch x tag x head rows) that padding was
    a measured 21 GB HBM request vs 16 GiB on v5e (round-4 bench OOM)."""
    short = _qkv((4, 7, 4, 16), seed=17)
    long_ = _qkv((1, 200, 1, 8), seed=19)
    jaxpr_short = str(jax.make_jaxpr(flash_attention)(*short))
    jaxpr_long = str(jax.make_jaxpr(flash_attention)(*long_))
    assert "pallas_call" not in jaxpr_short  # dense fallback taken
    assert "pallas_call" in jaxpr_long  # real kernel above one tile
    np.testing.assert_allclose(
        np.asarray(flash_attention(*short)),
        np.asarray(dense_attention(*short)),
        atol=2e-5,
    )


def test_flash_asymmetric_blocks():
    """block_q > block_k pads the sequence beyond a block_k multiple — the
    phantom key block must be masked (regression: the mask guard used to
    check seq % block_k only). seq=200 > min(block) so the KERNEL runs
    (seq=128 would take the dense fallback and test nothing), padding to
    lcm=256 with phantom keys 200-255."""
    q, k, v = _qkv((1, 200, 1, 8), seed=11)
    ours = flash_attention(q, k, v, block_q=256, block_k=128)
    ref = dense_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(ours), np.asarray(ref), atol=2e-5)


def test_flash_non_divisible_blocks():
    """block_k not dividing block_q: padding must reach a common multiple
    of both, or trailing key blocks are never visited (regression: keys
    64-79 were silently dropped for block_q=96, block_k=64, seq=80).
    seq=200 > min(block) so the kernel runs (not the dense fallback); pad
    target is lcm(96,64)=192 -> 384, trailing keys must all be visited."""
    q, k, v = _qkv((1, 200, 1, 8), seed=13)
    ours = flash_attention(q, k, v, block_q=96, block_k=64)
    ref = dense_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(ours), np.asarray(ref), atol=2e-5)


def test_flash_matches_dense_gradients():
    q, k, v = _qkv((1, 40, 2, 8), seed=3)
    g = jnp.asarray(
        np.random.default_rng(9).normal(size=q.shape), jnp.float32
    )

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * g)

    flash = lambda q, k, v: flash_attention(q, k, v, block_q=16, block_k=16)
    ours = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    ref = jax.grad(loss(dense_attention), argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(ours, ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-5, err_msg=f"d{name}"
        )


@pytest.mark.parametrize(
    "seq,blocks",
    [
        (40, dict(block_q=16, block_k=16)),  # several tiles, the last one padded
        (37, dict(block_q=8, block_k=16)),  # odd length, unequal blocks
        (160, {}),  # the default blocks
    ],
)
def test_causal_flash_with_a_value_width_of_its_own_matches_dense(seq, blocks):
    """Latent attention's shape: keys and queries 24 wide (16 + 8 rotary),
    values 16 wide, a query sees the keys up to its own position. Forward
    and the gradient of all three against ``dense_attention``."""
    rng = np.random.default_rng(21)
    q, k = (jnp.asarray(rng.normal(scale=0.5, size=(2, seq, 2, 24)), jnp.float32) for _ in range(2))
    v = jnp.asarray(rng.normal(scale=0.5, size=(2, seq, 2, 16)), jnp.float32)
    g = jnp.asarray(rng.normal(size=v.shape), jnp.float32)
    scale = 24 ** -0.5

    def flash(q, k, v):
        return flash_attention(q, k, v, scale=scale, causal=True, **blocks)

    def dense(q, k, v):
        return dense_attention(q, k, v, scale=scale, causal=True)

    ours, ref = flash(q, k, v), dense(q, k, v)
    assert ours.shape == v.shape
    np.testing.assert_allclose(np.asarray(ours), np.asarray(ref), atol=2e-5)
    # the first position sees itself alone: its value comes back unmixed
    np.testing.assert_allclose(np.asarray(ours[:, 0]), np.asarray(v[:, 0]), atol=1e-6)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * g)

    for a, b, name in zip(
        jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v),
        jax.grad(loss(dense), argnums=(0, 1, 2))(q, k, v), "qkv",
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-5, err_msg=f"d{name}"
        )


def test_flash_bfloat16_forward():
    q, k, v = _qkv((2, 32, 2, 8), seed=5, dtype=jnp.bfloat16)
    ours = flash_attention(q, k, v, block_q=16, block_k=16)
    assert ours.dtype == jnp.bfloat16
    ref = dense_attention(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32)
    )
    np.testing.assert_allclose(
        np.asarray(ours, np.float32), np.asarray(ref), atol=2e-2
    )


def test_flash_custom_scale_and_no_batch():
    q, k, v = _qkv((24, 2, 8), seed=7)  # no leading batch dim
    ours = flash_attention(q, k, v, scale=0.3, block_q=8, block_k=8)
    ref = dense_attention(q, k, v, scale=0.3)
    np.testing.assert_allclose(np.asarray(ours), np.asarray(ref), atol=2e-5)


def test_patchtst_flash_kind_matches_dense():
    """attention_impl='flash' is reachable from the registered kind and its
    forward matches the dense impl with identical params."""
    from gordo_components_tpu.models.register import get_factory

    kwargs = dict(
        n_features=3,
        lookback_window=24,
        patch_length=4,
        stride=4,
        d_model=16,
        n_heads=2,
        n_layers=1,
    )
    dense_spec = get_factory("patchtst")(**kwargs, attention_impl="dense")
    flash_spec = get_factory("patchtst")(**kwargs, attention_impl="flash")
    x = jnp.asarray(
        np.random.default_rng(0).normal(size=(2, 24, 3)), jnp.float32
    )
    params = dense_spec.module.init(jax.random.PRNGKey(0), x, deterministic=True)
    out_dense = dense_spec.module.apply(params, x, deterministic=True)
    out_flash = flash_spec.module.apply(params, x, deterministic=True)
    np.testing.assert_allclose(
        np.asarray(out_flash), np.asarray(out_dense), atol=5e-5
    )


# -- the banded form: grouped key/value heads and a window ---------------------
def _masked_dense(q, k, v, scale, window):
    """Grouped, windowed causal attention with the whole score matrix:
    query head ``h`` reads key head ``h // group``; row ``i`` sees ``i -
    window < j <= i``."""
    seq, group = q.shape[-3], q.shape[-2] // k.shape[-2]
    k, v = (jnp.repeat(a, group, axis=-2) for a in (k, v))
    logits = jnp.einsum("...qhd,...khd->...hqk", q, k) * scale
    i, j = jnp.arange(seq)[:, None], jnp.arange(seq)[None, :]
    seen = j <= i
    if window is not None:
        seen &= j > i - window
    weights = jax.nn.softmax(jnp.where(seen, logits, -jnp.inf), axis=-1)
    return jnp.einsum("...hqk,...khd->...qhd", weights, v)


BANDED = [
    # (sequence, window, blocks (q, k), query heads a key head)
    (64, 16, (8, 8), 4),  # a band of three blocks of eight
    (64, 200, (16, 16), 2),  # a window longer than the sequence
    (96, 20, (16, 16), 1),  # a window that is no multiple of the block
    (37, 12, (8, 16), 2),  # an odd length, unequal blocks
    (64, None, (16, 8), 4),  # grouped heads, no window (the full layers)
    (48, 16, (16, 16), 1),  # equal heads, the window alone
    (40, 1, (8, 8), 2),  # a window of one: every row sees itself
]


@pytest.mark.parametrize(
    "seq,window,blocks,group,operand_dtype",
    [(*case, None) for case in BANDED]
    # what the 8k cell's model asks for: the kernels' bodies with every cast
    # the TPU lowers, in interpret mode, at a bfloat16 tolerance
    + [(*BANDED[i], "bfloat16") for i in (0, 3, 4)],
)
def test_the_banded_kernel_is_masked_dense_attention_forward_and_every_gradient(
    seq, window, blocks, group, operand_dtype
):
    rng = np.random.default_rng(31)
    q = jnp.asarray(rng.normal(scale=0.5, size=(2, seq, 2 * group, 8)), jnp.float32)
    k = jnp.asarray(rng.normal(scale=0.5, size=(2, seq, 2, 8)), jnp.float32)
    v = jnp.asarray(rng.normal(scale=0.5, size=(2, seq, 2, 8)), jnp.float32)
    g = jnp.asarray(rng.normal(size=q.shape), jnp.float32)
    scale = 8 ** -0.5
    rounded = operand_dtype is not None
    out_atol, grad_atol = (6e-3, 3e-2) if rounded else (2e-5, 5e-5)

    def flash(q, k, v):
        return flash_attention(
            q, k, v, scale=scale, block_q=blocks[0], block_k=blocks[1],
            causal=True, window=window, operand_dtype=operand_dtype,
        )

    def dense(q, k, v):
        return _masked_dense(q, k, v, scale, window)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * g)

    grads = jax.grad(loss(flash), argnums=(0, 1, 2))
    assert "pallas_call" in str(jax.make_jaxpr(flash)(q, k, v))
    # the casts are in the traced program, forward and backward, only when
    # the caller asks: the kernel layer reads neither backend nor global
    assert ("bf16" in str(jax.make_jaxpr(grads)(q, k, v))) == rounded
    out, exact = np.asarray(flash(q, k, v)), np.asarray(dense(q, k, v))
    assert out.dtype == np.float32
    np.testing.assert_allclose(out, exact, atol=out_atol)
    assert (np.abs(out - exact).max() > 1e-4) == rounded  # rounded as bfloat16 rounds
    ref = jax.grad(loss(dense), argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(grads(q, k, v), ref, "qkv"):
        # a key head's, summed over its query heads; float32 whatever the tiles
        assert a.shape == b.shape and a.dtype == jnp.float32
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=grad_atol, err_msg=f"d{name}"
        )


@pytest.mark.parametrize("seq,window,blocks,group", BANDED)
def test_the_visit_counter_is_the_bands_block_count(seq, window, blocks, group):
    """``visited_blocks`` against the tiles that hold a visible pair,
    counted from the mask itself: forward once, backward twice (``dq`` by
    query block, ``dk/dv`` by key block)."""
    import math

    from gordo_components_tpu.ops.flash_attention import visited_blocks

    bq, bk = blocks
    s_pad = -(-seq // math.lcm(bq, bk)) * math.lcm(bq, bk)
    i, j = np.arange(s_pad)[:, None], np.arange(s_pad)[None, :]
    seen = (j <= i) & ((j > i - window) if window is not None else True)
    tiles = seen.reshape(s_pad // bq, bq, s_pad // bk, bk).any(axis=(1, 3))
    forward, backward = visited_blocks(seq, bq, bk, window)
    assert forward == tiles.sum() and backward == 2 * tiles.sum()
    if window is not None and window < seq // 2:
        assert forward < visited_blocks(seq, bq, bk, None)[0]


def test_blocks_the_window_hides_are_neither_computed_nor_fetched():
    """What lies outside a block's band is poisoned: a kernel that only
    masked it would carry the poison into the result (0 · NaN), forward
    (values in key blocks before the band) and backward (``dk``, ``dv`` of
    a key block whose band ends before the poisoned rows of the cotangent;
    ``dq`` of rows whose band begins after the poisoned keys)."""
    seq, window, block = 64, 16, 8
    rng = np.random.default_rng(5)
    q, k, v = (
        jnp.asarray(rng.normal(scale=0.5, size=(1, seq, heads, 8)), jnp.float32)
        for heads in (4, 2, 2)
    )

    def flash(q, k, v):
        return flash_attention(
            q, k, v, block_q=block, block_k=block, causal=True, window=window
        )

    # rows 40.. see keys 25..: the first three key blocks are outside
    poisoned_v = v.at[:, :24].set(jnp.nan)
    out = flash(q, k, poisoned_v)
    assert np.all(np.isfinite(np.asarray(out[:, 40:])))
    assert not np.all(np.isfinite(np.asarray(out[:, :24])))
    # a cotangent poisoned from row 40 on: keys 0..23 are seen by rows
    # 0..38 alone
    _, pull = jax.vjp(flash, q, k, v)
    g = jnp.ones_like(out).at[:, 40:].set(jnp.nan)
    dq, dk, dv = pull(g)
    assert np.all(np.isfinite(np.asarray(dk[:, :24])))
    assert np.all(np.isfinite(np.asarray(dv[:, :24])))
    assert np.all(np.isfinite(np.asarray(dq[:, :40])))
    # and dq of rows 40.. never reads the keys before their band
    _, pull = jax.vjp(flash, q, k.at[:, :24].set(jnp.nan), poisoned_v)
    dq, _, _ = pull(jnp.ones_like(out).at[:, :40].set(0.0))
    assert np.all(np.isfinite(np.asarray(dq[:, 40:])))


def test_a_window_without_causal_or_heads_that_do_not_divide_are_refused():
    q, k, v = _qkv((1, 32, 4, 8), seed=3)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, window=8)
    with pytest.raises(ValueError, match="divide"):
        flash_attention(q, k[:, :, :3], v[:, :, :3], causal=True)
    with pytest.raises(ValueError, match="operand_dtype"):
        flash_attention(q, k, v, causal=True, operand_dtype="bfloat16")
