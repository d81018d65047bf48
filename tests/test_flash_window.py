"""Flash attention with a window and grouped key-value heads, interpreted
on the CPU, against the dense masked oracle (``full_attention``): forward
and backward, both layouts (head size 128: lane-blocked; smaller:
head-major), blocks smaller than, equal to and larger than the window,
padded lengths, and the block ranges that decide what is skipped."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_mnist_tpu.ops.attention import full_attention
from pytorch_distributed_mnist_tpu.ops.pallas import flash
from pytorch_distributed_mnist_tpu.ops.pallas.flash import flash_attention


def _qkv(t, h, kv, d, seed=0, b=2):
    ks = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(ks[0], (b, t, h, d), jnp.float32),
            jax.random.normal(ks[1], (b, t, kv, d), jnp.float32),
            jax.random.normal(ks[2], (b, t, kv, d), jnp.float32))


# (T, H_q, H_kv, D, window, block)
CASES = [
    (64, 4, 2, 16, 8, 8),      # window == block
    (64, 4, 2, 16, 8, 16),     # window < block
    (64, 4, 4, 16, 24, 8),     # window spans three blocks, no grouping
    (64, 6, 2, 16, 8, 32),     # three query heads a key-value head
    (50, 4, 2, 16, 12, 16),    # padded: T is no multiple of the block
    (64, 4, 2, 16, None, 16),  # causal only, grouped
    (32, 2, 1, 128, 8, 16),    # head size 128: the lane-blocked layout
    (40, 2, 2, 128, None, 16), # lane-blocked and padded
]


@pytest.mark.parametrize("t,h,kv,d,window,block", CASES)
def test_flash_window_forward_matches_dense(t, h, kv, d, window, block):
    q, k, v = _qkv(t, h, kv, d)
    got = flash_attention(q, k, v, causal=True, window=window, block=block)
    want = full_attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("t,h,kv,d,window,block", CASES)
def test_flash_window_backward_matches_dense(t, h, kv, d, window, block):
    q, k, v = _qkv(t, h, kv, d, seed=1)

    def loss(f, **kw):
        return lambda *a: jnp.sum(jnp.sin(f(*a, causal=True, window=window,
                                            **kw)))

    got = jax.grad(loss(flash_attention, block=block), (0, 1, 2))(q, k, v)
    want = jax.grad(loss(full_attention), (0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        assert g.shape == w.shape
        np.testing.assert_allclose(g, w, atol=5e-5, rtol=5e-5)


def test_flash_grouped_heads_not_causal():
    q, k, v = _qkv(48, 4, 2, 16, seed=2)
    got = flash_attention(q, k, v, block=16)
    want = full_attention(q, k, v)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_flash_bf16_operands_keep_their_type():
    q, k, v = (x.astype(jnp.bfloat16) for x in _qkv(32, 4, 2, 16, seed=3))
    got = flash_attention(q, k, v, causal=True, window=8, block=16)
    want = full_attention(q, k, v, causal=True, window=8)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(got.astype(jnp.float32),
                               want.astype(jnp.float32), atol=2e-2)
    grads = jax.grad(lambda *a: jnp.sum(flash_attention(
        *a, causal=True, window=8, block=16).astype(jnp.float32)),
        (0, 1, 2))(q, k, v)
    assert [g.dtype for g in grads] == [jnp.bfloat16] * 3


def test_window_mask_at_its_edges():
    """Query t sees keys t-window+1 .. t: the dense oracle's mask row."""
    from pytorch_distributed_mnist_tpu.ops.attention import (
        NEG_INF,
        _masked_scores,
    )

    q = jnp.ones((1, 12, 1, 4))
    s = np.asarray(_masked_scores(q, q, True, 1.0, window=4))[0, 0]
    seen = s > NEG_INF / 2
    for t in range(12):
        assert list(np.nonzero(seen[t])[0]) == list(
            range(max(0, t - 3), t + 1))


@pytest.mark.parametrize("t_real", [64, 50])
@pytest.mark.parametrize("window", [None, 8, 24])
@pytest.mark.parametrize("block", [8, 16])
def test_block_ranges_cover_exactly_the_band(t_real, window, block):
    """Every visible pair lies in [lo, hi); no pair of a block in [a, b)
    is masked; blocks outside [lo, hi) hold no visible pair."""
    n = -(-t_real // block)
    qi = np.arange(n * block)[:, None]
    ki = np.arange(n * block)[None, :]
    vis = (qi < t_real) & (ki < t_real) & (ki <= qi)
    if window is not None:
        vis &= ki > qi - window
    for fn, by_query in ((flash._key_blocks, True),
                         (flash._query_blocks, False)):
        for i in range(n):
            lo, a, b, hi = (int(x) for x in fn(
                jnp.int32(i), block, n, t_real, True, window))
            assert 0 <= lo <= a <= b <= hi <= n
            for j in range(n):
                tile = (vis[i * block:(i + 1) * block,
                            j * block:(j + 1) * block] if by_query else
                        vis[j * block:(j + 1) * block,
                            i * block:(i + 1) * block])
                if not lo <= j < hi:
                    assert not tile.any(), (fn.__name__, i, j)
                if a <= j < b:
                    assert tile.all(), (fn.__name__, i, j)
    # A window layer at block == window touches at most two key blocks.
    if window == block:
        for i in range(n):
            lo, _, _, hi = (int(x) for x in flash._key_blocks(
                jnp.int32(i), block, n, t_real, True, window))
            assert hi - lo <= 2


def test_flash_rejects_a_window_without_causal():
    q, k, v = _qkv(16, 2, 2, 16)
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, k, v, window=4)
    with pytest.raises(ValueError, match="H_kv"):
        flash_attention(q, k, v[:, :, :1])
