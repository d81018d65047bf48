"""Flash attention with a window and grouped key-value heads, interpreted
on the CPU, against the dense masked oracle (``full_attention``): forward
and backward, both layouts (head size 128: lane-blocked; smaller:
head-major), blocks smaller than, equal to and larger than the window,
padded lengths, the block ranges that decide what is skipped, and the
folded schedule of a window that is a multiple of the block (the band's
diagonal and edge tiles evaluated as one): its results, what it covers,
its counts, and which calls it leaves alone."""

import functools

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
# Windows that are multiples of the block at an unpadded length: the folded
# schedule (as are the first and third of CASES).
FOLD_CASES = [
    (64, 4, 2, 16, 16, 8),     # window == 2 x block: a plain tile between
    (96, 2, 2, 16, 48, 16),    # 3 x block
    (64, 6, 2, 16, 16, 16),    # head-major, three query heads a kv head
    (64, 4, 2, 128, 16, 16),   # lane-blocked, grouped, window == block
    (64, 8, 2, 128, 32, 16),   # lane-blocked, four a group, 2 x block
    (16, 2, 1, 16, 16, 16),    # T of one block: no edge block anywhere
    (32, 2, 1, 16, 32, 8),     # window == T: no query block has an edge
]


@pytest.mark.parametrize("t,h,kv,d,window,block", CASES + FOLD_CASES)
def test_flash_window_forward_matches_dense(t, h, kv, d, window, block):
    q, k, v = _qkv(t, h, kv, d)
    got = flash_attention(q, k, v, causal=True, window=window, block=block)
    want = full_attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("t,h,kv,d,window,block", CASES + FOLD_CASES)
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


@pytest.mark.parametrize("t,h,kv,d,window,block",
                         [(64, 4, 2, 16, 16, 16), (64, 4, 2, 128, 32, 16)])
def test_flash_folded_bf16_matches_dense(t, h, kv, d, window, block):
    q, k, v = (x.astype(jnp.bfloat16) for x in _qkv(t, h, kv, d, seed=4))
    assert flash.tile_counts(t, block, True, window)["folded"]

    def loss(f, **kw):
        return lambda *a: jnp.sum(jnp.sin(f(
            *a, causal=True, window=window, **kw).astype(jnp.float32)))

    got = flash_attention(q, k, v, causal=True, window=window, block=block)
    want = full_attention(q, k, v, causal=True, window=window)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(got.astype(jnp.float32),
                               want.astype(jnp.float32), atol=2e-2)
    grads = jax.grad(loss(flash_attention, block=block), (0, 1, 2))(q, k, v)
    wants = jax.grad(loss(full_attention), (0, 1, 2))(q, k, v)
    for g, w in zip(grads, wants):
        assert g.dtype == jnp.bfloat16
        w = np.asarray(w.astype(jnp.float32))
        np.testing.assert_allclose(g.astype(jnp.float32), w,
                                   atol=0.03 * np.abs(w).max() + 1e-2)


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


def _band(t_real, n, block, window):
    qi = np.arange(n * block)[:, None]
    ki = np.arange(n * block)[None, :]
    vis = (qi < t_real) & (ki < t_real) & (ki <= qi)
    if window is not None:
        vis &= ki > qi - window
    return vis


@pytest.mark.parametrize("t_real", [64, 50])
@pytest.mark.parametrize("window", [None, 8, 16, 24, 32])
@pytest.mark.parametrize("block", [8, 16])
def test_block_ranges_cover_exactly_the_band(t_real, window, block):
    """Every visible pair lies in [lo, hi); no pair of a block in [a, b)
    is masked; blocks outside [lo, hi) hold no visible pair. And the
    schedule the kernels run, by query block and by key block (folded
    tiles where the call folds, else plain and masked ones), evaluates
    every visible pair exactly once and no other pair of a masked or
    folded tile."""
    n = -(-t_real // block)
    vis = _band(t_real, n, block, window)
    fold = flash._fold_width(t_real, block, n, window)
    assert bool(fold) == (window is not None and window % block == 0
                          and t_real % block == 0)
    low = np.arange(block)[None, :] <= np.arange(block)[:, None]  # c <= r
    for fn, by_query in ((flash._key_blocks, True),
                         (flash._query_blocks, False)):
        seen = np.zeros(vis.shape, int)

        def tile(i, j):  # of query block i and key block j
            q, k = (i, j) if by_query else (j, i)
            rows = slice(q * block, (q + 1) * block)
            cols = slice(k * block, (k + 1) * block)
            return vis[rows, cols], seen[rows, cols]

        for i in range(n):
            lo, a, b, hi = (int(x) for x in fn(
                jnp.int32(i), block, n, t_real, True, window))
            assert (lo, a, b, hi) == tuple(int(x) for x in fn(
                i, block, n, t_real, True, window, xp=np))
            assert 0 <= lo <= a <= b <= hi <= n
            for j in range(n):
                if not lo <= j < hi:
                    assert not tile(i, j)[0].any(), (fn.__name__, i, j)
                if a <= j < b:
                    assert tile(i, j)[0].all(), (fn.__name__, i, j)
            # What the kernel of this block runs (``pl.when`` in each).
            other = i - fold if by_query else i + fold
            if fold and 0 <= other < n:
                # the folded tile: the diagonal block's pairs at or below
                # the diagonal, the edge block's above it
                assert tile(i, i)[0][low].all()
                assert not tile(i, i)[0][~low].any()
                assert tile(i, other)[0][~low].all()
                assert not tile(i, other)[0][low].any()
                tile(i, i)[1][low] += 1
                tile(i, other)[1][~low] += 1
                for j in range(min(i, other) + 1, max(i, other)):
                    assert tile(i, j)[0].all()
                    tile(i, j)[1][...] += 1
            else:
                for j in range(lo, hi):
                    if a <= j < b:
                        tile(i, j)[1][...] += 1
                    else:  # masked: what ``_visible`` keeps
                        tile(i, j)[1][tile(i, j)[0]] += 1
        np.testing.assert_array_equal(seen, vis.astype(int))
    # A window layer at block == window touches at most two key blocks.
    if window == block:
        for i in range(n):
            lo, _, _, hi = (int(x) for x in flash._key_blocks(
                jnp.int32(i), block, n, t_real, True, window))
            assert hi - lo <= 2


# (T, block, causal, window)
COUNT_CASES = [
    (64, 8, True, 8), (64, 8, True, 16), (64, 8, True, 24), (64, 16, True, 8),
    (50, 16, True, 16), (64, 16, True, None), (48, 16, False, None),
    (16, 16, True, 16), (64, 16, True, 64), (40, 8, True, 12),
]


@pytest.mark.parametrize("t,block,causal,window", COUNT_CASES)
def test_tile_counts_match_a_count_over_the_mask(t, block, causal, window):
    n = -(-t // block)
    vis = _band(t, n, block, window) if causal else (
        (np.arange(n * block)[:, None] < t) & (np.arange(n * block) < t))
    tiles = vis.reshape(n, block, n, block).transpose(0, 2, 1, 3)
    nonempty = int(tiles.any(axis=(2, 3)).sum())
    whole = int(tiles.all(axis=(2, 3)).sum())
    got = flash.tile_counts(t, block, causal, window)
    fold = flash._fold_width(t, block, n, window)
    assert got["needed_pairs"] == int(vis.sum())
    assert got["folded"] == (max(n - fold, 0) if fold else 0)
    # A fold makes one tile of two half-masked ones; nothing empty is run.
    assert got["plain"] + got["masked"] + 2 * got["folded"] == nonempty
    assert got["evaluated_pairs"] == block * block * (
        got["plain"] + got["masked"] + got["folded"])
    if t % block == 0:  # a padded block's whole tiles are run masked
        assert got["plain"] == whole
    # The kernel that goes by key block runs the same tiles.
    by_key = [tuple(int(x) for x in flash._query_blocks(
        j, block, n, t, causal, window, xp=np)) for j in range(n)]
    folded = [j for j in range(n) if fold and j + fold < n]
    assert len(folded) == got["folded"]
    assert sum((hi - lo) for j, (lo, a, b, hi) in enumerate(by_key)
               if j not in folded) + fold * len(folded) \
        == got["plain"] + got["masked"] + got["folded"]
    if not fold:  # what the ranges alone give, as before the fold
        ranges = [tuple(int(x) for x in flash._key_blocks(
            jnp.int32(i), block, n, t, causal, window)) for i in range(n)]
        assert got["plain"] == sum(b - a for _, a, b, _ in ranges)
        assert got["masked"] == sum(
            (a - lo) + (hi - b) for lo, a, b, hi in ranges)


def test_tile_counts_at_the_cell_shape():
    """T 8,192, block 512 (the default), window 512: 1.03 evaluated pairs
    a needed pair, 2.00 before the fold; the need is the benchmark's."""
    from benchmark import flash_cost

    got = flash.tile_counts(8192, None, True, 512)
    assert got["needed_pairs"] == flash_cost.causal_pairs(8192, 512)
    assert (got["plain"], got["masked"], got["folded"]) == (0, 1, 15)
    assert got["evaluated_pairs"] / got["needed_pairs"] < 1.05
    unfolded = 31 * 512 * 512  # two masked tiles a query block but the first
    assert round(unfolded / got["needed_pairs"], 2) == 2.0
    full = flash.tile_counts(8192, None, True, None)
    assert full["needed_pairs"] == flash_cost.causal_pairs(8192)
    assert (full["plain"], full["masked"], full["folded"]) == (120, 16, 0)


# No window; a window that is no multiple of the block; a padded length.
UNFOLDED = [(64, None, 16), (64, 12, 16), (50, 16, 16)]


@pytest.mark.parametrize("t,window,block", UNFOLDED + [(64, 16, 16)])
def test_only_a_window_of_whole_blocks_changes_the_program(
        t, window, block, monkeypatch):
    """The three calls the fold leaves alone trace the very program they
    trace with the fold taken out; a window of whole blocks does not."""
    q, k, v = _qkv(t, 2, 1, 16, b=1)

    def program():
        return str(jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(
            flash_attention(*a, causal=True, window=window, block=block)),
            (0, 1, 2)))(q, k, v))

    folds = (t, window, block) not in UNFOLDED
    assert bool(flash.tile_counts(t, block, True, window)["folded"]) == folds
    with_fold = program()
    monkeypatch.setattr(flash, "_fold_width", lambda *a: 0)
    without = program()
    assert (with_fold != without) == folds
    # the folded kernels' ``pl.when``, beside the two of every backward
    # (zero the dQ accumulator, write it out)
    assert without.count("cond[") == 2
    assert (with_fold.count("cond[") > 2) == folds


# The one backward kernel against the dense oracle under ``jax.vjp``: every
# kind of tile it has a body for, by the sizes that choose the body, the
# layout and the grouping (two key-value heads, so that dK and dV leave
# the kernel in another order of heads than the queries'). T = 48 at block
# 16 is three key blocks, so every query block's rows of the dQ accumulator
# but the first's are read back and added to by more than one program;
# T = 40 pads the last block.
KINDS = {
    "full causal": dict(causal=True, window=None),
    "window of whole blocks": dict(causal=True, window=16),  # folds unpadded
    "window across blocks": dict(causal=True, window=12),
    "non-causal": dict(causal=False, window=None),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("t", [48, 40], ids=["unpadded", "padded"])
@pytest.mark.parametrize("d", [128, 64], ids=["lane-blocked", "head-major"])
@pytest.mark.parametrize("group", [1, 2, 8])
@pytest.mark.parametrize("kind", list(KINDS))
def test_one_backward_kernel_matches_dense_vjp(kind, group, d, t, dtype):
    block = 16
    mask = KINDS[kind]
    assert bool(flash.tile_counts(t, block, mask["causal"],
                                  mask["window"])["folded"]) \
        == (kind == "window of whole blocks" and t == 48)
    q, k, v = (x.astype(dtype) for x in _qkv(t, 2 * group, 2, d, seed=5, b=1))
    g = jax.random.normal(jax.random.key(6), q.shape, jnp.float32)

    def grads(f, **kw):  # jitted whole: op by op the oracle takes 2 s
        def run(*a):
            out, vjp = jax.vjp(functools.partial(f, **mask, **kw), *a)
            return out, vjp(g.astype(dtype))
        return jax.jit(run)(q, k, v)

    out, got = grads(flash_attention, block=block)
    _, want = grads(full_attention)
    assert out.dtype == dtype
    for name, g_, w in zip(("dq", "dk", "dv"), got, want):
        assert g_.shape == w.shape and g_.dtype == dtype, name
        w = np.asarray(w.astype(jnp.float32))
        if dtype == jnp.float32:
            limits = dict(atol=5e-5, rtol=5e-5)
        else:
            limits = dict(atol=0.03 * np.abs(w).max() + 1e-2)
        np.testing.assert_allclose(g_.astype(jnp.float32), w,
                                   err_msg=name, **limits)


def test_dq_is_summed_over_every_key_block_of_the_band():
    """Five key blocks and a window of two: a folded program adds to the
    accumulator rows of two query blocks and its plain tile to a third's;
    the last two key blocks run the masked body. Each query block's dQ is
    what the oracle gives, so no program's share was lost or added twice."""
    q, k, v = _qkv(80, 2, 1, 16, seed=7, b=1)
    assert flash.tile_counts(80, 16, True, 32) == {
        "needed_pairs": 32 * 33 // 2 + 48 * 32, "evaluated_pairs": 9 * 256,
        "plain": 4, "masked": 2, "folded": 3}

    def dq(f, **kw):
        return jax.grad(lambda q: jnp.sum(jnp.sin(
            f(q, k, v, causal=True, window=32, **kw))))(q)

    np.testing.assert_allclose(dq(flash_attention, block=16),
                               dq(full_attention), atol=5e-5, rtol=5e-5)


def test_backward_vmem_follows_the_shape():
    """The shape rule: what a backward program keeps resident sets its
    scoped VMEM. Both training cells' calls leave the tiles their room
    under the constant; a longer call is given more; one the chip cannot
    hold is refused by name, at trace time, forward untouched."""
    mib = 1 << 20
    assert flash._backward_vmem_limit(8192, 128, 2) == flash.VMEM_LIMIT_BYTES
    assert flash._backward_vmem_limit(16384, 128, 2) == flash.VMEM_LIMIT_BYTES
    assert flash._backward_vmem_limit(16384, 128, 4) == (56 + 16) * mib
    assert flash._backward_vmem_limit(32768, 128, 2) == (64 + 16) * mib
    # a head of 64 fills 128 lanes
    assert flash._backward_vmem_limit(32768, 64, 2) == (64 + 16) * mib
    assert flash._backward_vmem_limit(49152, 128, 2) == flash.VMEM_MAX_BYTES
    x = jax.ShapeDtypeStruct((1, 65536, 1, 128), jnp.bfloat16)
    assert jax.eval_shape(
        lambda *a: flash_attention(*a, causal=True), x, x, x).shape == x.shape
    with pytest.raises(ValueError, match="T=65536, D=128.*shard"):
        jax.eval_shape(jax.grad(lambda *a: jnp.sum(flash_attention(
            *a, causal=True).astype(jnp.float32))), x, x, x)


def test_a_traced_call_records_its_schedule():
    from pytorch_distributed_mnist_tpu.utils.profiling import (
        device_report,
        flash_schedules,
    )

    q, k, v = _qkv(64, 2, 1, 16, b=1)
    before = flash_schedules.snapshot()
    jax.make_jaxpr(lambda *a: flash_attention(*a, causal=True))(q, k, v)
    after = flash_schedules.snapshot()
    assert after["sites"] == before["sites"] + 1
    assert after["folded_sites"] == before["folded_sites"]
    assert after["backward_sites"] == before["backward_sites"]
    # forward and backward of a folded call: two sites more
    jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(flash_attention(
        *a, causal=True, window=16, block=16))))(q, k, v)
    folded = flash_schedules.snapshot()
    assert folded["sites"] == after["sites"] + 2
    assert folded["folded_sites"] == after["folded_sites"] + 2
    assert 1.0 <= folded["folded_evaluated_over_needed"] < 2.0
    # the backward is one site and one kernel, which the program shows
    assert folded["backward_sites"] == after["backward_sites"] + 1
    assert folded["fused_backward_sites"] == folded["backward_sites"]
    # no policy asked for the forward's results: none kept
    assert folded["kept_results"] == before["kept_results"]
    assert device_report()["flash_schedules"] == folded


def test_flash_rejects_a_window_without_causal():
    q, k, v = _qkv(16, 2, 2, 16)
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, k, v, window=4)
    with pytest.raises(ValueError, match="H_kv"):
        flash_attention(q, k, v[:, :, :1])
