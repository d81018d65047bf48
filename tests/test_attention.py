"""Attention + sequence-parallel correctness on the virtual 8-device mesh.

Strategy (SURVEY.md section 4 "multi-device without a cluster"): the dense
``full_attention`` is the semantic reference; ring and Ulysses sequence-
parallel implementations must match it allclose with the token axis sharded
8 ways. The ViT model trains a few steps and must be finite/learning.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from pytorch_distributed_mnist_tpu.ops.attention import (
    full_attention,
    online_softmax_block,
    online_softmax_finish,
    online_softmax_init,
)
from pytorch_distributed_mnist_tpu.parallel.mesh import make_mesh
from pytorch_distributed_mnist_tpu.parallel.ring import ring_attention
from pytorch_distributed_mnist_tpu.parallel.ulysses import ulysses_attention


B, T, H, D = 2, 64, 8, 16


@pytest.fixture(scope="module")
def qkv():
    ks = jax.random.split(jax.random.key(0), 3)
    shape = (B, T, H, D)
    return tuple(jax.random.normal(k, shape, jnp.float32) for k in ks)


@pytest.fixture(scope="module")
def seq_mesh():
    return make_mesh(("seq",))


def _naive(q, k, v, causal=False, scale=None):
    """Independent softmax attention (``jax.nn.softmax``, plain autodiff) in
    the inputs' type. Causal masks are end-aligned; a row with nothing to
    attend to gives zeros."""
    scale = q.shape[-1] ** -0.5 if scale is None else scale
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        tq, tk = s.shape[-2:]
        mask = np.tril(np.ones((tq, tk), bool), k=tk - tq)
        s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    if causal:
        p = jnp.where(mask.any(axis=-1, keepdims=True), p, 0.0)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("causal", [False, True])
def test_full_attention_matches_naive(qkv, causal):
    q, k, v = qkv
    np.testing.assert_allclose(
        full_attention(q, k, v, causal=causal), _naive(q, k, v, causal),
        rtol=1e-5, atol=1e-5,
    )


def test_online_softmax_blockwise_matches_dense(qkv):
    """Folding K/V in 8 blocks through the online recurrence == dense."""
    q, k, v = qkv
    state = online_softmax_init(q)
    for blk in range(8):
        sl = slice(blk * T // 8, (blk + 1) * T // 8)
        state = online_softmax_block(state, q, k[:, sl], v[:, sl])
    np.testing.assert_allclose(
        online_softmax_finish(state), _naive(q, k, v), rtol=1e-5, atol=1e-5
    )


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_dense(qkv, seq_mesh, causal):
    q, k, v = qkv
    out = jax.jit(
        lambda a, b, c: ring_attention(a, b, c, mesh=seq_mesh, causal=causal)
    )(q, k, v)
    np.testing.assert_allclose(out, _naive(q, k, v, causal), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_attention_matches_dense(qkv, seq_mesh, causal):
    q, k, v = qkv
    out = jax.jit(
        lambda a, b, c: ulysses_attention(a, b, c, mesh=seq_mesh, causal=causal)
    )(q, k, v)
    np.testing.assert_allclose(out, _naive(q, k, v, causal), rtol=1e-5, atol=1e-5)


def test_ring_attention_uneven_heads_ok(seq_mesh):
    """Ring has no head-divisibility constraint (unlike Ulysses)."""
    ks = jax.random.split(jax.random.key(1), 3)
    q, k, v = (jax.random.normal(kk, (1, 16, 3, 8), jnp.float32) for kk in ks)
    out = ring_attention(q, k, v, mesh=seq_mesh)
    ref = full_attention(q, k, v)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


def test_ulysses_rejects_indivisible_heads(seq_mesh):
    ks = jax.random.split(jax.random.key(2), 3)
    q, k, v = (jax.random.normal(kk, (1, 16, 3, 8), jnp.float32) for kk in ks)
    with pytest.raises(ValueError, match="not divisible"):
        ulysses_attention(q, k, v, mesh=seq_mesh)


def test_ulysses_with_flash_local_matches_dense():
    """Ulysses + Pallas flash as the per-device local attention: the
    composition the CLI exposes as --sequence-parallel-impl ulysses
    --attention flash. Must match single-device dense attention."""
    from pytorch_distributed_mnist_tpu.ops.pallas.flash import flash_attention
    from pytorch_distributed_mnist_tpu.parallel.ulysses import (
        ulysses_attention,
    )

    mesh = make_mesh(("data", "seq"), shape=(2, 4))
    b, t, h, d = 2, 32, 8, 16
    k1, k2, k3 = jax.random.split(jax.random.key(7), 3)
    q = jax.random.normal(k1, (b, t, h, d), jnp.float32)
    k = jax.random.normal(k2, (b, t, h, d), jnp.float32)
    v = jax.random.normal(k3, (b, t, h, d), jnp.float32)

    for causal in (False, True):
        want = full_attention(q, k, v, causal=causal)
        got = ulysses_attention(
            q, k, v, mesh=mesh, axis="seq", batch_axis="data",
            causal=causal, local_attention=flash_attention,
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_sharded_flash_matches_dense_on_tp_mesh():
    """sharded_flash_attention on a data x model mesh: batch and heads
    sharded, kernel runs per-device, output equals dense attention."""
    from pytorch_distributed_mnist_tpu.ops.pallas.flash import (
        sharded_flash_attention,
    )

    mesh = make_mesh(("data", "model"), shape=(2, 4))
    b, t, h, d = 2, 32, 8, 16
    k1, k2, k3 = jax.random.split(jax.random.key(9), 3)
    q = jax.random.normal(k1, (b, t, h, d), jnp.float32)
    k = jax.random.normal(k2, (b, t, h, d), jnp.float32)
    v = jax.random.normal(k3, (b, t, h, d), jnp.float32)
    for causal in (False, True):
        want = full_attention(q, k, v, causal=causal)
        got = sharded_flash_attention(
            q, k, v, mesh=mesh, batch_axis="data", head_axis="model",
            causal=causal,
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


# -- the dense op's closed-form backward (ops/attention.py::_dense_bwd) -------

def _grad_inputs(tq, tk, dtype, seed=11, b=2, h=3, h_kv=None):
    ks = jax.random.split(jax.random.key(seed), 4)
    q = jax.random.normal(ks[0], (b, tq, h, 8), jnp.float32)
    k, v = (jax.random.normal(kk, (b, tk, h_kv or h, 8), jnp.float32)
            for kk in ks[1:3])
    w = jax.random.normal(ks[3], (b, tq, h, 8), jnp.float32)  # the cotangent
    return tuple(x.astype(dtype) for x in (q, k, v)), w


def _weighted(fn, w, **kwargs):
    """Scalar loss whose gradient is the VJP of ``fn`` against ``w``."""
    def loss(q, k, v):
        return jnp.sum(fn(q, k, v, **kwargs).astype(jnp.float32) * w)
    return loss


@pytest.mark.parametrize("scale", [None, 0.37])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("tq,tk", [(16, 16), (8, 24), (24, 8)])
@pytest.mark.parametrize("causal", [False, True])
def test_full_attention_grads_match_autodiff_of_naive(causal, tq, tk, dtype,
                                                      tol, scale):
    """dq, dk, dv of the hand-written VJP against autodiff of ``_naive`` on
    the same values in float32. bf16: the operands and probabilities enter
    the matmuls as bf16 (2^-8 relative each), hence the loose tolerance."""
    (q, k, v), w = _grad_inputs(tq, tk, dtype)
    out = full_attention(q, k, v, causal=causal, scale=scale)
    got = jax.grad(_weighted(full_attention, w, causal=causal, scale=scale),
                   (0, 1, 2))(q, k, v)
    qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))
    want_out = _naive(qf, kf, vf, causal, scale)
    want = jax.grad(_weighted(_naive, w, causal=causal, scale=scale),
                    (0, 1, 2))(qf, kf, vf)
    assert out.dtype == dtype and all(g.dtype == dtype for g in got)
    for g, r in zip((out,) + got, (want_out,) + want):
        g = np.asarray(g.astype(jnp.float32))
        assert np.isfinite(g).all()
        np.testing.assert_allclose(g, r, rtol=tol, atol=tol * np.abs(r).max())
    if causal and tq > tk:
        # Queries before the first key attend to nothing: zeros out, and
        # neither they nor anything else gets a gradient through them.
        dead = tq - tk
        assert not np.asarray(out[:, :dead].astype(jnp.float32)).any()
        assert not np.asarray(got[0][:, :dead].astype(jnp.float32)).any()


def test_full_attention_grads_under_checkpoint(qkv):
    """``jax.checkpoint`` replays the custom forward and takes the same
    backward: gradients equal the unwrapped function's."""
    q, k, v = qkv
    w = jnp.cos(jnp.arange(q.size, dtype=jnp.float32)).reshape(q.shape)
    plain = _weighted(full_attention, w, causal=True)
    remat = _weighted(jax.checkpoint(partial(full_attention, causal=True)), w)
    want = jax.grad(_weighted(_naive, w, causal=True), (0, 1, 2))(q, k, v)
    got = jax.jit(jax.grad(remat, (0, 1, 2)))(q, k, v)
    for g, p, r in zip(got, jax.grad(plain, (0, 1, 2))(q, k, v), want):
        np.testing.assert_allclose(g, p, rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(g, r, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("remat", [False, True])
def test_vit_grads_match_naive_attention(remat):
    """The default attention inside ``VisionTransformer`` (float32, with and
    without ``nn.remat`` round each block) against the same parameters with
    the naive attention passed as ``attention_fn``."""
    from pytorch_distributed_mnist_tpu.models.attention import (
        VisionTransformer,
    )

    kwargs = dict(patch_size=7, embed_dim=32, depth=2, num_heads=4,
                  compute_dtype=jnp.float32)
    model = VisionTransformer(remat=remat, **kwargs)
    naive = VisionTransformer(attention_fn=_naive, **kwargs)
    x = jax.random.normal(jax.random.key(5), (4, 28, 28, 1), jnp.float32)
    labels = jnp.arange(4) % 10
    params = model.init(jax.random.key(6), x)

    def loss(m):
        def fn(p):
            logp = jax.nn.log_softmax(m.apply(p, x))
            return -jnp.mean(logp[jnp.arange(4), labels])
        return jax.jit(jax.value_and_grad(fn))

    (got_loss, got), (want_loss, want) = loss(model)(params), loss(naive)(params)
    np.testing.assert_allclose(got_loss, want_loss, rtol=1e-6)
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want))
    for path, g in jax.tree_util.tree_leaves_with_path(got):
        r = flat_want[path]
        np.testing.assert_allclose(
            g, r, rtol=1e-4, atol=1e-5 * max(float(np.abs(r).max()), 1e-6),
            err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("causal", [False, True])
def test_full_attention_keeps_no_softmax_intermediates(causal):
    """What ``jax.vjp`` keeps for the backward: no mask and at most one
    float32 tensor the size of the scores (the closed form keeps none; it
    recomputes the probabilities from q, k and the row log-sum-exp). An
    edit that falls back to autodiff of the softmax keeps the ``exp``, the
    normalised probabilities and, when causal, ``pred`` masks, and fails
    here without a chip."""
    (q, k, v), _ = _grad_inputs(24, 40, jnp.float32)
    (b, tq, h, _), tk = q.shape, k.shape[1]
    _, vjp = jax.vjp(partial(full_attention, causal=causal), q, k, v)
    kept = jax.tree_util.tree_leaves(vjp)
    assert kept, "jax.vjp's function no longer shows what it closes over"
    assert not [x.shape for x in kept if x.dtype == jnp.bool_]
    score_sized = [x for x in kept if x.size >= b * h * tq * tk]
    assert len(score_sized) <= 1, [(x.shape, x.dtype) for x in score_sized]
    assert all(x.shape == (b, h, tq, tk) and x.dtype == jnp.float32
               for x in score_sized)


def test_full_attention_refuses_forward_mode(qkv):
    """The docstring's promise: a ``custom_vjp`` has no JVP rule."""
    q, k, v = qkv
    with pytest.raises(TypeError, match="custom_vjp"):
        jax.jvp(full_attention, (q, k, v), (q, k, v))


# -- the dense op over slices of the batch (ops/attention.py::_over_slices) ---

def _score_bytes(examples, h, tq, tk):
    return examples * h * tq * tk * 4


@pytest.mark.parametrize("shape,limit,want", [
    # the cells' calls under the constant the module ships
    ((128, 12, 196, 196), None, 8),   # vit-b16: 236 MB, 16 images a slice
    ((32, 16, 196, 196), None, 4),    # vit-l16: 78.7 MB, 8 images a slice
    ((128, 16, 196, 196), None, 16),  # train_l16_dp4's global batch: 2 a chip
    ((8, 12, 196, 196), None, 1),     # 14.8 MB stays whole
    ((1, 48, 8192, 8192), None, 1),   # the 8,192-token oracle: B = 1
    # the rule alone
    ((6, 2, 8, 8), _score_bytes(4, 2, 8, 8), 2),    # 3 + 3, not 4 + 2
    ((6, 2, 8, 8), _score_bytes(1, 2, 8, 8), 6),
    ((5, 2, 8, 8), _score_bytes(2, 2, 8, 8), 5),    # a prime: slices of one
    ((4, 2, 8, 8), _score_bytes(4, 2, 8, 8), 1),    # fits whole
    ((4, 2, 8, 8), _score_bytes(1, 2, 8, 8) - 1, 1),  # no divisor fits
    ((1, 2, 8, 8), 1, 1),
])
def test_slice_count_reads_only_the_shape(monkeypatch, shape, limit, want):
    from pytorch_distributed_mnist_tpu.ops import attention

    if limit is not None:
        monkeypatch.setattr(attention, "SLICE_SCORE_BYTES", limit)
    assert attention.slice_count(*shape) == want


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-6),
                                       (jnp.bfloat16, 1e-2)])
@pytest.mark.parametrize("b,per,slices,tq,tk,h_kv,kwargs", [
    (4, 2, 2, 16, 16, 4, {}),
    (4, 1, 4, 8, 24, 4, {"causal": True}),
    (4, 2, 2, 24, 8, 4, {"causal": True}),    # rows with nothing to see
    (6, 4, 2, 16, 16, 4, {"causal": True, "window": 5}),
    (6, 1, 6, 16, 16, 2, {}),                 # grouped key-value heads
    (5, 2, 5, 8, 24, 1, {"causal": True, "window": 3}),
    (4, 0.5, 1, 16, 16, 4, {}),               # no divisor fits: whole
    (1, 0.5, 1, 16, 16, 4, {"causal": True}),  # B = 1 stays whole
])
def test_sliced_dense_attention_is_the_whole_one(monkeypatch, b, per, slices,
                                                 tq, tk, h_kv, kwargs, dtype,
                                                 tol):
    """The output and dq, dk, dv with the constant set so low that these
    tiny calls slice, against the same calls kept whole: a slice only
    decides which examples share a fusion."""
    from pytorch_distributed_mnist_tpu.ops import attention

    (q, k, v), w = _grad_inputs(tq, tk, dtype, b=b, h=4, h_kv=h_kv)
    loss = _weighted(full_attention, w, **kwargs)

    def run():
        before = attention.dense_attention_slices.snapshot()
        out = full_attention(q, k, v, **kwargs)
        grads = jax.grad(loss, (0, 1, 2))(q, k, v)
        after = attention.dense_attention_slices.snapshot()
        return (out,) + grads, after["sliced_sites"] - before["sliced_sites"]

    monkeypatch.setattr(attention, "SLICE_SCORE_BYTES",
                        int(_score_bytes(per, 4, tq, tk)))
    assert attention.slice_count(b, 4, tq, tk) == slices
    got, sliced_sites = run()
    assert sliced_sites == (3 if slices > 1 else 0)  # fwd, then fwd and bwd
    monkeypatch.setattr(attention, "SLICE_SCORE_BYTES", 2 ** 40)
    want, sliced_sites = run()
    assert sliced_sites == 0
    for g, r in zip(got, want):
        assert g.dtype == r.dtype and g.shape == r.shape
        g, r = (np.asarray(x.astype(jnp.float32)) for x in (g, r))
        assert np.isfinite(g).all()
        np.testing.assert_allclose(g, r, rtol=tol, atol=tol * np.abs(r).max())


def _contiguous_slices(fn, n, *operands):
    """``_over_slices`` with slice ``j`` the ``j``-th contiguous run of the
    batch: what point 2 of ISSUE 30 rules out."""
    views = tuple(x.reshape(n, x.shape[0] // n, *x.shape[1:])
                  for x in operands)

    def on_slice(j):
        return fn(*(jax.lax.dynamic_index_in_dim(x, j, 0, keepdims=False)
                    for x in views))

    def body(j, done):
        return tuple(jax.lax.dynamic_update_index_in_dim(r, o, j, 0)
                     for r, o in zip(done, on_slice(j)))

    done = jax.lax.fori_loop(0, n, body, tuple(
        jnp.zeros((n, *x.shape), x.dtype)
        for x in jax.eval_shape(on_slice, 0)))
    return tuple(x.reshape(n * x.shape[1], *x.shape[2:]) for x in done)


COLLECTIVES = ("all-gather", "all-to-all", "collective-permute")


def test_slices_of_a_sharded_batch_move_nothing_between_devices(monkeypatch):
    """``jax.grad`` of the sliced core with q, k, v spread over ``data`` on
    four devices: interleaved slices leave every example on its device;
    contiguous slices (written here) make GSPMD gather them."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from pytorch_distributed_mnist_tpu.ops import attention

    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    (q, k, v), w = _grad_inputs(16, 16, jnp.float32, b=16, h=4)
    sharded = NamedSharding(mesh, P("data"))
    args = tuple(jax.device_put(x, sharded) for x in (q, k, v))
    monkeypatch.setattr(attention, "SLICE_SCORE_BYTES",
                        _score_bytes(4, 4, 16, 16))
    assert attention.slice_count(16, 4, 16, 16) == 4

    def program():
        fn = jax.jit(jax.grad(_weighted(full_attention, w), (0, 1, 2)),
                     out_shardings=(sharded,) * 3)
        compiled = fn.lower(*args).compile()
        return compiled.as_text(), compiled(*args)

    text, got = program()
    assert " while(" in text
    assert not [c for c in COLLECTIVES if c in text]
    monkeypatch.setattr(attention, "_over_slices", _contiguous_slices)
    jax.clear_caches()  # the sliced passes are jitted: trace them anew
    try:
        contiguous, same = program()
    finally:
        jax.clear_caches()  # and let no later test find these traces
    assert [c for c in COLLECTIVES if c in contiguous]
    for g, r in zip(got, same):
        np.testing.assert_allclose(g, r, rtol=1e-6, atol=1e-6)


def test_a_call_under_the_constant_is_the_unsliced_program(monkeypatch):
    """With the slicing taken out (``slice_count`` always 1) a call under
    the constant lowers to the same text, and one over it does not; the
    process's counter tells a sliced call from a whole one."""
    from pytorch_distributed_mnist_tpu.ops import attention
    from pytorch_distributed_mnist_tpu.utils.profiling import device_report

    (q, k, v), w = _grad_inputs(16, 16, jnp.bfloat16, b=4, h=4)

    def text():
        return jax.jit(jax.grad(_weighted(full_attention, w, causal=True),
                                (0, 1, 2))).lower(q, k, v).as_text()

    def counted(fn):
        before = device_report()["dense_attention_slices"]
        out = fn()
        after = device_report()["dense_attention_slices"]
        assert after == attention.dense_attention_slices.snapshot()
        return out, after["sites"] - before["sites"], (
            after["sliced_sites"] - before["sliced_sites"])

    assert attention.slice_count(4, 4, 16, 16) == 1
    whole, sites, sliced = counted(text)
    assert (sites, sliced) == (2, 0)  # a forward and a backward, both whole
    with monkeypatch.context() as m:
        m.setattr(attention, "slice_count", lambda *a: 1)
        assert text() == whole
    monkeypatch.setattr(attention, "SLICE_SCORE_BYTES",
                        _score_bytes(2, 4, 16, 16))
    sliced_text, sites, sliced = counted(text)
    assert (sites, sliced) == (2, 2)
    assert device_report()["dense_attention_slices"][
        "slices_per_sliced_site"] is not None
    assert sliced_text != whole and "while" in sliced_text
    monkeypatch.setattr(attention, "slice_count", lambda *a: 1)
    assert text() == whole
