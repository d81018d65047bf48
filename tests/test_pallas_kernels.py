"""Pallas kernels vs their XLA/optax oracles (interpret mode on CPU).

Every kernel runs in interpreter mode on the CPU backend
(``ops/pallas/backend.py`` decides, from ``jax.default_backend()``), so
these tests exercise the identical kernel bodies that compile on real
chips.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from pytorch_distributed_mnist_tpu.ops.attention import full_attention
from pytorch_distributed_mnist_tpu.ops.pallas.adam import fused_adam_leaf, pallas_adam
from pytorch_distributed_mnist_tpu.ops.pallas.flash import flash_attention
from pytorch_distributed_mnist_tpu.ops.pallas.matmul_i8 import (
    int8_dot_general,
    matmul_i8,
    quantize_dynamic_i8,
)
from pytorch_distributed_mnist_tpu.train.state import create_train_state, make_optimizer
from pytorch_distributed_mnist_tpu.models import get_model
from pytorch_distributed_mnist_tpu.train.steps import make_train_step


# ------------------------------------------------------- lowering decision

def test_should_interpret_cpu_true_tpu_false_else_error(monkeypatch):
    """Interpret only on ``cpu``; ``tpu`` lowers through Mosaic; any other
    platform is an error, never a quiet interpret. Each decision is
    counted for the run summary / /healthz."""
    from pytorch_distributed_mnist_tpu.ops.pallas.backend import (
        should_interpret,
    )
    from pytorch_distributed_mnist_tpu.utils.profiling import (
        pallas_lowerings,
    )

    before = pallas_lowerings.snapshot()
    assert jax.default_backend() == "cpu" and should_interpret() is True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert should_interpret() is False
    after = pallas_lowerings.snapshot()
    assert after == {"mosaic": before["mosaic"] + 1,
                     "interpret": before["interpret"] + 1}
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu'"):
        should_interpret()
    assert pallas_lowerings.snapshot() == after


# ---------------------------------------------------------------- fused adam

def test_pallas_adam_shard_maps_its_kernel_on_a_multi_device_mesh(mesh8):
    """GSPMD cannot partition a Mosaic kernel (real multi-chip lowering
    refuses it; the CPU interpreter never sees that rule), so with a mesh
    of more than one device the update wraps each leaf's kernel in a
    shard_map — same numbers, and no wrapper without a mesh."""
    params = {"w": jnp.ones((16, 8)), "b": jnp.zeros((8,))}
    grads = jax.tree.map(lambda p: jnp.full_like(p, 0.25), params)
    plain, meshed = pallas_adam(1e-3), pallas_adam(1e-3, mesh=mesh8)
    state = plain.init(params)
    assert "shard_map" not in str(jax.make_jaxpr(plain.update)(grads, state))
    assert "shard_map" in str(jax.make_jaxpr(meshed.update)(grads, state))
    want, _ = plain.update(grads, state)
    got, _ = jax.jit(meshed.update)(grads, state)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("shape", [(7,), (32, 10), (3, 3, 8, 5), ()])
def test_fused_adam_leaf_matches_optax(shape):
    """Kernel == optax.adam update for one leaf, any shape incl. scalar."""
    rng = np.random.default_rng(0)
    p = jnp.asarray(rng.normal(size=shape), jnp.float32)
    g = jnp.asarray(rng.normal(size=shape), jnp.float32)
    lr, b1, b2, eps = 1e-3, 0.9, 0.999, 1e-8

    tx = optax.adam(lr, b1=b1, b2=b2, eps=eps)
    state = tx.init(p)
    want_delta, state = tx.update(g, state, p)

    m = jnp.zeros(shape, jnp.float32)
    v = jnp.zeros(shape, jnp.float32)
    hypers = jnp.asarray(
        [lr, b1, b2, eps, 1 / (1 - b1), 1 / (1 - b2), 1 - b1, 1 - b2, 0.0],
        jnp.float32,
    )
    delta, m1, v1 = fused_adam_leaf(g, m, v, hypers)
    adam_state = state[0]  # optax.adam = chain(scale_by_adam, scale)
    np.testing.assert_allclose(delta, want_delta, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(m1, adam_state.mu, rtol=1e-6, atol=1e-8)
    np.testing.assert_allclose(v1, adam_state.nu, rtol=1e-6, atol=1e-8)


def test_pallas_adam_transform_matches_optax_over_steps():
    """Full transform: 5 steps on a pytree track optax.adam elementwise."""
    rng = np.random.default_rng(1)
    params = {
        "w": jnp.asarray(rng.normal(size=(13, 4)), jnp.float32),
        "b": jnp.asarray(rng.normal(size=(4,)), jnp.float32),
    }
    ref_tx = optax.adam(1e-2)
    pal_tx = pallas_adam(1e-2)
    ref_state, pal_state = ref_tx.init(params), pal_tx.init(params)
    ref_p = pal_p = params
    for i in range(5):
        g = jax.tree_util.tree_map(
            lambda p: jnp.asarray(rng.normal(size=p.shape), jnp.float32), params
        )
        ref_u, ref_state = ref_tx.update(g, ref_state, ref_p)
        pal_u, pal_state = pal_tx.update(g, pal_state, pal_p)
        ref_p = optax.apply_updates(ref_p, ref_u)
        pal_p = optax.apply_updates(pal_p, pal_u)
    for a, b in zip(jax.tree_util.tree_leaves(ref_p),
                    jax.tree_util.tree_leaves(pal_p)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)


def test_adam_pallas_trains_end_to_end():
    """A jitted train step with the fused optimizer learns on a fixed batch."""
    model = get_model("cnn")
    state = create_train_state(model, jax.random.key(0), optimizer="adam_pallas")
    step = make_train_step()
    rng = np.random.default_rng(2)
    batch = {
        "image": jnp.asarray(rng.normal(size=(16, 28, 28, 1)), jnp.float32),
        "label": jnp.asarray(rng.integers(0, 10, size=(16,)), jnp.int32),
    }
    losses = []
    for _ in range(8):
        state, m = step(state, batch)
        losses.append(float(m.loss_sum) / float(m.count))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


def test_adam_pallas_checkpoint_state_shape_matches_adam():
    """Same opt_state pytree as stock adam -> checkpoints interchangeable."""
    model = get_model("linear")
    s1 = create_train_state(model, jax.random.key(0), optimizer="adam")
    s2 = create_train_state(model, jax.random.key(0), optimizer="adam_pallas")
    t1 = jax.tree_util.tree_structure(s1.opt_state)
    t2 = jax.tree_util.tree_structure(s2.opt_state)
    assert t1 == t2


# ----------------------------------------------------------- flash attention

@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("t", [64, 256])
def test_flash_attention_matches_dense(causal, t):
    ks = jax.random.split(jax.random.key(3), 3)
    q, k, v = (jax.random.normal(kk, (2, t, 4, 32), jnp.float32) for kk in ks)
    out = flash_attention(q, k, v, causal=causal)
    ref = full_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("t", [64, 256])
def test_flash_attention_grad_matches_dense(causal, t):
    """Fused Pallas backward (one kernel: dq, dk and dv of each tile) ==
    vjp of the dense oracle."""
    ks = jax.random.split(jax.random.key(4), 3)
    q, k, v = (jax.random.normal(kk, (1, t, 2, 16), jnp.float32) for kk in ks)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(full_attention(q, k, v, causal=causal) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("block", [64, 256])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_block_override(block, causal):
    """Numerics are block-size invariant (fwd AND bwd): whatever tile
    edge the ``block`` override names, any size must produce the same
    attention, including when the
    block exceeds T (256 > 192: single padded tile) and when it divides
    T unevenly (64 into 192)."""
    t = 192
    ks = jax.random.split(jax.random.key(9), 3)
    q, k, v = (jax.random.normal(kk, (1, t, 2, 16), jnp.float32) for kk in ks)

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, causal=causal, block=block) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(full_attention(q, k, v, causal=causal) ** 2)

    out = flash_attention(q, k, v, causal=causal, block=block)
    ref = full_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)
    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def test_flash_attention_block_bounds_rejected():
    """The block override is bounded on both ends: non-multiple-of-8
    below, and >512 above (the block^2 f32 VMEM scratch would blow the
    ~16 MB/core budget with an opaque Mosaic error instead of this
    message — round-3 advisor finding)."""
    ks = jax.random.split(jax.random.key(9), 3)
    q, k, v = (jax.random.normal(kk, (1, 64, 2, 16), jnp.float32)
               for kk in ks)
    with pytest.raises(ValueError, match="multiple of 8"):
        flash_attention(q, k, v, block=20)
    with pytest.raises(ValueError, match="<= 512"):
        flash_attention(q, k, v, block=1024)


@pytest.mark.parametrize("t", [49, 200])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_grad_unaligned_lengths(t, causal):
    """Backward kernels mask padded rows/cols exactly like the forward."""
    ks = jax.random.split(jax.random.key(6), 3)
    q, k, v = (jax.random.normal(kk, (1, t, 2, 16), jnp.float32) for kk in ks)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(full_attention(q, k, v, causal=causal) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def test_flash_attention_bwd_never_materializes_scores():
    """No (T, T) intermediate anywhere in the grad program.

    With T=256 and 128-blocks, a dense-recompute backward would carry a
    (..., 256, 256) score matrix; the fused kernels only ever hold
    (128, 128) tiles. Checked on the whole grad jaxpr."""
    ks = jax.random.split(jax.random.key(7), 3)
    t = 256
    q, k, v = (jax.random.normal(kk, (1, t, 2, 16), jnp.float32) for kk in ks)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v) ** 2)

    jaxpr = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v))
    assert f"{t},{t}" not in jaxpr
    # One kernel forward and one backward: each tile's scores and dp are
    # evaluated once for all three gradients. The backward's name begins
    # ``flash_bwd_dq``, under which benchmark/scopes_lm.py finds its time.
    # (``flash_out`` and ``flash_lse`` are the names of the forward's results.)
    assert re.findall(r"flash_(?:fwd|bwd)\w*", jaxpr) == [
        "flash_fwd", "flash_bwd_dq_dkv"]


def test_flash_attention_rejects_cross_attention_shapes():
    """Tq != Tk raises: the kernel's causal mask alignment assumes Tq == Tk."""
    k1, k2 = jax.random.split(jax.random.key(8))
    q = jax.random.normal(k1, (1, 32, 2, 16), jnp.float32)
    k = v = jax.random.normal(k2, (1, 64, 2, 16), jnp.float32)
    with pytest.raises(ValueError, match="Tq == Tk"):
        flash_attention(q, k, v)


@pytest.mark.parametrize("t", [49, 127, 200])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_unaligned_lengths(t, causal):
    """Odd/prime T pads to a block multiple with masked tail positions."""
    ks = jax.random.split(jax.random.key(5), 3)
    q, k, v = (jax.random.normal(kk, (1, t, 4, 16), jnp.float32) for kk in ks)
    np.testing.assert_allclose(
        flash_attention(q, k, v, causal=causal),
        full_attention(q, k, v, causal=causal),
        rtol=1e-5, atol=1e-5,
    )


def test_fused_adam_bf16_grads_keep_f32_moments():
    """bf16 gradients must not demote the f32 moment buffers."""
    g = jnp.ones((10,), jnp.bfloat16)
    m = jnp.zeros((10,), jnp.float32)
    v = jnp.zeros((10,), jnp.float32)
    hypers = jnp.asarray(
        [1e-3, 0.9, 0.999, 1e-8, 10.0, 1000.0, 0.1, 0.001, 0.0], jnp.float32
    )
    delta, m1, v1 = fused_adam_leaf(g, m, v, hypers)
    assert delta.dtype == jnp.bfloat16
    assert m1.dtype == jnp.float32 and v1.dtype == jnp.float32


# --------------------------------------------------------- int8 MXU matmul

@pytest.mark.parametrize("shape", [(5, 7, 11), (128, 64, 10), (33, 200, 130)])
def test_matmul_i8_exact_integer_oracle(shape):
    """int8 x int8 -> int32 is EXACT integer arithmetic (the int32
    accumulator never rounds), so the kernel must equal np.matmul
    bit-for-bit — including the unaligned shapes that exercise the
    (32, 128) tile padding, whose zero rows/lanes contribute nothing."""
    m, k, n = shape
    rng = np.random.default_rng(10)
    a = rng.integers(-127, 128, size=(m, k), dtype=np.int8)
    b = rng.integers(-127, 128, size=(k, n), dtype=np.int8)
    out = matmul_i8(jnp.asarray(a), jnp.asarray(b))
    want = np.matmul(a.astype(np.int32), b.astype(np.int32))
    assert out.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(out), want)


def test_matmul_i8_rejects_non_int8_operands():
    a = jnp.zeros((4, 8), jnp.float32)
    b = jnp.zeros((8, 4), jnp.int8)
    with pytest.raises(ValueError, match="int8 operands"):
        matmul_i8(a, b)


def test_quantize_dynamic_i8_roundtrip():
    """Symmetric per-tensor quantization: values stay in [-127, 127],
    the dequantized round-trip lands within half a quantization step,
    and the extremum maps onto the grid end exactly."""
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.normal(size=(64, 32)), jnp.float32)
    q, scale = quantize_dynamic_i8(x)
    assert q.dtype == jnp.int8 and float(scale) > 0
    qn = np.asarray(q, np.int32)
    assert qn.min() >= -127 and qn.max() <= 127
    np.testing.assert_allclose(
        qn.astype(np.float32) * float(scale), np.asarray(x),
        atol=float(scale) / 2 + 1e-7)
    assert np.max(np.abs(qn)) == 127  # the extremum pins the grid end


def test_int8_dot_general_matches_dequant_oracle():
    """The Dense contraction through the kernel == quantize-then-f32-
    matmul, tightly: the int32 accumulation is exact where the f32
    oracle rounds, so any gap beyond f32 epsilon is a kernel bug. The
    loose pin vs the unquantized f32 product bounds total quantization
    error (per-tensor scales over K=64 terms)."""
    rng = np.random.default_rng(12)
    lhs = jnp.asarray(rng.normal(size=(4, 6, 64)), jnp.float32)
    rhs = jnp.asarray(rng.normal(size=(64, 10)), jnp.float32)
    dn = (((2,), (0,)), ((), ()))
    out = int8_dot_general(lhs, rhs, dn)
    assert out.shape == (4, 6, 10) and out.dtype == jnp.float32
    qa, sa = quantize_dynamic_i8(lhs.reshape(-1, 64))
    qb, sb = quantize_dynamic_i8(rhs)
    oracle = (qa.astype(jnp.float32) * sa) @ (qb.astype(jnp.float32) * sb)
    np.testing.assert_allclose(
        np.asarray(out).reshape(-1, 10), np.asarray(oracle),
        rtol=1e-5, atol=1e-5)
    ref = jax.lax.dot_general(lhs, rhs, dn)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=0.5)


def test_int8_dot_general_falls_back_verbatim_on_batch_dims():
    """Any contraction that is not the plain Dense shape (here: batched
    einsum) must be lax.dot_general UNCHANGED — bitwise, not allclose —
    so wiring the kernel through a model's dot_general field can never
    miscompute a contraction it wasn't built for."""
    rng = np.random.default_rng(13)
    lhs = jnp.asarray(rng.normal(size=(2, 8, 16)), jnp.float32)
    rhs = jnp.asarray(rng.normal(size=(2, 16, 4)), jnp.float32)
    dn = (((2,), (1,)), ((0,), (0,)))
    out = int8_dot_general(lhs, rhs, dn)
    ref = jax.lax.dot_general(lhs, rhs, dn)
    np.testing.assert_array_equal(
        np.asarray(out).view(np.uint32), np.asarray(ref).view(np.uint32))


def test_int8_dot_general_injects_through_model_field():
    """End-to-end through the serving wiring: get_model(...,
    dot_general=int8_dot_general) — the int8 plane's injection — keeps
    the linear model's logits within quantization error of the plain
    instance on the SAME checkpoint tree, preserving argmax."""
    model = get_model("linear")
    params = model.init(jax.random.key(0), jnp.zeros((1, 28, 28, 1)))["params"]
    rng = np.random.default_rng(14)
    x = jnp.asarray(rng.random(size=(16, 28, 28, 1)), jnp.float32)
    plain = model.apply({"params": params}, x, train=False)
    quant = get_model("linear", dot_general=int8_dot_general).apply(
        {"params": params}, x, train=False)
    np.testing.assert_allclose(
        np.asarray(quant), np.asarray(plain), atol=0.05)
    assert float(jnp.mean(
        jnp.argmax(quant, -1) == jnp.argmax(plain, -1))) >= 0.9
