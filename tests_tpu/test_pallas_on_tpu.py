"""Pallas kernels on REAL TPU: Mosaic compile + numerics vs XLA oracles.

The hermetic suite (tests/test_pallas_kernels.py) pins the same numerics in
interpret mode; this suite is the hardware half — it catches Mosaic-only
failures (block tiling rules, SMEM refs, lane alignment for the ViT head
dims D=16/32) that interpret mode cannot see. Each of the five kernels is
compiled at one shape the main path uses (what ``chip_smoke.py`` drives).

Oracle comparisons run under ``jax_default_matmul_precision=highest``
because the dense oracle's MXU matmuls otherwise run bf16 passes and the
~5e-3 "error" would be the oracle's, not the kernel's.
"""

import jax
import jax.numpy as jnp
import optax
import pytest

from benchmark.reference.laguna import _attention_core as reference_core
from pytorch_distributed_mnist_tpu.ops.attention import full_attention
from pytorch_distributed_mnist_tpu.ops.pallas.adam import pallas_adam
from pytorch_distributed_mnist_tpu.ops.pallas.flash import flash_attention


@pytest.fixture(autouse=True)
def _highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


# ViT head dim D=16 (sub-128-lane, the flagged Mosaic hazard), a ragged T
# requiring pad+mask, and the smoke's own shape: the CLI's ViT at
# --patch-size 1 (T=784 pads to 896 at block 128; 4 heads of D=16). Each
# case costs several real Mosaic compiles, so the list stays short.
SHAPES = [(2, 64, 4, 16), (1, 200, 2, 32), (2, 784, 4, 16)]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("causal", [False, True])
def test_flash_forward_backward_on_tpu(shape, causal):
    b, t, h, d = shape
    k1, k2, k3 = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(k1, shape, jnp.float32)
    k = jax.random.normal(k2, shape, jnp.float32)
    v = jax.random.normal(k3, shape, jnp.float32)

    def loss(f):
        return lambda *a: jnp.sum(jnp.sin(f(*a, causal=causal)))

    out = flash_attention(q, k, v, causal=causal)
    ref = full_attention(q, k, v, causal=causal)
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-5

    grads = jax.grad(loss(flash_attention), argnums=(0, 1, 2))(q, k, v)
    grads_ref = jax.grad(loss(full_attention), argnums=(0, 1, 2))(q, k, v)
    for g, gr in zip(grads, grads_ref):
        assert float(jnp.max(jnp.abs(g - gr))) < 2e-3


def test_fused_adam_on_tpu_matches_optax():
    params = {
        "w": jnp.ones((3, 3, 1, 32)),
        "b": jnp.zeros((10,)),
        "fc": jnp.ones((12544, 128)),
        "s": jnp.ones((1,)),
    }
    grads = jax.tree.map(lambda p: jnp.full_like(p, 0.1), params)
    opt_a, opt_b = pallas_adam(1e-3), optax.adam(1e-3)
    sa, sb = opt_a.init(params), opt_b.init(params)
    for _ in range(3):
        ua, sa = opt_a.update(grads, sa)
        ub, sb = opt_b.update(grads, sb)
        for x, y in zip(jax.tree.leaves(ua), jax.tree.leaves(ub)):
            assert float(jnp.max(jnp.abs(x - y))) < 1e-6


def test_fused_xent_on_tpu_matches_oracle():
    """Mosaic compile of the xent fwd+bwd kernels; value and grad vs the
    XLA oracle. C=10 (sub-128-lane block) and a ragged batch exercise the
    pad/mask path on real tiling rules."""
    from pytorch_distributed_mnist_tpu.ops.loss import (
        cross_entropy_per_example,
    )
    from pytorch_distributed_mnist_tpu.ops.pallas.xent import (
        fused_cross_entropy_per_example,
    )

    k1, k2 = jax.random.split(jax.random.key(1))
    for b in (256, 300):
        logits = jax.random.normal(k1, (b, 10), jnp.float32) * 5
        labels = jax.random.randint(k2, (b,), 0, 10)
        g = jax.random.normal(k2, (b,), jnp.float32)

        want, vjp_o = jax.vjp(
            lambda l: cross_entropy_per_example(l, labels), logits)
        got, vjp_k = jax.vjp(
            lambda l: fused_cross_entropy_per_example(l, labels), logits)
        assert float(jnp.max(jnp.abs(got - want))) < 1e-5
        dl_want = vjp_o(g)[0]
        dl_got = vjp_k(g)[0]
        # Backward tolerance is wider than interpret mode's 1e-5
        # (tests/test_pallas_kernels.py): the kernel computes softmax as
        # one exp(l - lse) while the oracle's autodiff divides
        # exp(l - m) by the saved sum, and the chip's f32 transcendental
        # rounding differs from the host's — the divergence on these
        # x5-scaled logits stays under 1e-4 on the v5e; algorithmic
        # regressions are caught at 1e-5 hermetically.
        assert float(jnp.max(jnp.abs(dl_got - dl_want))) < 2e-4


# The int8 serving plane's Dense contractions on the CNN at the server's
# buckets: fc1 (B, 12544) x (12544, 128) and fc2 (B, 128) x (128, 10).
I8_SHAPES = [(8, 12544, 128), (128, 12544, 128), (32, 128, 10)]


@pytest.mark.parametrize("m,k,n", I8_SHAPES)
def test_matmul_i8_on_tpu_matches_dot_general(m, k, n):
    """Mosaic compile of the int8 MXU kernel. The integer contraction is
    exact, so the kernel must EQUAL ``lax.dot_general`` on the same int8
    operands; the quantize-matmul-rescale drop-in is then held to the
    quantization error of its two per-tensor scales against f32."""
    from pytorch_distributed_mnist_tpu.ops.pallas.matmul_i8 import (
        int8_dot_general,
        matmul_i8,
    )

    k1, k2 = jax.random.split(jax.random.key(2))
    a = jax.random.randint(k1, (m, k), -127, 128, jnp.int32).astype(jnp.int8)
    b = jax.random.randint(k2, (k, n), -127, 128, jnp.int32).astype(jnp.int8)
    dims = (((1,), (0,)), ((), ()))
    want = jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.int32)
    got = matmul_i8(a, b)
    assert got.dtype == jnp.int32 and got.shape == (m, n)
    assert bool(jnp.all(got == want))

    x = jax.random.normal(k1, (m, k), jnp.float32)
    w = jax.random.normal(k2, (k, n), jnp.float32) * k ** -0.5
    ref = jax.lax.dot_general(x, w, dims)
    out = int8_dot_general(x, w, dims)
    # Each operand rounds to 1/254 of its max; over K terms of unit
    # variance the error's std is ~sqrt(K) * max|x| max|w| / 254 / sqrt(3)
    # per operand — bound it by the worst-case linear term instead.
    tol = (float(jnp.max(jnp.abs(x))) * float(jnp.max(jnp.abs(w)))
           * (k ** 0.5) * 2 / 254)
    assert float(jnp.max(jnp.abs(out - ref))) < tol


def test_no_kernel_was_interpreted():
    """Runs last in this file: every pallas_call traced above was lowered
    through Mosaic — the interpreter is for the CPU backend only."""
    from pytorch_distributed_mnist_tpu.utils.profiling import (
        pallas_lowerings,
    )

    counts = pallas_lowerings.snapshot()
    assert counts["interpret"] == 0 and counts["mosaic"] > 0, counts


# The decoder's attention at its head size and grouping, T = 1,024 (two
# blocks of 512, so the window's band and the causal triangle both skip a
# block): bf16 operands as the training cell passes them, against the dense
# masked oracle. The last case is the training cell's own window layer, 64
# query heads on 8 key-value heads, at T = 2,048 (8,192 is too large for
# the dense oracle in one piece): four blocks, so the folded schedule's
# first block (no edge), folded blocks and, in the backward kernel, which
# goes by key block, last block (no later query block) all occur; the one
# before it folds at block 128, where a quadrant is half a vector register
# wide.
# (B, T, H_q, H_kv, window).
WINDOW_CASES = [(2, 1024, 8, 1, 512), (2, 1024, 6, 1, None),
                (2, 1024, 16, 2, 200), (2, 256, 4, 2, 128),
                (1, 2048, 64, 8, 512)]


@pytest.mark.parametrize("b,t,heads,kv_heads,window", WINDOW_CASES)
def test_flash_window_grouped_heads_on_tpu(b, t, heads, kv_heads, window):
    d = 128
    ks = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(ks[0], (b, t, heads, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, t, kv_heads, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, t, kv_heads, d), jnp.bfloat16)

    def loss(f):
        return lambda *a: jnp.sum(jnp.sin(
            f(*a, causal=True, window=window).astype(jnp.float32)))

    out = flash_attention(q, k, v, causal=True, window=window)
    ref = full_attention(q, k, v, causal=True, window=window)
    assert out.dtype == jnp.bfloat16
    assert float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                 - ref.astype(jnp.float32)))) < 3e-2

    grads = jax.grad(loss(flash_attention), argnums=(0, 1, 2))(q, k, v)
    grads_ref = jax.grad(loss(full_attention), argnums=(0, 1, 2))(q, k, v)
    for g, gr in zip(grads, grads_ref):
        gr = gr.astype(jnp.float32)
        err = float(jnp.max(jnp.abs(g.astype(jnp.float32) - gr)))
        assert err < 0.03 * float(jnp.max(jnp.abs(gr))) + 1e-2


# The one backward kernel at the training cells' lengths, head size and
# grouping, two key-value heads of each: Laguna's full layer (6 query heads
# a key-value head, 16 key blocks) and window layer (8 a head, folded),
# Phi-4-mini-flash's full and cross layers (2 a head, 32 key blocks, the
# dQ accumulator 8 MB). The oracle is the benchmark's plain reference core,
# float32 in query blocks that the backward recomputes one at a time: the
# whole ``(T, T)`` scores here are 3 GB a tensor. (T, H_q, H_kv, window).
CELL_CASES = [(8192, 12, 2, None), (8192, 16, 2, 512), (16384, 4, 2, None)]


@pytest.mark.parametrize("t,heads,kv_heads,window", CELL_CASES)
def test_flash_gradients_at_the_cells_lengths_on_tpu(t, heads, kv_heads,
                                                     window):
    d = 128
    ks = jax.random.split(jax.random.key(3), 3)
    q = jax.random.normal(ks[0], (1, t, heads, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, t, kv_heads, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, t, kv_heads, d), jnp.bfloat16)

    def grads(f):
        return jax.jit(jax.grad(lambda *a: jnp.sum(jnp.sin(
            f(*a).astype(jnp.float32))), argnums=(0, 1, 2)))(q, k, v)

    got = grads(lambda *a: flash_attention(*a, causal=True, window=window))
    want = grads(lambda *a: reference_core(
        *(x.astype(jnp.float32) for x in a), window))
    for name, g, gr in zip(("dq", "dk", "dv"), got, want):
        assert g.dtype == jnp.bfloat16, name
        gr = gr.astype(jnp.float32)
        err = float(jnp.max(jnp.abs(g.astype(jnp.float32) - gr)))
        assert err < 0.03 * float(jnp.max(jnp.abs(gr))) + 1e-2, (name, err)


# The selective scan's two kernels (ops/pallas/ssm.py) at the tiny preset's
# shape (one piece of 128 channels, 4 states) and at the training cell's
# widths on a shorter sequence (ten pieces of 512 channels, 16 states, four
# chunks of 256 positions, so the state and its gradient cross chunk and
# piece boundaries), against the recurrence position by position.
# (B, T, C, N).
SCAN_CASES = [(2, 1024, 128, 4), (1, 1024, 5120, 16)]


@pytest.mark.parametrize("b,t,c,n", SCAN_CASES)
def test_selective_scan_on_tpu_matches_the_loop(b, t, c, n):
    from pytorch_distributed_mnist_tpu.ops.ssm import selective_scan

    ks = jax.random.split(jax.random.key(2), 7)
    a = jax.random.normal(ks[0], (b, t, c))
    dt = jnp.exp(jax.random.uniform(ks[1], (b, t, c)) * 4.6 - 6.9)
    A = -jnp.broadcast_to(jnp.arange(1.0, n + 1), (c, n))
    B = jax.random.normal(ks[2], (b, t, n))
    C = jax.random.normal(ks[3], (b, t, n))
    D = jax.random.normal(ks[4], (c,))
    weight = jax.random.normal(ks[5], (b, t, c))

    def loop(a, dt, A, B, C, D):
        def step(s, x):
            a_t, dt_t, b_t, c_t = x
            s = jnp.exp(dt_t[:, None] * A) * s \
                + (dt_t * a_t)[:, None] * b_t[None]
            return s, jnp.sum(s * c_t[None], axis=-1) + D * a_t

        return jax.vmap(lambda a, dt, B, C: jax.lax.scan(
            step, jnp.zeros(A.shape), (a, dt, B, C))[1])(a, dt, B, C)

    def rel(x, y):
        return float(jnp.max(jnp.abs(x - y)) / jnp.max(jnp.abs(y)))

    args = (a, dt, A, B, C, D)
    assert rel(jax.jit(selective_scan)(*args), jax.jit(loop)(*args)) < 1e-4
    grads, want = (
        jax.jit(jax.grad(lambda *xs: jnp.sum(f(*xs) * weight),
                         argnums=range(6)))(*args)
        for f in (selective_scan, loop))
    for name, g, w in zip("a dt A B C D".split(), grads, want):
        assert rel(g, w) < 1e-3, name


# The rotary's kernel (ops/pallas/rope.py) at the training cell's two kinds
# of layer, queries and keys: 64 heads with all 128 lanes rotated (one
# rotation of the lanes) and 48 with 64 (two rotations and a select), 8
# key-value heads of each, bf16 at (2, 8192). The oracle is the body
# ``apply_rope`` had before, slices of a head and a concatenate
# (``tests/test_rope.py sliced``), and its ``jax.grad``: the same float32
# products in the same order, so at most one bfloat16 rounding apart.
# (heads, kind of layer).
ROPE_CASES = [(64, "sliding_attention"), (8, "sliding_attention"),
              (48, "full_attention"), (8, "full_attention")]


@pytest.mark.parametrize("heads,kind", ROPE_CASES)
def test_rotary_on_whole_heads_on_tpu(heads, kind):
    import json

    from pytorch_distributed_mnist_tpu.models import decoder
    from pytorch_distributed_mnist_tpu.utils.profiling import (
        pallas_lowerings,
        rotary_sites,
    )
    from tests.test_rope import sliced

    with open("benchmark/configs/laguna-xs2-ep8.json") as f:
        params = json.load(f)["rope_parameters"][kind]
    inv_freq, factor = decoder.rope_frequencies(128, params)
    rot = 2 * len(inv_freq)

    ks = jax.random.split(jax.random.key(4), 2)
    x = jax.random.normal(ks[0], (2, 8192, heads, 128), jnp.bfloat16)
    weight = jax.random.normal(ks[1], x.shape, jnp.bfloat16)
    before = pallas_lowerings.snapshot(), rotary_sites.snapshot()

    def both(f):
        return jax.jit(jax.value_and_grad(lambda x: jnp.sum(
            (f(x) * weight).astype(jnp.float32)), has_aux=False))(x)[1], \
            jax.jit(f)(x)

    (g, y), (g_ref, y_ref) = (both(f) for f in (
        lambda x: decoder.apply_rope(x, inv_freq, factor),
        lambda x: sliced(x, inv_freq, factor)))
    after = pallas_lowerings.snapshot(), rotary_sites.snapshot()
    assert after[0]["interpret"] == before[0]["interpret"]
    assert after[0]["mosaic"] > before[0]["mosaic"]
    assert after[1]["whole_head_sites"] - before[1]["whole_head_sites"] \
        == after[1]["sites"] - before[1]["sites"] == 3  # y; y and g
    for name, got, want in (("values", y, y_ref), ("gradient", g, g_ref)):
        assert got.dtype == jnp.bfloat16, name
        got, want = (a.astype(jnp.float32) for a in (got, want))
        assert bool(jnp.all(jnp.abs(got - want)
                            <= 2.0 ** -7 * jnp.abs(want))), name
    assert bool(jnp.all(y[..., rot:] == x[..., rot:]))
    assert bool(jnp.all(g[..., rot:] == weight[..., rot:]))
