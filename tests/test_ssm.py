"""The chunked selective scan (``ops/ssm.py``) against the recurrence
written position by position: values and all six gradients, at lengths that
are and are not multiples of the chunk, at two chunk lengths and at the one
the shape gives; the chunk length as a function of the shape; the counter."""

import jax
import jax.numpy as jnp
import pytest

from pytorch_distributed_mnist_tpu.ops.pallas import ssm as kernels
from pytorch_distributed_mnist_tpu.ops.pallas.ssm import chunk_length
from pytorch_distributed_mnist_tpu.ops.ssm import selective_scan
from pytorch_distributed_mnist_tpu.utils.profiling import (
    device_report,
    scan_log,
)

NAMES = ("a", "dt", "A", "B", "C", "D")


def step_by_step(a, dt, A, B, C, D):
    """``s_t = exp(dt_t A) s_{t-1} + dt_t B_t a_t``; ``m_t = C_t . s_t + D
    a_t``, one position after the other."""
    def step(s, x):
        a_t, dt_t, b_t, c_t = x
        s = jnp.exp(dt_t[:, None] * A) * s + (dt_t * a_t)[:, None] * b_t[None]
        return s, jnp.sum(s * c_t[None], axis=-1) + D * a_t

    def one(a, dt, B, C):
        return jax.lax.scan(step, jnp.zeros(A.shape), (a, dt, B, C))[1]

    return jax.vmap(one)(a, dt, B, C)


def operands(t, seed=0, b=2, c=24, n=4):
    k = jax.random.split(jax.random.key(seed), 7)
    return (jax.random.normal(k[0], (b, t, c)),
            jax.nn.softplus(jax.random.normal(k[1], (b, t, c))),
            -jnp.exp(jax.random.normal(k[2], (c, n))),
            jax.random.normal(k[3], (b, t, n)),
            jax.random.normal(k[4], (b, t, n)),
            jax.random.normal(k[5], (c,))), jax.random.normal(k[6], (b, t, c))


def rel(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("t, chunk", [
    (32, 8), (32, 16), (37, 8), (37, 16), (5, None), (37, None)])
def test_values_and_all_six_gradients_match_the_loop(t, chunk):
    xs, weight = operands(t, seed=t)
    got = selective_scan(*xs, chunk=chunk)
    want = step_by_step(*xs)
    assert got.shape == want.shape and rel(got, want) < 1e-5
    grads = [jax.grad(lambda *xs: jnp.sum(f(*xs) * weight),
                      argnums=range(6))(*xs)
             for f in (lambda *xs: selective_scan(*xs, chunk=chunk),
                       step_by_step)]
    for name, g, w in zip(NAMES, *grads):
        assert g.shape == w.shape and rel(g, w) < 1e-5, name


def test_the_state_crosses_chunk_boundaries():
    """With a decay near 1 the last position's output depends on the first
    position's input, eight chunks back."""
    xs, _ = operands(64)
    a, dt, A, B, C, D = xs
    slow = (a, dt * 1e-2, A, B, C, D)
    moved = (a.at[:, 0].add(1.0),) + slow[1:]
    delta = selective_scan(*moved, chunk=8) - selective_scan(*slow, chunk=8)
    assert float(jnp.max(jnp.abs(delta[:, -1]))) > 1e-4
    assert rel(selective_scan(*moved, chunk=8), step_by_step(*moved)) < 1e-5


def test_bfloat16_operands_keep_a_float32_state():
    xs, _ = operands(32)
    low = tuple(x.astype(jnp.bfloat16) if i in (0, 3, 4) else x
                for i, x in enumerate(xs))
    got = selective_scan(*low)
    assert got.dtype == jnp.bfloat16
    want = step_by_step(*(x.astype(jnp.float32) for x in low))
    assert rel(got.astype(jnp.float32), want) < 1e-2


def test_chunk_length_follows_from_the_shape():
    # the benchmark's shape: pieces of 512 channels, 256 positions of
    # (16, 512) float32 are the 8 MiB of states the backward recomputes
    assert kernels.piece_width(5120) == 512
    assert chunk_length(16384, 5120, 16) == 256
    assert 256 * 16 * 512 * 4 == kernels.CHUNK_STATE_BYTES
    assert kernels.piece_width(128) == 128 and kernels.piece_width(768) == 256
    assert chunk_length(64, 128, 4) == 64  # no longer than the sequence
    assert chunk_length(5, 128, 4) == 8 and chunk_length(37, 24, 4) == 64
    assert chunk_length(1 << 20, 128, 4) == 4096


def test_a_scan_is_counted_with_its_chunks_and_kept_state():
    xs, _ = operands(37)
    before = scan_log.snapshot()
    selective_scan(*xs, chunk=8)
    after = scan_log.snapshot()
    assert after["sites"] == before["sites"] + 1
    # 5 chunks of 8 cover 37 positions; 2 sequences x 5 x (24, 4) float32
    def totals(s):
        return (s["sites"] * (s["chunks_per_site"] or 0),
                s["sites"] * (s["state_bytes_kept_per_site"] or 0))

    assert totals(after)[0] - totals(before)[0] == pytest.approx(5)
    assert totals(after)[1] - totals(before)[1] \
        == pytest.approx(2 * 5 * 24 * 4 * 4)
    assert device_report()["state_scans"]["sites"] == after["sites"]


@pytest.fixture(scope="module")
def one_chip():
    """A described v5e chip (the TPU's compiler is installed here and
    compiles for a chip that is not attached); skipped where it cannot be
    described. Only this file's worker loads the library."""
    import os

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:  # no compiler, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
    return SingleDeviceSharding(topo.devices[0])


def test_the_kernels_compile_at_the_cells_shape_and_hold_no_states(
        one_chip, monkeypatch):
    """Both kernels through Mosaic at (1, 16384, 5120), N = 16: what the
    interpreter cannot refuse (tiling, VMEM), and that the compiled
    gradient's temporaries are a fraction of the 5.4 GB of states."""
    monkeypatch.setattr(kernels, "should_interpret", lambda: False)
    t, c, n = 16384, 5120, 16

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    args = (shape((1, t, c), jnp.bfloat16), shape((1, t, c), jnp.float32),
            shape((c, n), jnp.float32), shape((1, t, n), jnp.bfloat16),
            shape((1, t, n), jnp.bfloat16), shape((c,), jnp.float32))

    def loss(*xs):
        return jnp.sum(selective_scan(*xs).astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=range(6))).lower(
        *args).compile()
    text = compiled.as_text()
    assert "selective_scan_fwd" in text and "selective_scan_bwd" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2e9
    assert f"[1,{t},{n},{c}]" not in text and f"[1,{t},{c},{n}]" not in text


def test_no_op_of_xlas_carries_the_flash_backwards_scope(one_chip,
                                                         monkeypatch):
    """In this file because it is the one that describes the chip. The
    benchmark counts a backward call for every device op whose scope names
    the backward kernel (``benchmark/scopes_lm.py kernel_of``), and a copy
    that XLA puts behind a kernel inherits the kernel's scope: with dK and
    dV viewed as ``(H/G, G, D)`` for the group's sum, a group of 6 cost two
    such copies a call and ``flash_bwd_roofline`` read three calls for
    one. Compiled among free layouts (projections before and behind, as
    in ``models/decoder.py``), the kernel is alone under its name."""
    import re

    from pytorch_distributed_mnist_tpu.ops.pallas import flash

    monkeypatch.setattr(flash, "should_interpret", lambda: False)
    b, t, h, kv, d, width = 1, 1024, 12, 2, 128, 256

    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.bfloat16, sharding=one_chip)

    def loss(x, wq, wk, wv, wo):
        q, k, v = ((x @ w).reshape(b, t, -1, d) for w in (wq, wk, wv))
        o = flash.flash_attention(q, k, v, causal=True)
        return jnp.sum((o.reshape(b, t, h * d) @ wo).astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=range(5))).lower(
        shape(b, t, width), shape(width, h * d), shape(width, kv * d),
        shape(width, kv * d), shape(h * d, width)).compile().as_text()
    under = re.findall(
        r"= \S+ ([\w-]+)\([^\n]*op_name=\"[^\"]*flash_bwd", text)
    assert under and set(under) <= {"custom-call", "get-tuple-element"}
