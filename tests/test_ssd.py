"""The chunked state-space scan (``ops/ssd.py``: Mamba-2's recurrence as
matrix products a chunk) against the recurrence written position by
position: values and all six gradients, at lengths that are and are not
multiples of the chunk, at two chunk lengths, in one chunk and at the
length the shape gives, at every head size a block of lanes holds whole,
in float32 and bfloat16, under ``jax.checkpoint``, and with decays large
enough that a ratio of exponentials would overflow; the chunk length as a
function of the shape; the counter."""

import jax
import jax.numpy as jnp
import pytest

from pytorch_distributed_mnist_tpu.ops.pallas import ssd as kernels
from pytorch_distributed_mnist_tpu.ops.ssd import ssd_scan
from pytorch_distributed_mnist_tpu.utils.profiling import (
    device_report,
    scan_log,
)

NAMES = ("x", "dt", "A", "B", "C", "D")


def step_by_step(x, dt, A, B, C, D):
    """``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``; ``y_t = S_t C_t + D
    x_t``, a head's state (P, N), one position after the other."""
    def step(s, xs):
        x_t, dt_t, b_t, c_t = xs  # (H, P), (H,), (N,), (N,)
        s = jnp.exp(dt_t * A)[:, None, None] * s \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        return s, jnp.einsum("hpn,n->hp", s, c_t) + D[:, None] * x_t

    def one(x, dt, B, C):
        h, p = x.shape[1:]
        return jax.lax.scan(step, jnp.zeros((h, p, B.shape[-1])),
                            (x, dt, B, C))[1]

    return jax.vmap(one)(x, dt, B, C)


def operands(t, seed=0, b=2, h=3, p=64, n=8, dt_scale=1.0):
    k = jax.random.split(jax.random.key(seed), 7)
    return (jax.random.normal(k[0], (b, t, h, p)),
            dt_scale * jax.nn.softplus(jax.random.normal(k[1], (b, t, h))),
            -jnp.exp(jax.random.normal(k[2], (h,))),
            jax.random.normal(k[3], (b, t, n)),
            jax.random.normal(k[4], (b, t, n)),
            jax.random.normal(k[5], (h,))), \
        jax.random.normal(k[6], (b, t, h, p))


def rel(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def both_gradients(xs, weight, chunk):
    return [jax.grad(lambda *xs: jnp.sum(f(*xs) * weight),
                     argnums=range(6))(*xs)
            for f in (lambda *xs: ssd_scan(*xs, chunk=chunk), step_by_step)]


@pytest.mark.parametrize("t, chunk, h, p", [
    (32, 8, 3, 64), (32, 16, 3, 64), (37, 8, 3, 64), (37, 16, 5, 32),
    (20, None, 2, 128), (40, 8, 9, 16), (5, None, 3, 64), (37, None, 3, 64)],
    ids=["whole_chunks_of_8", "whole_chunks_of_16", "ragged_chunks_of_8",
         "ragged_chunks_of_16_four_heads_a_block", "one_head_a_block",
         "eight_heads_a_block", "one_short_chunk", "one_chunk_from_the_shape"])
def test_values_and_all_six_gradients_match_the_loop(t, chunk, h, p):
    xs, weight = operands(t, seed=t, h=h, p=p)
    got = ssd_scan(*xs, chunk=chunk)
    want = step_by_step(*xs)
    assert got.shape == want.shape and rel(got, want) < 1e-5
    for name, g, w in zip(NAMES, *both_gradients(xs, weight, chunk)):
        assert g.shape == w.shape and rel(g, w) < 2e-5, name


def test_decays_that_a_ratio_of_exponentials_could_not_hold():
    """``dt A`` of -20 and more a position: the running sum inside a chunk
    of 16 passes -300 and ``exp`` of it is 0 in float32, so ``exp(L_i) /
    exp(L_j)`` would be 0 / 0; the difference, masked before ``exp``, is
    exact, and neighbours still see each other."""
    xs, weight = operands(48, seed=3, dt_scale=20.0)
    x, dt, A, B, C, D = xs
    A = -jnp.ones_like(A)
    xs = (x, dt, A, B, C, D)
    assert float(jnp.min(jnp.cumsum((dt * A)[:, :16], axis=1))) < -200
    got, want = ssd_scan(*xs, chunk=16), step_by_step(*xs)
    assert bool(jnp.all(jnp.isfinite(got))) and rel(got, want) < 1e-5
    for name, g, w in zip(NAMES, *both_gradients(xs, weight, 16)):
        assert bool(jnp.all(jnp.isfinite(g))), name
        # dA sums dt da over terms twenty times the usual size
        assert rel(g, w) < 5e-4, name


def test_the_state_crosses_chunk_boundaries():
    """With a decay near 1 the last position's output depends on the first
    position's input, eight chunks back."""
    xs, _ = operands(64)
    x, dt, A, B, C, D = xs
    slow = (x, dt * 1e-2, A, B, C, D)
    moved = (x.at[:, 0].add(1.0),) + slow[1:]
    delta = ssd_scan(*moved, chunk=8) - ssd_scan(*slow, chunk=8)
    assert float(jnp.max(jnp.abs(delta[:, -1]))) > 1e-4
    assert rel(ssd_scan(*moved, chunk=8), step_by_step(*moved)) < 1e-5


@pytest.mark.parametrize("chunk", [16, 32])
def test_bfloat16_operands_keep_float32_decays_and_state(chunk):
    xs, weight = operands(64, seed=5)
    low = tuple(x.astype(jnp.bfloat16) if i in (0, 3, 4) else x
                for i, x in enumerate(xs))
    got = ssd_scan(*low, chunk=chunk)
    assert got.dtype == jnp.bfloat16
    exact = tuple(x.astype(jnp.float32) for x in low)
    assert rel(got.astype(jnp.float32), step_by_step(*exact)) < 2e-2
    grads = jax.grad(
        lambda *xs: jnp.sum(ssd_scan(*xs, chunk=chunk) * weight),
        argnums=range(6))(*low)
    want = jax.grad(lambda *xs: jnp.sum(step_by_step(*xs) * weight),
                    argnums=range(6))(*exact)
    assert [g.dtype for g in grads] == [x.dtype for x in low]
    for name, g, w in zip(NAMES, grads, want):
        assert rel(g.astype(jnp.float32), w) < 3e-2, name


def test_under_checkpoint_the_gradients_are_the_same():
    xs, weight = operands(37, seed=7)

    def loss(*xs):
        return jnp.sum(ssd_scan(*xs, chunk=8) * weight)

    plain = jax.grad(loss, argnums=range(6))(*xs)
    again = jax.grad(jax.checkpoint(loss), argnums=range(6))(*xs)
    for name, g, w in zip(NAMES, again, plain):
        assert rel(g, w) < 1e-6, name


def test_a_head_size_that_no_block_of_lanes_holds_whole_is_refused():
    xs, _ = operands(16, p=48)
    with pytest.raises(ValueError, match="does not divide"):
        ssd_scan(*xs)


def test_chunk_length_follows_from_the_shape():
    # the benchmark's shape: Mamba-2's published chunk
    assert kernels.chunk_length(8192) == 256 == kernels.CHUNK
    assert kernels.chunk_length(256) == 256
    assert kernels.chunk_length(1 << 20) == 256
    assert kernels.chunk_length(48) == 64  # no longer than it has to be
    assert kernels.chunk_length(5) == 16 and kernels.chunk_length(17) == 32
    # 64 heads of 64: eight programs a chunk, four blocks of lanes each
    assert kernels.group_width(64 * 64) == 512
    assert kernels.group_width(128) == 128 and kernels.group_width(768) == 256


def test_a_chunked_scan_is_counted_under_keys_of_its_own():
    xs, _ = operands(37)
    before = scan_log.snapshot()
    ssd_scan(*xs, chunk=8)
    after = scan_log.snapshot()
    assert after["chunked_sites"] == before["chunked_sites"] + 1
    # the selective scan's keys read what they read
    for key in ("sites", "chunks_per_site", "state_bytes_kept_per_site",
                "memory_readers", "kv_readers"):
        assert after[key] == before[key], key

    def totals(s):
        return (s["chunked_sites"] * (s["chunked_chunks_per_site"] or 0),
                s["chunked_sites"]
                * (s["chunked_state_bytes_kept_per_site"] or 0))

    # 5 chunks of 8 cover 37 positions; 2 sequences x 5 x 3 heads of
    # (64, 8) float32
    assert totals(after)[0] - totals(before)[0] == pytest.approx(5)
    assert totals(after)[1] - totals(before)[1] \
        == pytest.approx(2 * 5 * 3 * 64 * 8 * 4)
    assert device_report()["state_scans"]["chunked_sites"] \
        == after["chunked_sites"]
