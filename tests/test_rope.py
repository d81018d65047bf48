"""The half-split rotary (``models/decoder.py apply_rope``) on both of its
paths, the kernel on whole heads of 128 lanes (``ops/pallas/rope.py``,
interpreted here) and the sliced body of any other head size, against two
oracles written apart from it: numpy in float64 by pairs ``(i, i + rot/2)``
for the values, and ``jax.grad`` of the slice-and-concatenate body, kept
here, for the closed-form backward; and the counter of its traced calls."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_mnist_tpu.models import decoder
from pytorch_distributed_mnist_tpu.ops.pallas import rope as kernel
from pytorch_distributed_mnist_tpu.utils.profiling import (
    device_report,
    rotary_sites,
)

# The benchmark's two kinds of layer: every lane rotated at plain
# frequencies (window layers), YaRN on half the head with its
# ``attention_factor`` (full layers).
with open(os.path.join(os.path.dirname(__file__), "..", "benchmark",
                       "configs", "laguna-xs2-ep8.json")) as _f:
    _ROPE = json.load(_f)["rope_parameters"]
PLAIN, YARN = _ROPE["sliding_attention"], _ROPE["full_attention"]


def by_pairs(x, inv_freq, factor):
    """Float64, one pair of dimensions at a time; the angle is the float32
    product the implementation forms (at position 300 a float64 angle
    would differ by more than the result's own rounding)."""
    x = np.asarray(x.astype(jnp.float32), np.float64)
    half = len(inv_freq)
    pos = np.arange(x.shape[1], dtype=np.float32)
    out = x.copy()
    for i in range(half):
        angle = (pos * np.float32(inv_freq[i])).astype(np.float64)
        cos = (np.cos(angle) * factor)[None, :, None]
        sin = (np.sin(angle) * factor)[None, :, None]
        a, b = x[..., i], x[..., i + half]
        out[..., i] = a * cos - b * sin
        out[..., i + half] = b * cos + a * sin
    return out


def sliced(x, inv_freq, factor):
    """The body ``apply_rope`` had before the kernel, slices of a head and
    a concatenate, whose ``jax.grad`` is the second oracle."""
    rot = 2 * inv_freq.shape[0]
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv_freq, jnp.float32)[None, :]
    cos = (jnp.cos(angles) * factor)[None, :, None, :]
    sin = (jnp.sin(angles) * factor)[None, :, None, :]
    xf = x.astype(jnp.float32)
    x1, x2, rest = xf[..., :rot // 2], xf[..., rot // 2:rot], xf[..., rot:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)
    return out.astype(x.dtype)


# (head size, heads, positions): the cell's 64 / 48 query and 8 key-value
# heads of 128; 300 positions are blocks of 256 (bfloat16) or 128 (float32)
# rows with a part block last, 40 one block that is no multiple of the
# inner loop's chunk; 16 is the tiny preset's head, the sliced path.
SHAPES = [(128, 64, 300), (128, 48, 64), (128, 8, 40), (16, 6, 40)]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("params", [PLAIN, YARN], ids=["whole", "half_yarn"])
@pytest.mark.parametrize("d, heads, t", SHAPES)
def test_rotary_against_both_oracles(d, heads, t, params, dtype):
    inv_freq, factor = decoder.rope_frequencies(d, params)
    rot = 2 * len(inv_freq)
    assert rot == int(d * params["partial_rotary_factor"])
    assert kernel.whole_heads(d) == (d == 128)
    kx, kw, kv = jax.random.split(jax.random.key(d + heads + t), 3)
    x = jax.random.normal(kx, (2, t, heads, d), dtype)
    weight = jax.random.normal(kw, x.shape, dtype)
    low = dtype == jnp.bfloat16
    step = 2.0 ** -7 if low else 2e-6  # a rounding of the result's type

    def close(got, want, what):
        got, want = (np.asarray(a, np.float64) for a in (
            got.astype(jnp.float32), want))
        np.testing.assert_allclose(
            got, want, rtol=step, atol=step * np.abs(want).max(),
            err_msg=what)

    rope = jax.jit(lambda x: decoder.apply_rope(x, inv_freq, factor))
    y = rope(x)
    assert y.dtype == dtype and y.shape == x.shape
    close(y, by_pairs(x, inv_freq, factor), "values")
    # the lanes behind ``rot`` and, at factor 1, position 0: the input's bits
    np.testing.assert_array_equal(y[..., rot:], x[..., rot:])
    if factor == 1.0:
        np.testing.assert_array_equal(y[:, 0], x[:, 0])
    np.testing.assert_array_equal(
        y[:, 0], sliced(x, inv_freq, factor)[:, 0])

    def grad_of(f):
        return jax.jit(jax.grad(lambda x: jnp.sum(
            (f(x, inv_freq, factor) * weight).astype(jnp.float32))))(x)

    g = grad_of(decoder.apply_rope)
    assert g.dtype == dtype
    close(g, grad_of(sliced).astype(jnp.float32), "gradient")
    np.testing.assert_array_equal(g[..., rot:], weight[..., rot:])

    # the backward of the backward is the forward
    _, back = jax.vjp(rope, x)
    v = jax.random.normal(kv, x.shape, dtype)
    _, back_of_back = jax.vjp(lambda g: back(g)[0], weight)
    close(back_of_back(v)[0], rope(v).astype(jnp.float32),
          "vjp of the vjp")


def test_the_kernel_takes_its_block_from_the_shape():
    # 64 heads of 128: 256 rows of bfloat16 are the 4 MiB block, 128 of
    # float32; the 8 key-value heads get as many rows as fill it; a
    # sequence shorter than a block is one block
    assert kernel._block_rows(8192, 64 * 128, 2) == 256
    assert kernel._block_rows(8192, 48 * 128, 2) == 256
    assert kernel._block_rows(8192, 64 * 128, 4) == 128
    assert kernel._block_rows(8192, 8 * 128, 2) == 2048
    assert kernel._block_rows(300, 8 * 128, 2) == 300
    assert kernel._block_rows(8192, 1024 * 128, 4) == kernel.CHUNK


def _traced_gradient(**kwargs):
    model = decoder.Decoder(remat=True, attention="dense", **kwargs)
    tokens = jnp.zeros((2, 32), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.key(0), tokens)
    before = rotary_sites.snapshot()
    jax.make_jaxpr(jax.grad(lambda p: jnp.sum(model.apply(p, tokens))))(
        params)
    after = rotary_sites.snapshot()
    return {k: after[k] - before[k] for k in ("sites", "whole_head_sites")}, \
        after["rotated_lanes"]


def test_every_traced_rotary_is_counted_with_its_path():
    """Five layers rotate q and k. At heads of 128 every call is the
    kernel's and a traced gradient counts 20: the forward's 10 and the
    backward's 10 (``remat`` traces nothing again: its recomputed forward
    is the traced forward's jaxpr). The tiny preset's heads of 16 are
    sliced: 10 forward calls, the backward being autodiff's."""
    sites, lanes = _traced_gradient(head_dim=128)
    assert sites == {"sites": 20, "whole_head_sites": 20}
    assert {64, 128} <= set(lanes)  # YaRN on half the head, plain on all
    sites, lanes = _traced_gradient()
    assert sites == {"sites": 10, "whole_head_sites": 0}
    assert {8, 16, 64, 128} <= set(lanes)
    report = device_report()["rotary_sites"]
    assert report == rotary_sites.snapshot()
    assert set(report) == {"sites", "whole_head_sites", "rotated_lanes"}
    assert report["rotated_lanes"] == sorted(report["rotated_lanes"])
