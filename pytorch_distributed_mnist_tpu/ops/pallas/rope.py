"""Half-split rotary on whole heads (Pallas TPU): one kernel, forward and
backward.

A head of 128 is one register's lanes. ``models/decoder.py apply_rope``
pairs lane ``i`` of a head with lane ``i + rot/2`` inside its ``rot``
leading lanes; written with slices of a head (64 or 32 wide) each half
becomes an array of its own in a padded layout, and autodiff's transpose
of slice-and-concatenate pads and adds them back. Here the head stays
whole:

    y = x * C + swap(x) * S

with ``(T, D)`` float32 tables (``C``: ``factor * cos`` on the ``rot``
leading lanes and 1 behind them; ``S``: ``-factor * sin`` on lanes
``[0, rot/2)``, ``+factor * sin`` on ``[rot/2, rot)`` and 0 behind) and
``swap`` exchanging lanes ``i`` and ``i + rot/2``: one rotation of the
lanes where ``rot == D``, two (by ``rot/2`` either way) and a select on the
lane index otherwise; the lanes behind ``rot`` are the head's own, so they
and position 0 come back to the bit. The same float32 products in the same
order as the slices gave.

The map is linear in ``x`` and its transpose is the same map at the negated
angle, ``dx = g * C + swap(g) * (-S)``: the backward is this kernel with
``-S`` (:func:`rotate_heads` is a ``jax.custom_vjp`` whose only residuals
are the tables), so a recomputed forward and a backward each run the one op.

The kernel works on the lane-blocked view ``(B, T, H*D)`` that a projection
writes and ``flash.py`` reads: a program takes a block of rows by all of
``H*D`` (about ``BLOCK_BYTES``) and the tables' rows of that block, and
walks it ``CHUNK`` rows at a time, the tables' chunk loaded once for all
heads. One read and one write of ``x``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from pytorch_distributed_mnist_tpu.ops.pallas.backend import should_interpret
from pytorch_distributed_mnist_tpu.utils.profiling import rotary_sites

__all__ = ["rotate_heads", "whole_heads"]

LANES = 128
# A program's block of ``x``: with its result and two buffers each, four of
# these are in VMEM (256 rows of 64 heads of 128 in bf16).
BLOCK_BYTES = 4 * 1024 * 1024
VMEM_LIMIT_BYTES = 64 * 1024 * 1024
# Rows a step of the inner loop holds in registers: two packed bf16 tiles.
CHUNK = 32


def whole_heads(head_dim: int) -> bool:
    """Whether a head is whole registers of lanes (what ``flash.py``'s
    lane-blocked path asks too): the shapes this kernel takes."""
    return head_dim % LANES == 0


def _swap(x, rot: int):
    """Lanes ``i`` and ``i + rot/2`` of ``x`` (rows, D) exchanged inside
    the ``rot`` leading lanes."""
    d, half = x.shape[1], rot // 2
    if rot == d:
        return pltpu.roll(x, half, 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where(
        lane < half, pltpu.roll(x, d - half, 1),
        jnp.where(lane < rot, pltpu.roll(x, half, 1), x))


def _kernel(x_ref, c_ref, s_ref, o_ref, *, rot: int, d: int, chunk: int):
    rows, width = x_ref.shape

    def step(i, carry):
        r = pl.multiple_of(i * chunk, chunk)
        c, s = c_ref[pl.ds(r, chunk), :], s_ref[pl.ds(r, chunk), :]
        for h in range(width // d):
            head = (pl.ds(r, chunk), slice(h * d, (h + 1) * d))
            x = x_ref[head].astype(jnp.float32)
            o_ref[head] = (x * c + _swap(x, rot) * s).astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, rows // chunk, step, None)


def _block_rows(t: int, width: int, itemsize: int) -> int:
    """The largest power of two of rows whose block is within
    ``BLOCK_BYTES`` (at least one chunk), or all ``t`` rows if fewer."""
    rows = max(BLOCK_BYTES // (width * itemsize), CHUNK)
    rows = 1 << (rows.bit_length() - 1)
    return t if t <= rows else rows


@functools.partial(jax.jit, static_argnames=("rot", "interpret"))
def _rotate(x, c, s, *, rot: int, interpret: bool):
    """Jitted of its own so that every layer's calls of one shape share one
    trace and lowering; XLA inlines the call."""
    b, t, h, d = x.shape
    rows = _block_rows(t, h * d, x.dtype.itemsize)
    chunk = CHUNK if rows % CHUNK == 0 else rows
    block = pl.BlockSpec((None, rows, h * d), lambda i, j: (i, j, 0))
    table = pl.BlockSpec((rows, d), lambda i, j: (j, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, rot=rot, d=d, chunk=chunk),
        grid=(b, pl.cdiv(t, rows)),
        in_specs=[block, table, table],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct((b, t, h * d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="rope_whole_head",
    )(x.reshape(b, t, h * d), c, s)
    return out.reshape(b, t, h, d)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _rotate_vjp(x, c, s, rot: int):
    return _rotate(x, c, s, rot=rot, interpret=should_interpret())


def rotate_heads(x, c, s, rot: int):
    """``x * c + swap(x) * s`` for ``x`` (B, T, H, D), ``D`` a multiple of
    128, and float32 tables ``c``, ``s`` (T, D); ``swap`` exchanges lanes
    ``i`` and ``i + rot/2`` inside the ``rot`` leading lanes of each head.
    Float32 inside, the result in ``x``'s type. Differentiable in ``x``
    any number of times (the backward is this function at ``-s``); the
    tables get no gradient. Every traced call, a backward's too, is
    counted in ``utils.profiling.rotary_sites``."""
    rotary_sites.record(rot, whole_head=True)
    return _rotate_vjp(x, c, s, rot)


def _rotate_fwd(x, c, s, rot):
    return _rotate_vjp(x, c, s, rot), (c, s)


def _rotate_bwd(rot, tables, g):
    c, s = tables
    return rotate_heads(g, c, -s, rot), jnp.zeros_like(c), jnp.zeros_like(s)


_rotate_vjp.defvjp(_rotate_fwd, _rotate_bwd)
