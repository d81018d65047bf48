"""Selective scan (Pallas TPU): kernels that step through time with the
state in VMEM, forward and backward. ``ops/ssm.py`` has the recurrence and
the public function; this file is what computes a chunk.

Layout. The state of one sequence is ``(N, C)``: states along the
sublanes, channels along the lanes. A program works on ``piece`` channels
(a multiple of 128 lanes) of one chunk of ``length`` positions; the grid is
``(sequence, chunk, piece)`` with the pieces innermost, and the state of
every piece stays in a VMEM scratch ``(pieces, N, piece)`` from one chunk
to the next, so the chunks of a sequence run in order and the ``(T, N,
C)`` states exist nowhere. ``a`` and ``dt`` are read eight positions (one
sublane tile) at a time; ``B_t`` and ``C_t``, which the recurrence needs as
a column over the states, come in already spread over 128 lanes, ``(T, N,
128)``, and are widened to the piece in registers.

Forward: ``s = exp(dt_t A) s + dt_t a_t B_t``; ``m_t = sum_n C_t s + D
a_t``; the state at each chunk's start is written out, ``(chunks, pieces,
N, piece)`` float32 a sequence, which is all the backward keeps.

Backward, chunks from the last to the first: the chunk's states are
computed again from its kept start into a VMEM scratch ``(length + 1, N,
piece)``, then the positions are walked backwards with the state's
gradient ``g`` carried (and kept across chunks like the state): ``g_t = g +
C_t dm_t``; with ``e = exp(dt_t A)`` and ``w = g_t s_{t-1} e``: ``ddt_t =
sum_n(w A) + a_t sum_n(g_t B_t)``, ``da_t = dt_t sum_n(g_t B_t) + D dm_t``,
``dA += w dt_t``, ``dB_t = sum_c g_t dt_t a_t``, ``dC_t = sum_c s_t dm_t``,
``g = e g_t``. The sums over channels leave the kernel as 128-lane partial
sums ``(T, N, 128)``, accumulated over the pieces in the output block, and
XLA adds the lanes; ``dA`` is accumulated in its output block, resident for
a whole sequence.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from pytorch_distributed_mnist_tpu.ops.pallas.backend import should_interpret

LANES = 128
ROWS = 8  # positions read at a time: one sublane tile of float32
VMEM_LIMIT_BYTES = 64 * 1024 * 1024
# The backward's recomputed states of one chunk and piece, ``length * N *
# piece * 4`` bytes of VMEM.
CHUNK_STATE_BYTES = 8 * 1024 * 1024


def piece_width(channels: int) -> int:
    """Channels a program: the widest of 512, 256, 128 that divides
    ``channels`` (a multiple of 128)."""
    return next(w for w in (512, 256, 128) if channels % w == 0)


def chunk_length(t: int, channels: int, states: int) -> int:
    """Positions a chunk: the largest power of two, from 8, that is no
    longer than ``t`` rounded up to a power of two and whose states of one
    piece fit :data:`CHUNK_STATE_BYTES`."""
    piece = piece_width(-(-channels // LANES) * LANES)
    length = ROWS
    while length < t and length * 2 * states * piece * 4 <= CHUNK_STATE_BYTES:
        length *= 2
    return length


def _widen(x, width: int):
    """``(N, 128)`` whose lanes are alike -> ``(N, width)``."""
    return jnp.concatenate([x] * (width // LANES), axis=1) \
        if width > LANES else x


def _lane_partial(x):
    """``(N, width)`` -> ``(N, 128)``: the sum of its 128-lane pieces."""
    out = x[:, :LANES]
    for p in range(1, x.shape[1] // LANES):
        out = out + x[:, p * LANES:(p + 1) * LANES]
    return out


def _advance(s, a_t, dt_t, at, b_t):
    """The state after one position: ``exp(dt_t A) s + dt_t a_t B_t``."""
    return jnp.exp(dt_t * at) * s + (dt_t * a_t) * b_t


def _fwd_kernel(a_ref, dt_ref, at_ref, d_ref, b_ref, c_ref, m_ref, s0_ref,
                state, *, length: int):
    k, j = pl.program_id(1), pl.program_id(2)
    width = at_ref.shape[1]

    @pl.when(k == 0)
    def _():
        state[j] = jnp.zeros(state.shape[1:], jnp.float32)

    s = state[j]
    s0_ref[...] = s
    at, skip = at_ref[...], d_ref[...]

    def group(g, s):
        r = pl.multiple_of(g * ROWS, ROWS)
        a8, dt8 = a_ref[pl.ds(r, ROWS), :], dt_ref[pl.ds(r, ROWS), :]
        rows = []
        for i in range(ROWS):
            a_t, dt_t = a8[i:i + 1], dt8[i:i + 1]
            s = _advance(s, a_t, dt_t, at, _widen(b_ref[r + i], width))
            rows.append(jnp.sum(_widen(c_ref[r + i], width) * s, axis=0,
                                keepdims=True) + skip * a_t)
        m_ref[pl.ds(r, ROWS), :] = jnp.concatenate(rows, axis=0)
        return s

    state[j] = jax.lax.fori_loop(0, length // ROWS, group, s)


def _bwd_kernel(a_ref, dt_ref, at_ref, d_ref, b_ref, c_ref, g_ref, s0_ref,
                da_ref, ddt_ref, dat_ref, db_ref, dc_ref, carry, states, *,
                length: int):
    k, j = pl.program_id(1), pl.program_id(2)
    width = at_ref.shape[1]
    at, skip = at_ref[...], d_ref[...]

    @pl.when(k == 0)  # the sequence's last chunk: nothing comes from behind
    def _():
        carry[j] = jnp.zeros(carry.shape[1:], jnp.float32)
        dat_ref[j] = jnp.zeros(dat_ref.shape[1:], jnp.float32)

    @pl.when(j == 0)
    def _():
        db_ref[...] = jnp.zeros(db_ref.shape, jnp.float32)
        dc_ref[...] = jnp.zeros(dc_ref.shape, jnp.float32)

    # The chunk's states again: states[t] is the state before position t.
    states[0] = s0_ref[...]

    def again(g, s):
        r = pl.multiple_of(g * ROWS, ROWS)
        a8, dt8 = a_ref[pl.ds(r, ROWS), :], dt_ref[pl.ds(r, ROWS), :]
        for i in range(ROWS):
            a_t, dt_t = a8[i:i + 1], dt8[i:i + 1]
            s = _advance(s, a_t, dt_t, at, _widen(b_ref[r + i], width))
            states[r + i + 1] = s
        return s

    jax.lax.fori_loop(0, length // ROWS, again, states[0])

    def group(step, acc):
        grad, d_at = acc
        r = pl.multiple_of((length // ROWS - 1 - step) * ROWS, ROWS)
        a8, dt8 = a_ref[pl.ds(r, ROWS), :], dt_ref[pl.ds(r, ROWS), :]
        g8 = g_ref[pl.ds(r, ROWS), :]
        da_rows, ddt_rows = [None] * ROWS, [None] * ROWS
        for i in reversed(range(ROWS)):
            a_t, dt_t, dm_t = a8[i:i + 1], dt8[i:i + 1], g8[i:i + 1]
            t = r + i
            b_t = _widen(b_ref[t], width)
            grad = grad + _widen(c_ref[t], width) * dm_t
            dc_ref[t] += _lane_partial(states[t + 1] * dm_t)
            db_ref[t] += _lane_partial(grad * (dt_t * a_t))
            decay = jnp.exp(dt_t * at)
            w = grad * states[t] * decay
            fed = jnp.sum(grad * b_t, axis=0, keepdims=True)
            ddt_rows[i] = jnp.sum(w * at, axis=0, keepdims=True) + fed * a_t
            da_rows[i] = fed * dt_t + skip * dm_t
            d_at = d_at + w * dt_t
            grad = decay * grad
        da_ref[pl.ds(r, ROWS), :] = jnp.concatenate(da_rows, axis=0)
        ddt_ref[pl.ds(r, ROWS), :] = jnp.concatenate(ddt_rows, axis=0)
        return grad, d_at

    grad, d_at = jax.lax.fori_loop(0, length // ROWS, group,
                                   (carry[j], dat_ref[j]))
    carry[j] = grad
    dat_ref[j] = d_at


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _spread(x):
    """``(B, T, N)`` -> ``(B, T, N, 128)``, every lane alike."""
    return jnp.broadcast_to(x[..., None], (*x.shape, LANES))


def forward(a, dt, at, d, b, c, length: int):
    """``(m (B, T, C), starts (B, chunks, pieces, N, piece))`` for float32
    ``a``, ``dt`` (B, T, C), ``at`` = A transposed (N, C), ``d`` (C,),
    ``b``, ``c`` (B, T, N); T a multiple of ``length``, C of 128."""
    bsz, t, channels = a.shape
    n = at.shape[0]
    width = piece_width(channels)
    pieces, chunks = channels // width, t // length
    rows = pl.BlockSpec((None, length, width), lambda s, k, j: (s, k, j))
    cols = pl.BlockSpec((None, length, n, LANES),
                        lambda s, k, j: (s, k, 0, 0))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, length=length),
        grid=(bsz, chunks, pieces),
        in_specs=[rows, rows,
                  pl.BlockSpec((n, width), lambda s, k, j: (0, j)),
                  pl.BlockSpec((1, width), lambda s, k, j: (0, j)),
                  cols, cols],
        out_specs=(rows, pl.BlockSpec((None, None, None, n, width),
                                      lambda s, k, j: (s, k, j, 0, 0))),
        out_shape=(
            jax.ShapeDtypeStruct((bsz, t, channels), jnp.float32),
            jax.ShapeDtypeStruct((bsz, chunks, pieces, n, width),
                                 jnp.float32)),
        scratch_shapes=[pltpu.VMEM((pieces, n, width), jnp.float32)],
        compiler_params=_params(),
        interpret=should_interpret(),
        name="selective_scan_fwd",
    )(a, dt, at, d[None], _spread(b), _spread(c))


def backward(a, dt, at, d, b, c, starts, g, length: int):
    """Gradients ``(da, ddt (B, T, C), d_at (N, C), db, dc (B, T, N))`` of
    ``sum(m * g)``; operands as :func:`forward`'s."""
    bsz, t, channels = a.shape
    n = at.shape[0]
    width = piece_width(channels)
    pieces, chunks = channels // width, t // length
    last = chunks - 1
    rows = pl.BlockSpec((None, length, width),
                        lambda s, k, j: (s, last - k, j))
    cols = pl.BlockSpec((None, length, n, LANES),
                        lambda s, k, j: (s, last - k, 0, 0))
    da, ddt, d_at, db, dc = pl.pallas_call(
        functools.partial(_bwd_kernel, length=length),
        grid=(bsz, chunks, pieces),
        in_specs=[rows, rows,
                  pl.BlockSpec((n, width), lambda s, k, j: (0, j)),
                  pl.BlockSpec((1, width), lambda s, k, j: (0, j)),
                  cols, cols, rows,
                  pl.BlockSpec((None, None, None, n, width),
                               lambda s, k, j: (s, last - k, j, 0, 0))],
        out_specs=(rows, rows,
                   pl.BlockSpec((None, pieces, n, width),
                                lambda s, k, j: (s, 0, 0, 0)),
                   cols, cols),
        out_shape=(
            jax.ShapeDtypeStruct((bsz, t, channels), jnp.float32),
            jax.ShapeDtypeStruct((bsz, t, channels), jnp.float32),
            jax.ShapeDtypeStruct((bsz, pieces, n, width), jnp.float32),
            jax.ShapeDtypeStruct((bsz, t, n, LANES), jnp.float32),
            jax.ShapeDtypeStruct((bsz, t, n, LANES), jnp.float32)),
        scratch_shapes=[pltpu.VMEM((pieces, n, width), jnp.float32),
                        pltpu.VMEM((length + 1, n, width), jnp.float32)],
        compiler_params=_params(),
        interpret=should_interpret(),
        name="selective_scan_bwd",
    )(a, dt, at, d[None], _spread(b), _spread(c), g, starts)
    d_at = jnp.sum(d_at, axis=0).transpose(1, 0, 2).reshape(n, channels)
    return da, ddt, d_at, jnp.sum(db, axis=-1), jnp.sum(dc, axis=-1)
