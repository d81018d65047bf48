"""State-space duality (Pallas TPU): the Mamba-2 recurrence chunk by chunk
as matrix products, forward and backward. ``ops/ssd.py`` has the recurrence
and the public function; this file is what computes a chunk.

One chunk of ``Q`` positions of one head, with ``a_i = dt_i A``, ``L_i``
the sum of ``a`` from the chunk's start to ``i``, ``xd_j = dt_j x_j`` and
``S`` the head's ``(P, N)`` state at the chunk's start:

    y_i = sum_{j<=i} (C_i . B_j) exp(L_i - L_j) xd_j + exp(L_i) S C_i + D x_i
    S' = exp(L_Q) S + sum_j exp(L_Q - L_j) xd_j B_j^T

Every exponent is a difference, masked before ``exp``, and none is
positive. ``L`` and ``dt`` come in already summed (``ops/ssd.py``: a
cumulative sum of ``(T, H)`` float32 is XLA's), twice, because the decay
tile ``exp(L_i - L_j)`` needs ``L`` along the rows and along the columns:
``cols`` ``(Q, 2H)`` holds ``L`` and then ``dt`` with a head a lane,
``rows`` ``(2H, Q)`` a head a sublane.

Layout. ``x`` is ``(B, T, H * P)``, a head ``P`` lanes wide. A program
works on one chunk and one *group* of ``width`` lanes (a multiple of 128),
128 lanes at a time, statically; 128 lanes hold ``128 / P`` heads, which
share the matmuls whose other operand does not depend on the head (the
state's, ``(128, N)`` with a head ``P`` sublanes tall) and take their own
for the masked product, each keeping its own lanes of the result. The grid
is ``(sequence, chunk, group)``, groups innermost; every head's state stays
in a VMEM scratch ``(H * P / 128, 128, N)`` float32 from one chunk to the
next, so a sequence's chunks run in order. The scores ``C B^T`` of a chunk
are one ``(Q, Q)`` product for all heads (one group of ``B`` and ``C``).

Forward: writes ``y`` and the state at each chunk's start, ``(chunks, H * P
/ 128, 128, N)`` float32 a sequence, which is all the backward keeps. No
``(Q, Q)`` tile leaves the chip.

Backward, chunks from the last to the first, with ``G`` the state's
gradient at the chunk's end carried like the state, ``E_ij = exp(L_i -
L_j)`` and ``R = dy xd^T`` (a head's lanes):

    dxd_j = sum_i (C_i . B_j) E_ij dy_i + exp(L_Q - L_j) G B_j
    dx = dt dxd + D dy;  ddt (its direct part) = x . dxd
    dscores = sum_heads E * R;  dC = dscores B + sum_heads exp(L_i) dy_i S
    dB = dscores^T C + sum_heads exp(L_Q - L_j) xd_j G
    G' = exp(L_Q) G + sum_i exp(L_i) dy_i C_i^T

and the gradient of ``L`` without a ratio and without the cancellation of
two separately rounded sums: with ``T = scores * E * R`` in float32, ``dL_i
= rowsum_i(T) - colsum_i(T) + dy_i . (exp(L_i) S C_i) - xd_i . (exp(L_Q -
L_i) G B_i)``, and on the chunk's last position the sum of those last
terms and ``exp(L_Q) <G, S>`` besides (what the end state owes ``L_Q``).
Row sums leave as columns (``cols_out``: ``dL`` less the column sums, then
``ddt``'s direct part), column sums as rows (``rows_out``); ``ops/ssd.py``
subtracts, sums ``dL`` from each position to its chunk's end for ``da``,
and finishes ``ddt``, ``dA`` and ``dD``.

Matrix operands are in ``x``'s type (bfloat16 in the benchmark, one MXU
pass) and accumulate in float32; ``L``, ``dt``, the decay, the state and
its gradient are float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from pytorch_distributed_mnist_tpu.ops.pallas.flash import (
    _NN,
    _NT,
    _TN,
    _dot,
)

LANES = 128
SUBLANES = 8
CHUNK = 256  # positions a chunk at most: Mamba-2's published chunk
MIN_CHUNK = 16  # one sublane tile of bfloat16
VMEM_LIMIT_BYTES = 64 * 1024 * 1024
MASKED = -1e30  # an exponent that is not needed: exp gives 0

def chunk_length(t: int) -> int:
    """Positions a chunk: the smallest power of two, from 16, that covers
    ``t``, and no more than :data:`CHUNK`."""
    length = MIN_CHUNK
    while length < t and length < CHUNK:
        length *= 2
    return length


def group_width(lanes: int) -> int:
    """Lanes a program: the widest of 512, 256, 128 that divides ``lanes``
    (a multiple of 128)."""
    return next(w for w in (512, 256, 128) if lanes % w == 0)


def round_up(n: int, to: int) -> int:
    return -(-n // to) * to


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _within(index, r: int, p: int):
    """Where ``index`` (lanes or sublanes of a 128-block) is head ``r``'s."""
    return (index >= r * p) & (index < (r + 1) * p)


def _spread(parts, index, p: int):
    """A head's value on that head's lanes (or sublanes): ``parts[r]``
    where ``index`` is head ``r``'s."""
    out = parts[0]
    for r in range(1, len(parts)):
        out = jnp.where(_within(index, r, p), parts[r], out)
    return out


def _own(value, index, r: int, per: int, p: int):
    """``value`` on head ``r``'s lanes and zero elsewhere."""
    return value if per == 1 else jnp.where(_within(index, r, p), value, 0)


def _column(cols, lane, head):
    """Lane ``head`` of ``cols`` (Q, W) as a column (Q, 1)."""
    return jnp.sum(jnp.where(lane == head, cols, 0.0), axis=1, keepdims=True)


class _Heads:
    """What the ``per`` heads of one block of 128 lanes read of ``cols``:
    ``L`` and ``dt`` as columns, and spread over the heads' lanes."""

    def __init__(self, cols, lane_c, first, heads: int, per: int, p: int):
        q = cols.shape[0]
        self.lane = _iota((q, LANES), 1)
        self.l_col = [_column(cols, lane_c, first + r) for r in range(per)]
        dt_col = [_column(cols, lane_c, heads + first + r)
                  for r in range(per)]
        self.l_end = [c[q - 1:q] for c in self.l_col]  # (1, 1): L_Q
        self.l = _spread(self.l_col, self.lane, p)  # (Q, 128)
        self.dt = _spread(dt_col, self.lane, p)
        # exp(L_Q - L_j): what of position j is left at the chunk's end
        self.to_end = jnp.exp(
            _spread(self.l_end, self.lane[:1], p) - self.l)
        # exp(L_Q) a head, on the head's sublanes of its (128, N) state
        sub = _iota((LANES, 1), 0)
        self.sub = sub
        self.keep = _spread([jnp.exp(e) for e in self.l_end], sub, p)


def _decay(l_col, l_row, causal):
    """``exp(L_i - L_j)`` where ``j <= i`` and 0 elsewhere, (Q, Q)."""
    return jnp.exp(jnp.where(causal, l_col - l_row, MASKED))


def _fwd_kernel(x_ref, b_ref, c_ref, cols_ref, rows_ref, d_ref, y_ref,
                s0_ref, state, *, heads: int, p: int):
    k, g = pl.program_id(1), pl.program_id(2)
    q, width = x_ref.shape
    blocks, per = width // LANES, LANES // p

    @pl.when(k == 0)
    def _():
        state[pl.ds(g * blocks, blocks)] = jnp.zeros(
            (blocks,) + state.shape[1:], jnp.float32)

    bm, cm = b_ref[...], c_ref[...]
    scores = _dot(cm, bm, _NT)  # (Q, Q), for all heads
    causal = _iota((q, q), 1) <= _iota((q, q), 0)
    cols = cols_ref[...]
    lane_c = _iota(cols.shape, 1)
    for j in range(blocks):
        block = g * blocks + j
        at = slice(j * LANES, (j + 1) * LANES)
        h = _Heads(cols, lane_c, block * per, heads, per, p)
        xb = x_ref[:, at]
        xf = xb.astype(jnp.float32)
        xd = xf * h.dt
        xd_op = xd.astype(xb.dtype)
        s = state[block]  # (128, N) float32
        s0_ref[j] = s
        y = _dot(cm, s.astype(xb.dtype), _NT) * jnp.exp(h.l)
        for r in range(per):
            l_row = rows_ref[pl.ds(block * per + r, 1), :]
            w = scores * _decay(h.l_col[r], l_row, causal)
            y = y + _own(_dot(w.astype(xb.dtype), xd_op, _NN), h.lane, r,
                         per, p)
        y_ref[:, at] = (y + xf * d_ref[:, at]).astype(y_ref.dtype)
        state[block] = s * h.keep + _dot(
            (xd * h.to_end).astype(xb.dtype), bm, _TN)


def _bwd_kernel(x_ref, g_ref, b_ref, c_ref, cols_ref, rows_ref, d_ref,
                s0_ref, dx_ref, db_ref, dc_ref, cols_out, rows_out, grad,
                dscores, *, heads: int, p: int):
    k, g = pl.program_id(1), pl.program_id(2)
    q, width = x_ref.shape
    blocks, per = width // LANES, LANES // p

    @pl.when(k == 0)  # the sequence's last chunk: nothing comes from behind
    def _():
        grad[pl.ds(g * blocks, blocks)] = jnp.zeros(
            (blocks,) + grad.shape[1:], jnp.float32)

    @pl.when(g == 0)
    def _():
        for ref in (db_ref, dc_ref, cols_out, rows_out, dscores):
            ref[...] = jnp.zeros(ref.shape, jnp.float32)

    bm, cm = b_ref[...], c_ref[...]
    scores = _dot(cm, bm, _NT)
    causal = _iota((q, q), 1) <= _iota((q, q), 0)
    last = _iota((q, 1), 0) == q - 1
    cols = cols_ref[...]
    lane_c = _iota(cols.shape, 1)
    for j in range(blocks):
        block = g * blocks + j
        at = slice(j * LANES, (j + 1) * LANES)
        h = _Heads(cols, lane_c, block * per, heads, per, p)
        xb, gb = x_ref[:, at], g_ref[:, at]
        op = xb.dtype
        xf, gf = xb.astype(jnp.float32), gb.astype(jnp.float32)
        xd = xf * h.dt
        xd_op = xd.astype(op)
        s, ds = s0_ref[j], grad[block]  # (128, N) float32
        s_op, ds_op = s.astype(op), ds.astype(op)
        e = jnp.exp(h.l)
        ge_op = (gf * e).astype(op)  # dy_i exp(L_i)
        fed_op = (xd * h.to_end).astype(op)  # xd_j exp(L_Q - L_j)
        # the parts of y and of dxd that go through the state
        through = gf * (_dot(cm, s_op, _NT) * e)  # dy . y's inter part
        dxd = _dot(bm, ds_op, _NT) * h.to_end
        owed = xd * dxd  # xd . dxd's state part
        held = ds * s  # <G, S>, a head its sublanes
        for r in range(per):
            head = block * per + r
            l_row = rows_ref[pl.ds(head, 1), :]
            decay = _decay(h.l_col[r], l_row, causal)
            w = scores * decay
            dxd = dxd + _own(_dot(w.astype(op), gb, _TN), h.lane, r, per, p)
            pairs = _dot(_own(gb, h.lane, r, per, p), xd_op, _NT)  # R
            dscores[...] += decay * pairs
            t = w * pairs
            rows_out[pl.ds(head, 1), :] = jnp.sum(t, axis=0, keepdims=True)

            def mine(value):
                return jnp.sum(_own(value, h.lane, r, per, p), axis=1,
                               keepdims=True)

            owed_r = mine(owed)
            tail = jnp.sum(owed_r, axis=0, keepdims=True) \
                + jnp.exp(h.l_end[r]) * jnp.sum(
                    _own(held, h.sub, r, per, p), keepdims=True)
            dl = jnp.sum(t, axis=1, keepdims=True) + mine(through) \
                - owed_r + jnp.where(last, tail, 0.0)
            ddt = mine(xf * dxd)
            cols_out[...] = jnp.where(
                lane_c == head, dl,
                jnp.where(lane_c == heads + head, ddt, cols_out[...]))
        dx_ref[:, at] = (dxd * h.dt + gf * d_ref[:, at]).astype(dx_ref.dtype)
        dc_ref[...] += _dot(ge_op, s_op, _NN)
        db_ref[...] += _dot(fed_op, ds_op, _NN)
        grad[block] = ds * h.keep + _dot(ge_op, cm, _TN)

    @pl.when(g == pl.num_programs(2) - 1)
    def _():
        ds_op = dscores[...].astype(bm.dtype)
        dc_ref[...] += _dot(ds_op, bm, _NN)
        db_ref[...] += _dot(ds_op, cm, _TN)


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _specs(x, b, cols, rows, q: int):
    """The operands' block specs, the grid and the counts they follow
    from; ``turn`` maps the grid's chunk to the array's (the backward walks
    them from the last)."""
    bsz, t, lanes = x.shape
    width = group_width(lanes)
    chunks, groups = t // q, lanes // width

    def specs(turn):
        wide = pl.BlockSpec((None, q, width),
                            lambda s, k, g: (s, turn(k), g))
        narrow = pl.BlockSpec((None, q, b.shape[-1]),
                              lambda s, k, g: (s, turn(k), 0))
        col = pl.BlockSpec((None, None, q, cols.shape[-1]),
                           lambda s, k, g: (s, turn(k), 0, 0))
        row = pl.BlockSpec((None, None, rows.shape[-2], q),
                           lambda s, k, g: (s, turn(k), 0, 0))
        skip = pl.BlockSpec((1, width), lambda s, k, g: (0, g))
        starts = pl.BlockSpec(
            (None, None, width // LANES, LANES, b.shape[-1]),
            lambda s, k, g: (s, turn(k), g, 0, 0))
        return wide, narrow, col, row, skip, starts

    return specs, (bsz, chunks, groups), width


def forward(x, b, c, cols, rows, d, *, heads: int, p: int, q: int,
            interpret: bool):
    """``(y (B, T, H * P) in x's type, starts (B, chunks, H * P / 128, 128,
    N) float32)`` for ``x`` (B, T, H * P), ``b`` and ``c`` (B, T, N) in
    ``x``'s type, ``cols`` (B, chunks, Q, W) and ``rows`` (B, chunks, R, Q)
    float32 (module docstring), ``d`` (1, H * P) float32: ``D`` a lane; T a
    multiple of ``q``, H * P of 128, 128 of ``p``. ``interpret`` is
    ``backend.should_interpret()``'s answer, asked by the caller a call."""
    bsz, t, lanes = x.shape
    n = b.shape[-1]
    specs, grid, _ = _specs(x, b, cols, rows, q)
    wide, narrow, col, row, skip, starts = specs(lambda k: k)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, heads=heads, p=p),
        grid=grid,
        in_specs=[wide, narrow, narrow, col, row, skip],
        out_specs=(wide, starts),
        out_shape=(
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct((bsz, t // q, lanes // LANES, LANES, n),
                                 jnp.float32)),
        scratch_shapes=[pltpu.VMEM((lanes // LANES, LANES, n), jnp.float32)],
        compiler_params=_params(),
        interpret=interpret,
        name="ssd_fwd",
    )(x, b, c, cols, rows, d)


def backward(x, g, b, c, cols, rows, d, starts, *, heads: int, p: int,
             q: int, interpret: bool):
    """``(dx (B, T, H * P), db, dc (B, T, N) float32, cols_out (B, chunks,
    Q, W), rows_out (B, chunks, R', Q))`` of ``sum(y * g)``; operands as
    :func:`forward`'s, the two last results as the module docstring has
    them (``R'`` = H rounded up to whole sublane tiles)."""
    bsz, t, lanes = x.shape
    n = b.shape[-1]
    specs, grid, _ = _specs(x, b, cols, rows, q)
    final = grid[1] - 1
    wide, narrow, col, row, skip, kept = specs(lambda k: final - k)
    out_rows = round_up(heads, SUBLANES)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, heads=heads, p=p),
        grid=grid,
        in_specs=[wide, wide, narrow, narrow, col, row, skip, kept],
        out_specs=(wide, narrow, narrow, col,
                   pl.BlockSpec((None, None, out_rows, q),
                                lambda s, k, g: (s, final - k, 0, 0))),
        out_shape=(
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct(b.shape, jnp.float32),
            jax.ShapeDtypeStruct(b.shape, jnp.float32),
            jax.ShapeDtypeStruct(cols.shape, jnp.float32),
            jax.ShapeDtypeStruct((bsz, t // q, out_rows, q), jnp.float32)),
        scratch_shapes=[pltpu.VMEM((lanes // LANES, LANES, n), jnp.float32),
                        pltpu.VMEM((q, q), jnp.float32)],
        compiler_params=_params(),
        interpret=interpret,
        name="ssd_bwd",
    )(x, g, b, c, cols, rows, d, starts)
