"""Flash attention (Pallas TPU): fused forward AND backward kernels.

Blockwise online-softmax attention: scores are computed tile-by-tile in
VMEM and never materialized as a (T, T) matrix in HBM — in either pass.
The forward kernel additionally emits the per-row logsumexp; the backward
is one kernel over that residual, which evaluates every tile once and
takes all three gradients from it (how the residual reaches it, and what a
recomputed block keeps of it: the last section):

  delta_i = rowsum(dO_i * O_i)                       (tiny elementwise, XLA)
  P_ij    = exp(scale * q_i.k_j - lse_i)             (recomputed per tile)
  dV_j    = sum_i P_ij^T dO_i
  dS_ij   = P_ij * (dO_i.V_j - delta_i)
  dK_j    = scale * sum_i dS_ij^T Q_i
  dQ_i    = scale * sum_j dS_ij K_j

5 matmuls a tile. The grid goes over key blocks ``j``: dK_j and dV_j are
a program's own, and dQ_i, which every key block of the band adds to, is
summed in a float32 accumulator that holds the head's whole ``(T, D)`` in
VMEM across the head's programs (no partial dQ and no float32 dQ in HBM).
Two kernels, one by query block for dQ and one by key block for dK and
dV, would each compute the scores and ``dO.V`` for themselves: 7 matmuls
and the softmax's elementwise work twice.

Gradients also run at flash memory cost — no ``jax.vjp`` of a dense
reference anywhere. Oracle for both kernels: ``full_attention`` under
``jax.vjp``, asserted in interpret mode by tests/test_pallas_kernels.py and
tests/test_flash_window.py, and on the chip by tests_tpu/.

What the kernels take (``flash_attention``'s docstring has the contract):

- self-attention, ``causal`` or not, and with ``causal`` a ``window``: a
  query sees itself and the ``window - 1`` keys before it. Tiles that lie
  wholly above the diagonal or wholly behind the window are *skipped*, not
  masked: each program loops over the key (or query) blocks of its band
  only, and masks only the tiles the diagonal, the window's edge or the
  padding cuts through. Where the window is a multiple of the block (and
  the length is unpadded) those two tiles of a query block are evaluated
  as *one*: with local row ``r`` and column ``c`` the diagonal tile keeps
  ``c <= r`` and the tile at the window's edge, ``window / block`` key
  blocks back, keeps ``c > r``, together one tile of pairs. The folded
  tile's scores go through one max, ``exp`` and sum (no running max, no
  rescale at ``window == block``), and each needed pair is evaluated once
  (1.03 evaluations a needed pair at T = 8192, block and window 512, for
  2.00; ``tile_counts``). It is put together by quadrants of half a block
  (``_fold_nt``, ``_fold_nn``): the lower left is the diagonal block's
  whole, the upper right the edge block's, and the two on the diagonal are
  selected from both by a mask of two local iotas, so that of either
  block's product only the three quadrants that hold needed pairs are
  multiplied. The backward folds the same way (key block ``j`` with query
  blocks ``j`` and ``j + window / block``, the row statistics selected
  with them; ``_fold_tn`` takes the tile's ``ds`` apart again for the two
  blocks' dQ). The query blocks with no edge block (the first ``window /
  block``) and the key blocks with no later query block keep the masked
  tiles; any other window, and every padded length, compiles the masked
  schedule alone;
- grouped key-value heads: ``k`` and ``v`` may hold ``H / G`` heads; query
  head ``h`` reads head ``h // G`` through the block index, so the repeated
  keys and values are never written anywhere. ``dk`` and ``dv`` come out of
  the kernel per query head, through the block index ordered by a head's
  place in its group first, and are summed over the group by XLA as ``G``
  runs side by side;
- any head size. Where it is a multiple of 128 the kernels read
  ``(B, T, H*D)`` as it leaves the projection, a 128-lane column block a
  head, with no transpose; otherwise (the ViT's 16 to 64) the arrays are
  put head-major first, ``(B, H, T, D)``, because a block's last dimension
  has to be a multiple of 128 lanes or the whole array's. Who calls at
  which size: ``models/decoder.py`` (``laguna``) at 128; the ViT at 16 to
  64, head-major; ``models/sambay.py`` (differential attention: heads of
  64 whose values are 128 wide) lays its queries and keys out at the
  values' width with zeros behind and passes the scale of 64, so its
  three kinds of core (window, full, and cross over an earlier layer's
  keys and values) are calls at 128 on the lane-blocked path, one score
  map a query head, and the key and value widths stay one argument;
- operands in the type they arrive in (bf16 in the training cells, f32 in
  the tests), every matmul accumulated in float32, scores and softmax in
  float32, the probabilities cast to the operands' type only where they
  enter a matmul.

A forward program holds one head's whole ``(T, D)`` keys and values in
VMEM: 2 MB each at T = 8192, D = 128 in bf16, fetched once per key-value
head (the block index does not change inside a group). A backward program
holds the head's whole queries and output gradients, the dQ block they
leave through (two buffers each) and the float32 accumulator:
``T D (6 itemsize + 4)`` bytes, 16 MiB at T = 8192 and 32 MiB at 16,384
in bf16 at D = 128, under ``VMEM_LIMIT_BYTES`` with room for the tiles.
The shape rule (``_backward_vmem_limit``): a longer call raises its limit
by what it keeps resident, and one that would pass ``VMEM_MAX_BYTES``
(T = 49,152 in bf16 at D = 128) is refused by name; there is no second
path.

What a recomputed block keeps. The backward kernel reads q, k, v and the
forward kernel's two results, ``out`` and the rows' ``lse``. Were the
forward kernel called inside a ``custom_vjp``'s forward rule, a block under
``jax.checkpoint`` (``nn.remat``) would run it twice a step: a policy is
asked about the equations of the block's own program, where the whole
``custom_vjp`` is one equation, so it never sees inside the rule, and the
recomputation runs the rule again for its residuals. So the kernel is
called outside any ``custom_vjp``, on ``stop_gradient`` of q, k and v (no
tangent reaches it, so nothing asks for its derivative), its results are
named there (``FLASH_OUT_NAME``, ``FLASH_LSE_NAME``: ``checkpoint_name``,
an identity by itself), and ``_with_gradients``, whose primal is the
identity on ``out``, takes ``(q, k, v, out, lse)``, keeps them as its
residuals and hands the backward kernel's ``dq, dk, dv`` back. Under a
policy that keeps the two names (``models/decoder.py recomputed``: the
three token models) the recomputed block has ``out`` and ``lse`` already
and the kernel's equation is dropped from it: ``flash_fwd`` runs once a
layer and step, for ``out`` in the operands' type and 4 bytes a (row, head)
kept from the forward pass to the block's backward. With no policy, or one
without the names (``nn.remat(TransformerBlock)``), and with no
recomputation at all, the program is what it was.

Layout of the public function: ``(B, T, H, D)``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from pytorch_distributed_mnist_tpu.ops.attention import (
    CORE_SCOPE,
    FLASH_LSE_NAME,
    FLASH_OUT_NAME,
    NEG_INF,
)
from pytorch_distributed_mnist_tpu.ops.pallas.backend import should_interpret
from pytorch_distributed_mnist_tpu.utils.profiling import flash_schedules

__all__ = ["flash_attention", "sharded_flash_attention", "tile_counts"]

LANES = 128
# Scoped VMEM a program may use: two buffers each of a head's whole keys
# and values (8 MB at T = 8192, D = 128, bf16; 16 MB in f32) and a few
# (block, block) float32 tiles. The v5e has 128 MiB.
VMEM_LIMIT_BYTES = 64 * 1024 * 1024
# The backward's shape rule (``_backward_vmem_limit``): beside what it
# keeps resident a program's tiles and stack took 1.5 MiB (bf16) to 9.2 MiB
# (float32 operands under ``highest``) in compiles for a described v5e; no
# call is given more than 112 of the chip's 128 MiB.
VMEM_TILE_BYTES = 16 * 1024 * 1024
VMEM_MAX_BYTES = 112 * 1024 * 1024
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_NN = (((1,), (0,)), ((), ()))  # a @ b
_TN = (((0,), (0,)), ((), ()))  # a.T @ b


def _dot(a, b, dims):
    """A tile matmul accumulated in float32. Float32 operands take the
    ambient ``jax.default_matmul_precision`` (the tests' ``highest``);
    16-bit operands are one MXU pass whatever it says, which Mosaic
    refuses to be told otherwise."""
    precision = (jax.lax.Precision.DEFAULT
                 if jnp.dtype(a.dtype).itemsize < 4 else None)
    return jax.lax.dot_general(a, b, dims, precision=precision,
                               preferred_element_type=jnp.float32)


def _visible(iq, jk, block, t_real, causal, window, keys_first=False):
    """Which (query, key) pairs of tile (iq, jk) count: both in range, the
    key not after the query (``causal``) and fewer than ``window`` before
    it. ``(block, block)`` bool, queries along the rows, or along the
    columns where ``keys_first`` (the transposed tiles of ``_bwd_kernel``).

    The causal form is start-aligned (qi >= ki), identical to the dense
    oracle's end-aligned mask because Tq == Tk, which ``flash_attention``
    asserts."""
    shape = (block, block)
    q_axis, k_axis = (1, 0) if keys_first else (0, 1)
    qi = iq * block + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
    ki = jk * block + jax.lax.broadcasted_iota(jnp.int32, shape, k_axis)
    keep = (qi < t_real) & (ki < t_real)
    if causal:
        keep &= ki <= qi
    if window is not None:
        keep &= ki > qi - window
    return keep


def _key_blocks(iq, block, n, t_real, causal, window, xp=jnp):
    """Key blocks of query block ``iq``: ``[lo, hi)`` holds every visible
    key; inside it ``[a, b)`` are the blocks every query of the block sees
    whole, which need no mask. Integers, traced where ``iq`` is; with
    ``xp=np`` and a Python ``iq`` plain numbers (``tile_counts``)."""
    q0 = iq * block
    q1 = q0 + block - 1
    lo, hi, a = 0, n, 0
    b = t_real // block  # key blocks without padding
    if causal:
        hi = xp.minimum(iq + 1, n)
        b = xp.minimum(b, (q0 + 1) // block)  # last key not after q0
    if window is not None:
        lo = xp.maximum(q0 - window + 1, 0) // block
        a = (xp.maximum(q1 - window + 1, 0) + block - 1) // block
    # A block with padded query rows is masked throughout.
    b = xp.where((iq + 1) * block <= t_real, b, 0)
    a = xp.clip(a, lo, hi)
    return lo, a, xp.clip(b, a, hi), hi


def _query_blocks(jk, block, n, t_real, causal, window, xp=jnp):
    """The same for key block ``jk``: the query blocks that see any of its
    keys, and those among them that see all of them unmasked."""
    k0 = jk * block
    k1 = k0 + block - 1
    lo, hi, a = 0, n, 0
    b = t_real // block  # query blocks without padding
    if causal:
        lo = xp.minimum(jk, n)
        a = (k1 + block - 1) // block  # first block wholly at or after k1
    if window is not None:
        hi = xp.minimum((k1 + window - 1) // block + 1, n)
        # last row of block i, i*block + block - 1, still sees k0
        b = xp.minimum(b, (k0 + window - block) // block + 1)
    b = xp.where((jk + 1) * block <= t_real, b, 0)
    a = xp.clip(a, lo, hi)
    return lo, a, xp.clip(b, a, hi), hi


def _fold_width(t, block, n, window):
    """``window / block`` where a banded call folds (module docstring): the
    window a multiple of the block, the length unpadded, and some query
    block far enough in to have an edge block. Else 0, and the call
    compiles the masked schedule alone."""
    if window is None or window % block or t != n * block:
        return 0
    width = window // block
    return width if width < n else 0


def _on_diagonal(block, keys_first=False):
    """The pairs a diagonal tile keeps, key not after query, by local row
    and column: ``(block, block)`` bool, queries along the rows or, where
    ``keys_first``, along the columns. Where the window is a multiple of
    the block, the tile at the window's edge keeps exactly the others."""
    r = jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)
    return r <= c if keys_first else c <= r


def _fold_nt(x, y_lo, y_up, low):
    """The folded ``(block, block)`` tile of ``x y^T``: its entries at and
    below the diagonal from ``y_lo``, those above from ``y_up``. By
    quadrants of half a block: the lower left is ``y_lo``'s whole, the
    upper right ``y_up``'s, and only the two on the diagonal are selected,
    by ``low`` (``(block / 2, block / 2)``; it says on which side the
    diagonal itself falls). The halves of the two products that a select
    over the whole tile would drop are never multiplied: 6 quarter tiles
    for 8."""
    h = x.shape[0] // 2
    left = _dot(x, y_lo[:h], _NT)    # columns [0, h): every row reads y_lo
    right = _dot(x, y_up[h:], _NT)   # columns [h, block): every row y_up
    top_left = jnp.where(low, left[:h], _dot(x[:h], y_up[:h], _NT))
    bottom_right = jnp.where(low, _dot(x[h:], y_lo[h:], _NT), right[h:])
    return jnp.concatenate(
        [jnp.concatenate([top_left, left[h:]], axis=0),
         jnp.concatenate([right[:h], bottom_right], axis=0)], axis=1)


def _fold_nn(p, y_lo, y_up, low):
    """``p_lo y_lo + p_up y_up`` for a folded tile ``p`` (``_fold_nt``'s
    layout): its entries at and below the diagonal meet ``y_lo`` and the
    others ``y_up``, by the same quadrants and again in 6 quarter tiles."""
    h = p.shape[0] // 2
    dtype = y_lo.dtype
    top_left, bottom_right = p[:h, :h], p[h:, h:]
    to_lo = jnp.concatenate(
        [jnp.where(low, top_left, 0.0), p[h:, :h]], axis=0)
    to_up = jnp.concatenate(
        [p[:h, h:], jnp.where(low, 0.0, bottom_right)], axis=0)
    return (_dot(to_lo.astype(dtype), y_lo[:h], _NN)
            + _dot(to_up.astype(dtype), y_up[h:], _NN)
            + jnp.concatenate(
                [_dot(jnp.where(low, 0.0, top_left).astype(dtype),
                      y_up[:h], _NN),
                 _dot(jnp.where(low, bottom_right, 0.0).astype(dtype),
                      y_lo[h:], _NN)], axis=0))


def _banded_loop(ranges, body, carry):
    """``body(j, carry, masked)`` over ``[lo, a)`` masked, ``[a, b)`` not,
    ``[b, hi)`` masked: three loops, so the unmasked body is compiled
    without the mask's iota, compares and selects."""
    lo, a, b, hi = ranges
    for start, stop, masked in ((lo, a, True), (a, b, False), (b, hi, True)):
        carry = jax.lax.fori_loop(
            start, stop, functools.partial(body, masked=masked), carry)
    return carry


def _eye(n):
    return (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
            == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))


def _col_to_row(col):
    """(n, 1) -> (1, n) with a select and a reduction over sublanes: the
    row statistics are kept lane-major in HBM (n floats, not n x 128), and
    this costs n^2 selects once a block against ~10 n^2 a loop step."""
    return jnp.sum(jnp.where(_eye(col.shape[0]), col, 0.0), axis=0,
                   keepdims=True)


def _rows(ref, j, block):
    return ref[pl.ds(pl.multiple_of(j * block, block), block), :]


# --------------------------------------------------------------------------
# Forward
# --------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block: int,
                causal: bool, window, scale: float, t_real: int, fold: int):
    """One (batch, head, q-block) program: stream the key blocks of the
    band, online softmax; where the call folds (``fold`` blocks a window),
    a query block that has an edge block starts from its folded tile.

    Emits both the normalized output block and the row logsumexp
    ``lse = m + log(l)`` — the single residual the backward kernels need to
    reconstruct any P tile.
    """
    iq = pl.program_id(2)
    q = q_ref[...]  # (BQ, D)
    d = q.shape[-1]

    def body(j, carry, masked):
        o, m, l = carry
        k_blk, v_blk = _rows(k_ref, j, block), _rows(v_ref, j, block)
        s = _dot(q, k_blk, _NT) * scale  # (BQ, BK) float32
        if masked:
            keep = _visible(iq, j, block, t_real, causal, window)
            s = jnp.where(keep, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if masked:
            p = jnp.where(keep, p, 0.0)
        corr = jnp.exp(m - m_new)  # m == m_new == NEG_INF: 1, times 0
        l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        return o * corr + _dot(p.astype(v_blk.dtype), v_blk, _NN), m_new, l

    def finish(o, m, l):
        o_ref[...] = (o / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        lse = jnp.where(l > 0, m + jnp.log(jnp.maximum(l, 1e-30)), NEG_INF)
        lse_ref[...] = _col_to_row(lse)

    def banded():
        finish(*_banded_loop(
            _key_blocks(iq, block, k_ref.shape[0] // block, t_real, causal,
                        window),
            body,
            (jnp.zeros((block, d), jnp.float32),
             jnp.full((block, 1), NEG_INF, jnp.float32),
             jnp.zeros((block, 1), jnp.float32))))

    def folded():
        # Every row holds ``block`` pairs: a plain softmax, no rescale.
        low = _on_diagonal(block // 2)
        k_d, v_d = _rows(k_ref, iq, block), _rows(v_ref, iq, block)
        k_e, v_e = (_rows(k_ref, iq - fold, block),
                    _rows(v_ref, iq - fold, block))
        s = _fold_nt(q, k_d, k_e, low) * scale
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        carry = _fold_nn(p, v_d, v_e, low), m, l
        if fold > 1:  # the whole blocks between edge and diagonal
            carry = jax.lax.fori_loop(
                iq - fold + 1, iq, functools.partial(body, masked=False),
                carry)
        finish(*carry)

    if fold:
        pl.when(iq < fold)(banded)
        pl.when(iq >= fold)(folded)
    else:
        banded()


def _block_sizes(t: int, block: int | None = None):
    # Pad T up to a tile-friendly block multiple (never shrink the block to
    # a divisor of T — a prime T would degrade to block 1); padded K
    # positions are masked inside the kernels, padded Q rows sliced off.
    # Default: 512 from T = 512 up (a (512, 512) float32 score tile is
    # 1 MB; fewer, larger loop steps, and a window of 512 is then one
    # folded tile a query block: its own key block and the one before it,
    # each pair evaluated once), the MXU tile 128 from T = 128 up, and
    # below that T itself rounded up to the sublane count. 256 ran slower
    # on the v5e for both kinds of layer, folded or not (PERF.md).
    if block is None:
        block = 512 if t >= 512 else 128 if t >= 128 else ((t + 7) // 8) * 8
    t_pad = ((t + block - 1) // block) * block
    return block, t_pad


def tile_counts(t: int, block: int | None, causal: bool, window) -> dict:
    """What one sequence and head of a call costs in tiles, by the ranges
    the kernels loop over: ``needed_pairs`` (query, key) pairs the mask
    keeps, ``evaluated_pairs`` the tiles' ``block * block`` each, and the
    tiles by body: ``plain`` (no mask), ``masked`` (``_visible``),
    ``folded`` (diagonal and edge in one). By query block, as the forward
    goes; the backward visits the same tiles by key block."""
    block, t_pad = _block_sizes(t, block)
    n = t_pad // block
    fold = _fold_width(t, block, n, window)
    plain = masked = folded = 0
    for iq in range(n):
        if fold and iq >= fold:
            folded += 1
            plain += fold - 1
        else:
            lo, a, b, hi = (int(x) for x in _key_blocks(
                iq, block, n, t, causal, window, xp=np))
            plain += b - a
            masked += (a - lo) + (hi - b)
    if not causal:
        needed = t * t
    elif window is None or window >= t:
        needed = t * (t + 1) // 2
    else:
        needed = window * (window + 1) // 2 + (t - window) * window
    return {"needed_pairs": needed,
            "evaluated_pairs": (plain + masked + folded) * block * block,
            "plain": plain, "masked": masked, "folded": folded}


class _Layout:
    """How ``(B, T, H, D)`` arrays reach the kernels and how a program's
    ``(rows, D)`` block of head ``h`` is addressed (module docstring)."""

    def __init__(self, d: int, t: int, t_pad: int):
        self.d, self.t, self.t_pad = d, t, t_pad
        self.lane_blocked = d % LANES == 0

    def pack(self, x):
        b, t, h, d = x.shape
        x = (x.reshape(b, t, h * d) if self.lane_blocked
             else x.transpose(0, 2, 1, 3))
        if self.t_pad != t:
            pad = [(0, 0)] * x.ndim
            pad[-2] = (0, self.t_pad - t)
            x = jnp.pad(x, pad)
        return x

    def unpack(self, x):
        x = x[..., :self.t, :]
        if self.lane_blocked:
            return x.reshape(x.shape[0], self.t, -1, self.d)
        return x.transpose(0, 2, 1, 3)

    def shape(self, b: int, h: int):
        return ((b, self.t_pad, h * self.d) if self.lane_blocked
                else (b, h, self.t_pad, self.d))

    def spec(self, rows: int, *, blocked: bool, head=lambda h: h):
        """Block ``i`` of ``rows`` rows (or, not ``blocked``, the whole
        sequence) of head ``head(h)``, for a grid (b, h, i)."""
        if self.lane_blocked:
            return pl.BlockSpec(
                (None, rows, self.d),
                lambda b, h, i: (b, i if blocked else 0, head(h)))
        return pl.BlockSpec(
            (None, None, rows, self.d),
            lambda b, h, i: (b, head(h), i if blocked else 0, 0))


def _row_stat_spec(n_blocks: int, block: int, *, blocked: bool):
    """Spec of a per-row float32 statistic (lse, delta) kept lane-major as
    ``(B, H, T/block, 1, block)``: one ``(1, block)`` row a block, or all
    ``n_blocks`` of a head."""
    if blocked:
        return pl.BlockSpec((None, None, None, 1, block),
                            lambda b, h, i: (b, h, i, 0, 0))
    return pl.BlockSpec((None, None, n_blocks, 1, block),
                        lambda b, h, i: (b, h, 0, 0, 0))


def _params(vmem_limit_bytes: int = VMEM_LIMIT_BYTES):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=vmem_limit_bytes)


def _flash_forward(q, k, v, causal: bool, window, scale: float,
                   interpret: bool, block_override: int | None = None):
    b, t, h, d = q.shape
    group = h // k.shape[2]
    block, t_pad = _block_sizes(t, block_override)
    n = t_pad // block
    lay = _Layout(d, t, t_pad)
    flash_schedules.record(tile_counts(t, block, causal, window))
    whole_kv = lay.spec(t_pad, blocked=False, head=lambda hq: hq // group)
    kernel = functools.partial(
        _fwd_kernel, block=block, causal=causal, window=window,
        scale=scale, t_real=t, fold=_fold_width(t, block, n, window))
    out, lse = pl.pallas_call(
        kernel,
        grid=(b, h, n),
        in_specs=[lay.spec(block, blocked=True), whole_kv, whole_kv],
        out_specs=(lay.spec(block, blocked=True),
                   _row_stat_spec(n, block, blocked=True)),
        out_shape=(
            jax.ShapeDtypeStruct(lay.shape(b, h), q.dtype),
            jax.ShapeDtypeStruct((b, h, n, 1, block), jnp.float32),
        ),
        compiler_params=_params(),
        interpret=interpret,
        name="flash_fwd",
    )(lay.pack(q), lay.pack(k), lay.pack(v))
    return lay.unpack(out), lse


# --------------------------------------------------------------------------
# Backward
# --------------------------------------------------------------------------


def _fold_tn(p, y, low):
    """``p_lo^T y`` and ``p_up^T y`` for a folded tile ``p``
    (``_fold_nt``'s layout): the inverse of ``_fold_nn``, what the tile's
    entries at and below the diagonal give their columns' block and what
    the others give theirs, by the same quadrants, 3 quarter tiles each."""
    h = p.shape[0] // 2
    top_left, bottom_right = p[:h, :h], p[h:, h:]

    def tn(a, b):
        return _dot(a.astype(y.dtype), b, _TN)

    from_lo = jnp.concatenate(
        [tn(jnp.concatenate([jnp.where(low, top_left, 0.0), p[h:, :h]],
                            axis=0), y),
         tn(jnp.where(low, bottom_right, 0.0), y[h:])], axis=0)
    from_up = jnp.concatenate(
        [tn(jnp.where(low, 0.0, top_left), y[:h]),
         tn(jnp.concatenate([p[:h, h:], jnp.where(low, 0.0, bottom_right)],
                            axis=0), y)], axis=0)
    return from_lo, from_up


def _bwd_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref,
                dq_ref, dk_ref, dv_ref, dq_acc, *, block: int, causal: bool,
                window, scale: float, t_real: int, fold: int):
    """Grid (B, H, k-block): stream the band's Q/dO rows, evaluate each
    tile once and take all three gradients from it: one query head's share
    of this key block's dK and dV as the loop's carry, and each query
    block's share of dQ added into ``dq_acc``, the head's whole ``(T, D)`` in
    float32, zeroed at key block 0 and written out at the last (``dq_ref``
    is the head's whole sequence, so it leaves VMEM once a head). The
    tiles are transposed, keys along the rows, so that the per-query
    statistics broadcast as the lane-major rows they are stored as and
    four matmuls are plain or transposed-right; the fifth, ``ds^T k``,
    contracts the rows of both. Folded, key block ``jk`` meets query
    block ``jk`` through the diagonal and ``jk + fold`` through the
    window's edge."""
    jk = pl.program_id(2)
    n = q_ref.shape[0] // block
    k_blk, v_blk = k_ref[...], v_ref[...]     # (BK, D)

    @pl.when(jk == 0)
    def _():
        dq_acc[...] = jnp.zeros(dq_acc.shape, jnp.float32)

    def add_dq(i, dq):
        dq_acc[pl.ds(pl.multiple_of(i * block, block), block), :] += dq

    def body(i, carry, masked):
        dk, dv = carry
        q, do = _rows(q_ref, i, block), _rows(do_ref, i, block)
        lse, delta = lse_ref[i], delta_ref[i]  # (1, BQ)
        p = jnp.exp(_dot(k_blk, q, _NT) * scale - lse)  # (BK, BQ)
        if masked:
            p = jnp.where(
                _visible(i, jk, block, t_real, causal, window,
                         keys_first=True), p, 0.0)
        dv = dv + _dot(p.astype(do.dtype), do, _NN)
        ds = (p * (_dot(v_blk, do, _NT) - delta)).astype(q.dtype)
        add_dq(i, _dot(ds, k_blk, _TN))
        return dk + _dot(ds, q, _NN), dv

    def finish(dk, dv):
        dk_ref[...] = (scale * dk).astype(dk_ref.dtype)
        dv_ref[...] = dv.astype(dv_ref.dtype)

    def banded():
        zero = jnp.zeros(k_blk.shape, jnp.float32)
        finish(*_banded_loop(
            _query_blocks(jk, block, n, t_real, causal, window),
            body, (zero, zero)))

    def folded():
        # Keys along the rows: below the diagonal is the edge block's.
        diag = _on_diagonal(block, keys_first=True)
        low = jnp.logical_not(_on_diagonal(block // 2, keys_first=True))
        q_d, do_d = _rows(q_ref, jk, block), _rows(do_ref, jk, block)
        q_e, do_e = (_rows(q_ref, jk + fold, block),
                     _rows(do_ref, jk + fold, block))
        lse = jnp.where(diag, lse_ref[jk], lse_ref[jk + fold])
        delta = jnp.where(diag, delta_ref[jk], delta_ref[jk + fold])
        p = jnp.exp(_fold_nt(k_blk, q_e, q_d, low) * scale - lse)
        dv = _fold_nn(p, do_e, do_d, low)
        ds = p * (_fold_nt(v_blk, do_e, do_d, low) - delta)
        dq_e, dq_d = _fold_tn(ds, k_blk, low)
        add_dq(jk + fold, dq_e)
        add_dq(jk, dq_d)
        carry = _fold_nn(ds, q_e, q_d, low), dv
        if fold > 1:
            carry = jax.lax.fori_loop(
                jk + 1, jk + fold, functools.partial(body, masked=False),
                carry)
        finish(*carry)

    if fold:
        pl.when(jk + fold >= n)(banded)
        pl.when(jk + fold < n)(folded)
    else:
        banded()

    @pl.when(jk == n - 1)
    def _():
        dq_ref[...] = (scale * dq_acc[...]).astype(dq_ref.dtype)


def _sum_over_group(x, group: int, lay: _Layout, dtype):
    """Per-query-head dK or dV as the kernel wrote it, the heads ordered by
    their place in the group first (``_flash_backward``) -> per key-value
    head, summed in float32: ``group`` runs side by side, of lanes or,
    head-major, of heads. (Ordered as the queries are, the sum would view
    the lanes as ``(H/G, G, D)``, and the array changes its tiling on the
    way there: a copy of all of it wherever ``G`` is not 8.)"""
    if group == 1:
        return x
    parts = jnp.split(x, group, axis=-1 if lay.lane_blocked else 1)
    return sum(part.astype(jnp.float32) for part in parts).astype(dtype)


def _backward_vmem_limit(t_pad: int, d: int, itemsize: int) -> int:
    """Scoped VMEM for a backward program, from what it keeps resident
    (module docstring): the head's whole ``q`` and ``do`` and the dQ block,
    two buffers each, and the float32 accumulator; ``VMEM_LIMIT_BYTES``
    wherever that leaves the tiles ``VMEM_TILE_BYTES``, as both training
    cells' calls do."""
    resident = t_pad * max(d, LANES) * (6 * itemsize + 4)
    limit = max(VMEM_LIMIT_BYTES, resident + VMEM_TILE_BYTES)
    if limit > VMEM_MAX_BYTES:
        raise ValueError(
            f"flash_attention's backward keeps a head's whole queries, "
            f"output gradients and dQ in VMEM: {resident >> 20} MiB at "
            f"T={t_pad}, D={d} and {itemsize} bytes an element, and with "
            f"{VMEM_TILE_BYTES >> 20} MiB for its tiles that passes "
            f"{VMEM_MAX_BYTES >> 20} MiB; shard the sequence")
    return limit


def _flash_backward(q, k, v, o, lse, g, causal: bool, window, scale: float,
                    interpret: bool, block_override: int | None = None):
    b, t, h, d = q.shape
    group = h // k.shape[2]
    block, t_pad = _block_sizes(t, block_override)
    n = t_pad // block
    lay = _Layout(d, t, t_pad)
    vmem_limit = _backward_vmem_limit(t_pad, d, q.dtype.itemsize)
    # delta = rowsum(dO * O): tiny elementwise op, fine in XLA; kept
    # lane-major like lse.
    delta = jnp.einsum("bthd,bthd->bht", g, o,
                       preferred_element_type=jnp.float32)
    if t_pad != t:
        delta = jnp.pad(delta, ((0, 0), (0, 0), (0, t_pad - t)))
    delta = delta.reshape(b, h, n, 1, block)

    flash_schedules.record(tile_counts(t, block, causal, window),
                           backward_kernels=1)
    kv_heads = h // group
    blk_kv = lay.spec(block, blocked=True, head=lambda hq: hq // group)
    # dK and dV leave per query head, head ``j * group + g`` in place
    # ``g * kv_heads + j``: ``_sum_over_group`` adds the ``g``.
    blk_out = lay.spec(
        block, blocked=True,
        head=lambda hq: hq % group * kv_heads + hq // group)
    whole = lay.spec(t_pad, blocked=False)
    stat_whole = _row_stat_spec(n, block, blocked=False)
    dq, dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_kernel, block=block, causal=causal, window=window,
            scale=scale, t_real=t, fold=_fold_width(t, block, n, window)),
        grid=(b, h, n),
        in_specs=[blk_kv, blk_kv, whole, whole, stat_whole, stat_whole],
        out_specs=(whole, blk_out, blk_out),
        out_shape=(
            jax.ShapeDtypeStruct(lay.shape(b, h), q.dtype),
            jax.ShapeDtypeStruct(lay.shape(b, h), k.dtype),
            jax.ShapeDtypeStruct(lay.shape(b, h), v.dtype),
        ),
        scratch_shapes=[pltpu.VMEM((t_pad, d), jnp.float32)],
        compiler_params=_params(vmem_limit),
        interpret=interpret,
        # ``benchmark/scopes_lm.py`` finds the backward's calls and time
        # under a name that begins ``flash_bwd_dq``.
        name="flash_bwd_dq_dkv",
    )(lay.pack(k), lay.pack(v), lay.pack(q), lay.pack(g), lse, delta)

    return (
        lay.unpack(dq),
        lay.unpack(_sum_over_group(dk, group, lay, k.dtype)),
        lay.unpack(_sum_over_group(dv, group, lay, v.dtype)),
    )


# --------------------------------------------------------------------------
# custom_vjp plumbing
# --------------------------------------------------------------------------


def _flash(q, k, v, causal, window, scale, block):
    """The forward kernel on constants of the differentiation, its two
    results named, and ``_with_gradients`` to carry the gradients past it
    (module docstring, "What a recomputed block keeps")."""
    out, lse = _flash_forward(
        *map(jax.lax.stop_gradient, (q, k, v)), causal, window, scale,
        should_interpret(), block)
    return _with_gradients(
        q, k, v, checkpoint_name(out, FLASH_OUT_NAME),
        checkpoint_name(lse, FLASH_LSE_NAME), causal, window, scale, block)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _with_gradients(q, k, v, out, lse, causal, window, scale, block):
    """``out``, as a function of q, k and v whose gradients are the
    backward kernel's."""
    return out


def _with_gradients_fwd(q, k, v, out, lse, causal, window, scale, block):
    return out, (q, k, v, out, lse)


def _with_gradients_bwd(causal, window, scale, block, residuals, g):
    q, k, v, out, lse = residuals
    # ``out`` and ``lse`` arrive as constants: no gradient goes back to them.
    return *_flash_backward(
        q, k, v, out, lse, g, causal, window, scale, should_interpret(),
        block), None, None


_with_gradients.defvjp(_with_gradients_fwd, _with_gradients_bwd)


def flash_attention(q, k, v, *, causal: bool = False,
                    window: int | None = None,
                    scale: float | None = None, block: int | None = None):
    """Flash attention on ``(B, T, H, D)``; drop-in for ``full_attention``.

    Fully differentiable with fused Pallas forward and backward kernels
    (no (T, T) materialization in either pass); on the CPU backend the
    kernels run in interpreter mode so tests are hermetic. Self-attention
    shapes only: Tq must equal Tk (the kernel's start-aligned causal mask
    and the dense oracle's end-aligned mask agree exactly there).

    ``k`` and ``v`` may hold fewer heads than ``q`` (grouped key-value
    heads: ``H_q`` a multiple of ``H_kv``, query head ``h`` reads head
    ``h // (H_q / H_kv)``). ``window`` (with ``causal``): a query sees
    itself and the ``window - 1`` keys before it; blocks outside the band
    are not computed.

    ``block`` overrides the q/k tile edge (multiple of 8; default 512 from
    T = 512 up, 128 from T = 128 up).
    """
    if q.shape[1] != k.shape[1]:
        raise ValueError(
            f"flash_attention requires Tq == Tk (self-attention); got "
            f"Tq={q.shape[1]}, Tk={k.shape[1]} — use full_attention for "
            f"cross-attention shapes"
        )
    if k.shape != v.shape or q.shape[2] % k.shape[2] \
            or q.shape[::3] != k.shape[::3]:
        raise ValueError(
            f"flash_attention: q {q.shape} needs k and v of one shape "
            f"(B, T, H_kv, D) with H_kv dividing H_q; got k {k.shape}, "
            f"v {v.shape}")
    if window is not None and (not causal or window < 1):
        raise ValueError(
            f"window={window} needs causal=True and window >= 1")
    if block is not None and (block < 8 or block % 8):
        raise ValueError(f"block must be a multiple of 8, got {block}")
    if block is not None and block > 512:
        # VMEM-derived cap: the kernels' f32 tiles grow as block^2 (s, p,
        # ds — 1 MB each at 512) beside several block x D operands.
        raise ValueError(
            f"block must be <= 512 (block^2 f32 scratch exceeds VMEM "
            f"beyond that), got {block}")
    if scale is None:
        scale = q.shape[-1] ** -0.5
    with jax.named_scope(CORE_SCOPE):
        return _flash(q, k, v, causal, window, float(scale), block)


def sharded_flash_attention(q, k, v, *, mesh, batch_axis=None,
                            head_axis=None, causal: bool = False,
                            scale: float | None = None):
    """Flash attention embedded in a GSPMD program via nested shard_map.

    Attention is embarrassingly parallel over batch AND heads, so on a
    ``data x model`` mesh each device runs the kernel on its local
    ``(B/dp, T, H/tp, D)`` block — no gather, no cross-device softmax.
    This is how ``--attention flash`` composes with ``--tensor-parallel``
    (the CLI passes ``head_axis='model'``): the Megatron rule table
    shards the qkv/proj weights on heads, and this wrapper keeps the
    kernel's view consistent with that layout. Head count must divide the
    head-axis size (the same requirement the TP rules impose).
    """
    from jax.sharding import PartitionSpec as P

    spec = P(batch_axis, None, head_axis, None)
    fn = functools.partial(flash_attention, causal=causal, scale=scale)
    return jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )(q, k, v)
