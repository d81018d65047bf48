"""Attention ops: dense reference + blockwise online-softmax building block.

The reference repo has no attention anywhere (its model is a single
``Linear(784, 10)``, ``/root/reference/multi_proc_single_gpu.py:119-126``;
SURVEY.md section 2c marks every sequence-parallel strategy ABSENT). This
framework carries attention as a first-class op family anyway, because
long-context is first-class in the TPU design: the sequence-parallel
machinery in ``parallel/ring.py`` / ``parallel/ulysses.py`` is built on the
blockwise kernel here, and the ``vit`` model (``models/attention.py``)
exercises it end to end.

Layout convention throughout: ``(B, T, H, D)`` — batch, tokens, heads, head
dim. Precision of the dense op: its matmuls take their operands in the
type the caller passes (bf16 in the ``vit`` cells, float32 in the CPU
presets) and accumulate in float32; the scores, the row max, ``exp``, the
row sum and its log, and the backward's ``delta`` and ``ds`` are float32,
and the probabilities are cast to the operands' type only where they enter
a matmul. No float32 input is rounded to a narrower type. The dense op's
backward is written out (``jax.custom_vjp``), so it reads and writes the
``(B, H, Tq, Tk)`` scores a few times instead of once per primitive of the
softmax; the blockwise form is exactly the online-softmax recurrence
XLA:TPU fuses well — no materialized (T, T) matrix bigger than one
(T_q_block, T_k_block) tile.

Slices. The float32 scores are the largest thing the dense op holds, and
what it costs follows where they live: a call whose scores the compiler
can keep in the chip's fast memory passes over them there, a larger one
streams them through HBM several times a pass. So the forward and the
backward each work on slices of the batch, a slice being every ``n``-th
example (``_over_slices``), with ``n`` read off the call's shape alone
(``slice_count``): the fewest slices whose float32 scores are at most
``SLICE_SCORE_BYTES`` each. A call under the constant, or one whose batch
no divisor brings under it, is traced whole, with no loop. Each example's
values are the same expressions either way.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from pytorch_distributed_mnist_tpu.utils.profiling import (
    dense_attention_slices,
)


NEG_INF = -1e30  # softmax mask value; avoids -inf NaN propagation in exp

# Every ``attention_fn`` runs under this scope, so a profile separates the
# attention core from the qkv and output projections whichever one is in.
CORE_SCOPE = "attn_core"

# What a flash attention call names in its caller's program
# (``jax.ad_checkpoint.checkpoint_name``; ``ops/pallas/flash.py``): the
# forward kernel's result and its rows' logsumexp, all its backward reads
# beside q, k and v. A recomputing policy that keeps both
# (``models/decoder.py recomputed``) runs the forward kernel once a step.
FLASH_OUT_NAME = "flash_out"
FLASH_LSE_NAME = "flash_lse"

# The most float32 scores, in bytes, that one slice of a dense call holds
# (``slice_count``). Measured on a v5e (PR 30; T = 196, head size 64, bf16
# operands; the core's device time, forward and backward, from a trace). Two
# blocks alone at 12 heads and batch 128 (236 MB whole): whole 6.40 ms,
# slices of 64 images (118 MB) 8.20, of 32 (59 MB) 5.76, of 16 (29.5 MB)
# 3.78, of 8 (14.8 MB) 3.75. Inside the ViT training step, a step: at 12
# heads whole 38.6 ms, slices of 32 34.8, of 16 22.6; at 16 heads and batch
# 32 (78.7 MB whole) whole 11.3, slices of 16 (39.3 MB) 13.8, of 8 (19.7 MB)
# 10.1, of 2 (4.9 MB) 11.8. So the line lies between 29.5 MB, where the
# compiler keeps a slice's scores in the fast memory through both matmuls,
# and 39.3 MB, where it does not.
SLICE_SCORE_BYTES = 32 * 2 ** 20


def _masked_scores(q, k, causal, scale, window=None):
    """``scale * q k^T`` in float32, ``(B, H, Tq, Tk)``; what a causal query
    may not see is ``NEG_INF`` (end-aligned, so ``Tq != Tk`` is allowed).
    With ``window`` a query sees itself and the ``window - 1`` keys before
    it."""
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if causal:
        tq, tk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((tq, tk), bool), k=tk - tq)
        if window is not None:
            mask &= ~jnp.tril(jnp.ones((tq, tk), bool), k=tk - tq - window)
        s = jnp.where(mask, s, NEG_INF)
    return s


def slice_count(b: int, h: int, tq: int, tk: int) -> int:
    """Into how many slices of the batch the dense core cuts a call: the
    smallest divisor ``n`` of ``b`` for which the float32 scores of ``b / n``
    examples are at most ``SLICE_SCORE_BYTES``, and 1 (the call stays whole)
    where they already are or no divisor brings them under it."""
    per_example = h * tq * tk * 4
    for n in range(1, b + 1):
        if b % n == 0 and (b // n) * per_example <= SLICE_SCORE_BYTES:
            return n
    return 1


def _over_slices(fn, n, *operands):
    """``fn`` over ``n`` interleaved slices of the leading axis of every
    operand and result: the batch is viewed as ``(B / n, n)`` and slice
    ``j`` is ``[:, j]``, read and written back by index inside one loop, so
    a batch axis that GSPMD has sharded stays sharded the same way in every
    slice and nothing is transposed to put a slice axis first."""
    views = tuple(x.reshape(x.shape[0] // n, n, *x.shape[1:])
                  for x in operands)

    def on_slice(j):
        return fn(*(jax.lax.dynamic_index_in_dim(x, j, 1, keepdims=False)
                    for x in views))

    def body(j, done):
        return tuple(jax.lax.dynamic_update_index_in_dim(r, o, j, 1)
                     for r, o in zip(done, on_slice(j)))

    done = jax.lax.fori_loop(0, n, body, tuple(
        jnp.zeros((x.shape[0], n, *x.shape[1:]), x.dtype)
        for x in jax.eval_shape(on_slice, 0)))
    return tuple(x.reshape(x.shape[0] * n, *x.shape[2:]) for x in done)


def _fwd_slice(q, k, v, causal, scale, window):
    s = _masked_scores(q, k, causal, scale, window)
    m = jnp.max(s, axis=-1, keepdims=True)
    if causal:
        # A fully-masked row (possible when Tq > Tk) must output zeros, not
        # the uniform mean of V: with 0 for its max, every exp(NEG_INF - 0)
        # of the row is 0, here and in the backward's exp(s - lse).
        m = jnp.where(m <= NEG_INF / 2, 0.0, m)
    p = jnp.exp(s - m)
    l = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    o = jnp.einsum(
        "bhqk,bkhd->bqhd", (p / l).astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    ).astype(q.dtype)
    lse = (m + jnp.log(l))[..., 0]  # (B, H, Tq) float32
    return o, lse


# The sliced passes are jitted of their own, so that a model's layers, which
# call them with the same shapes, share one trace and one lowered function:
# traced in line, 24 layers' loops added 5 s to the set-up of ``vit-l16``.
@partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _sliced_fwd(q, k, v, causal, scale, window, n):
    return _over_slices(
        partial(_fwd_slice, causal=causal, scale=scale, window=window),
        n, q, k, v)


@partial(jax.jit, static_argnums=(6, 7, 8, 9))
def _sliced_bwd(q, k, v, o, lse, do, causal, scale, window, n):
    return _over_slices(
        partial(_bwd_slice, causal=causal, scale=scale, window=window),
        n, q, k, v, o, lse, do)


def _traced_slices(q, k):
    """``slice_count`` of a call, entered in the process's counter."""
    n = slice_count(q.shape[0], q.shape[2], q.shape[1], k.shape[1])
    dense_attention_slices.record(n)
    return n


def _dense_fwd(q, k, v, causal, scale, window=None):
    n = _traced_slices(q, k)
    if n == 1:
        o, lse = _fwd_slice(q, k, v, causal, scale, window)
    else:
        o, lse = _sliced_fwd(q, k, v, causal, scale, window, n)
    return o, (q, k, v, o, lse)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _dense_attention(q, k, v, causal, scale, window=None):
    return _dense_fwd(q, k, v, causal, scale, window)[0]


def _bwd_slice(q, k, v, o, lse, do, causal, scale, window):
    s = _masked_scores(q, k, causal, scale, window)
    p = jnp.exp(s - lse[..., None])  # a masked entry is exp(NEG_INF): 0
    delta = jnp.einsum(
        "bqhd,bqhd->bhq", do, o, preferred_element_type=jnp.float32)
    dv = jnp.einsum(
        "bhqk,bqhd->bkhd", p.astype(do.dtype), do,
        preferred_element_type=jnp.float32)
    dp = jnp.einsum(
        "bqhd,bkhd->bhqk", do, v, preferred_element_type=jnp.float32)
    ds = (scale * p * (dp - delta[..., None])).astype(q.dtype)
    dq = jnp.einsum(
        "bhqk,bkhd->bqhd", ds, k, preferred_element_type=jnp.float32)
    dk = jnp.einsum(
        "bhqk,bqhd->bkhd", ds, q, preferred_element_type=jnp.float32)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def _dense_bwd(causal, scale, window, residuals, do):
    """Closed form of softmax attention's VJP: with ``p = softmax(s)``,
    ``ds = scale * p * (dp - rowsum(do * o))``. The probabilities are not
    kept; they are recomputed in float32 from the same operands and the
    row log-sum-exp, so they are the forward's values. Sliced as the
    forward is, in a loop of its own."""
    q, k, v, o, lse = residuals
    n = _traced_slices(q, k)
    if n == 1:
        return _bwd_slice(q, k, v, o, lse, do, causal, scale, window)
    return _sliced_bwd(q, k, v, o, lse, do, causal, scale, window, n)


_dense_attention.defvjp(_dense_fwd, _dense_bwd)


def full_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    window: Optional[int] = None,
) -> jnp.ndarray:
    """Dense softmax attention, ``(B, T, H, D)`` in and out.

    The single-device reference semantics that the ring / Ulysses
    sequence-parallel paths must reproduce exactly (their tests assert
    allclose against this). ``causal`` is end-aligned (``Tq != Tk``
    allowed); a row with nothing to attend to gives zeros and zero
    gradients. ``scale`` defaults to ``D ** -0.5`` and must be a Python
    number, not a traced value. ``window`` (with ``causal``): a query sees
    itself and the ``window - 1`` keys before it. ``k`` and ``v`` may hold
    fewer heads than ``q`` (grouped key-value heads; query head ``h`` reads
    head ``h // (H_q / H_kv)``): they are repeated here, which the flash
    kernels avoid (``ops/pallas/flash.py``).

    A call whose float32 scores ``(B, H, Tq, Tk)`` pass
    ``SLICE_SCORE_BYTES`` runs, in each pass, as a loop over interleaved
    slices of the batch (every ``n``-th example, ``n = slice_count(B, H,
    Tq, Tk)``): one slice's float32 scores are then the largest thing the
    op holds at a time, and the results are the whole call's.

    Matmul operands keep ``q.dtype``, accumulation and the softmax are
    float32 (module docstring). Reverse-mode differentiation takes the
    closed form of ``_dense_bwd``, which keeps q, k, v, the output and the
    float32 row log-sum-exp ``(B, H, Tq)`` between the passes and no
    ``(B, H, Tq, Tk)`` tensor. Forward-mode differentiation (``jax.jvp``,
    ``jacfwd``, ``linearize``) is not supported: ``jax.custom_vjp`` raises.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if window is not None and not causal:
        raise ValueError("window needs causal=True")
    group = q.shape[2] // k.shape[2]
    if group > 1:
        k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
    with jax.named_scope(CORE_SCOPE):
        return _dense_attention(q, k, v, causal, scale, window)


class OnlineSoftmaxState(NamedTuple):
    """Carry of the blockwise (flash-style) attention recurrence.

    ``o``: unnormalized output accumulator, (B, Tq, H, D) float32;
    ``m``: running row max of scores, (B, H, Tq) float32;
    ``l``: running softmax normalizer, (B, H, Tq) float32.
    """

    o: jnp.ndarray
    m: jnp.ndarray
    l: jnp.ndarray


def online_softmax_init(q: jnp.ndarray) -> OnlineSoftmaxState:
    b, tq, h, d = q.shape
    return OnlineSoftmaxState(
        o=jnp.zeros((b, tq, h, d), jnp.float32),
        m=jnp.full((b, h, tq), NEG_INF, jnp.float32),
        l=jnp.zeros((b, h, tq), jnp.float32),
    )


def online_softmax_block(
    state: OnlineSoftmaxState,
    q: jnp.ndarray,
    k_blk: jnp.ndarray,
    v_blk: jnp.ndarray,
    *,
    scale: Optional[float] = None,
    mask: Optional[jnp.ndarray] = None,
) -> OnlineSoftmaxState:
    """Fold one K/V block into the running attention state.

    ``mask``: optional (Tq, Tk_blk) or (B, H, Tq, Tk_blk) boolean, True =
    attend. This is the standard streaming-softmax update: rescale the old
    accumulator by ``exp(m_old - m_new)``, add the new block's contribution.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.float32), k_blk.astype(jnp.float32)
    ) * scale
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    m_new = jnp.maximum(state.m, jnp.max(s, axis=-1))
    # exp(NEG_INF - NEG_INF) must be 0, not 1: a fully-masked-so-far row has
    # m == NEG_INF; guard the correction term.
    corr = jnp.where(state.m <= NEG_INF / 2, 0.0, jnp.exp(state.m - m_new))
    p = jnp.exp(s - m_new[..., None])
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    l_new = state.l * corr + jnp.sum(p, axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p, v_blk.astype(jnp.float32))
    # corr is (B, H, Tq); o is (B, Tq, H, D) -> align axes.
    o_new = state.o * corr.transpose(0, 2, 1)[..., None] + pv
    return OnlineSoftmaxState(o=o_new, m=m_new, l=l_new)


def online_softmax_finish(state: OnlineSoftmaxState, dtype=jnp.float32) -> jnp.ndarray:
    """Normalize the accumulator: ``o / l`` (safe where l == 0)."""
    l = state.l.transpose(0, 2, 1)[..., None]  # (B, Tq, H, 1)
    return (state.o / jnp.maximum(l, 1e-30)).astype(dtype)
