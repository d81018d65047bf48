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
dim. TPU notes: scores are computed in float32 (softmax is the numerically
delicate reduction; the MXU matmuls feeding it may be bf16), and the
blockwise form is exactly the online-softmax recurrence XLA:TPU fuses well —
no materialized (T, T) matrix bigger than one (T_q_block, T_k_block) tile.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp


NEG_INF = -1e30  # softmax mask value; avoids -inf NaN propagation in exp

# Every ``attention_fn`` runs under this scope, so a profile separates the
# attention core from the qkv and output projections whichever one is in.
CORE_SCOPE = "attn_core"


def full_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Dense softmax attention, ``(B, T, H, D)`` in and out.

    The single-device reference semantics that the ring / Ulysses
    sequence-parallel paths must reproduce exactly (their tests assert
    allclose against this).
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    with jax.named_scope(CORE_SCOPE):
        qf = q.astype(jnp.float32)
        kf = k.astype(jnp.float32)
        # (B, H, Tq, Tk)
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kf) * scale
        if causal:
            tq, tk = s.shape[-2], s.shape[-1]
            mask = jnp.tril(jnp.ones((tq, tk), bool), k=tk - tq)
            s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
        if causal:
            # A fully-masked row (possible when Tq > Tk) must output zeros, not
            # the uniform mean of V — match the blockwise op's guard below.
            p = jnp.where(mask, p, 0.0)
        p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
        o = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
        return o.astype(q.dtype)


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
