"""Flash attention (Pallas TPU): fused forward AND backward kernels.

Blockwise online-softmax attention: scores are computed tile-by-tile in
VMEM and never materialized as a (T, T) matrix in HBM — in either pass.
The forward kernel additionally emits the per-row logsumexp; the backward
is the standard two-pass flash recipe over that residual:

  delta_i = rowsum(dO_i * O_i)                       (tiny elementwise, XLA)
  P_ij    = exp(scale * q_i.k_j - lse_i)             (recomputed per tile)
  dV_j    = sum_i P_ij^T dO_i
  dS_ij   = P_ij * (dO_i.V_j - delta_i)
  dQ_i    = scale * sum_j dS_ij K_j                  (kernel 1: grid over i)
  dK_j    = scale * sum_i dS_ij^T Q_i                (kernel 2: grid over j)

so gradients also run at flash memory cost — no ``jax.vjp`` of a dense
reference anywhere (earlier revisions recomputed a (T, T) matrix in the
backward, which forfeited the memory win for training). Oracle for all
three kernels: ``full_attention`` under ``jax.vjp``, asserted in interpret
mode by tests/test_pallas_kernels.py.

The reference repo has no attention at all
(``/root/reference/multi_proc_single_gpu.py:119-126``; SURVEY.md section 2c
marks every sequence strategy ABSENT) — this op family exists because
long-context is first-class in the TPU design: ``ring_attention_local``
(parallel/ring.py) accepts any per-block attention update, and this kernel
is what a production config uses inside each ring step.

Layout: ``(B, T, H, D)``; kernels run per (batch*head) with both matmuls
per tile on the MXU in f32 accumulation.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from pytorch_distributed_mnist_tpu.ops.attention import CORE_SCOPE, NEG_INF
from pytorch_distributed_mnist_tpu.ops.pallas.backend import should_interpret

__all__ = ["flash_attention", "sharded_flash_attention"]


def _keep_mask(iq, jk, block_q, block_k, t_real, causal):
    """(BQ, BK) validity: in-range q row, in-range k col, causal triangle.

    The causal form is start-aligned (qi >= ki), identical to the dense
    oracle's end-aligned tril only when Tq == Tk — which ``flash_attention``
    asserts, since the same residuals/padding already require it."""
    qi = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    ki = jk * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    keep = (qi < t_real) & (ki < t_real)
    if causal:
        keep &= qi >= ki
    return keep


# --------------------------------------------------------------------------
# Forward
# --------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_k: int,
                causal: bool, scale: float, block_q: int, t_real: int):
    """One (batch*head, q-block) program: stream K/V blocks, online softmax.

    Emits both the normalized output block and the row logsumexp
    ``lse = m + log(l)`` — the single residual the backward kernels need to
    reconstruct any P tile.
    """
    q = q_ref[0].astype(jnp.float32) * scale  # (BQ, D)
    t = k_ref.shape[1]
    nk = t // block_k
    iq = pl.program_id(1)
    masked = causal or t_real < t

    def body(j, carry):
        o, m, l = carry
        k_blk = k_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (BQ, BK)
        if masked:
            s = jnp.where(
                _keep_mask(iq, j, block_q, block_k, t_real, causal), s, NEG_INF
            )
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if masked:
            p = jnp.where(s <= NEG_INF / 2, 0.0, p)
        corr = jnp.where(m <= NEG_INF / 2, 0.0, jnp.exp(m - m_new))
        l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return o * corr + pv, m_new, l

    d = q_ref.shape[-1]
    o = jnp.zeros((block_q, d), jnp.float32)
    m = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((block_q, 1), jnp.float32)
    o, m, l = jax.lax.fori_loop(0, nk, body, (o, m, l))
    o_ref[0] = (o / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    # lse is carried as (BQ, 1): Mosaic requires the last two block dims be
    # (8, 128)-tile friendly or equal to the array dims, which a flat (1, BQ)
    # row block violates on real TPU (BQ lands in the sublane slot).
    lse_ref[0] = jnp.where(l > 0, m + jnp.log(jnp.maximum(l, 1e-30)), NEG_INF)


def _block_sizes(t: int, block: int | None = None):
    # Pad T up to a tile-friendly block multiple (never shrink the block to
    # a divisor of T — a prime T would degrade to block 1); padded K
    # positions are masked inside the kernels, padded Q rows sliced off.
    # Default block 128 = the MXU tile. No flash-vs-dense ratio is
    # measured at any T on today's code. Bigger tiles at long T are a
    # plausible win (amortized loop/pipeline overhead; s/p scratch is
    # block^2 f32, 256 KB at 256 — well inside VMEM) but UNMEASURED: the
    # on-chip sweep (tools/sweep_flash.py) exists to decide it. Until
    # then the default stays the MXU tile and the hypothesis is reachable
    # via the explicit ``block=`` override.
    if block is None:
        block = 128 if t >= 128 else ((t + 7) // 8) * 8
    t_pad = ((t + block - 1) // block) * block
    return block, t_pad


def _to_heads(x, b, t, h, d, t_pad):
    """(B, T, H, D) -> (B*H, Tp, D): one grid row per batch-head pair."""
    x = x.transpose(0, 2, 1, 3).reshape(b * h, t, d)
    if t_pad != t:
        x = jnp.pad(x, ((0, 0), (0, t_pad - t), (0, 0)))
    return x


def _from_heads(x, b, t, h, d):
    return x[:, :t].reshape(b, h, t, d).transpose(0, 2, 1, 3)


def _flash_forward(q, k, v, causal: bool, scale: float, interpret: bool,
                   block_override: int | None = None):
    b, t, h, d = q.shape
    block, t_pad = _block_sizes(t, block_override)
    qh = _to_heads(q, b, t, h, d, t_pad)
    kh = _to_heads(k, b, t, h, d, t_pad)
    vh = _to_heads(v, b, t, h, d, t_pad)
    kernel = functools.partial(
        _fwd_kernel, block_k=block, causal=causal,
        scale=scale, block_q=block, t_real=t,
    )
    # NOTE: each program holds the full (Tp, D) K and V in VMEM, which caps
    # the sequence around T ~ 16k at D=64 f32 (~16 MB VMEM budget). Past
    # that, stream K/V through a third grid dimension — the online-softmax
    # carry already supports it; the ring (parallel/ring.py) also divides T
    # by the seq-axis size per device before this kernel sees it.
    out, lse = pl.pallas_call(
        kernel,
        grid=(b * h, t_pad // block),
        in_specs=[
            pl.BlockSpec((1, block, d), lambda i, j: (i, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, t_pad, d), lambda i, j: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, t_pad, d), lambda i, j: (i, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=(
            pl.BlockSpec((1, block, d), lambda i, j: (i, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block, 1), lambda i, j: (i, j, 0),
                         memory_space=pltpu.VMEM),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((b * h, t_pad, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, t_pad, 1), jnp.float32),
        ),
        interpret=interpret,
    )(qh, kh, vh)
    return _from_heads(out, b, t, h, d), out, lse


# --------------------------------------------------------------------------
# Backward
# --------------------------------------------------------------------------


def _dq_kernel(q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref, dq_ref, *,
               block_k: int, causal: bool, scale: float, block_q: int,
               t_real: int):
    """Grid (B*H, q-block): stream K/V, accumulate this q-block's dQ."""
    q = q_ref[0].astype(jnp.float32)          # (BQ, D)
    do = do_ref[0].astype(jnp.float32)        # (BQ, D)
    lse = lse_ref[0]                          # (BQ, 1)
    delta = delta_ref[0]                      # (BQ, 1)
    t = k_ref.shape[1]
    nk = t // block_k
    iq = pl.program_id(1)

    def body(j, dq):
        k_blk = k_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        s = scale * jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (BQ, BK)
        keep = _keep_mask(iq, j, block_q, block_k, t_real, causal)
        p = jnp.where(keep, jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (BQ, BK)
        ds = p * (dp - delta)
        return dq + jax.lax.dot_general(
            ds, k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    d = q_ref.shape[-1]
    dq = jax.lax.fori_loop(0, nk, body, jnp.zeros((block_q, d), jnp.float32))
    dq_ref[0] = (scale * dq).astype(dq_ref.dtype)


def _dkv_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, *, block_k: int, causal: bool, scale: float,
                block_q: int, t_real: int):
    """Grid (B*H, k-block): stream Q/dO rows, accumulate dK and dV."""
    k_blk = k_ref[0].astype(jnp.float32)      # (BK, D)
    v_blk = v_ref[0].astype(jnp.float32)      # (BK, D)
    t = q_ref.shape[1]
    nq = t // block_q
    jk = pl.program_id(1)

    def body(i, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(i * block_q, block_q), :].astype(jnp.float32)
        do = do_ref[0, pl.ds(i * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, pl.ds(i * block_q, block_q), :]      # (BQ, 1)
        delta = delta_ref[0, pl.ds(i * block_q, block_q), :]  # (BQ, 1)
        s = scale * jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (BQ, BK)
        keep = _keep_mask(i, jk, block_q, block_k, t_real, causal)
        p = jnp.where(keep, jnp.exp(s - lse), 0.0)
        dv = dv + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (BK, D)
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (BQ, BK)
        ds = p * (dp - delta)
        dk = dk + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (BK, D)
        return dk, dv

    d = k_ref.shape[-1]
    zero = jnp.zeros((block_k, d), jnp.float32)
    dk, dv = jax.lax.fori_loop(0, nq, body, (zero, zero))
    dk_ref[0] = (scale * dk).astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _flash_backward(q, k, v, o_heads, lse, g, causal: bool, scale: float,
                    interpret: bool, block_override: int | None = None):
    b, t, h, d = q.shape
    block, t_pad = _block_sizes(t, block_override)
    qh = _to_heads(q, b, t, h, d, t_pad)
    kh = _to_heads(k, b, t, h, d, t_pad)
    vh = _to_heads(v, b, t, h, d, t_pad)
    doh = _to_heads(g, b, t, h, d, t_pad)
    # delta = rowsum(dO * O): tiny elementwise op, fine in XLA. o_heads is
    # the forward kernel's padded (B*H, Tp, D) output, reused as-is. Kept
    # as (B*H, Tp, 1) like lse so row blocks are Mosaic-tileable.
    delta = jnp.sum(doh * o_heads.astype(jnp.float32), axis=-1,
                    keepdims=True)  # (B*H, Tp, 1)

    common = dict(block_k=block, causal=causal, scale=scale,
                  block_q=block, t_real=t)
    seq_spec = pl.BlockSpec((1, block, d), lambda i, j: (i, j, 0),
                            memory_space=pltpu.VMEM)
    row_spec = pl.BlockSpec((1, block, 1), lambda i, j: (i, j, 0),
                            memory_space=pltpu.VMEM)
    full_spec = pl.BlockSpec((1, t_pad, d), lambda i, j: (i, 0, 0),
                             memory_space=pltpu.VMEM)
    full_row = pl.BlockSpec((1, t_pad, 1), lambda i, j: (i, 0, 0),
                            memory_space=pltpu.VMEM)
    grid = (b * h, t_pad // block)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **common),
        grid=grid,
        in_specs=[seq_spec, seq_spec, row_spec, row_spec, full_spec, full_spec],
        out_specs=seq_spec,
        out_shape=jax.ShapeDtypeStruct((b * h, t_pad, d), q.dtype),
        interpret=interpret,
    )(qh, doh, lse, delta, kh, vh)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, **common),
        grid=grid,
        in_specs=[seq_spec, seq_spec, full_spec, full_spec, full_row, full_row],
        out_specs=(seq_spec, seq_spec),
        out_shape=(
            jax.ShapeDtypeStruct((b * h, t_pad, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, t_pad, d), v.dtype),
        ),
        interpret=interpret,
    )(kh, vh, qh, doh, lse, delta)

    return (
        _from_heads(dq, b, t, h, d),
        _from_heads(dk, b, t, h, d),
        _from_heads(dv, b, t, h, d),
    )


# --------------------------------------------------------------------------
# custom_vjp plumbing
# --------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, causal, scale, block):
    out, _, _ = _flash_forward(
        q, k, v, causal, scale, should_interpret(), block)
    return out


def _flash_fwd(q, k, v, causal, scale, block):
    out, o_heads, lse = _flash_forward(
        q, k, v, causal, scale, should_interpret(), block
    )
    return out, (q, k, v, o_heads, lse)


def _flash_bwd(causal, scale, block, residuals, g):
    q, k, v, o_heads, lse = residuals
    return _flash_backward(
        q, k, v, o_heads, lse, g, causal, scale, should_interpret(), block
    )


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, *, causal: bool = False,
                    scale: float | None = None, block: int | None = None):
    """Flash attention on ``(B, T, H, D)``; drop-in for ``full_attention``.

    Fully differentiable with fused Pallas forward and backward kernels
    (no (T, T) materialization in either pass); on the CPU backend the
    kernels run in interpreter mode so tests are hermetic. Self-attention
    shapes only: Tq must equal Tk (the kernel's start-aligned causal mask and the dense
    oracle's end-aligned mask agree exactly there).

    ``block`` overrides the q/k tile edge (multiple of 8; default 128 —
    the MXU tile. The override exists for the on-chip block sweep,
    tools/sweep_flash.py, which decides whether long sequences get a
    bigger default).
    """
    if q.shape[1] != k.shape[1]:
        raise ValueError(
            f"flash_attention requires Tq == Tk (self-attention); got "
            f"Tq={q.shape[1]}, Tk={k.shape[1]} — use full_attention for "
            f"cross-attention shapes"
        )
    if block is not None and (block < 8 or block % 8):
        raise ValueError(f"block must be a multiple of 8, got {block}")
    if block is not None and block > 512:
        # VMEM-derived cap: the bwd kernel's f32 scratch grows as block^2
        # (s/p tiles — 1 MB each at 512) plus several block x D operands;
        # past 512 the working set approaches the ~16 MB/core VMEM and
        # Mosaic fails with an opaque allocation error rather than this
        # message. The sweep (tools/sweep_flash.py) tops out at 512 too.
        raise ValueError(
            f"block must be <= 512 (block^2 f32 scratch exceeds VMEM "
            f"beyond that), got {block}")
    if scale is None:
        scale = q.shape[-1] ** -0.5
    with jax.named_scope(CORE_SCOPE):
        return _flash(q, k, v, causal, float(scale), block)


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
