"""Fused softmax-cross-entropy as a Pallas TPU kernel (forward + backward).

Parity target is the reference's ``F.cross_entropy(output, target)``
(``/root/reference/multi_proc_single_gpu.py:88``), whose CUDA implementation
is a fused log-softmax + NLL kernel pair. The XLA path
(``ops/loss.py``) already fuses well; this kernel makes the fusion a
guarantee and keeps the whole row pass — max, exp, sum, log, pick — in VMEM
with one HBM read of the logits per direction, the same honesty contract as
the fused Adam kernel (``ops/pallas/adam.py``): guaranteed single-pass, not
a 10x.

Forward: one block row-pass computes the per-example loss AND saves the
log-sum-exp, so the backward never re-reduces — ``dlogits = (exp(l - lse)
- onehot(label)) * g`` is a second single-pass kernel over the same rows.
No (B, C) softmax matrix is ever materialized in HBM in f32 beyond the
dlogits the optimizer actually needs.

Class-count restriction: ``C`` must fit one 128-lane tile (C <= 128 —
MNIST/FashionMNIST have 10). Wider heads would need a lane-tiled
online-softmax (the flash-attention pattern); ``fused_cross_entropy``
asserts rather than silently slowing down.

On the CPU backend the identical kernel runs in Pallas interpret mode, so
the CPU suite exercises the same code path the chip compiles (conftest +
``tests_tpu/`` split, like the other kernels; ``ops/pallas/backend.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from pytorch_distributed_mnist_tpu.ops.pallas.backend import should_interpret

_LANES = 128
_BLOCK_ROWS = 128
_SUBLANE = 8


def _xent_fwd_kernel(c: int, logits_ref, label_ref, loss_ref, lse_ref):
    """One (R, 128) block: per-row loss and log-sum-exp.

    Lanes >= ``c`` are padding: masked to -inf before the max so they
    contribute nothing to the reduction. Padded *rows* (batch tail)
    compute garbage from zero logits; the wrapper slices them away.
    """
    l = logits_ref[:]
    col = jax.lax.broadcasted_iota(jnp.int32, l.shape, 1)
    valid = col < c
    l = jnp.where(valid, l, -jnp.inf)
    m = jnp.max(l, axis=1, keepdims=True)
    ex = jnp.where(valid, jnp.exp(l - m), 0.0)
    lse = m + jnp.log(jnp.sum(ex, axis=1, keepdims=True))
    picked = jnp.sum(
        jnp.where(col == label_ref[:], l, 0.0), axis=1, keepdims=True
    )
    # CE >= 0 analytically; clamp the same way the XLA oracle does
    # (ops/loss.py) so saturated logits never report a negative loss.
    loss_ref[:] = jnp.maximum(lse - picked, 0.0)
    lse_ref[:] = lse


def _xent_bwd_kernel(c: int, logits_ref, label_ref, lse_ref, g_ref, dl_ref):
    """dlogits = (softmax - onehot) * upstream, one pass over the block.

    Gated on the forward's ``max(lse - picked, 0)`` clamp exactly the way
    XLA differentiates it: gradient factor 1 where ``lse > picked``, 0
    where the clamp engaged (``lse < picked``, float-saturation artifact),
    and 0.5 at the exact tie — ``d/dx max(x, 0)`` at x == 0 splits evenly
    on the XLA path, so the fused gradient matches it even at
    float-saturated logits."""
    l = logits_ref[:]
    col = jax.lax.broadcasted_iota(jnp.int32, l.shape, 1)
    valid = col < c
    p = jnp.where(valid, jnp.exp(l - lse_ref[:]), 0.0)
    onehot = jnp.where(col == label_ref[:], 1.0, 0.0)
    picked = jnp.sum(jnp.where(col == label_ref[:], l, 0.0),
                     axis=1, keepdims=True)
    diff = lse_ref[:] - picked
    live = jnp.where(diff > 0.0, 1.0, jnp.where(diff == 0.0, 0.5, 0.0))
    dl_ref[:] = (p - onehot * valid) * g_ref[:] * live


def _pad_rows(b: int) -> int:
    r = min(_BLOCK_ROWS, ((b + _SUBLANE - 1) // _SUBLANE) * _SUBLANE)
    return r


def _prep(logits, labels):
    b, c = logits.shape
    if c > _LANES:
        raise ValueError(
            f"fused cross-entropy handles up to {_LANES} classes per "
            f"128-lane tile; got C={c} — use ops.loss.cross_entropy"
        )
    r = _pad_rows(b)
    n_blocks = (b + r - 1) // r
    bp = n_blocks * r
    # f32 boundary outside the kernel, same rationale as the XLA path's
    # optimization barrier: the reduction must not demote to bf16.
    l32 = jnp.zeros((bp, _LANES), jnp.float32)
    l32 = jax.lax.dynamic_update_slice(
        l32, logits.astype(jnp.float32), (0, 0))
    lab = jnp.zeros((bp, 1), jnp.int32)
    lab = jax.lax.dynamic_update_slice(
        lab, labels.astype(jnp.int32)[:, None], (0, 0))
    return l32, lab, r, n_blocks, bp, c


@functools.partial(jax.custom_vjp, nondiff_argnums=())
def fused_cross_entropy_per_example(logits, labels):
    """Per-example loss, shape (B,) f32 — drop-in for the XLA oracle
    (``ops.loss.cross_entropy_per_example``), differentiable w.r.t.
    ``logits`` through a fused backward kernel."""
    loss, _ = _fwd_impl(logits, labels)
    return loss


def _fwd_impl(logits, labels):
    b = logits.shape[0]
    l32, lab, r, n_blocks, bp, c = _prep(logits, labels)
    loss, lse = pl.pallas_call(
        functools.partial(_xent_fwd_kernel, c),
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((r, _LANES), lambda i: (i, 0)),
            pl.BlockSpec((r, 1), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((r, 1), lambda i: (i, 0)),
            pl.BlockSpec((r, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bp, 1), jnp.float32),
            jax.ShapeDtypeStruct((bp, 1), jnp.float32),
        ],
        interpret=should_interpret(),
    )(l32, lab)
    return loss[:b, 0], lse


def _fwd_rule(logits, labels):
    loss, lse = _fwd_impl(logits, labels)
    return loss, (logits, labels, lse)


def _bwd_rule(res, g):
    logits, labels, lse = res
    b = logits.shape[0]
    l32, lab, r, n_blocks, bp, c = _prep(logits, labels)
    gp = jnp.zeros((bp, 1), jnp.float32)
    gp = jax.lax.dynamic_update_slice(
        gp, g.astype(jnp.float32)[:, None], (0, 0))
    dl = pl.pallas_call(
        functools.partial(_xent_bwd_kernel, c),
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((r, _LANES), lambda i: (i, 0)),
            pl.BlockSpec((r, 1), lambda i: (i, 0)),
            pl.BlockSpec((r, 1), lambda i: (i, 0)),
            pl.BlockSpec((r, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((r, _LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((bp, _LANES), jnp.float32),
        interpret=should_interpret(),
    )(l32, lab, lse, gp)
    dlogits = dl[:b, : logits.shape[1]].astype(logits.dtype)
    return dlogits, None


fused_cross_entropy_per_example.defvjp(_fwd_rule, _bwd_rule)


def fused_cross_entropy(logits, labels, mask=None):
    """Mean (or masked-mean) fused loss — signature parity with
    ``ops.loss.cross_entropy``. The reduction is ``ops.loss.masked_mean``,
    the single owner of the mean semantics for both impls (local import:
    ``loss`` only imports this module inside a function, so no cycle)."""
    from pytorch_distributed_mnist_tpu.ops.loss import masked_mean

    return masked_mean(fused_cross_entropy_per_example(logits, labels), mask)
