"""State-space duality (Mamba-2, arXiv:2405.21060): the state-space
recurrence whose decay is one number a head and position, computed by
chunks as matrix products.

For every sequence and head ``h``, with a state ``S`` of ``(P, N)``:

    S_t = exp(dt_t[h] A[h]) S_{t-1} + dt_t[h] x_t[h] B_t^T,      S_0 = 0
    y_t[h] = S_t C_t + D[h] x_t[h]

``B_t`` and ``C_t`` (N,) are shared by all heads (one group). Because the
decay is a scalar, what position ``j`` adds to ``y_i`` is ``(C_i . B_j)
exp(a_{j+1} + .. + a_i) dt_j x_j`` with ``a = dt A``: inside a chunk of
``Q`` positions that is a masked ``(Q, Q)`` product, and between chunks
only the state at a chunk's end is handed on (``ops/pallas/ssd.py`` has the
chunk's equations and the kernels, interpreted on the CPU). The selective
scan of ``ops/ssm.py`` is the other case: its decay differs by channel and
state, so it has no such form and steps through time. They are two
functions because they are two recurrences, not two ways to one result:
neither computes what the other is asked for.

:func:`ssd_scan` never holds the per-position states (17 GB a sequence in
float32 at T = 8,192, 64 heads of 64 x 128) nor a ``(Q, Q)`` tile in HBM.
The forward keeps, for the backward, its operands and the float32 state at
each chunk's start (``T / Q`` states of ``(H, P, N)``); the backward, a
``custom_vjp``, walks the chunks from the last to the first, computes a
chunk again from its kept start and carries the state's gradient across
the boundary. The chunk's length follows from the shape
(``ops/pallas/ssd.py chunk_length``: 256, Mamba-2's, from 256 positions
up). ``dt``, ``a``, its running sum, the decay and the state are float32
whatever ``x`` is; matrix operands are in ``x``'s type with float32
accumulation.

Every traced call is counted in ``utils.profiling.scan_log``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from pytorch_distributed_mnist_tpu.ops.pallas import ssd as kernels
from pytorch_distributed_mnist_tpu.ops.pallas.backend import should_interpret
from pytorch_distributed_mnist_tpu.utils.profiling import scan_log

__all__ = ["ssd_scan"]

# Jitted so that a model's layers of one shape trace a kernel's body once a
# program and not once a layer (0.1-0.9 s a trace; nine layers, forward,
# recomputed forward and backward, are 27 a pass of the benchmark's cell);
# XLA inlines the call. Which lowering a call gets is asked outside, a
# call, and is part of the key.
_STATIC = ("heads", "p", "q", "interpret")
_forward = jax.jit(kernels.forward, static_argnames=_STATIC)
_backward = jax.jit(kernels.backward, static_argnames=_STATIC)


def _by_position(dt, a_neg, q: int):
    """``cols`` (B, chunks, Q, W) and ``rows`` (B, chunks, R, Q) of
    ``ops/pallas/ssd.py``: the running sum of ``dt A`` inside each chunk
    and ``dt``, a head a lane and a head a sublane."""
    bsz, t, heads = dt.shape
    dt = dt.reshape(bsz, t // q, q, heads)
    both = jnp.concatenate([jnp.cumsum(dt * a_neg, axis=2), dt], axis=-1)
    cols = jnp.pad(both, ((0, 0),) * 3 + (
        (0, kernels.round_up(2 * heads, kernels.LANES) - 2 * heads),))
    rows = jnp.pad(jnp.swapaxes(both, 2, 3), ((0, 0),) * 2 + (
        (0, kernels.round_up(2 * heads, kernels.SUBLANES) - 2 * heads),
        (0, 0)))
    return cols, rows


def _call_forward(x, dt, a_neg, d, b, c, p, q):
    cols, rows = _by_position(dt, a_neg, q)
    return _forward(x, b, c, cols, rows, jnp.repeat(d, p)[None],
                    heads=dt.shape[-1], p=p, q=q,
                    interpret=should_interpret())


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _ssd(x, dt, a_neg, d, b, c, p, q):
    return _call_forward(x, dt, a_neg, d, b, c, p, q)[0]


def _ssd_fwd(x, dt, a_neg, d, b, c, p, q):
    y, starts = _call_forward(x, dt, a_neg, d, b, c, p, q)
    return y, (x, dt, a_neg, d, b, c, starts)


def _ssd_bwd(p, q, residuals, g):
    x, dt, a_neg, d, b, c, starts = residuals
    bsz, t, heads = dt.shape
    cols, rows = _by_position(dt, a_neg, q)
    dx, db, dc, cols_out, rows_out = _backward(
        x, g, b, c, cols, rows, jnp.repeat(d, p)[None], starts,
        heads=heads, p=p, q=q, interpret=should_interpret())
    # dL: the row sums and what goes through the state came out as columns,
    # the column sums as rows; da_k is dL summed from k to the chunk's end.
    dl = cols_out[..., :heads] \
        - jnp.swapaxes(rows_out[:, :, :heads], 2, 3)
    da = jax.lax.cumsum(dl, axis=2, reverse=True).reshape(bsz, t, heads)
    ddt = cols_out[..., heads:2 * heads].reshape(bsz, t, heads) + da * a_neg
    d_skip = jnp.sum(
        (g.astype(jnp.float32) * x.astype(jnp.float32)).reshape(
            bsz, t, heads, p), axis=(0, 1, 3))
    return (dx, ddt, jnp.sum(da * dt, axis=(0, 1)), d_skip,
            db.astype(b.dtype), dc.astype(c.dtype))


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def ssd_scan(x, dt, A, B, C, D, *, chunk: int | None = None):
    """``y`` (B, T, H, P) in ``x``'s type for ``x`` (B, T, H, P), ``dt``
    (B, T, H) positive, ``A`` (H,) negative, ``B`` and ``C`` (B, T, N) and
    ``D`` (H,); the module docstring has the recurrence. Differentiable in
    all six. ``P`` divides 128 (a block of 128 lanes holds whole heads).
    ``chunk`` overrides the chunk's length (tests; a multiple of 8)."""
    bsz, t, heads, p = x.shape
    n = B.shape[-1]
    if kernels.LANES % p:
        raise ValueError(
            f"ssd_scan lays {kernels.LANES} // P heads side by side in a "
            f"block of {kernels.LANES} lanes; P = {p} does not divide it")
    q = chunk or kernels.chunk_length(t)
    n_chunks = -(-t // q)
    scan_log.record_chunked(
        chunks=n_chunks, state_bytes=bsz * n_chunks * heads * p * n * 4)
    # Behind the end dt = 0: the state stays and nothing is fed; heads are
    # filled to whole blocks of lanes with zeros, which stay zero.
    pad_t = n_chunks * q - t
    pad_h = -heads % (kernels.LANES // p)
    x2 = jnp.pad(x, ((0, 0), (0, pad_t), (0, pad_h), (0, 0))).reshape(
        bsz, t + pad_t, (heads + pad_h) * p)
    dt32 = jnp.pad(dt.astype(jnp.float32), ((0, 0), (0, pad_t), (0, pad_h)))
    b2, c2 = (jnp.pad(m.astype(x.dtype), ((0, 0), (0, pad_t), (0, 0)))
              for m in (B, C))
    with jax.named_scope("ssd_scan"):
        y = _ssd(x2, dt32, jnp.pad(A.astype(jnp.float32), (0, pad_h)),
                 jnp.pad(D.astype(jnp.float32), (0, pad_h)), b2, c2, p, q)
        return y[:, :t].reshape(bsz, t, heads + pad_h, p)[:, :, :heads]
