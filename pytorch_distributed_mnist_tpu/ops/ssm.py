"""Selective scan (Mamba-1, arXiv:2312.00752): the one sequence mixer here
whose state is a fixed-size matrix and not a cache of keys.

For every sequence, channel ``c`` and state ``n``:

    s_t[c, n] = exp(dt_t[c] A[c, n]) s_{t-1}[c, n] + dt_t[c] B_t[n] a_t[c]
    m_t[c]    = sum_n C_t[n] s_t[c, n] + D[c] a_t[c],            s_0 = 0

The decay differs by channel, by state and by position, so this
recurrence has no matrix-multiplication form: the work is elementwise, 7
operations a (position, channel, state), and sequential in ``t``. The
recurrence that has one, Mamba-2's, whose decay is one number a head and
position, is ``ops/ssd.py``'s: another function, because it is another
recurrence.

:func:`selective_scan` never holds the ``(T, C, N)`` states (5.4 GB a
sequence in float32 at T = 16,384, C = 5,120, N = 16). It walks the
sequence in chunks: the forward keeps the state at each chunk's start (``T
/ chunk`` states of ``(N, C)`` float32), and the backward, a
``custom_vjp``, walks the chunks from the last to the first, recomputes a
chunk's states from its kept start and carries the state's gradient
across the boundary. A chunk is computed by the kernels of
``ops/pallas/ssm.py``, which step through time with the state in VMEM
(interpreted on the CPU); the chunk's length follows from the shape
(``ops/pallas/ssm.py chunk_length``). ``dt``, ``A``, the decay and the
state are float32 whatever ``a`` is.

Every traced call is counted in ``utils.profiling.scan_log``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from pytorch_distributed_mnist_tpu.ops.pallas import ssm as kernels
from pytorch_distributed_mnist_tpu.utils.profiling import scan_log

__all__ = ["selective_scan"]


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _scan(a, dt, a_t, d, b, c, length):
    return kernels.forward(a, dt, a_t, d, b, c, length)[0]


def _scan_fwd(a, dt, a_t, d, b, c, length):
    m, starts = kernels.forward(a, dt, a_t, d, b, c, length)
    return m, (a, dt, a_t, d, b, c, starts)


def _scan_bwd(length, residuals, g):
    a, dt, a_t, d, b, c, starts = residuals
    da, ddt, d_at, db, dc = kernels.backward(
        a, dt, a_t, d, b, c, starts, g, length)
    return da, ddt, d_at, jnp.sum(g * a, axis=(0, 1)), db, dc


_scan.defvjp(_scan_fwd, _scan_bwd)


def selective_scan(a, dt, A, B, C, D, *, chunk: int | None = None):
    """``m`` (B, T, C) in ``a``'s type for ``a`` (B, T, C), ``dt`` (B, T,
    C) positive, ``A`` (C, N) negative, ``B`` and ``C`` (B, T, N) and ``D``
    (C,); the module docstring has the recurrence. Differentiable in all
    six. ``chunk`` overrides the chunk's length (tests; a multiple of 8)."""
    bsz, t, channels = a.shape
    states = A.shape[1]
    length = chunk or kernels.chunk_length(t, channels, states)
    n_chunks = -(-t // length)
    # Behind the end dt = 0: the state stays and nothing is fed; channels
    # are filled to whole lanes with zeros, which stay zero.
    pad_t, pad_c = n_chunks * length - t, -channels % kernels.LANES
    scan_log.record_scan(
        chunks=n_chunks, state_bytes=bsz * n_chunks * channels * states * 4)
    a32, dt32 = (jnp.pad(x.astype(jnp.float32),
                         ((0, 0), (0, pad_t), (0, pad_c))) for x in (a, dt))
    b32, c32 = (jnp.pad(x.astype(jnp.float32), ((0, 0), (0, pad_t), (0, 0)))
                for x in (B, C))
    with jax.named_scope("selective_scan"):
        m = _scan(a32, dt32,
                  jnp.pad(A.astype(jnp.float32).T, ((0, 0), (0, pad_c))),
                  jnp.pad(D.astype(jnp.float32), (0, pad_c)), b32, c32,
                  length)
        return m[:, :t, :channels].astype(a.dtype)
