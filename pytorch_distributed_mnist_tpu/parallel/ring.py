"""Ring attention: sequence/context parallelism over a mesh axis.

The reference has no sequence axis at all (its model is ``Linear(784, 10)``,
``/root/reference/multi_proc_single_gpu.py:119-126``; SURVEY.md section 2c
lists ring attention / SP as ABSENT), but long-context is first-class in
this framework's design, so the machinery exists and is tested on the
virtual 8-device mesh.

Design (blockwise ring, a la Ring Attention / blockwise-parallel
transformers): the token axis T is sharded across the ``seq`` mesh axis —
each device holds ``(B, T/n, H, D)`` of Q, K, V. The ring runs n steps; at
step j every device computes one (local Q block) x (visiting K/V block)
online-softmax update (``ops/attention.py``) while ``lax.ppermute`` rotates
the K/V blocks one hop around the ring. Communication is neighbor-to-
neighbor only, which XLA maps onto ICI links; HBM never materializes a
(T, T) score matrix, so sequence length scales linearly in memory per chip.

Causal masking: after j hops, the device at ring position i holds the K/V
block that started at position ``(i - j) mod n``. Block-level global offsets
reconstruct the exact (Tq, Tk) triangular mask, so causal ring attention is
bit-comparable to dense causal attention.

``ring_attention`` works both ways:
- called on GLOBAL arrays under jit (it wraps itself in ``jax.shard_map``
  over the given mesh), or
- ``ring_attention_local`` called INSIDE an enclosing shard_map whose specs
  already shard the token axis.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from pytorch_distributed_mnist_tpu.ops.attention import (
    CORE_SCOPE,
    online_softmax_block,
    online_softmax_finish,
    online_softmax_init,
)


def ring_attention_local(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    axis_name: str,
    causal: bool = False,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Per-device body: local Q/K/V blocks ``(B, T_local, H, D)`` -> local O.

    Must run inside ``shard_map`` (or any context where ``axis_name`` is
    bound) with the token axis sharded on ``axis_name``.
    """
    n = lax.axis_size(axis_name)
    me = lax.axis_index(axis_name)
    t_local = q.shape[1]

    def block_mask(kv_owner):
        """(Tq_local, Tk_local) causal mask between my Q block and the block
        that originated on device ``kv_owner``."""
        q_off = me * t_local
        k_off = kv_owner * t_local
        qi = q_off + jnp.arange(t_local)[:, None]
        ki = k_off + jnp.arange(t_local)[None, :]
        return qi >= ki

    def update(state, kv, j):
        k_blk, v_blk = kv
        owner = (me - j) % n
        mask = block_mask(owner) if causal else None
        return online_softmax_block(state, q, k_blk, v_blk, scale=scale, mask=mask)

    def body(carry, j):
        state, kv = carry
        state = update(state, kv, j)
        # Rotate K/V one hop: device i sends to i+1 (mod n), so at the next
        # step we hold the block owned by (me - j - 1) mod n.
        kv = lax.ppermute(
            kv, axis_name, perm=[(i, (i + 1) % n) for i in range(n)]
        )
        return (state, kv), None

    # n-1 rotations, not n: the blocks rotated on a final scan step would be
    # discarded, so the last update runs outside the scan.
    (state, kv), _ = lax.scan(
        body, (online_softmax_init(q), (k, v)), jnp.arange(n - 1)
    )
    state = update(state, kv, n - 1)
    return online_softmax_finish(state, dtype=q.dtype)


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    mesh: Mesh,
    axis: str = "seq",
    batch_axis: Optional[str] = None,
    head_axis: Optional[str] = None,
    causal: bool = False,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Ring attention on GLOBAL ``(B, T, H, D)`` arrays; T sharded on ``axis``.

    Jit-compatible (shard_map composes under jit). ``batch_axis`` /
    ``head_axis`` extend the in/out specs so the same call composes with
    data parallelism (B sharded) and tensor parallelism (H sharded): the
    ring only ever communicates along ``axis``; the other axes just make
    each device's block smaller.
    """
    spec = P(batch_axis, axis, head_axis, None)
    fn = partial(
        ring_attention_local, axis_name=axis, causal=causal, scale=scale
    )
    with jax.named_scope(CORE_SCOPE):
        return jax.shard_map(
            fn,
            mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
            check_vma=False,
        )(q, k, v)
