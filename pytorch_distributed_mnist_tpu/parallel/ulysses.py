"""Ulysses-style sequence parallelism: all-to-all head resharding.

The second of the two sequence-parallel strategies (the brief's "ring
attention OR all-to-all sequence/context parallelism"); the reference has
neither (no attention at all, ``/root/reference/multi_proc_single_gpu.py:
119-126``, SURVEY.md section 2c).

Scheme: activations arrive sequence-sharded ``(B, T/n, H, D)``. One
``lax.all_to_all`` re-shards heads instead of tokens -> ``(B, T, H/n, D)``;
each device then runs plain dense attention over the FULL sequence for its
own head subset (attention is embarrassingly parallel over heads); a second
all-to-all restores sequence sharding. Two all-to-alls per attention call
ride ICI; compute is untouched dense attention, which XLA already maps
perfectly onto the MXU — the tradeoff vs the ring (``parallel/ring.py``) is
O(T^2) score memory per device but fewer, larger collectives.

Requires ``num_heads % axis_size == 0``.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from pytorch_distributed_mnist_tpu.ops.attention import CORE_SCOPE, full_attention


def ulysses_attention_local(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    axis_name: str,
    causal: bool = False,
    scale: Optional[float] = None,
    local_attention: Optional[Callable] = None,
) -> jnp.ndarray:
    """Per-device body; token axis sharded on ``axis_name`` (inside shard_map).

    ``local_attention`` is the per-device kernel over the full sequence /
    local heads (default: dense ``full_attention``). Because Ulysses hands
    each device the WHOLE sequence for its head subset, the Pallas flash
    kernel slots in directly — unlike the ring, whose blockwise online
    softmax supplies its own attention. This is how ``--attention flash``
    composes with ``--sequence-parallel-impl ulysses`` from the CLI.
    """
    n = lax.axis_size(axis_name)
    if q.shape[2] % n:
        raise ValueError(
            f"num_heads {q.shape[2]} not divisible by axis size {n}"
        )
    attn = local_attention if local_attention is not None else full_attention

    def to_heads(x):  # (B, T/n, H, D) -> (B, T, H/n, D)
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=True)

    def to_tokens(x):  # (B, T, H/n, D) -> (B, T/n, H, D)
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2, tiled=True)

    o = attn(to_heads(q), to_heads(k), to_heads(v), causal=causal, scale=scale)
    return to_tokens(o)


def ulysses_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    mesh: Mesh,
    axis: str = "seq",
    batch_axis: Optional[str] = None,
    causal: bool = False,
    scale: Optional[float] = None,
    local_attention: Optional[Callable] = None,
) -> jnp.ndarray:
    """Ulysses attention on GLOBAL ``(B, T, H, D)`` arrays; T sharded on ``axis``.

    ``batch_axis`` composes with data parallelism (B sharded); the head axis
    cannot also be mesh-sharded here — Ulysses itself re-shards heads.
    """
    spec = P(batch_axis, axis, None, None)
    fn = partial(ulysses_attention_local, axis_name=axis, causal=causal,
                 scale=scale, local_attention=local_attention)
    with jax.named_scope(CORE_SCOPE):
        return jax.shard_map(
            fn,
            mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
            check_vma=False,
        )(q, k, v)
