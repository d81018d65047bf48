"""Loss functions.

Parity target: ``F.cross_entropy(output, target)`` at
``/root/reference/multi_proc_single_gpu.py:88`` — softmax cross-entropy over
integer class targets, *mean*-reduced over the batch. The mean reduction
matters for distributed semantics: DDP averages gradients across ranks, so a
per-rank batch-mean loss yields the global-batch-mean gradient. The TPU DP
step keeps the same convention (see ``parallel/collectives.py``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def cross_entropy_per_example(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Per-example softmax cross-entropy with integer labels: the class axis
    is the last of ``logits``, and the result has the shape of ``labels``
    (``(B,)`` for images, ``(B, T)`` for token sequences).

    Computed in float32 regardless of the model's compute dtype: the
    log-sum-exp reduction is the numerically delicate part, and float32 here
    costs nothing measurable on TPU (the FLOPs live in the matmuls).

    The optimization barrier is load-bearing: when logits arrive as
    ``astype(f32)`` of a bf16 model output, XLA:TPU's convert-folding will
    otherwise demote the fused exp/log chain back to bf16, inflating the
    reported loss by >10x on a converged model (observed: 0.0105 vs the true
    0.0004 on saturated CNN logits). The barrier pins the f32 boundary; it
    only costs the fusion of this epilogue into the preceding matmul.
    """
    logits = jax.lax.optimization_barrier(logits.astype(jnp.float32))
    logz = jax.nn.logsumexp(logits, axis=-1)
    label_logits = jnp.take_along_axis(
        logits, labels[..., None], axis=-1)[..., 0]
    # CE = -log p >= 0 analytically; XLA:TPU's fused exp/log approximations
    # can drift a saturated logsumexp a few 1e-4 below the max logit, which
    # would surface as a (confusing) negative loss. Clamp at the true bound.
    return jnp.maximum(logz - label_logits, 0.0)


_IMPL = "xla"
_MESH = None
_MESH_AXIS = "data"


def set_loss_impl(name: str, mesh=None, data_axis: str = "data") -> None:
    """Select the cross-entropy implementation: ``xla`` (default) or
    ``fused`` (the Pallas kernel, ``ops/pallas/xent.py``). Resolved at
    trace time, so it must be set before the step functions are jitted
    (the CLI sets it before constructing the Trainer).

    ``mesh``: a pallas call under GSPMD batch sharding would be gathered,
    not partitioned; passing the mesh makes ``cross_entropy`` wrap the
    kernel in a nested ``shard_map`` over ``data_axis`` so each device
    runs it on its local batch shard — the standard way to embed a manual
    kernel in a GSPMD program. Leave ``mesh=None`` when the caller is
    ALREADY inside a shard_map (the explicit trainer mode): shard_maps do
    not nest over the same axis, and there the batch is local anyway."""
    if name not in ("xla", "fused"):
        raise ValueError(f"unknown loss impl {name!r}")
    global _IMPL, _MESH, _MESH_AXIS
    _IMPL = name
    _MESH = mesh if name == "fused" else None
    _MESH_AXIS = data_axis


def get_loss_impl() -> str:
    return _IMPL


def _fused_per_example(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    from pytorch_distributed_mnist_tpu.ops.pallas.xent import (
        fused_cross_entropy_per_example,
    )

    if labels.ndim > 1:  # the kernel takes (rows, classes)
        flat = _fused_per_example(
            logits.reshape(-1, logits.shape[-1]), labels.reshape(-1))
        return flat.reshape(labels.shape)
    if _MESH is None or _MESH.size == 1:
        return fused_cross_entropy_per_example(logits, labels)
    size = _MESH.shape[_MESH_AXIS]
    if logits.shape[0] % size:
        # shard_map needs exact divisibility (GSPMD pads, manual regions
        # cannot); a ragged tail batch statically falls back to the XLA
        # impl — same values, different fusion.
        return cross_entropy_per_example(logits, labels)
    from jax.sharding import PartitionSpec as P

    return jax.shard_map(
        fused_cross_entropy_per_example,
        mesh=_MESH,
        in_specs=(P(_MESH_AXIS), P(_MESH_AXIS)),
        out_specs=P(_MESH_AXIS),
        check_vma=False,
    )(logits, labels)


def example_weights(labels: jnp.ndarray, mask: jnp.ndarray | None):
    """What each label counts for, or ``None`` where all count alike.

    Labels of shape ``(B,)`` (images): ``mask`` as it is, so nothing
    changes for them. Labels with further axes (token sequences, ``(B,
    T)``): a position labelled below zero (``data.tokens.IGNORE``: the last
    of a sequence has no next token) counts nothing, and ``mask`` (0/1 per
    example) applies to all positions of its example."""
    if labels.ndim == 1:
        return mask
    weights = (labels >= 0).astype(jnp.float32)
    if mask is not None:
        weights = weights * mask.astype(jnp.float32).reshape(
            mask.shape + (1,) * (labels.ndim - mask.ndim))
    return weights


def masked_mean(per_ex: jnp.ndarray, mask: jnp.ndarray | None) -> jnp.ndarray:
    """Mean (or masked mean) over per-example losses — the ONE place the
    reduction semantics live, shared by both loss impls so they cannot
    drift. Padded examples (0 in ``mask``) contribute nothing."""
    if mask is None:
        return jnp.mean(per_ex)
    mask = mask.astype(jnp.float32)
    return jnp.sum(per_ex * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def cross_entropy(
    logits: jnp.ndarray, labels: jnp.ndarray, mask: jnp.ndarray | None = None
) -> jnp.ndarray:
    """Mean softmax cross-entropy; with ``mask`` (0/1 per example), a masked
    mean so padded examples (eval batch padding) contribute nothing.
    ``logits`` carry the classes on their last axis and any number of
    leading axes, which ``labels`` share (``example_weights``)."""
    weights = example_weights(labels, mask)
    if labels.ndim > 1:
        labels = jnp.maximum(labels, 0)  # an ignored label indexes class 0
    if _IMPL == "fused":
        per_ex = _fused_per_example(logits, labels)
    else:
        per_ex = cross_entropy_per_example(logits, labels)
    return masked_mean(per_ex, weights)
