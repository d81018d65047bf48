"""Patch-transformer (ViT) model family with pluggable attention.

The reference's zoo is exactly one hard-coded ``Linear(784, 10)``
(``/root/reference/multi_proc_single_gpu.py:119-126, 185``). This framework
treats the model as a registry entry (SURVEY.md section 0) and carries a
small vision transformer in addition to ``linear``/``cnn`` — it is the model
that actually has a sequence axis, so it is the vehicle for the
sequence-parallel machinery (``parallel/ring.py``, ``parallel/ulysses.py``):
``tests/test_vit.py`` trains it with ring attention swapped in (gradients
flow through shard_map + ppermute) and checks ring/dense forward parity.

TPU notes: bfloat16 compute / float32 params and logits (same policy as
``models/cnn.py``); token count is (28/patch)^2 (49 for the default patch 4) —
tiny for MNIST, but the code path is the same one a long-context model
takes, just with T larger and the ``seq`` axis sharded wider.

``attention_fn`` is a static module field: any ``(q, k, v) -> o`` on
``(B, T, H, D)``. Default is dense ``ops.attention.full_attention``
(matmuls in ``compute_dtype`` accumulated in float32, float32 softmax, a
hand-written backward: differentiable in reverse mode only); pass
``partial(ring_attention, mesh=mesh)`` (or the Ulysses variant) to make
every block's attention sequence-parallel with no other model change.
"""

from __future__ import annotations

from typing import Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from pytorch_distributed_mnist_tpu.models.registry import register_model
from pytorch_distributed_mnist_tpu.ops.attention import full_attention


def patchify(x: jnp.ndarray, patch_size: int, compute_dtype) -> jnp.ndarray:
    """(B, 784) / (B, 28, 28) / (B, 28, 28, C) -> (B, T, p*p*C) patches.

    Shared by the sequential ViT below and the pipeline-parallel assembly
    (parallel/pipeline_vit.py) so the two paths cannot drift; the
    forward-parity test in tests/test_pipeline_vit.py pins them equal.
    """
    if x.ndim == 2:
        x = x.reshape((x.shape[0], 28, 28, 1))
    elif x.ndim == 3:
        x = x[..., None]
    x = x.astype(compute_dtype)
    p = patch_size
    b, hh, ww, ch = x.shape
    gh, gw = hh // p, ww // p
    # (B, gh, p, gw, p, C) -> (B, gh*gw, p*p*C): non-overlapping patches.
    x = x.reshape(b, gh, p, gw, p, ch).transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(b, gh * gw, p * p * ch)


class MultiHeadSelfAttention(nn.Module):
    """QKV projection -> pluggable core attention -> output projection."""

    num_heads: int
    attention_fn: Optional[Callable] = None
    compute_dtype: jnp.dtype = jnp.bfloat16
    dot_general: Optional[Callable] = None

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        b, t, c = x.shape
        h = self.num_heads
        assert c % h == 0, f"embed dim {c} not divisible by heads {h}"
        d = c // h
        qkv = nn.Dense(3 * c, dtype=self.compute_dtype,
                       dot_general=self.dot_general, name="qkv")(x)
        qkv = qkv.reshape(b, t, 3, h, d)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        attend = self.attention_fn or full_attention
        o = attend(q, k, v)  # (B, T, H, D)
        o = o.reshape(b, t, c).astype(self.compute_dtype)
        return nn.Dense(c, dtype=self.compute_dtype,
                        dot_general=self.dot_general, name="proj")(o)


class TransformerBlock(nn.Module):
    """Pre-LN block: LN -> MHSA -> residual; LN -> MLP -> residual."""

    num_heads: int
    mlp_ratio: int = 4
    attention_fn: Optional[Callable] = None
    compute_dtype: jnp.dtype = jnp.bfloat16
    dot_general: Optional[Callable] = None

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        c = x.shape[-1]
        y = nn.LayerNorm(dtype=self.compute_dtype, name="ln1")(x)
        x = x + MultiHeadSelfAttention(
            self.num_heads, self.attention_fn, self.compute_dtype,
            dot_general=self.dot_general, name="attn"
        )(y)
        y = nn.LayerNorm(dtype=self.compute_dtype, name="ln2")(x)
        # nn.gelu is a function, with no scope of its own in a profile.
        with jax.named_scope("mlp"):
            y = nn.Dense(self.mlp_ratio * c, dtype=self.compute_dtype,
                         dot_general=self.dot_general, name="mlp1")(y)
            y = nn.gelu(y)
            y = nn.Dense(c, dtype=self.compute_dtype,
                         dot_general=self.dot_general, name="mlp2")(y)
        return x + y


@register_model("vit")
class VisionTransformer(nn.Module):
    """Small ViT: patchify -> embed (+pos) -> blocks -> LN -> mean-pool -> head."""

    num_classes: int = 10
    patch_size: int = 4
    embed_dim: int = 64
    depth: int = 2
    num_heads: int = 4
    mlp_ratio: int = 4
    attention_fn: Optional[Callable] = None
    compute_dtype: jnp.dtype = jnp.bfloat16
    # Matmul implementation for every Dense in the model (None =
    # lax.dot_general); the int8 serving plane injects the MXU-native
    # int8 kernel (ops/pallas/matmul_i8.py) through this field.
    dot_general: Optional[Callable] = None
    # jax.checkpoint around each block: activations inside a block are
    # recomputed during backward instead of stored, the standard TPU
    # HBM-for-FLOPs trade for long sequences (the FLOPs rerun on an MXU
    # that was stalling on HBM anyway). Param structure is unchanged, so
    # checkpoints round-trip between remat and non-remat models.
    remat: bool = False

    @nn.compact
    def __call__(self, x: jnp.ndarray, *, train: bool = False) -> jnp.ndarray:
        del train
        # Accept flat (B, 784), (B, 28, 28), or (B, 28, 28, 1) like the other
        # zoo models, so the same data pipeline feeds all of them.
        x = patchify(x, self.patch_size, self.compute_dtype)
        x = nn.Dense(self.embed_dim, dtype=self.compute_dtype,
                     dot_general=self.dot_general, name="embed")(x)
        pos = self.param(
            "pos_embed",
            nn.initializers.normal(stddev=0.02),
            (1, x.shape[1], self.embed_dim),
        )
        x = x + pos.astype(self.compute_dtype)
        block_cls = nn.remat(TransformerBlock) if self.remat else TransformerBlock
        for i in range(self.depth):
            x = block_cls(
                self.num_heads, self.mlp_ratio, self.attention_fn,
                self.compute_dtype, dot_general=self.dot_general,
                name=f"block{i}",
            )(x)
        x = nn.LayerNorm(dtype=self.compute_dtype, name="ln_f")(x)
        x = jnp.mean(x, axis=1)
        x = nn.Dense(self.num_classes, dtype=self.compute_dtype,
                     dot_general=self.dot_general, name="head")(x)
        return x.astype(jnp.float32)
