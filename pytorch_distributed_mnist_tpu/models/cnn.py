"""Small convnet for the >=99% MNIST target.

The reference's model is a bare ``Linear(784, 10)``
(``/root/reference/multi_proc_single_gpu.py:119-126``) which tops out around
92-93% MNIST test accuracy; BASELINE.json's north star (>=99% in <60s on TPU)
requires a conv model, so the zoo carries this 2-conv CNN in addition to the
parity ``linear`` model (SURVEY.md section 0).

TPU notes: NHWC layout (XLA:TPU's native conv layout), bfloat16 compute so
convs and the dense layers hit the MXU, float32 params/logits. Channel widths
are multiples of 8 to line up with VPU/MXU tiling.
"""

from __future__ import annotations

from typing import Callable, Optional

import flax.linen as nn
import jax.numpy as jnp

from pytorch_distributed_mnist_tpu.models.registry import register_model


@register_model("cnn")
class ConvNet(nn.Module):
    """conv3x3(32) -> conv3x3(64) -> maxpool2 -> dense(128) -> dense(10)."""

    num_classes: int = 10
    compute_dtype: jnp.dtype = jnp.bfloat16
    # Matmul implementation for the Dense layers (None = lax.dot_general).
    # The int8 serving plane injects the MXU-native int8 kernel here
    # (ops/pallas/matmul_i8.py); model_accepts("cnn", "dot_general")
    # gates the wiring.
    dot_general: Optional[Callable] = None

    @nn.compact
    def __call__(self, x: jnp.ndarray, *, train: bool = False) -> jnp.ndarray:
        del train
        # Accept flat (B, 784) or image (B, 28, 28) / (B, 28, 28, 1) input so
        # the CNN is a drop-in for the linear model on the same pipeline.
        if x.ndim == 2:
            x = x.reshape((x.shape[0], 28, 28, 1))
        elif x.ndim == 3:
            x = x[..., None]
        x = x.astype(self.compute_dtype)
        x = nn.Conv(32, (3, 3), dtype=self.compute_dtype, name="conv1")(x)
        x = nn.relu(x)
        x = nn.Conv(64, (3, 3), dtype=self.compute_dtype, name="conv2")(x)
        x = nn.relu(x)
        x = nn.max_pool(x, (2, 2), strides=(2, 2))
        x = x.reshape((x.shape[0], -1))
        x = nn.Dense(128, dtype=self.compute_dtype,
                     dot_general=self.dot_general, name="fc1")(x)
        x = nn.relu(x)
        x = nn.Dense(self.num_classes, dtype=self.compute_dtype,
                     dot_general=self.dot_general, name="fc2")(x)
        return x.astype(jnp.float32)
