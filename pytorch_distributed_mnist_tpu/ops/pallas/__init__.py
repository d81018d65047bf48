"""Pallas TPU kernels for the hot ops.

The reference gets its device kernels from cuDNN/cuBLAS through torch ops
(``/root/reference/multi_proc_single_gpu.py:87-92, 216``; SURVEY.md
section 2b "Device kernels"). On TPU, XLA compiles the jitted step — these
hand-written kernels cover the two places a fused kernel beats stock XLA:

- ``fused_adam``: the whole Adam update (moments + bias correction + step)
  as ONE VMEM-resident pass per parameter instead of XLA's chain of
  elementwise HLOs — one read and one write of each buffer, pure
  HBM-bandwidth win on the optimizer, which is the memory-bound part of
  small-model training.
- ``flash_attention``: blockwise online-softmax attention that never
  materializes the (T, T) score matrix in HBM — the long-context hot op;
  same math as ``ops/attention.py``'s blockwise reference, tiled for the
  MXU. ``sharded_flash_attention`` embeds it in GSPMD programs
  (batch x heads shard_map, the ``--tensor-parallel`` composition).
- ``fused_cross_entropy``: single-pass softmax-xent forward (loss + lse
  in VMEM) with a single-pass backward from the saved lse
  (``--loss fused``; ``ops/loss.py`` embeds it in GSPMD via a nested
  shard_map over the data axis).
- ``int8_dot_general``: int8 x int8 -> int32 MXU-native matmul (dynamic
  per-tensor symmetric scales, RNE rounding) behind a ``lax.dot_general``
  drop-in — the int8 serving precision's forward matmul, injected
  through the models' ``dot_general`` field so int8 buys chip clock,
  not just smaller transfers.

Every kernel lowers through Mosaic on a TPU and runs interpreted on the CPU
backend, so the whole suite runs hermetically on the virtual CPU mesh
(tests/conftest.py); any other backend is an error (``backend.py``).
"""

from pytorch_distributed_mnist_tpu.ops.pallas.adam import fused_adam_leaf, pallas_adam
from pytorch_distributed_mnist_tpu.ops.pallas.flash import (
    flash_attention,
    sharded_flash_attention,
)
from pytorch_distributed_mnist_tpu.ops.pallas.matmul_i8 import (
    int8_dot_general,
    matmul_i8,
    quantize_dynamic_i8,
)
from pytorch_distributed_mnist_tpu.ops.pallas.xent import (
    fused_cross_entropy,
    fused_cross_entropy_per_example,
)

__all__ = [
    "fused_adam_leaf",
    "pallas_adam",
    "flash_attention",
    "sharded_flash_attention",
    "fused_cross_entropy",
    "fused_cross_entropy_per_example",
    "int8_dot_general",
    "matmul_i8",
    "quantize_dynamic_i8",
]
