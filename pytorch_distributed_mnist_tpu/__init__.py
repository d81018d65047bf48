"""tpu-mnist: a TPU-native (JAX/XLA/pjit) distributed training framework.

Re-implements, TPU-first, every capability of the reference
``flybirdtian/pytorch_distributed_mnist`` (``multi_proc_single_gpu.py``):

- data-parallel training over a ``jax.sharding.Mesh`` (DDP's NCCL allreduce
  becomes an XLA AllReduce / ``lax.psum`` over the mesh's ``data`` axis),
- ``DistributedSampler``-style disjoint per-host sharding with per-epoch
  reshuffle,
- step-decay LR schedule, per-epoch checkpointing with best-model tracking,
  ``--resume`` and ``--evaluate``,
- a CLI with flag parity,

plus the tests, profiling, and benchmarks the reference lacks. The compute
path is JAX/XLA (jit + sharding + Pallas); the host-side data path can be
backed by the optional native C++ loader under ``native/`` when built.
"""

__version__ = "0.1.0"

from pytorch_distributed_mnist_tpu.train.state import TrainState, create_train_state
from pytorch_distributed_mnist_tpu.train.trainer import Trainer
from pytorch_distributed_mnist_tpu.models import get_model
from pytorch_distributed_mnist_tpu.parallel.mesh import make_mesh

__all__ = [
    "TrainState",
    "create_train_state",
    "Trainer",
    "get_model",
    "make_mesh",
    "__version__",
]
