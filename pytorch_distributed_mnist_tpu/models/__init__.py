"""Model zoo.

The reference hard-codes a single model (``Net``, a ``Linear(784, 10)``,
``/root/reference/multi_proc_single_gpu.py:119-126``) and constructs it at a
fixed call site (``:185``). Here the model is pluggable via a registry:
``linear`` is the exact reference-parity model, ``cnn`` is the small convnet
required for the >=99% MNIST accuracy target (BASELINE.json north star),
``vit`` and ``moe_mlp`` carry attention and experts, ``laguna`` is the
decoder-only token model family (``models/decoder.py``), ``sambay`` the
hybrid of state-space, differential-attention, gated-memory and shared-KV
cross layers (``models/sambay.py``), ``instella`` the latent-attention
sparse decoder with a selection bias, a multi-token-prediction module and
the FarSkip residual (``models/instella.py``) and ``granite_hybrid`` the
hybrid of Mamba-2 layers in their chunked matrix-product form and
position-free grouped-query attention under the Granite family's four
multipliers (``models/granite.py``).
"""

from pytorch_distributed_mnist_tpu.models.linear import LinearNet
from pytorch_distributed_mnist_tpu.models.cnn import ConvNet
from pytorch_distributed_mnist_tpu.models.attention import VisionTransformer
from pytorch_distributed_mnist_tpu.models.moe import MoEClassifier, SparseExperts, SwitchMoE
from pytorch_distributed_mnist_tpu.models.decoder import Decoder
from pytorch_distributed_mnist_tpu.models.sambay import SambaY
from pytorch_distributed_mnist_tpu.models.instella import Instella
from pytorch_distributed_mnist_tpu.models.granite import GraniteHybrid
from pytorch_distributed_mnist_tpu.models.registry import get_model, register_model, list_models, model_accepts

__all__ = [
    "LinearNet",
    "ConvNet",
    "VisionTransformer",
    "MoEClassifier",
    "SparseExperts",
    "SwitchMoE",
    "Decoder",
    "SambaY",
    "Instella",
    "GraniteHybrid",
    "get_model",
    "register_model",
    "list_models",
    "model_accepts",
]
