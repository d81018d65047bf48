"""Data pipeline: dataset IO, distributed shard sampling, host-sharded loading."""

from pytorch_distributed_mnist_tpu.data.mnist import (
    MNIST_MEAN,
    MNIST_STD,
    load_dataset,
    synthetic_dataset,
    normalize_images,
    parse_idx,
    write_idx,
)
from pytorch_distributed_mnist_tpu.data.sampler import DistributedShardSampler
from pytorch_distributed_mnist_tpu.data.tokens import synthetic_token_corpus
from pytorch_distributed_mnist_tpu.data.loader import MNISTDataLoader, make_global_batch

__all__ = [
    "MNIST_MEAN",
    "MNIST_STD",
    "load_dataset",
    "synthetic_dataset",
    "synthetic_token_corpus",
    "normalize_images",
    "parse_idx",
    "write_idx",
    "DistributedShardSampler",
    "MNISTDataLoader",
    "make_global_batch",
]
