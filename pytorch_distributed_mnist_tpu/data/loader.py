"""Host-sharded batch loading.

Capability parity with ``MNISTDataLoader``
(``/root/reference/multi_proc_single_gpu.py:129-161``), redesigned for the
TPU input path:

- the reference's per-process ``DataLoader`` + ``DistributedSampler`` +
  per-batch ``.cuda()`` H2D copies (``:84-85``) become: a per-*host* loader
  that yields this host's shard of each global batch as NumPy, plus
  ``make_global_batch`` which assembles the device-sharded ``jax.Array``
  (``device_put`` with a NamedSharding on one host;
  ``jax.make_array_from_process_local_data`` across hosts);
- ``set_sample_epoch(epoch)`` keeps its name and semantics (``:159-161``);
- the sampler-only-for-train policy (``:143-144``) is *configurable* here:
  the reference replicates eval on every rank (SURVEY.md section 3.3); the
  TPU default shards eval too, but ``shard_eval=False`` reproduces the
  reference behavior exactly;
- ``stacked_epoch()`` pre-stages a whole epoch as (steps, batch, ...) arrays
  for the ``lax.scan`` fast path — no per-batch host work at all.

Batch-size semantics: ``batch_size`` here is the **global** batch; each host
yields ``batch_size / num_hosts`` rows, and the array is further sharded
across that host's devices by the mesh. This makes the reference's
"``--batch-size`` is per-node total, divided among workers" rule (``:174``,
``:297-300``) explicit and host-count-invariant.
"""

from __future__ import annotations

from typing import Dict, Iterator, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from pytorch_distributed_mnist_tpu.data.sampler import DistributedShardSampler


class MNISTDataLoader:
    """Iterates (image, label) batches over this process's shard."""

    def __init__(
        self,
        images: np.ndarray,  # float32 (N, 28, 28, 1), already normalized;
        # or token sequences (N, T) int32 (data/tokens.py)
        labels: np.ndarray,  # int (N,), or (N, T) next-token labels
        batch_size: int,
        train: bool = True,
        num_replicas: int = 1,
        rank: int = 0,
        seed: int = 0,
        shard: Optional[bool] = None,
        drop_last: Optional[bool] = None,
        workers: int = 4,
    ) -> None:
        if batch_size % num_replicas != 0:
            raise ValueError(
                f"global batch_size {batch_size} not divisible by "
                f"{num_replicas} processes"
            )
        self.images = images
        self.labels = np.asarray(labels, np.int32)
        self.workers = workers
        self.global_batch_size = batch_size
        self.local_batch_size = batch_size // num_replicas
        self.train = train
        # Parity default: shard train, replicate eval (reference :143-144);
        # pass shard=True on the eval loader for the faster sharded eval.
        shard = train if shard is None else shard
        # Train drops the ragged last batch so every step has a static shape
        # (XLA recompiles per shape); eval pads instead so all samples count.
        self.drop_last = train if drop_last is None else drop_last
        self.sampler = DistributedShardSampler(
            dataset_len=images.shape[0],
            num_replicas=num_replicas if shard else 1,
            rank=rank if shard else 0,
            shuffle=train,
            seed=seed,
        )

    def set_sample_epoch(self, epoch: int) -> None:
        """Reference-parity name (``:159-161``): reseed this epoch's shuffle."""
        self.sampler.set_epoch(epoch)

    @property
    def steps_per_epoch(self) -> int:
        n = len(self.sampler)
        return n // self.local_batch_size if self.drop_last else -(-n // self.local_batch_size)

    def epoch_ticks(self, epoch: Optional[int] = None):
        """(steps, local_batch) int index matrix + 0/1 validity mask —
        the public index-space form of an epoch, consumed by the
        device-gather path (``train/steps.py make_train_epoch_indexed``)
        the way ``stacked_epoch`` serves the host-gather path.

        Padding (wrapping from the front) keeps shapes static for XLA; the
        mask marks padded positions so metrics never double-count them.
        ``epoch`` selects a specific epoch's shuffle without mutating the
        sampler (see ``DistributedShardSampler.indices_and_mask``).
        """
        idx, valid = self.sampler.indices_and_mask(epoch)
        steps = self.steps_per_epoch
        need = steps * self.local_batch_size
        mask = np.ones(need, np.float32)
        mask[: min(idx.size, need)] = valid[:need]
        if need > idx.size:
            mask[idx.size :] = 0.0
            idx = np.concatenate([idx, idx[: need - idx.size]])
        shape = (steps, self.local_batch_size)
        return idx[:need].reshape(shape), mask.reshape(shape)

    def host_batch(self, row: np.ndarray, mrow: np.ndarray) -> Dict[str, np.ndarray]:
        """One batch's host-side rows for an ``epoch_ticks`` row — THE
        gather both ``__iter__`` and the pipelined feeder
        (``data/staging.py``) run, so the two paths cannot drift."""
        return {"image": self.images[row], "label": self.labels[row], "mask": mrow}

    def __iter__(self) -> Iterator[Dict[str, np.ndarray]]:
        m, mask = self.epoch_ticks()
        for row, mrow in zip(m, mask):
            yield self.host_batch(row, mrow)

    def __len__(self) -> int:
        return self.steps_per_epoch

    def batch_spec(self) -> Dict[str, jax.ShapeDtypeStruct]:
        """Abstract (shape, dtype) form of one assembled GLOBAL batch —
        what ``make_global_batch`` yields for one ``__iter__`` item. The
        AOT precompile path (``train/steps.py precompile``) lowers against
        this, so it lives HERE next to the code whose output it mirrors:
        a loader change that altered batch layout would break the spec in
        the same file."""
        b = self.global_batch_size
        return {
            "image": jax.ShapeDtypeStruct((b,) + self.images.shape[1:],
                                          self.images.dtype),
            "label": jax.ShapeDtypeStruct((b,) + self.labels.shape[1:],
                                          self.labels.dtype),
            "mask": jax.ShapeDtypeStruct((b,), np.float32),
        }

    def epoch_spec(self) -> Dict[str, jax.ShapeDtypeStruct]:
        """Abstract form of a whole staged GLOBAL epoch — ``stacked_epoch``
        assembled by ``make_global_batch(..., leading_replicated=True)``:
        every ``batch_spec`` leaf gains the leading steps axis."""
        s = self.steps_per_epoch
        return {
            k: jax.ShapeDtypeStruct((s,) + v.shape, v.dtype)
            for k, v in self.batch_spec().items()
        }

    def ticks_spec(self) -> Dict[str, jax.ShapeDtypeStruct]:
        """Abstract form of a GLOBAL ``epoch_ticks`` index matrix + mask —
        the device-gather path's per-epoch upload."""
        shape = (self.steps_per_epoch, self.global_batch_size)
        return {
            "idx": jax.ShapeDtypeStruct(shape, np.int32),
            "mask": jax.ShapeDtypeStruct(shape, np.float32),
        }

    def stacked_epoch(self, epoch: Optional[int] = None) -> Dict[str, np.ndarray]:
        """Whole epoch as {'image': (S, B, ...), 'label': (S, B), 'mask': (S, B)}
        for lax.scan.

        The gather is the host-side hot path (one full-dataset permutation
        copy per epoch); it runs in multithreaded C++ when the native
        backend is built (``-j/--workers`` controls the thread count).
        ``epoch`` gathers a specific epoch's shuffle purely (no sampler
        mutation) — the trainer's background prefetch path.
        """
        from pytorch_distributed_mnist_tpu.data import native

        m, mask = self.epoch_ticks(epoch)
        if self.images.dtype == np.float32 and native.available():
            got = native.gather_epoch(self.images, self.labels, m, self.workers)
            if got is not None:
                images, labels = got
                return {"image": images, "label": labels, "mask": mask}
        return {
            "image": self.images[m.reshape(-1)].reshape(m.shape + self.images.shape[1:]),
            "label": self.labels[m.reshape(-1)].reshape(
                m.shape + self.labels.shape[1:]),
            "mask": mask,
        }


def make_replicated(data: Dict[str, np.ndarray], mesh: Optional[Mesh]):
    """Place host arrays on device fully replicated (every device, every
    host, the whole array) — the layout the device-gather epoch path uses
    for the resident dataset (train/steps.py make_train_epoch_indexed)."""
    return make_global_batch(data, mesh, spec=P())


def make_global_batch(
    batch: Dict[str, np.ndarray],
    mesh: Optional[Mesh],
    axis: str = "data",
    leading_replicated: bool = False,
    spec: Optional[P] = None,
) -> Dict[str, jax.Array]:
    """Assemble this host's local batch into a (possibly) global jax.Array.

    Single host: a ``device_put`` with NamedSharding splits the batch across
    local devices. Multi-host: ``jax.make_array_from_process_local_data``
    builds the global array from per-host shards — the TPU analog of each
    DDP rank holding its own sampler shard (``:143-144``).

    ``leading_replicated=True`` is for stacked epochs (steps axis first):
    shards dim 1 instead of dim 0. ``spec`` overrides the PartitionSpec
    entirely (``P()`` = fully replicated, every host passing the full
    array — ``make_replicated``).
    """
    if mesh is None:
        return {k: jax.device_put(v) for k, v in batch.items()}
    if spec is None:
        from pytorch_distributed_mnist_tpu.parallel.mesh import (
            resolve_data_axis,
        )

        # Hierarchical meshes shard rows over the composed ('dcn',
        # 'ici') pair — same rows per composed coordinate either way.
        axis = resolve_data_axis(mesh, axis)
        spec = P(None, axis) if leading_replicated else P(axis)
    sharding = NamedSharding(mesh, spec)
    if jax.process_count() == 1:
        return {k: jax.device_put(v, sharding) for k, v in batch.items()}
    return {
        k: jax.make_array_from_process_local_data(sharding, v) for k, v in batch.items()
    }
