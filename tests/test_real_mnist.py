"""Real-MNIST integration: activates only when the actual IDX files exist.

This environment has zero egress (both documented mirrors fail DNS), so
these tests are skipped here; in any environment where `data/mnist/` holds
the real files (hand-placed or downloaded), they run automatically and pin
the claim the synthetic proxy cannot: the CNN reaches real-MNIST accuracy.

Ref contrast: the reference's default path downloads and trains on the
real dataset (`/root/reference/multi_proc_single_gpu.py:137-138`,
`README.md:42-48`).

Search order for the dataset root: $TPU_MNIST_DATA_ROOT, then the repo's
`data/` (the CLI's --root default).
"""

import os

import numpy as np
import pytest

from pytorch_distributed_mnist_tpu.data.download import dataset_present

_ROOTS = [r for r in (os.environ.get("TPU_MNIST_DATA_ROOT"),
                      os.path.join(os.path.dirname(os.path.dirname(
                          os.path.abspath(__file__))), "data"))
          if r]
_REAL_ROOT = next(
    (r for r in _ROOTS if dataset_present(os.path.join(r, "mnist"))), None)

pytestmark = pytest.mark.skipif(
    _REAL_ROOT is None,
    reason="real MNIST IDX files not present (zero-egress environment)",
)


def test_real_mnist_loads_true_shapes():
    from pytorch_distributed_mnist_tpu.data.mnist import load_dataset

    images, labels = load_dataset(_REAL_ROOT, train=True,
                                  synthesize_if_missing=False)
    assert images.shape == (60000, 28, 28)
    assert labels.shape == (60000,)
    assert set(np.unique(labels)) == set(range(10))


@pytest.mark.slow
def test_cnn_reaches_97pct_on_real_mnist(tmp_path):
    """2 epochs of the CNN on real MNIST must clear 97% test accuracy —
    the integration claim the synthetic glyphs cannot make. (The >=99%
    north star uses the full 20-epoch config; this is the fast gate.)"""
    from pytorch_distributed_mnist_tpu.cli import build_parser, run

    summary = run(build_parser().parse_args([
        "--dataset", "mnist", "--root", _REAL_ROOT,
        "--model", "cnn", "--epochs", "2", "--batch-size", "256",
        "--seed", "0", "--checkpoint-dir", str(tmp_path / "ckpt"),
    ]))
    assert not summary.get("dataset_synthesized")
    assert summary["best_acc"] >= 0.97