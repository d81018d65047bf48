"""On-hardware training smoke: the full driver on the real chip.

The hermetic suite proves correctness on virtual CPU devices; this proves
the same driver actually runs on TPU silicon — bf16 convs on the MXU, the
scan-epoch program, checkpoint write — and that the run summary names the
device it ran on (asserted, not inferred from a throughput floor).
"""

import numpy as np

from pytorch_distributed_mnist_tpu.cli import build_parser, run


def test_cnn_trains_on_tpu(tmp_path):
    summary = run(build_parser().parse_args([
        "--dataset", "synthetic", "--model", "cnn", "--epochs", "2",
        "--batch-size", "512", "--synthetic-train-size", "4096",
        "--synthetic-test-size", "1024", "--seed", "1",
        "--checkpoint-dir", str(tmp_path / "ckpt"),
        "--root", str(tmp_path / "data"),
    ]))
    assert summary["epochs_run"] == 2
    # learns: accuracy well above chance by epoch 1
    assert summary["history"][-1]["test_acc"] > 0.5
    # The summary names the device, in jax's own words.
    assert summary["platform"] == "tpu"
    assert summary["device_count"] >= 1 and summary["device_kind"]
    assert (tmp_path / "ckpt" / "model_best.npz").exists()


def test_device_gather_on_tpu(tmp_path):
    """--epoch-gather device on silicon: the dataset stays resident in HBM
    and each scan tick gathers with jnp.take; per-epoch host traffic drops
    to the index matrix. Trajectory must match the host-gather run
    exactly (same programs, same data — tests/test_device_gather.py pins
    this on CPU; here we pin it on the chip)."""
    common = [
        "--dataset", "synthetic", "--model", "cnn", "--epochs", "2",
        "--batch-size", "512", "--synthetic-train-size", "4096",
        "--synthetic-test-size", "1024", "--seed", "1",
        "--root", str(tmp_path / "data"),
    ]
    host = run(build_parser().parse_args(
        common + ["--checkpoint-dir", str(tmp_path / "h")]))
    dev = run(build_parser().parse_args(
        common + ["--checkpoint-dir", str(tmp_path / "d"),
                  "--epoch-gather", "device"]))
    assert dev["history"] == host["history"]
    assert dev["platform"] == host["platform"] == "tpu"


def test_all_first_party_kernels_train_on_tpu(tmp_path):
    """One run exercising every first-party Pallas kernel in the real
    training loop on silicon: fused cross-entropy (--loss fused) and the
    fused Adam update (--optimizer adam_pallas). Numerics: the loss
    trajectory must match the XLA-path run to bf16-training tolerance."""
    common = [
        "--dataset", "synthetic", "--model", "cnn", "--epochs", "1",
        "--batch-size", "512", "--synthetic-train-size", "2048",
        "--synthetic-test-size", "512", "--seed", "1",
        "--root", str(tmp_path / "data"),
    ]
    base = run(build_parser().parse_args(
        common + ["--checkpoint-dir", str(tmp_path / "a")]))
    fused = run(build_parser().parse_args(
        common + ["--checkpoint-dir", str(tmp_path / "b"),
                  "--loss", "fused", "--optimizer", "adam_pallas"]))
    assert fused["epochs_run"] == 1
    # Both kernels really lowered through Mosaic in this process.
    assert fused["platform"] == "tpu"
    assert fused["pallas_lowerings"]["interpret"] == 0
    assert fused["pallas_lowerings"]["mosaic"] > base["pallas_lowerings"]["mosaic"]
    np.testing.assert_allclose(
        fused["history"][0]["train_loss"],
        base["history"][0]["train_loss"], rtol=0.05)
    assert abs(fused["history"][0]["test_acc"]
               - base["history"][0]["test_acc"]) < 0.05
