"""End-to-end integration via the CLI driver (SURVEY.md section 4):
overfit synthetic data, checkpoint -> resume continuity, --evaluate from
checkpoint reproducing best_acc (BASELINE configs 1, 3, 4)."""

import os

import pytest

from pytorch_distributed_mnist_tpu.cli import build_parser, run


def make_args(tmp_path, **overrides):
    argv = [
        "--dataset", "synthetic",
        "--synthetic-train-size", "512",
        "--synthetic-test-size", "256",
        "--batch-size", "128",
        "--epochs", "2",
        "--model", "linear",
        "--lr", "0.01",
        "--seed", "0",
        "--checkpoint-dir", str(tmp_path / "ckpt"),
        "--root", str(tmp_path / "data"),
    ]
    for k, v in overrides.items():
        flag = "--" + k.replace("_", "-")
        if v is True:
            argv.append(flag)
        else:
            argv += [flag, str(v)]
    return build_parser().parse_args(argv)


def test_train_and_improve(tmp_path):
    out = run(make_args(tmp_path, epochs=3))
    assert out["epochs_run"] == 3
    assert out["best_acc"] > 0.5  # synthetic digits are easy; must beat chance 0.1
    losses = [h["train_loss"] for h in out["history"]]
    assert losses[-1] < losses[0]
    assert os.path.isfile(tmp_path / "ckpt" / "checkpoint_2.npz")
    assert os.path.isfile(tmp_path / "ckpt" / "model_best.npz")


def test_resume_continues_at_next_epoch(tmp_path):
    run(make_args(tmp_path, epochs=2))
    out = run(make_args(tmp_path, epochs=4,
                        resume=str(tmp_path / "ckpt" / "checkpoint_1.npz")))
    epochs = [h["epoch"] for h in out["history"]]
    assert epochs == [2, 3]  # resumed at saved epoch+1 (:204, :251)


def test_evaluate_short_circuit_reproduces_best_acc(tmp_path):
    trained = run(make_args(tmp_path, epochs=2))
    out = run(make_args(tmp_path, evaluate=True,
                        resume=str(tmp_path / "ckpt" / "model_best.npz")))
    assert out["epochs_run"] == 0
    assert abs(out["test_acc"] - trained["best_acc"]) < 1e-6


@pytest.mark.parametrize("mode", ["stepwise", "explicit"])
def test_trainer_modes_run(tmp_path, mode):
    out = run(make_args(tmp_path, epochs=1, trainer_mode=mode))
    assert out["epochs_run"] == 1


@pytest.mark.slow
def test_cnn_overfits_synthetic(tmp_path):
    out = run(make_args(tmp_path, model="cnn", epochs=8, batch_size=64, lr=1e-3,
                        synthetic_train_size=256, synthetic_test_size=128))
    assert out["best_acc"] > 0.6  # CNN learns noised glyph digits in 32 steps


def test_fashion_mnist_dataset_flag(tmp_path):
    # No real FashionMNIST on disk -> --allow-synthetic opts into the
    # labelled fallback (BASELINE config 5's dataset swap-in is a flag,
    # not a code edit).
    out = run(make_args(tmp_path, dataset="fashion_mnist", epochs=1,
                        allow_synthetic=True))
    assert out["epochs_run"] == 1
    assert out["dataset_synthesized"]


def test_workers_noop_note_when_native_absent(tmp_path, capsys, monkeypatch):
    """The reference's --workers feeds real DataLoader processes (:156);
    when our native backend isn't built the flag must SAY it's a no-op
    at startup, not silently swallow it (round-3 VERDICT missing #3)."""
    from pytorch_distributed_mnist_tpu.data import native

    monkeypatch.setattr(native, "available", lambda: False)
    run(make_args(tmp_path, epochs=1))
    assert "-j/--workers 4 is a no-op" in capsys.readouterr().out


def test_missing_dataset_fails_fast(tmp_path):
    # The reference ALWAYS downloads a missing dataset (:137-138); a
    # missing dataset here without --download/--allow-synthetic must be
    # a hard error, never a silent synthetic run with fake accuracy.
    with pytest.raises(SystemExit, match="allow-synthetic"):
        run(make_args(tmp_path, dataset="fashion_mnist", epochs=1))


def test_multihost_presence_decision_is_agreed_without_download(
        tmp_path, monkeypatch):
    """Round-4 advisor (medium): the dataset-presence decision must be
    agreed across hosts in EVERY multi-host path, not only under
    --download — otherwise a host missing the IDX files either falls back
    to synthetic alone (silent cross-host data divergence) or raises
    SystemExit alone while its peers hang at the next collective.
    Hermetic twin: process_count/allgather stubbed (on the supervision
    record channel the agreement now rides) to simulate a 2-host job
    where the peer host lacks the files."""
    import numpy as np

    from pytorch_distributed_mnist_tpu import cli
    from pytorch_distributed_mnist_tpu.runtime import supervision as sup

    monkeypatch.setattr(cli, "process_count", lambda: 2)
    monkeypatch.setattr(sup, "process_count", lambda: 2)
    monkeypatch.setattr(sup, "process_index", lambda: 0)
    calls = []

    def fake_allgather(x):
        calls.append(np.asarray(x))
        peer = np.frombuffer(
            sup._encode_record(sup._ERR, "files missing on host 1"),
            np.uint8)
        return np.stack([np.asarray(x), peer])

    monkeypatch.setattr(sup, "_raw_allgather", fake_allgather)

    from pytorch_distributed_mnist_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(("data",))

    # Without --allow-synthetic: every host raises the same fail-fast —
    # and the agreement allgather really ran (no --download given).
    with pytest.raises(SystemExit, match="not present on every host"):
        cli._build_loaders(
            make_args(tmp_path, dataset="fashion_mnist"), seed=0, mesh=mesh)
    assert calls, "presence agreement must run even without --download"

    # With --allow-synthetic: all hosts take the synthetic fallback
    # together instead of deciding per host inside load_split.
    _, _, used_synth = cli._build_loaders(
        make_args(tmp_path, dataset="fashion_mnist", allow_synthetic=True),
        seed=0, mesh=mesh)
    assert used_synth


def test_synthetic_tag_on_epoch_lines_and_metrics(tmp_path, capsys):
    mf = tmp_path / "metrics.jsonl"
    out = run(make_args(tmp_path, dataset="fashion_mnist", epochs=1,
                        allow_synthetic=True, metrics_file=str(mf)))
    assert out["dataset_synthesized"]
    printed = capsys.readouterr().out
    epoch_lines = [l for l in printed.splitlines() if l.startswith("Epoch:")]
    assert epoch_lines and all(
        "dataset: synthetic" in l for l in epoch_lines)
    import json

    rows = [json.loads(l) for l in mf.read_text().splitlines()]
    epoch_rows = [r for r in rows if "epoch" in r]
    assert epoch_rows and all(
        r["dataset"] == "synthetic" for r in epoch_rows)


def test_explicit_synthetic_needs_no_flag_and_is_tagged(tmp_path, capsys):
    out = run(make_args(tmp_path, epochs=1))  # --dataset synthetic
    assert out["dataset_synthesized"]
    printed = capsys.readouterr().out
    epoch_lines = [l for l in printed.splitlines() if l.startswith("Epoch:")]
    assert epoch_lines and all(
        "dataset: synthetic" in l for l in epoch_lines)


def test_debug_nans_flag(tmp_path):
    """--debug-nans wires jax_debug_nans: a healthy run still passes, and a
    poisoned loss raises FloatingPointError at the producing op (SURVEY.md
    section 5's NaN-debug subsystem)."""
    import jax
    import jax.numpy as jnp
    import pytest

    from pytorch_distributed_mnist_tpu.cli import build_parser, run

    args = build_parser().parse_args([
        "--dataset", "synthetic", "--model", "linear", "--epochs", "1",
        "--batch-size", "64", "--synthetic-train-size", "128",
        "--synthetic-test-size", "64", "--debug-nans",
        "--checkpoint-dir", str(tmp_path / "ckpt"),
        "--root", str(tmp_path / "data"),
    ])
    try:
        summary = run(args)
        assert jnp.isfinite(summary["history"][0]["train_loss"])
        # the flag is active process-wide: a NaN-producing jitted op raises
        with pytest.raises(FloatingPointError):
            jax.jit(lambda x: jnp.log(x))(jnp.zeros(4) - 1.0).block_until_ready()
    finally:
        jax.config.update("jax_debug_nans", False)


def test_metrics_file(tmp_path):
    """--metrics-file appends one JSON line per epoch (SURVEY section 5),
    then the run_summary row: the machine-readable account of what the run
    ran on (jax's own platform/device_kind/count), which host input path
    fed it, and how its Pallas calls were lowered."""
    import json

    import jax

    from pytorch_distributed_mnist_tpu.cli import build_parser, run

    mf = tmp_path / "metrics.jsonl"
    common = [
        "--dataset", "synthetic", "--model", "linear",
        "--batch-size", "64", "--synthetic-train-size", "128",
        "--synthetic-test-size", "64", "--seed", "0",
        "--metrics-file", str(mf),
        "--checkpoint-dir", str(tmp_path / "ckpt"),
        "--root", str(tmp_path / "data"),
    ]
    returned = run(build_parser().parse_args(common + ["--epochs", "2"]))
    lines = [json.loads(l) for l in mf.read_text().splitlines()]
    assert len(lines) == 3
    assert lines[0]["epoch"] == 0 and lines[1]["epoch"] == 1
    for row in lines[:2]:
        for key in ("train_loss", "test_acc", "lr", "best_acc",
                    "images_per_sec"):
            assert key in row
    summary = lines[2]
    assert summary["kind"] == "run_summary" and summary["epochs_run"] == 2
    device = jax.devices()[0]
    assert (summary["platform"], summary["device_kind"],
            summary["device_count"]) \
        == (device.platform, device.device_kind, jax.device_count())
    assert summary["input_backend"] in ("native", "numpy")
    assert set(summary["pallas_lowerings"]) == {"mosaic", "interpret"}
    assert set(summary["flash_schedules"]) == {
        "sites", "folded_sites", "folded_evaluated_over_needed",
        "backward_sites", "fused_backward_sites", "kept_results"}
    assert set(summary["dense_attention_slices"]) == {
        "sites", "sliced_sites", "slices_per_sliced_site"}
    assert set(summary["rotary_sites"]) == {
        "sites", "whole_head_sites", "rotated_lanes"}
    assert set(summary["head_gate_sites"]) == {"sites", "head_widths"}
    assert "train_epoch" in summary["compile_stats"]["programs"]
    assert "history" not in summary  # the epoch rows above already say it
    for key in ("platform", "device_kind", "device_count"):
        assert returned[key] == summary[key]

    # -e --resume writes its own summary row (no epoch row).
    run(build_parser().parse_args(common + [
        "-e", "--resume", str(tmp_path / "ckpt" / "checkpoint_1.npz")]))
    evaluated = json.loads(mf.read_text().splitlines()[-1])
    assert evaluated["kind"] == "run_summary"
    assert evaluated["epochs_run"] == 0 and evaluated["start_epoch"] == 2
    assert evaluated["platform"] == device.platform
    assert "evaluate" in evaluated["compile_stats"]["programs"]


def test_compile_cache_populated(tmp_path):
    """--compile-cache DIR: the persistent XLA cache receives entries, and
    a second identical run still trains correctly while reading from it."""
    from pytorch_distributed_mnist_tpu.cli import build_parser, run

    cache = tmp_path / "xla_cache"
    common = [
        "--dataset", "synthetic", "--model", "linear",
        "--batch-size", "64", "--synthetic-train-size", "128",
        "--synthetic-test-size", "64", "--seed", "0", "--epochs", "1",
        "--trainer-mode", "stepwise", "--compile-cache", str(cache),
    ]
    s1 = run(build_parser().parse_args(
        common + ["--checkpoint-dir", str(tmp_path / "a")]))
    assert cache.is_dir() and len(list(cache.iterdir())) > 0
    s2 = run(build_parser().parse_args(
        common + ["--checkpoint-dir", str(tmp_path / "b")]))
    assert s2["history"][0]["train_loss"] == s1["history"][0]["train_loss"]


def test_profile_dir_writes_trace(tmp_path):
    """--profile-dir: a jax.profiler trace capture lands on disk, with the
    per-phase annotations active inside it (smoke: capture dir non-empty)."""
    from pytorch_distributed_mnist_tpu.cli import build_parser, run

    trace = tmp_path / "trace"
    run(build_parser().parse_args([
        "--dataset", "synthetic", "--model", "linear",
        "--batch-size", "64", "--synthetic-train-size", "128",
        "--synthetic-test-size", "64", "--seed", "0", "--epochs", "1",
        "--trainer-mode", "stepwise", "--profile-dir", str(trace),
        "--checkpoint-dir", str(tmp_path / "ck"),
    ]))
    assert trace.is_dir()
    files = [p for p in trace.rglob("*") if p.is_file()]
    assert files, "profiler trace directory is empty"
