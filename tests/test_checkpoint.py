"""Checkpoint save/restore: schema parity (epoch+1, best_acc), atomicity,
resharding restore, missing-file policy (reference ``:197-214, 249-271``)."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_mnist_tpu.models import get_model
from pytorch_distributed_mnist_tpu.parallel.mesh import replicated_sharding
from pytorch_distributed_mnist_tpu.train.checkpoint import (
    load_checkpoint,
    save_checkpoint,
    try_resume,
)
from pytorch_distributed_mnist_tpu.train.state import create_train_state
from pytorch_distributed_mnist_tpu.train.steps import make_train_step


def fresh_state(seed=0):
    model = get_model("linear", compute_dtype=jnp.float32)
    return create_train_state(model, jax.random.key(seed))


def test_round_trip_bitwise(tmp_path, tiny_data):
    state = fresh_state()
    step = make_train_step()
    images, labels = tiny_data
    batch = {"image": jnp.asarray(images[:32]), "label": jnp.asarray(labels[:32])}
    for _ in range(3):
        state, _ = step(state, batch)
    path = save_checkpoint(state, epoch=2, best_acc=0.5, is_best=True,
                           directory=str(tmp_path), process_index=0)
    assert path and os.path.isfile(path)

    template = fresh_state(seed=1)  # different init; must be fully overwritten
    restored, start_epoch, best_acc = load_checkpoint(path, template)
    assert start_epoch == 3  # saved as epoch+1 (:251), resume at next (:204)
    assert best_acc == 0.5
    assert int(restored.step) == 3
    for a, b in zip(jax.tree.leaves(state.params), jax.tree.leaves(restored.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(state.opt_state), jax.tree.leaves(restored.opt_state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_best_copy_written_only_when_best(tmp_path):
    state = fresh_state()
    save_checkpoint(state, epoch=0, best_acc=0.1, is_best=False,
                    directory=str(tmp_path), process_index=0)
    assert not os.path.exists(tmp_path / "model_best.npz")
    save_checkpoint(state, epoch=1, best_acc=0.2, is_best=True,
                    directory=str(tmp_path), process_index=0)
    assert os.path.exists(tmp_path / "model_best.npz")
    assert os.path.exists(tmp_path / "checkpoint_0.npz")  # per-epoch files kept


def test_nonzero_process_does_not_write(tmp_path):
    state = fresh_state()
    out = save_checkpoint(state, epoch=0, best_acc=0.0, is_best=True,
                          directory=str(tmp_path / "p1"), process_index=1)
    assert out is None
    assert not os.path.exists(tmp_path / "p1")


def test_try_resume_missing_file_continues_fresh(capsys):
    state = fresh_state()
    s2, epoch, best = try_resume("/nonexistent/ckpt.npz", state)
    assert epoch == 0 and best == 0.0 and s2 is state
    assert "no checkpoint found" in capsys.readouterr().out


def test_restore_onto_mesh_resharding(tmp_path, mesh8):
    """Train-on-N -> restore replicated on a mesh (BASELINE configs 3-4)."""
    state = fresh_state()
    path = save_checkpoint(state, epoch=0, best_acc=0.0, is_best=False,
                           directory=str(tmp_path), process_index=0)
    template = fresh_state(seed=1)
    repl = replicated_sharding(mesh8)
    template = template.replace(
        params=jax.device_put(template.params, repl),
        opt_state=jax.device_put(template.opt_state, repl),
    )
    restored, _, _ = load_checkpoint(path, template)
    leaf = jax.tree.leaves(restored.params)[0]
    assert leaf.sharding.is_equivalent_to(repl, leaf.ndim)


def test_shape_mismatch_raises(tmp_path):
    state = fresh_state()
    path = save_checkpoint(state, epoch=0, best_acc=0.0, is_best=False,
                           directory=str(tmp_path), process_index=0)
    model = get_model("cnn")
    cnn_state = create_train_state(model, jax.random.key(0))
    with pytest.raises(ValueError):
        load_checkpoint(path, cnn_state)


# ---------------------------------------------------------------------------
# Sharded directory layout (multi-host TP/EP/ZeRO states; VERDICT item 8)
# ---------------------------------------------------------------------------


def _zero1_state_on(mesh):
    from pytorch_distributed_mnist_tpu.parallel.zero import shard_state_zero1

    state = fresh_state()
    state, _ = shard_state_zero1(state, mesh)
    return state


def test_sharded_round_trip_across_mesh_shapes(tmp_path, mesh8):
    """ZeRO-sharded state -> .ckpt dir -> restore on a DIFFERENT mesh,
    bitwise equal. This is the save path a multi-host non-addressable
    state takes (here forced via layout='sharded' since a single-process
    suite is always fully addressable)."""
    from pytorch_distributed_mnist_tpu.parallel.mesh import make_mesh

    state = _zero1_state_on(mesh8)
    path = save_checkpoint(state, epoch=4, best_acc=0.7, is_best=True,
                           directory=str(tmp_path), process_index=0,
                           layout="sharded")
    assert path.endswith("checkpoint_4.ckpt") and os.path.isdir(path)
    assert os.path.isdir(tmp_path / "model_best.ckpt")
    assert not os.path.exists(path + ".tmp")  # atomically published

    mesh42 = make_mesh(("data", "model"), shape=(4, 2))
    template = _zero1_state_on(mesh42)
    restored, start_epoch, best_acc = load_checkpoint(path, template)
    assert (start_epoch, best_acc) == (5, 0.7)
    for a, b in zip(jax.tree.leaves(state.opt_state),
                    jax.tree.leaves(restored.opt_state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # restored leaves live on the TEMPLATE's (4,2)-mesh shardings
    leaf = jax.tree.leaves(restored.opt_state)[0]
    assert dict(leaf.sharding.mesh.shape) == {"data": 4, "model": 2}


def test_sharded_try_resume_accepts_directory(tmp_path, mesh8):
    state = _zero1_state_on(mesh8)
    path = save_checkpoint(state, epoch=0, best_acc=0.3, is_best=False,
                           directory=str(tmp_path), process_index=0,
                           layout="sharded")
    _, epoch, best = try_resume(path, _zero1_state_on(mesh8))
    assert (epoch, best) == (1, 0.3)


def test_sharded_missing_shard_raises(tmp_path, mesh8):
    state = _zero1_state_on(mesh8)
    path = save_checkpoint(state, epoch=0, best_acc=0.0, is_best=False,
                           directory=str(tmp_path), process_index=0,
                           layout="sharded")
    # simulate a lost per-process shard file
    for name in os.listdir(path):
        if name.startswith("shards_"):
            os.unlink(os.path.join(path, name))
    with pytest.raises(ValueError, match="missing shards"):
        load_checkpoint(path, _zero1_state_on(mesh8))


def test_sharded_and_npz_round_trips_agree(tmp_path, mesh8):
    """The two layouts must restore identical states from the same save."""
    state = _zero1_state_on(mesh8)
    p_npz = save_checkpoint(state, epoch=0, best_acc=0.0, is_best=False,
                            directory=str(tmp_path / "a"), process_index=0,
                            layout="npz")
    p_dir = save_checkpoint(state, epoch=0, best_acc=0.0, is_best=False,
                            directory=str(tmp_path / "b"), process_index=0,
                            layout="sharded")
    ra, _, _ = load_checkpoint(p_npz, fresh_state(seed=1))
    rb, _, _ = load_checkpoint(p_dir, fresh_state(seed=2))
    for a, b in zip(jax.tree.leaves(ra.params), jax.tree.leaves(rb.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_latest_checkpoint_and_prune(tmp_path):
    from pytorch_distributed_mnist_tpu.train.checkpoint import (
        latest_checkpoint,
        prune_checkpoints,
    )

    assert latest_checkpoint(str(tmp_path / "nope")) is None
    state = fresh_state()
    for e in range(4):
        save_checkpoint(state, epoch=e, best_acc=0.1, is_best=(e == 1),
                        directory=str(tmp_path), process_index=0)
    assert latest_checkpoint(str(tmp_path)).endswith("checkpoint_3.npz")
    # in-flight tmp names are never eligible
    open(tmp_path / "checkpoint_9.npz.tmp", "w").close()
    assert latest_checkpoint(str(tmp_path)).endswith("checkpoint_3.npz")

    # Window semantics (the serve-reload ordering guarantee): keep every
    # epoch in [latest - N, latest] = [1, 3], delete strictly older.
    prune_checkpoints(str(tmp_path), keep_last=2)
    kept = sorted(os.listdir(tmp_path))
    assert {"checkpoint_1.npz", "checkpoint_2.npz",
            "checkpoint_3.npz"} <= set(kept)
    assert "checkpoint_0.npz" not in kept
    assert "model_best.npz" in kept  # never pruned
    # keep_last=0 is the reference's keep-everything default
    prune_checkpoints(str(tmp_path), keep_last=0)
    assert "checkpoint_1.npz" in os.listdir(tmp_path)


def test_save_checkpoint_keep_last_inline(tmp_path):
    state = fresh_state()
    for e in range(3):
        save_checkpoint(state, epoch=e, best_acc=0.1, is_best=False,
                        directory=str(tmp_path), process_index=0,
                        keep_last=1)
    names = sorted(n for n in os.listdir(tmp_path)
                   if n.startswith("checkpoint_"))
    # keep_last=1 keeps the window [latest-1, latest]: the previous
    # latest survives each publish so a serve watcher mid-load on it can
    # never lose the file (train/checkpoint.py ordering guarantee).
    assert names == ["checkpoint_1.npz", "checkpoint_2.npz"]


def test_async_checkpointer_matches_sync(tmp_path, tiny_data):
    from pytorch_distributed_mnist_tpu.train.checkpoint import (
        AsyncCheckpointer,
    )

    state = fresh_state()
    step = make_train_step()
    images, labels = tiny_data
    batch = {"image": jnp.asarray(images[:32]), "label": jnp.asarray(labels[:32])}
    state, _ = step(state, batch)

    sync_path = save_checkpoint(state, epoch=0, best_acc=0.2, is_best=True,
                                directory=str(tmp_path / "sync"),
                                process_index=0)
    with AsyncCheckpointer() as saver:
        saver.save(state, epoch=0, best_acc=0.2, is_best=True,
                   directory=str(tmp_path / "async"), process_index=0)
        async_path = saver.wait()
    assert os.path.basename(async_path) == os.path.basename(sync_path)
    # byte-identical files: the host snapshot is the same state
    ra, ea, ba = load_checkpoint(async_path, fresh_state(seed=1))
    rs, es, bs = load_checkpoint(sync_path, fresh_state(seed=2))
    assert (ea, ba) == (es, bs)
    for a, b in zip(jax.tree.leaves(ra.params), jax.tree.leaves(rs.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert os.path.exists(tmp_path / "async" / "model_best.npz")


def test_async_checkpointer_sharded_deferred_publish(tmp_path, mesh8):
    """Async + sharded layout (round-4): the shard snapshot happens in
    save(), the file writes on the worker thread, and the PUBLISH (the
    collective barrier + atomic rename) at the next main-thread drain.
    The published directory must be bitwise identical to a sync save."""
    from pytorch_distributed_mnist_tpu.train.checkpoint import (
        AsyncCheckpointer,
    )

    state = _zero1_state_on(mesh8)
    sync_path = save_checkpoint(state, epoch=0, best_acc=0.4, is_best=True,
                                directory=str(tmp_path / "sync"),
                                process_index=0, layout="sharded")
    adir = tmp_path / "async"
    with AsyncCheckpointer() as saver:
        saver.save(state, epoch=0, best_acc=0.4, is_best=True,
                   directory=str(adir), process_index=0, layout="sharded")
        # Not published yet: only the tmp dir may exist until the drain.
        assert not os.path.isdir(adir / "checkpoint_0.ckpt")
        # Next save drains epoch 0 (join + publish) before snapshotting.
        saver.save(state, epoch=1, best_acc=0.4, is_best=False,
                   directory=str(adir), process_index=0, layout="sharded")
        assert os.path.isdir(adir / "checkpoint_0.ckpt")
        assert not os.path.isdir(adir / "checkpoint_1.ckpt")
        path1 = saver.wait()  # context exit would drain too; explicit here
    assert path1.endswith("checkpoint_1.ckpt") and os.path.isdir(path1)
    assert not os.path.exists(str(adir / "checkpoint_1.ckpt") + ".tmp")
    assert os.path.isdir(adir / "model_best.ckpt")  # epoch 0 was best

    ra, ea, ba = load_checkpoint(str(adir / "checkpoint_0.ckpt"),
                                 _zero1_state_on(mesh8))
    rs, es, bs = load_checkpoint(sync_path, _zero1_state_on(mesh8))
    assert (ea, ba) == (es, bs) == (1, 0.4)
    for a, b in zip(jax.tree.leaves(ra.opt_state),
                    jax.tree.leaves(rs.opt_state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_async_checkpointer_sharded_publish_on_exit(tmp_path, mesh8):
    """A single save followed by context exit still publishes (the drain
    at __exit__), so the last epoch of a run is never lost."""
    from pytorch_distributed_mnist_tpu.train.checkpoint import (
        AsyncCheckpointer,
    )

    state = _zero1_state_on(mesh8)
    with AsyncCheckpointer() as saver:
        saver.save(state, epoch=2, best_acc=0.1, is_best=False,
                   directory=str(tmp_path), process_index=0,
                   layout="sharded")
    assert os.path.isdir(tmp_path / "checkpoint_2.ckpt")
    _, epoch, best = try_resume(str(tmp_path / "checkpoint_2.ckpt"),
                                _zero1_state_on(mesh8))
    assert (epoch, best) == (3, 0.1)


def test_async_checkpointer_surfaces_write_error(tmp_path):
    from pytorch_distributed_mnist_tpu.train.checkpoint import (
        AsyncCheckpointer,
    )

    state = fresh_state()
    saver = AsyncCheckpointer()
    # an unwritable target (a path component is a FILE, so makedirs raises
    # regardless of uid): the failure must surface at wait(), not be
    # swallowed on the worker thread
    blocked = tmp_path / "blocked"
    blocked.write_text("not a directory")
    saver.save(state, epoch=0, best_acc=0.0, is_best=False,
               directory=str(blocked / "sub"), process_index=0)
    with pytest.raises(OSError):
        saver.wait()


def test_async_sharded_peer_failure_agreed_before_publish_barrier(
        monkeypatch, tmp_path):
    """Round-4 advisor: when one host's writer thread fails, the hosts
    whose writes succeeded must NOT enter the publish barrier (it has no
    timeout — they would hang forever waiting for the raising host).
    The write outcome is allgathered first; all hosts fail together.
    Hermetic twin: process_count/allgather stubbed (via the supervision
    record channel the agreement now rides) to simulate host 1 failing
    while we (host 0) succeeded."""
    import numpy as np

    from pytorch_distributed_mnist_tpu.runtime import supervision as sup
    from pytorch_distributed_mnist_tpu.train import checkpoint as ckpt

    saver = ckpt.AsyncCheckpointer()
    saver._pending_publish = dict(
        tmp=str(tmp_path / "checkpoint_3.ckpt.tmp"),
        final=str(tmp_path / "checkpoint_3.ckpt"),
        directory=str(tmp_path), epoch=3, is_best=False, keep_last=0,
        pid=0)
    monkeypatch.setattr(ckpt.jax, "process_count", lambda: 2)
    monkeypatch.setattr(sup, "process_count", lambda: 2)
    monkeypatch.setattr(sup, "process_index", lambda: 0)

    def fake_allgather(payload):
        peer = np.frombuffer(
            sup._encode_record(sup._ERR, "OSError('peer write failed')"),
            np.uint8)
        return np.stack([np.asarray(payload), peer])

    monkeypatch.setattr(sup, "_raw_allgather", fake_allgather)
    published = []
    monkeypatch.setattr(ckpt, "_sharded_publish",
                        lambda **kw: published.append(kw))
    with pytest.raises(RuntimeError, match=r"failed on host\(s\) \[1\]"):
        saver.wait()
    assert not published
    assert saver._pending_publish is None

    # Local-failure twin: our own write failed — the local error is what
    # surfaces (after the agreement), and the publish never runs.
    saver = ckpt.AsyncCheckpointer()
    saver._pending_publish = dict(
        tmp=str(tmp_path / "checkpoint_4.ckpt.tmp"),
        final=str(tmp_path / "checkpoint_4.ckpt"),
        directory=str(tmp_path), epoch=4, is_best=False, keep_last=0,
        pid=0)
    saver._error = OSError("disk full on this host")
    with pytest.raises(OSError, match="disk full"):
        saver.wait()
    assert not published


def test_async_exit_logs_swallowed_error_and_dropped_publish(
        tmp_path, capsys):
    """Round-4 advisor: the unwinding __exit__ must not silently discard
    a write failure or an unpublished checkpoint — postmortems need to
    see that epoch N's save was lost."""
    from pytorch_distributed_mnist_tpu.train.checkpoint import (
        AsyncCheckpointer,
    )

    blocked = tmp_path / "blocked"
    blocked.write_text("not a directory")
    with pytest.raises(ValueError, match="body exception"):
        with AsyncCheckpointer() as saver:
            saver.save(fresh_state(), epoch=0, best_acc=0.0, is_best=False,
                       directory=str(blocked / "sub"), process_index=0)
            raise ValueError("body exception")
    err = capsys.readouterr().err
    assert "async checkpoint write failed" in err

    with pytest.raises(ValueError, match="body exception"):
        with AsyncCheckpointer() as saver:
            saver.save(fresh_state(), epoch=1, best_acc=0.0, is_best=False,
                       directory=str(tmp_path), process_index=0,
                       layout="sharded")
            raise ValueError("body exception")
    err = capsys.readouterr().err
    assert "unpublished checkpoint" in err
    # The publish barrier was skipped: the directory was never renamed.
    assert not (tmp_path / "checkpoint_1.ckpt").exists()


def test_resume_auto_cli(tmp_path, capsys):
    """--resume auto: fresh when the dir is empty, newest checkpoint after."""
    from pytorch_distributed_mnist_tpu.cli import build_parser, run

    common = [
        "--dataset", "synthetic", "--model", "linear",
        "--batch-size", "64", "--synthetic-train-size", "256",
        "--synthetic-test-size", "128", "--seed", "0",
        "--checkpoint-dir", str(tmp_path), "--resume", "auto",
        "--trainer-mode", "stepwise",
    ]
    run(build_parser().parse_args(common + ["--epochs", "2"]))
    out1 = capsys.readouterr().out
    assert "training fresh" in out1
    first = {n for n in os.listdir(tmp_path) if n.startswith("checkpoint_")}
    assert first == {"checkpoint_0.npz", "checkpoint_1.npz"}

    summary = run(build_parser().parse_args(common + ["--epochs", "3"]))
    out2 = capsys.readouterr().out
    assert "loaded checkpoint" in out2 and "checkpoint_1.npz" in out2
    # resumed at epoch 2: exactly one new epoch ran
    assert summary["epochs_run"] == 1
    assert "checkpoint_2.npz" in os.listdir(tmp_path)


def test_async_and_keep_last_cli(tmp_path):
    from pytorch_distributed_mnist_tpu.cli import build_parser, run

    run(build_parser().parse_args([
        "--dataset", "synthetic", "--model", "linear",
        "--batch-size", "64", "--synthetic-train-size", "256",
        "--synthetic-test-size", "128", "--seed", "0", "--epochs", "3",
        "--checkpoint-dir", str(tmp_path), "--trainer-mode", "stepwise",
        "--async-checkpoint", "--keep-last", "1",
    ]))
    names = sorted(os.listdir(tmp_path))
    # keep_last=1 retains the window [latest-1, latest] (the serve-reload
    # ordering guarantee, train/checkpoint.py).
    assert [n for n in names if n.startswith("checkpoint_")] == [
        "checkpoint_1.npz", "checkpoint_2.npz"]
    assert "model_best.npz" in names
    # the retained file is complete and loadable (async write landed)
    _, epoch, _ = load_checkpoint(str(tmp_path / "checkpoint_2.npz"),
                                  fresh_state())
    assert epoch == 3


# -- corrupt-checkpoint quarantine at resume (run-supervision satellite) ----


def _resume_args(ckpt_dir, resume="auto"):
    import argparse

    return argparse.Namespace(resume=resume, checkpoint_dir=str(ckpt_dir))


def test_corrupt_latest_quarantined_falls_back(tmp_path, capsys):
    """A truncated latest checkpoint is renamed *.corrupt and --resume
    auto continues from the next-older epoch instead of aborting — the
    crash-mid-write postmortem no longer needs a human to move a file."""
    from pytorch_distributed_mnist_tpu.cli import _resume_supervised

    state = fresh_state()
    save_checkpoint(state, epoch=0, best_acc=0.5, is_best=True,
                    directory=str(tmp_path))
    save_checkpoint(state, epoch=1, best_acc=0.6, is_best=True,
                    directory=str(tmp_path))
    # torn write: valid zip prefix, garbage tail
    good = (tmp_path / "checkpoint_1.npz").read_bytes()
    (tmp_path / "checkpoint_1.npz").write_bytes(good[: len(good) // 3])

    new_state, start_epoch, best_acc, path = _resume_supervised(
        _resume_args(tmp_path), state)
    assert start_epoch == 1  # fell back to epoch 0's file (epoch+1 == 1)
    assert best_acc == 0.5
    assert path.endswith("checkpoint_0.npz")
    assert (tmp_path / "checkpoint_1.npz.corrupt").exists()
    assert not (tmp_path / "checkpoint_1.npz").exists()
    assert "quarantined corrupt checkpoint" in capsys.readouterr().out


def test_all_checkpoints_corrupt_trains_fresh(tmp_path):
    from pytorch_distributed_mnist_tpu.cli import _resume_supervised

    state = fresh_state()
    for e in range(2):
        save_checkpoint(state, epoch=e, best_acc=0.1, is_best=False,
                        directory=str(tmp_path))
        (tmp_path / f"checkpoint_{e}.npz").write_bytes(b"not a zip at all")
    _, start_epoch, best_acc, path = _resume_supervised(
        _resume_args(tmp_path), state)
    assert (start_epoch, best_acc, path) == (0, 0.0, "")
    names = sorted(os.listdir(tmp_path))
    assert names == ["checkpoint_0.npz.corrupt", "checkpoint_1.npz.corrupt"]


def test_corrupt_sharded_directory_quarantined(tmp_path):
    """The .ckpt directory layout quarantines too (torn meta.json)."""
    from pytorch_distributed_mnist_tpu.cli import _resume_supervised

    state = fresh_state()
    save_checkpoint(state, epoch=0, best_acc=0.3, is_best=False,
                    directory=str(tmp_path))
    save_checkpoint(state, epoch=1, best_acc=0.4, is_best=False,
                    directory=str(tmp_path), layout="sharded")
    meta = tmp_path / "checkpoint_1.ckpt" / "meta.json"
    meta.write_text(meta.read_text()[:10])  # torn JSON

    _, start_epoch, _, path = _resume_supervised(
        _resume_args(tmp_path), state)
    assert start_epoch == 1 and path.endswith("checkpoint_0.npz")
    assert (tmp_path / "checkpoint_1.ckpt.corrupt").is_dir()


def test_explicit_resume_path_never_quarantined(tmp_path):
    """Quarantine is an auto-mode policy: an explicitly named corrupt
    checkpoint must abort loudly and stay on disk for the postmortem."""
    from pytorch_distributed_mnist_tpu.cli import _resume_supervised

    state = fresh_state()
    save_checkpoint(state, epoch=0, best_acc=0.1, is_best=False,
                    directory=str(tmp_path))
    target = tmp_path / "checkpoint_0.npz"
    target.write_bytes(b"garbage")
    with pytest.raises(Exception):
        _resume_supervised(_resume_args(tmp_path, resume=str(target)),
                           state)
    assert target.exists()  # evidence untouched
    assert not (tmp_path / "checkpoint_0.npz.corrupt").exists()


def test_model_mismatch_is_not_corruption(tmp_path):
    """A checkpoint that loads but does not FIT (leaf-count mismatch —
    the user changed --model) must abort, not be quarantined: renaming a
    good checkpoint would destroy training history."""
    from pytorch_distributed_mnist_tpu.cli import _resume_supervised

    state = fresh_state()
    save_checkpoint(state, epoch=0, best_acc=0.1, is_best=False,
                    directory=str(tmp_path))
    other = create_train_state(get_model("cnn"), jax.random.key(0))
    with pytest.raises(ValueError, match="mismatch"):
        _resume_supervised(_resume_args(tmp_path), other)
    assert (tmp_path / "checkpoint_0.npz").exists()


def test_is_corrupt_checkpoint_error_classification():
    import json as _json
    import zipfile
    import zlib

    from pytorch_distributed_mnist_tpu.train.checkpoint import (
        is_corrupt_checkpoint_error,
    )

    assert is_corrupt_checkpoint_error(zipfile.BadZipFile("x"))
    assert is_corrupt_checkpoint_error(zlib.error("x"))
    assert is_corrupt_checkpoint_error(EOFError())
    assert is_corrupt_checkpoint_error(KeyError("__meta__"))
    assert is_corrupt_checkpoint_error(
        _json.JSONDecodeError("x", "doc", 0))
    # NOT corruption: the caller is wrong, the file is fine.
    assert not is_corrupt_checkpoint_error(
        ValueError("checkpoint has 4 leaves, current state has 8 — "
                   "model/optimizer mismatch"))
    assert not is_corrupt_checkpoint_error(
        ValueError("leaf x shape (3,) != expected (4,)"))
    assert not is_corrupt_checkpoint_error(RuntimeError("unrelated"))
    # NOT corruption: absence-level signals — a published directory was
    # complete at publish time, so a missing member at resume time is
    # far more likely a stale NFS view than damage; quarantining on it
    # would destroy the newest good checkpoint (review finding).
    assert not is_corrupt_checkpoint_error(FileNotFoundError("meta.json"))
    assert not is_corrupt_checkpoint_error(
        ValueError("leaf params is missing shards (3/9 elements present)"))


def test_quarantine_checkpoint_numbered_on_collision(tmp_path):
    from pytorch_distributed_mnist_tpu.train.checkpoint import (
        latest_checkpoint,
        quarantine_checkpoint,
    )

    for _ in range(2):
        p = tmp_path / "checkpoint_3.npz"
        p.write_bytes(b"bad")
        quarantine_checkpoint(str(p))
    names = sorted(os.listdir(tmp_path))
    assert names == ["checkpoint_3.npz.corrupt", "checkpoint_3.npz.corrupt2"]
    # quarantined names are invisible to resolution and pruning
    assert latest_checkpoint(str(tmp_path)) is None


# -- state that no gradient moves (TrainState.buffers) ----------------------

def _instella_state(seed=0):
    model = get_model("instella", compute_dtype=jnp.float32,
                      attention="dense")
    return create_train_state(model, jax.random.key(seed),
                              input_shape=(1, 32))


def _token_batch(seed=0):
    from pytorch_distributed_mnist_tpu.data.tokens import (
        synthetic_token_corpus,
    )

    tokens, labels = synthetic_token_corpus(
        2, 32, 256, seed=seed, median_len=8, min_len=4)
    return {"image": jnp.asarray(tokens), "label": jnp.asarray(labels)}


def _leaves_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("layout", ["npz", "async", "sharded"])
def test_buffers_round_trip_bit_for_bit(tmp_path, layout, mesh8):
    """A checkpoint of a state with a selection bias carries the bias: the
    three layouts restore it, the parameters and the moments bit for bit
    onto a template that was initialised otherwise."""
    from pytorch_distributed_mnist_tpu.train.checkpoint import (
        AsyncCheckpointer,
    )

    state = _instella_state()
    step = make_train_step(aux_weight=1e-4, mtp_weight=0.3, bias_rate=1e-3)
    for _ in range(2):
        state, _ = step(state, _token_batch())
    assert float(max(jnp.max(jnp.abs(b))
                     for b in jax.tree.leaves(state.buffers))) > 0
    kwargs = dict(epoch=1, best_acc=0.25, is_best=False,
                  directory=str(tmp_path), process_index=0)
    if layout == "async":
        with AsyncCheckpointer() as saver:
            saver.save(state, **kwargs)
        path = str(tmp_path / "checkpoint_1.npz")
    elif layout == "sharded":
        state = jax.device_put(state, replicated_sharding(mesh8))
        path = save_checkpoint(state, **kwargs, layout="sharded")
        assert os.path.isdir(path)
    else:
        path = save_checkpoint(state, **kwargs)
    restored, start_epoch, best_acc = load_checkpoint(
        path, _instella_state(seed=1))
    assert (start_epoch, best_acc, int(restored.step)) == (2, 0.25, 2)
    _leaves_equal(state.buffers, restored.buffers)
    _leaves_equal(state.params, restored.params)
    _leaves_equal(state.opt_state, restored.opt_state)


def test_a_resumed_step_equals_the_uninterrupted_one(tmp_path):
    """Save after two steps, restore onto a fresh template, take the third:
    parameters, moments and bias are the uninterrupted run's, bit for bit
    (the bias chose the third step's experts)."""
    step = make_train_step(aux_weight=1e-4, mtp_weight=0.3, bias_rate=1e-3)
    state = _instella_state()
    for i in range(2):
        state, _ = step(state, _token_batch(i))
    path = save_checkpoint(state, epoch=0, best_acc=0.0, is_best=False,
                           directory=str(tmp_path), process_index=0)
    straight, straight_metrics = step(state, _token_batch(2))
    restored, _, _ = load_checkpoint(path, _instella_state(seed=7))
    resumed, resumed_metrics = step(restored, _token_batch(2))
    _leaves_equal(straight.buffers, resumed.buffers)
    _leaves_equal(straight.params, resumed.params)
    _leaves_equal(straight.opt_state, resumed.opt_state)
    np.testing.assert_array_equal(straight_metrics.routing,
                                  resumed_metrics.routing)


def test_a_state_without_buffers_writes_what_it_always_wrote(tmp_path):
    """``buffers`` is a key of the checkpoint's tree only where the state
    has them: a state without writes the leaves, names and order it wrote
    before the field existed, and a checkpoint of one kind does not load
    onto the other."""
    import json

    from pytorch_distributed_mnist_tpu.train.checkpoint import (
        _leaves_with_names,
        _state_tree,
    )

    state = fresh_state()
    assert state.buffers is None
    assert set(_state_tree(state)) == {"params", "opt_state", "step"}
    path = save_checkpoint(state, epoch=0, best_acc=0.0, is_best=False,
                           directory=str(tmp_path), process_index=0)
    with np.load(path) as z:
        meta = json.loads(bytes(z["__meta__"]).decode())
        assert len(z.files) == len(meta["leaf_names"]) + 1
    assert meta["leaf_names"] == [name for name, _ in _leaves_with_names(
        {"params": state.params, "opt_state": state.opt_state,
         "step": state.step})]
    assert not [n for n in meta["leaf_names"] if "buffers" in n]
    with_bias = _instella_state()
    names = [n for n, _ in _leaves_with_names(_state_tree(with_bias))]
    assert len([n for n in names if n.startswith("['buffers']")]) == 3
    with pytest.raises(ValueError, match="leaves"):
        load_checkpoint(path, with_bias)
