"""Pipelined per-batch input staging (data/staging.py): the feeder is a
latency optimization, never a semantics change. Window 1 must reproduce
today's synchronous gather->put->step alternation bit-for-bit (the
``prefetch_enabled`` rule, extended to the per-batch modes), the conduit
must respect its window bound, and abandoning an epoch must never leak
a blocked feeder thread."""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_mnist_tpu.data import staging as staging_mod
from pytorch_distributed_mnist_tpu.data.loader import (
    MNISTDataLoader,
    make_global_batch,
)
from pytorch_distributed_mnist_tpu.data.staging import BatchFeeder, _EpochRun
from pytorch_distributed_mnist_tpu.models import get_model
from pytorch_distributed_mnist_tpu.parallel.mesh import make_mesh
from pytorch_distributed_mnist_tpu.train.state import create_train_state
from pytorch_distributed_mnist_tpu.train.trainer import Trainer
from pytorch_distributed_mnist_tpu.utils.profiling import StagingLog


def _setup(seed=0, n=128, bs=32):
    rng = np.random.default_rng(seed)
    images = rng.normal(size=(n, 28, 28, 1)).astype(np.float32)
    labels = (np.arange(n) % 10).astype(np.int32)
    model = get_model("linear", compute_dtype=jnp.float32)
    state = create_train_state(model, jax.random.key(0))
    train = MNISTDataLoader(images, labels, batch_size=bs, train=True, seed=7)
    test = MNISTDataLoader(images, labels, batch_size=bs, train=False, seed=7)
    return state, train, test


def _run_epochs(mode, window, epochs=3):
    state, train, test = _setup()
    trainer = Trainer(state, train, test, mesh=make_mesh(("data",)),
                      mode=mode, feed_window=window)
    history = []
    for epoch in range(epochs):
        train.set_sample_epoch(epoch)
        loss, acc = trainer.train()
        tloss, tacc = trainer.evaluate()
        history.append((loss.average, acc.accuracy,
                        tloss.average, tacc.accuracy))
    return trainer.state, history


# -- the acceptance pin ------------------------------------------------------


@pytest.mark.parametrize("mode", ["stepwise", "explicit"])
def test_pipelined_trajectory_bitwise_equals_synchronous(mode):
    """Window 2 (feeder thread) vs window 1 (inline, today's strict
    alternation): identical metrics AND bitwise-identical params."""
    s_pipe, h_pipe = _run_epochs(mode, window=2)
    s_sync, h_sync = _run_epochs(mode, window=1)
    assert h_pipe == h_sync  # exact float equality: same programs, same data
    for a, b in zip(jax.tree.leaves(s_pipe.params),
                    jax.tree.leaves(s_sync.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_deep_window_trajectory_bitwise_equals_synchronous():
    """A deeper conduit changes overlap, not order: window 4 matches
    window 1 bitwise too."""
    s_deep, h_deep = _run_epochs("stepwise", window=4, epochs=2)
    s_sync, h_sync = _run_epochs("stepwise", window=1, epochs=2)
    assert h_deep == h_sync
    for a, b in zip(jax.tree.leaves(s_deep.params),
                    jax.tree.leaves(s_sync.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -- feeder semantics --------------------------------------------------------


def test_feeder_yields_same_batches_in_order():
    """The staged global batches are the synchronous loop's batches —
    same values, same order."""
    _, train, _ = _setup()
    mesh = make_mesh(("data",))
    train.set_sample_epoch(1)
    want = [make_global_batch(b, mesh) for b in train]
    feeder = BatchFeeder(train, mesh, window=2)
    got = list(feeder.epoch())
    assert len(got) == len(want)
    for g, w in zip(got, want):
        for key in ("image", "label", "mask"):
            np.testing.assert_array_equal(np.asarray(g[key]),
                                          np.asarray(w[key]))


def test_window_validation_and_pipelined_property():
    _, train, _ = _setup()
    mesh = make_mesh(("data",))
    with pytest.raises(ValueError):
        BatchFeeder(train, mesh, window=0)
    assert not BatchFeeder(train, mesh, window=1).pipelined
    assert BatchFeeder(train, mesh, window=2).pipelined


def test_multi_process_world_degrades_to_inline(monkeypatch):
    """No array assembly off the main thread in multi-process worlds
    (supervision's no-concurrent-collectives rule): the feeder reports
    itself inline regardless of window."""
    _, train, _ = _setup()
    mesh = make_mesh(("data",))
    feeder = BatchFeeder(train, mesh, window=4)
    monkeypatch.setattr(staging_mod.jax, "process_count", lambda: 2)
    assert not feeder.pipelined
    # And the epoch still delivers every batch, inline.
    train.set_sample_epoch(0)
    assert len(list(feeder.epoch())) == len(train)


class _StubFeeder:
    """Drives _EpochRun directly: stages are the row values themselves."""

    def __init__(self, window, stage_error_at=None):
        self.window = window
        self.staging_log = None
        self.stage_error_at = stage_error_at
        self.stage_calls = 0

    def _stage(self, row, mrow, pipelined):
        self.stage_calls += 1
        if self.stage_error_at is not None and row == self.stage_error_at:
            raise RuntimeError(f"boom at {row}")
        return row


def test_conduit_respects_window_bound():
    """The feeder keeps at most window-1 staged batches beyond the one
    the consumer holds — counting the batch it is staging in-hand, not
    just the conduit entries: with a stalled consumer, _stage runs
    exactly window-1 times (a stage-then-wait loop would silently hold
    one extra full global batch resident in device memory)."""
    feeder = _StubFeeder(window=3)
    run = _EpochRun(feeder, list(range(8)), list(range(8)))
    try:
        time.sleep(0.2)  # give the feeder every chance to overfill
        with run._cv:
            assert len(run._staged) <= feeder.window - 1
        assert feeder.stage_calls == feeder.window - 1
        got = [run.next_batch() for _ in range(8)]
        assert got == list(range(8))
        with pytest.raises(StopIteration):
            run.next_batch()
    finally:
        run.close()


def test_feeder_error_reraised_at_consumer():
    """A staging failure (bad row, OOM, device error) surfaces on the
    consumer thread as the original exception, after the batches staged
    before it were consumed."""
    feeder = _StubFeeder(window=2, stage_error_at=2)
    run = _EpochRun(feeder, list(range(5)), list(range(5)))
    try:
        assert run.next_batch() == 0
        assert run.next_batch() == 1
        with pytest.raises(RuntimeError, match="boom at 2"):
            run.next_batch()
    finally:
        run.close()


def test_cross_thread_close_unblocks_parked_consumer():
    """close() from ANOTHER thread (teardown hooks) must unblock a
    consumer parked in next_batch's cv.wait — a cancelled run reads as
    end-of-epoch (StopIteration), never a permanent wait: cancellation
    sets neither _done nor _error, so the wait predicate must also
    check _cancelled."""
    gate = threading.Event()

    class _SlowFeeder(_StubFeeder):
        def _stage(self, row, mrow, pipelined):
            gate.wait(5)
            return super()._stage(row, mrow, pipelined)

    feeder = _SlowFeeder(window=2)
    run = _EpochRun(feeder, [0], [0])
    out = {}

    def consume():
        try:
            out["batch"] = run.next_batch()
        except StopIteration:
            out["stopped"] = True

    consumer = threading.Thread(target=consume)
    consumer.start()
    time.sleep(0.1)  # consumer parked on the cv (nothing staged yet)
    closer = threading.Thread(target=run.close)
    closer.start()
    # The consumer must unblock on the cancel itself — promptly, while
    # the feeder is still stuck staging (the gate is not set yet).
    consumer.join(2)
    assert not consumer.is_alive()
    assert out.get("stopped") is True
    gate.set()
    closer.join(5)
    assert not closer.is_alive()


def test_abandoned_epoch_joins_feeder_thread():
    """A consumer that abandons the epoch mid-way (raise in the step)
    must not strand the feeder blocked on a full conduit."""
    feeder = _StubFeeder(window=2)
    run = _EpochRun(feeder, list(range(64)), list(range(64)))
    assert run.next_batch() == 0
    run.close()
    assert not run._thread.is_alive()
    run.close()  # idempotent


def test_generator_close_joins_feeder_thread():
    """The BatchFeeder.epoch() generator path: dropping the iterator
    triggers the finally that cancels and joins the feeder."""
    _, train, _ = _setup()
    mesh = make_mesh(("data",))
    feeder = BatchFeeder(train, mesh, window=2)
    before = {t.ident for t in threading.enumerate()}
    it = feeder.epoch()
    next(it)
    it.close()  # abandon mid-epoch
    time.sleep(0.05)
    leaked = [t for t in threading.enumerate()
              if t.ident not in before and t.name == "input-feeder"]
    assert leaked == []


def test_feeder_close_joins_abandoned_epoch_without_gc():
    """An exception out of the step loop does NOT finalize the epoch()
    generator promptly (the traceback keeps the frame alive), so
    teardown must be able to join the feeder WITHOUT dropping the
    iterator: BatchFeeder.close() — reached via Trainer.close() and
    cli's closing(trainer) — joins the in-flight run directly."""
    _, train, _ = _setup()
    mesh = make_mesh(("data",))
    feeder = BatchFeeder(train, mesh, window=2)
    it = feeder.epoch()
    next(it)
    run = feeder._active_run
    assert run is not None and run._thread.is_alive()
    feeder.close()  # iterator still referenced — no GC finalization
    assert not run._thread.is_alive()
    assert feeder._active_run is None
    feeder.close()  # idempotent
    del it


def test_reentrant_epoch_joins_previous_abandoned_run():
    """Starting a new epoch while a previous abandoned run is still
    live (its generator pinned by an exception traceback) must join the
    old feeder BEFORE reassigning _active_run — reassignment would
    orphan the thread beyond close()'s reach."""
    _, train, _ = _setup()
    feeder = BatchFeeder(train, make_mesh(("data",)), window=2)
    it1 = feeder.epoch()
    next(it1)
    old = feeder._active_run
    assert old is not None and old._thread.is_alive()
    it2 = feeder.epoch()  # re-entrant: previous epoch abandoned, un-GC'd
    assert not old._thread.is_alive()
    next(it2)
    assert feeder._active_run is not old
    # The abandoned iterator, if ever resumed, drains cleanly (its run
    # is cancelled -> end-of-epoch), never crashes or blocks.
    with pytest.raises(StopIteration):
        next(it1)
    feeder.close()
    del it1, it2


def test_trainer_close_joins_per_batch_feeder():
    """Trainer.close() must reach the per-batch feeder, not just the
    scan prefetch: abandon a stepwise epoch via a raising step and
    assert the input-feeder thread is joined by close()."""
    state, train, test = _setup()
    trainer = Trainer(state, train, test, mesh=make_mesh(("data",)),
                      mode="stepwise", feed_window=2)
    it = trainer._feeder.epoch()
    next(it)  # feeder thread live, mid-epoch
    run = trainer._feeder._active_run
    assert run is not None
    trainer.close()
    assert not run._thread.is_alive()
    del it


def test_epoch_snapshot_tracks_sampler_jump():
    """epoch() snapshots the CURRENT sampler epoch on the consumer
    thread: a resume-style jump between epochs feeds the jumped-to
    epoch's permutation, not a stale one."""
    _, train, _ = _setup()
    mesh = make_mesh(("data",))
    feeder = BatchFeeder(train, mesh, window=2)
    train.set_sample_epoch(5)
    want = [np.asarray(make_global_batch(b, mesh)["label"]) for b in train]
    got = [np.asarray(b["label"]) for b in feeder.epoch()]
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


# -- staging log -------------------------------------------------------------


def test_staging_log_inline_overlap_is_zero():
    """The inline path records its own wall as consumer wait, so the
    overlap fraction honestly reads 0."""
    log = StagingLog()
    _, train, _ = _setup()
    mesh = make_mesh(("data",))
    feeder = BatchFeeder(train, mesh, window=1, staging_log=log)
    list(feeder.epoch())
    s = log.summary()
    assert s["stages"] == len(train) and s["pipelined_stages"] == 0
    assert s["overlap_fraction"] == 0.0
    assert s["images"] == len(train) * train.local_batch_size


def test_staging_log_pipelined_records_feeder_stages():
    log = StagingLog()
    _, train, _ = _setup()
    mesh = make_mesh(("data",))
    feeder = BatchFeeder(train, mesh, window=2, staging_log=log)
    list(feeder.epoch())
    s = log.summary()
    assert s["stages"] == len(train)
    assert s["pipelined_stages"] == len(train)


# -- per-batch eval staging cache (satellite) --------------------------------


@pytest.mark.parametrize("mode", ["stepwise", "explicit"])
def test_eval_staging_cached_once_and_metrics_identical(mode, monkeypatch):
    """Trainer.evaluate in the per-batch modes stages the (never
    reshuffled) eval batches exactly once; repeat evaluations reuse the
    staged arrays and report identical metrics."""
    state, train, test = _setup()
    trainer = Trainer(state, train, test, mesh=make_mesh(("data",)),
                      mode=mode)
    calls = {"n": 0}
    import pytorch_distributed_mnist_tpu.train.trainer as trainer_mod
    real = trainer_mod.make_global_batch

    def counting(*args, **kwargs):
        calls["n"] += 1
        return real(*args, **kwargs)

    monkeypatch.setattr(trainer_mod, "make_global_batch", counting)
    l1, a1 = trainer.evaluate()
    staged = calls["n"]
    assert staged == len(test)  # one stage per eval batch
    cached = trainer._eval_staged_batches
    assert cached is not None
    l2, a2 = trainer.evaluate()
    assert calls["n"] == staged  # only-once staging
    assert trainer._eval_staged_batches is cached
    assert (l1.average, a1.accuracy) == (l2.average, a2.accuracy)


def test_eval_cache_matches_fresh_gather_metrics():
    """The cached staging cannot drift from a fresh per-pass gather."""
    state, train, test = _setup()
    mesh = make_mesh(("data",))
    trainer = Trainer(state, train, test, mesh=mesh, mode="stepwise")
    l_cached, a_cached = trainer.evaluate()

    state2, train2, test2 = _setup()
    t2 = Trainer(state2, train2, test2, mesh=mesh, mode="stepwise")
    t2._eval_staged_batches = [make_global_batch(b, mesh)
                               for b in test2]  # fresh gather, same data
    l_fresh, a_fresh = t2.evaluate()
    assert (l_cached.average, a_cached.accuracy) == \
        (l_fresh.average, a_fresh.accuracy)
