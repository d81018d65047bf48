"""Pipelined host->device input staging for the per-batch trainer modes.

The reference hides input latency behind torch DataLoader worker
processes and still pays a per-batch ``.cuda()`` copy on the critical
path (``/root/reference/multi_proc_single_gpu.py:84-85, 156``). The scan
trainer already beat that by staging whole epochs; the per-batch modes
(``stepwise``/``explicit``) kept the reference's shape — every step
blocks on ``make_global_batch`` (host gather + sharded ``device_put``)
before it can dispatch. :class:`BatchFeeder` is the train twin of the
serve plane's pipelined dispatch (``serve/batcher.py`` form/dispatch vs
completion): a feeder thread performs batch N+1's host gather and H2D
transfer while the jitted step for batch N executes, bounded by a
window.

Window semantics (mirroring ``--max-inflight``): ``window`` counts the
batch the consumer holds plus at most ``window - 1`` existing beyond it
(staged or mid-staging — the batch in the feeder's hands counts against
the bound). ``window=1`` disables the feeder thread entirely — staging
runs inline on the consumer thread, today's strict gather->put->step
alternation, bit-for-bit (pinned by test). ``window=2`` is classic
double buffering: one batch consumed while one stages ahead.

Correctness rules, in the house style:

- **Purity.** The feeder thread never mutates the shared sampler: the
  epoch's index matrix is snapshotted via ``loader.epoch_ticks()`` on
  the CONSUMER thread before the feeder starts, so a concurrent
  ``set_sample_epoch`` (resume jump) cannot race it — the next
  ``epoch()`` call simply snapshots the new epoch. Within one epoch
  there is no staleness to rule on.
- **No collectives on the feeder thread.** Supervision's
  no-concurrent-collectives invariant: multi-process assembly
  (``jax.make_array_from_process_local_data``) stays off the feeder, so
  pipelined feeding engages only in single-process worlds
  (``jax.process_count() == 1``); multi-host runs degenerate to the
  inline window-1 path, exactly the behavior they had. Nothing on the
  feeder thread is conditioned on ``process_index()``.
- **Bitwise invariance.** The staged batches are the same NumPy rows
  through the same ``make_global_batch`` in the same order whichever
  thread runs it; pipelining is a latency optimization, never a
  semantics change (the ``prefetch_enabled`` rule, extended).

Every stage records into a :class:`~pytorch_distributed_mnist_tpu.
utils.profiling.StagingLog` when one is attached: host-gather ms, H2D
ms, and how long the consumer actually blocked — the overlap evidence
the cli summary and ``benchmark/layers/input_wait_share.py`` read.
"""

from __future__ import annotations

import collections
import threading
import time
from typing import Iterator, Optional

import jax

from pytorch_distributed_mnist_tpu.data.loader import make_global_batch


class _EpochRun:
    """One epoch's feeder thread + bounded staged-batch conduit.

    The conduit is a deque guarded by one condition variable
    (``BatchFeeder._cv`` idiom, same as the serve batcher's ``_cv``):
    the feeder stages OUTSIDE the lock — gather and ``device_put`` are
    the slow parts, and blocking work under a held lock is exactly what
    the lock-discipline checker forbids — then appends under it;
    the consumer waits under it and pops. ``close()`` unblocks both
    sides so an abandoned epoch (consumer raised mid-step) never leaks
    a thread blocked on a full conduit.
    """

    def __init__(self, feeder: "BatchFeeder", m, mask) -> None:
        self.feeder = feeder
        self._cv = threading.Condition()
        self._staged: collections.deque = collections.deque()
        self._error: Optional[BaseException] = None
        self._done = False
        self._cancelled = False
        self._thread = threading.Thread(
            target=self._feed, args=(m, mask), daemon=True,
            name="input-feeder")
        self._thread.start()

    def _feed(self, m, mask) -> None:
        feeder = self.feeder
        try:
            for row, mrow in zip(m, mask):
                # Wait for conduit room BEFORE staging: the batch being
                # staged counts against the window too, so window W keeps
                # at most W-1 staged batches beyond the one the consumer
                # holds (W=2 = one ahead, classic double buffering) —
                # staging first would silently hold one extra full
                # global batch resident in device memory.
                with self._cv:
                    while (len(self._staged) >= feeder.window - 1
                           and not self._cancelled):
                        self._cv.wait()
                    if self._cancelled:
                        return
                staged = feeder._stage(row, mrow, pipelined=True)
                with self._cv:
                    if self._cancelled:
                        return
                    self._staged.append(staged)
                    self._cv.notify_all()
        except BaseException as exc:  # noqa: BLE001 - re-raised at next()
            with self._cv:
                self._error = exc
                self._cv.notify_all()
        else:
            with self._cv:
                self._done = True
                self._cv.notify_all()

    def next_batch(self):
        """Pop the next staged batch, blocking until the feeder delivers
        one (the blocked time is the un-overlapped staging cost and is
        recorded as such). Raises the feeder's error, or StopIteration
        when the epoch is drained."""
        t0 = time.perf_counter()
        with self._cv:
            while not self._staged and not self._done \
                    and self._error is None and not self._cancelled:
                self._cv.wait()
            wait_ms = (time.perf_counter() - t0) * 1e3
            if self._staged:
                batch = self._staged.popleft()
                self._cv.notify_all()
            elif self._error is not None:
                raise self._error
            else:
                # Done and drained — or cancelled: a close() from
                # ANOTHER thread (teardown hooks) must unblock a
                # consumer parked on the cv, not strand it; cancelled
                # reads as end-of-epoch.
                batch = None
        log = self.feeder.staging_log
        if log is not None:
            log.record_wait(wait_ms)
        if batch is None:
            raise StopIteration
        return batch

    def close(self) -> None:
        """Cancel and join the feeder (idempotent): a consumer that
        abandons the epoch mid-way must not strand a thread blocked on
        the full conduit."""
        with self._cv:
            self._cancelled = True
            self._staged.clear()
            self._cv.notify_all()
        self._thread.join()


class BatchFeeder:
    """Double-buffered host->device staging for one loader.

    ``epoch()`` yields the same device-sharded global batches the
    synchronous ``make_global_batch(batch, mesh)`` loop produced, in the
    same order, for the loader's CURRENT sampler epoch — with the
    staging of batch N+1 overlapped against whatever the caller does
    with batch N (dispatching a jitted step, under JAX async dispatch)
    when ``window > 1``.
    """

    def __init__(self, loader, mesh, window: int = 2,
                 staging_log=None) -> None:
        if window < 1:
            raise ValueError(f"feed window must be >= 1, got {window}")
        self.loader = loader
        self.mesh = mesh
        self.window = int(window)
        self.staging_log = staging_log
        self._active_run: Optional[_EpochRun] = None

    @property
    def pipelined(self) -> bool:
        """Whether epochs will actually run the feeder thread: a window
        of 1 is the inline path by definition, and multi-process worlds
        stay inline so no array assembly (a cross-host-visible
        operation) ever runs off the main thread (supervision's
        no-concurrent-collectives rule)."""
        return self.window > 1 and jax.process_count() == 1

    def _stage(self, row, mrow, pipelined: bool):
        """Gather one batch's rows and assemble the global array,
        recording host vs H2D wall into the staging log."""
        t0 = time.perf_counter()
        batch = self.loader.host_batch(row, mrow)
        t1 = time.perf_counter()
        staged = make_global_batch(batch, self.mesh)
        if self.staging_log is not None:
            t2 = time.perf_counter()
            self.staging_log.record_stage(
                host_ms=(t1 - t0) * 1e3, h2d_ms=(t2 - t1) * 1e3,
                images=len(row), pipelined=pipelined)
        return staged

    def epoch(self) -> Iterator[dict]:
        """Iterate one epoch of staged global batches.

        The index matrix is snapshotted HERE, on the consumer thread,
        before any background work starts — the feeder never reads the
        (mutable) sampler, so epoch jumps between ``epoch()`` calls are
        trivially safe."""
        # A previous epoch abandoned via exception may still be live
        # (the traceback pins its generator — and the finally that
        # would close it — until GC): join it BEFORE starting the next
        # run, or reassigning _active_run below would orphan its feeder
        # thread beyond close()'s reach.
        self.close()
        m, mask = self.loader.epoch_ticks()
        if not self.pipelined or len(m) == 0:
            return self._inline_epoch(m, mask)
        return self._pipelined_epoch(m, mask)

    def _inline_epoch(self, m, mask) -> Iterator[dict]:
        """Window 1 / multi-process: stage on the consumer thread —
        today's strict alternation, bit-for-bit. The whole staging wall
        is un-overlapped by construction, recorded as consumer wait so
        the overlap fraction honestly reads 0."""
        for row, mrow in zip(m, mask):
            t0 = time.perf_counter()
            staged = self._stage(row, mrow, pipelined=False)
            if self.staging_log is not None:
                self.staging_log.record_wait(
                    (time.perf_counter() - t0) * 1e3)
            yield staged

    def close(self) -> None:
        """Cancel and join the in-flight epoch's feeder thread, if any
        (idempotent). A consumer that abandons ``epoch()`` via an
        exception does NOT run the generator's ``finally`` promptly —
        the traceback keeps the frame (and iterator) alive until GC —
        so teardown paths (``Trainer.close``, cli's ``closing``) call
        this to join the feeder before the runtime goes away."""
        run = self._active_run
        if run is not None:
            self._active_run = None
            run.close()

    def _pipelined_epoch(self, m, mask) -> Iterator[dict]:
        run = _EpochRun(self, m, mask)
        self._active_run = run
        try:
            while True:
                try:
                    batch = run.next_batch()
                except StopIteration:
                    return
                yield batch
        finally:
            if self._active_run is run:
                self._active_run = None
            run.close()
