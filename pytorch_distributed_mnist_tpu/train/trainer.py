"""Training engine.

API parity with the reference ``Trainer``
(``/root/reference/multi_proc_single_gpu.py:68-116``): construct with model
state + train/test loaders, then ``train()`` / ``evaluate()`` each run one
pass and return ``(Average, Accuracy)`` meters — same return contract as
``:96-97`` / ``:115-116``.

The execution model is TPU-first rather than a translation:

- the reference's per-batch sequence (H2D copy, forward, loss, backward +
  DDP allreduce, Adam step, two ``.item()`` syncs — ``:83-95``) is one
  donated jitted program per batch;
- ``mode='scan'`` (default when the dataset is device-resident) stages the
  whole epoch and runs it as a single ``lax.scan`` program — zero host
  round-trips per epoch;
- ``mode='explicit'`` uses the shard_map/psum step from
  ``parallel/collectives.py`` — the auditable direct DDP analog;
- metrics accumulate on device (``ops/metrics.py``) and transfer once per
  pass;
- the scan mode's host-side epoch gather is pipelined: epoch N+1's
  permutation copy runs on a background thread while the device executes
  epoch N (jit dispatch is async), and the eval pass — whose sampler never
  reshuffles — stages its device-resident batches exactly once. The
  reference hides the same cost behind DataLoader worker processes
  (``/root/reference/multi_proc_single_gpu.py:156``); here it leaves the
  critical path entirely.
"""

from __future__ import annotations

import sys
import threading
import time
from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from pytorch_distributed_mnist_tpu.data.loader import (
    MNISTDataLoader,
    make_global_batch,
    make_replicated,
)
from pytorch_distributed_mnist_tpu.data.staging import BatchFeeder
from pytorch_distributed_mnist_tpu.ops.metrics import Accuracy, Average, MetricState
from pytorch_distributed_mnist_tpu.parallel.collectives import make_explicit_dp_train_step
from pytorch_distributed_mnist_tpu.train.state import TrainState
from pytorch_distributed_mnist_tpu.train.steps import (
    abstract_spec,
    accumulate_metrics,
    make_eval_epoch,
    make_eval_step,
    make_train_epoch,
    make_train_epoch_indexed,
    make_train_step,
    precompile,
)
from pytorch_distributed_mnist_tpu.utils.profiling import phase, routing_log


def _meters(ms: Optional[MetricState]) -> Tuple[Average, Accuracy]:
    """One device->host sync: fold a MetricState into parity meter objects.

    ``None`` (an empty loader produced zero batches) yields empty meters,
    matching the reference meters' zero-division guard (``:37-39, 55-57``).
    """
    loss, acc = Average(), Accuracy()
    if ms is None:
        return loss, acc
    ms = jax.device_get(ms)  # the sync; the routing counters ride along
    count = int(ms.count)
    if count:
        loss.update(float(ms.loss_sum) / count, count)
        acc.update(int(ms.correct), count)
    if ms.routing is not None:
        routing_log.record(ms.routing)
    return loss, acc


class Trainer:
    """Runs train/eval passes of jitted steps over sharded batches."""

    def __init__(
        self,
        state: TrainState,
        train_loader: MNISTDataLoader,
        test_loader: MNISTDataLoader,
        mesh: Optional[Mesh] = None,
        mode: str = "scan",
        state_sharding=None,
        grad_accum: int = 1,
        epoch_gather: str = "host",
        aux_weight: float = 0.0,
        mtp_weight: float = 0.0,
        bias_rate: float = 0.0,
        feed_window: int = 2,
        staging_log=None,
        zero_overlap: bool = False,
        zero_level: int = 1,
        zero_bucket_mb: float = 4.0,
        zero_bucket_mb_dcn: float = 0.0,
    ) -> None:
        if mode not in ("scan", "stepwise", "explicit"):
            raise ValueError(f"unknown trainer mode {mode!r}")
        if feed_window < 1:
            raise ValueError(f"feed_window must be >= 1, got {feed_window}")
        if epoch_gather not in ("host", "device"):
            raise ValueError(f"unknown epoch_gather {epoch_gather!r}")
        if epoch_gather == "device" and mode != "scan":
            raise ValueError(
                "epoch_gather='device' is a scan-mode path (the gather "
                "lives inside the scanned epoch program)"
            )
        if state_sharding is not None and mesh is None:
            raise ValueError("state_sharding requires a mesh")
        if getattr(state, "buffers", None) is not None and (
                mode == "explicit" or zero_overlap):
            raise ValueError(
                "a state with buffers (a selection bias that the step "
                "moves) trains on the propagation path only: mode "
                "'scan' or 'stepwise', without zero_overlap")
        # What weighs the objective's further terms and moves the bias
        # (train/steps.py _train_step).
        objective = dict(aux_weight=aux_weight, mtp_weight=mtp_weight,
                         bias_rate=bias_rate)
        if zero_overlap:
            # The explicit overlapped-ZeRO data plane
            # (parallel/zero_overlap.py): pure data parallelism with the
            # propagation path's state layout. Host-side composition
            # limits are rejected here (and with flag language in
            # cli.py) rather than discovered as trace errors.
            if mesh is None:
                raise ValueError("zero_overlap requires a mesh")
            if state_sharding is None:
                raise ValueError(
                    "zero_overlap requires the ZeRO state sharding "
                    "(parallel/zero.py shard_state_zero)")
            if mode == "explicit":
                raise ValueError(
                    "zero_overlap does not compose with mode='explicit' "
                    "(both own the mesh as one shard_map data axis)")
            if epoch_gather == "device":
                raise ValueError(
                    "zero_overlap requires epoch_gather='host' (the "
                    "overlapped step is not embedded in the indexed "
                    "device-gather epoch program)")
            if aux_weight:
                raise ValueError(
                    "zero_overlap does not support aux_weight (the sown "
                    "aux statistic is a global-batch quantity; the "
                    "overlapped body sees local shards)")
        self._zero_overlap = zero_overlap
        self._zero_level = zero_level
        self._zero_gather = None
        self._zero_gathered = None
        self.state = state
        self.train_loader = train_loader
        self.test_loader = test_loader
        self.mesh = mesh
        self.mode = mode
        self._state_sharding = state_sharding
        if mode == "explicit":
            if mesh is None:
                raise ValueError("mode='explicit' requires a mesh")
            if state_sharding is not None:
                raise ValueError(
                    "mode='explicit' is the replicated-DP shard_map path; "
                    "use scan/stepwise with a sharded state"
                )
            if grad_accum > 1:
                raise ValueError(
                    "mode='explicit' does not support grad_accum; use "
                    "scan/stepwise"
                )
            if aux_weight:
                raise ValueError(
                    "mode='explicit' does not support aux_weight; use "
                    "scan/stepwise"
                )
            self._train_step = make_explicit_dp_train_step(mesh)
            # Explicit end to end: the eval step must be shard_map too, or
            # eval would silently run the auto-GSPMD path beside the
            # explicit train step (and with the fused pallas loss, gather
            # the batch the shard_map body otherwise keeps local).
            from pytorch_distributed_mnist_tpu.parallel.collectives import (
                make_explicit_dp_eval_step,
            )

            self._eval_step = make_explicit_dp_eval_step(mesh)
        elif zero_overlap:
            from pytorch_distributed_mnist_tpu.parallel.zero_overlap import (
                make_overlap_train_step,
                make_param_gather,
            )

            # Only the programs this mode executes are traced: the scan
            # path never calls the per-batch step. Eval stays on the
            # propagation path — it shares the state layout, and the
            # forward-only program has no weight update to overlap.
            self._train_step = (
                make_overlap_train_step(
                    state, mesh, level=zero_level,
                    bucket_mb=zero_bucket_mb, grad_accum=grad_accum,
                    bucket_mb_dcn=zero_bucket_mb_dcn or None)
                if mode != "scan" else None
            )
            if zero_level == 3:
                self._zero_gather = make_param_gather(mesh)
            self._eval_step = make_eval_step(mesh, state_sharding=state_sharding)
        else:
            self._train_step = make_train_step(
                mesh, state_sharding=state_sharding, grad_accum=grad_accum,
                **objective)
            self._eval_step = make_eval_step(mesh, state_sharding=state_sharding)
        self.epoch_gather = epoch_gather
        if mode == "scan" and epoch_gather == "device":
            self._train_epoch = make_train_epoch_indexed(
                mesh, state_sharding=state_sharding, grad_accum=grad_accum,
                **objective)
        elif mode == "scan" and zero_overlap:
            from pytorch_distributed_mnist_tpu.parallel.zero_overlap import (
                make_overlap_train_epoch,
            )

            self._train_epoch = make_overlap_train_epoch(
                state, mesh, level=zero_level, bucket_mb=zero_bucket_mb,
                grad_accum=grad_accum,
                bucket_mb_dcn=zero_bucket_mb_dcn or None)
        else:
            self._train_epoch = (
                make_train_epoch(mesh, state_sharding=state_sharding,
                                 grad_accum=grad_accum, **objective)
                if mode == "scan" else None
            )
        # Eval always uses the one-time device staging (_eval_staged):
        # the eval sampler never reshuffles, so the sharded staged epoch
        # already has zero per-pass host work — a device-gather eval would
        # only replicate the test set into every device's HBM for nothing.
        self._eval_epoch = (
            make_eval_epoch(mesh, state_sharding=state_sharding)
            if mode == "scan" else None
        )
        self.staging_log = staging_log
        self.feed_window = feed_window
        # Per-batch input plane (stepwise/explicit): the double-buffered
        # feeder stages batch N+1 (host gather + sharded device_put) on a
        # background thread while the jitted step for batch N executes;
        # window 1 is the inline strict-alternation path, bit-for-bit
        # (data/staging.py; pinned by tests/test_staging.py).
        self._feeder = (
            BatchFeeder(train_loader, mesh, window=feed_window,
                        staging_log=staging_log)
            if mode != "scan" else None
        )
        # Per-batch eval staging cache: the eval sampler never
        # reshuffles, so the staged global batches are identical every
        # pass — gather + device_put them exactly once (the per-batch
        # twin of the scan path's _eval_staged).
        self._eval_staged_batches = None
        # Device-resident train dataset for the device-gather path
        # (uploaded lazily, once per run).
        self._train_data = None
        # Epoch-gather pipelining (scan mode): (epoch, thread, holder) of a
        # background stacked_epoch() for the NEXT epoch, plus the one-time
        # device-resident eval stage. prefetch_enabled exists for the
        # equivalence test that pins prefetched == synchronous trajectories.
        self._prefetch = None
        self.prefetch_enabled = True
        self._eval_staged = None
        # AOT precompile state: program name -> Compiled executable, the
        # threads (by program name) still building them, and any
        # per-program failures (surfaced once at that program's join; the
        # lazy jit path stays the fallback).
        self._precompiled = {}
        self._precompile_threads = {}
        self._precompile_errors = {}
        self._precompile_started = False

    @property
    def state(self):
        return self._state

    @state.setter
    def state(self, value) -> None:
        # Installing a state from outside (resume, per-epoch LR update,
        # tests) invalidates the ZeRO-3 gathered-param carry: the carry
        # is DERIVED state (gathered == allgather(state.params), always)
        # and a stale copy would silently run every forward pass on old
        # weights while the optimizer updates the new shards. The train
        # loops re-derive it lazily (one allgather, off the per-step
        # path) and assign ``_state`` directly when installing a step's
        # own output next to its matching carry.
        self._state = value
        self._zero_gathered = None

    def _start_prefetch(self) -> None:
        """Stage the NEXT epoch's gather while the device runs this one.

        Runs after the epoch program is dispatched (dispatch is async, so
        the chips are already crunching). The gather is the PURE form
        (``stacked_epoch(epoch)``) — the thread never mutates the shared
        sampler, so a concurrent ``set_sample_epoch`` from the caller
        cannot race it. ``train()`` validates the staged epoch against
        the sampler's epoch at consumption time, so a caller that jumps
        epochs (resume) just invalidates the stage — correctness never
        depends on the prediction being right.

        Single-process worlds carry the H2D transfer too: the one big
        ``make_global_batch`` (sharded ``device_put`` of the whole
        stacked epoch) used to run synchronously at the epoch boundary
        even though the host-side stacking was prefetched; now the whole
        stage overlaps the previous epoch's compute and eval. Multi-host
        assembly stays on the main thread — no cross-host-visible array
        work off it (supervision's no-concurrent-collectives rule).
        """
        epoch = self.train_loader.sampler.epoch + 1
        holder = {}

        def work():
            t0 = time.perf_counter()
            with phase("trainer:stack_epoch"):
                staged = self.train_loader.stacked_epoch(epoch)
            t1 = time.perf_counter()
            holder["batches"] = staged
            # Timings only; the staging log is written at CONSUMPTION
            # (train() below), so a prefetch that is discarded — epoch
            # jump, or the run's final fire-and-forget stage — never
            # skews the input-plane story with an epoch nobody used.
            holder["host_ms"] = (t1 - t0) * 1e3
            if jax.process_count() == 1:
                with phase("trainer:h2d"):
                    holder["device_batches"] = make_global_batch(
                        staged, self.mesh, leading_replicated=True)
                holder["h2d_ms"] = (time.perf_counter() - t1) * 1e3

        t = threading.Thread(target=work, daemon=True,
                             name="epoch-prefetch")
        t.start()
        self._prefetch = (epoch, t, holder)

    def close(self) -> None:
        """Join and discard any in-flight input-plane thread
        (idempotent): the scan prefetch AND the per-batch feeder.

        The last ``train()`` of a run launches a prefetch nobody will
        consume — and since the stage now carries the full-epoch H2D
        transfer, letting that daemon thread race process teardown means
        a ``device_put`` against a shutting-down runtime and a
        full-epoch device copy held through post-training eval. The
        per-batch feeder has the same hazard when an exception abandons
        ``train()`` mid-epoch: the traceback keeps the generator (and
        its ``finally``) alive until GC, so the feeder must be joined
        explicitly. Callers that finish training (cli.run) close the
        trainer; the staged arrays drop with the holder."""
        if self._prefetch is not None:
            _epoch, t, _holder = self._prefetch
            self._prefetch = None
            t.join()
        if self._feeder is not None:
            self._feeder.close()

    # -- AOT precompile ---------------------------------------------------

    def _precompile_jobs(self):
        """(program name, jitted fn, abstract args) for every program this
        trainer's mode will actually run. Batch specs come from the
        loaders (``data/loader.py batch_spec/epoch_spec/ticks_spec``) so
        they cannot drift from what staging really produces."""
        state_spec = abstract_spec(self.state)
        # Overlapped ZeRO-3 carries the gathered (replicated) params as
        # an explicit argument through the step/epoch boundary.
        carry = ((abstract_spec(self.state.params),)
                 if self._zero_overlap and self._zero_level == 3 else ())
        if self.mode == "scan":
            jobs = [("eval_epoch", self._eval_epoch,
                     (state_spec, self.test_loader.epoch_spec()))]
            if self.epoch_gather == "device":
                data_spec = abstract_spec({
                    "image": self.train_loader.images,
                    "label": self.train_loader.labels,
                })
                jobs.insert(0, (
                    "train_epoch_indexed", self._train_epoch,
                    (state_spec, data_spec, self.train_loader.ticks_spec()),
                ))
            elif self._zero_overlap:
                jobs.insert(0, (
                    "train_epoch_zero_overlap", self._train_epoch,
                    (state_spec,) + carry
                    + (self.train_loader.epoch_spec(),),
                ))
            else:
                jobs.insert(0, ("train_epoch", self._train_epoch,
                                (state_spec, self.train_loader.epoch_spec())))
            return jobs
        if self._zero_overlap:
            return [
                ("train_step_zero_overlap", self._train_step,
                 (state_spec,) + carry + (self.train_loader.batch_spec(),)),
                ("eval_step", self._eval_step,
                 (state_spec, self.test_loader.batch_spec())),
            ]
        suffix = "_explicit" if self.mode == "explicit" else ""
        return [
            ("train_step" + suffix, self._train_step,
             (state_spec, self.train_loader.batch_spec())),
            ("eval_step" + suffix, self._eval_step,
             (state_spec, self.test_loader.batch_spec())),
        ]

    def precompile(self, wait: bool = False) -> None:
        """AOT-compile this trainer's programs on background threads.

        Each program is ``.lower(...).compile()``-d on abstract shapes
        (``train/steps.py precompile``), CONCURRENTLY with whatever the
        caller does next — in ``cli.run`` that is the first epoch's MNIST
        staging/host-gather, so compile leaves the cold-start critical
        path instead of serializing at first use. The compiled
        executables are used directly by ``train()``/``evaluate()`` (no
        re-lowering, no second compile); any failure or signature
        mismatch falls back to the lazy jit path, which is
        trajectory-identical (tests/test_compile_cache.py pins this).

        ``wait=True`` blocks until every program is built — tests and
        callers with nothing to overlap.
        """
        if self._precompile_started:
            return
        self._precompile_started = True
        if self.mesh is not None and self._state_sharding is None \
                and jax.process_count() == 1:
            # Commit the state to the replicated layout the programs are
            # compiled for. Fresh states arrive uncommitted (accepted
            # either way); a resumed state arrives committed to device 0
            # (checkpoint restore) and would otherwise fail the compiled
            # executable's sharding check and recompile lazily. Sharded
            # layouts (TP/ZeRO/PP) are placed by their constructors.
            # Single-process only: a host->multi-host-sharding device_put
            # runs a cross-process value-equality collective (and cannot
            # run at all on the CPU sim); multi-host states stay as they
            # arrive, and a sharding mismatch just takes the lazy path.
            self.state = jax.device_put(
                self.state, NamedSharding(self.mesh, P()))
        for name, fn, specs in self._precompile_jobs():
            def work(name=name, fn=fn, specs=specs):
                try:
                    self._precompiled[name] = precompile(
                        fn, *specs, program=name)
                except Exception as exc:  # noqa: BLE001 - surfaced at join
                    self._precompile_errors[name] = exc

            t = threading.Thread(target=work, daemon=True,
                                 name=f"precompile-{name}")
            t.start()
            self._precompile_threads[name] = t
        if wait:
            self._join_precompile()

    def _join_precompile(self, name: str = None) -> None:
        """Join the thread building ``name`` (all threads when None). Only
        the REQUESTED program blocks the caller: the first train epoch
        must not wait out the eval program's compile — that would
        re-serialize part of the compile time the overlap exists to
        hide; eval's thread keeps compiling during epoch 1 and is joined
        when evaluate() first needs it."""
        names = (list(self._precompile_threads) if name is None
                 else [name] if name in self._precompile_threads else [])
        for n in names:
            self._precompile_threads.pop(n).join()
            exc = self._precompile_errors.pop(n, None)
            if exc is not None:
                print(
                    f"WARNING: precompile of {n} failed; falling back "
                    f"to lazy compilation: {exc!r}",
                    file=sys.stderr, flush=True,
                )

    def _run_program(self, name: str, fn, *args):
        """Run ``name`` via its precompiled executable when one exists and
        matches, else via the lazy jit ``fn`` (identical program)."""
        self._join_precompile(name)
        compiled = self._precompiled.get(name)
        if compiled is not None:
            try:
                return compiled(*args)
            except (TypeError, ValueError) as exc:
                # Shapes/shardings drifted from the precompiled signature
                # (e.g. a mid-run loader swap): drop the stale executable
                # once and let jit recompile for the new signature.
                del self._precompiled[name]
                print(
                    f"WARNING: precompiled {name} no longer matches its "
                    f"arguments; recompiling lazily: {str(exc)[:200]}",
                    file=sys.stderr, flush=True,
                )
        return fn(*args)

    def train(self) -> Tuple[Average, Accuracy]:
        """One training epoch; returns (loss meter, accuracy meter).

        Parity contract: reference ``Trainer.train`` (``:77-97``).
        """
        from pytorch_distributed_mnist_tpu.runtime.supervision import (
            maybe_fault,
        )

        maybe_fault("train_epoch")
        if self.mode == "scan" and self.epoch_gather == "device":
            if self._train_data is None:
                # The dataset crosses the host boundary exactly once.
                self._train_data = make_replicated(
                    {"image": self.train_loader.images,
                     "label": self.train_loader.labels}, self.mesh)
            idx, mask = self.train_loader.epoch_ticks()
            ticks = make_global_batch(
                {"idx": idx.astype(np.int32), "mask": mask}, self.mesh,
                leading_replicated=True)
            with phase("trainer:dispatch"):
                self.state, ms = self._run_program(
                    "train_epoch_indexed", self._train_epoch,
                    self.state, self._train_data, ticks)
        elif self.mode == "scan":
            staged = None
            batches = None
            prefetched_host_ms = None
            with phase("trainer:input_wait"):
                if self._prefetch is not None:
                    epoch, t, holder = self._prefetch
                    self._prefetch = None
                    t_wait = time.perf_counter()
                    t.join()
                    if self.staging_log is not None:
                        self.staging_log.record_wait(
                            (time.perf_counter() - t_wait) * 1e3)
                    if epoch == self.train_loader.sampler.epoch:
                        staged = holder.get("batches")
                        if staged is not None:
                            prefetched_host_ms = holder.get("host_ms")
                        batches = holder.get("device_batches")
                        if batches is not None and self.staging_log is not None:
                            self.staging_log.record_stage(
                                host_ms=holder["host_ms"],
                                h2d_ms=holder["h2d_ms"],
                                images=int(staged["label"].size),
                                pipelined=True)
                if batches is None:
                    # No (valid) prefetched device stage: do whatever is
                    # left on the consumer thread — the whole gather on a
                    # cold first epoch, just the H2D in a multi-host world
                    # where the thread staged host-side only.
                    t0 = time.perf_counter()
                    if staged is None:
                        staged = self.train_loader.stacked_epoch()
                    t1 = time.perf_counter()
                    batches = make_global_batch(
                        staged, self.mesh, leading_replicated=True
                    )
                    if self.staging_log is not None:
                        t2 = time.perf_counter()
                        if prefetched_host_ms is not None:
                            # Multi-host: the gather DID run on the prefetch
                            # thread (its real wall, not the ~0 ms of the
                            # skipped re-gather above); only the H2D was
                            # inline — the wait below carries exactly that
                            # un-overlapped part, so the overlap fraction
                            # credits the hidden host half and nothing else.
                            self.staging_log.record_stage(
                                host_ms=prefetched_host_ms,
                                h2d_ms=(t2 - t1) * 1e3,
                                images=int(staged["label"].size),
                                pipelined=True)
                        else:
                            self.staging_log.record_stage(
                                host_ms=(t1 - t0) * 1e3, h2d_ms=(t2 - t1) * 1e3,
                                images=int(staged["label"].size),
                                pipelined=False)
                        self.staging_log.record_wait((t2 - t0) * 1e3)
            with phase("trainer:dispatch"):
                if self._zero_overlap and self._zero_level == 3:
                    # The carried gathered-param copy: step N's tail
                    # allgather rides the scan carry into step N+1's
                    # forward. Derived state (== allgather(state.params)),
                    # rebuilt whenever absent — first epoch, or any outside
                    # state install (the state setter invalidates it).
                    if self._zero_gathered is None:
                        self._zero_gathered = self._zero_gather(
                            self.state.params)
                    new_state, gathered, ms = self._run_program(
                        "train_epoch_zero_overlap", self._train_epoch,
                        self.state, self._zero_gathered, batches)
                    self._state = new_state  # direct: keep the matching carry
                    self._zero_gathered = gathered
                elif self._zero_overlap:
                    self.state, ms = self._run_program(
                        "train_epoch_zero_overlap", self._train_epoch,
                        self.state, batches)
                else:
                    self.state, ms = self._run_program(
                        "train_epoch", self._train_epoch, self.state, batches)
            if self.prefetch_enabled:
                self._start_prefetch()
        else:
            ms = None
            carried = self._zero_overlap and self._zero_level == 3
            if carried and self._zero_gathered is None:
                self._zero_gathered = self._zero_gather(self.state.params)
            name = ("train_step_explicit" if self.mode == "explicit"
                    else "train_step_zero_overlap" if self._zero_overlap
                    else "train_step")
            for gbatch in self._feeder.epoch():
                # Per-batch chaos hook: a kill here lands BETWEEN device
                # programs, genuinely mid-epoch — the host-loss shape
                # the elastic runtime (runtime/elastic.py) shrinks
                # around. One dict probe when no fault plan is set.
                maybe_fault("train_step")
                if carried:
                    new_state, gathered, m = self._run_program(
                        name, self._train_step,
                        self.state, self._zero_gathered, gbatch)
                    self._state = new_state  # direct: keep matching carry
                    self._zero_gathered = gathered
                else:
                    self.state, m = self._run_program(
                        name, self._train_step, self.state, gbatch)
                ms = m if ms is None else accumulate_metrics(ms, m)
        with phase("trainer:read_metrics"):
            return _meters(ms)

    def evaluate(self) -> Tuple[Average, Accuracy]:
        """One evaluation pass; returns (loss meter, accuracy meter).

        Parity contract: reference ``Trainer.evaluate`` (``:99-116``). No
        gradient, no state update. When the eval loader is sharded the
        metric reduction crosses devices inside the jitted program.
        """
        from pytorch_distributed_mnist_tpu.runtime.supervision import (
            maybe_fault,
        )

        maybe_fault("eval")
        if self.mode == "scan":
            if self._eval_staged is None:
                # The eval sampler never reshuffles, so the stacked epoch
                # — and its device placement — is identical every pass:
                # stage it once, host gather and H2D both leave the
                # per-epoch path.
                self._eval_staged = make_global_batch(
                    self.test_loader.stacked_epoch(), self.mesh,
                    leading_replicated=True
                )
            ms = self._run_program(
                "eval_epoch", self._eval_epoch, self.state, self._eval_staged)
        else:
            ms = None
            name = ("eval_step_explicit" if self.mode == "explicit"
                    else "eval_step")
            if self._eval_staged_batches is None:
                # The eval sampler never reshuffles: every pass gathers
                # and device_puts the IDENTICAL batches, so stage them
                # exactly once (the per-batch twin of _eval_staged;
                # only-once staging pinned by tests/test_staging.py).
                self._eval_staged_batches = [
                    make_global_batch(batch, self.mesh)
                    for batch in self.test_loader
                ]
            for gbatch in self._eval_staged_batches:
                m = self._run_program(
                    name, self._eval_step, self.state, gbatch)
                ms = m if ms is None else accumulate_metrics(ms, m)
        return _meters(ms)
