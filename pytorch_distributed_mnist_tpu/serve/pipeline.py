"""MPMD pipeline serving: independent per-stage programs, streamed
micro-batches.

The SPMD serving planes (``serve/programs.py``) lower ONE program over
the whole mesh — which is exactly why the pipeline layout could not
serve: a pipeline-trained checkpoint's params are stage-stacked, and a
single spanning program would hold every stage's weights everywhere,
forfeiting the one thing pipeline parallelism buys (params bigger than
one chip's HBM). Following the MPMD pipeline-parallelism direction in
PAPERS.md — and in contrast to the one-program-over-the-mesh pjit
approach — this module compiles each stage as an INDEPENDENT program on
its own chip:

- **Stage split.** ``parallel/pipeline_vit.py::split_stage_params`` cuts
  the checkpoint's ``{embed, blocks, head}`` tree at the SAME block
  boundaries training's stage axis used; stage 0 carries the patch
  embedding, the last stage the head. Each stage's params commit to that
  stage's chip only — no chip ever holds another stage's weights.
- **Per-stage AOT programs.** One compiled forward per batch bucket PER
  STAGE (``CompileLog`` names ``serve_forward_b{b}@pipeline.s{k}``;
  ``@pipeline.g{i}.s{k}`` on multi-chain pools), built through the same
  ``precompile`` path as every other serve program — zero steady-state
  recompiles per bucket x stage, params an ARGUMENT of every program so
  hot-reload stays swap-only.
- **Streaming.** ``dispatch_logits`` stages the batch onto stage 0's
  chip and enqueues the whole chain — stage k's program, then an async
  device-to-device hop of the activation to stage k+1 — and returns
  without waiting (JAX async dispatch: every device runs its own
  execution stream). With the batcher's in-flight window >= stages, the
  chain fills like a GPipe schedule: stage k runs batch N while stage
  k+1 runs batch N-1, and steady-state throughput approaches the
  SLOWEST stage's clock rather than the sum of stages. Window 1
  degenerates to strict fill-and-drain (every batch pays the full chain
  latency serially).

Hot-reload swaps are COORDINATED across stages: ``swap_params`` splits
and places every stage's slice off-lock, then installs the whole
per-stage list under one lock together with the epoch; dispatch captures
the full list under the same lock once per batch — so one batch can
never run stage 0 on epoch E and stage 1 on epoch E+1 (the no-mixed-
epoch guarantee, now per-chain instead of per-device).

The engine surface (``warmup`` / ``swap_params`` / ``dispatch_logits``
/ ``complete`` / ``preprocess`` / ``buckets`` / ``params_epoch``)
mirrors :class:`~pytorch_distributed_mnist_tpu.serve.engine.
InferenceEngine`, so ``EnginePool`` treats a pipeline CHAIN as one
replica spanning its stage chips: least-loaded dispatch across chains,
quarantine/regroup of the WHOLE chain (a pipeline with a dead stage can
serve nothing — the pool's group machinery is already chain-shaped),
and the reload fan-out all work unchanged. Registered as serve mode
``pipeline`` via ``register_serve_mode``, which is what routes the boot
gate, the divisibility walk and ``/stats`` through it without
special-casing.
"""

from __future__ import annotations

import contextlib
import threading
from typing import List, Optional, Sequence, Tuple

import jax
import numpy as np

from pytorch_distributed_mnist_tpu.parallel.pipeline_vit import (
    make_stage_forward_fns,
    split_stage_params,
    split_vit_params,
)
from pytorch_distributed_mnist_tpu.serve.engine import (
    DEFAULT_BUCKETS,
    StagingPool,
    _InFlightBatch,
    _quiet_donation,
    as_raw_images,
    bucket_for,
    preprocess_images,
    stage_batch,
)
from pytorch_distributed_mnist_tpu.train.steps import abstract_spec, precompile

__all__ = ["PipelineEngine", "make_pipeline_template",
           "pipeline_engine_factory"]


class _StageProgram:
    """One pipeline stage: its forward jitted for its own chip, one AOT
    executable per batch bucket. Holds no params — the engine owns the
    per-stage params list so the cross-stage swap stays atomic."""

    __slots__ = ("index", "device", "sharding", "name", "forward", "fused",
                 "_jit", "_compiled")

    def __init__(self, index: int, forward, device, name: str,
                 fused: bool = False) -> None:
        self.index = index
        self.device = device
        self.name = name  # e.g. "pipeline.s0" / "pipeline.g1.s0"
        self.forward = forward
        self.fused = fused
        self.sharding = jax.sharding.SingleDeviceSharding(device)
        jit_kwargs = dict(in_shardings=self.sharding,
                          out_shardings=self.sharding)
        if fused:
            # The fused stage-0 program consumes the raw uint8 staging
            # buffer and DONATES it — the chain's only H2D transfer is
            # the raw bytes, and XLA owns them afterwards.
            jit_kwargs["donate_argnums"] = (1,)
        self._jit = jax.jit(forward, **jit_kwargs)
        self._compiled = {}  # bucket -> Compiled executable

    def program_name(self, bucket: int) -> str:
        tag = ".fused" if self.fused else ""
        return f"serve_forward_b{bucket}{tag}@{self.name}"

    def warmup(self, params_spec, in_specs: dict) -> dict:
        """AOT-compile every bucket's program (idempotent; measured
        under ``program_name`` so the zero-recompile verdict stays
        attributable per bucket x stage). Returns the bucket -> output
        spec map — the next stage's input specs, chained by the engine
        so no stage ever guesses an activation shape."""
        out_specs = {}
        for bucket, spec in in_specs.items():
            if bucket not in self._compiled:
                quiet = (_quiet_donation() if self.fused
                         else contextlib.nullcontext())
                with quiet:
                    self._compiled[bucket] = precompile(
                        self._jit, params_spec, spec,
                        program=self.program_name(bucket))
            out_specs[bucket] = jax.eval_shape(self.forward, params_spec,
                                               spec)
        return out_specs

    def run(self, params, x):
        """Enqueue this stage's program on its chip (async dispatch).
        ``x`` must already be committed to this stage's device."""
        compiled = self._compiled.get(x.shape[0])
        if compiled is not None:
            return compiled(params, x)
        # Lazy fallback (warmup skipped or failed): same program via
        # jit — correctness preserved; the no-recompile guarantee is
        # what warmup buys.
        quiet = _quiet_donation() if self.fused else contextlib.nullcontext()
        with quiet:
            return self._jit(params, x)


class PipelineEngine:
    """S independent per-stage programs behind the one-engine surface.

    ``devices`` gives one chip per stage (stage k pinned to
    ``devices[k]``); ``params`` is the FULL pipelined checkpoint tree
    (``{embed, blocks, head}``) — the engine splits it by stage itself,
    at construction and on every ``swap_params``, so callers (pool
    fan-out, reload watcher, regroup) never learn the stage layout.
    ``model`` is the :class:`VisionTransformer` config the stage
    forwards are built from (per-stage programs cannot be derived from a
    bare ``apply_fn``: the stage boundary cuts THROUGH it).
    """

    def __init__(
        self,
        model,
        params,
        devices: Sequence,
        buckets: Sequence[int] = DEFAULT_BUCKETS,
        input_shape: Tuple[int, ...] = (28, 28, 1),
        serve_log=None,
        params_epoch: Optional[int] = None,
        name: str = "pipeline",
        workers: int = 4,
        precision: Optional[str] = None,
        fuse: bool = False,
    ) -> None:
        devices = list(devices)
        if not devices:
            raise ValueError("PipelineEngine needs at least one device")
        buckets = sorted({int(b) for b in buckets})
        if not buckets or buckets[0] < 1:
            raise ValueError(f"buckets must be positive ints, got {buckets!r}")
        self.buckets = tuple(buckets)
        self.input_shape = tuple(input_shape)
        self.serve_log = serve_log
        self.workers = workers
        self.name = name
        self.n_stages = len(devices)
        self.devices = tuple(devices)
        # The precision plane, per stage: each stage's param slice
        # quantizes independently (its own per-leaf scales), the FIRST
        # stage consumes the host-staged input dtype (int8 activations),
        # inter-stage D2D hops ride the precision's hop dtype (bf16
        # stays bf16 — half the hop bytes), and only the LAST stage
        # casts logits back to f32. f32 resolves to the identity spec:
        # every path below is byte-identical to the pre-precision chain.
        from pytorch_distributed_mnist_tpu.serve.programs import get_precision

        self._precision_spec = get_precision(precision)
        self.precision = self._precision_spec.name
        stage_fwds = list(make_stage_forward_fns(model, self.n_stages))
        forwards = [
            self._precision_spec.wrap_stage_forward(
                fwd, first=(k == 0), last=(k == self.n_stages - 1))
            for k, fwd in enumerate(stage_fwds)
        ]
        self._stages = [
            _StageProgram(k, fwd, dev, f"{name}.s{k}")
            for k, (fwd, dev) in enumerate(zip(forwards, devices))
        ]
        # Whole-program fusion cuts in at the chain's ONLY host boundary
        # — stage 0: a second stage-0 program consumes the raw staged
        # uint8 bytes (normalize + int8 activation quant inside XLA,
        # bitwise twins of the host path) and donates its buffer. Later
        # stages see the identical activation contract either way, so
        # they need no fused variant — the split chain past stage 0 IS
        # the fused chain past stage 0.
        self.fuse = bool(fuse)
        self.raw_shape = self.input_shape[:-1]
        if self.fuse:
            fused0 = self._precision_spec.wrap_fused_stage_forward(
                stage_fwds[0], first=True, last=(self.n_stages == 1))
            self._fused_stage0 = _StageProgram(
                0, fused0, devices[0], f"{name}.s0", fused=True)
            self._fused_staging = StagingPool(self.buckets, self.raw_shape,
                                              dtype=np.uint8)
        self._lock = threading.Lock()
        self._stage_params = self._place_stages(params)
        self._params_epoch = params_epoch
        self._staging = StagingPool(self.buckets, self.input_shape,
                                    dtype=self._precision_spec.input_dtype)

    def _place_stages(self, params) -> List:
        """Split the full pipelined tree by stage, quantize each slice
        (per-stage scales — the split runs on the f32 tree the stage
        boundaries are defined over), and commit each slice to its
        stage's chip — stage k's weights live on ``devices[k]`` ONLY
        (the HBM story: no chip holds the whole model)."""
        split = split_stage_params(params, self.n_stages)
        return [jax.device_put(
                    self._precision_spec.quantize(tree, workers=self.workers),
                    stage.sharding)
                for tree, stage in zip(split, self._stages)]

    # -- lifecycle ---------------------------------------------------------

    @property
    def max_batch(self) -> int:
        return self.buckets[-1]

    @property
    def params_epoch(self) -> Optional[int]:
        with self._lock:
            return self._params_epoch

    def stage_names(self) -> List[str]:
        return [s.name for s in self._stages]

    def warmup(self) -> None:
        """AOT-compile every bucket x stage program (idempotent). Input
        specs CHAIN: stage 0 lowers against the image buckets, each later
        stage against the previous stage's ``eval_shape`` output — the
        activation contract between independently-compiled programs is
        derived, never assumed."""
        with self._lock:
            stage_params = list(self._stage_params)
        specs = {
            b: jax.ShapeDtypeStruct((b,) + self.input_shape,
                                    self._precision_spec.input_dtype)
            for b in self.buckets
        }
        for stage, params in zip(self._stages, stage_params):
            specs = stage.warmup(abstract_spec(params), specs)
        if not self.fuse:
            return
        # The fused stage-0 programs warm alongside: raw uint8 buckets
        # in, the SAME activation spec out as split stage 0 (the fused
        # wrapper prepends in-XLA normalize/quant to the identical
        # post-normalize math), so stages 1..S-1 — already warmed above
        # — cover both planes and the fused chain adds exactly one
        # program per bucket.
        raw_specs = {
            b: jax.ShapeDtypeStruct((b,) + self.raw_shape, np.uint8)
            for b in self.buckets
        }
        self._fused_stage0.warmup(abstract_spec(stage_params[0]), raw_specs)

    def swap_params(self, params, epoch: Optional[int] = None,
                    path: Optional[str] = None) -> bool:
        """Coordinated per-stage hot-reload swap; the signature is the
        reload watcher's ``on_params`` callback, the return the engine
        swap-ordering contract (False == rejected as stale).

        The split + per-stage ``device_put`` run OUTSIDE the lock (the
        slow part); the install writes the WHOLE per-stage list and the
        epoch under one lock, and dispatch snapshots that list under the
        same lock once per batch — so a batch either runs every stage on
        the old epoch or every stage on the new one, never mixed.
        """
        del path  # provenance lives on the watcher (current_path)
        placed = self._place_stages(params)
        with self._lock:
            if (epoch is not None and self._params_epoch is not None
                    and epoch < self._params_epoch):
                return False  # a newer checkpoint already installed
            self._stage_params = placed
            self._params_epoch = epoch
            return True

    # -- inference ---------------------------------------------------------

    def bucket_for(self, n: int) -> int:
        return bucket_for(self.buckets, n)

    def preprocess(self, images) -> np.ndarray:
        if self.fuse:
            raw = as_raw_images(images, self.input_shape)
            if raw is not None:
                return raw  # validated raw bytes: the fused plane's input
        return preprocess_images(images, self.input_shape, self.workers)

    def staging_allocated(self) -> dict:
        return self._staging.allocated()

    def _retire_fused_staging(self,
                              buffers: List[Tuple[int, np.ndarray]]) -> None:
        # Retirement-only twin of the split plane's release path: a
        # donated buffer must never reach release() (the analyzer's
        # donation-discipline rule pins that retire and release never
        # share a routing function).
        self._fused_staging.retire(buffers)

    def fused_staging_retired(self) -> dict:
        """Donated-and-dropped buffer counts per bucket (empty when the
        fused plane is off)."""
        if not self.fuse:
            return {}
        return self._fused_staging.retired()

    def _dispatch_bucket(self, stage_params: List, images: np.ndarray,
                         buffers) -> Tuple:
        """Stage one chunk onto stage 0's chip and enqueue the whole
        chain: stage k's program, then the async device-to-device hop of
        its activation onto stage k+1's chip. Nothing here blocks — the
        returned logits are futures, and with several batches in flight
        every stage chip works a different batch concurrently."""
        n = images.shape[0]
        bucket = self.bucket_for(n)
        staged = stage_batch(images, bucket, self._staging, self.workers,
                             buffers)
        x = jax.device_put(staged, self._stages[0].sharding)
        for stage, params in zip(self._stages, stage_params):
            if stage.index:
                x = jax.device_put(x, stage.sharding)  # D2D hop
            x = stage.run(params, x)
        if self.serve_log is not None:
            self.serve_log.record_batch(n, bucket, replica=self.name)
        return x

    def _dispatch_fused(self, raw: np.ndarray) -> _InFlightBatch:
        """Whole-program chain dispatch: one bytes-copy into the raw
        uint8 staging buffer, the fused stage-0 program (normalize/quant
        inside XLA, buffer DONATED and retired at dispatch), then the
        ordinary stage 1..S-1 chain — identical activations, identical
        programs. The in-flight batch pins no buffers."""
        with self._lock:
            stage_params = list(self._stage_params)  # captured ONCE
            epoch = self._params_epoch
        chunks = []
        for start in range(0, raw.shape[0], self.max_batch):
            chunk = raw[start:start + self.max_batch]
            n = chunk.shape[0]
            bucket = self.bucket_for(n)
            buf = self._fused_staging.acquire(bucket)
            buf[:n] = chunk
            if n < bucket:
                buf[n:] = 0  # pad rows sliced off at complete()
            x = jax.device_put(buf, self._stages[0].sharding)
            self._retire_fused_staging([(bucket, buf)])
            x = self._fused_stage0.run(stage_params[0], x)
            for stage, params in zip(self._stages[1:], stage_params[1:]):
                x = jax.device_put(x, stage.sharding)  # D2D hop
                x = stage.run(params, x)
            if self.serve_log is not None:
                self.serve_log.record_batch(n, bucket, replica=self.name)
            chunks.append((x, n))
        return _InFlightBatch(self, chunks, epoch, [])

    def dispatch_logits(self, images) -> _InFlightBatch:
        """Preprocess + stage + enqueue the per-stage chain WITHOUT
        waiting (the PR 4 two-phase API): the returned batch holds
        device futures that materialize while the caller forms the next
        batch. The per-stage params and the epoch are captured together
        under the lock, once per batch — the cross-stage swap-atomicity
        boundary. Batches larger than the top bucket are chunked.

        A FUSED chain routes validated raw uint8 input through the fused
        stage-0 programs (:meth:`_dispatch_fused`); float input keeps
        the split path below — the ``--no-fuse`` reference plane."""
        if self.fuse:
            raw = as_raw_images(images, self.input_shape)
            if raw is not None:
                return self._dispatch_fused(raw)
        x = self.preprocess(images)
        # Host-side activation transform (int8 plane: quantize once with
        # the fixed scale before chunking — the staged buffers and the
        # stage-0 H2D transfer are int8).
        x = self._precision_spec.stage_host(x, workers=self.workers)
        with self._lock:
            stage_params = list(self._stage_params)  # captured ONCE
            epoch = self._params_epoch
        chunks, buffers = [], []
        try:
            for start in range(0, x.shape[0], self.max_batch):
                chunk = x[start:start + self.max_batch]
                chunks.append(
                    (self._dispatch_bucket(stage_params, chunk, buffers),
                     chunk.shape[0]))
        except BaseException:
            self._staging.release(buffers)
            raise
        return _InFlightBatch(self, chunks, epoch, buffers)

    def complete(self, inflight: _InFlightBatch) \
            -> Tuple[np.ndarray, Optional[int]]:
        """Block on the last stage's device results, release the staging
        buffers, and return ``(logits (N, classes), epoch)`` — exactly
        the single-engine contract, so pool failover and the batcher's
        completion stage treat a chain like any replica."""
        try:
            out = [np.asarray(dev)[:n] for dev, n in inflight.chunks]
        finally:
            self._staging.release(inflight.buffers)
            inflight.buffers = []
        return np.concatenate(out, axis=0), inflight.epoch

    def logits_with_epoch(self, images) -> Tuple[np.ndarray, Optional[int]]:
        return self.dispatch_logits(images).complete()

    def logits(self, images) -> np.ndarray:
        return self.logits_with_epoch(images)[0]

    def predict(self, images) -> np.ndarray:
        return np.argmax(self.logits(images), axis=-1)

    def predict_with_epoch(self, images) -> Tuple[np.ndarray, Optional[int]]:
        logits, epoch = self.logits_with_epoch(images)
        return np.argmax(logits, axis=-1), epoch


def make_pipeline_template(model, rng):
    """The template state a pipeline-trained checkpoint restores onto:
    params in the PIPELINED ``{embed, blocks, head}`` layout (leaves
    stacked on the depth dim — what training saved), optimizer moments
    mirroring it, host-side and meshless (the serve plane splits by
    stage itself; it never builds the training mesh). The serve boot and
    every hot reload load through this, the same
    ``load_checkpoint``-onto-template validation as every other mode."""
    import jax.numpy as jnp

    from pytorch_distributed_mnist_tpu.train.state import (
        TrainState,
        make_optimizer,
    )

    params = split_vit_params(
        model.init(rng, jnp.zeros((1, 28, 28, 1), jnp.float32)))
    tx = make_optimizer()
    return TrainState(
        step=jnp.zeros((), jnp.int32),
        params=params,
        opt_state=tx.init(params),
        apply_fn=model.apply,
        tx=tx,
    )


def pipeline_engine_factory(*, model, model_name, params, devices, name,
                            buckets, input_shape, serve_log, params_epoch,
                            workers, apply_fn=None, precision=None,
                            fuse=False):
    """The registry's engine hook (``serve/programs.py`` registers mode
    ``pipeline`` with it): one pipeline CHAIN spanning ``devices``
    (stage k on chip k). Needs the model CONFIG, not just an apply_fn —
    the stage boundary cuts through the forward."""
    del apply_fn  # the chain rebuilds the forward per stage
    if model is None:
        raise ValueError(
            "--serve-mode pipeline needs the model object (stage "
            f"programs are built from --model {model_name}'s structure, "
            "not an apply_fn); pass model= to the pool")
    return PipelineEngine(
        model, params, devices, buckets=buckets, input_shape=input_shape,
        serve_log=serve_log, params_epoch=params_epoch, name=name,
        workers=workers, precision=precision, fuse=fuse)
