"""Bucketed AOT inference engine.

Serving on TPU has one cardinal rule: a request must NEVER trigger an XLA
compile. A compile is 20-40 s of wall-clock on a real chip — against a
p99 budget of milliseconds — and jit keys programs by input shape, so a
naive ``jit(forward)(params, batch)`` recompiles for every distinct batch
size the batcher happens to form. The engine therefore owns a FIXED set
of batch buckets (default 1/8/32/128), AOT-compiles one forward program
per bucket at startup (``.lower().compile()`` through the same
``precompile`` path the trainer uses, so compiles land in ``CompileLog``
and the persistent cache applies), and pads every batch up to the
nearest bucket. Steady-state serving touches only those executables:
zero recompiles, asserted by test via ``CompileLog``.

The forward program is built by ``train/steps.py make_forward_program``
— the SAME builder the ``-e/--evaluate`` eval step traces — so serving
can never disagree with evaluation on forward math or dtype policy, and
preprocessing goes through the same ``normalize_images`` the training
loaders use. Params are an explicit argument of the compiled programs
(not a closure capture), which is what makes checkpoint hot-reload free:
``swap_params`` is an atomic reference swap between batches; an in-flight
batch keeps the params it captured at call entry, the next batch sees the
new ones, and no executable is invalidated.

Two data-plane mechanisms serve the multi-chip pool (``serve/pool.py``):

- **Device pinning.** An engine built with ``device=`` commits params
  and compiles its bucket programs for THAT device
  (``SingleDeviceSharding`` on params, inputs, and outputs), so N
  engines on N local chips execute concurrently instead of contending
  for ``devices()[0]``. ``device=None`` keeps today's default placement
  bit-for-bit.
- **Dispatch/complete split.** ``dispatch_logits`` stages the batch,
  enqueues the device execution, and returns immediately with an
  :class:`_InFlightBatch` (JAX async dispatch: the returned arrays are
  futures); ``complete`` blocks on the result fetch. The pipelined
  batcher overlaps batch N+1's host-side preprocessing and padding with
  batch N's device execution through exactly this seam —
  ``logits_with_epoch`` is just dispatch immediately followed by
  complete, so the synchronous path cannot drift from the pipelined one.

The ``precision=`` plane (``serve/programs.py``): a quantized precision
wraps the forward (on-chip dequant/cast, pure jnp), turns ``_place``
into quantize-then-commit (per-leaf symmetric scales computed once per
install, OUTSIDE the lock, riding the quantized tree as ARGUMENTS of
the compiled programs — hot reload still swaps a reference and
recompiles nothing), and sets the staging dtype (the int8 plane stages
and transfers int8, a quarter of the f32 bytes). ``f32`` — the default
— resolves to the identity spec: every path below is byte-identical to
the pre-precision engine.

Staging-buffer lifecycle: padding a batch up to its bucket reuses a
per-bucket float32 buffer from a free-list instead of allocating per
batch. A buffer is acquired at dispatch, referenced by the in-flight
batch until its completion fetch proves the device has consumed the
input, then returned to the free-list — so the steady-state pool depth
equals the in-flight window and per-batch allocation drops to zero, and
the reuse is safe even on backends that alias host buffers into device
arrays. Exact-fit float32 C-contiguous batches skip the staging copy
entirely (the bitwise-exactness tests pin that path).
"""

from __future__ import annotations

import contextlib
import threading
import warnings
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import numpy as np

from pytorch_distributed_mnist_tpu.data import native
from pytorch_distributed_mnist_tpu.data.mnist import normalize_images
from pytorch_distributed_mnist_tpu.train.steps import (
    abstract_spec,
    make_forward_program,
    precompile,
)

DEFAULT_BUCKETS = (1, 8, 32, 128)


@contextlib.contextmanager
def _quiet_donation():
    """Backends that cannot alias a donated host buffer (CPU — the test
    and interpret-mode world) warn once per fused-program compile that
    the donation was unusable. The fused plane is DESIGNED to run there
    (correctness is backend-independent; the aliasing is a TPU win), so
    the warning is expected noise around fused compiles, not a bug."""
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        yield


class StagingPool:
    """Per-bucket float32 staging free-lists (the lifecycle in the module
    docstring), factored out so every serving engine shares ONE
    implementation: the single/pooled/sharded ``InferenceEngine`` and the
    MPMD per-stage plane (``serve/pipeline.py``) acquire at dispatch, pin
    until the completion fetch, and release for reuse through the same
    code."""

    def __init__(self, buckets: Sequence[int],
                 input_shape: Tuple[int, ...],
                 dtype=np.float32) -> None:
        self.input_shape = tuple(input_shape)
        # float32 everywhere except the int8-activation serving plane,
        # whose staged batches (and H2D transfers) are int8 — a quarter
        # of the bytes. The lifecycle is dtype-oblivious.
        self.dtype = np.dtype(dtype)
        self._lock = threading.Lock()
        self._free: dict = {b: [] for b in buckets}
        self._allocated = {b: 0 for b in buckets}
        self._retired = {b: 0 for b in buckets}

    def acquire(self, bucket: int) -> np.ndarray:
        """Pop a free staging buffer for ``bucket`` (allocate only when
        the free-list is dry — i.e. only until the pool has grown to the
        in-flight window's depth)."""
        with self._lock:
            free = self._free[bucket]
            if free:
                return free.pop()
            self._allocated[bucket] += 1
        return np.zeros((bucket,) + self.input_shape, self.dtype)

    def release(self, buffers: List[Tuple[int, np.ndarray]]) -> None:
        with self._lock:
            for bucket, buf in buffers:
                self._free[bucket].append(buf)

    def retire(self, buffers: List[Tuple[int, np.ndarray]]) -> None:
        """Permanently drop buffers whose bytes were DONATED to a
        compiled program (``donate_argnums``): XLA owns that memory now
        — on backends that alias host buffers into device arrays,
        re-appending a donated buffer to the free-list would hand a
        future batch memory the program may already have overwritten (a
        use-after-free in staging clothing). Retired buffers are counted
        so tests can pin the lifecycle; the free-list never sees them
        again."""
        with self._lock:
            for bucket, _buf in buffers:
                self._retired[bucket] += 1

    def retired(self) -> dict:
        """Total buffers retired (donated, dropped) per bucket."""
        with self._lock:
            return dict(self._retired)

    def allocated(self) -> dict:
        """Total buffers ever allocated per bucket — the steady-state
        invariant (no per-batch allocation) is that this stops growing
        once the in-flight window is warm; tests pin it."""
        with self._lock:
            return dict(self._allocated)


def stage_batch(images: np.ndarray, bucket: int, staging: StagingPool,
                workers: int, buffers: List) -> np.ndarray:
    """Stage one chunk into its bucket: the exact-fit no-copy fast path,
    or a pad-into-staging fill (multithreaded native kernel with the
    bitwise-identical NumPy fallback — padded rows are zeros, as they
    always were). Any buffer acquired is appended to ``buffers`` so the
    in-flight batch pins it until completion proves the device consumed
    the input. Shared by ``InferenceEngine`` and the per-stage MPMD
    plane so the staging bytes can never drift between them."""
    n = images.shape[0]
    if (n == bucket and images.dtype == staging.dtype
            and images.flags["C_CONTIGUOUS"]):
        # Exact fit, already contiguous at the staging dtype: no pad, no
        # copy — the array goes to the device as-is (bitwise-pinned
        # equal to the padded path by the exactness tests).
        return images
    buf = staging.acquire(bucket)
    # Anything not already C-contiguous at the staging dtype goes
    # straight to the fallback's one converting copy — a pre-conversion
    # just to feed the native kernel would cost a second full-batch
    # copy. (The native pad kernel is f32-only; int8 staging pads via
    # NumPy — a quarter of the bytes, so the copy it skips is smaller
    # than the one the f32 kernel earns its keep on.)
    filled = (staging.dtype == np.float32
              and images.dtype == np.float32
              and images.flags["C_CONTIGUOUS"]
              and native.pad_into(buf, images, workers=workers))
    if not filled:
        buf[:n] = images
        if n < bucket:
            buf[n:] = 0.0
    buffers.append((bucket, buf))
    return buf


def preprocess_images(images, input_shape: Tuple[int, ...],
                      workers: int) -> np.ndarray:
    """Raw request pixels -> the float32 normalized layout training
    uses. Accepts uint8 ``(N, 28, 28)`` raw images (normalized with the
    SAME ``normalize_images`` the training loaders apply) or
    already-normalized float32 ``(N,) + input_shape`` arrays; a single
    example may drop its leading axis either way.

    Zero Python-side array math on the dispatch path when the native
    library is built: normalize and the f64->f32 cast run in
    multithreaded C++ over ``workers`` threads, with the NumPy
    expressions as the mandatory bitwise-identical fallback."""
    arr = np.asarray(images)
    if arr.size == 0:
        raise ValueError("at least one image required")
    raw_shape = input_shape[:-1]  # e.g. (28, 28): pre-channel
    if arr.dtype == np.uint8:
        if arr.shape == raw_shape:
            arr = arr[None]
        if arr.ndim == len(raw_shape) + 1 and arr.shape[1:] == raw_shape:
            return normalize_images(arr, workers=workers)
    elif np.issubdtype(arr.dtype, np.floating):
        cast = native.cast_f32(arr, workers=workers) \
            if arr.dtype == np.float64 else None
        arr = cast if cast is not None \
            else arr.astype(np.float32, copy=False)
        if arr.shape == input_shape:
            arr = arr[None]
        if arr.ndim == len(input_shape) + 1 \
                and arr.shape[1:] == input_shape:
            return arr
    raise ValueError(
        f"expected uint8 (N, {', '.join(map(str, raw_shape))}) raw "
        f"images or float32 (N, {', '.join(map(str, input_shape))})"
        f" normalized images; got {arr.dtype} {arr.shape}")


def as_raw_images(images, input_shape: Tuple[int, ...]) \
        -> Optional[np.ndarray]:
    """The fused plane's validation: raw uint8 ``(N, 28, 28)`` request
    pixels (a single example may drop its leading axis) pass through
    UNNORMALIZED — the fused bucket programs take the bytes themselves.
    Returns ``None`` for anything else (already-normalized float input,
    wrong shape), which routes the caller to the split plane — the split
    path stays the one place float inputs are validated and served."""
    arr = np.asarray(images)
    if arr.dtype != np.uint8 or arr.size == 0:
        return None
    raw_shape = input_shape[:-1]  # e.g. (28, 28): pre-channel
    if arr.shape == raw_shape:
        arr = arr[None]
    if arr.ndim == len(raw_shape) + 1 and arr.shape[1:] == raw_shape:
        return arr
    return None


def bucket_for(buckets: Sequence[int], n: int) -> int:
    """Smallest bucket >= n (n must not exceed the largest bucket — the
    dispatch paths chunk oversized batches before calling this)."""
    for b in buckets:
        if n <= b:
            return b
    raise ValueError(f"batch of {n} exceeds largest bucket {buckets[-1]}")


class _InFlightBatch:
    """One dispatched-but-not-fetched batch: the device arrays (futures
    under JAX async dispatch), the epoch of the params that computed
    them, and the staging buffers the batch still pins. ``complete()``
    blocks on the fetch and releases the buffers."""

    __slots__ = ("engine", "chunks", "epoch", "buffers")

    def __init__(self, engine: "InferenceEngine", chunks, epoch,
                 buffers) -> None:
        self.engine = engine
        self.chunks = chunks  # [(device_logits, real_rows), ...]
        self.epoch = epoch
        self.buffers = buffers  # staging buffers pinned until complete

    def complete(self) -> Tuple[np.ndarray, Optional[int]]:
        return self.engine.complete(self)


class InferenceEngine:
    """Params + one AOT-compiled forward executable per batch bucket.

    Threading contract: ``logits``/``predict``/``dispatch_logits`` are
    normally called from ONE thread at a time (the batcher's dispatch
    worker serializes device submission — concurrent forward calls to
    one chip would just contend for it); ``complete`` runs on the
    batcher's completion worker, which only touches the in-flight
    batch's own state plus the staging free-list (its own lock);
    ``swap_params`` may be called from any thread (the reload watcher)
    at any moment. One-thread dispatch is a contention guideline, not a
    correctness invariant: per-batch dispatch state is function-local
    (chunks, buffers) or lock-protected (the params+epoch capture, the
    staging free-list), so the pool's failover path may re-dispatch a
    failed batch from its completion thread concurrently with the
    dispatch worker. The only shared mutable state is the params
    reference + epoch, read together once per batch under the lock.

    ``device``: pin this engine to one local device — params are
    committed there and every bucket program is AOT-compiled for it
    (the replica-pool placement). ``None`` keeps jax's default
    placement, identical to the single-device data plane this engine
    shipped with. ``name`` suffixes the per-bucket ``CompileLog``
    program names (``serve_forward_b8@r2``) so a pool's compile stats
    and the zero-recompile check stay attributable per replica.

    ``placement``: a :class:`~pytorch_distributed_mnist_tpu.serve.
    programs.MeshPlacement` — the SHARDED plane. The engine then spans
    the placement's mesh: params commit with the mode's ``NamedSharding``
    tree (derived from the training rule tables by the program
    registry), each bucket program pjit-lowers with those in/out
    shardings (``serve_forward_b{b}@{mode}`` in ``CompileLog``), inputs
    replicate over the mesh, and outputs come back replicated so
    ``complete`` reads them exactly as it reads single-device results.
    Everything else — buckets, staging free-lists, the dispatch/complete
    split, the swap-ordering rule — is mode-agnostic and unchanged.
    Mutually exclusive with ``device``.
    """

    def __init__(
        self,
        apply_fn,
        params,
        buckets: Sequence[int] = DEFAULT_BUCKETS,
        input_shape: Tuple[int, ...] = (28, 28, 1),
        serve_log=None,
        params_epoch: Optional[int] = None,
        device=None,
        name: Optional[str] = None,
        workers: int = 4,
        placement=None,
        precision: Optional[str] = None,
        fuse: bool = False,
    ) -> None:
        buckets = sorted({int(b) for b in buckets})
        if not buckets or buckets[0] < 1:
            raise ValueError(f"buckets must be positive ints, got {buckets!r}")
        self.buckets = tuple(buckets)
        self.input_shape = tuple(input_shape)
        self.serve_log = serve_log
        # Host-side preprocessing thread count (the serve analog of the
        # training loaders' -j/--workers): normalize, f64->f32 cast, and
        # the pad-into-staging copy run in multithreaded C++ when the
        # native library is built, over this many threads.
        self.workers = workers
        self.device = device
        self.placement = placement
        self.name = name
        # The precision plane (serve/programs.py): f32 — the default —
        # resolves to the identity spec and every path below stays
        # byte-identical to the pre-precision engine. A quantized
        # precision wraps the forward (dequant/cast in-program), turns
        # _place into quantize-then-device_put, and sets the staging
        # dtype (int8 activations stage as int8).
        from pytorch_distributed_mnist_tpu.serve.programs import get_precision

        self._precision_spec = get_precision(precision)
        self.precision = self._precision_spec.name
        self._forward = self._precision_spec.wrap_forward(
            make_forward_program(apply_fn))
        if placement is not None:
            if device is not None:
                raise ValueError(
                    "pass device= (single-chip pinning) or placement= "
                    "(sharded mesh), not both")
            # Sharded plane: the placement owns commit + lowering —
            # params with the mode's NamedSharding tree, inputs/outputs
            # replicated over the mesh (serve/programs.py).
            self._sharding = None
            self._jit = placement.jit_forward(self._forward)
        elif device is not None:
            # Pin params, inputs, and outputs to THIS device so the AOT
            # executables land there (default lowering would compile for
            # devices()[0] and reject arguments committed elsewhere).
            self._sharding = jax.sharding.SingleDeviceSharding(device)
            self._jit = jax.jit(self._forward, in_shardings=self._sharding,
                                out_shardings=self._sharding)
        else:
            self._sharding = None
            self._jit = jax.jit(self._forward)  # lazy fallback, same program
        # The FUSED (whole-program) plane: one additional program per
        # bucket taking the raw staged uint8 bytes — normalize (and int8
        # activation quantization) runs inside XLA, bitwise-pinned to
        # the host twins (serve/programs.py), and the staged batch is
        # DONATED (its buffer is retired from the free-list, never
        # re-pinned). The split programs above stay compiled alongside:
        # they serve float (already-normalized) inputs, and they are the
        # bitwise reference --no-fuse pins against.
        self.fuse = bool(fuse)
        self.raw_shape = self.input_shape[:-1]
        self._fused_compiled = {}  # bucket -> Compiled executable
        if self.fuse:
            fused = self._precision_spec.wrap_fused_forward(
                make_forward_program(apply_fn))
            if placement is not None:
                self._fused_jit = placement.jit_fused_forward(fused)
            elif device is not None:
                self._fused_jit = jax.jit(
                    fused, in_shardings=self._sharding,
                    out_shardings=self._sharding, donate_argnums=(1,))
            else:
                self._fused_jit = jax.jit(fused, donate_argnums=(1,))
            # Raw uint8 staging, one buffer per dispatch: acquired, always
            # COPIED into (donating a request's own array would corrupt
            # the pool's failover redispatch, which re-sends the same
            # rows), then retired at dispatch because donation hands the
            # bytes to XLA.
            self._fused_staging = StagingPool(self.buckets, self.raw_shape,
                                              dtype=np.uint8)
        self._lock = threading.Lock()
        # Committed to device once per swap, not once per request.
        self._params = self._place(params)
        self._params_epoch = params_epoch
        # Swap hooks (ISSUE 19): called UNDER _lock right after an
        # install, so cache-generation bumps are atomic with the params
        # swap — no request can hit a pre-swap cache entry after the
        # new params are visible. Hooks must be O(1) arithmetic
        # (ResponseCache.bump_generation is one integer increment).
        self._swap_hooks: List[Callable] = []
        self._compiled = {}  # bucket -> Compiled executable
        # bucket -> free staging buffers (see module docstring lifecycle).
        self._staging = StagingPool(self.buckets, self.input_shape,
                                    dtype=self._precision_spec.input_dtype)

    def _place(self, tree):
        """Commit a PARAMS tree to this engine's device(s): the mesh
        placement's sharding tree on the sharded plane, the pinned
        device's ``SingleDeviceSharding`` on the pooled one, default
        placement when unpinned.

        On a quantized precision the tree is QUANTIZED first (per-leaf
        symmetric scales, computed once per install, host-side) — this
        runs from ``__init__`` and from ``swap_params`` BEFORE the lock
        is taken, so quantization rides the same slow-part-outside-the-
        lock discipline as the ``device_put`` it precedes, and the
        installed reference swap stays what in-flight batches race
        against."""
        tree = self._precision_spec.quantize(tree, workers=self.workers)
        if self.placement is not None:
            return self.placement.place_params(tree)
        if self._sharding is not None:
            return jax.device_put(tree, self._sharding)
        return jax.device_put(tree)

    def _place_input(self, staged):
        """Commit one staged input batch: replicated over the mesh on
        the sharded plane; otherwise exactly the pre-sharding behavior
        (committed to the pinned device, or left to jax's default)."""
        if self.placement is not None:
            return self.placement.place_input(staged)
        if self._sharding is not None:
            return jax.device_put(staged, self._sharding)
        return jax.numpy.asarray(staged)

    # -- lifecycle ---------------------------------------------------------

    @property
    def max_batch(self) -> int:
        return self.buckets[-1]

    @property
    def params_epoch(self) -> Optional[int]:
        with self._lock:
            return self._params_epoch

    def program_name(self, bucket: int) -> str:
        """The ``CompileLog`` program name of one bucket's executable —
        ``serve_forward_b{bucket}``, suffixed ``@{name}`` on a named
        (pool-replica) engine so compile stats stay per-replica."""
        base = f"serve_forward_b{bucket}"
        return f"{base}@{self.name}" if self.name else base

    def fused_program_name(self, bucket: int) -> str:
        """The fused program's ``CompileLog`` name: the ``.fused`` tag
        rides the bucket segment (``serve_forward_b{bucket}.fused@{name}``)
        so every ``serve_forward_`` prefix filter (/stats' compile
        block) covers both planes."""
        base = f"serve_forward_b{bucket}.fused"
        return f"{base}@{self.name}" if self.name else base

    def warmup(self) -> None:
        """AOT-compile every bucket's forward program (idempotent).

        Each program is measured under ``program_name(bucket)`` in the
        process ``CompileLog``, so startup cost is attributable per bucket
        (and per replica) and the zero-steady-state-recompiles acceptance
        check has an anchor to diff against. With a warm persistent
        compile cache these degenerate to executable fetches.
        """
        with self._lock:
            params_spec = abstract_spec(self._params)
        for bucket in self.buckets:
            if bucket in self._compiled:
                continue
            image_spec = jax.ShapeDtypeStruct(
                (bucket,) + self.input_shape,
                self._precision_spec.input_dtype)
            self._compiled[bucket] = precompile(
                self._jit, params_spec, image_spec,
                program=self.program_name(bucket))
        if not self.fuse:
            return
        # The fused plane warms alongside the split one: BOTH are
        # steady-state programs (raw uint8 requests ride fused, float
        # ones ride split), so both must be executables before the
        # socket opens for the zero-recompile guarantee to cover them.
        for bucket in self.buckets:
            if bucket in self._fused_compiled:
                continue
            raw_spec = jax.ShapeDtypeStruct(
                (bucket,) + self.raw_shape, np.uint8)
            with _quiet_donation():
                self._fused_compiled[bucket] = precompile(
                    self._fused_jit, params_spec, raw_spec,
                    program=self.fused_program_name(bucket))

    def add_swap_hook(self, hook: Callable) -> None:
        """Register ``hook(epoch)`` to run UNDER the params lock each
        time a swap installs (hot reload / precision swap): the
        response cache's ``bump_generation`` seam — atomic with the
        install, O(1) arithmetic only."""
        with self._lock:
            self._swap_hooks.append(hook)

    def swap_params(self, params, epoch: Optional[int] = None,
                    path: Optional[str] = None) -> bool:
        """Atomically install new params (checkpoint hot-reload); the
        signature is exactly the reload watcher's ``on_params`` callback.
        Returns True when installed, False when rejected as stale.

        The device_put runs OUTSIDE the lock (it is the slow part); the
        installed reference swap is what in-flight batches race against,
        and they only ever read the reference once, at call entry.
        Because the slow part is unlocked, two concurrent swaps can reach
        the install point in either order — so the install compares
        epochs UNDER the lock and refuses to put an older checkpoint over
        a newer one (the swap-ordering guarantee; a pool fan-out applies
        this rule per replica). Epoch-less swaps (fresh-init params, unit
        tests) always install: the ordering rule is about checkpoint
        provenance, and they have none.
        """
        del path  # provenance lives on the watcher (current_path)
        placed = self._place(params)
        with self._lock:
            if (epoch is not None and self._params_epoch is not None
                    and epoch < self._params_epoch):
                return False  # a newer checkpoint already installed
            self._params = placed
            self._params_epoch = epoch
            for hook in self._swap_hooks:
                hook(epoch)
            return True

    # -- inference ---------------------------------------------------------

    def bucket_for(self, n: int) -> int:
        """Smallest bucket >= n (n must not exceed the largest bucket —
        ``logits`` chunks oversized batches before calling this)."""
        return bucket_for(self.buckets, n)

    def preprocess(self, images: np.ndarray) -> np.ndarray:
        """Raw request pixels -> the float32 normalized layout training
        uses (module-level :func:`preprocess_images`, shared with the
        per-stage MPMD plane).

        On a FUSED engine, validated raw uint8 input passes through
        unnormalized — the whole point of the fused plane is that the
        normalize runs inside the compiled program, so the batcher
        coalesces uint8 rows and dispatch routes them to the fused
        bucket programs. Float (already-normalized) input still takes
        the split path either way."""
        if self.fuse:
            raw = as_raw_images(images, self.input_shape)
            if raw is not None:
                return raw
        return preprocess_images(images, self.input_shape, self.workers)

    # -- staging-buffer lifecycle -----------------------------------------

    def _release_staging(self, buffers: List[Tuple[int, np.ndarray]]) -> None:
        self._staging.release(buffers)

    def _retire_fused_staging(self,
                              buffers: List[Tuple[int, np.ndarray]]) -> None:
        # Deliberately a SEPARATE function from _release_staging: a
        # donated buffer must never reach release() (the analyzer's
        # donation-discipline rule fires on any function that can route
        # one buffer to both).
        self._fused_staging.retire(buffers)

    def staging_allocated(self) -> dict:
        """Total buffers ever allocated per bucket (see
        :meth:`StagingPool.allocated`)."""
        return self._staging.allocated()

    def fused_staging_retired(self) -> dict:
        """Donated-and-dropped fused staging buffers per bucket (the
        donation lifecycle's observable; zeros on an unfused engine)."""
        if not self.fuse:
            return {}
        return self._fused_staging.retired()

    # -- dispatch / complete ----------------------------------------------

    def _dispatch_bucket(self, params, images: np.ndarray, buffers):
        """Stage one chunk into its bucket and enqueue the forward on the
        device (JAX async dispatch: returns the un-fetched device logits
        without waiting). Any staging buffer used is appended to
        ``buffers`` so the in-flight batch pins it until completion."""
        n = images.shape[0]
        bucket = self.bucket_for(n)
        staged = stage_batch(images, bucket, self._staging, self.workers,
                             buffers)
        compiled = self._compiled.get(bucket)
        x = self._place_input(staged)
        if compiled is not None:
            out = compiled(params, x)
        else:
            # Lazy fallback (warmup skipped or failed): same program via
            # jit — correctness preserved, the no-recompile guarantee is
            # what warmup buys.
            out = self._jit(params, x)
        if self.serve_log is not None:
            self.serve_log.record_batch(n, bucket, replica=self.name)
        return out

    def _dispatch_fused(self, raw: np.ndarray) -> _InFlightBatch:
        """The whole-program hot path: host work is ONE bytes-copy into
        a raw uint8 staging buffer per chunk; normalize/quantize/forward
        all run inside the fused bucket program. The staging buffer is
        ALWAYS copied into (never the split path's exact-fit zero-copy:
        the program donates its input, and donating a request's own
        array would corrupt the pool's failover redispatch, which
        re-sends the same rows) and RETIRED at dispatch — donation hands
        the bytes to XLA, so the free-list must never see the buffer
        again. The in-flight batch therefore pins nothing."""
        with self._lock:
            params = self._params  # captured ONCE: swap-atomicity boundary
            epoch = self._params_epoch
        chunks = []
        for start in range(0, raw.shape[0], self.max_batch):
            chunk = raw[start:start + self.max_batch]
            n = chunk.shape[0]
            bucket = self.bucket_for(n)
            buf = self._fused_staging.acquire(bucket)
            buf[:n] = chunk
            if n < bucket:
                # Raw-zero padding: the program normalizes pad rows to
                # (0-mean)/std rather than the split plane's 0.0 — the
                # real rows' logits are unaffected (the forward is
                # row-independent) and pad rows are sliced off at
                # complete(); DESIGN.md §7k names the one exception
                # (batch-coupled capacity routing) as a --no-fuse case.
                buf[n:] = 0
            x = self._place_input(buf)
            self._retire_fused_staging([(bucket, buf)])
            compiled = self._fused_compiled.get(bucket)
            if compiled is not None:
                out = compiled(params, x)
            else:
                with _quiet_donation():
                    out = self._fused_jit(params, x)
            if self.serve_log is not None:
                self.serve_log.record_batch(n, bucket, replica=self.name)
            chunks.append((out, n))
        return _InFlightBatch(self, chunks, epoch, [])

    def dispatch_logits(self, images) -> _InFlightBatch:
        """Preprocess + stage + enqueue the forward WITHOUT waiting for
        the result: the returned :class:`_InFlightBatch` holds device
        arrays that materialize under JAX async dispatch while the caller
        goes on to form/stage the next batch. Params and epoch are
        captured together under the lock, once for every chunk — the same
        swap-atomicity boundary the synchronous path has. Batches larger
        than the top bucket are chunked through it.

        A FUSED engine routes validated raw uint8 input to the fused
        bucket programs (:meth:`_dispatch_fused`); float input — already
        normalized upstream — keeps the split path below, which is also
        the ``--no-fuse`` reference plane."""
        if self.fuse:
            raw = as_raw_images(images, self.input_shape)
            if raw is not None:
                return self._dispatch_fused(raw)
        x = self.preprocess(images)
        # Host-side activation transform (int8 plane: quantize the whole
        # normalized batch once with the fixed scale — native v4 kernel,
        # bitwise NumPy fallback — BEFORE chunking/staging, so the
        # staged buffers and the H2D transfers are int8).
        x = self._precision_spec.stage_host(x, workers=self.workers)
        with self._lock:
            params = self._params  # captured ONCE: swap-atomicity boundary
            epoch = self._params_epoch
        chunks, buffers = [], []
        try:
            for start in range(0, x.shape[0], self.max_batch):
                chunk = x[start:start + self.max_batch]
                chunks.append((self._dispatch_bucket(params, chunk, buffers),
                               chunk.shape[0]))
        except BaseException:
            self._release_staging(buffers)
            raise
        return _InFlightBatch(self, chunks, epoch, buffers)

    def complete(self, inflight: _InFlightBatch) \
            -> Tuple[np.ndarray, Optional[int]]:
        """Block on an in-flight batch's device results, release its
        staging buffers, and return ``(logits (N, classes), epoch)``."""
        try:
            out = [np.asarray(dev)[:n] for dev, n in inflight.chunks]
        finally:
            self._release_staging(inflight.buffers)
            inflight.buffers = []
        return np.concatenate(out, axis=0), inflight.epoch

    def logits_with_epoch(self, images) -> Tuple[np.ndarray, Optional[int]]:
        """Forward ``images`` (raw uint8 or normalized float32) through
        the bucketed programs; returns ``(logits (N, classes), epoch)``
        where ``epoch`` is the checkpoint epoch of the params that
        ACTUALLY computed these logits. Dispatch immediately followed by
        complete: the synchronous path and the pipelined one are the same
        code."""
        return self.dispatch_logits(images).complete()

    def logits(self, images) -> np.ndarray:
        return self.logits_with_epoch(images)[0]

    def predict(self, images) -> np.ndarray:
        """Class labels (int64) for ``images``. The argmax stays on the
        host so the device program remains byte-identical to the eval
        forward pass."""
        return np.argmax(self.logits(images), axis=-1)

    def predict_with_epoch(self, images) -> Tuple[np.ndarray, Optional[int]]:
        logits, epoch = self.logits_with_epoch(images)
        return np.argmax(logits, axis=-1), epoch


def load_params_for_serving(path: str, template_state) -> Tuple[object, int]:
    """Restore just ``(params, epoch)`` from a published checkpoint onto
    ``template_state``'s layout — the serve-side restore used at boot and
    by every hot reload. ``epoch`` is the checkpoint's own epoch number
    (the file's ``checkpoint_{e}`` index), not the stored resume epoch."""
    from pytorch_distributed_mnist_tpu.train.checkpoint import load_checkpoint

    state, next_epoch, _best = load_checkpoint(path, template_state)
    return state.variables, next_epoch - 1
