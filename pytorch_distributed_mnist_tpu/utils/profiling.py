"""Profiling hooks.

The reference has none — ``time`` is imported but never used
(``/root/reference/multi_proc_single_gpu.py:5``; SURVEY.md section 5
"Tracing/profiling: ABSENT"). The TPU build reports steps/sec and
images/sec/chip (the BASELINE.json metric) and can capture an XLA profiler
trace for xprof/tensorboard.
"""

from __future__ import annotations

import collections
import contextlib
import functools
import json
import os
import threading
import time
from typing import Callable, Dict, Optional

import jax

_IMPORTED_AT = time.time()


class StepTimer:
    """Throughput meter over explicitly measured phases.

    Only wall-time spent inside ``measure(...)`` blocks counts toward the
    rate, so training throughput is not diluted by eval/checkpoint time
    happening between measured phases (a phase-mixing bug in earlier
    revisions of ``cli.py`` that understated images/sec)."""

    def __init__(self, num_chips: Optional[int] = None) -> None:
        self.num_chips = num_chips or jax.device_count()
        self.reset()

    def reset(self) -> None:
        self.images = 0
        self.steps = 0
        self.seconds = 0.0
        self.last_images = 0
        self.last_seconds = 0.0

    @contextlib.contextmanager
    def measure(self, images: int):
        """Time the enclosed phase and attribute ``images`` to it.

        The caller must ensure device work is complete before the block
        exits (e.g. by folding metrics to host values inside it)."""
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.last_seconds = time.perf_counter() - t0
            self.last_images = images
            self.seconds += self.last_seconds
            self.images += images
            self.steps += 1

    @property
    def elapsed(self) -> float:
        return self.seconds

    @property
    def images_per_sec(self) -> float:
        return self.images / max(self.elapsed, 1e-9)

    @property
    def images_per_sec_per_chip(self) -> float:
        return self.images_per_sec / self.num_chips

    @property
    def last_images_per_sec(self) -> float:
        """Rate of the most recent measured phase only — per-epoch
        throughput unpolluted by earlier epochs' compile time."""
        return self.last_images / max(self.last_seconds, 1e-9)

    @property
    def last_images_per_sec_per_chip(self) -> float:
        return self.last_images_per_sec / self.num_chips


class StagingLog:
    """Input data-plane observability: where does feeding the chip spend
    its time, and how much of it is hidden behind compute?

    The staging pipeline (``data/staging.py`` for the per-batch modes,
    the scan trainer's epoch prefetch) records one ``record_stage`` per
    staged batch/epoch — host-gather ms (the permutation copy) and H2D
    ms (``make_global_batch``'s sharded ``device_put``), tagged with
    whether it ran on a feeder thread — and the CONSUMER records how
    long it actually blocked waiting for staged data
    (``record_wait``). The difference is the overlap evidence:

    - ``overlap_fraction`` = 1 - blocked_ms / staging_ms: 0 on the
      synchronous path (every staging millisecond stalls the consumer,
      and the inline path records its own wall as wait so the figure is
      honest by construction), approaching 1 when the feeder fully
      hides staging behind compute. Stage walls time the
      ``device_put`` DISPATCH (JAX async dispatch returns before the
      transfer lands); the consumer's wait is what a reader should
      trust (``benchmark/layers/input_wait_share.py`` reads it).

    Thread-safe: the feeder thread records stages while the consumer
    records waits. A process singleton (``staging_log``) follows the
    ``compile_log`` pattern: ``cli.run`` and the benchmark's runners
    attach it per run and reset it at entry.
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.reset()

    def reset(self) -> None:
        with self._lock:
            self._stages = 0
            self._pipelined_stages = 0
            self._host_ms = 0.0
            self._h2d_ms = 0.0
            self._images = 0
            self._waits = 0
            self._wait_ms = 0.0

    def record_stage(self, host_ms: float, h2d_ms: float, images: int,
                     pipelined: bool) -> None:
        """One staged batch (or stacked epoch): host-gather wall, H2D
        wall, the images it carried, and whether a feeder thread (not
        the consumer) ran it."""
        with self._lock:
            self._stages += 1
            if pipelined:
                self._pipelined_stages += 1
            self._host_ms += host_ms
            self._h2d_ms += h2d_ms
            self._images += images

    def record_wait(self, wait_ms: float) -> None:
        """Consumer-side blocked time for one batch handoff."""
        with self._lock:
            self._waits += 1
            self._wait_ms += wait_ms

    def summary(self) -> Dict:
        """Snapshot for cli summaries and the benchmark's runners;
        all-zero (with ``overlap_fraction`` 0.0) when nothing was
        recorded."""
        with self._lock:
            staging_ms = self._host_ms + self._h2d_ms
            overlap = 0.0
            if staging_ms > 0:
                overlap = max(0.0, min(1.0, 1.0 - self._wait_ms / staging_ms))
            return {
                "stages": self._stages,
                "pipelined_stages": self._pipelined_stages,
                "host_ms": round(self._host_ms, 1),
                "h2d_ms": round(self._h2d_ms, 1),
                "consumer_wait_ms": round(self._wait_ms, 1),
                "overlap_fraction": round(overlap, 4),
                "images": self._images,
            }


# Singleton for the same reason as compile_log: one run, one input-plane
# story. cli.run and the benchmark's runners reset() it at entry.
staging_log = StagingLog()


class RoutingLog:
    """What the expert layers counted of their routing, pass by pass.

    A model with top-k expert layers that hold a share of the experts
    (``models/moe.py SparseExperts``) counts, in every layer and step, the
    (token, choice) pairs that landed on an expert held here, the pairs
    routed in all, the pairs dropped (those that named a held expert and
    were not served; the layer drops none) and the fullest held expert's
    tokens over the mean. The
    counts ride out of the pass's program in ``MetricState.routing``
    (``ops/metrics.py ROUTING_COUNTERS``) and the trainer records them
    here when it reads the pass's metrics: no host sync of their own.

    Where the layers choose under a selection bias that the train step
    moves, the step's own counters follow the layers' (``ops/metrics.py
    STEP_COUNTERS``) and the summary gains ``bias_range`` (max - min over
    a layer's experts after a step's update, the largest over the layers;
    the mean over the recorded steps, and ``bias_range_last_pass`` over
    the newest pass's), ``mtp_loss`` (the multi-token-prediction head's
    cross-entropy, the mean over the steps; ``mtp_loss_last_pass``) and
    ``objective`` (what the step's gradients are of: both cross-entropies
    and the sown term under the job's weights; ``objective_last_pass``).
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.reset()

    def reset(self) -> None:
        with self._lock:
            self._sums = None
            self._last = None
            self._passes = 0

    def record(self, counters) -> None:
        """One pass's summed counters: ``(len(ROUTING_COUNTERS),)``, or
        with the step's own after them."""
        with self._lock:
            values = [float(x) for x in counters]
            self._sums = values if self._sums is None else [
                a + b for a, b in zip(self._sums, values)]
            self._last = values
            self._passes += 1

    def summary(self) -> Dict:
        """``{}`` when nothing was recorded (a model without such layers),
        else the sums and the ratios the benchmark reads."""
        from pytorch_distributed_mnist_tpu.ops.metrics import (
            ROUTING_COUNTERS,
            STEP_COUNTERS,
        )

        with self._lock:
            if self._sums is None:
                return {}
            names = ROUTING_COUNTERS + STEP_COUNTERS
            out = dict(zip(names, self._sums))
            out["passes"] = self._passes
            out["local_pair_share"] = out["landed"] / max(out["routed"], 1.0)
            out["load_max_over_mean"] = (
                out["max_over_mean"] / max(out["summands"], 1.0))
            if "steps" in out:
                last = dict(zip(names, self._last))
                for key in ("bias_range", "mtp_loss", "objective"):
                    out[key] = out[key] / max(out["steps"], 1.0)
                    out[f"{key}_last_pass"] = (
                        last[key] / max(last["steps"], 1.0))
            return out


routing_log = RoutingLog()


def device_report() -> dict:
    """What this process runs on and through, for every run summary and
    ``/healthz`` reply, so nothing is read without its device:
    ``platform``/``device_kind``/``device_count`` in jax's own words,
    ``input_backend`` (the host input path in use: ``native`` C++ or
    ``numpy``), ``pallas_lowerings`` (:class:`LoweringLog`),
    ``flash_schedules`` (:class:`ScheduleLog`),
    ``dense_attention_slices`` (:class:`SliceLog`), ``rotary_sites``
    (:class:`RotaryLog`), ``head_gate_sites`` (:class:`HeadGateLog`),
    ``state_scans`` (:class:`ScanLog`) and
    ``expert_routing`` (:class:`RoutingLog`: ``{}`` for a model without
    top-k expert layers)."""
    from pytorch_distributed_mnist_tpu.data import native

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices),
            "input_backend": "native" if native.available() else "numpy",
            "pallas_lowerings": pallas_lowerings.snapshot(),
            "flash_schedules": flash_schedules.snapshot(),
            "dense_attention_slices": dense_attention_slices.snapshot(),
            "rotary_sites": rotary_sites.snapshot(),
            "head_gate_sites": head_gate_sites.snapshot(),
            "state_scans": scan_log.snapshot(),
            "expert_routing": routing_log.summary()}


class LoweringLog:
    """How many ``pallas_call`` sites were traced under each lowering in
    this process — ``mosaic`` (compiled for the TPU) or ``interpret`` (the
    CPU interpreter). Trace-time decisions, not executions
    (``ops/pallas/backend.py`` records them). Run summaries and
    ``/healthz`` carry the snapshot: a chip run must show zero
    interpreted, and a run that selected a kernel must show it lowered."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._counts = {"mosaic": 0, "interpret": 0}

    def record(self, kind: str) -> None:
        with self._lock:
            self._counts[kind] += 1

    def snapshot(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._counts)


# Process-wide for the same reason as compile_log: kernels are traced from
# whatever thread compiles the program that contains them.
pallas_lowerings = LoweringLog()


class ScheduleLog:
    """The tile schedules of the flash attention calls traced in this
    process (``ops/pallas/flash.py`` records ``tile_counts`` once a
    forward and once a backward, beside its ``pallas_lowerings`` entry):
    how many there were, how many of them fold the band's two half-masked
    tiles into one, and over those the pairs the tiles evaluate a pair
    the mask keeps — 1 is a kernel that evaluates no pair in vain. Of the
    backward calls, how many there were and how many run as one kernel,
    which evaluates each tile once for all three gradients. And how often
    a recomputing policy kept a forward's result or its rows' logsumexp
    (``models/decoder.py recomputed``: two a call whose block is
    recomputed, each time such a block is differentiated), so that the
    backward pass does not run the forward kernel again."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._sites = self._folded_sites = 0
        self._needed = self._evaluated = 0
        self._backward_sites = self._fused_backward_sites = 0
        self._kept_results = 0

    def record(self, counts: Dict[str, int],
               backward_kernels: int = 0) -> None:
        """``backward_kernels``: the Pallas calls a backward site makes;
        0 for a forward."""
        with self._lock:
            self._sites += 1
            if counts["folded"]:
                self._folded_sites += 1
                self._needed += counts["needed_pairs"]
                self._evaluated += counts["evaluated_pairs"]
            if backward_kernels:
                self._backward_sites += 1
                self._fused_backward_sites += backward_kernels == 1

    def record_kept(self) -> None:
        with self._lock:
            self._kept_results += 1

    def snapshot(self) -> Dict:
        with self._lock:
            return {
                "sites": self._sites,
                "folded_sites": self._folded_sites,
                "folded_evaluated_over_needed": (
                    round(self._evaluated / self._needed, 4)
                    if self._needed else None),
                "backward_sites": self._backward_sites,
                "fused_backward_sites": self._fused_backward_sites,
                "kept_results": self._kept_results}


flash_schedules = ScheduleLog()


class SliceLog:
    """The dense attention calls traced in this process
    (``ops/attention.py`` records ``slice_count`` once a forward and once a
    backward): how many there were, how many of them work on slices of the
    batch, and the slices a sliced call — ``None`` where none sliced."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._sites = self._sliced_sites = self._slices = 0

    def record(self, slices: int) -> None:
        with self._lock:
            self._sites += 1
            if slices > 1:
                self._sliced_sites += 1
                self._slices += slices

    def snapshot(self) -> Dict:
        with self._lock:
            return {
                "sites": self._sites,
                "sliced_sites": self._sliced_sites,
                "slices_per_sliced_site": (
                    round(self._slices / self._sliced_sites, 2)
                    if self._sliced_sites else None)}


dense_attention_slices = SliceLog()


class RotaryLog:
    """The half-split rotary calls traced in this process
    (``models/decoder.py apply_rope``): how many there were, how many of
    them work on whole heads of 128 lanes (``ops/pallas/rope.py``, which
    records a forward and a backward each; a call on slices of a head
    records its forward, its backward being autodiff's), and the numbers of
    rotated lanes a head (``rot``) seen."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._sites = self._whole_head_sites = 0
        self._rotated_lanes = set()

    def record(self, rot: int, *, whole_head: bool) -> None:
        with self._lock:
            self._sites += 1
            self._whole_head_sites += whole_head
            self._rotated_lanes.add(rot)

    def snapshot(self) -> Dict:
        with self._lock:
            return {"sites": self._sites,
                    "whole_head_sites": self._whole_head_sites,
                    "rotated_lanes": sorted(self._rotated_lanes)}


rotary_sites = RotaryLog()


class HeadGateLog:
    """The output gates a head traced in this process
    (``models/decoder.py gate_heads``, which gates the attention's result
    on its packed ``(B, T, H * D)`` view and records each traced forward
    call): how many there were, and the head widths ``D`` seen."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._sites = 0
        self._head_widths = set()

    def record(self, head_dim: int) -> None:
        with self._lock:
            self._sites += 1
            self._head_widths.add(head_dim)

    def snapshot(self) -> Dict:
        with self._lock:
            return {"sites": self._sites,
                    "head_widths": sorted(self._head_widths)}


head_gate_sites = HeadGateLog()


class ScanLog:
    """The state-space scans traced in this process and who reads what a
    layer publishes for later layers (``models/sambay.py`` records one a
    reading layer). Two recurrences, counted apart: the selective scans
    that step through time (``ops/ssm.py`` records one a call: ``sites``,
    ``chunks_per_site``, ``state_bytes_kept_per_site``) and the chunked
    scans in matrix-product form (``ops/ssd.py``: the same three under
    ``chunked_``). For either: how many there were, the chunks a scan
    walks, and the bytes of state a scan keeps at its chunks' starts for
    the backward (all it keeps of the per-position states). And the layers
    traced that read the published scan output (``memory``) and the
    published keys and values (``kv``)."""

    _KINDS = {"stepped": "", "chunked": "chunked_"}  # kind: its keys' prefix

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.reset()

    def reset(self) -> None:
        with self._lock:
            # kind -> [sites, chunks, state bytes]
            self._scans = {kind: [0, 0, 0] for kind in self._KINDS}
            self._readers = {"memory": 0, "kv": 0}

    def _record(self, kind: str, chunks: int, state_bytes: int) -> None:
        with self._lock:
            totals = self._scans[kind]
            totals[0] += 1
            totals[1] += chunks
            totals[2] += state_bytes

    def record_scan(self, *, chunks: int, state_bytes: int) -> None:
        self._record("stepped", chunks, state_bytes)

    def record_chunked(self, *, chunks: int, state_bytes: int) -> None:
        self._record("chunked", chunks, state_bytes)

    def record_reader(self, what: str) -> None:
        with self._lock:
            self._readers[what] += 1

    def snapshot(self) -> Dict:
        with self._lock:
            out = {}
            for kind, prefix in self._KINDS.items():
                n, chunks, kept = self._scans[kind]
                out[f"{prefix}sites"] = n
                out[f"{prefix}chunks_per_site"] = chunks / n if n else None
                out[f"{prefix}state_bytes_kept_per_site"] = \
                    kept / n if n else None
            return {**out, "memory_readers": self._readers["memory"],
                    "kv_readers": self._readers["kv"]}


scan_log = ScanLog()


@functools.lru_cache(maxsize=None)
def process_started_at() -> float:
    """Unix time at which this process started, from ``/proc`` (its start
    and the machine's uptime are both counted from boot, to 10 ms), as
    ``benchmark/run.py process_started_at`` reckons the origin of
    ``setup_s``; this module's import where ``/proc`` cannot say. Reckoned
    once: every caller gets the same instant."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        age = uptime - ticks / os.sysconf("SC_CLK_TCK")
        if 0 <= age < 24 * 3600:
            return time.time() - age
    except (OSError, ValueError, IndexError):
        pass
    return _IMPORTED_AT


# The jax.monitoring durations CompileLog keeps. Tracing and lowering fire
# for nested calls too (a jitted function called inside another's trace
# reports first, inside the outer one's interval): kept as intervals.
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
# A nesting kind's number is its place in _COMPILE_MS_KEYS, in a thread's
# pair of interval lists and in a function's row.
_NESTING_EVENTS = {_TRACE_EVENT: 0, _LOWER_EVENT: 1}
_COMPILE_MS_KEYS = ("trace_ms", "lower_ms", "cache_load_ms",
                    "backend_compile_ms")


def _zero_counters() -> Dict:
    """What the totals and every program's record count."""
    return {"cache_hits": 0, "cache_misses": 0, "backend_compiles": 0,
            **dict.fromkeys(_COMPILE_MS_KEYS, 0.0)}


class CompileLog:
    """Per-program compile observability: wall ms, seconds of tracing,
    lowering, backend compile and cache load, persistent-cache hit/miss,
    attributed to named programs, and the timeline of the measures
    themselves.

    jax reports compile activity through ``jax.monitoring`` events —
    ``/jax/compilation_cache/cache_hits`` / ``cache_misses`` fire per XLA
    compile request when the persistent cache is enabled, and the
    backend-compile duration event fires for every compile (a
    persistent-cache *hit* still reports there: that is the executable's
    retrieval and deserialization, which ``cache_load_ms`` gives apart, not
    a compile). Listeners run on the thread doing the compiling, so
    attribution is thread-local: whatever program name the current thread
    has open via ``measure(name)`` owns the events — concurrent background
    precompiles (train/trainer.py) can't misfile each other's counts.

    Tracing (Python to a jaxpr) and lowering (jaxpr to MLIR) report a
    duration at their end, so each is the interval ``[now - secs, now]``
    on the listening thread, and **traces nest**: a jitted function called
    inside another's trace (every ``jnp`` primitive is one) fires first,
    inside the outer one's interval. ``trace_ms`` and ``lower_ms`` are
    therefore the length of the *union* of the intervals, folded as they
    come (an arriving parent swallows the children inside it), never the
    sum of the events, which reads several times the wall. A program's
    ``functions`` are the eight names with the most *self* seconds (an
    interval less the children inside it; lowering's ``jit(f)`` is filed
    under ``f``): which function's tracing grew. The kinds are folded
    apart, so a small program that an eager call traces, lowers and
    compiles *while* an outer trace runs counts in each: their sum can pass
    the wall by that much.

    ``stats()["spans"]`` is every opening of ``measure`` since the last
    ``reset()`` with its start, end and the measure it was opened inside
    on its thread, in order of opening (the first :attr:`MAX_SPANS`),
    led by ``startup``: from the process's start to the first measure
    opened after the reset, with the part before :meth:`backend_ready`
    as ``startup:imports_attach`` and the rest as ``startup:build``.
    Inside a ``jax.profiler`` capture each measure is also the host span
    ``compile:<program>`` (:func:`phase`).

    ``cache_misses`` is the honest "programs actually compiled" counter:
    the acceptance bar for a warm start is zero misses, not zero
    backend-duration events.
    """

    MAX_SPANS = 256
    # Finished intervals a thread keeps for a parent that may still arrive;
    # beyond it the older half becomes one block (exact unless a parent
    # starts inside the block, which it then cannot split).
    MAX_OPEN_INTERVALS = 1024
    MAX_FUNCTIONS = 512  # names a program keeps; the rest are "(other)"
    TOP_FUNCTIONS = 8

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._tls = threading.local()
        self._listening = False
        self._backend_ready_unix: Optional[float] = None
        self.reset()

    def reset(self) -> None:
        with self._lock:
            self._programs: Dict[str, Dict] = {}
            self._totals = _zero_counters()
            self._spans: list = []

    # -- jax.monitoring plumbing ------------------------------------------

    def _ensure_listening(self) -> None:
        from jax import monitoring

        # Under the lock: concurrent FIRST measures (the trainer's
        # background precompile threads) must not both register, or every
        # later compile event would be double-counted for the process
        # lifetime.
        with self._lock:
            if self._listening:
                return
            monitoring.register_event_listener(self._on_event)
            monitoring.register_event_duration_secs_listener(
                self._on_duration)
            self._listening = True

    def close(self) -> None:
        """Detach this log from jax.monitoring. The registered listeners
        hold a strong reference to the instance and fire on every future
        compile — fine for the module singleton, a leak for throwaway
        instances (tests), which should close() when done."""
        from jax import monitoring

        with self._lock:
            if not self._listening:
                return
            monitoring.unregister_event_listener(self._on_event)
            monitoring.unregister_event_duration_listener(self._on_duration)
            self._listening = False

    def _open_measure(self) -> tuple:
        """``(record, name)`` of the measure open on this thread."""
        return getattr(self._tls, "measure", (None, None))

    def _on_event(self, name: str, **kwargs) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            key = "cache_hits"
        elif name == "/jax/compilation_cache/cache_misses":
            key = "cache_misses"
        else:
            return
        rec = self._open_measure()[0]
        with self._lock:
            self._totals[key] += 1
            if rec is not None:
                rec[key] += 1

    def _self_seconds(self, kind: int, secs: float) -> float:
        """Fold the interval ``[now - secs, now]`` into this thread's
        finished intervals of ``kind`` and return what it adds to their
        union: its length less the children inside it. Events come in
        order of their ends, so the children are the newest entries."""
        try:
            done = self._tls.done[kind]
        except AttributeError:
            self._tls.done = ([], [])
            done = self._tls.done[kind]
        end = time.time()
        start = end - secs
        inside = 0.0
        while done and done[-1][0] >= start:
            inside += done.pop()[2]
        if done and done[-1][1] > start:
            # A child that began within the clock's jitter of its parent,
            # or a block: counted already, so the parent starts after it.
            start = done[-1][1]
        length = end - start
        done.append((start, end, length))
        if len(done) > self.MAX_OPEN_INTERVALS:
            half = len(done) // 2
            done[:half] = [(done[0][0], done[half - 1][1],
                            sum(d[2] for d in done[:half]))]
        return max(length - inside, 0.0)

    def _on_duration(self, name: str, secs: float, **kwargs) -> None:
        kind = _NESTING_EVENTS.get(name)
        if kind is not None:
            key = _COMPILE_MS_KEYS[kind]
            ms = self._self_seconds(kind, secs) * 1e3
            compiles = 0
        elif name == _BACKEND_EVENT:
            key, ms, compiles = "backend_compile_ms", secs * 1e3, 1
        elif name == _CACHE_LOAD_EVENT:
            key, ms, compiles = "cache_load_ms", secs * 1e3, 0
        else:
            return
        rec = self._open_measure()[0]
        with self._lock:
            self._totals[key] += ms
            self._totals["backend_compiles"] += compiles
            if rec is None:
                return
            rec[key] += ms
            rec["backend_compiles"] += compiles
            if kind is not None:
                functions = rec["functions"]
                fun = kwargs.get("fun_name", "?")
                if kind and fun.startswith("jit(") and fun.endswith(")"):
                    fun = fun[4:-1]  # lowering names f's module "jit(f)"
                row = functions.get(fun)
                if row is None:
                    if len(functions) >= self.MAX_FUNCTIONS:
                        fun = "(other)"
                    row = functions.setdefault(fun, [0.0, 0.0, 0])
                row[kind] += ms
                row[2] += kind == 0

    # -- public API --------------------------------------------------------

    def backend_ready(self) -> None:
        """Stamp, once a process, the instant the backend was up: called
        where a mesh is made (``parallel/mesh.py``), which ``cli.run`` and
        the benchmark's runners both pass after the chip is attached. It
        splits ``startup``; ``reset()`` keeps it, as it keeps the
        process's start."""
        if self._backend_ready_unix is None:
            self._backend_ready_unix = time.time()

    def _startup_spans(self, end: float) -> list:
        start = process_started_at()
        spans = [{"name": "startup", "start_unix": start, "end_unix": end,
                  "parent": None}]
        ready = self._backend_ready_unix
        if ready is not None and start <= ready <= end:
            spans += [
                {"name": "startup:imports_attach", "start_unix": start,
                 "end_unix": ready, "parent": "startup"},
                {"name": "startup:build", "start_unix": ready,
                 "end_unix": end, "parent": "startup"}]
        return spans

    @contextlib.contextmanager
    def measure(self, program: str):
        """Attribute this thread's compile activity to ``program`` while
        the block runs; the record accumulates across repeat measures of
        the same name (e.g. precompile then first call), and each opening
        is one span."""
        self._ensure_listening()
        outer = self._open_measure()
        opened = time.time()
        span = {"name": program, "start_unix": opened, "end_unix": None,
                "parent": outer[1]}
        with self._lock:
            rec = self._programs.get(program)
            if rec is None:
                rec = self._programs[program] = {
                    "wall_ms": 0.0, **_zero_counters(), "functions": {}}
            if not self._spans:
                self._spans = self._startup_spans(opened)
            if len(self._spans) < self.MAX_SPANS:
                self._spans.append(span)
        self._tls.measure = (rec, program)
        t0 = time.perf_counter()
        try:
            with phase(f"compile:{program}"):
                yield rec
        finally:
            dt = (time.perf_counter() - t0) * 1e3
            self._tls.measure = outer
            with self._lock:
                rec["wall_ms"] += dt
                span["end_unix"] = time.time()

    def stats(self) -> Dict:
        """``{"programs": {name: record}, "totals": {...}, "spans": [...]}``
        snapshot.

        Each program record carries ``persistent_cache_hit``: True when
        every XLA compile request inside its measures was served from the
        persistent cache, False when any real compile happened, None when
        the persistent cache was disabled (no hit/miss events at all);
        ``trace_ms`` and ``lower_ms`` (unions), ``cache_load_ms`` (a sum,
        inside ``backend_compile_ms``) and ``functions``, the
        :attr:`TOP_FUNCTIONS` names with the most self time:
        ``[{"name", "trace_ms", "lower_ms", "calls"}]``, ``calls`` being
        the trace events heard. A span still open has ``end_unix`` None."""
        with self._lock:
            programs = {}
            for name, rec in self._programs.items():
                rec = dict(rec)
                for key in ("wall_ms",) + _COMPILE_MS_KEYS:
                    rec[key] = round(rec[key], 1)
                if rec["cache_hits"] or rec["cache_misses"]:
                    rec["persistent_cache_hit"] = rec["cache_misses"] == 0
                else:
                    rec["persistent_cache_hit"] = None
                largest = sorted(rec["functions"].items(),
                                 key=lambda kv: -(kv[1][0] + kv[1][1]))
                rec["functions"] = [
                    {"name": fun, "trace_ms": round(row[0], 1),
                     "lower_ms": round(row[1], 1), "calls": row[2]}
                    for fun, row in largest[:self.TOP_FUNCTIONS]]
                programs[name] = rec
            totals = dict(self._totals)
            spans = [dict(span) for span in self._spans]
        for key in _COMPILE_MS_KEYS:
            totals[key] = round(totals[key], 1)
        return {"programs": programs, "totals": totals, "spans": spans}


# Process-wide singleton: entry points (cli, benchmark, tools) and the trainer's
# background precompile all feed one log, so a run's compile story lands in
# one place. Tests reset() it between cases.
compile_log = CompileLog()


class JsonlSink:
    """Append-only JSONL file shared by every metrics producer.

    One line per record, written atomically under a lock (the async
    checkpoint writer, watchdog timers, the serve batcher worker, and the
    reload watcher all record from their own threads). ``--metrics-file``
    resolves to ONE of these per process, so training epoch rows,
    supervision events, and serving stats land in the same file in the
    same format — a consumer tails one stream whichever mode produced it.
    """

    def __init__(self, path: str) -> None:
        self.path = path
        self._lock = threading.Lock()
        self._warned = False
        parent = os.path.dirname(path)
        if parent:
            os.makedirs(parent, exist_ok=True)

    def write(self, record: Dict) -> None:
        """Append one record; raises on I/O failure (the per-epoch metric
        row keeps its historical fail-loudly contract)."""
        line = json.dumps(record)
        with self._lock:
            with open(self.path, "a") as f:
                f.write(line + "\n")

    def try_write(self, record: Dict) -> bool:
        """Best-effort append for callers on failure/supervision paths:
        a metrics-disk error (ENOSPC/EIO — plausible exactly when the
        run is already failing) must never mask the event being
        reported or break the agreed-exit machinery. Warns once."""
        try:
            self.write(record)
            return True
        except OSError as exc:
            with self._lock:
                first, self._warned = not self._warned, True
            if first:
                import sys

                print(f"WARNING: metrics sink {self.path!r} write failed "
                      f"({exc!r}); further events stay in memory only",
                      file=sys.stderr, flush=True)
            return False


class EventLog:
    """Append-only log of supervision/failure events for the run summary.

    The run-supervision layer (``runtime/supervision.py``) records every
    watchdog trip, poison-pill sent/received, retry, and quarantine here,
    and ``cli.run`` surfaces the snapshot as the summary's
    ``failure_events`` — so "what went wrong, when, in which phase" is one
    JSON block in the same place throughput and compile stats already
    live, instead of a grep through interleaved stderr. Thread-safe:
    watchdog timers and the async checkpoint writer record from their own
    threads.

    With a :class:`JsonlSink` attached (``set_sink``), every event is also
    appended to the sink as it happens — the ``--metrics-file`` stream —
    tagged with ``kind`` and ``source`` so train and serve events are
    distinguishable in the shared file.
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._events = []
        self._sink: Optional[JsonlSink] = None
        self._source = "train"

    def set_sink(self, sink: Optional[JsonlSink],
                 source: str = "train") -> None:
        """Attach (or detach, ``None``) the shared JSONL sink. ``source``
        stamps each mirrored line so a file shared by a trainer and a
        serve process stays attributable."""
        with self._lock:
            self._sink = sink
            self._source = source

    def record(self, kind: str, detail: str, **fields) -> Dict:
        event = {"t": round(time.time(), 3), "kind": kind,
                 "detail": detail, **fields}
        with self._lock:
            self._events.append(event)
            sink, source = self._sink, self._source
        if sink is not None:
            # try_write: record() runs inside poison-pill delivery and
            # watchdog escalation — a sink I/O error must not mask the
            # failure being recorded.
            sink.try_write({**event, "source": source})
        return event

    def snapshot(self) -> list:
        with self._lock:
            return [dict(e) for e in self._events]

    def reset(self) -> None:
        """Clear events (and detach any sink: a re-entrant run must not
        keep appending to the previous run's metrics file)."""
        with self._lock:
            self._events.clear()
            self._sink = None
            self._source = "train"


# Singleton for the same reason as compile_log: one run, one failure story.
# cli.run resets it at entry so re-entrant runs report their own events.
failure_events = EventLog()


def record_world_shrunk(old_members, new_members, generation) -> Dict:
    """Record the elastic runtime's shrink event: this run is the
    rebuilt world after a host loss (``runtime/elastic.py``).

    One structured ``world_shrunk`` failure event carrying the old and
    new membership (stable host ids) and the rebuild generation — so
    the shrink shows up in the run summary's ``failure_events`` block
    AND, through the attached sink, as one line in the shared
    ``--metrics-file`` JSONL next to the epoch rows it explains (epoch
    metrics jump worlds exactly here). Called by
    ``elastic.note_rebuilt_world`` at run start, after ``cli.run``
    resets the log and attaches the sink."""
    old_members, new_members = list(old_members), list(new_members)
    return failure_events.record(
        "world_shrunk",
        f"world shrank from {len(old_members)} to {len(new_members)} "
        f"host(s) at generation {int(generation)}: members "
        f"{old_members} -> {new_members}; resumed from the last "
        f"published checkpoint",
        old_members=old_members, new_members=new_members,
        generation=int(generation))


def record_world_grown(old_members, new_members, generation) -> Dict:
    """The grow mirror of :func:`record_world_shrunk`: this run is the
    rebuilt world after a join rendezvous admitted a returned or
    replacement host (``runtime/elastic.py`` grow path). Same shape,
    distinct ``world_grown`` kind, so the metrics JSONL tells the two
    topology directions apart at a glance."""
    old_members, new_members = list(old_members), list(new_members)
    return failure_events.record(
        "world_grown",
        f"world grew from {len(old_members)} to {len(new_members)} "
        f"host(s) at generation {int(generation)}: members "
        f"{old_members} -> {new_members}; resumed from the last "
        f"published checkpoint (cross-world reshard onto the larger "
        f"world)",
        old_members=old_members, new_members=new_members,
        generation=int(generation))


def record_fleet_event(sink, kind: str, **fields) -> None:
    """Fleet-router lifecycle line (``fleet_quarantine`` /
    ``fleet_failover`` / ``fleet_rollout_*`` / ``fleet_canary_*`` /
    ``fleet_scale_*``) into a :class:`JsonlSink`.

    The sibling of :meth:`ServeLog.record_pool_event` one level up, but
    a free function taking the sink explicitly: the router
    (``serve/router.py``) is deliberately pure-stdlib and owns no
    ServeLog — it imports this lazily, only when ``--metrics-file``
    gave it a sink, so a router that never logs never touches the jax
    import chain. ``source: "router"`` keys the fleet tier's lines
    apart from the per-backend ``serve_*`` events riding the same
    stream."""
    if sink is None:
        return
    sink.try_write({"t": round(time.time(), 3), "kind": kind,
                    "source": "router", **fields})


def _percentile(sorted_vals: list, q: float) -> float:
    """Nearest-rank percentile over an already-sorted list (0 when empty).
    Nearest-rank (not interpolated) so p99 of a small sample is a latency
    that actually happened, never an optimistic blend."""
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1, max(0, int(round(q * (len(sorted_vals) - 1)))))
    return float(sorted_vals[idx])


class ServeLog:
    """Serving observability: latency quantiles, batch-size histogram,
    queue depth, admission-control rejections, and hot reloads.

    The serve-side sibling of :class:`EventLog` + :class:`StepTimer`: the
    batcher worker records per-request latency, the engine records each
    executed bucket, the HTTP layer records rejections, and the reload
    watcher records checkpoint swaps — ``snapshot()`` is the ``/stats``
    payload. Thread-safe throughout (requests complete on the batcher
    worker thread while ``/stats`` reads from HTTP handler threads).

    Latency samples live in a bounded deque (recent-window quantiles, no
    unbounded growth under sustained load). With a :class:`JsonlSink`
    attached, ``write_stats()`` appends a ``{"kind": "serve_stats", ...}``
    snapshot line — the same ``--metrics-file`` stream training writes its
    epoch rows and failure events to.

    Two schema-ADDITIVE planes ride the same log:

    - a **rolling window** (``window_s``, default 60s): every snapshot
      carries a ``window`` block — p50/p95/p99 and requests/sec over
      the last ``window_s`` seconds ONLY — because the lifetime
      quantiles the block sits next to converge to history and cannot
      see current load (the autoscaler and an operator mid-incident
      both need "now", not "since boot"). ``window_stats()`` is the
      cheap probe the autoscaler samples.
    - **per-class counters** (priority serving): requests recorded with
      a ``klass`` land per-class latency quantiles, shed (503) and
      quota (429) counts in a ``classes`` block — present only when a
      class was ever recorded, so the single-class schema is unchanged.
    """

    #: Rolling-window sample bounds: latency samples and request
    #: timestamps kept for the window quantiles/rps. At 60s these cap
    #: the honest window at ~1k rps sustained — beyond that the window
    #: rps undercounts (documented, bounded memory wins).
    WINDOW_SAMPLES = 8192
    WINDOW_TIMES = 65536

    def __init__(self, max_samples: int = 8192,
                 window_s: float = 60.0) -> None:
        self._lock = threading.Lock()
        self._max_samples = max_samples
        self.window_s = float(window_s)
        self._now = time.monotonic  # overridable clock (tests)
        self._sink: Optional[JsonlSink] = None
        self._source = "serve"
        self._queue_depth_probe: Optional[Callable[[], int]] = None
        self._replicas_probe: Optional[Callable[[], Dict]] = None
        self.reset()

    def reset(self) -> None:
        with self._lock:
            self._latency = collections.deque(maxlen=self._max_samples)
            self._queue_wait = collections.deque(maxlen=self._max_samples)
            self._batch_hist: Dict[int, int] = {}
            self._counts = {"requests": 0, "images": 0, "batches": 0,
                            "rejected": 0, "reloads": 0,
                            "reload_failures": 0}
            # Rolling window: (t, latency_s) samples + bare timestamps
            # (for rps), pruned past window_s at record/snapshot time.
            self._win = collections.deque(maxlen=self.WINDOW_SAMPLES)
            self._win_times = collections.deque(maxlen=self.WINDOW_TIMES)
            self._t_reset = self._now()
            # Per-priority-class accounting (priority serving only):
            # stays empty — and out of the snapshot — when no request
            # ever carried a class.
            self._classes: Dict[str, Dict] = {}
            # Per-replica execution counters (multi-chip pool only): the
            # single-engine data plane records with replica=None and this
            # stays empty, keeping its snapshot/JSONL schema unchanged.
            self._replica_counts: Dict[str, Dict] = {}

    def set_sink(self, sink: Optional[JsonlSink],
                 source: str = "serve") -> None:
        with self._lock:
            self._sink = sink
            self._source = source

    def set_queue_depth_probe(self, probe: Optional[Callable[[], int]]) -> None:
        """Register a live queue-depth callable (the batcher's); read at
        snapshot time so ``/stats`` shows the instantaneous depth."""
        with self._lock:
            self._queue_depth_probe = probe

    def set_replicas_probe(self, probe: Optional[Callable[[], Dict]]) -> None:
        """Register the pool's per-replica snapshot callable (device,
        serving epoch, in-flight count per replica); merged into this
        log's per-replica batch counters at snapshot time so ``/stats``
        and the JSONL ``serve_stats`` lines carry one row per replica."""
        with self._lock:
            self._replicas_probe = probe

    # -- recorders (each from its owning thread) --------------------------

    def _class_rec(self, klass: str) -> Dict:
        """Per-class record (caller holds the lock)."""
        rec = self._classes.get(klass)
        if rec is None:
            rec = self._classes[klass] = {
                "requests": 0, "images": 0, "shed": 0,
                "quota_rejected": 0,
                "latency": collections.deque(maxlen=4096),
            }
        return rec

    def record_request(self, latency_s: float, queue_wait_s: float = 0.0,
                       images: int = 1,
                       klass: Optional[str] = None) -> None:
        now = self._now()
        with self._lock:
            self._counts["requests"] += 1
            self._counts["images"] += images
            self._latency.append(latency_s)
            self._queue_wait.append(queue_wait_s)
            self._win.append((now, latency_s))
            self._win_times.append(now)
            if klass is not None:
                rec = self._class_rec(klass)
                rec["requests"] += 1
                rec["images"] += images
                rec["latency"].append(latency_s)

    def _prune_window(self, now: float) -> None:
        """Drop window samples older than ``window_s`` (lock held)."""
        cutoff = now - self.window_s
        while self._win and self._win[0][0] < cutoff:
            self._win.popleft()
        while self._win_times and self._win_times[0] < cutoff:
            self._win_times.popleft()

    def window_stats(self) -> Dict:
        """The rolling-window block: latency quantiles + rps over the
        last ``window_s`` seconds only. Cheap enough to sample on the
        autoscaler's interval; also merged into every ``snapshot()``."""
        now = self._now()
        with self._lock:
            self._prune_window(now)
            lat = [s for _, s in self._win]
            n_requests = len(self._win_times)
            t_reset = self._t_reset
            probe = self._queue_depth_probe
        # The honest span: the full window once one has elapsed, the
        # log's lifetime before that (a fresh boot's rps must neither
        # be diluted over a window it hasn't lived nor inflated over
        # the microseconds since its first request), floored at 1s.
        span = max(1.0, min(self.window_s, now - t_reset))
        stats = self._quantiles(lat)
        depth = 0
        if probe is not None:
            try:
                depth = int(probe())
            except Exception:  # noqa: BLE001 - stats must never raise
                depth = -1
        return {
            "seconds": self.window_s,
            "rps": round(n_requests / span, 2),
            "queue_depth": depth,
            "p50_ms": stats["p50"], "p95_ms": stats["p95"],
            "p99_ms": stats["p99"], "count": stats["count"],
        }

    def record_batch(self, rows: int, bucket: int,
                     replica: Optional[str] = None) -> None:
        """One executed forward program: ``rows`` real examples padded up
        to ``bucket``, on ``replica`` (None = the single-engine plane)."""
        with self._lock:
            self._counts["batches"] += 1
            self._batch_hist[bucket] = self._batch_hist.get(bucket, 0) + 1
            if replica is not None:
                rec = self._replica_counts.setdefault(
                    replica, {"batches": 0, "images": 0,
                              "batch_histogram": {}})
                rec["batches"] += 1
                rec["images"] += rows
                hist = rec["batch_histogram"]
                hist[bucket] = hist.get(bucket, 0) + 1

    def record_rejection(self, klass: Optional[str] = None,
                         quota: bool = False) -> None:
        """One shed (503) or — with ``quota=True`` — one per-client
        quota refusal (429). Quota refusals never touch the lifetime
        ``rejected`` counter: they are the CLIENT's overload, not the
        server's, and conflating them would make the admission-control
        history unreadable."""
        with self._lock:
            if not quota:
                self._counts["rejected"] += 1
            if klass is not None:
                rec = self._class_rec(klass)
                rec["quota_rejected" if quota else "shed"] += 1

    def record_reload(self, path: str, epoch: int) -> None:
        with self._lock:
            self._counts["reloads"] += 1
            sink, source = self._sink, self._source
        if sink is not None:
            sink.try_write({"t": round(time.time(), 3),
                            "kind": "serve_reload", "path": path,
                            "epoch": epoch, "source": source})

    def record_reload_failure(self, path: str, detail: str) -> None:
        with self._lock:
            self._counts["reload_failures"] += 1
            sink, source = self._sink, self._source
        if sink is not None:
            sink.try_write({"t": round(time.time(), 3),
                            "kind": "serve_reload_failed", "path": path,
                            "detail": detail, "source": source})

    def record_pool_event(self, kind: str, **fields) -> None:
        """Sink-only serve lifecycle line (``serve_quarantine`` /
        ``serve_regroup`` / ``serve_resize``, and the shadow canary's
        ``serve_canary`` promote/rollback/reset transitions): the
        counters live in the pool's ``topology()`` / the canary's
        ``snapshot()`` blocks (surfaced via ``/stats``), so the
        single-engine snapshot schema stays untouched — this just lands
        the event in the shared ``--metrics-file`` stream next to the
        reloads it rides with."""
        with self._lock:
            sink, source = self._sink, self._source
        if sink is not None:
            sink.try_write({"t": round(time.time(), 3), "kind": kind,
                            "source": source, **fields})

    # -- consumers --------------------------------------------------------

    @staticmethod
    def _quantiles(samples) -> Dict[str, float]:
        vals = sorted(samples)
        ms = lambda s: round(s * 1e3, 3)  # noqa: E731
        return {
            "p50": ms(_percentile(vals, 0.50)),
            "p95": ms(_percentile(vals, 0.95)),
            "p99": ms(_percentile(vals, 0.99)),
            "mean": ms(sum(vals) / len(vals)) if vals else 0.0,
            "max": ms(vals[-1]) if vals else 0.0,
            "count": len(vals),
        }

    def snapshot(self) -> Dict:
        with self._lock:
            counts = dict(self._counts)
            latency = list(self._latency)
            queue_wait = list(self._queue_wait)
            hist = {str(k): v for k, v in sorted(self._batch_hist.items())}
            probe = self._queue_depth_probe
            replicas_probe = self._replicas_probe
            classes = {
                klass: {
                    "requests": rec["requests"],
                    "images": rec["images"],
                    "shed": rec["shed"],
                    "quota_rejected": rec["quota_rejected"],
                    "latency_ms": self._quantiles(list(rec["latency"])),
                }
                for klass, rec in sorted(self._classes.items())
            }
            replicas = {name: {**rec,
                               "batch_histogram": {
                                   str(k): v for k, v in
                                   sorted(rec["batch_histogram"].items())}}
                        for name, rec in self._replica_counts.items()}
        depth = 0
        if probe is not None:
            try:
                depth = int(probe())
            except Exception:  # noqa: BLE001 - stats must never raise
                depth = -1
        if replicas_probe is not None:
            try:
                for name, row in replicas_probe().items():
                    replicas.setdefault(
                        name, {"batches": 0, "images": 0,
                               "batch_histogram": {}}).update(row)
            except Exception:  # noqa: BLE001 - stats must never raise
                pass
        snap = {
            **counts,
            "queue_depth": depth,
            "latency_ms": self._quantiles(latency),
            "queue_wait_ms": self._quantiles(queue_wait),
            "batch_histogram": hist,
            # Rolling-window block (schema-ADDITIVE next to the
            # lifetime quantiles): what the load looks like NOW.
            "window": self.window_stats(),
        }
        # Per-priority-class rows appear only once a request carried a
        # class (priority serving) — classless servers' schema is
        # unchanged beyond the window block.
        if classes:
            snap["classes"] = classes
        # Per-replica rows appear only on the pooled data plane — the
        # single-engine snapshot/JSONL schema is unchanged.
        if replicas:
            snap["replicas"] = {k: replicas[k] for k in sorted(replicas)}
        return snap

    def write_stats(self, **extra) -> Dict:
        """Snapshot + append it to the attached sink (no-op without one);
        returns the snapshot either way."""
        snap = self.snapshot()
        with self._lock:
            sink, source = self._sink, self._source
        if sink is not None:
            sink.try_write({"t": round(time.time(), 3),
                            "kind": "serve_stats", "source": source,
                            **snap, **extra})
        return snap


@contextlib.contextmanager
def profile_trace(logdir: Optional[str]):
    """Capture a jax.profiler trace to ``logdir`` when set; no-op otherwise."""
    if not logdir:
        yield
        return
    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def phase(name: str, **kwargs):
    """Named trace span (``jax.profiler.TraceAnnotation``) for one lifecycle
    phase — train/eval/checkpoint per epoch. Zero-cost when no trace is
    being captured; inside a ``--profile-dir`` capture the spans label the
    host timeline so the train/eval/checkpoint split is readable in
    xprof/perfetto instead of one undifferentiated epoch blob.

    The names in use. ``cli.run``, per epoch: ``train``, ``eval``,
    ``checkpoint``, ``checkpoint_drain``. ``Trainer.train()`` in scan mode,
    per pass, on the calling thread: ``trainer:input_wait`` (join of the
    prefetch thread and whatever staging is left to do inline),
    ``trainer:dispatch`` (the call of the epoch program, which returns
    before the device is done), ``trainer:read_metrics`` (the host read
    that ends the pass); on the prefetch thread, for the next pass:
    ``trainer:stack_epoch`` (host gather) and ``trainer:h2d`` (sharded
    ``device_put``). ``benchmark/scopes.py`` puts each idle gap of the
    device under the innermost ``trainer:`` span that covers it."""
    return jax.profiler.TraceAnnotation(name, **kwargs)
