"""The ``serve`` CLI subcommand: a stdlib HTTP JSON inference endpoint.

``python -m pytorch_distributed_mnist_tpu serve --checkpoint-dir ckpt
--model cnn`` boots: model + template state, newest published checkpoint
(or fresh init with a loud warning), the bucketed
:class:`~pytorch_distributed_mnist_tpu.serve.engine.InferenceEngine`
(all buckets AOT-compiled before the socket opens — a request can never
pay a compile), the
:class:`~pytorch_distributed_mnist_tpu.serve.batcher.MicroBatcher`, and
the :class:`~pytorch_distributed_mnist_tpu.serve.reload.CheckpointWatcher`
sharing the training run's checkpoint directory. With
``--serve-devices N`` (0 = all local devices) the engine becomes an
:class:`~pytorch_distributed_mnist_tpu.serve.pool.EnginePool` — one
replica per chip behind a least-loaded dispatcher — and the batcher
pipelines up to ``--max-inflight`` batches (default replicas+1) between
its form/dispatch and completion stages.

Endpoints (stdlib ``http.server``; one handler thread per connection,
all of them funneling into the batcher's dispatch worker that owns
device submission):

- ``POST /predict`` — body ``{"images": ...}``: one 28x28 image or a
  list of them, raw 0-255 pixel values. Replies
  ``{"predictions": [...], "model_epoch": e, "latency_ms": t}``;
  503 ``{"error": "overloaded"}`` under admission control.
- ``GET /healthz`` — liveness + which checkpoint epoch is serving.
- ``GET /stats`` — the ServeLog snapshot: p50/p95/p99 latency, queue
  depth/waits, batch-size histogram, reload + rejection counters, and
  the serve programs' compile stats (the zero-recompile evidence);
  pooled servers add the topology block (``topology_generation``,
  ``groups``/``active_groups``, ``quarantined_groups``, ``regroups``,
  ``failovers``) the self-healing pool maintains.
- ``POST /resize`` — the admin topology dial (pooled servers):
  ``{"serve_devices": N?, "serve_mesh": M?}`` re-shapes the pool under
  live traffic with zero dropped requests (``serve/pool.py::resize``).
- ``POST /drain`` — the fleet primitive: ``{"drain": true|false}``
  closes/reopens /predict admission (503 + Retry-After) while in-flight
  requests complete; ``/healthz`` and ``/stats`` expose ``draining`` so
  a router's rolling reload (``serve/router.py``) can publish against a
  quiescent backend and rejoin it afterwards.

The deliberately boring transport (no asyncio, no framework dep) is the
point: the serving smarts live in engine/batcher/reload, which are all
driveable in-process by tests.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional

import numpy as np

from pytorch_distributed_mnist_tpu.serve.batcher import MicroBatcher, Overloaded
from pytorch_distributed_mnist_tpu.serve.control import (
    PRIORITY_CLASSES,
    AutoScaler,
    ClientQuotas,
    ShedPolicy,
    WeightedFairGate,
    parse_quota_spec,
    parse_weight_spec,
    priority_rank,
)
from pytorch_distributed_mnist_tpu.serve.engine import (
    DEFAULT_BUCKETS,
    InferenceEngine,
    load_params_for_serving,
)
from pytorch_distributed_mnist_tpu.serve.canary import (
    SHADOW as CANARY_SHADOW,
)
from pytorch_distributed_mnist_tpu.serve.canary import ShadowCanary
from pytorch_distributed_mnist_tpu.serve.economics import (
    HIT_COST,
    CostModel,
    ResponseCache,
    request_key,
)
from pytorch_distributed_mnist_tpu.serve.programs import (
    precision_engine_name,
    serve_modes,
    serve_precisions,
)
from pytorch_distributed_mnist_tpu.serve.reload import CheckpointWatcher
from pytorch_distributed_mnist_tpu.utils.profiling import (
    JsonlSink,
    ServeLog,
    compile_log,
    device_report,
)


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="tpu-mnist serve",
        description="JSON inference endpoint over a training run's "
                    "checkpoint directory",
        allow_abbrev=False,
    )
    p.add_argument("--checkpoint-dir", type=str, default="checkpoints",
                   help="directory the training run publishes checkpoints "
                        "into; the newest is served and newer ones are "
                        "hot-reloaded as they appear")
    p.add_argument("--model", type=str, default="cnn",
                   help="model architecture the checkpoints belong to "
                        "(must match training's --model; a mismatched "
                        "checkpoint is rejected at load, not served)")
    p.add_argument("--model-set", type=str, default=None,
                   metavar="NAME=DIR[,NAME=DIR...]",
                   help="multi-model serving: boot one full engine-set "
                        "(engine/pool + batcher + watcher + canary + "
                        "layout gate) per MODEL=CHECKPOINT_DIR pair from "
                        "ONE process sharing the chip budget; requests "
                        "route on their 'model' field. Overrides "
                        "--model/--checkpoint-dir; every other serving "
                        "flag applies to each model's plane")
    p.add_argument("--model-weights", type=str, default=None,
                   metavar="NAME=W[,NAME=W...]",
                   help="multi-model weighted-fair dispatch: when more "
                        "than one model has queued work, device dispatch "
                        "grants interleave in this weight proportion "
                        "(unnamed models weigh 1.0) — one model's "
                        "backlog cannot starve another's. Requires "
                        "--model-set")
    p.add_argument("--dtype", type=str, default=None, choices=["bf16", "f32"],
                   help="compute dtype override, same semantics as "
                        "training's --dtype")
    p.add_argument("--host", type=str, default="127.0.0.1")
    p.add_argument("--port", type=int, default=8000,
                   help="0 picks a free port (printed at startup)")
    p.add_argument("--buckets", type=str,
                   default=",".join(str(b) for b in DEFAULT_BUCKETS),
                   help="comma-separated batch buckets, each AOT-compiled "
                        "at startup; batches pad up to the nearest bucket "
                        "so steady-state serving never recompiles")
    p.add_argument("--serve-devices", type=int, default=1,
                   help="chips the data plane spans (0 = every local "
                        "device). Replicated mode: one engine replica per "
                        "device behind the least-loaded dispatcher. "
                        "Sharded modes: the chips partition into "
                        "--serve-mesh-sized groups. Default 1 is the "
                        "single-device data plane")
    # choices read the LIVE registry at parser-build time, so a mode
    # added through register_serve_mode (the documented extension seam)
    # is accepted without editing this file.
    p.add_argument("--serve-mode", type=str, default="replicated",
                   choices=serve_modes(),
                   help="how one forward spans chips: 'replicated' runs "
                        "the whole model per chip (default, every model); "
                        "'tensor' Megatron-shards the ViT weights over a "
                        "mesh (parallel/tensor.py rules); 'expert' shards "
                        "moe_mlp experts (parallel/expert.py); 'pipeline' "
                        "compiles one INDEPENDENT program per stage chip "
                        "and streams batches between them (MPMD, "
                        "serve/pipeline.py — the mode pipeline-trained "
                        "checkpoints serve under). All share the "
                        "AOT/zero-recompile/hot-reload contract")
    p.add_argument("--serve-mesh", type=int, default=0,
                   help="devices per serving mesh group for sharded "
                        "modes — for --serve-mode pipeline, the STAGE "
                        "count per chain — (0 = all --serve-devices "
                        "chips in ONE group). Must divide "
                        "--serve-devices; the pool then runs one "
                        "spanning engine per group. Ignored (must be "
                        "left 0) in replicated mode")
    # choices read the LIVE precision registry (register_precision is
    # the documented extension seam, mirroring --serve-mode's).
    p.add_argument("--serve-precision", type=str, default="f32",
                   choices=serve_precisions(),
                   help="numeric precision of the serving programs "
                        "(serve/programs.py precision plane): 'f32' is "
                        "the full-precision default; 'bf16' stores "
                        "weights bfloat16 (compute follows the model's "
                        "--dtype policy); 'int8w' "
                        "quantizes weights to int8 (per-leaf symmetric "
                        "scales, dequantized on-chip, f32 compute); "
                        "'int8' additionally stages activations as "
                        "int8 (a quarter of the H2D bytes). "
                        "Quantization happens at param-install time, "
                        "so hot reload stays an atomic swap. Composes "
                        "with every --serve-mode")
    p.add_argument("--no-fuse", action="store_true",
                   help="disable whole-program dispatch and serve every "
                        "request on the SPLIT plane (host-side "
                        "normalize/quantize/pad, float staging) — the "
                        "bitwise reference the fused plane is pinned "
                        "against. Default: fused ON — raw uint8 "
                        "requests run ONE compiled program per bucket "
                        "(normalize + quantization inside XLA, staging "
                        "buffer donated), collapsing host work to a "
                        "bytes-copy. Use --no-fuse for batch-coupled "
                        "models whose pad-row semantics must match the "
                        "host plane exactly (DESIGN.md §7k)")
    p.add_argument("--canary-fraction", type=float, default=0.0,
                   help="shadow-traffic accuracy canary: serve replies "
                        "from the f32 BASELINE while this fraction of "
                        "live batches also runs the --serve-precision "
                        "plane in shadow; argmax disagreements and "
                        "logit deltas accumulate in /stats, the "
                        "precision PROMOTES to primary after "
                        "--canary-promote-after clean rows and AUTO-"
                        "ROLLS-BACK (permanent for that publish; the "
                        "server keeps serving) past --canary-budget. "
                        "0 (default) trusts --serve-precision outright "
                        "and serves it directly; requires a quantized "
                        "--serve-precision when set")
    p.add_argument("--canary-promote-after", type=int, default=200,
                   help="canary: shadowed rows (images) that must "
                        "compare within budget before the quantized "
                        "plane is promoted to primary")
    p.add_argument("--canary-budget", type=float, default=0.02,
                   help="canary: allowed argmax-disagreement fraction "
                        "of the promotion window (budget x promote-"
                        "after rows; shadow-plane errors count); "
                        "exceeding it rolls the publish back")
    p.add_argument("--quarantine-after", type=int, default=3,
                   help="serve-pool self-healing threshold: this many "
                        "CONSECUTIVE dispatch/completion failures on one "
                        "replica/mesh group (any success resets the "
                        "count) quarantine it — dispatch skips it, "
                        "in-flight batches fail over to healthy groups, "
                        "and a background regroup rebuilds it from its "
                        "chips under live traffic. Pooled data plane "
                        "only; input-shaped (4xx) errors never count")
    p.add_argument("--max-inflight", type=int, default=0,
                   help="pipelined dispatch window: batches dispatched "
                        "but not yet completed (0 = auto: replicas+1 on "
                        "a multi-replica pool, 1 otherwise; 1 disables "
                        "pipelining — batch N+1's host-side staging then "
                        "serializes behind batch N's result fetch)")
    p.add_argument("--max-wait-ms", type=float, default=5.0,
                   help="micro-batcher deadline: a request waits at most "
                        "this long for co-riders before its batch flushes")
    p.add_argument("--max-queue", type=int, default=256,
                   help="admission control: pending requests beyond this "
                        "are rejected with 503 instead of queuing "
                        "unboundedly")
    p.add_argument("--shed-watermarks", type=str, default=None,
                   metavar="CLASS=FRAC[,...]",
                   help="priority shedding: per-class admission "
                        "watermarks as fractions of --max-queue — a "
                        "class is shed (503 + Retry-After) once the "
                        "queue is past its fraction. Defaults "
                        "best_effort=0.5, batch=0.75, interactive=1.0: "
                        "best_effort sheds first, interactive keeps the "
                        "full queue (exactly the classic admission "
                        "bound). The queue itself is priority-ORDERED: "
                        "interactive requests overtake queued batch/"
                        "best_effort ones")
    p.add_argument("--quota-rps", type=str, default=None,
                   metavar="RPS[,CLASS=RPS...]",
                   help="per-client token-bucket quotas: each client_id "
                        "(anonymous requests share one bucket) may "
                        "submit this many requests/sec per priority "
                        "class, with a 2s burst; an over-quota request "
                        "is rejected 429 + Retry-After BEFORE it "
                        "consumes a queue slot, so one hot client "
                        "cannot starve the rest. A bare number bounds "
                        "every class; CLASS=RPS overrides per class "
                        "(e.g. '100,interactive=20'); unset = no quotas")
    p.add_argument("--quota-burst-s", type=float, default=2.0,
                   help="quota burst allowance in seconds of the class "
                        "rate (bucket capacity = rps x this)")
    p.add_argument("--stats-window-s", type=float, default=60.0,
                   help="rolling-window size for /stats' `window` block "
                        "(p50/p95/p99 + rps over the last N seconds "
                        "only, next to the lifetime quantiles) — what "
                        "the autoscaler and an operator mid-incident "
                        "react to")
    p.add_argument("--autoscale", action="store_true",
                   help="SLO-driven autoscaling: a background controller "
                        "samples the rolling-window p95 and queue depth "
                        "and actuates the pool's /resize path — scale up "
                        "on an SLO breach (--slo-p95-ms, or the queue "
                        "high watermark), scale down after sustained "
                        "calm; hysteresis + cooldown prevent flapping; "
                        "every decision is a serve_autoscale JSONL "
                        "event. Needs the pooled data plane "
                        "(--serve-devices/--max-inflight) and is "
                        "incompatible with an active canary (the two "
                        "planes' topology must not diverge)")
    p.add_argument("--autoscale-dry-run", action="store_true",
                   help="autoscaler twin mode: record every scale "
                        "decision (JSONL + /stats) without actuating "
                        "the resize")
    p.add_argument("--slo-p95-ms", type=float, default=100.0,
                   help="the serving SLO the autoscaler defends: "
                        "rolling-window p95 latency above this is a "
                        "breach (scale up); sustained p95 below half of "
                        "it with an empty-ish queue scales down")
    p.add_argument("--autoscale-queue-high", type=float, default=0.75,
                   help="autoscaler queue-depth high watermark as a "
                        "fraction of --max-queue: depth at/above it is "
                        "a breach even while p95 holds (latency "
                        "quantiles lag; queue depth leads)")
    p.add_argument("--autoscale-interval-s", type=float, default=2.0,
                   help="seconds between autoscaler samples")
    p.add_argument("--autoscale-cooldown-s", type=float, default=10.0,
                   help="seconds after any scale action before the next "
                        "may fire (a resize builds + AOT-warms a whole "
                        "layout; back-to-back resizes would spend the "
                        "capacity they add)")
    p.add_argument("--autoscale-down-after", type=int, default=3,
                   help="consecutive calm samples required before a "
                        "scale-down (with the halved p95 bar, the "
                        "hysteresis that prevents flapping)")
    p.add_argument("--autoscale-min-devices", type=int, default=1,
                   help="autoscaler floor: never scale below this many "
                        "devices")
    p.add_argument("--autoscale-max-devices", type=int, default=0,
                   help="autoscaler ceiling (0 = all local devices)")
    p.add_argument("--cache-mb", type=float, default=64.0,
                   help="response-cache byte budget in MB (bounded LRU): "
                        "an exact-byte repeat of a served request — same "
                        "raw body, model, serve mode and precision — "
                        "answers from the cache without touching the "
                        "batcher or a chip. Entries are stamped with a "
                        "generation counter bumped atomically under the "
                        "param-swap lock, so a hot reload / precision "
                        "swap / canary promote invalidates the whole "
                        "cache in O(1) — a stale logit can never be "
                        "served. 0 disables (same as --no-cache)")
    p.add_argument("--no-cache", action="store_true",
                   help="disable the response cache (and in-flight "
                        "request collapsing keeps working — identical "
                        "concurrent requests still share one compute). "
                        "Replies are byte-identical to the cached path; "
                        "only the X-Cache header and the /stats cache "
                        "block disappear")
    p.add_argument("--price-admission", action="store_true",
                   help="cost-priced admission: each request is priced "
                        "in measured step-cost units (per-bucket geometry "
                        "seed refreshed by an online EWMA at serve "
                        "time) instead of counting 1 per request — "
                        "queue watermarks, per-client quotas and "
                        "Retry-After all account in cost units, and a "
                        "cache hit prices at ~0. Default off: every "
                        "request costs 1.0, byte-identical to the "
                        "classic count-based admission")
    p.add_argument("--max-request-images", type=int, default=1024,
                   help="reject /predict requests with more images than "
                        "this (400): one giant request occupies a single "
                        "queue slot, so without a bound it could "
                        "monopolize the batcher past admission control — "
                        "batch client-side instead")
    p.add_argument("--poll-interval", type=float, default=2.0,
                   help="seconds between checkpoint-directory polls for "
                        "hot reload")
    p.add_argument("--no-reload", action="store_true",
                   help="serve the boot-time checkpoint forever (no "
                        "directory watching)")
    p.add_argument("--chunk-peers", type=str, default=None, metavar="URLS",
                   help="comma-separated peer backend base URLs "
                        "(http://host:port) to gossip checkpoint chunks "
                        "from: a delta-published manifest's missing "
                        "chunks are pulled from peers' GET /chunks/<hash> "
                        "before the --chunk-source fallback, so a fleet "
                        "publish costs the source O(chunks), not "
                        "O(replicas)")
    p.add_argument("--chunk-source", type=str, default=None, metavar="DIR",
                   help="source chunk-store directory (the trainer's "
                        "--checkpoint-dir) to fall back to when no peer "
                        "holds a chunk; defaults to the watch directory "
                        "itself, which a shared filesystem already covers")
    p.add_argument("--register-dir", type=str, default=None, metavar="DIR",
                   help="fleet registration directory: write a backend "
                        "record (tmp+rename JSON naming this server's "
                        "URL) on boot, remove it while draining and on "
                        "shutdown — a router's --backends-dir polls it "
                        "for dynamic join/leave without a restart")
    p.add_argument("--require-checkpoint", action="store_true",
                   help="refuse to start without a published checkpoint "
                        "(default: warn and serve fresh-init params, "
                        "hot-reloading the first checkpoint when it "
                        "appears)")
    p.add_argument("--compile-cache", type=str, default=None, metavar="DIR",
                   help="persistent XLA compile cache (same resolution as "
                        "training: JAX_COMPILATION_CACHE_DIR when set, "
                        "else this flag, else <checkout>/.xla_cache; '' "
                        "disables) — a warm cache turns the startup "
                        "bucket compiles into fetches")
    p.add_argument("--metrics-file", type=str, default=None,
                   help="append serve_stats / serve_reload JSONL lines "
                        "here — the same format/flag as training, so one "
                        "file can carry both sides of a shared run")
    p.add_argument("--stats-interval", type=float, default=30.0,
                   help="seconds between serve_stats lines to "
                        "--metrics-file (0 disables periodic writes)")
    p.add_argument("--seed", type=int, default=0,
                   help="fresh-init param seed when no checkpoint exists")
    p.add_argument("-j", "--workers", type=int, default=4,
                   help="host-side preprocessing threads per engine "
                        "(same flag as training's data loaders): "
                        "normalize, f64->f32 cast, and the pad-into-"
                        "staging copy run in multithreaded C++ when the "
                        "native library is built; no-op on the NumPy "
                        "fallback. Default 4")
    return p


# One oversized body must not buy unbounded JSON parsing on a handler
# thread; 16 MB comfortably fits --max-request-images' worth of pixels.
MAX_BODY_BYTES = 16 << 20


def _estimate_rows(images) -> int:
    """Cheap pure-Python row-count estimate for ADMISSION PRICING only
    (len/isinstance — no numpy before the quota gate): a multi-image
    request is a list whose first element is itself a 2-D image (list
    of lists); anything else prices as one row. The engine's
    preprocess still decides the real shape (and 400s malformed
    bodies); the batcher re-prices at the real row count."""
    if isinstance(images, list) and images \
            and isinstance(images[0], list) \
            and images[0] and isinstance(images[0][0], list):
        return len(images)
    return 1


class _HTTPServer(ThreadingHTTPServer):
    # Overload must reach ADMISSION CONTROL (a 503 with Retry-After),
    # not the kernel: the stdlib default accept backlog of 5 turns a
    # burst into connection-refused at the TCP layer — an unattributed
    # drop no policy ever saw. 128 rides out any burst the bounded
    # request queue is sized to answer.
    request_queue_size = 128


class ModelPlane:
    """One model's complete serving stack: engine/pool, batcher, reload
    watcher, optional canary and autoscaler, and its own
    :class:`ServeLog`. The single-model server is the degenerate case of
    one plane; ``--model-set`` boots N of these from one process, each
    keeping its own watcher/canary/layout-gate while sharing the chip
    budget through the weighted-fair dispatch gate."""

    def __init__(self, model_name: str, engine, batcher, watcher,
                 serve_log, boot_path: Optional[str], pool=None,
                 canary=None, autoscaler=None,
                 checkpoint_dir: Optional[str] = None) -> None:
        self.model_name = model_name
        self.engine = engine
        self.batcher = batcher
        self.watcher = watcher
        self.serve_log = serve_log
        self.boot_path = boot_path
        self.pool = pool
        self.canary = canary
        self.autoscaler = autoscaler
        self.checkpoint_dir = checkpoint_dir

    @property
    def checkpoint_path(self) -> Optional[str]:
        """The checkpoint currently serving: the watcher's view when
        reloading is on, else the boot-time restore."""
        if self.watcher is not None:
            return self.watcher.current_path
        return self.boot_path

    def close(self) -> None:
        if self.autoscaler is not None:
            self.autoscaler.stop()
        if self.watcher is not None:
            self.watcher.stop()
        self.batcher.close()


class ServeContext:
    """Everything one serving process owns; built by :func:`create_server`
    and shared with the HTTP handlers via the server object.

    ``planes`` maps model name -> :class:`ModelPlane`;
    ``default_model`` names the plane a request without a ``model``
    field routes to (the sole plane on a single-model server — where
    requests NEVER need the field). The flat attributes (``engine``,
    ``pool``, ``batcher``, ...) alias the default plane, so everything
    written against the single-model context keeps working."""

    def __init__(self, planes, default_model: str, sink,
                 max_request_images: int = 1024,
                 max_inflight: int = 1,
                 serve_mode: str = "replicated",
                 serve_precision: str = "f32",
                 quotas=None, fair_gate=None, fused: bool = True,
                 cache=None, price_admission: bool = False) -> None:
        self.planes = planes
        self.default_model = default_model
        self.sink = sink
        self.max_request_images = max_request_images
        self.serve_mode = serve_mode
        self.serve_precision = serve_precision
        # Request-path economics (DESIGN.md §7n): the epoch-stamped
        # response cache shared by every plane (keys carry the model
        # name, so one budget serves the whole process) and whether
        # admission accounts in measured cost units.
        self.cache = cache
        self.price_admission = bool(price_admission)
        # Which dispatch plane answers raw uint8 requests: fused
        # whole-program (default) or the --no-fuse split reference.
        self.fused = fused
        self.quotas = quotas
        self.fair_gate = fair_gate
        self.max_inflight = max_inflight
        self.t_start = time.time()
        # Drain state (POST /drain): while draining, /predict admission
        # rejects new work with Retry-After and in-flight requests run
        # to completion — the primitive a fleet router's rolling reload
        # and scale-down both sequence on. `_active_predicts` counts
        # every /predict handler past the drain gate, so `draining &&
        # active_requests == 0` means no request can still be executing.
        self.draining = False
        self._drain_lock = threading.Lock()
        self._active_predicts = 0
        # Fleet registration (--register-dir): the record announcing
        # this backend to a router's --backends-dir poller. Written on
        # boot, removed while draining (a draining backend must leave
        # the discovered set BEFORE the next health sweep routes to
        # it), re-written on undrain, removed on close.
        self._register_path: Optional[str] = None
        self._register_url: Optional[str] = None
        default = planes[default_model]
        # Single-model aliases (the historical surface).
        self.model_name = default.model_name
        self.engine = default.engine
        self.pool = default.pool
        self.batcher = default.batcher
        self.watcher = default.watcher
        self.canary = default.canary
        self.serve_log = default.serve_log
        self.boot_path = default.boot_path

    @property
    def multi_model(self) -> bool:
        return len(self.planes) > 1

    @property
    def checkpoint_path(self) -> Optional[str]:
        return self.planes[self.default_model].checkpoint_path

    def plane_for(self, model: Optional[str]) -> ModelPlane:
        """Route one request's ``model`` field to its plane. ``None``
        routes to the default ONLY on a single-model server — a
        multi-model server requires the field (silently defaulting
        would misroute every legacy client the moment a second model
        is added)."""
        if model is None:
            if self.multi_model:
                raise ValueError(
                    f"multi-model server: the request body must name "
                    f"'model' (one of {sorted(self.planes)})")
            return self.planes[self.default_model]
        plane = self.planes.get(model)
        if plane is None:
            raise ValueError(
                f"unknown model {model!r}; this server serves "
                f"{sorted(self.planes)}")
        return plane

    def predict_begin(self) -> None:
        with self._drain_lock:
            self._active_predicts += 1

    def predict_end(self) -> None:
        with self._drain_lock:
            self._active_predicts -= 1

    def active_requests(self) -> int:
        with self._drain_lock:
            return self._active_predicts

    def set_draining(self, draining: bool) -> bool:
        """Flip the drain gate; returns the previous state. Idempotent —
        a second drain (or undrain) is a no-op, so a router retrying the
        admin call cannot wedge the state."""
        with self._drain_lock:
            prev, self.draining = self.draining, bool(draining)
        if prev != draining and self._register_path is not None:
            # Registration follows the drain gate (file IO outside the
            # lock): a drained backend un-registers so a dynamic router
            # drops it at the next sweep; undrain re-announces it.
            if draining:
                _remove_register_record(self._register_path)
            else:
                _write_register_record(self._register_path,
                                       self._register_url)
        return prev

    def chunk_dirs(self) -> list:
        """Every plane's checkpoint directory — where the local chunk
        stores live; the ``GET /chunks/<hash>`` route searches them in
        plane order (digests are content-addressed, so a hit in any
        store is THE chunk)."""
        return [p.checkpoint_dir for p in self.planes.values()
                if p.checkpoint_dir]

    def enable_registration(self, register_dir: str, url: str) -> None:
        os.makedirs(register_dir, exist_ok=True)
        safe = url.split("//", 1)[-1].replace(":", "_").replace("/", "_")
        self._register_path = os.path.join(
            register_dir, f"backend_{safe}.json")
        self._register_url = url
        _write_register_record(self._register_path, url)
        print(f"registered backend {url} in {register_dir}", flush=True)

    def write_all_stats(self, **extra) -> None:
        if self.cache is not None and self.cache.enabled:
            # The cache block rides the periodic serve_stats JSONL
            # lines (PR 3 sink) — no separate event stream to tail.
            extra.setdefault("cache", self.cache.snapshot())
        for plane in self.planes.values():
            plane.serve_log.write_stats(**extra)

    def close(self) -> None:
        if self._register_path is not None:
            _remove_register_record(self._register_path)
            self._register_path = None
        for plane in self.planes.values():
            plane.close()
        if self.sink is not None:
            self.write_all_stats(final=True)


def _write_register_record(path: str, url: Optional[str]) -> None:
    tmp = f"{path}.tmp{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump({"url": url}, f)
    os.replace(tmp, path)


def _remove_register_record(path: str) -> None:
    try:
        os.remove(path)
    except OSError:
        pass  # already gone (double drain, shutdown after drain)


class _Handler(BaseHTTPRequestHandler):
    # Per-request stderr lines would swamp the log at serving rates.
    def log_message(self, fmt, *args):  # noqa: N802 - stdlib name
        pass

    @property
    def ctx(self) -> ServeContext:
        return self.server.ctx  # type: ignore[attr-defined]

    def _reply(self, code: int, payload: dict,
               headers: Optional[dict] = None) -> None:
        body = json.dumps(payload).encode()
        try:
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            for name, value in (headers or {}).items():
                self.send_header(name, str(value))
            self.end_headers()
            self.wfile.write(body)
        except OSError:
            # The client gave up (short timeout under overload) and
            # closed the socket: nobody is listening, and a per-request
            # traceback from socketserver would be exactly the log spam
            # the silenced log_message avoids.
            pass

    def _plane_stats(self, plane: ModelPlane) -> dict:
        """One plane's /stats payload — the historical single-model
        schema, byte-compatible for the default configuration."""
        ctx = self.ctx
        stats = plane.serve_log.snapshot()
        compile_stats = compile_log.stats()

        def _is_planes(name: str) -> bool:
            if not name.startswith("serve_forward_"):
                return False
            if not ctx.multi_model:
                return True
            # Multi-model: engine/replica names carry the model as
            # their first dotted segment after '@' ('serve_forward_b8@
            # linear.r0'), so each plane's block shows only its own
            # programs.
            _, _, engine_name = name.partition("@")
            return engine_name.split(".")[0] == plane.model_name

        stats["compile"] = {
            "programs": {
                name: rec for name, rec in
                compile_stats["programs"].items() if _is_planes(name)
            },
            "totals": compile_stats["totals"],
        }
        stats["buckets"] = list(plane.engine.buckets)
        stats["model_epoch"] = plane.engine.params_epoch
        stats["serve_mode"] = ctx.serve_mode
        # Always present (like serve_mode): what precision the
        # serving programs lower at — loadgen's report and the
        # --expect-precision smoke read it.
        stats["serve_precision"] = ctx.serve_precision
        # Always present: which dispatch plane answers raw uint8
        # requests — True is the fused whole-program plane (raw bytes
        # -> logits in one XLA program per bucket, donated staging),
        # False the --no-fuse split reference. loadgen's report and
        # the --expect-fused smoke read it.
        stats["fused"] = ctx.fused
        if ctx.fused:
            # The donation lifecycle's observable (DESIGN.md §7k):
            # every fused dispatch donates its staging buffer, which is
            # then RETIRED — counted here per bucket, summed across the
            # pool's replicas — never re-listed for reuse.
            src = plane.pool if plane.pool is not None else plane.engine
            stats["donated_staging_retired"] = src.fused_staging_retired()
        if ctx.cache is not None and ctx.cache.enabled:
            # Request-path economics block: cache hit/miss/eviction
            # counters, the invalidation generation, and how many
            # duplicate in-flight requests collapsed onto one compute.
            cache_block = ctx.cache.snapshot()
            cache_block["collapsed"] = plane.batcher.collapsed
            stats["cache"] = cache_block
        if ctx.price_admission and plane.batcher.cost_model is not None:
            # Cost-table provenance: per-bucket prices (geometry seed
            # refreshed by the serve-time EWMA) admission accounts in.
            stats["cost_model"] = plane.batcher.cost_model.snapshot()
        if plane.canary is not None:
            # The shadow-canary block: state machine position,
            # sampling shape, disagreement counters, logit-delta
            # quantiles (serve/canary.py::snapshot).
            stats["canary"] = plane.canary.snapshot()
        if plane.autoscaler is not None:
            # The control-loop block: configuration, scale counters,
            # and the recent decision log (what the dry-run chaos twin
            # asserts before the real resize is trusted).
            stats["autoscaler"] = plane.autoscaler.snapshot()
        if plane.pool is not None:
            stats["serve_devices"] = plane.pool.n_devices
            stats["max_inflight"] = ctx.max_inflight
            # The self-healing/resize topology block (read LIVE from
            # the pool, so a /resize or regroup shows up on the next
            # fetch): generation counter, group counts, quarantine
            # state, failover/regroup totals. loadgen's
            # --expect-groups smoke asserts active_groups; its report
            # carries topology_generation.
            topo = plane.pool.topology()
            for key in ("topology_generation", "groups",
                        "active_groups", "quarantined_groups",
                        "regroups", "failovers"):
                stats[key] = topo[key]
            if ctx.serve_mode != "replicated":
                # The mesh shape the sharded plane is running:
                # loadgen's report and --expect-mode smoke read
                # these.
                stats["mesh_devices"] = plane.pool.mesh_size
                stats["mesh_groups"] = plane.pool.n_replicas
            if "pipeline_stages" in topo:
                # Staged (pipeline) modes: chips per chain — what
                # loadgen --expect-stages asserts.
                stats["pipeline_stages"] = topo["pipeline_stages"]
            if "slice_straddling_groups" in topo:
                # Slice-alignment warning (present only when a DCN
                # slice topology exists): mesh groups whose chips
                # straddle slices — their intra-group collectives
                # ride the slow cross-slice axis. loadgen reports
                # carry it.
                stats["slice_straddling_groups"] = \
                    topo["slice_straddling_groups"]
        return stats

    def do_GET(self) -> None:  # noqa: N802 - stdlib name
        ctx = self.ctx
        if self.path == "/healthz":
            payload = {
                "ok": True,
                "model": ctx.model_name,
                "model_epoch": ctx.engine.params_epoch,
                "checkpoint": ctx.checkpoint_path,
                "uptime_s": round(time.time() - ctx.t_start, 3),
                # Drain state rides on /healthz (not a separate probe):
                # a draining backend is ALIVE but not routable — the
                # router must distinguish "drain in progress" from
                # "dead" or it would quarantine every rolling deploy.
                "draining": ctx.draining,
                # What answers, as jax reports it, so a client (loadgen
                # --smoke, chip_smoke.py) asserts the device through this
                # interface rather than from a boot log.
                **device_report(),
            }
            if ctx.multi_model:
                payload["models"] = {
                    name: plane.engine.params_epoch
                    for name, plane in sorted(ctx.planes.items())}
            self._reply(200, payload)
        elif self.path == "/stats":
            # Top level = the default plane's historical schema; the
            # multi-model server ADDS a per-plane `models` block (and
            # `model_set`), and quotas add their own block — every
            # change is schema-additive.
            stats = self._plane_stats(ctx.planes[ctx.default_model])
            if ctx.multi_model:
                stats["model_set"] = sorted(ctx.planes)
                stats["models"] = {
                    name: self._plane_stats(plane)
                    for name, plane in sorted(ctx.planes.items())}
                if ctx.fair_gate is not None:
                    stats["fair_dispatch"] = ctx.fair_gate.snapshot()
            if ctx.quotas is not None:
                stats["quota"] = ctx.quotas.snapshot()
            # Drain observables: the rolling-reload sequencer polls
            # `draining && active_requests == 0` before publishing.
            stats["draining"] = ctx.draining
            stats["active_requests"] = ctx.active_requests()
            self._reply(200, stats)
        elif self.path.startswith("/chunks/"):
            self._do_chunk(self.path[len("/chunks/"):])
        else:
            self._reply(404, {"error": f"no route {self.path!r}"})

    def _do_chunk(self, digest: str) -> None:
        """``GET /chunks/<sha256>`` — the gossip plane: serve one chunk
        from this backend's local store(s) so peers fetch a publish's
        bytes from each other instead of all hammering the source.
        Content-addressed, so the reply needs no freshness logic: a hex
        digest either resolves to its immutable bytes or 404s. NOT
        gated by drain: a draining backend stops taking predict traffic
        but keeps seeding chunks — a rolling reload is exactly when
        peers need them."""
        import re as _re

        if not _re.fullmatch(r"[0-9a-f]{64}", digest):
            self._reply(404, {"error": "malformed chunk digest"})
            return
        ctx = self.ctx
        for directory in ctx.chunk_dirs():
            path = os.path.join(directory, "chunks", digest)
            try:
                with open(path, "rb") as f:
                    data = f.read()
            except OSError:
                continue
            # `Range: bytes=N-` resumes a torn fetch from byte N
            # (DeltaFetcher retries a mid-body disconnect with the
            # partial offset instead of re-downloading): 206 + a
            # Content-Range naming the suffix; N past the end is 416.
            # Content addressing makes this trivially safe — the bytes
            # behind a digest can never change between attempts. A
            # malformed/unsupported Range falls back to the full 200.
            start = 0
            range_header = (self.headers.get("Range") or "").strip()
            if range_header:
                match = _re.fullmatch(r"bytes=(\d+)-", range_header)
                if match:
                    start = int(match.group(1))
                    if start >= len(data):
                        self._reply(
                            416, {"error": f"range start {start} past "
                                           f"chunk end {len(data)}"},
                            headers={"Content-Range":
                                     f"bytes */{len(data)}"})
                        return
            body = data[start:] if start else data
            try:
                self.send_response(206 if start else 200)
                self.send_header("Content-Type", "application/octet-stream")
                self.send_header("Content-Length", str(len(body)))
                if start:
                    self.send_header(
                        "Content-Range",
                        f"bytes {start}-{len(data) - 1}/{len(data)}")
                self.end_headers()
                self.wfile.write(body)
            except OSError:
                pass  # client went away mid-transfer
            return
        self._reply(404, {"error": f"no chunk {digest}"})

    def do_POST(self) -> None:  # noqa: N802 - stdlib name
        if self.path == "/resize":
            self._do_resize()
            return
        if self.path == "/drain":
            self._do_drain()
            return
        if self.path != "/predict":
            self._reply(404, {"error": f"no route {self.path!r}"})
            return
        ctx = self.ctx
        # The active counter brackets the WHOLE predict path (parse
        # included) and the drain gate sits inside it, so once a drain
        # observer sees `draining && active_requests == 0` no handler
        # can still be ahead of the gate — publish-after-drain never
        # races a request that slipped past a narrower window.
        ctx.predict_begin()
        try:
            if ctx.draining:
                self._reject_draining()
                return
            self._do_predict()
        finally:
            ctx.predict_end()

    def _reject_draining(self) -> None:
        """503 while the drain gate is closed: same admission-control
        contract as overload shedding — Retry-After derived from the
        batcher's measured drain rate, so the client's back-off tracks
        how long the in-flight work plausibly takes to finish."""
        ctx = self.ctx
        length = int(self.headers.get("Content-Length", 0))
        if 0 < length <= MAX_BODY_BYTES:
            # Drain the request body so the reply lands on a clean
            # socket instead of a client-side broken pipe.
            self.rfile.read(length)
        depth = sum(p.batcher.queue_depth() for p in ctx.planes.values())
        rate = max(p.batcher.drain_rps() for p in ctx.planes.values())
        retry_after = min(30.0, max(1.0, depth / rate if rate > 0 else 1.0))
        self._reply(
            503,
            {"error": "draining", "draining": True,
             "retry_after_s": round(retry_after, 3)},
            headers={"Retry-After": max(1, round(retry_after))})

    def _do_drain(self) -> None:
        """``POST /drain`` — the fleet primitive: body ``{"drain":
        true|false}`` (default true) closes/reopens the /predict
        admission gate. In-flight requests complete; ``/stats`` exposes
        ``draining`` + ``active_requests`` so a rolling reload can wait
        for quiescence before publishing."""
        ctx = self.ctx
        length = int(self.headers.get("Content-Length", 0))
        if length > MAX_BODY_BYTES:
            self._reply(413, {"error": "oversized /drain body"})
            return
        try:
            payload = json.loads(self.rfile.read(length) or b"{}")
            if not isinstance(payload, dict):
                raise ValueError("body must be a JSON object")
            drain = payload.get("drain", True)
            if not isinstance(drain, bool):
                raise ValueError("'drain' must be a boolean")
        except (ValueError, TypeError, json.JSONDecodeError) as exc:
            self._reply(400, {"error": str(exc)})
            return
        prev = ctx.set_draining(drain)
        if prev != drain:
            ctx.serve_log.record_pool_event(
                "serve_drain", draining=drain,
                active_requests=ctx.active_requests())
        self._reply(200, {"ok": True, "draining": drain,
                          "was_draining": prev,
                          "active_requests": ctx.active_requests()})

    def _do_predict(self) -> None:
        ctx = self.ctx
        t0 = time.perf_counter()
        length = int(self.headers.get("Content-Length", 0))
        if length > MAX_BODY_BYTES:
            # Refuse BEFORE reading/parsing: a multi-GB body must not buy
            # memory and JSON-parse time on this handler thread.
            self._reply(413, {"error": f"body over {MAX_BODY_BYTES} bytes;"
                                       f" batch client-side"})
            return
        raw_body = self.rfile.read(length) or b"{}"
        try:
            payload = json.loads(raw_body)
            # Control-plane fields first, all cheap string work: the
            # model route, the priority class (vocabulary-checked), and
            # the client identity — so quota refusal below happens
            # before any per-pixel array work is paid.
            plane = ctx.plane_for(payload.get("model"))
            # None (no priority field) stays None end to end: treated
            # as the most urgent class but never recorded as one, so a
            # server whose clients don't speak priorities keeps the
            # classless /stats schema.
            klass = payload.get("priority") or None
            if klass is not None:
                priority_rank(klass)  # 400 on an unknown class
            client_id = payload.get("client_id")
            if client_id is not None and not isinstance(client_id, str):
                raise ValueError("client_id must be a string")
        except (ValueError, TypeError, json.JSONDecodeError) as exc:
            self._reply(400, {"error": str(exc)})
            return
        # Response-cache probe (still pure byte/hash work — no numpy):
        # the key is the RAW request bytes plus everything else that
        # shapes the answer (model, serve mode, precision); the probe
        # snapshots the invalidation generation so an insert after a
        # concurrent swap is dropped, never served stale.
        cache = ctx.cache if ctx.cache is not None and ctx.cache.enabled \
            else None
        if cache is not None and plane.canary is not None \
                and plane.canary.state == CANARY_SHADOW:
            # A SHADOW canary judges only dispatched traffic: serving
            # duplicates from cache (or collapsing them onto one
            # dispatch — the key is also the collapse key) would starve
            # the comparison stream and stall promotion. Same rule as
            # the router during a fleet canary; normal caching resumes
            # on promote or rollback.
            cache = None
        ckey, hit_value, gen = None, None, 0
        if cache is not None:
            ckey = request_key(raw_body, plane.model_name,
                               ctx.serve_mode, ctx.serve_precision)
            hit_value, _hit_epoch, gen = cache.get(ckey)
        if ctx.quotas is not None:
            # Per-client quotas run BEFORE the request consumes a queue
            # slot (or any preprocessing): 429 is the CLIENT's overload
            # — admission control (503 below) is the server's. Under
            # --price-admission the bucket drains in measured cost
            # units: a cache hit is ~free, a big-bucket miss costs its
            # seeded/EWMA price (row count estimated from JSON nesting —
            # cheap; the engine still decides the real shape below).
            cost = 1.0
            if ctx.price_admission:
                if hit_value is not None:
                    cost = HIT_COST
                elif plane.batcher.cost_model is not None:
                    cost = plane.batcher.cost_model.price(
                        _estimate_rows(payload.get("images")))
            admitted, retry_after = ctx.quotas.admit(
                client_id, klass or PRIORITY_CLASSES[0], cost=cost)
            if not admitted:
                plane.serve_log.record_rejection(klass=klass, quota=True)
                self._reply(
                    429,
                    {"error": "quota exceeded",
                     "priority": klass or PRIORITY_CLASSES[0],
                     "retry_after_s": retry_after},
                    headers={"Retry-After": max(1, round(retry_after))})
                return
        if hit_value is not None:
            # Cache hit: replay the stored predictions + epoch without
            # touching the batcher or a chip. The body is built by the
            # SAME code path as a miss (latency_ms is per-request
            # either way); only the X-Cache header differs. A hit is
            # still a SERVED request — it counts in the ServeLog like
            # any other (zero queue wait), so request totals, rps and
            # the rolling window the autoscaler reads stay honest.
            predictions, hit_epoch = hit_value
            latency_s = time.perf_counter() - t0
            plane.serve_log.record_request(
                latency_s, queue_wait_s=0.0,
                images=len(predictions), klass=klass)
            reply = {
                "predictions": list(predictions),
                "model_epoch": hit_epoch,
                "latency_ms": round(latency_s * 1e3, 3),
            }
            if ctx.multi_model:
                reply["model"] = plane.model_name
            self._reply(200, reply, headers={"X-Cache": "hit"})
            return
        try:
            images = payload.get("images")
            if images is None:
                raise ValueError("body must be JSON {\"images\": ...}")
            arr = np.asarray(images, dtype=np.float32)
            # Raw 0-255 pixels over the wire; quantize to the exact uint8
            # domain training reads from disk, then the engine applies
            # the training normalize. One preprocessing path, no drift.
            raw = np.clip(np.rint(arr), 0, 255).astype(np.uint8)
            batch = plane.engine.preprocess(raw)
            if batch.shape[0] > ctx.max_request_images:
                # One request = one queue slot: an unbounded row count
                # would monopolize the batcher past admission control.
                raise ValueError(
                    f"{batch.shape[0]} images in one request (max "
                    f"{ctx.max_request_images}); batch client-side")
        except (ValueError, TypeError, json.JSONDecodeError) as exc:
            self._reply(400, {"error": str(exc)})
            return
        try:
            # Each output row is (label, epoch-of-the-params-that-
            # computed-it) — see create_server's infer wrapper — so the
            # reply can never attribute a batch to a checkpoint a
            # concurrent hot reload installed after it ran. The cache
            # key doubles as the collapse key: a concurrent identical
            # request joins this one's pending future instead of
            # re-dispatching (it already paid quota at its own price).
            submit_cost = 1.0
            if ctx.price_admission and plane.batcher.cost_model is not None:
                submit_cost = plane.batcher.cost_model.price(
                    int(batch.shape[0]))
            out = plane.batcher.predict(batch, klass=klass,
                                        collapse_key=ckey,
                                        cost=submit_cost)
        except Overloaded as exc:
            # The shed reply: Retry-After (derived from the batcher's
            # measured drain rate) tells the client when this priority
            # class plausibly re-admits — back-off becomes a contract,
            # not a guess.
            payload = {"error": "overloaded", "detail": str(exc),
                       "priority": klass or PRIORITY_CLASSES[0]}
            headers = None
            if exc.retry_after_s is not None:
                payload["retry_after_s"] = exc.retry_after_s
                headers = {"Retry-After": max(1, round(exc.retry_after_s))}
            self._reply(503, payload, headers=headers)
            return
        except TimeoutError as exc:
            self._reply(504, {"error": str(exc)})
            return
        except Exception as exc:  # noqa: BLE001 - a request never kills the server
            self._reply(500, {"error": repr(exc)})
            return
        epoch = int(out[0, 1])
        model_epoch = None if epoch < 0 else epoch
        predictions = [int(v) for v in out[:, 0]]
        reply = {
            "predictions": predictions,
            "model_epoch": model_epoch,
            "latency_ms": round((time.perf_counter() - t0) * 1e3, 3),
        }
        if ctx.multi_model:
            reply["model"] = plane.model_name
        headers = None
        if cache is not None:
            # Insert stamped with the PROBE-TIME generation: if a hot
            # reload / precision swap / canary promote bumped it while
            # this request computed, put() drops the entry — the cache
            # can only ever replay the current generation's params.
            cache.put(ckey, (predictions, model_epoch),
                      len(raw_body) + 16 * len(predictions) + 64,
                      epoch=model_epoch, generation=gen)
            headers = {"X-Cache": "miss"}
        self._reply(200, reply, headers=headers)

    def _do_resize(self) -> None:
        """``POST /resize`` — the admin topology dial: body
        ``{"serve_devices": N?, "serve_mesh": M?}`` re-shapes the pool
        under live traffic (new layout built + AOT-warmed while the old
        one keeps serving; atomic swap; in-flight batches drain on the
        old engines — zero dropped requests). Replies with the old and
        new topology. An operator's curl today, the autoscaler's
        actuator tomorrow (ROADMAP item 1)."""
        ctx = self.ctx
        # Multi-model: an optional "model" field routes the resize to
        # that plane's pool (peeked before the full parse below so the
        # plane's canary/pool checks see the right plane).
        length_peek = int(self.headers.get("Content-Length", 0))
        if length_peek > MAX_BODY_BYTES:
            self._reply(413, {"error": "oversized /resize body"})
            return
        raw_body = self.rfile.read(length_peek)
        try:
            peek = json.loads(raw_body or b"{}")
            plane = ctx.plane_for(
                peek.get("model") if isinstance(peek, dict) else None)
        except (ValueError, TypeError, json.JSONDecodeError) as exc:
            self._reply(400, {"error": str(exc)})
            return
        if plane.pool is None:
            self._reply(400, {
                "error": "resize needs the pooled data plane; start "
                         "with --serve-devices/--max-inflight/"
                         "--serve-mode (the default single-engine "
                         "server has no pool to re-shape)"})
            return
        if plane.canary is not None:
            # A resize mid-canary would re-shape only the baseline pool
            # while the candidate keeps the old topology — the two
            # planes' capacity (and failure surface) would silently
            # diverge under the comparison. Deliberately refused.
            self._reply(400, {
                "error": "resize is not supported while a precision "
                         "canary is active (--canary-fraction); the "
                         "baseline and shadow planes must keep the "
                         "same topology — restart to change it"})
            return
        try:
            payload = json.loads(raw_body or b"{}")
            if not isinstance(payload, dict):
                raise ValueError(
                    "body must be a JSON object with serve_devices "
                    "and/or serve_mesh")
            n_devices = payload.get("serve_devices")
            mesh_size = payload.get("serve_mesh")
            if n_devices is None and mesh_size is None:
                raise ValueError(
                    "body must be JSON with serve_devices and/or "
                    "serve_mesh")
            if n_devices is not None:
                n_devices = int(n_devices)
            if mesh_size is not None:
                mesh_size = int(mesh_size)
        except (ValueError, TypeError, json.JSONDecodeError) as exc:
            self._reply(400, {"error": str(exc)})
            return
        t0 = time.perf_counter()
        try:
            result = plane.pool.resize(n_devices=n_devices,
                                       mesh_size=mesh_size)
        except ValueError as exc:
            # An invalid target topology (device bounds, mesh
            # divisibility, a replicated mesh) — flag-language message,
            # nothing changed.
            self._reply(400, {"error": str(exc)})
            return
        except RuntimeError as exc:
            # One resize at a time: the concurrent caller backs off.
            self._reply(409, {"error": str(exc)})
            return
        except Exception as exc:  # noqa: BLE001 - an admin op never kills serving
            self._reply(500, {"error": repr(exc)})
            return
        self._reply(200, {
            "ok": True,
            **result,
            "warm_s": round(time.perf_counter() - t0, 3),
        })


def _parse_buckets(spec: str):
    try:
        buckets = tuple(int(tok) for tok in spec.split(",") if tok.strip())
    except ValueError:
        raise SystemExit(f"--buckets must be comma-separated ints, "
                         f"got {spec!r}") from None
    if not buckets or min(buckets) < 1:
        raise SystemExit(f"--buckets needs at least one positive size, "
                         f"got {spec!r}")
    return buckets


def _parse_model_set(spec: str, list_models) -> "dict":
    """``--model-set NAME=DIR[,NAME=DIR...]`` -> ordered
    ``{model: checkpoint_dir}``; flag-language SystemExits on unknown
    models, duplicates, or a malformed pair."""
    entries: dict = {}
    for tok in spec.split(","):
        tok = tok.strip()
        if not tok:
            continue
        name, sep, directory = tok.partition("=")
        name, directory = name.strip(), directory.strip()
        if not sep or not name or not directory:
            raise SystemExit(
                f"--model-set: expected MODEL=CHECKPOINT_DIR, got "
                f"{tok!r}")
        if name not in list_models():
            raise SystemExit(f"--model-set names unknown model {name!r}; "
                             f"available: {list_models()}")
        if name in entries:
            raise SystemExit(
                f"--model-set names {name!r} twice (one engine-set per "
                f"model; point retrains at one directory)")
        entries[name] = directory
    if not entries:
        raise SystemExit("--model-set needs at least one MODEL=DIR pair")
    return entries


def _parse_watermarks(spec: Optional[str]) -> ShedPolicy:
    if not spec:
        return ShedPolicy()
    marks = {}
    for tok in spec.split(","):
        tok = tok.strip()
        if not tok:
            continue
        klass, sep, frac = tok.partition("=")
        if not sep:
            raise SystemExit(
                f"--shed-watermarks: expected CLASS=FRACTION, got "
                f"{tok!r}")
        try:
            marks[klass.strip()] = float(frac)
        except ValueError:
            raise SystemExit(
                f"--shed-watermarks: {frac!r} is not a number") from None
    try:
        return ShedPolicy(marks)
    except ValueError as exc:
        raise SystemExit(f"--shed-watermarks: {exc}") from None


def _build_plane(args, model_name: str, checkpoint_dir: str, *,
                 shape: dict, sink, shed_policy, fair_gate,
                 multi_model: bool) -> ModelPlane:
    """One model's full serving stack over the resolved data-plane
    ``shape`` — the single-model server builds exactly one of these;
    ``--model-set`` builds one per model (each with its own ServeLog,
    reload watcher, canary, layout gate, and — when autoscaling — its
    own controller over its own pool)."""
    import jax

    from pytorch_distributed_mnist_tpu.models import get_model, model_accepts
    from pytorch_distributed_mnist_tpu.serve.programs import (
        check_checkpoint_layout,
        make_serve_template,
        staged_mode,
        validate_serve_mode,
    )
    from pytorch_distributed_mnist_tpu.train.checkpoint import (
        _epoch_checkpoints,
        checkpoint_parallel_layout,
        checkpoint_world,
    )

    devices = shape["devices"]
    n_devices = shape["n_devices"]
    serve_mode = shape["serve_mode"]
    mesh_size = shape["mesh_size"]
    sharded = shape["sharded"]
    max_inflight = shape["max_inflight"]
    pooled = shape["pooled"]
    n_groups = shape["n_groups"]

    model_kwargs = {}
    if getattr(args, "dtype", None):
        import jax.numpy as jnp

        model_kwargs["compute_dtype"] = {
            "bf16": jnp.bfloat16, "f32": jnp.float32}[args.dtype]
    model = get_model(model_name, **model_kwargs)

    if sharded:
        try:
            # The mode/model PAIR check (mode registered, rule table for
            # this model) must precede the template build: a mode's
            # make_template hook assumes its model family (pipeline
            # splits block layers), so an unservable pair has to die
            # with flag language HERE, not a traceback in there. The
            # full check with the real mesh and params runs below.
            validate_serve_mode(serve_mode, model_name, 1)
        except ValueError as exc:
            raise SystemExit(str(exc)) from None
    template = make_serve_template(serve_mode, model,
                                   jax.random.key(args.seed))
    try:
        # ONE rule source (programs.validate_serve_mode): a mesh on the
        # replicated plane, a mode without a rule table for the model,
        # and a sharded weight dim that doesn't divide the mesh (the
        # template's shapes are every loadable checkpoint's shapes) all
        # fail HERE with flag language, before any mesh or program is
        # built.
        validate_serve_mode(serve_mode, model_name, mesh_size,
                            template.params if sharded else None)
    except ValueError as exc:
        raise SystemExit(str(exc)) from None

    # Precision plane + canary shape (argparse choices already bound
    # --serve-precision to the live registry): a canary only makes
    # sense shadowing a QUANTIZED plane against the f32 baseline.
    serve_precision = getattr(args, "serve_precision", "f32") or "f32"
    # Whole-program dispatch (ON by default, --no-fuse for the split
    # reference plane): raw uint8 requests run one fused program per
    # bucket — normalize/quantize inside XLA, staging donated. Under a
    # canary BOTH planes fuse (the batcher hands both the same raw
    # batch; mixed planes would compare different dispatch paths, not
    # different precisions).
    fuse = not getattr(args, "no_fuse", False)
    canary_fraction = float(getattr(args, "canary_fraction", 0.0) or 0.0)
    canary_promote_after = int(getattr(args, "canary_promote_after", 200))
    canary_budget = float(getattr(args, "canary_budget", 0.02))
    if canary_fraction:
        if serve_precision == "f32":
            raise SystemExit(
                "--canary-fraction shadows a quantized plane against "
                "the f32 baseline; pass a quantized --serve-precision "
                f"({serve_precisions()[1:]}) or drop the flag")
        if not (0.0 < canary_fraction <= 1.0):
            raise SystemExit(
                f"--canary-fraction {canary_fraction}: must be in "
                f"(0, 1]")
        if canary_promote_after < 1:
            raise SystemExit(
                f"--canary-promote-after {canary_promote_after}: must "
                f"be >= 1")
        if canary_budget < 0:
            raise SystemExit(
                f"--canary-budget {canary_budget}: must be >= 0")

    # Boot restore walks newest -> oldest: one corrupt latest file must
    # not turn a server RESTART (the natural operator response to any
    # incident) into a total outage — the same availability stance the
    # hot-reload watcher takes, and the serving analog of --resume auto's
    # fall-back-to-next-older (quarantining stays the trainer's job).
    # The parallel-layout gate applies PER CANDIDATE, on the meta-only
    # read and before the expensive template load: a layout-mismatched
    # newest file (a retrain under new parallelism flags sharing the
    # directory) is skipped in favor of an older compatible epoch, and
    # only when mismatches are the SOLE reason nothing is servable does
    # boot fail — loudly, naming the valid --serve-mode choices, never
    # by silently serving fresh-init params instead of the trained model.
    boot_path, params, epoch = None, None, None
    layout_rejection = None  # newest layout-mismatch (path, message)
    for _, candidate in reversed(_epoch_checkpoints(checkpoint_dir)):
        try:
            try:
                layout = checkpoint_parallel_layout(candidate)
            except Exception:  # noqa: BLE001 - unreadable meta: let the
                layout = None  # load attempt below classify the damage
            check_checkpoint_layout(layout, serve_mode, model_name)
        except ValueError as exc:
            if layout_rejection is None:
                layout_rejection = (candidate, str(exc))
            print(f"WARNING: cannot serve checkpoint {candidate!r} "
                  f"({exc}); trying the next-older epoch", flush=True)
            continue
        try:
            params, epoch = load_params_for_serving(candidate, template)
            boot_path = candidate
            break
        except Exception as exc:  # noqa: BLE001 - keep walking older epochs
            print(f"WARNING: cannot serve checkpoint {candidate!r} "
                  f"({exc!r}); trying the next-older epoch", flush=True)
    if boot_path is not None:
        # World provenance by meta inspection (the training world's
        # shape, stamped at save): a checkpoint from an N-host world is
        # served here after a cross-topology reshard — worth one log
        # line, since epoch metrics in a shared metrics file may
        # straddle world sizes (the elastic shrink path).
        try:
            world = checkpoint_world(boot_path)
        except Exception:  # noqa: BLE001 - provenance only; it loaded
            world = None
        provenance = (f", saved at world {world['processes']}x"
                      f"{world['devices']} processes x devices"
                      if world else "")
        print(f"serving checkpoint {boot_path!r} (epoch {epoch}"
              f"{provenance})", flush=True)
    elif layout_rejection is not None:
        raise SystemExit(
            f"{layout_rejection[0]!r}: {layout_rejection[1]}")
    elif getattr(args, "require_checkpoint", False):
        raise SystemExit(
            f"--require-checkpoint: no loadable published checkpoint in "
            f"{checkpoint_dir!r}")
    else:
        params, epoch = template.params, None
        print(f"WARNING: no loadable checkpoint in "
              f"{checkpoint_dir!r}; serving fresh-init params "
              f"(seed {args.seed}) until one is published", flush=True)

    serve_log = ServeLog(
        window_s=float(getattr(args, "stats_window_s", 60.0) or 60.0))
    if sink is not None:
        # One plane, one source tag: a multi-model process's JSONL
        # lines stay attributable per model in the shared file.
        serve_log.set_sink(
            sink, source=f"serve/{model_name}" if multi_model else "serve")

    # Multi-model names: the model is the first dotted segment of every
    # engine/replica name ('linear.r0', 'cnn.tensor.g0'), so /stats
    # rows, CompileLog programs, and recompile verdicts stay per model.
    name_prefix = f"{model_name}." if multi_model else ""

    def _tag(labels, epoch):
        # Row-tagged outputs (label, epoch): the epoch is captured WITH
        # the params inside the engine, and all rows of one batcher batch
        # ride one engine call (hence ONE replica), so per-request slices
        # stay consistent and the HTTP reply reports the checkpoint that
        # really computed it.
        tag = np.full_like(labels, -1 if epoch is None else epoch)
        return np.stack([labels, tag], axis=1)

    def _gated(dispatch_fn):
        """Wrap a dispatch with the weighted-fair gate: the grant runs
        on the batcher's dispatch thread (blocking only while OTHER
        models are ahead in virtual time), the dispatch itself after
        the grant — outside the gate's lock."""
        if fair_gate is None:
            return dispatch_fn

        def gated(images):
            fair_gate.grant(model_name, int(images.shape[0]))
            return dispatch_fn(images)

        return gated

    t0 = time.perf_counter()
    pool = None
    canary = None
    # Request-path economics: the per-bucket cost table (seeded from
    # the bucket geometry, EWMA-refreshed by the batcher per completed
    # batch) and whether admission accounts in its cost units.
    cost_model = CostModel(_parse_buckets(args.buckets))
    priced = bool(getattr(args, "price_admission", False))

    def _model_for(precision: str):
        """The model instance one precision plane lowers: the int8
        plane (and only it) gets the MXU-native int8 matmul injected
        through the model's ``dot_general`` field — PER-PRECISION
        instances, so a canary's f32 baseline never runs the kernel it
        is supposed to referee. Params are field-independent: the same
        checkpoint tree serves both instances."""
        if precision == "int8" and model_accepts(model_name, "dot_general"):
            from pytorch_distributed_mnist_tpu.ops.pallas import (
                int8_dot_general,
            )

            return get_model(model_name, dot_general=int8_dot_general,
                             **model_kwargs)
        return model

    def _make_plane(precision: str):
        """ONE data plane at ``precision`` over the resolved shape —
        the single builder both the direct path and the canary's two
        planes go through, so they cannot drift."""
        plane_model = _model_for(precision)
        if pooled:
            from pytorch_distributed_mnist_tpu.serve.pool import EnginePool

            return EnginePool(
                plane_model.apply, params, devices=devices[:n_devices],
                buckets=_parse_buckets(args.buckets), serve_log=serve_log,
                params_epoch=epoch, workers=getattr(args, "workers", 4),
                serve_mode=serve_mode, mesh_size=mesh_size,
                model_name=model_name, model=plane_model,
                quarantine_after=getattr(args, "quarantine_after", 3),
                precision=precision, name_prefix=name_prefix,
                fuse=fuse,
            )
        return InferenceEngine(
            plane_model.apply, params,
            buckets=_parse_buckets(args.buckets),
            serve_log=serve_log, params_epoch=epoch,
            workers=getattr(args, "workers", 4), precision=precision,
            name=precision_engine_name(
                model_name if multi_model else None, precision),
            fuse=fuse,
        )

    if canary_fraction:
        # Shadow canary: the f32 BASELINE answers, the quantized
        # candidate shadows --canary-fraction of batches; both planes
        # AOT-warm before the socket opens. /stats' topology block and
        # /resize talk to the baseline pool (the plane answering by
        # default); the candidate heals itself through the same pool
        # machinery.
        baseline = _make_plane("f32")
        candidate = _make_plane(serve_precision)
        pool = baseline if pooled else None
        if pooled and serve_log is not None:
            # Each pool registers its per-replica probe at construction;
            # the candidate (built second) would otherwise own /stats'
            # replica rows. The BASELINE answers by default — its rows
            # are the ones the probe should show.
            serve_log.set_replicas_probe(baseline.snapshot)
        canary = ShadowCanary(
            baseline, candidate, serve_precision,
            fraction=canary_fraction, promote_after=canary_promote_after,
            budget=canary_budget, serve_log=serve_log)
        engine = canary
        canary.warmup()
        batcher = MicroBatcher(
            None, max_batch=canary.max_batch,
            max_wait_s=args.max_wait_ms / 1e3, max_queue=args.max_queue,
            serve_log=serve_log,
            dispatch_fn=_gated(canary.dispatch),
            complete_fn=lambda handle: _tag(*canary.predict_complete(handle)),
            max_inflight=max_inflight, shed_policy=shed_policy,
            cost_model=cost_model, priced=priced,
        ).start()
    elif pooled:
        pool = _make_plane(serve_precision)
        engine = pool
        pool.warmup()
        batcher = MicroBatcher(
            None, max_batch=pool.max_batch,
            max_wait_s=args.max_wait_ms / 1e3, max_queue=args.max_queue,
            serve_log=serve_log,
            dispatch_fn=_gated(pool.dispatch),
            complete_fn=lambda handle: _tag(*pool.predict_complete(handle)),
            max_inflight=max_inflight, shed_policy=shed_policy,
            cost_model=cost_model, priced=priced,
        ).start()
    else:
        engine = _make_plane(serve_precision)
        engine.warmup()

        def infer(images):
            return _tag(*engine.predict_with_epoch(images))

        batcher = MicroBatcher(
            _gated(infer), max_batch=engine.max_batch,
            max_wait_s=args.max_wait_ms / 1e3, max_queue=args.max_queue,
            serve_log=serve_log, shed_policy=shed_policy,
            cost_model=cost_model, priced=priced,
        ).start()
    stats = compile_log.stats()["programs"]
    compiled_ms = sum(rec["wall_ms"] for name, rec in stats.items()
                      if name.startswith("serve_forward_"))
    if canary is not None:
        plane = (f"f32 baseline + {serve_precision} shadow canary "
                 f"(fraction {canary_fraction}, promote after "
                 f"{canary_promote_after} rows, budget {canary_budget})"
                 + (f" x {n_devices} device(s), {serve_mode}"
                    if pooled else ""))
    elif sharded and staged_mode(serve_mode):
        plane = (f"MPMD {serve_mode}: {n_groups} chain(s) x "
                 f"{mesh_size} per-chip stage programs x "
                 f"{len(engine.buckets)} buckets, in-flight window "
                 f"{max_inflight}")
    elif sharded:
        plane = (f"{serve_mode}-sharded: {n_groups} mesh group(s) x "
                 f"{mesh_size} chips x {len(engine.buckets)} buckets, "
                 f"in-flight window {max_inflight}")
    elif pooled:
        plane = (f"{n_devices} replica(s) x {len(engine.buckets)} "
                 f"buckets, in-flight window {max_inflight}")
    else:
        plane = f"{len(engine.buckets)} bucket programs"
    if serve_precision != "f32" and canary is None:
        plane = f"{serve_precision} {plane}"
    if fuse:
        plane = f"whole-program fused {plane}"
    print(f"{model_name}: AOT-compiled {plane} "
          f"{list(engine.buckets)} in {time.perf_counter() - t0:.1f}s "
          f"(compile wall {compiled_ms:.0f} ms); steady-state serving "
          f"never recompiles", flush=True)

    watcher = None
    if not getattr(args, "no_reload", False):
        # engine is the pool in the pooled case: ONE host-side checkpoint
        # load fans out to an atomic (and stale-rejecting) per-replica
        # swap.
        def _validate_reload(path: str) -> None:
            # The boot-time layout gate, re-applied per reload: a
            # checkpoint published with a mismatched training parallel
            # layout is skipped (permanent for that file) instead of
            # silently served under the wrong mode.
            check_checkpoint_layout(
                checkpoint_parallel_layout(path), serve_mode, model_name)

        # The delta-distribution loader: manifests are satisfied by
        # fetching only missing chunks (peers first, source dir
        # fallback) and patching/re-quantizing only dirty leaves; npz
        # and .ckpt paths fall through to the byte-identical whole-file
        # load, so directories that never see a manifest behave exactly
        # as before. Fetch-side quantization only when ONE plane owns
        # the loader's output — a canary's f32 baseline must never
        # receive pre-quantized leaves.
        from pytorch_distributed_mnist_tpu.distrib.fetch import DeltaFetcher
        from pytorch_distributed_mnist_tpu.serve.programs import (
            get_precision,
        )

        peers = [u.strip() for u in
                 (getattr(args, "chunk_peers", None) or "").split(",")
                 if u.strip()]
        fetcher = DeltaFetcher(
            checkpoint_dir,
            precision=(get_precision(serve_precision)
                       if canary is None else None),
            peers=peers,
            source_dir=getattr(args, "chunk_source", None),
            workers=getattr(args, "workers", 4),
        )
        watcher = CheckpointWatcher(
            checkpoint_dir, template, engine.swap_params,
            poll_interval_s=args.poll_interval, serve_log=serve_log,
            current_path=boot_path, validate_fn=_validate_reload,
            loader=fetcher.load,
        ).start()
        watcher.fetcher = fetcher  # observability: the chaos twins read stats

    autoscaler = None
    if getattr(args, "autoscale", False):
        # The SLO control loop over THIS plane's pool: samples the
        # plane's rolling-window p95/queue depth, actuates its resize.
        # Validation (pooled plane required, no canary, sane bounds,
        # mesh-multiple min/max on sharded modes) happened in
        # create_server before any plane was built. On a sharded pool
        # the scale STEP is one whole mesh group (mesh_size chips) —
        # resize validates serve_mesh | serve_devices, so a +1-chip
        # step could never actuate there.
        max_devices = getattr(args, "autoscale_max_devices", 0) or \
            (len(devices) - len(devices) % mesh_size)
        queue_high = max(1, int(getattr(args, "autoscale_queue_high",
                                        0.75) * args.max_queue))
        min_devices = getattr(args, "autoscale_min_devices", 1)
        if sharded:
            min_devices = max(min_devices, mesh_size)
        autoscaler = AutoScaler(
            pool, serve_log.window_stats,
            slo_p95_ms=getattr(args, "slo_p95_ms", 100.0),
            queue_high=queue_high,
            min_devices=min_devices,
            max_devices=max_devices,
            step=mesh_size,
            interval_s=getattr(args, "autoscale_interval_s", 2.0),
            cooldown_s=getattr(args, "autoscale_cooldown_s", 10.0),
            down_after=getattr(args, "autoscale_down_after", 3),
            dry_run=getattr(args, "autoscale_dry_run", False),
            serve_log=serve_log,
            model=model_name if multi_model else None,
        ).start()
        print(f"autoscaler: SLO p95 {autoscaler.slo_p95_ms}ms, queue "
              f"high {queue_high}, {autoscaler.min_devices}.."
              f"{max_devices} device(s), cooldown "
              f"{autoscaler.cooldown_s}s"
              + (" [dry run]" if autoscaler.dry_run else ""), flush=True)

    return ModelPlane(
        model_name, engine, batcher, watcher, serve_log, boot_path,
        pool=pool, canary=canary, autoscaler=autoscaler,
        checkpoint_dir=checkpoint_dir)


def create_server(args) -> ThreadingHTTPServer:
    """Build the model plane(s) — engine/pool + batcher + watcher (+
    canary/autoscaler) per model — and bind the HTTP server (socket
    bound, not yet serving — callers run ``serve_forever`` themselves, so
    tests can boot on port 0 in-process). ``server.ctx.close()`` tears
    the serving stack down."""
    import jax

    from pytorch_distributed_mnist_tpu.models import list_models
    from pytorch_distributed_mnist_tpu.serve.programs import staged_mode
    from pytorch_distributed_mnist_tpu.utils import compile_cache

    # The model set: --model-set wins (multi-model), else the classic
    # --model/--checkpoint-dir pair is a one-plane set.
    model_set_spec = getattr(args, "model_set", None)
    if model_set_spec:
        model_dirs = _parse_model_set(model_set_spec, list_models)
    else:
        if args.model not in list_models():
            raise SystemExit(f"unknown --model {args.model!r}; "
                             f"available: {list_models()}")
        model_dirs = {args.model: args.checkpoint_dir}
    multi_model = len(model_dirs) > 1

    cache_dir = compile_cache.configure(getattr(args, "compile_cache", None))
    if cache_dir:
        print(f"compile cache: {cache_dir}", flush=True)

    # Data-plane shape: --serve-devices chips (0 = all local devices),
    # --serve-mode deciding how a forward spans them (replicated per
    # chip, tensor/expert-sharded over --serve-mesh-chip groups, or a
    # pipeline of per-chip stage programs), with a --max-inflight
    # pipelined dispatch window (0 = auto). The default (replicated, 1
    # device, window 1) is the single-device plane, built exactly as it
    # always was. Shared by every model plane: N models serve from ONE
    # chip budget.
    devices = jax.local_devices()
    n_devices = getattr(args, "serve_devices", 1)
    if n_devices == 0:
        n_devices = len(devices)
    if n_devices < 0 or n_devices > len(devices):
        raise SystemExit(
            f"--serve-devices {n_devices}: this host has "
            f"{len(devices)} local device(s)")
    serve_mode = getattr(args, "serve_mode", "replicated")
    serve_mesh = getattr(args, "serve_mesh", 0)
    sharded = serve_mode != "replicated"
    mesh_size = 1
    if sharded:
        mesh_size = serve_mesh or n_devices
        if n_devices % mesh_size:
            raise SystemExit(
                f"--serve-mesh {mesh_size} must divide --serve-devices "
                f"{n_devices} (the pool runs one spanning engine per "
                f"mesh group)")
    elif serve_mesh not in (0, 1):
        mesh_size = serve_mesh  # rejected by per-plane validation
    max_inflight = getattr(args, "max_inflight", 0)
    if max_inflight < 0:
        raise SystemExit(f"--max-inflight {max_inflight}: must be >= 0")
    n_groups = n_devices // mesh_size
    if max_inflight == 0:
        # Auto window: one in-flight batch per engine plus one forming.
        # A single sharded group still defaults to 2 — host staging of
        # batch N+1 overlaps the mesh executing batch N. A STAGED mode's
        # group is a pipeline of per-chip programs, so its window sizes
        # per CHIP (stages x groups + 1): the pipe needs >= stages
        # batches in flight before every stage chip is busy.
        if sharded and staged_mode(serve_mode):
            max_inflight = n_devices + 1
        elif sharded:
            max_inflight = n_groups + 1
        else:
            max_inflight = n_devices + 1 if n_devices > 1 else 1
    pooled = n_devices > 1 or max_inflight > 1 or sharded
    shape = {"devices": devices, "n_devices": n_devices,
             "serve_mode": serve_mode, "mesh_size": mesh_size,
             "sharded": sharded, "max_inflight": max_inflight,
             "pooled": pooled, "n_groups": n_groups}

    # Control-plane configuration, validated BEFORE any plane is built
    # so a bad flag dies in milliseconds, not after the AOT compiles.
    shed_policy = _parse_watermarks(getattr(args, "shed_watermarks", None))
    quotas = None
    quota_spec = getattr(args, "quota_rps", None)
    if quota_spec:
        try:
            rates = parse_quota_spec(quota_spec)
            quotas = ClientQuotas(
                rates, burst_s=getattr(args, "quota_burst_s", 2.0))
        except ValueError as exc:
            raise SystemExit(f"--quota-rps: {exc}") from None
        if not quotas.enabled:
            quotas = None  # every class unlimited: no quota plane
    if getattr(args, "autoscale_dry_run", False) \
            and not getattr(args, "autoscale", False):
        raise SystemExit("--autoscale-dry-run modifies --autoscale; "
                         "pass both")
    if getattr(args, "autoscale", False):
        if not pooled:
            raise SystemExit(
                "--autoscale actuates the pool's resize path; start "
                "the pooled data plane (--serve-devices N / "
                "--max-inflight) — the single-engine server has no "
                "topology to scale")
        if float(getattr(args, "canary_fraction", 0.0) or 0.0):
            raise SystemExit(
                "--autoscale cannot run under an active precision "
                "canary (--canary-fraction): a resize would re-shape "
                "only the baseline pool and the two planes' topology "
                "must not diverge")
        if getattr(args, "autoscale_min_devices", 1) < 1:
            raise SystemExit("--autoscale-min-devices must be >= 1")
        max_dev = getattr(args, "autoscale_max_devices", 0)
        if max_dev and max_dev > len(devices):
            raise SystemExit(
                f"--autoscale-max-devices {max_dev}: this host has "
                f"{len(devices)} local device(s)")
        if sharded:
            # The autoscaler steps by whole MESH GROUPS (resize
            # validates serve_mesh | serve_devices): bounds that are
            # not mesh multiples would make every actuation a
            # validation error — reject them with flag language
            # instead of letting the controller spin on 400s.
            min_dev = getattr(args, "autoscale_min_devices", 1)
            if min_dev > 1 and min_dev % mesh_size:
                raise SystemExit(
                    f"--autoscale-min-devices {min_dev}: the sharded "
                    f"pool scales by whole {mesh_size}-chip mesh "
                    f"groups; pass a multiple of --serve-mesh")
            if max_dev and max_dev % mesh_size:
                raise SystemExit(
                    f"--autoscale-max-devices {max_dev}: the sharded "
                    f"pool scales by whole {mesh_size}-chip mesh "
                    f"groups; pass a multiple of --serve-mesh")
    fair_gate = None
    weight_spec = getattr(args, "model_weights", None)
    if weight_spec and not multi_model:
        raise SystemExit("--model-weights shapes multi-model dispatch; "
                         "it requires --model-set with >= 2 models")
    if multi_model:
        try:
            weights = parse_weight_spec(weight_spec or "",
                                        list(model_dirs))
        except ValueError as exc:
            raise SystemExit(str(exc)) from None
        fair_gate = WeightedFairGate(weights)

    sink = None
    metrics_file = getattr(args, "metrics_file", None)
    if metrics_file:
        sink = JsonlSink(metrics_file)

    planes = {}
    for model_name, checkpoint_dir in model_dirs.items():
        planes[model_name] = _build_plane(
            args, model_name, checkpoint_dir, shape=shape, sink=sink,
            shed_policy=shed_policy, fair_gate=fair_gate,
            multi_model=multi_model)
    default_model = next(iter(model_dirs))
    # Response cache (request-path economics): one shared budget for
    # the whole process — keys carry the model name, so planes cannot
    # collide. The invalidation hook registers on every plane's
    # answering engine (pool/canary/engine all expose add_swap_hook):
    # a hot reload, precision swap, or canary promote bumps the
    # generation under that plane's params lock — O(1), atomic with
    # the swap the entries must not outlive.
    cache_mb = float(getattr(args, "cache_mb", 64.0) or 0.0)
    if getattr(args, "no_cache", False) or cache_mb < 0:
        cache_mb = 0.0
    resp_cache = ResponseCache(int(cache_mb * (1 << 20)))
    if resp_cache.enabled:
        for plane in planes.values():
            plane.engine.add_swap_hook(resp_cache.bump_generation)
    if multi_model:
        print(f"multi-model serving: {sorted(planes)} from one "
              f"{n_devices}-device budget (weighted-fair dispatch "
              f"{fair_gate.weights}); requests route on their 'model' "
              f"field", flush=True)

    httpd = _HTTPServer((args.host, args.port), _Handler)
    httpd.daemon_threads = True
    httpd.ctx = ServeContext(  # type: ignore[attr-defined]
        planes, default_model, sink,
        max_request_images=getattr(args, "max_request_images", 1024),
        max_inflight=max_inflight, serve_mode=serve_mode,
        serve_precision=getattr(args, "serve_precision", "f32") or "f32",
        quotas=quotas, fair_gate=fair_gate,
        fused=not getattr(args, "no_fuse", False),
        cache=resp_cache if resp_cache.enabled else None,
        price_admission=getattr(args, "price_admission", False))
    register_dir = getattr(args, "register_dir", None)
    if register_dir:
        # Announce AFTER the socket is bound (the real port is known —
        # port 0 boots included) and the planes are warm: a router that
        # discovers this record can route to it immediately.
        port = httpd.server_address[1]
        adv_host = args.host if args.host not in ("", "0.0.0.0", "::") \
            else "127.0.0.1"
        httpd.ctx.enable_registration(
            register_dir, f"http://{adv_host}:{port}")
    return httpd


def main(argv: Optional[list] = None) -> None:
    args = build_parser().parse_args(argv)
    httpd = create_server(args)
    host, port = httpd.server_address[:2]
    print(f"serving on http://{host}:{port}  "
          f"(/predict, /healthz, /stats)", flush=True)
    stats_interval = getattr(args, "stats_interval", 0.0)
    stats_timer = None
    if httpd.ctx.sink is not None and stats_interval > 0:
        import threading

        stop = threading.Event()

        def _periodic():
            while not stop.wait(stats_interval):
                httpd.ctx.write_all_stats()

        stats_timer = (threading.Thread(target=_periodic, daemon=True,
                                        name="serve-stats"), stop)
        stats_timer[0].start()
    # SIGTERM (the signal an orchestrator, `kill`, or chip_smoke.py sends)
    # takes the same clean path as Ctrl-C: stop accepting, close the
    # serving stack, exit 0. Installed after create_server — the TPU
    # runtime installs its own fatal-signal reporter at backend init, and
    # this handler has to be the later one.
    def _terminate(signum, frame):
        raise KeyboardInterrupt

    signal.signal(signal.SIGTERM, _terminate)
    try:
        httpd.serve_forever()
    except KeyboardInterrupt:
        print("shutting down", flush=True)
    finally:
        if stats_timer is not None:
            stats_timer[1].set()
        httpd.ctx.close()
        httpd.server_close()


if __name__ == "__main__":
    main()
