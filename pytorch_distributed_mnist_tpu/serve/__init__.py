"""Serving subsystem: request-level inference decoupled from training.

The training side of this framework runs epochs; this package runs
REQUESTS — the north-star's "serves heavy traffic" capability. Pieces:

- ``engine.py``: :class:`InferenceEngine` — params + a fixed set of
  AOT-compiled forward programs at batch buckets (pad up, never
  recompile), built on the same forward-program builder ``--evaluate``
  uses (``train/steps.py make_forward_program``);
- ``batcher.py``: :class:`MicroBatcher` — dynamic micro-batching with a
  max-wait deadline, max-batch coalescing, and bounded-queue admission
  control (:class:`Overloaded` instead of unbounded latency);
- ``pool.py``: :class:`EnginePool` — the multi-chip data plane: one
  engine replica per local device (per-device params + AOT programs)
  behind a least-loaded dispatcher, driven through the batcher's
  pipelined dispatch/complete stages (``--serve-devices`` /
  ``--max-inflight``); with a sharded ``--serve-mode`` the chips
  partition into ``--serve-mesh``-sized mesh groups instead;
- ``programs.py``: the forward-program registry — given a model name
  and a ``--serve-mode`` (replicated / tensor / expert / pipeline,
  extensible), builds the serving mesh, derives param/input/output
  shardings from the training rule tables, and hands the engine a
  :class:`MeshPlacement` its bucket programs AOT-lower against, plus
  the checkpoint parallel-layout gate (``check_checkpoint_layout``)
  and the PRECISION plane (``--serve-precision``: f32 / bf16 / int8w /
  int8, extensible — install-time quantization with per-leaf scales as
  program arguments, so hot reload stays an atomic swap);
- ``canary.py``: :class:`ShadowCanary` — the shadow-traffic accuracy
  canary gating a quantized precision: the f32 baseline answers while
  a fraction of live batches shadows the quantized plane; promote
  after clean rows, auto-rollback past the disagreement budget,
  per-publish reset through the reload watcher;
- ``pipeline.py``: :class:`PipelineEngine` — the MPMD plane for
  pipeline-trained checkpoints: one INDEPENDENT program per stage chip
  (stage params split at the training stage boundaries), micro-batches
  streamed between stages with async device-to-device hops so stage k
  runs batch N while stage k+1 runs batch N-1;
- ``reload.py``: :class:`CheckpointWatcher` — polls a published
  checkpoint directory (``train/checkpoint.py`` conventions) and swaps
  params atomically between batches (fanned out per replica on a pool);
- ``control.py``: the CONTROL PLANE above the data plane — priority
  classes with per-class shed watermarks (:class:`ShedPolicy`),
  per-client token-bucket quotas (:class:`ClientQuotas`, 429 before a
  queue slot is spent), the SLO-driven :class:`AutoScaler` actuating
  the pool's resize path with hysteresis + cooldown, and the
  :class:`WeightedFairGate` sharing one chip budget across a
  ``--model-set`` of models;
- ``server.py``: the ``serve`` CLI subcommand — a stdlib HTTP JSON
  endpoint with ``/predict``, ``/healthz``, ``/stats``, ``/resize``
  (one model plane per ``--model-set`` entry, requests routed on their
  ``model`` field).

Drive it with ``tools/loadgen.py``. No cell of ``BENCHMARK.json``
measures it yet (``PERF.md`` section 7).
"""

from pytorch_distributed_mnist_tpu.serve.batcher import MicroBatcher, Overloaded
from pytorch_distributed_mnist_tpu.serve.canary import ShadowCanary
from pytorch_distributed_mnist_tpu.serve.control import (
    PRIORITY_CLASSES,
    AutoScaler,
    ClientQuotas,
    ShedPolicy,
    TokenBucket,
    WeightedFairGate,
)
from pytorch_distributed_mnist_tpu.serve.engine import InferenceEngine
from pytorch_distributed_mnist_tpu.serve.pipeline import PipelineEngine
from pytorch_distributed_mnist_tpu.serve.pool import EnginePool, EngineReplica
from pytorch_distributed_mnist_tpu.serve.programs import (
    SERVE_MODES,
    SERVE_PRECISIONS,
    MeshPlacement,
    ServePrecision,
    build_group_placements,
    build_placement,
    check_checkpoint_layout,
    servable_modes,
    serve_precisions,
)
from pytorch_distributed_mnist_tpu.serve.reload import CheckpointWatcher

__all__ = [
    "PRIORITY_CLASSES",
    "SERVE_MODES",
    "SERVE_PRECISIONS",
    "AutoScaler",
    "CheckpointWatcher",
    "ClientQuotas",
    "ShedPolicy",
    "TokenBucket",
    "WeightedFairGate",
    "EnginePool",
    "EngineReplica",
    "InferenceEngine",
    "MeshPlacement",
    "MicroBatcher",
    "Overloaded",
    "PipelineEngine",
    "ServePrecision",
    "ShadowCanary",
    "build_group_placements",
    "build_placement",
    "check_checkpoint_layout",
    "servable_modes",
    "serve_precisions",
]
