"""On-device metric accumulators.

Parity targets: ``Average`` (``/root/reference/multi_proc_single_gpu.py:28-43``)
— running weighted mean, ``update(value, n)`` accumulates ``sum += value*n``,
``count += n``, formatted to 6 decimals — and ``Accuracy`` (``:46-65``) —
argmax over the class axis, counts ``pred == target``, formatted as percent
with 2 decimals.

The TPU design differs deliberately from the reference's hot-loop behavior:
the reference calls ``.item()`` on device tensors every batch (``:94``,
``:62``), forcing a device->host sync per step. Here the accumulator state
(``MetricState``) is a pytree of device scalars updated *inside* the jitted
step; host transfer happens once per epoch when ``Average``/``Accuracy``
read it out (SURVEY.md section 3.2).
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax.numpy as jnp

from pytorch_distributed_mnist_tpu.ops.loss import example_weights

# Entries of ``MetricState.routing``, what an expert layer counts of one
# step (``parallel/moe_dispatch.held_experts_forward``), summed over layers
# and steps: (token, choice) pairs that landed on an expert held here,
# pairs routed in all, pairs dropped (named a held expert and were not
# served: ``moe_dispatch._served``), the fullest held expert's tokens over
# the mean, and the number of summands (layers x steps).
ROUTING_COUNTERS = ("landed", "routed", "dropped", "max_over_mean",
                    "summands")
# The flax collection the expert layers sow them into (``models/moe.py``);
# the train step asks for it where ``TrainState.counters`` says so.
ROUTING_COLLECTION = "counters"
# Where an expert layer chooses on ``score + bias``: the collection of the
# bias (a variable no gradient moves, ``TrainState.buffers``) and the one
# the layer sows its load into, the (token, choice) pairs given to each of
# all its experts, under the bias's own name, so that the two trees match
# leaf for leaf (``train/steps.py move_selection_bias``).
BIAS_COLLECTION = "router_bias"
LOAD_COLLECTION = "expert_load"
# What a step adds to ``MetricState.routing``, after ROUTING_COUNTERS,
# where its state carries a selection bias: the bias's range after the
# step's update (max - min over a layer's experts, the largest over the
# layers), the loss of the multi-token-prediction head (0 without one), the
# objective whose gradients the step applied (both cross-entropies and the
# sown term under the job's weights) and the number of steps summed.
STEP_COUNTERS = ("bias_range", "mtp_loss", "objective", "steps")


class MetricState(NamedTuple):
    """Device-resident accumulator: weighted loss sum, correct count, count."""

    loss_sum: jnp.ndarray  # f32 scalar: sum of per-example losses
    correct: jnp.ndarray  # f32 scalar: number of correct predictions
    count: jnp.ndarray  # f32 scalar: number of examples seen
    # f32 (len(ROUTING_COUNTERS),) where the model has expert layers that
    # count their routing, else None (no leaf: the programs of the other
    # models are what they were).
    routing: Optional[jnp.ndarray] = None


def metrics_init(routing: int = 0) -> MetricState:
    """``routing``: how many routing counters the step returns (0: none)."""
    zero = jnp.zeros((), jnp.float32)
    return MetricState(
        zero, zero, zero,
        jnp.zeros((routing,), jnp.float32) if routing else None)


def metrics_update(
    state: MetricState,
    loss: jnp.ndarray,
    logits: jnp.ndarray,
    labels: jnp.ndarray,
    mask: jnp.ndarray | None = None,
) -> MetricState:
    """Fold one batch into the accumulator (jit-friendly, no host sync).

    ``loss`` is the batch-*mean* loss (as produced by ``ops.loss.cross_entropy``);
    it is re-weighted by the number of *real* examples exactly like the
    reference's ``update(loss.item(), data.size(0))`` (``:94``, ``:41-43``).
    ``mask`` (0/1 per example) excludes eval-padding examples from all three
    counters, so padded samples are never double-counted — the reference
    never pads (its test loader just emits a ragged final batch).

    Token batches (``logits`` (B, T, V), ``labels`` (B, T)) count
    positions, less those ``ops.loss.example_weights`` leaves out.
    """
    mask = example_weights(labels, mask)
    if mask is None:
        n = jnp.asarray(labels.shape[0], jnp.float32)
        hit = (jnp.argmax(logits, axis=-1) == labels).astype(jnp.float32)
    else:
        mask = mask.astype(jnp.float32)
        n = jnp.sum(mask)
        hit = (jnp.argmax(logits, axis=-1) == labels).astype(jnp.float32) * mask
    return MetricState(
        loss_sum=state.loss_sum + loss.astype(jnp.float32) * n,
        correct=state.correct + jnp.sum(hit),
        count=state.count + n,
        routing=state.routing,
    )


def add_routing(state: MetricState, counters) -> MetricState:
    """``state`` with one step's routing counters (or ``None``) added."""
    if counters is None:
        return state
    return state._replace(
        routing=counters if state.routing is None
        else state.routing + counters)


def metrics_merge(a: MetricState, b: MetricState) -> MetricState:
    """Combine two accumulators (e.g. across devices after a psum gather,
    or one step's into an epoch's)."""
    return add_routing(
        MetricState(a.loss_sum + b.loss_sum, a.correct + b.correct,
                    a.count + b.count, a.routing), b.routing)


class Average:
    """Host-side running weighted mean; formatting parity with reference ``Average``.

    ``__str__`` renders the mean to 6 decimal places, matching
    ``/root/reference/multi_proc_single_gpu.py:34-35``.
    """

    def __init__(self) -> None:
        self.sum = 0.0
        self.count = 0

    @property
    def average(self) -> float:
        if self.count == 0:
            return 0.0
        return self.sum / self.count

    def update(self, value: float, number: int = 1) -> None:
        self.sum += float(value) * number
        self.count += number

    def __str__(self) -> str:
        return f"{self.average:.6f}"


class Accuracy:
    """Host-side accuracy meter; formatting parity with reference ``Accuracy``.

    ``__str__`` renders a percentage with 2 decimals, matching
    ``/root/reference/multi_proc_single_gpu.py:52-53``.
    """

    def __init__(self) -> None:
        self.correct = 0
        self.count = 0

    @property
    def accuracy(self) -> float:
        if self.count == 0:
            return 0.0
        return self.correct / self.count

    def update(self, correct: int, count: int) -> None:
        self.correct += int(correct)
        self.count += int(count)

    def update_from_state(self, state: MetricState) -> None:
        self.correct += int(state.correct)
        self.count += int(state.count)

    def __str__(self) -> str:
        return f"{self.accuracy * 100:.2f}%"
