"""Training state pytree.

Replaces the reference's scattered per-process mutable state — DDP-wrapped
``model`` + ``optimizer`` objects plus loose ``start_epoch`` / ``best_acc``
globals (``/root/reference/multi_proc_single_gpu.py:163-214``) — with one
immutable pytree that a jitted, donated ``train_step`` threads through the
epoch loop. ``epoch`` and ``best_acc`` live on the host side of the
checkpoint schema (see ``train/checkpoint.py``), matching the reference's
checkpoint dict (``:250-255``).

The optimizer is optax Adam with the reference's default ``lr=1e-3``
(``:191``), wrapped in ``inject_hyperparams`` so the per-epoch step-decay LR
(``:257-261``) is a plain float written into ``opt_state.hyperparams`` —
no re-jit when the LR changes.
"""

from __future__ import annotations

from typing import Any, Callable, Mapping

import flax.struct
import jax
import jax.numpy as jnp
import optax


@flax.struct.dataclass
class TrainState:
    """Immutable training state threaded through the jitted step."""

    step: jnp.ndarray  # i32 scalar, global step counter
    params: Any  # model parameter pytree
    opt_state: Any  # optax state (holds hyperparams.learning_rate)
    apply_fn: Callable = flax.struct.field(pytree_node=False)
    tx: optax.GradientTransformation = flax.struct.field(pytree_node=False)
    # True where the model's expert layers sow routing counters
    # (``ops/metrics.py`` ROUTING_COLLECTION): the train step then asks for that
    # collection and carries its sum out in ``MetricState.routing``.
    counters: bool = flax.struct.field(pytree_node=False, default=False)
    # Variables of the model that no gradient moves and the step updates
    # itself (the expert layers' selection bias, ``{"router_bias": ...}``:
    # ``train/steps.py move_selection_bias``), by flax collection, or None.
    # Kept apart from ``params`` so that the optimizer carries no moments
    # for them; donated, returned and checkpointed like the rest.
    buffers: Any = None

    @property
    def variables(self):
        """What ``apply_fn`` takes: ``params`` and, where the model has
        them, the ``buffers`` beside."""
        return self.params if self.buffers is None \
            else {**self.params, **self.buffers}

    def apply_gradients(self, grads):
        # The scope names these ops in a profile (README, "Profiling a run").
        with jax.named_scope("optimizer"):
            updates, new_opt_state = self.tx.update(grads, self.opt_state, self.params)
            new_params = optax.apply_updates(self.params, updates)
        return self.replace(step=self.step + 1, params=new_params, opt_state=new_opt_state)

    @property
    def learning_rate(self) -> float:
        return float(self.opt_state.hyperparams["learning_rate"])

    def with_learning_rate(self, lr: float) -> "TrainState":
        """Return state with the injected LR replaced (device-side, no re-jit)."""
        hyper = dict(self.opt_state.hyperparams)
        hyper["learning_rate"] = jnp.asarray(lr, jnp.float32)
        return self.replace(opt_state=self.opt_state._replace(hyperparams=hyper))


def make_optimizer(
    lr: float = 1e-3,
    optimizer: str = "adam",
    momentum: float = 0.9,
    weight_decay: float = 1e-4,
    mesh=None,
) -> optax.GradientTransformation:
    """Build the optimizer.

    ``adam`` with lr=1e-3 is the reference's active choice (``:191``); ``sgd``
    with momentum+weight-decay mirrors its commented-out alternative
    (``:192-194``) so the ``--momentum`` / ``--wd`` flags are functional here
    rather than dead as in the reference (SURVEY.md section 5 config notes).
    ``mesh`` is the mesh the train step runs over; only ``adam_pallas``
    needs it (a Mosaic kernel must be shard_mapped on a multi-device mesh).
    """
    if optimizer == "adam":
        return optax.inject_hyperparams(optax.adam)(learning_rate=lr)
    if optimizer == "adam_pallas":
        # Same state layout as adam (count/mu/nu) but the update is the
        # fused Pallas kernel (ops/pallas/adam.py) — checkpoint-compatible.
        from pytorch_distributed_mnist_tpu.ops.pallas.adam import pallas_adam

        # static: a Mesh is callable, and inject_hyperparams would take a
        # callable argument for a schedule.
        return optax.inject_hyperparams(pallas_adam, static_args="mesh")(
            learning_rate=lr, mesh=mesh)
    if optimizer == "sgd":

        def sgd_wd(learning_rate):
            return optax.chain(
                optax.add_decayed_weights(weight_decay),
                optax.sgd(learning_rate, momentum=momentum),
            )

        return optax.inject_hyperparams(sgd_wd)(learning_rate=lr)
    raise ValueError(f"unknown optimizer {optimizer!r}")


def create_train_state(
    model,
    rng: jax.Array,
    input_shape=(1, 28, 28, 1),
    lr: float = 1e-3,
    optimizer: str = "adam",
    momentum: float = 0.9,
    weight_decay: float = 1e-4,
    mesh=None,
) -> TrainState:
    """Initialize params (float32) and optimizer state for ``model``.
    ``mesh``: see :func:`make_optimizer`."""
    params = model.init(rng, jnp.zeros(input_shape, jnp.float32))
    return train_state_from_params(
        model, params, lr, optimizer, momentum, weight_decay, mesh)


def train_state_from_params(
    model,
    params,
    lr: float = 1e-3,
    optimizer: str = "adam",
    momentum: float = 0.9,
    weight_decay: float = 1e-4,
    mesh=None,
) -> TrainState:
    """The state :func:`create_train_state` makes, from parameters that
    exist already: a caller that needs the device's memory between
    initialising the weights and holding the optimizer's moments (the
    benchmark's reference check of a model that fills the chip) makes the
    two in turn."""
    tx = make_optimizer(lr, optimizer, momentum, weight_decay, mesh=mesh)
    # ``model.init`` returns every collection: what is not ``params`` is
    # state that no gradient moves.
    buffers = {k: v for k, v in params.items() if k != "params"} \
        if isinstance(params, Mapping) and "params" in params else {}
    if buffers:
        params = {"params": params["params"]}
    return TrainState(
        step=jnp.zeros((), jnp.int32),
        params=params,
        opt_state=tx.init(params),
        apply_fn=model.apply,
        tx=tx,
        counters=bool(getattr(model, "counters", False)),
        buffers=buffers or None,
    )
