"""Mixture-of-experts model family (expert parallelism vehicle).

Two expert layers. Expert weights carry a leading expert dim that
``moe_ep_rules`` (parallel/expert.py) shards on the ``expert`` mesh axis.

``SparseExperts`` is the expert layer of the decoders (``laguna``,
models/decoder.py; ``instella``, models/instella.py): a sigmoid router over
``num_experts``, ``top_k`` a token, SwiGLU experts and one shared SwiGLU
whose width is the sum of the source's shared experts (one of 512 for
``laguna``, two of 1,408 as one of 2,816 for ``instella``). It is told
which experts it holds (``experts_held = (first, count)``), routes over
all of them and computes its own experts' part for the pairs that land
here, none dropped, by grouped matmuls
(``parallel/moe_dispatch.held_experts_forward``). With every expert held it
is the whole layer; with a share it is one chip's part of an
expert-parallel deployment, without the exchange. With ``selection_bias``
the ``top_k`` are chosen on ``score + bias``: the bias is a variable of the
collection ``router_bias`` that no gradient moves, the layer sows the load
of all ``num_experts`` beside it and the train step moves the bias against
the load (``train/steps.py``); with ``balance`` it sows the sequence-wise
balance term as ``aux_loss``.

``SwitchMoE`` (``moe_mlp``) is the small classifier's layer: top-1 routing,
ReLU experts with biases, a softmax gate. Its ``dispatch='dense'`` runs
every expert on every token and is kept for what it pins: math that does
not depend on the layout, which the expert-parallel equivalence tests rely
on (XLA turns its expert-summed combine einsum into an AllReduce over the
``expert`` axis). ``SparseExperts`` does not subsume it: the two differ in
activation, biases and gate, and share the router's float32 policy only.

``SwitchMoE`` routing is top-1 (switch). Two dispatch modes behind one
interface:

- ``dispatch='dense'`` (default): every expert's MLP runs on every token
  algebraically, the one-hot combine zeroes all but the routed expert, and
  under EP sharding each device only materializes its own experts'
  activations. Layout-independent math — the property the EP equivalence
  tests pin — and cheap at MNIST scale.
- ``dispatch='capacity'``: GShard/switch-transformer physical dispatch
  (parallel/moe_dispatch.py) — tokens go to one expert buffer bounded by
  ``capacity_factor``, crossing the ``expert`` mesh axis via
  ``lax.all_to_all``; over-capacity tokens drop (the classifier's residual
  carries them). Equal to dense dispatch when nothing drops.

Both modes sow the switch load-balancing auxiliary loss under
``intermediates/aux_loss`` (E * sum_e f_e p_e; 1.0 = uniform): top-1
routing can collapse onto one expert under real training, so trainers that
optimize the MoE for accuracy should add ``aux_weight * aux_loss`` to the
objective (pull it out with ``capture_intermediates``).
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from pytorch_distributed_mnist_tpu.models.registry import register_model
from pytorch_distributed_mnist_tpu.ops.metrics import (
    BIAS_COLLECTION,
    LOAD_COLLECTION,
    ROUTING_COLLECTION,
)
from pytorch_distributed_mnist_tpu.parallel.moe_dispatch import (
    expert_load,
    held_experts_forward,
    load_balance_loss,
    moe_capacity_forward,
    route_topk,
    sequence_balance_loss,
    top1_mask_gate,
)


def residual_init(depth: int):
    """Initialiser of a matrix that writes into the residual stream of a
    model ``depth`` blocks deep: ``normal(0.02 / sqrt(2 depth))``, GPT-2's.
    (With flax's default a decoder's attention, alike for every late token,
    swamps the embedding at the seed and nearly every token picks the same
    experts.)"""
    return nn.initializers.normal(stddev=0.02 / math.sqrt(2 * depth))


class SwiGLU(nn.Module):
    """``down(silu(gate(x)) * up(x))``, no biases."""

    width: int
    depth: int  # of the model: residual_init
    compute_dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        def dense(n, name, **kw):
            return nn.Dense(n, use_bias=False, dtype=self.compute_dtype,
                            name=name, **kw)

        h = nn.silu(dense(self.width, "gate")(x)) * dense(self.width, "up")(x)
        return dense(x.shape[-1], "down",
                     kernel_init=residual_init(self.depth))(h)


class SparseExperts(nn.Module):
    """Top-k-routed SwiGLU experts and a shared one: (..., C) -> (..., C).

    ``F = scale * sum_{e in chosen, held here} w_e E_e(x) + E_shared(x)``
    with ``w_e = s_e / sum_chosen s``, ``s = sigmoid(x W_r)`` over all
    ``num_experts`` (module docstring). Router scores are float32 at the
    highest matmul precision: the choice is discrete. ``selection_bias``:
    the chosen are the ``top_k`` of ``s + b`` (``w_e`` still reads ``s``).
    ``balance``: the sequence-wise balance term is sown as ``aux_loss``;
    the input is then (B, T, C), one sequence a row.
    """

    num_experts: int
    top_k: int
    width: int
    shared_width: int
    depth: int  # of the model: residual_init
    experts_held: Optional[Tuple[int, int]] = None  # (first, count); all
    routed_scale: float = 1.0
    selection_bias: bool = False
    balance: bool = False
    compute_dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        first, count = self.experts_held or (0, self.num_experts)
        c = x.shape[-1]
        tokens = x.reshape(-1, c)
        logits = nn.Dense(
            self.num_experts, use_bias=False, dtype=jnp.float32,
            precision=jax.lax.Precision.HIGHEST, name="router",
        )(tokens.astype(jnp.float32))
        bias = self.variable(
            BIAS_COLLECTION, "select", jnp.zeros, (self.num_experts,),
            jnp.float32).value if self.selection_bias else None
        with jax.named_scope("router"):
            scores = nn.sigmoid(logits)
            idx, weight = route_topk(
                scores, self.top_k, self.routed_scale, bias)
            if self.selection_bias or self.balance:
                # (B, E), one sequence a row: what balances the load reads
                # the pairs given to each of all ``num_experts``.
                load = expert_load(
                    idx.reshape((-1, x.shape[-2], self.top_k)),
                    self.num_experts)
            if self.balance:
                self.sow("intermediates", "aux_loss", sequence_balance_loss(
                    scores.reshape(load.shape[0], x.shape[-2], -1), load,
                    self.top_k))
        if self.selection_bias and not self.is_initializing():
            self.sow(LOAD_COLLECTION, "select", jnp.sum(load, axis=0))
        # For a caller that compares this layer with another computation of
        # it on the same choices (``mutable=['intermediates']``).
        self.sow("intermediates", "choices", idx)
        init = nn.initializers.lecun_normal(batch_axis=(0,))
        w_gate = self.param("w_gate", init, (count, c, self.width))
        w_up = self.param("w_up", init, (count, c, self.width))
        w_down = self.param(
            "w_down", residual_init(self.depth), (count, self.width, c))
        routed, counters = held_experts_forward(
            tokens.astype(self.compute_dtype), idx, weight,
            w_gate, w_up, w_down, first=first)
        if not self.is_initializing():  # init returns parameters only
            self.sow(ROUTING_COLLECTION, "routing", counters)
        shared = SwiGLU(self.shared_width, self.depth, self.compute_dtype,
                        name="shared")(x)
        return routed.reshape(x.shape).astype(shared.dtype) + shared


class SwitchMoE(nn.Module):
    """Top-1-routed mixture of expert MLPs: (B, C) -> (B, C)."""

    num_experts: int = 8
    hidden: int = 128
    compute_dtype: jnp.dtype = jnp.float32
    dispatch: str = "dense"
    capacity_factor: float = 1.25
    mesh: Optional[Mesh] = None
    expert_axis: str = "expert"
    data_axis: Optional[str] = "data"

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        e, h, c = self.num_experts, self.hidden, x.shape[-1]
        router = nn.Dense(e, dtype=jnp.float32, name="router")
        # Router math in f32: top-1 selection is a discrete decision; bf16
        # logit noise would make routing (and therefore loss) layout-dependent.
        probs = nn.softmax(router(x.astype(jnp.float32)), axis=-1)  # (B, E)
        self.sow("intermediates", "aux_loss", load_balance_loss(probs))

        w1 = self.param("w1", nn.initializers.lecun_normal(), (e, c, h))
        b1 = self.param("b1", nn.initializers.zeros, (e, h))
        w2 = self.param("w2", nn.initializers.lecun_normal(), (e, h, c))
        b2 = self.param("b2", nn.initializers.zeros, (e, c))

        if self.dispatch == "capacity":
            out = moe_capacity_forward(
                x.astype(self.compute_dtype), probs, w1, b1, w2, b2,
                capacity_factor=self.capacity_factor,
                compute_dtype=self.compute_dtype, mesh=self.mesh,
                expert_axis=self.expert_axis, data_axis=self.data_axis,
            )
            return out.astype(x.dtype)
        if self.dispatch != "dense":
            raise ValueError(f"unknown dispatch {self.dispatch!r}")

        mask, gate = top1_mask_gate(probs)  # (B, E) one-hot, (B,) prob
        gate = gate[:, None]
        xc = x.astype(self.compute_dtype)
        # (B, E, H): per-expert hidden; E shards on the 'expert' mesh axis.
        hdn = nn.relu(
            jnp.einsum("bc,ech->beh", xc, w1.astype(self.compute_dtype))
            + b1.astype(self.compute_dtype)
        )
        y = (
            jnp.einsum("beh,ehc->bec", hdn, w2.astype(self.compute_dtype))
            + b2.astype(self.compute_dtype)
        )  # (B, E, C)
        # One-hot combine: the sum over E is the EP AllReduce.
        out = jnp.einsum("bec,be->bc", y.astype(jnp.float32), mask) * gate
        return out.astype(x.dtype)


@register_model("moe_mlp")
class MoEClassifier(nn.Module):
    """flatten -> embed -> residual SwitchMoE -> head (MNIST classifier)."""

    num_classes: int = 10
    num_experts: int = 8
    embed_dim: int = 64
    hidden: int = 128
    compute_dtype: jnp.dtype = jnp.float32
    dispatch: str = "dense"
    capacity_factor: float = 1.25
    mesh: Optional[Mesh] = None
    expert_axis: str = "expert"
    data_axis: Optional[str] = "data"

    @nn.compact
    def __call__(self, x: jnp.ndarray, *, train: bool = False) -> jnp.ndarray:
        del train
        x = x.reshape((x.shape[0], -1)).astype(self.compute_dtype)  # (B, 784)
        x = nn.Dense(self.embed_dim, dtype=self.compute_dtype, name="embed")(x)
        x = nn.relu(x)
        x = x + SwitchMoE(
            self.num_experts, self.hidden, self.compute_dtype,
            dispatch=self.dispatch, capacity_factor=self.capacity_factor,
            mesh=self.mesh, expert_axis=self.expert_axis,
            data_axis=self.data_axis, name="moe",
        )(x)
        x = nn.Dense(self.num_classes, dtype=self.compute_dtype, name="head")(x)
        return x.astype(jnp.float32)
