"""Pipeline-parallel ViT: embed -> pipelined transformer blocks -> head.

Lifts ``parallel/pipeline.py``'s uniform-stage GPipe machinery onto a real
model from the zoo (VERDICT round 1: the pipeline only ever ran a toy MLP).
The shape-ragged ends — patch embedding ((B, 28, 28, 1) -> (B, T, C)) and
the pooling head ((B, T, C) -> (B, 10)) — run replicated over the ``stage``
axis (they are a fraction of a percent of the FLOPs); the shape-uniform
middle, ``depth`` transformer blocks, is exactly what the GPipe scan
pipelines: stage ``s`` holds blocks ``[s*k, (s+1)*k)`` (``k = depth / S``)
as one stacked pytree sharded on ``stage``, and applies them with a local
``lax.scan``.

The reference has no pipeline parallelism at all (SURVEY.md section 2c:
PP ABSENT, the model is one Linear, ``/root/reference/
multi_proc_single_gpu.py:119-126``); this exists because the N-D mesh
design makes PP a layout + one collective program rather than a scheduler.

Param layout: a *pipelined* train state stores the ViT params re-grouped as

    {"embed": {embed, pos_embed}, "blocks": <one block tree, leaves with
     leading (depth,) dim>, "head": {ln_f, head}}

so the PP sharding rule is a single statement — every ``blocks`` leaf is
``P("stage")`` on dim 0 — and Adam moments inherit it through the pytree
mirror. ``split_vit_params`` / ``merge_vit_params`` convert to/from the
standard flax tree (bitwise: pure stack/unstack), pinned by
tests/test_pipeline_vit.py's forward-equality test.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import flax.linen as nn

from pytorch_distributed_mnist_tpu.models.attention import (
    TransformerBlock,
    VisionTransformer,
    patchify,
)
from pytorch_distributed_mnist_tpu.parallel.pipeline import pipeline_apply

__all__ = [
    "split_vit_params",
    "merge_vit_params",
    "make_pipelined_vit_apply",
    "make_stage_forward_fns",
    "pipeline_stage_rules",
    "pipelined_state_sharding",
    "create_pipelined_vit_state",
    "split_stage_params",
]


def split_vit_params(params):
    """Standard ViT flax tree -> pipelined {embed, blocks, head} layout."""
    p = params["params"]
    depth = sum(1 for k in p if k.startswith("block"))
    if not depth:
        # A blockless tree (wrong model family) would otherwise die in
        # tree_map with an argument-count error; name the real problem.
        raise ValueError(
            f"params have no block* layers to pipeline (keys: "
            f"{sorted(p)})")
    blocks = [p[f"block{i}"] for i in range(depth)]
    stacked = jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *blocks)
    return {
        "embed": {"embed": p["embed"], "pos_embed": p["pos_embed"]},
        "blocks": stacked,
        "head": {"ln_f": p["ln_f"], "head": p["head"]},
    }


def merge_vit_params(split):
    """Pipelined layout -> standard flax tree (exact inverse of split)."""
    depth = jax.tree_util.tree_leaves(split["blocks"])[0].shape[0]
    p = {
        "embed": split["embed"]["embed"],
        "pos_embed": split["embed"]["pos_embed"],
        "ln_f": split["head"]["ln_f"],
        "head": split["head"]["head"],
    }
    for i in range(depth):
        p[f"block{i}"] = jax.tree_util.tree_map(
            lambda a, i=i: a[i], split["blocks"]
        )
    return {"params": p}


def make_pipelined_vit_apply(
    model: VisionTransformer,
    mesh: Mesh,
    *,
    axis: str = "stage",
    data_axis: Optional[str] = None,
    num_microbatches: Optional[int] = None,
):
    """Return ``apply_fn(split_params, x, train=False) -> logits``.

    Drop-in for ``model.apply`` in a TrainState (same signature the train
    steps call), but the transformer blocks execute as an S-stage GPipe
    over ``mesh[axis]`` with the batch optionally sharded on ``data_axis``.
    """
    n_stages = mesh.shape[axis]
    if model.depth % n_stages:
        raise ValueError(
            f"vit depth {model.depth} not divisible by {n_stages} pipeline "
            f"stages"
        )
    cd = model.compute_dtype
    embed_mod = nn.Dense(model.embed_dim, dtype=cd)
    block_mod = TransformerBlock(
        model.num_heads, model.mlp_ratio, model.attention_fn, cd
    )
    ln_mod = nn.LayerNorm(dtype=cd)
    head_mod = nn.Dense(model.num_classes, dtype=cd)

    def stage_fn(stage_blocks, h):
        # stage_blocks: this stage's k blocks, leaves (k, ...); apply in
        # order with a scan so the stage body stays a single trace.
        def body(h, bp):
            return block_mod.apply({"params": bp}, h), None

        if model.remat:
            # Same contract as the non-pipelined model's nn.remat blocks:
            # per-block activations recompute in backward, so each stage
            # holds one block's activations instead of k.
            body = jax.checkpoint(body)
        h, _ = lax.scan(body, h, stage_blocks)
        return h

    def apply_fn(split, x, *, train: bool = False):
        del train
        h = patchify(x, model.patch_size, cd)
        h = embed_mod.apply({"params": split["embed"]["embed"]}, h)
        h = h + split["embed"]["pos_embed"].astype(cd)
        # leaves (depth, ...) sharded on dim 0 -> (S, k, ...): a local
        # reshape of the sharded dim (depth % S == 0 checked above).
        staged = jax.tree_util.tree_map(
            lambda a: a.reshape((n_stages, a.shape[0] // n_stages)
                                + a.shape[1:]),
            split["blocks"],
        )
        h = pipeline_apply(
            stage_fn, staged, h, mesh=mesh, axis=axis,
            num_microbatches=num_microbatches, data_axis=data_axis,
        )
        h = ln_mod.apply({"params": split["head"]["ln_f"]}, h)
        h = jnp.mean(h, axis=1)
        h = head_mod.apply({"params": split["head"]["head"]}, h)
        return h.astype(jnp.float32)

    return apply_fn


def pipeline_stage_rules(axis: str = "stage"):
    """Callable rule table for the serve registry (``leaf_spec`` accepts
    callables): every leaf under ``blocks`` is ``P(axis)`` on dim 0 — the
    stacked depth dim, which is the stage seam — everything else
    replicated. The divisibility walk ``serve/programs.py::
    validate_serve_mode`` runs over these reduces to exactly
    "depth % stages == 0", the same constraint
    ``make_pipelined_vit_apply`` enforces for training."""

    def rules(path):
        keys = [str(getattr(k, "key", getattr(k, "name", None)))
                for k in path]
        return P(axis) if "blocks" in keys else P()

    return rules


def split_stage_params(split, n_stages: int):
    """Pipelined ``{embed, blocks, head}`` params -> per-stage trees.

    Stage ``s`` gets blocks ``[s*k, (s+1)*k)`` (``k = depth / S`` — the
    SAME boundaries the training pipeline's stage-axis sharding cuts, so
    a served stage holds exactly what its training twin held); stage 0
    additionally carries ``embed`` and the last stage ``head`` (the
    shape-ragged ends, replicated over ``stage`` in training, belong to
    the end stages when each stage is an independent program). Pure
    dim-0 slicing — works on host numpy and jax arrays alike, no copy
    beyond the slice. The MPMD serve plane (``serve/pipeline.py``)
    splits every checkpoint through here.
    """
    blocks = split["blocks"]
    depth = jax.tree_util.tree_leaves(blocks)[0].shape[0]
    if n_stages < 1 or depth % n_stages:
        raise ValueError(
            f"vit depth {depth} not divisible by {n_stages} pipeline "
            f"stages"
        )
    k = depth // n_stages
    stages = []
    for s in range(n_stages):
        tree = {"blocks": jax.tree_util.tree_map(
            lambda a, s=s: a[s * k:(s + 1) * k], blocks)}
        if s == 0:
            tree["embed"] = split["embed"]
        if s == n_stages - 1:
            tree["head"] = split["head"]
        stages.append(tree)
    return stages


def make_stage_forward_fns(model: VisionTransformer, n_stages: int):
    """Per-stage inference forwards: ``[forward_k(stage_params, x) -> y]``.

    Stage 0 maps images to embedded tokens and applies its blocks;
    middle stages are pure block stacks ((B, T, C) in and out, the
    uniform-activation property the GPipe schedule relies on); the last
    stage closes with LN -> mean-pool -> head -> float32 logits. The
    module set and application order are literally
    ``make_pipelined_vit_apply``'s (same ``embed_mod``/``block_mod``/
    ``ln_mod``/``head_mod`` construction, same ``lax.scan`` over the
    stage's stacked blocks), so chaining the S forwards reproduces the
    trained pipeline's math — each one just compiles as an INDEPENDENT
    program on its own chip (``serve/pipeline.py``), no remat (inference
    keeps no activations).
    """
    if model.depth % n_stages:
        raise ValueError(
            f"vit depth {model.depth} not divisible by {n_stages} "
            f"pipeline stages"
        )
    cd = model.compute_dtype
    embed_mod = nn.Dense(model.embed_dim, dtype=cd)
    block_mod = TransformerBlock(
        model.num_heads, model.mlp_ratio, model.attention_fn, cd
    )
    ln_mod = nn.LayerNorm(dtype=cd)
    head_mod = nn.Dense(model.num_classes, dtype=cd)

    def apply_blocks(stage_blocks, h):
        def body(h, bp):
            return block_mod.apply({"params": bp}, h), None

        h, _ = lax.scan(body, h, stage_blocks)
        return h

    def make_forward(s: int):
        def forward(stage_params, x):
            h = x
            if s == 0:
                h = patchify(h, model.patch_size, cd)
                h = embed_mod.apply(
                    {"params": stage_params["embed"]["embed"]}, h)
                h = h + stage_params["embed"]["pos_embed"].astype(cd)
            h = apply_blocks(stage_params["blocks"], h)
            if s == n_stages - 1:
                h = ln_mod.apply({"params": stage_params["head"]["ln_f"]}, h)
                h = jnp.mean(h, axis=1)
                h = head_mod.apply({"params": stage_params["head"]["head"]},
                                   h)
                h = h.astype(jnp.float32)
            return h

        return forward

    return [make_forward(s) for s in range(n_stages)]


def create_pipelined_vit_state(
    model: VisionTransformer,
    rng: jax.Array,
    mesh: Mesh,
    *,
    axis: str = "stage",
    data_axis: Optional[str] = None,
    num_microbatches: Optional[int] = None,
    lr: float = 1e-3,
    optimizer: str = "adam",
    momentum: float = 0.9,
    weight_decay: float = 1e-4,
    place: bool = True,
):
    """Return ``(state, state_sharding)``: a TrainState whose params use
    the pipelined layout and whose ``apply_fn`` runs the GPipe program —
    a drop-in for ``create_train_state`` that the standard train/eval
    steps consume unchanged (same pair convention as
    ``shard_state_zero1``).

    ``place=False`` returns the HOST state unplaced (sharding tree still
    computed): a caller composing a further layout on top (ZeRO moments)
    must place exactly once onto the composed sharding — placing here
    first would commit the arrays and make the multi-host re-placement a
    cross-host reshard (see ``parallel.mesh.place_state``).
    """
    from pytorch_distributed_mnist_tpu.parallel.mesh import place_state
    from pytorch_distributed_mnist_tpu.train.state import (
        TrainState,
        make_optimizer,
    )

    params = split_vit_params(
        model.init(rng, jnp.zeros((1, 28, 28, 1), jnp.float32))
    )
    tx = make_optimizer(lr, optimizer, momentum, weight_decay, mesh=mesh)
    apply_fn = make_pipelined_vit_apply(
        model, mesh, axis=axis, data_axis=data_axis,
        num_microbatches=num_microbatches,
    )
    state = TrainState(
        step=jnp.zeros((), jnp.int32),
        params=params,
        opt_state=tx.init(params),
        apply_fn=apply_fn,
        tx=tx,
    )
    sharding = pipelined_state_sharding(state, mesh, axis)
    if not place:
        return state, sharding
    return place_state(state, sharding), sharding


def pipelined_state_sharding(state, mesh: Mesh, axis: str = "stage"):
    """NamedSharding pytree: ``blocks`` leaves P(axis) on dim 0, rest
    replicated. Adam ``mu``/``nu`` mirror the param tree, so the same
    path test covers them."""

    def spec_for(path, leaf):
        keys = [getattr(k, "key", getattr(k, "name", None)) for k in path]
        if "blocks" in keys and getattr(leaf, "ndim", 0) >= 1:
            return NamedSharding(mesh, P(axis))
        return NamedSharding(mesh, P())

    return jax.tree_util.tree_map_with_path(spec_for, state)
