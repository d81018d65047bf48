"""Pipeline x tensor parallelism: Megatron collectives inside the GPipe body.

Round-2 VERDICT flagged PP x TP as a rejected composition. The obstacle is
structural: DP x TP alone rides GSPMD (``parallel/tensor.py`` annotates
weights, XLA inserts the column/row-parallel collectives), but the pipeline
is an *explicit* shard_map program (``parallel/pipeline.py``) — and inside
a shard_map body there is no sharding propagation, so the TP matmuls must
close their own partial sums. This module supplies exactly that: the
transformer block re-expressed with explicit ``lax.psum`` over the
``model`` axis, run as the stage body of the unchanged GPipe scan on a
``data x stage x model`` mesh.

Layout note: the GSPMD rule table shards the flat ``(C, 3C)`` qkv kernel on
its output dim, which is *not* head-aligned (the 3C dim unpacks as
(3, H, D) — a contiguous 3C/tp slice straddles q/k/v). Explicit TP gets to
pick the layout, so here the attention kernels are stored head-major —
qkv ``(C, 3, H, D)``, proj ``(H, D, C)`` — and sharded on H: each model
rank owns ``H/tp`` whole heads, attention runs locally per head, and only
proj/mlp2 partial sums cross the axis (one psum each, the classic Megatron
pattern: 2 AllReduces per block per direction, riding ICI).

Parity contract: ``tp_block_apply`` reproduces ``models/attention.py``'s
``TransformerBlock`` math exactly (same flax LayerNorm/gelu modules, same
bf16-compute/f32-param policy); ``split_vit_params_tp`` /
``merge_vit_params_tp`` are bijective reshapes of the standard flax tree
(reference model zoo contrast: ``/root/reference/multi_proc_single_gpu.py
:119-126`` has a single Linear and no parallelism at all, SURVEY.md §2c).
Pinned by tests/test_pipeline_tp.py against the sequential dense model.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import flax.linen as nn

from pytorch_distributed_mnist_tpu.models.attention import (
    VisionTransformer,
    patchify,
)
from pytorch_distributed_mnist_tpu.ops.attention import full_attention
from pytorch_distributed_mnist_tpu.parallel.pipeline import pipeline_apply
from pytorch_distributed_mnist_tpu.parallel.pipeline_vit import (
    merge_vit_params,
    split_vit_params,
)

__all__ = [
    "split_vit_params_tp",
    "merge_vit_params_tp",
    "make_pipelined_tp_vit_apply",
    "pipelined_tp_state_sharding",
    "create_pipelined_tp_vit_state",
]


def split_vit_params_tp(params, num_heads: int):
    """Standard flax ViT tree -> pipelined layout with head-major attention.

    Same {embed, blocks, head} grouping as ``split_vit_params`` (leading
    (depth,) dim on every blocks leaf), with the attention leaves reshaped
    so the head dim is a real array axis a PartitionSpec can name:
    qkv kernel (depth, C, 3C) -> (depth, C, 3, H, D); qkv bias likewise;
    proj kernel (depth, C, C) -> (depth, H, D, C). Pure reshapes: bitwise
    inverse via ``merge_vit_params_tp``.
    """
    split = split_vit_params(params)
    attn = dict(split["blocks"]["attn"])
    qkv_k = attn["qkv"]["kernel"]
    depth, c, three_c = qkv_k.shape
    h = num_heads
    d = c // h
    assert three_c == 3 * c, (qkv_k.shape, c)
    attn["qkv"] = {
        "kernel": qkv_k.reshape(depth, c, 3, h, d),
        "bias": attn["qkv"]["bias"].reshape(depth, 3, h, d),
    }
    attn["proj"] = {
        "kernel": attn["proj"]["kernel"].reshape(depth, h, d, c),
        "bias": attn["proj"]["bias"],
    }
    blocks = dict(split["blocks"])
    blocks["attn"] = attn
    return {"embed": split["embed"], "blocks": blocks, "head": split["head"]}


def merge_vit_params_tp(split_tp):
    """Pipelined head-major layout -> standard flax tree (exact inverse)."""
    attn = dict(split_tp["blocks"]["attn"])
    qkv_k = attn["qkv"]["kernel"]
    depth, c, three, h, d = qkv_k.shape
    attn["qkv"] = {
        "kernel": qkv_k.reshape(depth, c, 3 * h * d),
        "bias": attn["qkv"]["bias"].reshape(depth, 3 * h * d),
    }
    attn["proj"] = {
        "kernel": attn["proj"]["kernel"].reshape(depth, h * d, c),
        "bias": attn["proj"]["bias"],
    }
    blocks = dict(split_tp["blocks"])
    blocks["attn"] = attn
    return merge_vit_params(
        {"embed": split_tp["embed"], "blocks": blocks,
         "head": split_tp["head"]})


# PartitionSpec per blocks leaf, keyed by its last two path keys. First
# axis entry is the stage dim; 'model' lands on the head dim (attention)
# or the MLP hidden dim — the Megatron column->row split.
def _block_rules(stage_axis: str, tp_axis: str):
    return {
        ("qkv", "kernel"): P(stage_axis, None, None, tp_axis, None),
        ("qkv", "bias"): P(stage_axis, None, tp_axis, None),
        ("proj", "kernel"): P(stage_axis, tp_axis, None, None),
        ("mlp1", "kernel"): P(stage_axis, None, tp_axis),
        ("mlp1", "bias"): P(stage_axis, tp_axis),
        ("mlp2", "kernel"): P(stage_axis, tp_axis, None),
    }


def _last2(path):
    keys = [str(getattr(k, "key", getattr(k, "name", None)))
            for k in path
            if getattr(k, "key", getattr(k, "name", None)) is not None]
    return tuple(keys[-2:])


def block_param_specs(blocks_tree, stage_axis: str, tp_axis: str):
    """PartitionSpec pytree for the (staged) blocks params: every leaf
    gets the stage dim; Megatron-split leaves add the model axis."""
    rules = _block_rules(stage_axis, tp_axis)
    return jax.tree_util.tree_map_with_path(
        lambda path, _: rules.get(_last2(path), P(stage_axis)), blocks_tree)


def tp_block_apply(bp, h, *, tp_axis: str, compute_dtype, mlp_ratio: int,
                   attention_fn=None):
    """One transformer block with model-axis-sharded weights.

    ``bp`` holds this device's shard: whole heads for qkv/proj, a slice of
    the MLP hidden dim for mlp1/mlp2. Residuals, LayerNorms, and ``h``
    itself stay replicated over ``tp_axis``; the two row-parallel matmuls
    (proj, mlp2) produce partial sums closed by one psum each — after
    which every model rank again holds identical activations, which is
    what lets the surrounding GPipe ppermute stay axis-local.

    Math parity with models/attention.py's TransformerBlock: identical
    flax LayerNorm/gelu modules and bf16 policy; the only difference is
    float reassociation in the psum'd partials.
    """
    del mlp_ratio  # implied by the shard shapes; kept for signature clarity
    cd = compute_dtype
    ln = nn.LayerNorm(dtype=cd)

    x = h
    y = ln.apply({"params": bp["ln1"]}, x)
    a = bp["attn"]
    wqkv = a["qkv"]["kernel"].astype(cd)        # (C, 3, Hl, D)
    bqkv = a["qkv"]["bias"].astype(cd)          # (3, Hl, D)
    qkv = jnp.einsum("btc,cahd->btahd", y.astype(cd), wqkv) + bqkv
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    attend = attention_fn or full_attention
    o = attend(q, k, v)                          # (B, T, Hl, D), local heads
    wproj = a["proj"]["kernel"].astype(cd)       # (Hl, D, C)
    part = jnp.einsum("bthd,hdc->btc", o.astype(cd), wproj)
    o = lax.psum(part, tp_axis) + a["proj"]["bias"].astype(cd)
    x = x + o

    y = ln.apply({"params": bp["ln2"]}, x)
    u = y.astype(cd) @ bp["mlp1"]["kernel"].astype(cd) \
        + bp["mlp1"]["bias"].astype(cd)          # (B, T, 4C/tp)
    u = nn.gelu(u)
    v2 = u @ bp["mlp2"]["kernel"].astype(cd)     # partial (B, T, C)
    v2 = lax.psum(v2, tp_axis) + bp["mlp2"]["bias"].astype(cd)
    return x + v2


def make_pipelined_tp_vit_apply(
    model: VisionTransformer,
    mesh: Mesh,
    *,
    stage_axis: str = "stage",
    tp_axis: str = "model",
    data_axis: Optional[str] = "data",
    num_microbatches: Optional[int] = None,
):
    """``apply_fn(split_tp_params, x, train=False) -> logits``.

    Drop-in for ``model.apply`` in a TrainState, like
    ``make_pipelined_vit_apply`` — but the stage body runs the explicit-TP
    block, so the same GPipe scan/ppermute schedule now also spans the
    ``model`` axis of a data x stage x model mesh.
    """
    n_stages = mesh.shape[stage_axis]
    tp = mesh.shape[tp_axis]
    if model.depth % n_stages:
        raise ValueError(
            f"vit depth {model.depth} not divisible by {n_stages} pipeline "
            f"stages")
    if model.num_heads % tp:
        raise ValueError(
            f"vit heads {model.num_heads} not divisible by "
            f"--tensor-parallel {tp}")
    hidden = model.embed_dim * model.mlp_ratio
    if hidden % tp:
        raise ValueError(
            f"vit MLP hidden dim {hidden} not divisible by "
            f"--tensor-parallel {tp}")
    cd = model.compute_dtype
    embed_mod = nn.Dense(model.embed_dim, dtype=cd)
    ln_mod = nn.LayerNorm(dtype=cd)
    head_mod = nn.Dense(model.num_classes, dtype=cd)

    def stage_fn(stage_blocks, h):
        def body(h, bp):
            return tp_block_apply(
                bp, h, tp_axis=tp_axis, compute_dtype=cd,
                mlp_ratio=model.mlp_ratio,
                attention_fn=model.attention_fn,
            ), None

        if model.remat:
            body = jax.checkpoint(body)
        h, _ = lax.scan(body, h, stage_blocks)
        return h

    def apply_fn(split_tp, x, *, train: bool = False):
        del train
        h = patchify(x, model.patch_size, cd)
        h = embed_mod.apply({"params": split_tp["embed"]["embed"]}, h)
        h = h + split_tp["embed"]["pos_embed"].astype(cd)
        staged = jax.tree_util.tree_map(
            lambda a: a.reshape((n_stages, a.shape[0] // n_stages)
                                + a.shape[1:]),
            split_tp["blocks"],
        )
        # Specs carry the extra (k = depth/S) dim the reshape introduced
        # between the stage dim and the weight dims.
        def staged_spec(spec):
            return P(spec[0], None, *spec[1:])

        specs = jax.tree_util.tree_map(
            staged_spec,
            block_param_specs(split_tp["blocks"], stage_axis, tp_axis),
            is_leaf=lambda s: isinstance(s, P),
        )
        h = pipeline_apply(
            stage_fn, staged, h, mesh=mesh, axis=stage_axis,
            num_microbatches=num_microbatches, data_axis=data_axis,
            param_specs=specs,
        )
        h = ln_mod.apply({"params": split_tp["head"]["ln_f"]}, h)
        h = jnp.mean(h, axis=1)
        h = head_mod.apply({"params": split_tp["head"]["head"]}, h)
        return h.astype(jnp.float32)

    return apply_fn


def pipelined_tp_state_sharding(state, mesh: Mesh,
                                stage_axis: str = "stage",
                                tp_axis: str = "model"):
    """NamedSharding pytree for the whole TrainState: blocks leaves get
    stage dim 0 plus their Megatron model-axis dims; everything else
    replicates. Adam mu/nu mirror the param tree, so one rule pass covers
    them (same property as ``parallel/tensor.py``)."""
    rules = _block_rules(stage_axis, tp_axis)

    def spec_for(path, leaf):
        keys = [str(getattr(k, "key", getattr(k, "name", None)))
                for k in path
                if getattr(k, "key", getattr(k, "name", None)) is not None]
        if "blocks" in keys and getattr(leaf, "ndim", 0) >= 1:
            return NamedSharding(
                mesh, rules.get(tuple(keys[-2:]), P(stage_axis)))
        return NamedSharding(mesh, P())

    return jax.tree_util.tree_map_with_path(spec_for, state)


def create_pipelined_tp_vit_state(
    model: VisionTransformer,
    rng: jax.Array,
    mesh: Mesh,
    *,
    stage_axis: str = "stage",
    tp_axis: str = "model",
    data_axis: Optional[str] = "data",
    num_microbatches: Optional[int] = None,
    lr: float = 1e-3,
    optimizer: str = "adam",
    momentum: float = 0.9,
    weight_decay: float = 1e-4,
    place: bool = True,
):
    """``(state, state_sharding)`` for the PP x TP ViT — the same pair
    contract as ``create_pipelined_vit_state`` / ``shard_state``, consumed
    by the standard train/eval steps unchanged. ``place=False`` defers
    placement for callers composing ZeRO on top (same rationale as
    ``create_pipelined_vit_state``)."""
    from pytorch_distributed_mnist_tpu.parallel.mesh import place_state
    from pytorch_distributed_mnist_tpu.train.state import (
        TrainState,
        make_optimizer,
    )

    params = split_vit_params_tp(
        model.init(rng, jnp.zeros((1, 28, 28, 1), jnp.float32)),
        model.num_heads,
    )
    tx = make_optimizer(lr, optimizer, momentum, weight_decay, mesh=mesh)
    apply_fn = make_pipelined_tp_vit_apply(
        model, mesh, stage_axis=stage_axis, tp_axis=tp_axis,
        data_axis=data_axis, num_microbatches=num_microbatches,
    )
    state = TrainState(
        step=jnp.zeros((), jnp.int32),
        params=params,
        opt_state=tx.init(params),
        apply_fn=apply_fn,
        tx=tx,
    )
    sharding = pipelined_tp_state_sharding(state, mesh, stage_axis, tp_axis)
    if not place:
        return state, sharding
    return place_state(state, sharding), sharding
