"""Tensor parallelism: param-sharding rules over an N-D mesh (GSPMD).

The reference's only strategy is data parallelism — DDP replicates every
weight (``/root/reference/multi_proc_single_gpu.py:188-189``; SURVEY.md
section 2c marks TP ABSENT). This framework keeps the mesh N-dimensional so
TP is a ``PartitionSpec`` change, not new machinery (SURVEY.md section 2c's
closing note): the functions here produce a sharding pytree for the whole
``TrainState`` from a small table of path-suffix rules, and a jitted step
factory whose in/out shardings carry it. XLA's sharding propagation then
inserts the Megatron-pattern collectives (column-parallel matmul ->
row-parallel matmul -> AllReduce of the partial sums) over the ``model``
mesh axis — on TPU these ride ICI next to the data-axis gradient AllReduce.

Rule matching is by the LAST TWO path keys of each leaf (e.g.
``('qkv', 'kernel')``). Optimizer moments (Adam ``mu``/``nu``) are full
param-tree replicas inside ``opt_state``, so their leaf paths end with the
same two keys — one rule table shards params and both moments consistently,
the property that makes this a ZeRO-free but layout-consistent design.

**Collective-matmul overlap** (``--tp-overlap``, off by default): the GSPMD
path above leaves the Megatron collectives' placement to XLA — on the
sequence-parallel layout that means a blocking allgather of the sequence
shard sits in front of every column-parallel matmul. ``allgather_matmul``
writes the overlapped schedule out explicitly (the "collective matmul" of
Wang et al., "Overlap Communication with Dependent Computation via
Decomposition", ASPLOS'23): the gather decomposes into ``tp - 1`` ring
``ppermute`` hops, and the matmul into one per-shard row-block step, so
hop k's transfer rides ICI while step k-1's block is on the MXU. Row
blocks of a matmul are independent, so the decomposition is exact — the
overlapped path is trajectory-equal to the unoverlapped one (pinned by
``tests/test_tp_overlap.py``). The fences are the same
``lax.optimization_barrier`` chain idiom as ``parallel/zero_overlap.py``:
they pin issue order without inventing data dependencies on unrelated
compute. ``make_overlap_tp_vit_apply`` embeds it in a Megatron-SP
(sequence-sharded residual stream) ViT body on the head-major explicit
layout from ``parallel/pipeline_tp.py``.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# Megatron-style column->row split for the ViT transformer blocks
# (models/attention.py): qkv/mlp1 shard their OUTPUT feature dim (column
# parallel — activations come out head/feature-sharded), proj/mlp2 shard
# their INPUT dim (row parallel — partial sums AllReduce back to replicated).
def vit_tp_rules(axis: str = "model") -> Dict[Tuple[str, str], P]:
    return {
        ("qkv", "kernel"): P(None, axis),
        ("qkv", "bias"): P(axis),
        ("proj", "kernel"): P(axis, None),
        ("mlp1", "kernel"): P(None, axis),
        ("mlp1", "bias"): P(axis),
        ("mlp2", "kernel"): P(axis, None),
    }


def _path_keys(path) -> Tuple[str, ...]:
    keys = []
    for entry in path:
        key = getattr(entry, "key", getattr(entry, "name", None))
        if key is not None:
            keys.append(str(key))
    return tuple(keys)


def leaf_spec(path, rules) -> P:
    """PartitionSpec for one leaf: match the last two path keys, default P().

    ``rules`` may also be a CALLABLE ``rules(path) -> PartitionSpec`` for
    layouts a two-key suffix table cannot express — the pipeline layout's
    "every leaf under ``blocks``" rule (``parallel/pipeline_vit.py::
    pipeline_stage_rules``) is the motivating case; the serve registry's
    divisibility walk (``serve/programs.py::validate_serve_mode``) feeds
    both forms through here.
    """
    if callable(rules):
        return rules(path)
    keys = _path_keys(path)
    return rules.get(tuple(keys[-2:]), P())


def state_shardings(state, mesh: Mesh, rules: Dict[Tuple[str, str], P]):
    """NamedSharding pytree mirroring ``state`` (params AND optimizer moments).

    Leaves with no matching rule — step counter, hyperparams, Adam ``count``,
    biases of unsharded layers — replicate, which is exactly the DDP layout
    the reference uses for everything (``:188-189``).
    """
    return jax.tree_util.tree_map_with_path(
        lambda path, _: NamedSharding(mesh, leaf_spec(path, rules)), state
    )


def shard_state(state, mesh: Mesh, rules: Dict[Tuple[str, str], P]):
    """Place an (unsharded) TrainState onto the mesh per the rule table.

    Returns ``(placed_state, sharding_tree)`` — the same pair contract as
    ``shard_state_zero1`` and ``create_pipelined_vit_state``, so callers
    never recompute the tree. Multi-host safe
    (see ``parallel.mesh.place_state``)."""
    from pytorch_distributed_mnist_tpu.parallel.mesh import place_state

    sharding = state_shardings(state, mesh, rules)
    return place_state(state, sharding), sharding


def make_tp_train_step(mesh: Mesh, state_sharding, data_axis: str = "data"):
    """Jitted DP x TP ``step(state, batch) -> (state, MetricState)``.

    Same program as the pure-DP step — this just forwards the TP layout to
    the shared step factory; XLA propagates the rest (column/row-parallel
    matmul collectives, grad AllReduce over ``data_axis``).
    """
    from pytorch_distributed_mnist_tpu.train.steps import make_train_step

    return make_train_step(mesh, data_axis, state_sharding=state_sharding)


def make_tp_eval_step(mesh: Mesh, state_sharding, data_axis: str = "data"):
    """Jitted DP x TP ``step(state, batch) -> MetricState``."""
    from pytorch_distributed_mnist_tpu.train.steps import make_eval_step

    return make_eval_step(mesh, data_axis, state_sharding=state_sharding)


# ---------------------------------------------------------------------------
# Collective-matmul overlap (--tp-overlap): explicit ring schedule.
# ---------------------------------------------------------------------------


def allgather_matmul(x: jnp.ndarray, w: jnp.ndarray, axis: str) -> jnp.ndarray:
    """Overlapped ``allgather(x) @ w``: per-shard matmul steps riding a ring.

    ``x`` is this rank's sequence shard ``(B, T/tp, C)`` (sharded on dim 1
    over mesh axis ``axis``); ``w`` is a replicated-or-local weight whose
    FIRST dim contracts with ``x``'s last. Returns the full-sequence
    product ``(B, T, *w.shape[1:])`` on every rank — the same value as

        jnp.tensordot(lax.all_gather(x, axis, axis=1, tiled=True), w, 1)

    but decomposed: the gather becomes ``tp - 1`` ring ``ppermute`` hops
    and the matmul one row-block step per shard, so each hop's transfer
    overlaps the previous block's compute instead of serializing in front
    of the whole matmul. Row blocks of a matmul are independent (each
    output row is one dot of an input row with ``w``), so the
    decomposition changes scheduling, not math.

    The ``optimization_barrier`` fence chain (``zero_overlap._fenced`` /
    ``_chain``) pins one ordered compute stream — block k's matmul after
    chunk k's arrival — while leaving every ppermute free to issue as
    soon as its operand exists, which is what the overlap needs.
    """
    # Lazy: parallel.zero imports this module's rule helpers, so a
    # module-level import of zero_overlap (which imports zero) would cycle.
    from pytorch_distributed_mnist_tpu.parallel.zero_overlap import (
        _chain,
        _fenced,
    )

    tp = lax.axis_size(axis)
    if tp == 1:
        return jnp.tensordot(x, w, axes=([x.ndim - 1], [0]))
    idx = lax.axis_index(axis)
    # Each rank sends to its predecessor / receives from its successor:
    # after s hops this rank holds the shard that started on rank
    # (idx + s) % tp, so the step-order pieces are a cyclic rotation of
    # the global order — one jnp.roll restores it.
    perm = [(j, (j - 1) % tp) for j in range(tp)]
    token = jnp.zeros((), jnp.float32)
    chunk = x
    pieces = []
    for step in range(tp):
        nxt = lax.ppermute(chunk, axis, perm) if step + 1 < tp else None
        # Fence this step's operand (and the in-flight transfer) behind
        # the chain token so the per-shard matmuls form one ordered
        # stream; the ppermute itself is NOT behind the matmul — its
        # operand is last step's chunk, so it issues while this block
        # multiplies.
        if nxt is None:
            (chunk,), token = _fenced((chunk,), token)
        else:
            (chunk, nxt), token = _fenced((chunk, nxt), token)
        piece = jnp.tensordot(chunk, w, axes=([chunk.ndim - 1], [0]))
        pieces.append(piece)
        token = _chain(token, jnp.sum(piece).astype(jnp.float32))
        chunk = nxt
    stacked = jnp.stack(pieces, axis=0)        # (tp, B, T/tp, ...) step order
    stacked = jnp.roll(stacked, idx, axis=0)   # source-rank (global) order
    moved = jnp.moveaxis(stacked, 0, 1)        # (B, tp, T/tp, ...)
    return moved.reshape(
        (moved.shape[0], tp * moved.shape[2]) + moved.shape[3:])


def overlap_tp_rules(axis: str = "model") -> Dict[Tuple[str, str], P]:
    """Suffix rules for the head-major DEPTH-STACKED layout
    (``pipeline_tp.split_vit_params_tp``): every blocks leaf carries a
    leading ``(depth,)`` dim, attention is head-major — qkv
    ``(depth, C, 3, H, D)``, proj ``(depth, H, D, C)`` — and ``axis``
    lands on the head dim / MLP hidden dim (the same Megatron column->row
    split as ``vit_tp_rules``, expressed on the explicit layout)."""
    return {
        ("qkv", "kernel"): P(None, None, None, axis, None),
        ("qkv", "bias"): P(None, None, axis, None),
        ("proj", "kernel"): P(None, axis, None, None),
        ("mlp1", "kernel"): P(None, None, axis),
        ("mlp1", "bias"): P(None, axis),
        ("mlp2", "kernel"): P(None, axis, None),
    }


def overlap_block_apply(bp, h, *, tp_axis: str, compute_dtype,
                        attention_fn=None):
    """One transformer block on a SEQUENCE-SHARDED residual stream.

    ``h`` is this rank's ``(B, T/tp, C)`` token shard; ``bp`` this rank's
    head-major weight shard (whole heads for qkv/proj, a slice of the MLP
    hidden dim for mlp1/mlp2). The Megatron-SP shape: LayerNorm runs on
    the token shard, each column-parallel matmul gathers the sequence
    THROUGH ``allgather_matmul`` (the overlapped form), attention runs on
    the full sequence with local heads, and each row-parallel matmul's
    partial sums reduce-scatter straight back to the token shard
    (``psum_scatter`` — the transpose of the gather, so between blocks
    only 1/tp of the activations exist per rank).

    Math parity with ``models/attention.py::TransformerBlock``: identical
    flax LayerNorm/gelu modules and compute-dtype policy; the only
    difference is float reassociation inside the psum_scatter.
    """
    import flax.linen as nn

    from pytorch_distributed_mnist_tpu.ops.attention import full_attention

    cd = compute_dtype
    ln = nn.LayerNorm(dtype=cd)

    x = h
    y = ln.apply({"params": bp["ln1"]}, x)
    a = bp["attn"]
    wqkv = a["qkv"]["kernel"].astype(cd)         # (C, 3, Hl, D)
    bqkv = a["qkv"]["bias"].astype(cd)           # (3, Hl, D)
    qkv = allgather_matmul(y.astype(cd), wqkv, tp_axis) + bqkv
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    attend = attention_fn or full_attention
    o = attend(q, k, v)                          # (B, T, Hl, D) local heads
    wproj = a["proj"]["kernel"].astype(cd)       # (Hl, D, C)
    part = jnp.einsum("bthd,hdc->btc", o.astype(cd), wproj)
    part = lax.psum_scatter(part, tp_axis, scatter_dimension=1, tiled=True)
    x = x + part + a["proj"]["bias"].astype(cd)

    y = ln.apply({"params": bp["ln2"]}, x)
    u = allgather_matmul(y.astype(cd), bp["mlp1"]["kernel"].astype(cd),
                         tp_axis) + bp["mlp1"]["bias"].astype(cd)
    u = nn.gelu(u)                               # (B, T, 4C/tp)
    v2 = u @ bp["mlp2"]["kernel"].astype(cd)     # partial (B, T, C)
    v2 = lax.psum_scatter(v2, tp_axis, scatter_dimension=1, tiled=True)
    return x + v2 + bp["mlp2"]["bias"].astype(cd)


def make_overlap_tp_vit_apply(model, mesh: Mesh, *, tp_axis: str = "model",
                              data_axis: Optional[str] = "data"):
    """``apply_fn(split_tp_params, x, train=False) -> logits`` running the
    overlapped-TP schedule in an explicit shard_map.

    Drop-in for ``model.apply`` in a TrainState (the
    ``make_pipelined_tp_vit_apply`` contract): params are the head-major
    split layout, embed/head run replicated over ``tp_axis``, the blocks
    run sequence-sharded with ``allgather_matmul``. The standard
    train/eval step factories consume it unchanged.
    """
    import flax.linen as nn

    from pytorch_distributed_mnist_tpu.models.attention import patchify

    tp = mesh.shape[tp_axis]
    tokens = (28 // model.patch_size) ** 2
    if model.num_heads % tp:
        raise ValueError(
            f"vit heads {model.num_heads} not divisible by "
            f"--tensor-parallel {tp}")
    hidden = model.embed_dim * model.mlp_ratio
    if hidden % tp:
        raise ValueError(
            f"vit MLP hidden dim {hidden} not divisible by "
            f"--tensor-parallel {tp}")
    if tokens % tp:
        raise ValueError(
            f"vit token count {tokens} not divisible by --tensor-parallel "
            f"{tp}; the overlapped schedule shards the sequence")
    cd = model.compute_dtype
    embed_mod = nn.Dense(model.embed_dim, dtype=cd)
    ln_mod = nn.LayerNorm(dtype=cd)
    head_mod = nn.Dense(model.num_classes, dtype=cd)
    rules = overlap_tp_rules(tp_axis)

    def body(split_tp, x):
        h = patchify(x, model.patch_size, cd)
        h = embed_mod.apply({"params": split_tp["embed"]["embed"]}, h)
        h = h + split_tp["embed"]["pos_embed"].astype(cd)
        # Enter the sequence-sharded regime: this rank keeps its T/tp
        # token slice; the exit all_gather below is the inverse.
        tl = tokens // tp
        h = lax.dynamic_slice_in_dim(
            h, lax.axis_index(tp_axis) * tl, tl, axis=1)

        def blk(hh, bp):
            return overlap_block_apply(
                bp, hh, tp_axis=tp_axis, compute_dtype=cd,
                attention_fn=model.attention_fn), None

        if model.remat:
            blk = jax.checkpoint(blk)
        h, _ = lax.scan(blk, h, split_tp["blocks"])
        h = lax.all_gather(h, tp_axis, axis=1, tiled=True)
        h = ln_mod.apply({"params": split_tp["head"]["ln_f"]}, h)
        h = jnp.mean(h, axis=1)
        h = head_mod.apply({"params": split_tp["head"]["head"]}, h)
        return h.astype(jnp.float32)

    def apply_fn(split_tp, x, *, train: bool = False):
        del train
        specs = jax.tree_util.tree_map_with_path(
            lambda path, _: leaf_spec(path, rules), split_tp)
        sharded = jax.shard_map(
            body, mesh=mesh,
            in_specs=(specs, P(data_axis)),
            out_specs=P(data_axis),
            check_vma=False,
        )
        return sharded(split_tp, x)

    return apply_fn


def create_overlap_tp_vit_state(model, rng: jax.Array, mesh: Mesh, *,
                                tp_axis: str = "model",
                                data_axis: Optional[str] = "data",
                                lr: float = 1e-3, optimizer: str = "adam",
                                momentum: float = 0.9,
                                weight_decay: float = 1e-4,
                                place: bool = True):
    """``(state, state_sharding)`` for the overlapped-TP ViT — the same
    pair contract as ``shard_state`` / ``create_pipelined_tp_vit_state``,
    consumed by the standard train/eval steps unchanged. Params are the
    head-major split layout (bitwise-bijective with the standard flax
    tree via ``pipeline_tp.merge_vit_params_tp``)."""
    from pytorch_distributed_mnist_tpu.parallel.mesh import place_state
    from pytorch_distributed_mnist_tpu.parallel.pipeline_tp import (
        split_vit_params_tp,
    )
    from pytorch_distributed_mnist_tpu.train.state import (
        TrainState,
        make_optimizer,
    )

    params = split_vit_params_tp(
        model.init(rng, jnp.zeros((1, 28, 28, 1), jnp.float32)),
        model.num_heads,
    )
    tx = make_optimizer(lr, optimizer, momentum, weight_decay, mesh=mesh)
    apply_fn = make_overlap_tp_vit_apply(
        model, mesh, tp_axis=tp_axis, data_axis=data_axis)
    state = TrainState(
        step=jnp.zeros((), jnp.int32),
        params=params,
        opt_state=tx.init(params),
        apply_fn=apply_fn,
        tx=tx,
    )
    sharding = state_shardings(state, mesh, overlap_tp_rules(tp_axis))
    if not place:
        return state, sharding
    return place_state(state, sharding), sharding
