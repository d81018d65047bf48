"""Explicit overlapped ZeRO: bucketized reduce-scatter / allgather weight
update with a compiler-visible overlap structure.

``parallel/zero.py`` shards optimizer state (ZeRO-1) and params (ZeRO-3)
purely via ``PartitionSpec``s and leaves every scheduling decision to
XLA's sharding propagation. That is the idiomatic default — but nothing
in it *expresses* the schedule the ZeRO paper ("Automatic Cross-Replica
Sharding of Weight Update in Data-Parallel Training", arXiv:2004.13336)
actually wants: gradient communication overlapped with the remaining
backward, and the parameter allgather overlapped with the next step's
forward. This module writes that schedule out explicitly:

- **Same state layout as the propagation path.** The step's in/out specs
  are exactly ``zero_state_sharding``'s (per-leaf largest-divisible-dim
  sharding), so checkpoints, ``--resume auto``, and the propagation eval
  step all keep working unchanged — the two paths are interchangeable
  per state, and the equivalence suite pins them numerically equal
  (``tests/test_zero_overlap.py``).
- **Bucketized reduce-scatter** (``bucket_plan``): gradient leaves are
  size-ordered and packed into flat byte-budgeted buckets
  (``--zero-bucket-mb``). Each bucket's reduce-scatters depend only on
  that bucket's gradient leaves plus a barrier token chained from the
  previous bucket — so bucket k's communication can start the moment its
  gradients exist, while the backward still computes other buckets'
  gradients, and XLA's latency-hiding scheduler is free to overlap the
  two. ``lax.optimization_barrier`` provides the fences: it pins bucket
  order without inventing data dependencies on unrelated compute.
- **Carried allgather** (ZeRO-3): the step takes the previous step's
  gathered (replicated) params as an argument and returns the next
  gathered copy rebuilt from the updated shards — the allgather sits at
  the tail of step N where it can overlap metric math and, across the
  scan carry in ``make_overlap_train_epoch`` (or the Trainer's explicit
  carry in stepwise mode), the head of step N+1's forward. The carry is
  derived state: ``gathered == allgather(state.params)`` always, and is
  rebuilt from the state by ``make_param_gather`` whenever dropped.

Gradient semantics are the per-example-sum form: each device accumulates
the SUM of per-example loss gradients over its local rows (micro-batched
under ``grad_accum``), the reduce-scatter produces global sums, and one
division by the global (psum'd) example count yields exactly the
global-batch masked-mean gradient for any mask distribution — the same
quantity the propagation path's autodiff computes, equal up to float
reduction order.

- **Two-tier (DCN x ICI) schedule** on hierarchical meshes
  (``parallel/mesh.py make_hier_mesh``): the arXiv:2004.13336 multi-pod
  form. Gradients **reduce-scatter within the slice over ``ici``**
  (fast tier, full gradient bytes), then **only the owner's 1/ici_size
  shard all-reduces across slices over ``dcn``** (slow tier — DCN
  traffic shrinks by the slice width), the optimizer updates the shard
  (replicated across slices, deterministically identical), and the
  updated shards **allgather back over ``ici``** — DCN never carries a
  full parameter. Each tier gets its own bucket budget (``bucket_mb``
  for ICI, ``bucket_mb_dcn`` for the shard-sized DCN buckets) and both
  tiers thread through the SAME ``optimization_barrier`` fence chain,
  one ordered communication stream. The state layout is
  ``zero_state_sharding``'s hierarchical resolution (shards over
  ``ici``, replicated over ``dcn``), so checkpoints interop through the
  world-agnostic reshard path exactly like any other layout change.

Scope: the pure data-parallel mesh (``data`` axis only, flat or
hierarchical). TP/EP rule tables and pipeline base shardings stay on
the propagation path, which remains the default (``cli.py`` gates the
compositions).
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from pytorch_distributed_mnist_tpu.ops.loss import cross_entropy
from pytorch_distributed_mnist_tpu.ops.metrics import MetricState, metrics_init
from pytorch_distributed_mnist_tpu.parallel.mesh import (
    HIER_DATA_AXES,
    is_hier_mesh,
)
from pytorch_distributed_mnist_tpu.parallel.zero import _zero_spec, zero_state_sharding
from pytorch_distributed_mnist_tpu.train.steps import accumulate_metrics


def _leaf_bytes(leaf) -> int:
    shape = tuple(getattr(leaf, "shape", ()) or ())
    dtype = np.dtype(getattr(leaf, "dtype", np.float32))
    return int(np.prod(shape, dtype=np.int64)) * dtype.itemsize if shape \
        else dtype.itemsize


def bucket_plan(leaves, bucket_mb: float) -> List[List[int]]:
    """Pack flattened-leaf indices into size-ordered byte-budgeted buckets.

    Leaves are ordered largest-first (ties broken by flat index, so the
    plan is deterministic across runs and hosts — the same property the
    ``_zero_spec`` tie-break pins for dim choice) and packed greedily:
    a bucket closes when adding the next leaf would exceed
    ``bucket_mb`` MiB. A single leaf larger than the budget gets its own
    bucket. Each bucket is one communication-issue group in the step:
    its collectives are fenced together and chained after the previous
    bucket's.
    """
    if bucket_mb <= 0:
        raise ValueError(f"bucket_mb must be > 0, got {bucket_mb}")
    budget = int(bucket_mb * (1 << 20))
    order = sorted(range(len(leaves)),
                   key=lambda i: (-_leaf_bytes(leaves[i]), i))
    plan: List[List[int]] = []
    cur: List[int] = []
    cur_bytes = 0
    for i in order:
        nbytes = _leaf_bytes(leaves[i])
        if cur and cur_bytes + nbytes > budget:
            plan.append(cur)
            cur, cur_bytes = [], 0
        cur.append(i)
        cur_bytes += nbytes
    if cur:
        plan.append(cur)
    return plan


def _shard_dims(param_leaves, axis_size: int, axis: str) -> List[Optional[int]]:
    """Per flattened param leaf: the dim its ZeRO shard (and its moment
    shard) splits over ``axis``, or None for leaves with no divisible dim
    — exactly ``zero._zero_spec``'s choice, so the explicit path can
    never disagree with the propagation layout."""
    dims: List[Optional[int]] = []
    for leaf in param_leaves:
        shape = tuple(getattr(leaf, "shape", ()) or ())
        spec = _zero_spec(shape, axis_size, axis, P())
        dim = None
        for d, entry in enumerate(spec):
            if entry == axis:
                dim = d
                break
        dims.append(dim)
    return dims


class _ShardView:
    """Shape/dtype stand-in for one leaf's post-reduce-scatter shard —
    what the DCN tier actually moves, so its bucket plan budgets shard
    bytes, not full-leaf bytes."""

    def __init__(self, leaf, dim: Optional[int], axis_size: int):
        shape = tuple(getattr(leaf, "shape", ()) or ())
        if dim is not None:
            shape = (shape[:dim] + (shape[dim] // axis_size,)
                     + shape[dim + 1:])
        self.shape = shape
        self.dtype = np.dtype(getattr(leaf, "dtype", np.float32))


def _dcn_bucket_plan(param_leaves, dims, axis_size: int,
                     bucket_mb: float) -> List[List[int]]:
    """The DCN tier's bucket plan: the same deterministic packing as
    ``bucket_plan``, but over SHARD-sized views (1/axis_size of each
    sharded leaf) — the cross-slice all-reduce only ever carries the
    owner shards, so its buckets budget those bytes independently of
    the ICI tier's full-gradient buckets (``--zero-bucket-mb-dcn``)."""
    views = [_ShardView(leaf, d, axis_size)
             for leaf, d in zip(param_leaves, dims)]
    return bucket_plan(views, bucket_mb)


def _tier_axes(mesh: Mesh, axis):
    """(shard_axis, outer_axis, all_axes) for the mesh: on a flat mesh
    the shard axis IS the whole data axis and there is no outer tier;
    on a hierarchical mesh ZeRO shards over ``ici`` and the owner
    shards cross slices over ``dcn``."""
    if axis == "data" and is_hier_mesh(mesh):
        return "ici", "dcn", HIER_DATA_AXES
    return axis, None, axis


def _fenced(values: Tuple, token):
    """One ``optimization_barrier`` over a bucket's values plus the chain
    token. All results of the barrier are scheduled after all operands,
    so consuming the returned values orders this bucket's collectives
    after the previous bucket's — without any data dependence on
    unrelated compute (the backward producing later buckets' gradients
    keeps running)."""
    out = lax.optimization_barrier(tuple(values) + (token,))
    return out[:-1], out[-1]


def _chain(token, anchor):
    """Advance the chain token so it depends on ``anchor`` (a collective
    result): the next bucket's fence is scheduled after this bucket's
    communication was issued."""
    return lax.optimization_barrier((token, anchor))[0]


def _local_grads_and_metrics(state, full_params, batch, grad_accum: int):
    """Per-device loss backward: per-example-SUM gradients over the local
    rows plus local metric sums (loss_sum/correct/count). ``grad_accum``
    micro-batches via ``lax.scan`` against the same params — the local
    twin of ``steps.make_accum_train_step_fn``'s accumulation."""

    def micro(params, images, labels, mask):
        n = (jnp.sum(mask.astype(jnp.float32)) if mask is not None
             else jnp.asarray(float(labels.shape[0])))

        def loss_fn(p):
            logits = state.apply_fn(p, images, train=True)
            ce = cross_entropy(logits, labels, mask)
            return ce * n, (ce, logits)

        (_, (ce, logits)), g = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        hit = (jnp.argmax(logits, axis=-1) == labels).astype(jnp.float32)
        if mask is not None:
            hit = hit * mask.astype(jnp.float32)
        m = MetricState(loss_sum=ce.astype(jnp.float32) * n,
                        correct=jnp.sum(hit), count=n)
        return g, m

    mask = batch.get("mask")
    if grad_accum < 2:
        return micro(full_params, batch["image"], batch["label"], mask)

    b = batch["image"].shape[0]
    if b % grad_accum:
        raise ValueError(
            f"per-device batch {b} not divisible by grad_accum {grad_accum}"
        )
    micros = jax.tree_util.tree_map(
        lambda v: v.reshape((grad_accum, b // grad_accum) + v.shape[1:]),
        batch,
    )

    def body(carry, mb):
        g_acc, m_acc = carry
        g, m = micro(full_params, mb["image"], mb["label"], mb.get("mask"))
        return (jax.tree_util.tree_map(jnp.add, g_acc, g),
                accumulate_metrics(m_acc, m)), None

    zeros = jax.tree_util.tree_map(
        lambda p: jnp.zeros(jnp.shape(p), jnp.result_type(p)), full_params)
    (g_sum, metrics), _ = lax.scan(body, (zeros, metrics_init()), micros)
    return g_sum, metrics


def _make_sharded_body(state, mesh: Mesh, axis: str, level: int,
                       bucket_mb: float, grad_accum: int,
                       bucket_mb_dcn: Optional[float] = None):
    """The per-device step body + its shard_map specs.

    Returns ``(sharded_step, state_specs)`` where ``sharded_step(state,
    gathered, batch) -> (state, gathered, metrics)`` is the shard_map'd
    (unjitted) program — the scan epoch embeds it directly; the step
    factory jits it. For ``level=1`` the ``gathered`` argument carries
    the replicated params redundantly (identical to ``state.params``) so
    both levels share one body; the level-1 public wrappers hide it.

    On a hierarchical mesh the body runs the two-tier schedule: RS over
    ``ici``, the owner shards all-reduced over ``dcn`` in their own
    ``bucket_mb_dcn``-budgeted buckets, AG over ``ici`` — all through
    the one fence chain.
    """
    if level not in (1, 3):
        raise ValueError(f"zero level must be 1 or 3, got {level}")
    shard_axis, outer_axis, all_axes = _tier_axes(mesh, axis)
    axis_size = mesh.shape[shard_axis]
    param_leaves, ptree = jax.tree_util.tree_flatten(state.params)
    dims = _shard_dims(param_leaves, axis_size, shard_axis)
    plan = bucket_plan(param_leaves, bucket_mb)
    dcn_plan = (_dcn_bucket_plan(param_leaves, dims, axis_size,
                                 bucket_mb_dcn or bucket_mb)
                if outer_axis is not None else None)
    sharding = zero_state_sharding(state, mesh, data_axis=axis, level=level)
    state_specs = jax.tree_util.tree_map(lambda ns: ns.spec, sharding)
    repl_params = jax.tree_util.tree_map(lambda _: P(), state.params)

    def body(st, gathered, batch):
        # Forward/backward against the FULL params: the carried gathered
        # copy (ZeRO-3) or the replicated state params (ZeRO-1).
        full_params = gathered if level == 3 else st.params
        g_sum, local_m = _local_grads_and_metrics(
            st, full_params, batch, grad_accum)
        n_global = lax.psum(local_m.count, all_axes)
        inv_n = 1.0 / jnp.maximum(n_global, 1.0)

        # Bucketized reduce-scatter over the shard (ICI) tier: bucket
        # k's collectives consume only bucket k's gradient leaves (plus
        # the chain token), so they can issue while the backward's other
        # buckets are still computing; the chain keeps one ordered
        # communication stream.
        g_flat = jax.tree_util.tree_flatten(g_sum)[0]
        g_shards: List = [None] * len(g_flat)
        token = jnp.zeros((), jnp.float32)
        for bucket in plan:
            fenced, token = _fenced(tuple(g_flat[i] for i in bucket), token)
            for leaf, i in zip(fenced, bucket):
                d = dims[i]
                if d is None:
                    red = lax.psum(leaf, shard_axis)
                else:
                    red = lax.psum_scatter(
                        leaf, shard_axis, scatter_dimension=d, tiled=True)
                g_shards[i] = red * inv_n.astype(red.dtype)
            token = _chain(token, jnp.sum(g_shards[bucket[0]]))

        if outer_axis is not None:
            # DCN tier: each intra-slice reduce-scatter left every
            # (slice, ici-rank) holding its slice's PARTIAL sum of shard
            # i; one all-reduce across slices of just that 1/ici_size
            # shard completes the global sum — DCN moves shard bytes,
            # never full gradients. Shard-sized buckets, same chain.
            for bucket in dcn_plan:
                fenced, token = _fenced(
                    tuple(g_shards[i] for i in bucket), token)
                for leaf, i in zip(fenced, bucket):
                    g_shards[i] = lax.psum(leaf, outer_axis)
                token = _chain(token, jnp.sum(g_shards[bucket[0]]))
        grad_shards = jax.tree_util.tree_unflatten(ptree, g_shards)

        # Owner-shard optimizer update: mu/nu arrive as local shards (the
        # shard_map in_specs ARE the ZeRO layout) and Adam is elementwise,
        # so tx.update on the shard view computes exactly the owned slice
        # of the full update. ZeRO-1 slices its shard out of the
        # replicated params; ZeRO-3 params already are the shards. On the
        # hierarchical mesh the shard index is the ICI coordinate alone:
        # every slice's rank i runs the identical update on identical
        # globally-summed gradients (replicated over dcn by construction).
        idx = lax.axis_index(shard_axis)

        def param_shard(p, d):
            if d is None or level == 3:
                return p
            size = p.shape[d] // axis_size
            return lax.dynamic_slice_in_dim(p, idx * size, size, axis=d)

        p_shards = jax.tree_util.tree_unflatten(ptree, [
            param_shard(p, d)
            for p, d in zip(jax.tree_util.tree_flatten(st.params)[0], dims)
        ])
        updates, new_opt = st.tx.update(grad_shards, st.opt_state, p_shards)
        new_p_shards = optax.apply_updates(p_shards, updates)

        # Bucketized allgather of the updated shards, same fence chain:
        # sitting at the step's tail, each bucket's gather may overlap
        # the remaining buckets' updates and — through the carry — the
        # next step's forward up to the first use of its leaves. Over
        # the shard (ICI) tier only: cross-slice copies of the gathered
        # params are already identical, so DCN carries nothing here.
        np_flat = jax.tree_util.tree_flatten(new_p_shards)[0]
        full: List = [None] * len(np_flat)
        for bucket in plan:
            fenced, token = _fenced(tuple(np_flat[i] for i in bucket), token)
            for leaf, i in zip(fenced, bucket):
                d = dims[i]
                full[i] = leaf if d is None else lax.all_gather(
                    leaf, shard_axis, axis=d, tiled=True)
            token = _chain(token, jnp.sum(full[bucket[0]]))
        new_full = jax.tree_util.tree_unflatten(ptree, full)

        new_state = st.replace(
            step=st.step + 1,
            params=new_p_shards if level == 3 else new_full,
            opt_state=new_opt,
        )
        metrics = MetricState(
            loss_sum=lax.psum(local_m.loss_sum, all_axes),
            correct=lax.psum(local_m.correct, all_axes),
            count=n_global,
        )
        return new_state, new_full, metrics

    sharded = jax.shard_map(
        body, mesh=mesh,
        in_specs=(state_specs, repl_params, P(all_axes)),
        out_specs=(state_specs, repl_params, P()),
        check_vma=False,
    )
    return sharded, state_specs


def make_overlap_train_step(state, mesh: Mesh, axis: str = "data",
                            level: int = 1, bucket_mb: float = 4.0,
                            grad_accum: int = 1,
                            bucket_mb_dcn: Optional[float] = None):
    """Jitted overlapped-ZeRO train step.

    ``level=1``: ``step(state, batch) -> (state, MetricState)`` — the
    ``make_train_step`` signature, params replicated in the state.
    ``level=3``: ``step(state, gathered, batch) -> (state, gathered,
    MetricState)`` — ``gathered`` is the carried replicated param copy
    (``make_param_gather`` builds the first one), donated and replaced
    each step.

    ``state`` may be concrete or an ``abstract_spec`` tree — only
    shapes/dtypes, ``tx``, and ``apply_fn`` are read. The state layout
    (in/out shardings) is ``zero_state_sharding(state, mesh, level)``,
    identical to the propagation path's, so the same placed state drives
    either step. On a hierarchical mesh the step runs the two-tier
    schedule; ``bucket_mb_dcn`` budgets the cross-slice shard buckets
    (defaults to ``bucket_mb``, ignored on flat meshes).
    """
    sharded, _specs = _make_sharded_body(
        state, mesh, axis, level, bucket_mb, grad_accum,
        bucket_mb_dcn=bucket_mb_dcn)
    if level == 3:
        return jax.jit(sharded, donate_argnums=(0, 1))

    def step(st, batch):
        new_state, _full, metrics = sharded(st, st.params, batch)
        return new_state, metrics

    return jax.jit(step, donate_argnums=(0,))


def make_overlap_train_epoch(state, mesh: Mesh, axis: str = "data",
                             level: int = 1, bucket_mb: float = 4.0,
                             grad_accum: int = 1,
                             bucket_mb_dcn: Optional[float] = None):
    """Jitted overlapped-ZeRO scan epoch (``lax.scan`` over pre-staged
    batches, the ``make_train_epoch`` shape).

    ``level=1``: ``epoch(state, batches) -> (state, MetricState)``.
    ``level=3``: ``epoch(state, gathered, batches) -> (state, gathered,
    MetricState)`` — the gathered params ride the scan carry, so step
    N's tail allgather and step N+1's forward live in one program with
    no barrier between them: the overlap the carry exists to enable.
    """
    sharded, _specs = _make_sharded_body(
        state, mesh, axis, level, bucket_mb, grad_accum,
        bucket_mb_dcn=bucket_mb_dcn)

    if level == 3:
        def epoch(st, gathered, batches):
            def body(carry, b):
                st, gp, acc = carry
                st, gp, m = sharded(st, gp, b)
                return (st, gp, accumulate_metrics(acc, m)), None

            (st, gathered, acc), _ = lax.scan(
                body, (st, gathered, metrics_init()), batches)
            return st, gathered, acc

        return jax.jit(epoch, donate_argnums=(0, 1))

    def epoch(st, batches):
        def body(carry, b):
            st, acc = carry
            st, _full, m = sharded(st, st.params, b)
            return (st, accumulate_metrics(acc, m)), None

        (st, acc), _ = lax.scan(body, (st, metrics_init()), batches)
        return st, acc

    return jax.jit(epoch, donate_argnums=(0,))


def make_param_gather(mesh: Mesh):
    """Jitted ``params -> replicated params``: builds (or rebuilds) the
    carried gathered copy from the state's shards. One allgather per
    sharded leaf, multi-host safe (an SPMD program, not a host-side
    ``device_put`` reshard)."""
    return jax.jit(lambda params: params,
                   out_shardings=NamedSharding(mesh, P()))

