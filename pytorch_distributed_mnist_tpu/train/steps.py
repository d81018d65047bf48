"""Jitted train/eval step factories (GSPMD auto-sharded path).

Replaces the reference's per-batch hot loop
(``/root/reference/multi_proc_single_gpu.py:83-95``): H2D copy, forward,
``F.cross_entropy``, ``zero_grad``/``backward``/``step``, plus two
``.item()`` host syncs per batch. Here the whole of that is ONE compiled XLA
program per batch — forward, loss, backward, gradient AllReduce (inserted by
sharding propagation), Adam update, and metric accumulation fused together,
with the input state donated so parameter buffers are updated in place.

``make_train_epoch`` goes further than the reference can: it ``lax.scan``s
the step over an epoch's worth of pre-staged batches, so an entire epoch is
a single device program with zero host round-trips (SURVEY.md section 3.2
names the reference's per-batch ``.item()`` syncs as the anti-pattern).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from pytorch_distributed_mnist_tpu.ops.loss import cross_entropy, example_weights
from pytorch_distributed_mnist_tpu.ops.metrics import (
    BIAS_COLLECTION,
    LOAD_COLLECTION,
    ROUTING_COLLECTION as COUNTERS,
    ROUTING_COUNTERS,
    STEP_COUNTERS,
    add_routing,
    metrics_init,
    metrics_merge,
    metrics_update,
)


def _forward_with_aux(state, params, images, aux_weight: float):
    """Training forward returning ``(logits, mtp_logits, aux, counters,
    load)``. ``logits`` is the model's array of logits, or the first of
    the two that a model with a multi-token-prediction module returns in
    training, ``mtp_logits`` then the second (else ``None``).
    ``aux`` is the sum of the ``aux_loss`` entries the model sowed under
    ``intermediates`` (the expert layers' balance terms, models/moe.py)
    — 0.0 when ``aux_weight`` is 0, in which case the capture is skipped
    entirely and the program is byte-identical to the plain path — and
    ``counters`` the sum of what its expert layers sowed under
    ``counters`` where the state says it has such layers
    (``TrainState.counters``), else ``None``. ``load`` is what the expert
    layers sowed beside their selection bias where the state carries one
    (``TrainState.buffers``), else ``None``.

    Only leaves whose key is literally ``aux_loss`` enter the objective;
    the expert layers' sown ``choices`` are passed over and any other sown
    intermediate raises, so a future diagnostic sow can
    never silently join the loss. The aux statistic is computed by the
    model over the full static batch — it cannot see the validity mask —
    so it assumes fully-valid train batches, which the train loader
    guarantees (``drop_last=train``, data/loader.py: the ragged tail is
    dropped, never padded; only EVAL batches pad, and eval never runs
    this path)."""
    balanced = state.buffers is not None and BIAS_COLLECTION in state.buffers
    collections = (["intermediates"] if aux_weight else []) \
        + ([COUNTERS] if _counts_routing(state) else []) \
        + ([LOAD_COLLECTION] if balanced else [])
    variables = state.replace(params=params).variables
    if not collections:
        out, mods = state.apply_fn(variables, images, train=True), {}
    else:
        out, mods = state.apply_fn(
            variables, images, train=True, mutable=collections)
    logits, mtp_logits = out if isinstance(out, tuple) else (out, None)
    if not collections:
        return logits, mtp_logits, 0.0, None, None
    counters = None
    if COUNTERS in collections:
        counters = sum(jax.tree_util.tree_leaves(mods.get(COUNTERS, {})))
        counters = jax.lax.stop_gradient(counters)
    load = jax.lax.stop_gradient(mods[LOAD_COLLECTION]) if balanced else None
    aux = jnp.float32(0.0) if aux_weight else 0.0
    for path, leaf in jax.tree_util.tree_leaves_with_path(
            mods.get("intermediates", {})):
        names = [getattr(k, "key", getattr(k, "name", None)) for k in path]
        if "choices" in names:
            continue
        if "aux_loss" not in names:
            raise ValueError(
                f"aux_weight is set but the model sowed a non-aux_loss "
                f"intermediate at {jax.tree_util.keystr(path)}; only "
                f"'aux_loss' entries may join the training objective"
            )
        aux = aux + jnp.sum(leaf)
    return logits, mtp_logits, aux, counters, load


def mtp_labels(labels: jnp.ndarray) -> jnp.ndarray:
    """The token after the next: ``labels`` (B, T) shifted by one more,
    with ``data.tokens.IGNORE`` at the last position (the one before it is
    ignored already: its label, the last position's, is)."""
    return jnp.pad(labels[:, 1:], ((0, 0), (0, 1)), constant_values=-1)


def move_selection_bias(bias, load, rate: float):
    """``b_e += rate * sign(mean_e' n_e' - n_e)`` for every expert layer
    (arXiv:2412.19437, section 4.2): ``bias`` the tree of (E,) biases,
    ``load`` the like tree the layers sowed, each leaf a tuple of the (E,)
    pair counts ``n`` of the layer's calls in the step."""
    def one(b, sown):
        n = sum(sown)
        return b + rate * jnp.sign(jnp.mean(n) - n)

    return jax.tree_util.tree_map(one, bias, load)


def _balance_state(state, load, mtp_loss, objective, bias_rate: float):
    """``(state, step counters)`` after the step's gradients were applied:
    the selection bias moved against ``load``, and STEP_COUNTERS."""
    with jax.named_scope("moe/bias"):
        bias = move_selection_bias(
            state.buffers[BIAS_COLLECTION], load, bias_rate)
        spread = jnp.max(jnp.stack([
            jnp.max(b) - jnp.min(b)
            for b in jax.tree_util.tree_leaves(bias)]))
    state = state.replace(buffers={**state.buffers, BIAS_COLLECTION: bias})
    return state, jnp.stack([
        spread, jnp.float32(0.0) if mtp_loss is None else mtp_loss,
        objective, jnp.float32(1.0)])


def _train_step(state, batch, aux_weight: float = 0.0,
                mtp_weight: float = 0.0, bias_rate: float = 0.0):
    """One optimizer step on one (global) batch. Pure; jitted by the factory.

    The objective is ``cross_entropy + aux_weight * sown_aux`` and, where
    the model returns the logits of a multi-token-prediction module,
    ``+ mtp_weight * cross_entropy(mtp_logits, the token after the
    next)``; metrics report the next token's cross-entropy alone so loss
    curves stay comparable with the reference (which has no auxiliary
    terms, ``:88``). A state with a selection bias has it moved by
    ``bias_rate`` after the gradients are applied."""
    mask = batch.get("mask")

    def loss_fn(params):
        logits, mtp_logits, aux, counters, load = _forward_with_aux(
            state, params, batch["image"], aux_weight)
        with jax.named_scope("loss"):
            ce = cross_entropy(logits, batch["label"], mask)
        objective = ce + aux_weight * aux
        mtp_ce = None
        if mtp_logits is not None:
            with jax.named_scope("mtp/loss"):
                mtp_ce = cross_entropy(
                    mtp_logits, mtp_labels(batch["label"]), mask)
            objective = objective + mtp_weight * mtp_ce
        return objective, (ce, logits, counters, load, mtp_ce)

    (objective, (loss, logits, counters, load, mtp_ce)), grads = \
        jax.value_and_grad(loss_fn, has_aux=True)(state.params)
    new_state = state.apply_gradients(grads)
    if load is not None:
        new_state, stepped = _balance_state(
            new_state, load, mtp_ce, objective, bias_rate)
        if counters is not None:
            counters = jnp.concatenate([counters, stepped])
    with jax.named_scope("loss"):
        metrics = metrics_update(
            metrics_init(), loss, logits, batch["label"], mask)
    return new_state, add_routing(metrics, counters)


def make_accum_train_step_fn(accum: int, aux_weight: float = 0.0,
                             mtp_weight: float = 0.0,
                             bias_rate: float = 0.0):
    """Pure ``step(state, batch)`` with ``accum``-way gradient accumulation.

    The batch splits into ``accum`` equal micro-batches along dim 0; a
    ``lax.scan`` runs forward+backward per micro-batch against the SAME
    params, accumulating per-example-SUM gradients, then one optimizer
    step applies the example-weighted mean — exactly the full-batch
    gradient (bitwise up to summation order), so DDP loss-mean semantics
    are preserved for any mask distribution across micro-batches. Peak
    activation memory drops by ~``accum`` while the optimizer cadence
    matches the reference's one-step-per-batch loop (``:90-92``).

    ``aux_weight``: the sown-aux objective term (see ``_train_step``).
    Under accumulation each micro-batch's aux is weighted by its example
    count — the example-weighted mean of micro-batch aux values, an
    approximation of the full-batch aux (the router's load fractions are
    per-micro-batch statistics), standard for MoE grad accumulation.

    ``mtp_weight``, ``bias_rate``: see ``_train_step``, the only step that
    has a second head or a selection bias; the accumulating one refuses a
    state that carries ``buffers``.
    """
    if accum < 2:
        return functools.partial(
            _train_step, aux_weight=aux_weight, mtp_weight=mtp_weight,
            bias_rate=bias_rate)

    def step(state, batch):
        if state.buffers is not None:
            raise ValueError(
                "--grad-accum > 1 does not carry the state that no "
                f"gradient moves ({sorted(state.buffers)}): the selection "
                "bias is moved once a step, from one batch's load")
        b = batch["image"].shape[0]
        if b % accum:
            raise ValueError(
                f"global batch {b} not divisible by --grad-accum {accum}"
            )
        micro = jax.tree_util.tree_map(
            lambda v: v.reshape((accum, b // accum) + v.shape[1:]), batch
        )

        def body(carry, mb):
            g_acc, m_acc = carry
            mask = mb.get("mask")
            weights = example_weights(mb["label"], mask)
            n = (jnp.sum(weights.astype(jnp.float32))
                 if weights is not None
                 else jnp.asarray(float(mb["label"].shape[0])))

            def loss_fn(params):
                logits, _, aux, counters, _ = _forward_with_aux(
                    state, params, mb["image"], aux_weight)
                # per-example SUM: micro-means weighted by real count so
                # the accumulated gradient equals the full-batch gradient
                # even when eval-style masks straddle micro-batches.
                with jax.named_scope("loss"):
                    ce_sum = cross_entropy(logits, mb["label"], mask) * n
                return ce_sum + aux_weight * aux * n, (
                    ce_sum, logits, counters)

            (_, (loss_sum_mb, logits, counters)), g = jax.value_and_grad(
                loss_fn, has_aux=True
            )(state.params)
            g_acc = jax.tree_util.tree_map(jnp.add, g_acc, g)
            with jax.named_scope("loss"):
                loss_mean = loss_sum_mb / jnp.maximum(n, 1.0)
                m_acc = add_routing(metrics_update(
                    m_acc, loss_mean, logits, mb["label"], mask), counters)
            return (g_acc, m_acc), None

        zeros = jax.tree_util.tree_map(
            lambda p: jnp.zeros(jnp.shape(p), jnp.result_type(p)), state.params
        )
        (grads_sum, metrics), _ = lax.scan(
            body, (zeros, metrics_init(_counts_routing(state))), micro
        )
        total = jnp.maximum(metrics.count, 1.0)
        grads = jax.tree_util.tree_map(lambda g: g / total, grads_sum)
        return state.apply_gradients(grads), metrics

    return step


def make_forward_program(apply_fn):
    """``forward(params, images) -> logits`` — the ONE inference forward
    pass, shared by the ``-e/--evaluate`` eval step below and the serving
    engine's bucketed AOT programs (``serve/engine.py``).

    Both consumers trace exactly this function (``train=False``, params as
    an explicit argument), so evaluate and serve cannot disagree on the
    forward math or dtype policy — ``tests/test_serve_engine.py`` pins
    their logits equal. Params are an argument rather than a closure
    capture so the serve engine can hot-swap checkpoints without
    invalidating its compiled executables (the no-recompile invariant).

    How it spans devices is NOT decided here: the serve-side program
    registry (``serve/programs.py``) lowers this same function per
    model x serve-mode — single-device, or pjit over a tensor/expert
    serving mesh with shardings derived from the training rule tables —
    which is what keeps every serving plane's math pinned to eval's.
    """

    def forward(params, images):
        return apply_fn(params, images, train=False)

    return forward


def _eval_step(state, batch):
    """Forward + metrics, no gradient (reference ``evaluate``, ``:99-116``).

    The batch's validity mask keeps padded examples out of the counts, so a
    sharded eval reports exact whole-dataset metrics (the reference instead
    evaluates the full set redundantly on every rank, ``:143-144``)."""
    mask = batch.get("mask")
    logits = make_forward_program(state.apply_fn)(
        state.variables, batch["image"])
    loss = cross_entropy(logits, batch["label"], mask)
    return metrics_update(metrics_init(), loss, logits, batch["label"], mask)


def _shardings(mesh: Optional[Mesh], axis: str):
    if mesh is None:
        return None, None
    from pytorch_distributed_mnist_tpu.parallel.mesh import resolve_data_axis

    # Hierarchical (DCN x ICI) meshes have no literal 'data' axis: the
    # batch shards over the composed ('dcn', 'ici') pair instead.
    axis = resolve_data_axis(mesh, axis)
    repl = NamedSharding(mesh, P())
    data = NamedSharding(mesh, P(axis))
    return repl, data


def make_train_step(
    mesh: Optional[Mesh] = None, axis: str = "data", state_sharding=None,
    grad_accum: int = 1, aux_weight: float = 0.0, mtp_weight: float = 0.0,
    bias_rate: float = 0.0,
):
    """Jitted ``step(state, batch) -> (state, MetricState)``.

    With a mesh: state replicated (or laid out per ``state_sharding`` — e.g.
    the tensor-parallel pytree from ``parallel/tensor.py``), batch sharded
    on ``axis`` — XLA's sharding propagation turns the gradient reduction
    into an AllReduce over ICI, the TPU equivalent of DDP's NCCL allreduce
    (``:188-189``). Without a mesh: plain single-device jit (the
    reference's world-size-1 mode). ``grad_accum > 1`` scans that many
    micro-batches before the single optimizer step
    (``make_accum_train_step_fn``).
    """
    step_fn = make_accum_train_step_fn(
        grad_accum, aux_weight, mtp_weight, bias_rate)
    repl, data = _shardings(mesh, axis)
    if mesh is None:
        return jax.jit(step_fn, donate_argnums=(0,))
    state_sh = repl if state_sharding is None else state_sharding
    # ``data`` is a prefix sharding: every batch leaf shards on dim 0.
    return jax.jit(
        step_fn,
        donate_argnums=(0,),
        in_shardings=(state_sh, data),
        out_shardings=(state_sh, repl),
    )


def make_eval_step(
    mesh: Optional[Mesh] = None, axis: str = "data", state_sharding=None
):
    """Jitted ``step(state, batch) -> MetricState`` (no state update).

    Unlike the reference — where every rank redundantly evaluates the full
    test set because the test loader never gets a ``DistributedSampler``
    (``:143-144``, SURVEY.md section 3.3) — the eval batch is sharded across
    the mesh too, and the counts reduce with the same AllReduce machinery.
    """
    repl, data = _shardings(mesh, axis)
    if mesh is None:
        return jax.jit(_eval_step)
    state_sh = repl if state_sharding is None else state_sharding
    return jax.jit(
        _eval_step,
        in_shardings=(state_sh, data),
        out_shardings=repl,
    )


def _take_batch(data, tick):
    """Gather one scan tick's batch from the device-resident dataset."""
    return {
        "image": jnp.take(data["image"], tick["idx"], axis=0),
        "label": jnp.take(data["label"], tick["idx"], axis=0),
        "mask": tick["mask"],
    }


def accumulate_metrics(acc, m):
    """Fold one step's MetricState into a running accumulator — the scan
    bodies' shared reduction, public so the overlapped-ZeRO epoch
    (``parallel/zero_overlap.py``) accumulates with the identical op."""
    return metrics_merge(acc, m)


def _counts_routing(state) -> int:
    """How many routing counters a train step on ``state`` returns (0:
    none), which a scan's metric carry then has to hold from its first
    step: the expert layers' own and, where the state carries a selection
    bias, the step's (``ops/metrics.py STEP_COUNTERS``)."""
    if not getattr(state, "counters", False):
        return 0
    buffers = getattr(state, "buffers", None)
    return len(ROUTING_COUNTERS) + (
        len(STEP_COUNTERS) if buffers and BIAS_COLLECTION in buffers else 0)


_accumulate = accumulate_metrics


def _make_epoch(mesh, axis, state_sharding, step_fn, train, indexed):
    """The one epoch builder behind all four make_*_epoch* factories.

    ``train`` selects whether the scan carries (and donates) the state;
    ``indexed`` selects the batch source: pre-staged ``(S, B, ...)``
    arrays, or a device-resident dataset gathered per tick
    (``_take_batch``). Everything else — scan body, metric accumulation,
    jit/sharding wiring — is shared, so the host- and device-gather paths
    cannot drift (tests/test_device_gather.py pins them
    trajectory-identical).
    """

    def scan_epoch(state, batch_of, xs):
        if train:
            def body(carry, x):
                st, acc = carry
                st, m = step_fn(st, batch_of(x))
                return (st, _accumulate(acc, m)), None

            (state, acc), _ = lax.scan(
                body, (state, metrics_init(_counts_routing(state))), xs)
            return state, acc

        def body(acc, x):
            return _accumulate(acc, _eval_step(state, batch_of(x))), None

        acc, _ = lax.scan(body, metrics_init(), xs)
        return acc

    if indexed:
        def epoch(state, data, ticks):
            return scan_epoch(state, lambda t: _take_batch(data, t), ticks)
    else:
        def epoch(state, batches):
            return scan_epoch(state, lambda b: b, batches)

    # The compiled module's name (``jit_train_epoch``) in a profile and in
    # the cache key: the name Trainer._run_program and CompileLog use.
    epoch.__name__ = (("train" if train else "eval") + "_epoch"
                      + ("_indexed" if indexed else ""))
    repl, _ = _shardings(mesh, axis)
    donate = (0,) if train else ()
    if mesh is None:
        return jax.jit(epoch, donate_argnums=donate)
    from pytorch_distributed_mnist_tpu.parallel.mesh import resolve_data_axis

    state_sh = repl if state_sharding is None else state_sharding
    xs_shard = NamedSharding(
        mesh, P(None, resolve_data_axis(mesh, axis)))  # (steps, batch) prefix
    in_sh = ((state_sh, repl, xs_shard) if indexed
             else (state_sh, xs_shard))
    out_sh = (state_sh, repl) if train else repl
    return jax.jit(
        epoch, donate_argnums=donate, in_shardings=in_sh,
        out_shardings=out_sh,
    )


def make_train_epoch(
    mesh: Optional[Mesh] = None, axis: str = "data", state_sharding=None,
    grad_accum: int = 1, aux_weight: float = 0.0, mtp_weight: float = 0.0,
    bias_rate: float = 0.0,
):
    """Jitted ``epoch(state, batches) -> (state, MetricState)`` via lax.scan.

    ``batches`` is a dict of arrays with a leading steps axis:
    ``image: (S, B, ...)``, ``label: (S, B)``; the batch axis B is sharded on
    the mesh. The whole epoch runs as one XLA program — S fused train steps
    with on-device metric accumulation, one host sync at the end.
    ``state_sharding`` overrides the replicated state layout (TP tables from
    ``parallel/tensor.py``, ZeRO-1 from ``parallel/zero.py``).
    """
    return _make_epoch(
        mesh, axis, state_sharding,
        make_accum_train_step_fn(
            grad_accum, aux_weight, mtp_weight, bias_rate),
        train=True, indexed=False)


def make_train_epoch_indexed(
    mesh: Optional[Mesh] = None, axis: str = "data", state_sharding=None,
    grad_accum: int = 1, aux_weight: float = 0.0, mtp_weight: float = 0.0,
    bias_rate: float = 0.0,
):
    """Jitted ``epoch(state, data, ticks) -> (state, MetricState)`` where
    the per-step batch is gathered ON DEVICE.

    ``data`` is the whole dataset resident on device ({'image': (N, ...),
    'label': (N,)}, replicated); ``ticks`` is {'idx': (S, B) int32,
    'mask': (S, B)} with B sharded on the mesh. Each scan tick does a
    ``jnp.take`` of its rows — so the dataset crosses the host boundary
    once per RUN and the per-epoch upload is the ~KB index matrix, not a
    full permuted copy of the dataset (the host-gather path's cost, which
    the reference hides behind DataLoader workers,
    ``/root/reference/multi_proc_single_gpu.py:156``). Device memory also
    drops: one (B, ...) batch materializes per tick instead of the staged
    (S, B, ...) epoch.

    Host against device gather is not measured on today's code: no cell
    of ``BENCHMARK.json`` runs this path (ROADMAP D3). Until one does it
    is the documented memory/host-bandwidth saver, not the default
    (``--epoch-gather host``).
    """
    return _make_epoch(
        mesh, axis, state_sharding,
        make_accum_train_step_fn(
            grad_accum, aux_weight, mtp_weight, bias_rate),
        train=True, indexed=True)


def make_eval_epoch(
    mesh: Optional[Mesh] = None, axis: str = "data", state_sharding=None
):
    """Jitted ``epoch(state, batches) -> MetricState`` via lax.scan.

    No device-gather twin on purpose: the eval set never reshuffles, so
    the Trainer stages its sharded epoch on device once and reuses it —
    already zero per-pass host work, without replicating the test set
    into every device's HBM the way a resident-dataset gather would.
    """
    return _make_epoch(mesh, axis, state_sharding, None,
                       train=False, indexed=False)


def abstract_spec(tree):
    """``jax.ShapeDtypeStruct`` pytree mirroring ``tree``'s array leaves —
    the abstract argument form every ``precompile`` call lowers against.
    Works on concrete jax arrays, NumPy arrays, and existing specs alike;
    only shape/dtype are read, so building a spec from the full dataset
    costs nothing."""
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x)),
        tree,
    )


def precompile(fn, *abstract_args, program: str = "program"):
    """AOT-compile a jitted step/epoch program on abstract shapes.

    ``fn.lower(*abstract_args).compile()`` runs the whole pipeline —
    trace, lower, XLA backend compile (or persistent-cache fetch) — ahead
    of the first real batch, off the critical path: the Trainer calls
    this from background threads while MNIST staging/host-gather runs on
    the main thread. The returned ``Compiled`` executable is the SAME
    program the first real call would build (tests pin the trajectories
    bit-identical) and is used directly by the Trainer, so the first step
    triggers zero further compiles — in-process reuse, no re-lowering,
    no cache round-trip.

    Compile wall-ms, XLA backend-compile count, and persistent-cache
    hit/miss land in ``utils.profiling.compile_log`` under ``program``.
    """
    from pytorch_distributed_mnist_tpu.utils.profiling import compile_log

    with compile_log.measure(program):
        return fn.lower(*abstract_args).compile()
