"""Runner ``train_lm``: a token model on the trainer's normal path.

The same path as ``runners/train.py`` (registry model -> weights from the
seed and ``train_state_from_params`` (``create_train_state``'s two halves,
with the reference check between them while the chip still has room for
it) -> mesh -> ``MNISTDataLoader`` -> ``Trainer`` in scan mode, one pass =
one ``Trainer.train()``), for a model
that reads packed token sequences: the loader is fed by
``data/tokens.py synthetic_token_corpus`` (seeded documents packed into
sequences of the traffic file's ``seq_len``), the model attends through the
flash kernels on a TPU (``models/decoder.py``, ``attention='auto'``;
``runners/train.py`` refuses a kernel), and one packed sequence counts as
one image in ``train_images_per_s_per_chip``.

``correct`` (before the window, on the freshly seeded weights): the
system's logits, loss and the gradients of the reference's named leaves on
ONE sequence of the timed length agree with the configuration's plain
reference, computed in blocks on the same device with the experts the
system chose, and few of those choices differ from the reference's own
(tolerances and their reasons are the reference module's); every pass's
loss is finite; nothing compiles and no Pallas call is interpreted inside
the window; the expert layers dropped no (token, choice) pair in any pass.

Helpers that do not depend on the model are ``runners/train.py``'s.
"""

from __future__ import annotations

import json
import math
import statistics
import time
from functools import partial

from benchmark import trace as trace_lib

N_CHECK = 1  # sequences the system is held to the reference on
TRACED_PASSES = 2  # the window's second and third


def _pick(tree, paths):
    out = {}
    for path in paths:
        node = tree
        for key in path.split("/"):
            node = node[key]
        out[path] = node
    return out


def error_sums(got, want):
    """Five float32 numbers for each array of ``got`` against the like tree
    ``want``, computed where the arrays are: the summed squared difference,
    the summed squared reference, the largest absolute difference, the
    largest absolute reference value, and 1 where ``got`` is finite. (The
    logits and named gradients of one 8,192-token sequence are 1.3 GB a
    side: fetched and compared on the host in float64 they took 33 of the
    check's 48 s.)"""
    import jax
    import jax.numpy as jnp

    def one(g, w):
        g, w = g.astype(jnp.float32), w.astype(jnp.float32)
        d = g - w
        return jnp.stack([
            jnp.sum(d * d), jnp.sum(w * w), jnp.max(jnp.abs(d)),
            jnp.max(jnp.abs(w)), jnp.all(jnp.isfinite(g)).astype(jnp.float32)])

    return jax.tree_util.tree_map(one, got, want)


def rms_err(sums) -> float:
    """Root of the summed squared difference over the root of the summed
    squared reference, from an array's ``error_sums``: one number for a
    whole array, which reads the precision of all of it. (The largest
    difference over the largest value, ``largest_err`` and the measure of
    ``runners/train.py rel_err``, reads the one entry furthest off; it is
    noted beside this.)"""
    if not sums[4]:
        return math.inf
    return math.sqrt(float(sums[0]) / max(float(sums[1]), 1e-60))


def largest_err(sums) -> float:
    """Largest absolute difference over the largest absolute reference
    value, from an array's ``error_sums``."""
    if not sums[4]:
        return math.inf
    return float(sums[2]) / max(float(sums[3]), 1e-30)


def errors_against(sums, leaves) -> tuple:
    """``(errors, largest)`` from the ``error_sums`` of (logits, loss,
    {leaf: gradient}): the errors ``correct`` is decided by (``rms_err`` of
    the logits and of each gradient, the loss's relative difference) and,
    beside them, the largest difference over the largest reference value
    of the same arrays, which is noted and not judged."""
    errors = {"logits": rms_err(sums[0]), "loss": largest_err(sums[1])}
    largest = {"logits": largest_err(sums[0])}
    for path in leaves:
        errors[f"grad:{path}"] = rms_err(sums[2][path])
        largest[f"grad:{path}"] = largest_err(sums[2][path])
    return errors, largest


def flip_share(theirs, own):
    """The share of the (token, choice) pairs ``theirs`` whose expert is
    not among the experts ``own`` of the same token; both tuples of
    (N, k) int arrays, one a sparse layer."""
    import jax.numpy as jnp

    hits = [jnp.mean(jnp.any(t[:, :, None] == o[:, None, :], axis=-1)
                     .astype(jnp.float32)) for t, o in zip(theirs, own)]
    return 1.0 - sum(hits) / len(hits)


def model_forward(model):
    """``forward(params, x) -> (logits, choices)`` of the registry model:
    ``choices`` are the experts each sparse layer's router chose, in the
    layers' order (``models/moe.py`` sows them)."""
    def forward(params, x):
        logits, state = model.apply(params, x, train=True,
                                    mutable=["intermediates"])
        blocks = state["intermediates"]
        return logits, tuple(
            blocks[name]["moe"]["choices"][0]
            for name in sorted(blocks, key=lambda b: int(b[5:]))
            if "moe" in blocks[name])

    return forward


def check_against_reference(ref, config, forward, loss_of, params, tokens,
                            labels) -> dict:
    """Errors of a system's logits, loss and named gradients against the
    plain reference ``ref`` (the module) of the configuration ``config`` on
    ``tokens`` (N_CHECK, T), each beside its limit. ``forward(params, x) ->
    (logits, choices)`` and ``loss_of(logits, y)`` are the system's.

    The reference computes with the experts the system chose: a token
    whose k-th and (k+1)-th router scores are nearly tied goes to another
    expert after one bfloat16 rounding, and whole rows of the routed
    leaves' gradients move with it, which says nothing of the arithmetic.
    The choice is held apart: ``choice_flips`` is the share of the
    system's pairs that are not among the reference's own ``k`` at that
    layer (given the same choices in the layers before it)."""
    import jax

    kwargs = ref.model_kwargs(config["kwargs"])
    shape = ref.shape_from_kwargs(kwargs)
    leaves = ref.grad_leaves(kwargs)

    def both(forward, loss_of):
        @jax.jit
        def fn(params, x, y, *given):
            def loss_fn(p):
                logits, choices = forward(p, x, *given)
                return loss_of(logits, y), (logits, choices)

            (loss, (logits, choices)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            return (logits, loss, _pick(grads, leaves)), choices

        return fn

    got, chosen = both(forward, loss_of)(params, tokens, labels)
    want, own = both(
        lambda p, x, given: ref.forward_and_choices(
            p, x, choices=given, **shape),
        ref.cross_entropy)(params, tokens, labels, chosen)
    sums, flips = jax.device_get(
        jax.jit(lambda g, w, c, o: (error_sums(g, w), flip_share(c, o)))(
            got, want, chosen, own))
    tol = ref.TOLERANCES[config["dtype"]]
    errors, largest = errors_against(sums, leaves)
    errors["choice_flips"] = float(flips)
    limits = {k: tol["grad" if k.startswith("grad:") else k]
              for k in errors}
    return {"errors": errors, "limits": limits, "largest": largest,
            "ok": all(errors[k] <= limits[k] for k in errors)}


def check_lower_precision(ref, config, params, tokens, labels) -> dict:
    """The control of the configuration's limits: the same comparison with
    the reference itself as the system, its weights in the nearest
    precision below the stated one (``ref.lower_precision``). It has to
    come out as not ``ok`` (tests/test_laguna_bench.py at a tiny size,
    tests_tpu/test_laguna_on_tpu.py at the timed one)."""
    shape = ref.shape_from_kwargs(ref.model_kwargs(config["kwargs"]))
    return check_against_reference(
        ref, config,
        lambda p, x: ref.forward_and_choices(
            ref.lower_precision(p), x, **shape),
        ref.cross_entropy, params, tokens, labels)


def build_model(run, base):
    """The registry model the configuration's file names, with the file's
    kwargs less what only the benchmark reads."""
    import jax.numpy as jnp

    from pytorch_distributed_mnist_tpu.models import get_model

    ref = run.module("reference", run.config["reference"])
    kwargs = dict(ref.model_kwargs(run.config["kwargs"]))
    kwargs["compute_dtype"] = getattr(jnp, base.DTYPES[run.config["dtype"]])
    return get_model(run.config["model"], **kwargs)


def run(run) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    from pytorch_distributed_mnist_tpu.data.loader import MNISTDataLoader
    from pytorch_distributed_mnist_tpu.data.tokens import (
        synthetic_token_corpus,
    )
    from pytorch_distributed_mnist_tpu.ops.loss import (
        cross_entropy,
        set_loss_impl,
    )
    from pytorch_distributed_mnist_tpu.parallel.mesh import make_mesh
    from pytorch_distributed_mnist_tpu.train.state import (
        train_state_from_params,
    )
    from pytorch_distributed_mnist_tpu.train.trainer import Trainer
    from pytorch_distributed_mnist_tpu.utils import compile_cache
    from pytorch_distributed_mnist_tpu.utils.profiling import (
        compile_log,
        pallas_lowerings,
        routing_log,
        staging_log,
    )

    base = run.module("runners", "train")
    job = run.traffic
    for knob, built in (("loss", "xla"), ("optimizer", "adam"),
                        ("optimizer_sharding", "none")):
        if job.get(knob, built) != built:
            raise ValueError(f"{knob} {job[knob]!r}: this runner builds "
                             f"{built!r}")
    seq_len = job["seq_len"]
    if run.config["kwargs"].get("seq_len", seq_len) != seq_len:
        raise ValueError(
            f"the traffic's seq_len {seq_len} is not the configuration's "
            f"{run.config['kwargs']['seq_len']}, which its FLOPs count")
    devices = run.devices()
    chips = len(devices)
    cache = compile_cache.configure(run.cache_dir)
    compile_log.reset()
    staging_log.reset()
    routing_log.reset()
    set_loss_impl("xla")
    # The process's count so far (a test process has run other kernels).
    lowered_before = pallas_lowerings.snapshot()

    # -- set-up: the system from the seed ---------------------------------
    model = build_model(run, base)
    mesh = make_mesh(("data",), devices=devices)
    with compile_log.measure("init"):
        params = jax.jit(model.init)(
            jax.random.key(run.seed), jnp.zeros((1, seq_len), jnp.float32))

    steps = job["steps_per_pass"]
    batch = job["batch_per_chip"] * chips
    tokens, labels = synthetic_token_corpus(
        steps * batch, seq_len, run.config["kwargs"]["vocab_size"],
        seed=run.seed, **job.get("documents", {}))
    train_loader, test_loader = (
        MNISTDataLoader(tokens, labels, batch_size=batch, train=train,
                        seed=run.seed) for train in (True, False))

    # The check runs before the optimizer's moments exist: the reference
    # at 8,192 tokens takes 5.9 GB of temporaries beside the weights.
    with compile_log.measure("reference_check"):
        check = check_against_reference(
            run.module("reference", run.config["reference"]), run.config,
            model_forward(model),
            lambda logits, y: cross_entropy(logits, y, None),
            params, tokens[:N_CHECK], labels[:N_CHECK])
    run.note(kind="reference_check", **check)
    with compile_log.measure("init_state"):
        state = jax.jit(
            partial(train_state_from_params, model, lr=job["lr"]),
            donate_argnums=0)(params)
    del params
    # As runners/train.py: the layout the pass's program returns.
    state = jax.device_put(state, NamedSharding(mesh, PartitionSpec()))

    trainer = Trainer(
        state, train_loader, test_loader, mesh=mesh,
        mode=job.get("trainer_mode", "scan"),
        grad_accum=job.get("grad_accum", 1),
        epoch_gather=job.get("epoch_gather", "host"),
        feed_window=job.get("feed_window", 2), staging_log=staging_log)
    del state

    def one_pass(epoch):
        train_loader.set_sample_epoch(epoch)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench:train_pass"):
            loss, _acc = trainer.train()
        return time.perf_counter() - t0, loss.average

    try:
        with compile_log.measure("train_pass"):
            warm_s, warm_loss = one_pass(0)
        setup_compile = compile_log.stats()["totals"]
        staging_log.reset()
        routing_log.reset()

        # -- the window ---------------------------------------------------
        walls, losses = [], []
        trace_dir = run.scratch_dir("trace")
        t_window = time.perf_counter()
        setup_s = time.time() - run.started_at

        def measured_pass():
            wall, loss = one_pass(len(walls) + 1)
            walls.append(wall)
            losses.append(loss)
            return time.perf_counter() - t_window

        elapsed = measured_pass()
        if run.trace:
            with base.traced_slice(trace_dir):
                for _ in range(TRACED_PASSES):
                    elapsed = measured_pass()
        while len(walls) < 3 or (
                elapsed + statistics.median(walls) <= run.seconds):
            elapsed = measured_pass()
        window_s = elapsed
        staging = staging_log.summary()
        routing = routing_log.summary()
        window_compile = compile_log.stats()["totals"]
    finally:
        trainer.close()

    # -- what was measured --------------------------------------------------
    images_per_pass = steps * batch
    n = len(walls)
    rate = n * images_per_pass / window_s / chips
    median_wall = statistics.median(walls)
    compiles_in_window = (
        window_compile["backend_compiles"]
        - setup_compile["backend_compiles"])
    lowerings = {k: v - lowered_before.get(k, 0)
                 for k, v in pallas_lowerings.snapshot().items()}
    bad_passes = sum(1 for x in losses if not math.isfinite(x))
    dropped = routing.get("dropped", 0.0)
    run.counters.update(
        chips=chips, device_kind=devices[0].device_kind,
        steps_per_pass=steps, images_per_pass=images_per_pass,
        tokens_per_image=seq_len, batch=batch,
        passes=n, pass_walls_s=walls, window_s=window_s,
        median_pass_s=median_wall, warm_pass_s=warm_s,
        traced_passes=TRACED_PASSES,
        compile=setup_compile, staging=staging, routing=routing,
        compile_cache=cache)
    run.note(kind="passes", n=n, window_s=window_s, warm_pass_s=warm_s,
             median_pass_s=median_wall,
             median_images_per_s_per_chip=images_per_pass / median_wall
             / chips,
             warm_loss=warm_loss, pass_losses=losses, pass_walls_s=walls)
    run.note(kind="routing", **routing)
    run.note(kind="setup", setup_s=setup_s, compile=setup_compile,
             programs=compile_log.stats()["programs"], compile_cache=cache,
             compiles_in_window=compiles_in_window,
             pallas_lowerings=lowerings, staging=staging,
             memory_stats=devices[0].memory_stats())

    if run.trace:
        planes = trace_lib.load(trace_lib.find_xplane(trace_dir))
        run.reduced_trace = trace_lib.reduce(planes)
        with open(run.out_path("trace.json"), "w") as f:
            json.dump({"reduced": run.reduced_trace,
                       "describe": trace_lib.describe(planes)}, f, indent=1)
        run.note(kind="trace", **{k: v for k, v in run.reduced_trace.items()
                                  if k not in ("device_ops", "idle_gaps")})

    return {
        "correct": (check["ok"] and bad_passes == 0
                    and compiles_in_window == 0
                    and lowerings["interpret"] == 0 and dropped == 0),
        "attempted": n * steps,
        "failed": bad_passes * steps,
        "end_to_end": {"train_images_per_s_per_chip": rate,
                       "setup_s": setup_s},
        "devices": devices,
    }
