"""Runner ``train_lm_plain``: a token model without expert layers on the
trainer's normal path.

``runners/train_lm.py``'s path and window (registry model -> weights from
the seed -> reference check while the chip still has room -> Adam state ->
``MNISTDataLoader`` over ``data/tokens.py`` -> ``Trainer`` in scan mode, one
pass = one ``Trainer.train()``; one packed sequence counts as one image),
for a model that has no router: there are no expert choices to hand the
reference and no routing counters to read, which ``train_lm.py`` requires
of its model. What does not depend on that is ``train_lm.py``'s
(``error_sums``, ``errors_against``) and ``train.py``'s (``traced_slice``,
``DTYPES``); the next ``benchmark`` PR folds the two runners into one
(PERF.md section 7).

``correct`` (before the window, on the freshly seeded weights): the
system's logits, loss and the gradients of the reference's named leaves on
ONE sequence of the timed length agree with the configuration's plain
reference, computed in blocks on the same device (tolerances and their
reasons are the reference module's); every pass's loss is finite; nothing
compiles and no Pallas call is interpreted inside the window.

The process's ``scan_log`` (``utils/profiling.py``: the selective scans
traced, their chunks and the state they keep, the readers of what layers
publish) goes to ``run.counters['scan']`` for the layer readers.
"""

from __future__ import annotations

import json
import math
import statistics
import time
from functools import partial

from benchmark import trace as trace_lib


def check_against_reference(lm, ref, config, forward, loss_of, params,
                            tokens, labels) -> dict:
    """Errors of a system's logits, loss and named gradients against the
    plain reference ``ref`` (the module) of the configuration ``config`` on
    ``tokens`` (``lm.N_CHECK``, T), each beside its limit.
    ``forward(params, x) -> logits`` and ``loss_of(logits, y)`` are the system's; ``lm`` is
    ``runners/train_lm.py``, whose measures these are."""
    import jax

    kwargs = ref.model_kwargs(config["kwargs"])
    shape = ref.shape_from_kwargs(kwargs)
    leaves = ref.grad_leaves(kwargs)

    def both(forward, loss_of):
        @jax.jit
        def fn(params, x, y):
            def loss_fn(p):
                logits = forward(p, x)
                return loss_of(logits, y), logits

            (loss, logits), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            return logits, loss, lm._pick(grads, leaves)

        return fn

    got = both(forward, loss_of)(params, tokens, labels)
    want = both(lambda p, x: ref.forward(p, x, **shape),
                ref.cross_entropy)(params, tokens, labels)
    sums = jax.device_get(jax.jit(lm.error_sums)(got, want))
    tol = ref.TOLERANCES[config["dtype"]]
    errors, largest = lm.errors_against(sums, leaves)
    # A leaf may have a limit of its own, under ``grad:<its last name>``.
    limits = {k: tol.get(f"grad:{k.rsplit('/', 1)[-1]}", tol["grad"])
              if k.startswith("grad:") else tol[k] for k in errors}
    return {"errors": errors, "limits": limits, "largest": largest,
            "ok": all(errors[k] <= limits[k] for k in errors)}


def check_lower_precision(lm, ref, config, params, tokens, labels) -> dict:
    """The control of the configuration's limits: the same comparison with
    the reference itself as the system, its weights in the nearest
    precision below the stated one (``ref.lower_precision``). It has to
    come out as not ``ok`` (tests/test_phi4flash_bench.py at a tiny size,
    tests_tpu/test_phi4flash_on_tpu.py at the timed one)."""
    shape = ref.shape_from_kwargs(ref.model_kwargs(config["kwargs"]))
    return check_against_reference(
        lm, ref, config,
        lambda p, x: ref.forward(ref.lower_precision(p), x, **shape),
        ref.cross_entropy, params, tokens, labels)


def run(run) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    base = run.module("runners", "train")
    lm = run.module("runners", "train_lm")
    ref = run.module("reference", run.config["reference"])
    # First of all: a checkout without the model stops here, at once.
    model = lm.build_model(run, base)

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
        flash_schedules,
        pallas_lowerings,
        scan_log,
        staging_log,
    )

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
    scan_log.reset()
    set_loss_impl("xla")
    # The process's count so far (a test process has run other kernels).
    lowered_before = pallas_lowerings.snapshot()

    # -- set-up: the system from the seed ---------------------------------
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
    # needs the room they take.
    with compile_log.measure("reference_check"):
        check = check_against_reference(
            lm, ref, run.config,
            lambda p, x: model.apply(p, x, train=True),
            lambda logits, y: cross_entropy(logits, y, None),
            params, tokens[:lm.N_CHECK], labels[:lm.N_CHECK])
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
                for _ in range(lm.TRACED_PASSES):
                    elapsed = measured_pass()
        while len(walls) < 3 or (
                elapsed + statistics.median(walls) <= run.seconds):
            elapsed = measured_pass()
        window_s = elapsed
        staging = staging_log.summary()
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
    scans = scan_log.snapshot()
    run.counters.update(
        chips=chips, device_kind=devices[0].device_kind,
        steps_per_pass=steps, images_per_pass=images_per_pass,
        tokens_per_image=seq_len, batch=batch,
        passes=n, pass_walls_s=walls, window_s=window_s,
        median_pass_s=median_wall, warm_pass_s=warm_s,
        traced_passes=lm.TRACED_PASSES,
        compile=setup_compile, staging=staging, scan=scans,
        compile_cache=cache)
    run.note(kind="passes", n=n, window_s=window_s, warm_pass_s=warm_s,
             median_pass_s=median_wall,
             median_images_per_s_per_chip=images_per_pass / median_wall
             / chips,
             warm_loss=warm_loss, pass_losses=losses, pass_walls_s=walls)
    run.note(kind="state_scans", **scans)
    run.note(kind="flash_schedules", **flash_schedules.snapshot())
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
                    and lowerings["interpret"] == 0),
        "attempted": n * steps,
        "failed": bad_passes * steps,
        "end_to_end": {"train_images_per_s_per_chip": rate,
                       "setup_s": setup_s},
        "devices": devices,
    }
