"""Runner ``train``: the trainer's normal path from ``Trainer`` down.

What ``cli.run`` does for a training job, less argparse (the command line
cannot name a width): registry model -> ``create_train_state`` from the
seed -> mesh over the cell's chips (and the ZeRO-1 layout where the job
asks for it) -> ``MNISTDataLoader`` over ``synthetic_dataset`` ->
``Trainer``. One pass is one call of ``Trainer.train()``: one scan-epoch
program of ``steps_per_pass`` steps that ends in the host read of its
metrics, with the next pass's input staged by the trainer's own prefetch
thread meanwhile. The first pass warms the program up and is set-up;
then passes run until ``--seconds`` is spent.

``correct`` (before the window, on the freshly seeded weights): the
system's logits, loss and the gradients of a few named leaves on 8 seeded
images agree with the configuration's plain reference (tolerances and
their reasons are the reference module's); every pass's loss is finite;
nothing compiles and no Pallas call is interpreted inside the window.

A traced run profiles passes 2 and 3 of its window only, so the trace
stays small enough to reduce in this process.
"""

from __future__ import annotations

import contextlib
import json
import math
import os
import shutil
import statistics
import time
from functools import partial

from benchmark import trace as trace_lib

N_CHECK = 8  # images the system is held to the reference on
TRACED_PASSES = 2  # the window's second and third
DTYPES = {"bf16": "bfloat16", "f32": "float32"}
# What a traffic file may say of these and what the runner builds: the
# kernels' wiring (shard_map round flash, the loss's mesh, pallas_adam's)
# lives in cli.py, and a file that named one would not get it here.
COMMAND_LINE_DEFAULTS = {"attention": "dense", "loss": "xla",
                         "optimizer": "adam"}


def build_model(run):
    """The configuration's model: its optional ``configs/<name>.py`` hook,
    else the registry model its file names with the file's kwargs."""
    import jax.numpy as jnp

    from pytorch_distributed_mnist_tpu.models import get_model

    hook = os.path.join(run.bench_dir, "configs",
                        f"{run.cell['config']}.py")
    if os.path.isfile(hook):
        return run.module("configs", run.cell["config"]).build(run)
    kwargs = dict(run.config["kwargs"])
    kwargs["compute_dtype"] = getattr(jnp, DTYPES[run.config["dtype"]])
    return get_model(run.config["model"], **kwargs)


@contextlib.contextmanager
def traced_slice(trace_dir: str):
    """Profile what runs inside into ``trace_dir``, under the host span
    that the reduction takes for its window."""
    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(trace_dir)
    try:
        with jax.profiler.TraceAnnotation(trace_lib.WINDOW_SPAN):
            yield
    finally:
        jax.profiler.stop_trace()


def rel_err(got, want) -> float:
    """Largest absolute difference over the largest absolute reference
    value: one number for a whole array, blind to where zeros fall."""
    import numpy as np

    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if not np.all(np.isfinite(got)):
        return math.inf
    return float(np.max(np.abs(got - want))
                 / max(float(np.max(np.abs(want))), 1e-30))


def check_against_reference(run, state, images, labels) -> dict:
    """Errors of the system's logits, loss and named gradients against the
    plain reference, each with the tolerance it is held to."""
    import jax

    from pytorch_distributed_mnist_tpu.ops.loss import cross_entropy

    ref = run.module("reference", run.config["reference"])
    shape = ref.shape_from_kwargs(run.config["kwargs"])
    leaves = ref.grad_leaves(run.config["kwargs"])

    def pick(grads):
        out = {}
        for path in leaves:
            node = grads
            for key in path.split("/"):
                node = node[key]
            out[path] = node
        return out

    @jax.jit
    def system(params, x, y):
        def loss_fn(p):
            logits = state.apply_fn(p, x, train=True)
            return cross_entropy(logits, y, None), logits

        (loss, logits), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        return logits, loss, pick(grads)

    @jax.jit
    def reference(params, x, y):
        def loss_fn(p):
            logits = ref.forward(p, x, **shape)
            return ref.cross_entropy(logits, y), logits

        (loss, logits), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        return logits, loss, pick(grads)

    got = jax.device_get(system(state.params, images, labels))
    want = jax.device_get(reference(state.params, images, labels))
    tol = ref.TOLERANCES[run.config["dtype"]]
    errors = {"logits": rel_err(got[0], want[0]),
              "loss": rel_err(got[1], want[1])}
    for path in leaves:
        errors[f"grad:{path}"] = rel_err(got[2][path], want[2][path])
    limits = {k: tol["grad" if k.startswith("grad:") else k]
              for k in errors}
    return {"errors": errors, "limits": limits,
            "ok": all(errors[k] <= limits[k] for k in errors)}


def run(run) -> dict:
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec

    from pytorch_distributed_mnist_tpu.data.loader import MNISTDataLoader
    from pytorch_distributed_mnist_tpu.data.mnist import (
        normalize_images,
        synthetic_dataset,
    )
    from pytorch_distributed_mnist_tpu.ops.loss import set_loss_impl
    from pytorch_distributed_mnist_tpu.parallel.mesh import make_mesh
    from pytorch_distributed_mnist_tpu.train.state import create_train_state
    from pytorch_distributed_mnist_tpu.train.trainer import Trainer
    from pytorch_distributed_mnist_tpu.utils import compile_cache
    from pytorch_distributed_mnist_tpu.utils.profiling import (
        compile_log,
        pallas_lowerings,
        staging_log,
    )

    job = run.traffic
    for knob, built in COMMAND_LINE_DEFAULTS.items():
        if job.get(knob, built) != built:
            raise ValueError(
                f"{knob} {job[knob]!r}: this runner builds the command "
                f"line's default ({built}); a cell of a kernel brings its "
                f"own runner or hook")
    devices = run.devices()
    chips = len(devices)
    cache = compile_cache.configure(run.cache_dir)
    compile_log.reset()
    staging_log.reset()
    set_loss_impl("xla")

    # -- set-up: the system from the seed ---------------------------------
    model = build_model(run)
    mesh = make_mesh(("data",), devices=devices)
    with compile_log.measure("init"):
        # One jitted call makes weights and optimizer state on the device.
        state = jax.jit(partial(
            create_train_state, model, lr=job["lr"]))(
                jax.random.key(run.seed))
    sharding = None
    layout = job.get("optimizer_sharding", "none")
    if layout == "zero1":
        from pytorch_distributed_mnist_tpu.parallel.zero import (
            shard_state_zero1,
        )

        state, sharding = shard_state_zero1(state, mesh)
    elif layout == "none":
        # Commit the state to the replicated layout the pass's program
        # returns it in, as Trainer.precompile does on the command line's
        # path: left on the device jit made it on, the second pass would
        # see another input layout than the first and compile again.
        state = jax.device_put(state, NamedSharding(mesh, PartitionSpec()))
    else:
        raise ValueError(f"unknown optimizer_sharding {layout!r}")

    steps = job["steps_per_pass"]
    batch = job["batch_per_chip"] * chips
    raw_images, raw_labels = synthetic_dataset(steps * batch, seed=run.seed)
    images = normalize_images(raw_images)
    labels = raw_labels.astype(np.int32)
    train_loader, test_loader = (
        MNISTDataLoader(images, labels, batch_size=batch, train=train,
                        seed=run.seed) for train in (True, False))

    with compile_log.measure("reference_check"):
        check = check_against_reference(
            run, state, images[:N_CHECK], labels[:N_CHECK])
    run.note(kind="reference_check", **check)

    trainer = Trainer(
        state, train_loader, test_loader, mesh=mesh,
        mode=job.get("trainer_mode", "scan"), state_sharding=sharding,
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
            with traced_slice(trace_dir):
                for _ in range(TRACED_PASSES):
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
    interpreted = pallas_lowerings.snapshot()["interpret"]
    bad_passes = sum(1 for x in losses if not math.isfinite(x))
    run.counters.update(
        chips=chips, device_kind=devices[0].device_kind,
        steps_per_pass=steps, images_per_pass=images_per_pass,
        passes=n, pass_walls_s=walls, window_s=window_s,
        median_pass_s=median_wall, warm_pass_s=warm_s,
        traced_passes=TRACED_PASSES,
        compile=setup_compile, staging=staging, compile_cache=cache)
    run.note(kind="passes", n=n, window_s=window_s, warm_pass_s=warm_s,
             median_pass_s=median_wall,
             median_images_per_s_per_chip=images_per_pass / median_wall
             / chips,
             warm_loss=warm_loss, pass_losses=losses, pass_walls_s=walls)
    run.note(kind="setup", setup_s=setup_s, compile=setup_compile,
             programs=compile_log.stats()["programs"], compile_cache=cache,
             compiles_in_window=compiles_in_window,
             interpreted_pallas=interpreted, staging=staging,
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
                    and compiles_in_window == 0 and interpreted == 0),
        "attempted": n * steps,
        "failed": bad_passes * steps,
        "end_to_end": {"train_images_per_s_per_chip": rate,
                       "setup_s": setup_s},
        "devices": devices,
    }
