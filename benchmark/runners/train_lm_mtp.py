"""Runner ``train_lm_mtp``: a sparse token model with two heads and a
selection bias on the trainer's normal path.

``runners/train_lm.py``'s path and window (registry model -> weights from
the seed -> reference check -> Adam state -> ``MNISTDataLoader`` over
``data/tokens.py`` -> ``Trainer`` in scan mode, one pass = one
``Trainer.train()``; one packed sequence counts as one image), for a model
whose training forward returns the logits of a multi-token-prediction
module beside the next token's, whose objective adds that module's
cross-entropy and a sown balance term, and whose expert layers choose under
a bias that the train step moves (``models/instella.py``). ``train_lm.py``
holds one logits array to the reference and knows no state beside the
parameters, so what differs is written here: both heads, and one step of
the trainer's own pass held to the reference. What does not differ is
``train_lm.py``'s (``error_sums``, ``rms_err``, ``largest_err``,
``flip_share``, ``build_model``, ``_pick``) and ``train.py``'s
(``traced_slice``, ``DTYPES``); the next ``benchmark`` PR folds the three
token runners into one (PERF.md section 7).

The job (the traffic file) states the objective's weights and the bias's
rate: ``mtp_weight``, ``aux_weight``, ``bias_rate``.

``correct`` (before the window, on the freshly seeded weights and a seeded
non-zero selection bias, on one step's batch of the timed length): the
model's two logit arrays agree with the configuration's plain reference,
computed in blocks on the same device with the experts the system chose,
and few of those choices differ from the reference's own. Then a
``Trainer`` built as the timed one takes ONE step on that batch with its
own pass's program, and what that program hands back is held to the
reference: the loss, the module's loss and the objective its metrics
report, the gradients of the named leaves (Adam's first moment after one
step from zero is a tenth of them), those leaves' change (Adam's first
step from the reference's gradient) and the selection bias in the state's
``buffers`` (tolerances and their reasons are the reference module's). The
timed state is then built anew from the seed, with the source's zero bias.
Every pass's loss is finite; nothing compiles and no Pallas call is
interpreted inside the window; the expert layers dropped no (token, choice)
pair in any pass.
"""

from __future__ import annotations

import json
import math
import statistics
import time
from functools import partial

from benchmark import trace as trace_lib

SCALARS = ("loss", "mtp_loss", "objective")
# The check's selection bias, normal(0, BIAS_SCALE) from the seed (the run
# itself starts from the source's zeros): a quarter of the spread of the
# scores at the seed (sigmoid of a unit normal), so that what ``score +
# bias`` chooses differs from what the scores alone would in a large share
# of the tokens, and no expert is left without any.
BIAS_SCALE = 0.05
# optax.adam's: after one step from zero the first moment is (1 - b1) g.
ADAM_B1 = 0.9


def system_forward(model):
    """``forward(variables, x) -> ((logits, mtp_logits), choices)`` of the
    registry model in training: ``choices`` the experts each sparse layer
    chose, the trunk's layers in order and then the module's."""
    def order(name):  # block0 .. block<L-1>, then the module's
        return (1, 0) if name == "mtp_block" else (0, int(name[5:]))

    def forward(variables, x):
        outputs, state = model.apply(
            variables, x, train=True, mutable=["intermediates"])
        blocks = state["intermediates"]
        return outputs, tuple(
            blocks[n]["moe"]["choices"][0]
            for n in sorted(blocks, key=order) if "moe" in blocks[n])

    return forward


def with_seeded_bias(variables, seed: int):
    """``variables`` with every selection bias drawn from ``seed``."""
    import jax

    from pytorch_distributed_mnist_tpu.ops.metrics import BIAS_COLLECTION

    flat, treedef = jax.tree_util.tree_flatten(variables[BIAS_COLLECTION])
    keys = jax.random.split(jax.random.key(seed + 1), len(flat))
    return {**variables, BIAS_COLLECTION: jax.tree_util.tree_unflatten(
        treedef, [BIAS_SCALE * jax.random.normal(k, b.shape, b.dtype)
                  for k, b in zip(keys, flat)])}


def reference_step(lm, ref, config, job, lower=False):
    """``fn(variables, x, y, given=None) -> (outputs, own, stepped)`` of the
    plain reference ``ref`` (the module) under the job's weights: both
    logit arrays, the experts its routers choose, and what one step is
    held to: the two losses, the objective, its gradients at the named
    leaves and the bias after the update. With ``given`` every sparse
    layer computes with those experts. ``lower``: its weights in the
    nearest precision below the stated one (``ref.lower_precision``)."""
    import jax

    from pytorch_distributed_mnist_tpu.ops.metrics import BIAS_COLLECTION

    kwargs = ref.model_kwargs(config["kwargs"])
    shape = ref.shape_from_kwargs(kwargs)
    leaves = ref.grad_leaves(kwargs)
    weights = {k: job[k] for k in ("mtp_weight", "aux_weight")}

    @jax.jit
    def fn(variables, x, y, given=None):
        bias = variables[BIAS_COLLECTION]

        def loss_fn(p):
            if lower:
                p = {"params": ref.lower_precision(p["params"])}
            outputs, own, loads, aux = ref.forward_all(
                {**p, BIAS_COLLECTION: bias}, x, choices=given, **shape)
            total, loss, mtp_loss = ref.objective(outputs, aux, y, **weights)
            return total, (outputs, own, loads, loss, mtp_loss)

        (total, (outputs, own, loads, loss, mtp_loss)), grads = \
            jax.value_and_grad(loss_fn, has_aux=True)(
                {"params": variables["params"]})
        return outputs, own, {
            "loss": loss, "mtp_loss": mtp_loss, "objective": total,
            "grads": lm._pick(grads, leaves),
            "bias": ref.bias_after_step(bias, loads, job["bias_rate"])}

    return fn


def forward_sums(lm, outputs, chosen, want_outputs, own):
    """What the two logit arrays and the choices are judged by, reduced
    where the arrays are (``lm.error_sums``, ``lm.flip_share``)."""
    import jax

    return jax.device_get(jax.jit(lambda g, w, c, o: {
        "logits": lm.error_sums(g[0], w[0]),
        "mtp_logits": lm.error_sums(g[1], w[1]),
        "choice_flips": lm.flip_share(c, o)})(
            outputs, want_outputs, chosen, own))


def step_sums(lm, ref, job, got, want):
    """What one step is judged by. ``want``: :func:`reference_step`'s
    ``stepped``. ``got``: the like from the system, with the step's own
    change of the named leaves under ``update``. That change is held to
    Adam's first step from the reference's gradient (``ref.
    adam_first_step``), each entry weighed by the size of that gradient:
    the first step is ``lr`` times the gradient's sign, so an entry whose
    gradient is smaller than its rounding is a coin's toss at full size
    and says nothing, and a state left unchanged reads 1."""
    import jax
    import jax.numpy as jnp

    def sums(got, want):
        out = {k: lm.error_sums(got[k], want[k]) for k in SCALARS}
        out["grads"] = lm.error_sums(got["grads"], want["grads"])
        weight = jax.tree_util.tree_map(jnp.abs, want["grads"])
        out["update"] = lm.error_sums(
            jax.tree_util.tree_map(jnp.multiply, weight, got["update"]),
            jax.tree_util.tree_map(
                lambda w, g: w * ref.adam_first_step(g, job["lr"]),
                weight, want["grads"]))
        # in updates: 0 where an entry moved as the reference's, 1 where
        # it stayed, 2 where it moved the other way
        moved = jnp.concatenate([
            jnp.abs(a - b) for a, b in zip(
                jax.tree_util.tree_leaves(got["bias"]),
                jax.tree_util.tree_leaves(want["bias"]))
        ]) / job["bias_rate"]
        out["bias"] = jnp.stack([jnp.mean(moved), jnp.sum(moved > 0.5)])
        return out

    return jax.device_get(jax.jit(sums)(got, want))


def routed_kind(kind: str, path: str) -> str:
    """``grad_routed`` for the gradient of a router or of the routed
    experts, which has a limit of its own: the reference computes with the
    experts that the model's forward program chose, the step's program
    rounds the stream otherwise and chooses others in a few pairs of a
    thousand, and a pair that goes elsewhere moves these leaves' gradient
    by all of its part (``ref.TOLERANCES``)."""
    routed = kind == "grad" and ("/moe/router/" in path or "/moe/w_" in path)
    return "grad_routed" if routed else kind


def judged(lm, ref, config, forward, stepped) -> dict:
    """The errors of :func:`forward_sums` and :func:`step_sums`, each
    beside its limit (``ref.TOLERANCES``, where their reasons are)."""
    tol = ref.TOLERANCES[config["dtype"]]
    errors = {k: lm.rms_err(forward[k]) for k in ("logits", "mtp_logits")}
    largest = {k: lm.largest_err(forward[k])
               for k in ("logits", "mtp_logits")}
    errors["choice_flips"] = float(forward["choice_flips"])
    errors.update({k: lm.largest_err(stepped[k]) for k in SCALARS})
    for kind, key in (("grad", "grads"), ("update", "update")):
        for path, sums in stepped[key].items():
            name = f"{routed_kind(kind, path)}:{path}"
            errors[name] = lm.rms_err(sums)
            largest[name] = lm.largest_err(sums)
    errors["bias"] = float(stepped["bias"][0])
    largest["bias_entries_apart"] = float(stepped["bias"][1])
    limits = {k: tol[k.split(":")[0]] for k in errors}
    return {"errors": errors, "limits": limits, "largest": largest,
            "ok": all(errors[k] <= limits[k] for k in errors)}


def check_against_reference(lm, ref, config, job, model, variables, x, y,
                            trainer_of, routing_log) -> dict:
    """Errors of the model's two logit arrays and of one step of the
    trainer's own pass against the plain reference ``ref`` (the module) of
    the configuration ``config`` under the job's weights, on one step's
    batch ``x``, ``y``, each beside its limit. ``trainer_of(variables)``
    builds the ``Trainer`` as the timed one is built, over a loader of
    that batch alone, and may consume ``variables``; ``lm`` is
    ``runners/train_lm.py``, whose measures these are.

    First the model's forward and the reference, while the device has the
    room the optimizer's moments will take; what the step is held to waits
    on the host. Then the step."""
    import jax

    leaves = ref.grad_leaves(ref.model_kwargs(config["kwargs"]))
    outputs, chosen = jax.jit(system_forward(model))(variables, x)
    want_outputs, own, want = reference_step(lm, ref, config, job)(
        variables, x, y, chosen)
    forward = forward_sums(lm, outputs, chosen, want_outputs, own)
    del outputs, want_outputs, chosen, own
    want = jax.device_get(want)
    before = jax.device_get(lm._pick(variables, leaves))
    trainer = trainer_of(variables)
    del variables
    try:
        stepped = step_sums(lm, ref, job, stepped_by(
            lm, trainer, routing_log, leaves, before), want)
    finally:
        trainer.close()
    return judged(lm, ref, config, forward, stepped)


def check_lower_precision(lm, ref, config, job, variables, tokens,
                          labels) -> dict:
    """The control of the configuration's limits: the reference itself as
    the system, its weights in the nearest precision below the stated one,
    and its step Adam's first from its own gradients. It has to come out
    as not ``ok`` (tests/test_instella_bench.py at a tiny size,
    tests_tpu/test_instella_on_tpu.py at the timed one)."""
    import jax

    outputs, chosen, got = reference_step(lm, ref, config, job, lower=True)(
        variables, tokens, labels)
    got = jax.device_get(got)  # room for the reference
    want_outputs, own, want = reference_step(lm, ref, config, job)(
        variables, tokens, labels, chosen)
    forward = forward_sums(lm, outputs, chosen, want_outputs, own)
    del outputs, want_outputs
    got["update"] = jax.tree_util.tree_map(
        lambda g: ref.adam_first_step(g, job["lr"]), got["grads"])
    return judged(lm, ref, config, forward,
                  step_sums(lm, ref, job, got, want))


def stepped_by(lm, trainer, routing_log, leaves, before):
    """One pass of one step on ``trainer``, and what it did in
    :func:`step_sums`'s form: the two losses and the objective as the
    pass's metrics report them, the gradients that Adam's first moment
    holds after one step from zero, the named leaves' change from
    ``before`` and the bias the state carries out."""
    import jax
    import optax

    from pytorch_distributed_mnist_tpu.ops.metrics import BIAS_COLLECTION

    loss, _acc = trainer.train()
    seen = routing_log.summary()
    if seen["steps"] != 1:
        raise ValueError(f"the check's pass took {seen['steps']} steps")
    state = trainer.state
    moment = lm._pick(
        optax.tree_utils.tree_get(state.opt_state, "mu"), leaves)
    after = lm._pick(state.params, leaves)
    return {
        "loss": loss.average, "mtp_loss": seen["mtp_loss_last_pass"],
        "objective": seen["objective_last_pass"],
        "grads": {k: v / (1.0 - ADAM_B1) for k, v in moment.items()},
        "update": {k: after[k] - before[k] for k in leaves},
        "bias": state.buffers[BIAS_COLLECTION]}


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
    from pytorch_distributed_mnist_tpu.ops.loss import set_loss_impl
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
        routing_log,
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
    routing_log.reset()
    set_loss_impl("xla")
    # The process's count so far (a test process has run other kernels).
    lowered_before = pallas_lowerings.snapshot()

    # -- set-up: the system from the seed ---------------------------------
    mesh = make_mesh(("data",), devices=devices)
    init = jax.jit(model.init)
    like = jnp.zeros((1, seq_len), jnp.float32)
    with compile_log.measure("init"):
        variables = with_seeded_bias(
            init(jax.random.key(run.seed), like), run.seed)

    steps = job["steps_per_pass"]
    batch = job["batch_per_chip"] * chips
    tokens, labels = synthetic_token_corpus(
        steps * batch, seq_len, run.config["kwargs"]["vocab_size"],
        seed=run.seed, **job.get("documents", {}))

    def loader(x, y, train):
        return MNISTDataLoader(x, y, batch_size=batch, train=train,
                               seed=run.seed)

    make_state = jax.jit(
        partial(train_state_from_params, model, lr=job["lr"]),
        donate_argnums=0)

    def trainer_of(variables, train_loader, test_loader):
        with compile_log.measure("init_state"):
            state = make_state(variables)
        # As runners/train.py: the layout the pass's program returns.
        state = jax.device_put(state, NamedSharding(mesh, PartitionSpec()))
        return Trainer(
            state, train_loader, test_loader, mesh=mesh,
            mode=job.get("trainer_mode", "scan"),
            grad_accum=job.get("grad_accum", 1),
            epoch_gather=job.get("epoch_gather", "host"),
            aux_weight=job["aux_weight"], mtp_weight=job["mtp_weight"],
            bias_rate=job["bias_rate"],
            feed_window=job.get("feed_window", 2), staging_log=staging_log)

    x, y = tokens[:batch], labels[:batch]
    one_step = loader(x, y, True)
    with compile_log.measure("reference_check"):
        check = check_against_reference(
            lm, ref, run.config, job, model, variables, x, y,
            lambda v: trainer_of(v, one_step, one_step), routing_log)
    del variables
    run.note(kind="reference_check", **check)

    # The run itself, from the seed's weights and the source's zero bias.
    train_loader, test_loader = (
        loader(tokens, labels, train) for train in (True, False))
    with compile_log.measure("init"):
        variables = init(jax.random.key(run.seed), like)
    trainer = trainer_of(variables, train_loader, test_loader)
    del variables

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
        walls, losses, by_pass = [], [], []
        trace_dir = run.scratch_dir("trace")
        t_window = time.perf_counter()
        setup_s = time.time() - run.started_at

        def measured_pass():
            wall, loss = one_pass(len(walls) + 1)
            walls.append(wall)
            losses.append(loss)
            # Read with the pass's metrics: no host read of its own.
            seen = routing_log.summary()
            by_pass.append({k: seen.get(k) for k in (
                "bias_range_last_pass", "mtp_loss_last_pass")})
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
        first_pass_s=walls[0], last_pass_s=walls[-1],
        traced_passes=lm.TRACED_PASSES,
        compile=setup_compile, staging=staging, routing=routing,
        compile_cache=cache)
    # The rate is a mean over a drift (PERF.md section 7: the router pulls
    # towards the held experts inside the window): the window's first and
    # last pass beside it, for a later claim to be read against.
    run.note(kind="passes", n=n, window_s=window_s, warm_pass_s=warm_s,
             median_pass_s=median_wall, first_pass_s=walls[0],
             last_pass_s=walls[-1],
             median_images_per_s_per_chip=images_per_pass / median_wall
             / chips,
             warm_loss=warm_loss, pass_losses=losses, pass_walls_s=walls,
             pass_mtp_losses=[p["mtp_loss_last_pass"] for p in by_pass],
             pass_bias_ranges=[p["bias_range_last_pass"] for p in by_pass])
    run.note(kind="routing", **routing)
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
                    and lowerings["interpret"] == 0 and dropped == 0),
        "attempted": n * steps,
        "failed": bad_passes * steps,
        "end_to_end": {"train_images_per_s_per_chip": rate,
                       "setup_s": setup_s},
        "devices": devices,
    }
