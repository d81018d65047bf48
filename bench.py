"""Benchmark: MNIST CNN training throughput, images/sec/chip (+ MFU).

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "images/sec/chip", "vs_baseline": N,
   "mfu": ..., "backend": ..., "device_kind": ..., ...}

``value`` is this framework's jitted scan-epoch training throughput.
``mfu`` is model-FLOPs utilization: (FLOPs/step x steps/sec) / chip peak
FLOPs, with FLOPs/step taken from the compiled program's own cost analysis
(falling back to an analytic count for the 2-conv CNN) and the peak from the
device kind's bf16 spec (the CNN computes in bfloat16, models/cnn.py).

``vs_baseline`` compares against the only baseline measurable here: the
reference implementation's approach — a PyTorch per-batch train loop with
the same CNN and optimizer — on the hardware the reference can use in this
environment (CPU; the reference repo is CUDA-only and publishes no numbers
of its own, see PARITY.md). The ``baseline`` field names this so the ratio
is not mistaken for a like-for-like chip comparison.

The accelerator bench runs in ONE child process with a timeout (the parent
stays off jax, so the child is the only process that touches the chip). The
child must report ``"backend": "tpu"``; anything else, or a failed child, is
a non-zero exit — a measurement path that finds no chip fails.
``BENCH_FORCE_CPU=1`` is the explicit switch the CPU schema tests use; its
line says ``"backend": "cpu"`` and is never a device measurement. The
persistent compile cache goes through the shared wiring
(``utils/compile_cache.py``: ``JAX_COMPILATION_CACHE_DIR`` when set, else
``<checkout>/.xla_cache``).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

BATCH = 2048
TORCH_STEPS = 8

# ViT bench mode (--vit): the CNN headline is HBM-bound at 1.9
# MFLOP/image, so its MFU says nothing about the MXU path. This config is
# the end-to-end MXU-bound twin: patch 1 -> T=784 tokens/image, width 512
# (head_dim 128 = the MXU/flash tile), depth 6, remat — ~111
# GFLOP/image model FLOPs, the regime where honest MFU is meaningful.
VIT_BATCH = 128
VIT_CFG = dict(patch_size=1, embed_dim=512, depth=6, num_heads=4)

# Per-chip peak dense bf16 FLOPs by TPU generation (public spec sheets).
_PEAK_FLOPS = [
    ("v6", 918e12),  # Trillium
    ("v5p", 459e12),
    ("v5 lite", 197e12),
    ("v5e", 197e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
]

# Analytic fallback: forward FLOPs/image for models/cnn.py (2 MACs per
# multiply-add), x3 for a training step (fwd + ~2x in bwd).
_CNN_FWD_FLOPS = (
    2 * 28 * 28 * 32 * 9 * 1  # conv1
    + 2 * 28 * 28 * 64 * 9 * 32  # conv2
    + 2 * (64 * 14 * 14) * 128  # fc1
    + 2 * 128 * 10  # fc2
)
_CNN_STEP_FLOPS_PER_IMAGE = 3 * _CNN_FWD_FLOPS


def _peak_flops(device_kind: str):
    fake = os.environ.get("BENCH_FAKE_PEAK_FLOPS")
    if fake:  # test-only: lets the hermetic CPU suite exercise the
        return float(fake)  # MFU math and the impossibility guard
    kind = device_kind.lower()
    if kind == "cpu":
        # The declared CPU schema run: there is no peak to divide by, so
        # its MFU stays null beside "backend": "cpu".
        return None
    for key, peak in _PEAK_FLOPS:
        if key in kind:
            return peak
    raise ValueError(
        f"no peak FLOP/s on record for device kind {device_kind!r}: MFU "
        f"against an unknown peak would be silently null — add the device "
        f"to _PEAK_FLOPS with its source")


def _fake_bounds() -> dict:
    """Test-only physical-bound overrides present in the environment.
    They must never silently shape a real capture: every child refuses
    to run on a real TPU with them set (``_refuse_fakes_on_tpu``) and
    stamps them into its output otherwise."""
    return {k: os.environ[k]
            for k in ("BENCH_FAKE_PEAK_FLOPS", "BENCH_FAKE_HBM_BW")
            if os.environ.get(k)}


def _refuse_fakes_on_tpu(result: dict, platform: str):
    """Returns an error dict when a test-only bound override leaked into
    a real TPU run (the capture would carry a valid-looking sync marker
    with bounds computed against a fake peak); stamps the overrides into
    ``result`` on non-TPU backends so a test run can never pass as
    evidence. Returns None when the run may proceed."""
    fakes = _fake_bounds()
    if not fakes:
        return None
    if platform == "tpu":
        return {"ok": False,
                "error": f"test-only bound overrides set on a real TPU "
                         f"run: {sorted(fakes)}"}
    result["fake_bounds"] = fakes
    return None


def configure_jax():
    """Shared jax prologue for every bench entry point (this file's
    children and modes, tools/bench_kernels.py, tools/sweep_flash.py): the
    persistent compile cache, through the ONE shared wiring every entry
    point uses (``utils/compile_cache.configure``, same as ``cli.run``) —
    ``JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.xla_cache``;
    set-but-empty disables, as the hermetic tests use. Returns the active
    directory (``None`` = disabled) so every line can name it."""
    from pytorch_distributed_mnist_tpu.utils.compile_cache import configure

    cache_dir = configure()
    if cache_dir:
        print(f"compile cache: {cache_dir}", file=sys.stderr, flush=True)
    return cache_dir


def _not_tpu_error(platform: str):
    """``None`` when this run may measure: on a TPU, or under the explicit
    ``BENCH_FORCE_CPU=1`` switch of the CPU schema tests (whose lines say
    ``"backend": "cpu"``). Otherwise the error naming the platform found —
    a measurement path that finds no chip fails."""
    if platform == "tpu" or os.environ.get("BENCH_FORCE_CPU"):
        return None
    return (f"bench ran on platform {platform!r}, not a TPU; a CPU number "
            f"is not a device measurement (BENCH_FORCE_CPU=1 runs the CPU "
            f"schema check explicitly)")


def _require_tpu(platform: str) -> None:
    """The bench modes' form of :func:`_not_tpu_error`: print the error
    line and exit non-zero."""
    error = _not_tpu_error(platform)
    if error:
        print(json.dumps({"error": error}))
        sys.exit(1)


def _warmup_and_time(run_fn, st, expected_count, reps: int):
    """Shared timing protocol: one compile/warmup pass synced by a full
    host read of the metric count, then best-of-``reps`` with the same
    host-read sync per rep — identical for every measured path (CNN
    primary, secondaries, ViT) so the numbers stay comparable. The host
    read is the sync point: it cannot complete before the device has
    executed everything queued ahead of it."""
    st, m = run_fn(st)
    float(m.count)  # full host roundtrip: execution definitely done
    t_best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        st, m = run_fn(st)
        assert float(m.count) == expected_count
        t_best = min(t_best, time.perf_counter() - t0)
    return st, t_best


def _vit_model_flops_per_image(t: int, c: int, depth: int, patch: int,
                               num_classes: int = 10,
                               mlp_ratio: int = 4) -> float:
    """Analytic MODEL FLOPs per image for one ViT training step (fwd +
    2x bwd), matmuls only — the MFU convention. Per block: qkv 6TC² +
    out-proj 2TC² + MLP 4·r·TC² + attention QKᵀ/PV 4T²C. Remat
    recompute is deliberately NOT credited: MFU counts useful model
    FLOPs, so a rematerialized run reports the lower honest figure."""
    per_block = (8 + 4 * mlp_ratio) * t * c * c + 4 * t * t * c
    embed = 2 * t * (patch * patch) * c
    head = 2 * c * num_classes
    return 3.0 * (depth * per_block + embed + head)


def child_bench_vit(steps: int, reps: int) -> dict:
    """End-to-end ViT training throughput + honest MFU (``--vit``).

    Same machinery as the CNN scan-epoch bench — create_train_state,
    make_train_epoch, metric-count host sync — on the MXU-bound
    VIT_CFG. Primary path: Pallas flash attention; secondary: the same
    model with dense XLA attention (the baseline ratio). The explicit
    BENCH_FORCE_CPU schema run shrinks to a smoke-test shape with dense
    f32 attention (flash on CPU is interpret-mode — a meaningless thing
    to time).
    """
    import jax

    cache_dir = configure_jax()

    import jax.numpy as jnp
    import numpy as np

    from pytorch_distributed_mnist_tpu.data.mnist import (
        normalize_images,
        synthetic_dataset,
    )
    from pytorch_distributed_mnist_tpu.models import get_model
    from pytorch_distributed_mnist_tpu.ops.pallas.flash import flash_attention
    from pytorch_distributed_mnist_tpu.parallel.mesh import make_mesh
    from pytorch_distributed_mnist_tpu.train.state import create_train_state
    from pytorch_distributed_mnist_tpu.train.steps import make_train_epoch

    n_chips = jax.device_count()
    device = jax.devices()[0]
    not_tpu = _not_tpu_error(device.platform)
    if not_tpu:
        return {"ok": False, "error": not_tpu}
    fake_stamp: dict = {}
    refused = _refuse_fakes_on_tpu(fake_stamp, device.platform)
    if refused:
        return refused
    mesh = make_mesh(("data",)) if n_chips > 1 else None
    on_tpu = device.platform == "tpu"
    # Test-only: drive the exact TPU branch (flash attention + remat +
    # bf16 + dense secondary) at tiny shapes on CPU (flash falls back to
    # interpret mode), so a latent bug there surfaces in the hermetic
    # suite instead of burning a rare chip-recovery window. Labelled in
    # the output via the shrunken model_config + backend "cpu".
    smoke = bool(os.environ.get("BENCH_VIT_TPU_SMOKE")) and not on_tpu
    flash_path = on_tpu or smoke
    if on_tpu:
        batch, cfg = VIT_BATCH, dict(VIT_CFG)
        dtype = jnp.bfloat16
    elif smoke:
        batch = 8
        cfg = dict(patch_size=7, embed_dim=32, depth=1, num_heads=2)
        dtype = jnp.bfloat16
    else:
        batch = 32
        cfg = dict(patch_size=4, embed_dim=64, depth=2, num_heads=4)
        dtype = jnp.float32
    t_seq = (28 // cfg["patch_size"]) ** 2
    flops_per_image = _vit_model_flops_per_image(
        t_seq, cfg["embed_dim"], cfg["depth"], cfg["patch_size"])

    images, labels = synthetic_dataset(batch, seed=0)
    x = normalize_images(images)
    y = labels.astype(np.int32)
    batches = {
        "image": jnp.broadcast_to(jnp.asarray(x), (steps,) + x.shape),
        "label": jnp.broadcast_to(jnp.asarray(y), (steps,) + y.shape),
    }

    from pytorch_distributed_mnist_tpu.utils.profiling import compile_log

    def measure(attn_fn, program):
        model = get_model(
            "vit", attention_fn=attn_fn, remat=flash_path,
            compute_dtype=dtype, **cfg)
        state = create_train_state(model, jax.random.key(0))
        epoch_fn = make_train_epoch(mesh)
        with compile_log.measure(program):
            compiled = epoch_fn.lower(state, batches).compile()
        state, best = _warmup_and_time(
            lambda st: compiled(st, batches), state, batch * steps, reps)
        del state
        return best

    flash_s = measure(flash_attention if flash_path else None,
                      "vit_epoch_flash" if flash_path else "vit_epoch_dense")
    peak = _peak_flops(device.device_kind)
    img_per_sec = batch * steps / flash_s / n_chips
    mfu = (flops_per_image * img_per_sec / peak) if peak else None
    if mfu is not None and mfu > 1.0:
        # Same physical bound as tools/bench_kernels.py: >100% of peak
        # means the sync failed; the number must not survive as evidence.
        return {"ok": False,
                "error": f"impossible ViT MFU {mfu:.3g} (>100% of peak): "
                         f"device sync did not wait for execution"}
    result = {
        "ok": True,
        "images_per_sec_per_chip": img_per_sec,
        "steps_per_sec": steps / flash_s,
        "global_batch": batch,
        "n_chips": n_chips,
        "backend": device.platform,
        "device_kind": device.device_kind,
        "seq_len": t_seq,
        "model_config": cfg,
        "attention": "flash" if flash_path else "dense",
        "remat": flash_path,
        "model_flops_per_image": flops_per_image,
        "peak_flops_per_chip": peak,
        "mfu": mfu,
        "sync": "host_read",
        "compile_cache": cache_dir,
    }
    result.update(fake_stamp)
    if flash_path:
        # Baseline ratio: byte-identical model/step with dense XLA
        # attention. A dense twin that does not compile or run fails the
        # child: a line with half its fields missing is not a result.
        dense_s = measure(None, "vit_epoch_dense")
        dense_mfu = (flops_per_image * batch * steps
                     / dense_s / n_chips / peak) if peak else None
        if dense_mfu is not None and dense_mfu > 1.0:
            # The dense twin is the DENOMINATOR of the headline
            # flash_over_dense ratio; an early-sync dense time would
            # publish a garbage speedup under a valid-looking flash line.
            return {"ok": False,
                    "error": f"impossible dense ViT MFU {dense_mfu:.3g} "
                             f"(>100% of peak): device sync did not wait "
                             f"for execution"}
        result["images_per_sec_per_chip_dense_attn"] = (
            batch * steps / dense_s / n_chips)
        result["flash_over_dense_speedup"] = dense_s / flash_s
        result["dense_attn_mfu"] = dense_mfu
    result["compile_stats"] = compile_log.stats()
    return result


def child_bench(steps: int, reps: int) -> dict:
    """Run the accelerator bench on whatever backend the env selects."""
    import jax

    cache_dir = configure_jax()

    import jax.numpy as jnp
    import numpy as np

    from pytorch_distributed_mnist_tpu.data.mnist import (
        normalize_images,
        synthetic_dataset,
    )
    from pytorch_distributed_mnist_tpu.models import get_model
    from pytorch_distributed_mnist_tpu.parallel.mesh import make_mesh
    from pytorch_distributed_mnist_tpu.train.state import create_train_state
    from pytorch_distributed_mnist_tpu.train.steps import (
        make_train_epoch,
        make_train_step,
    )

    n_chips = jax.device_count()
    device = jax.devices()[0]
    not_tpu = _not_tpu_error(device.platform)
    if not_tpu:
        return {"ok": False, "error": not_tpu}
    fake_stamp: dict = {}
    refused = _refuse_fakes_on_tpu(fake_stamp, device.platform)
    if refused:
        return refused
    mesh = make_mesh(("data",)) if n_chips > 1 else None
    # Stepwise = time the per-batch jitted step instead of the scan epoch:
    # the CPU schema run needs it (XLA:CPU pessimizes convs inside scanned
    # while-bodies ~30x).
    stepwise = device.platform == "cpu"
    if device.platform == "cpu":
        # CPU schema run: bf16 conv is emulated (and awful) on CPU; use f32
        # and a smaller batch so it finishes in seconds, not minutes. The
        # TPU path keeps the bf16 MXU configuration. The forced-secondaries
        # test mode shrinks further: its scan-epoch programs hit XLA:CPU's
        # pathological conv-in-loop path, and it only needs to prove the
        # plumbing, not measure.
        batch = 64 if os.environ.get("BENCH_FORCE_SECONDARIES") else 256
        model = get_model("cnn", compute_dtype=jnp.float32)
    else:
        batch = BATCH
        model = get_model("cnn")
    state = create_train_state(model, jax.random.key(0))

    images, labels = synthetic_dataset(batch, seed=0)
    x = normalize_images(images)
    y = labels.astype(np.int32)
    batches = {
        "image": jnp.broadcast_to(x, (steps,) + x.shape),
        "label": jnp.broadcast_to(y, (steps,) + y.shape),
    }

    from pytorch_distributed_mnist_tpu.utils.profiling import compile_log

    # AOT-compile the measured program ONCE (timed + cache-accounted per
    # program in compile_log) and drive the timing loop with the compiled
    # executable directly. One compile serves both the cost analysis and
    # the measurement.
    if stepwise:
        # On TPU the scan epoch is the whole point: one device program per
        # epoch, no host round-trips. The stepwise path exists for the CPU
        # schema run (see above).
        one = {"image": jnp.asarray(x), "label": jnp.asarray(y)}
        step_fn = make_train_step(mesh)
        with compile_log.measure("train_step"):
            compiled = step_fn.lower(state, one).compile()

        def run_pass(state):
            m = None
            for _ in range(steps):
                state, m = compiled(state, one)
            return state, m

        per_step_scale = 1.0
    else:
        epoch_fn = make_train_epoch(mesh)
        with compile_log.measure("train_epoch"):
            compiled = epoch_fn.lower(state, batches).compile()

        def run_pass(state):
            return compiled(state, batches)

        per_step_scale = float(steps)

    # FLOPs/step from the compiled program's own cost analysis; where the
    # backend reports none, the analytic count for this CNN — and the
    # line says which of the two it used.
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    total = float((cost or {}).get("flops", 0.0))
    if total > 0:
        flops_per_step = total / per_step_scale
        flops_source = "cost_analysis"
    else:
        flops_per_step = float(_CNN_STEP_FLOPS_PER_IMAGE * batch)
        flops_source = "analytic"

    expected = batch * (1 if stepwise else steps)
    state, best = _warmup_and_time(run_pass, state, expected, reps)

    steps_per_sec = steps / best
    peak = _peak_flops(device.device_kind)
    mfu = (flops_per_step * steps_per_sec / n_chips / peak) if peak else None
    if mfu is not None and mfu > 1.0:
        # Same physical bound as tools/bench_kernels.py: >100% of peak
        # means the sync failed; the number must not survive as evidence.
        return {"ok": False,
                "error": f"impossible CNN MFU {mfu:.3g} (>100% of peak): "
                         f"device sync did not wait for execution"}
    result = {
        "ok": True,
        "images_per_sec_per_chip": batch * steps / best / n_chips,
        "steps_per_sec": steps_per_sec,
        "global_batch": batch,
        "n_chips": n_chips,
        "backend": device.platform,
        "device_kind": device.device_kind,
        "flops_per_step": flops_per_step,
        "flops_source": flops_source,
        "peak_flops_per_chip": peak,
        "mfu": mfu,
        "compile_cache": cache_dir,
    }
    result.update(fake_stamp)
    if os.environ.get("BENCH_FORCE_SECONDARIES"):
        # Test-only mode (shrunken batch, CPU secondaries): label the
        # line so it can never pass silently as a comparable measurement.
        result["forced_secondaries"] = True

    # Secondaries normally run on accelerator only; BENCH_FORCE_SECONDARIES
    # exists so the hermetic suite can pin their plumbing on CPU. A
    # secondary that does not compile or run FAILS the child (the
    # exception reaches __main__'s ok=false line): a kernel the chip
    # refuses must not hide as a *_error field on a line that exits 0.
    secondaries = (device.platform != "cpu"
                   or bool(os.environ.get("BENCH_FORCE_SECONDARIES")))
    if secondaries and not os.environ.get("BENCH_SKIP_INDEXED"):
        # Secondary: the device-gather input path (--epoch-gather device)
        # on a real permuted dataset — the dataset resident in HBM, each
        # scan tick jnp.take-ing its rows. Unlike the primary (which
        # re-feeds one broadcast batch), this measures the throughput a
        # real epoch with fresh indices sees. Extra fields only.
        from pytorch_distributed_mnist_tpu.train.steps import (
            make_train_epoch_indexed,
        )

        n = steps * batch
        imgs, labs = synthetic_dataset(n, seed=1)
        data = {"image": jnp.asarray(normalize_images(imgs)),
                "label": jnp.asarray(labs.astype(np.int32))}
        perm = np.random.default_rng(0).permutation(n).astype(np.int32)
        ticks = {"idx": jnp.asarray(perm.reshape(steps, batch)),
                 "mask": jnp.ones((steps, batch), jnp.float32)}
        epoch_ix_fn = make_train_epoch_indexed(mesh)
        state_ix = create_train_state(model, jax.random.key(0))
        # Host snapshot of the fresh init: the sorted-ticks twin below
        # must start from IDENTICAL values, and the compiled
        # executable validates pytree statics strictly — a second
        # create_train_state would carry a fresh optax closure and be
        # rejected; np.copy of the same tree keeps treedef and values.
        import jax.tree_util as jtu

        init_ix = jtu.tree_map(np.asarray, state_ix)
        with compile_log.measure("train_epoch_indexed"):
            epoch_ix = epoch_ix_fn.lower(state_ix, data, ticks).compile()
        state_ix, best_ix = _warmup_and_time(
            lambda st: epoch_ix(st, data, ticks), state_ix,
            batch * steps, reps)
        result["images_per_sec_per_chip_device_gather"] = (
            batch * steps / best_ix / n_chips)
        # Hypothesis probe for the round-3 10%-slower finding: the
        # random-row gather's HBM locality. Same batch MEMBERSHIP
        # (identical loss/grad up to fp reduction order), indices
        # sorted within each tick — if this closes the gap, the
        # fix is sort-in-sampler; if not, the gather itself is the
        # cost and the north-star default should flip to host.
        ticks_sorted = {
            "idx": jnp.asarray(np.sort(
                perm.reshape(steps, batch), axis=1)),
            "mask": jnp.ones((steps, batch), jnp.float32)}
        state_ix2 = jtu.tree_map(np.copy, init_ix)
        state_ix2, best_ix2 = _warmup_and_time(
            lambda st: epoch_ix(st, data, ticks_sorted), state_ix2,
            batch * steps, reps)
        result["images_per_sec_per_chip_device_gather_sorted"] = (
            batch * steps / best_ix2 / n_chips)
        # Free the ~320 MB resident dataset before the next secondary
        # measures: dead bench arrays must not skew its HBM headroom.
        del data, ticks, ticks_sorted, state_ix, state_ix2

    if secondaries and not os.environ.get("BENCH_SKIP_FUSED"):
        # Secondary measurement: the all-first-party-kernel path (Pallas
        # fused cross-entropy + fused Adam). Extra fields only. Passing
        # the mesh embeds the loss kernel in the GSPMD program via its
        # nested shard_map (per-device batch shards, no gather) — the
        # same path `--loss fused` takes on a multi-chip run.
        from pytorch_distributed_mnist_tpu.ops.loss import set_loss_impl

        set_loss_impl("fused", mesh=mesh)
        try:
            state_f = create_train_state(
                model, jax.random.key(0), optimizer="adam_pallas",
                mesh=mesh)
            epoch_f_fn = make_train_epoch(mesh)
            with compile_log.measure("train_epoch_fused"):
                epoch_f = epoch_f_fn.lower(state_f, batches).compile()
            state_f, best_f = _warmup_and_time(
                lambda st: epoch_f(st, batches), state_f,
                batch * steps, reps)
            result["images_per_sec_per_chip_fused_kernels"] = (
                batch * steps / best_f / n_chips)
        finally:
            set_loss_impl("xla")
    # Per-program compile observability: wall ms, XLA compiles, and
    # persistent-cache hit/miss for every program measured above.
    result["compile_stats"] = compile_log.stats()
    return result


def _run_child(env_extra: dict, steps: int, reps: int, timeout: float):
    env = dict(os.environ, **env_extra)
    # Test-only mode must be an explicit opt-in per child, never inherited
    # from an ambient shell export (it shrinks the batch and runs the
    # CPU-pathological scan secondaries — a contaminated primary number).
    if "BENCH_FORCE_SECONDARIES" not in env_extra:
        env.pop("BENCH_FORCE_SECONDARIES", None)
    if "BENCH_VIT" not in env_extra:  # mode is per-child, never ambient
        env.pop("BENCH_VIT", None)
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--child",
             str(steps), str(reps)],
            capture_output=True, text=True, timeout=timeout, env=env,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
    except subprocess.TimeoutExpired:
        return None, f"child timed out after {timeout:.0f}s"
    child_error = None
    for line in reversed(proc.stdout.splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                result = json.loads(line)
            except json.JSONDecodeError:
                continue
            if result.get("ok"):
                return result, None
            if child_error is None and result.get("error"):
                child_error = result["error"]  # the child's own diagnosis
    if child_error is not None:
        return None, f"rc={proc.returncode}: {child_error}"
    tail = (proc.stderr or proc.stdout or "").strip().splitlines()[-6:]
    return None, f"rc={proc.returncode}: " + " | ".join(tail)


def _bench_child(env_extra: dict, steps: int, reps: int,
                 timeout: float) -> dict:
    """ONE child, on the backend the environment selects. The parent never
    touches jax, so the child is the only process that holds the chip. A
    child that ran anywhere but a TPU is a failure unless
    ``BENCH_FORCE_CPU=1`` asked for the CPU schema run explicitly."""
    result, err = _run_child(env_extra, steps=steps, reps=reps,
                             timeout=timeout)
    if result is None:
        return {"ok": False, "error": err}
    not_tpu = _not_tpu_error(result.get("backend"))
    if not_tpu:
        return {"ok": False, "error": not_tpu}
    return result


def bench_accelerator() -> dict:
    if os.environ.get("BENCH_FORCE_CPU"):
        # Keep the CPU schema run tiny: it exists to check the line's
        # shape, not to measure.
        return _bench_child({}, steps=4, reps=2, timeout=900.0)
    return _bench_child({}, steps=50, reps=3, timeout=1200.0)


VIT_STEPS = 20


def bench_vit_accelerator() -> dict:
    if os.environ.get("BENCH_FORCE_CPU"):
        return _bench_child({"BENCH_VIT": "1"}, steps=2, reps=1,
                            timeout=900.0)
    return _bench_child({"BENCH_VIT": "1"}, steps=VIT_STEPS, reps=3,
                        timeout=1800.0)


def main_vit() -> None:
    """The ``--vit`` output line: end-to-end MXU-bound perf evidence the
    CNN headline can't provide (VERDICT round-3 weak item 6)."""
    result = bench_vit_accelerator()
    out = {
        "metric": "mnist_vit_train_images_per_sec_per_chip",
        "unit": "images/sec/chip",
        "baseline": "same ViT/train-step with dense XLA attention "
                    "(flash_over_dense_speedup is the vs_baseline ratio)",
    }
    if result.get("ok"):
        out["value"] = round(result["images_per_sec_per_chip"], 1)
        speedup = result.get("flash_over_dense_speedup")
        out["vs_baseline"] = round(speedup, 3) if speedup else None
        mfu = result.get("mfu")
        out["mfu"] = round(mfu, 4) if mfu is not None else None
        for key in ("backend", "device_kind", "n_chips", "global_batch",
                    "steps_per_sec", "seq_len", "model_config", "attention",
                    "remat", "model_flops_per_image", "peak_flops_per_chip",
                    "images_per_sec_per_chip_dense_attn", "sync",
                    "compile_cache", "compile_stats"):
            if result.get(key) is not None:
                val = result[key]
                out[key] = round(val, 2) if isinstance(val, float) else val
    else:
        out["value"] = 0.0
        out["vs_baseline"] = 0.0
        out["error"] = result.get("error", "unknown failure")
    out["measured_at"] = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
    print(json.dumps(out))
    if not result.get("ok"):
        # Same convention as tools/bench_kernels.py / tools/sweep_flash.py:
        # a failed run never exits 0, so rc-gated consumers reject the
        # line without having to parse it.
        sys.exit(1)


SERVE_REQUESTS = 2000
SERVE_CONCURRENCY = 16


def _run_is_cpu_bound() -> bool:
    """ONE copy of the is-this-run-CPU decision the CPU-isolation helpers
    share, taken from the environment alone: the parent must not touch
    jax (or start a child that does) before XLA_FLAGS is final, and a
    chip belongs to one process at a time. A CPU run of a bench mode is
    always declared (``JAX_PLATFORMS=cpu`` or ``BENCH_FORCE_CPU=1``);
    undeclared, the mode runs on the chip or fails (``_require_tpu``)."""
    return (os.environ.get("JAX_PLATFORMS") == "cpu"
            or bool(os.environ.get("BENCH_FORCE_CPU")))


def _ensure_cpu_eigen_isolation() -> bool:
    """Append ``--xla_cpu_multi_thread_eigen=false`` to ``XLA_FLAGS`` so
    one XLA:CPU execution stops grabbing the whole host Eigen threadpool
    (one "chip" != the whole host); returns whether the isolation is
    active so the JSON lines can record the measurement environment
    honestly. Must run before the first jax device query — XLA_FLAGS are
    read once, at backend init. The installed XLA (jaxlib 0.9.0) accepts
    the flag; it only gates the CPU backend's intra-op pool, so it is a
    no-op on real accelerators."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_cpu_multi_thread_eigen" not in flags:
        flags = (flags + " --xla_cpu_multi_thread_eigen=false").strip()
        os.environ["XLA_FLAGS"] = flags
    return "xla_cpu_multi_thread_eigen=false" in flags


def _isolate_cpu_serve_devices() -> bool:
    """Make the forced-multi-device CPU backend behave like N chips.

    With ``--xla_force_host_platform_device_count=N`` (the CI stand-in
    for an N-chip host), a SINGLE XLA:CPU execution still grabs the whole
    host Eigen threadpool — so the N "devices" the replica pool fans out
    across contend for every core and the scaling/pipelining measurement
    measures only that contention. Eigen isolation pins each execution to
    one thread, which is exactly the resource model the forced device
    count is simulating.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        return False  # single-device CPU or a real backend: nothing to fix
    return _ensure_cpu_eigen_isolation()


def main_serve() -> None:
    """``--mode serve``: the serving trajectory's BENCH line.

    Drives the real serving stack — bucketed AOT
    :class:`InferenceEngine` + :class:`MicroBatcher` — in process
    (closed-loop worker threads submitting straight to the batcher, no
    sockets), so the line measures micro-batching + device forward
    throughput/latency rather than Python's HTTP server. Emits ONE JSON
    line: requests/sec headline, p50/p95/p99 latency, the batch-size
    histogram, and the zero-steady-state-recompiles invariant checked
    via ``CompileLog``. Never raises; failures become an ``error`` line
    (the always-emit-JSON contract the training bench follows).

    The multi-chip data plane rides the same line:

    - ``replica_scaling``: requests/sec through an :class:`EnginePool`
      at 1, 2, ..., ``n_devices`` replicas (pipelined dispatch, window
      replicas+1), each point re-checking zero steady-state recompiles
      PER REPLICA via the per-replica ``CompileLog`` program names;
    - ``pipeline_speedup``: the full pool driven with the in-flight
      window at replicas+1 vs 1 — window 1 serializes every batch's
      host-side staging behind the previous batch's result fetch AND
      caps the fleet at one busy replica, so this is the pipelining
      win the PR claims (>1.0 on any backend with real parallelism).
      Pool drives use fixed 8-row exact-bucket requests (batch
      formation pinned — see ``pool_stacks``) and the ratio is the
      median of interleaved paired drives, so CPU-share drift on a
      shared CI box cancels instead of deciding the sign.

    The SHARDED plane (``serve/programs.py``) gets its own ``sharded``
    block: for each registered mode (tensor x vit, expert x moe_mlp),
    the ABBA-paired sharded-vs-replicated throughput ratio at the SAME
    chip count, a mesh-scaling curve at fixed chips (mesh 1 = the
    replicated fleet, up to one all-chip mesh group), and per
    bucket x mode zero-recompile verdicts that fail the bench loudly.
    On a CPU world the block carries the BENCH_r05-style fallback
    caveat: host-thread collectives say nothing about ICI, so only the
    schema and the recompile verdicts are meaningful there.

    The MPMD pipeline plane (``serve/pipeline.py``) gets the
    ``pipeline_serving`` block: one chain of per-chip stage programs
    driven with the in-flight window >= stages vs window 1
    (``stage_overlap_speedup``, ABBA-paired — the win of stage k
    computing batch N while stage k+1 computes batch N-1), per-stage
    synchronous step walls + occupancy (where the pipe's clock is set),
    and per bucket x stage zero-recompile verdicts that fail the bench
    loudly. Same CPU caveat discipline: host-thread transfers say
    nothing about ICI hop costs.

    The WHOLE-PROGRAM plane (ISSUE 16) gets the ``whole_program``
    block: one fused engine on the MFU-honest ViT config serving BOTH
    routes — raw uint8 through the fused bucket programs (in-XLA
    normalize, staging donated) vs host-normalized float32 through the
    split ones — with the ABBA-paired fused-over-split ratio, the
    host-work collapse in ms/request, staged H2D bytes per request
    (float32 vs raw uint8), forward-only MFU, the donated-staging
    retirement counts, and zero-recompile verdicts across both planes
    that fail the bench loudly. On TPU a median paired speedup below
    1.0 also fails the line; on CPU it is caveated instead (no MXU, no
    real H2D hop).

    In CI this runs on CPU with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=4``.
    """
    out = {
        "metric": "mnist_serve_requests_per_sec",
        "unit": "requests/sec",
        "baseline": "same engine, batching disabled (bucket-1 program "
                    "per request): vs_baseline is the micro-batching "
                    "speedup",
    }
    try:
        # Must run before the first jax device query: XLA_FLAGS are read
        # once, at backend init.
        cpu_isolated = _isolate_cpu_serve_devices()

        import jax

        configure_jax()

        import threading

        from pytorch_distributed_mnist_tpu.data.mnist import synthetic_dataset
        from pytorch_distributed_mnist_tpu.models import get_model
        from pytorch_distributed_mnist_tpu.serve.batcher import MicroBatcher
        from pytorch_distributed_mnist_tpu.serve.engine import InferenceEngine
        from pytorch_distributed_mnist_tpu.train.state import create_train_state
        from pytorch_distributed_mnist_tpu.utils.profiling import (
            ServeLog,
            compile_log,
        )

        device = jax.devices()[0]
        _require_tpu(device.platform)
        import jax.numpy as jnp

        # Same backend policy as the training bench: bf16 MXU path on
        # TPU, f32 on the CPU fallback.
        model = get_model(
            "cnn", **({} if device.platform == "tpu"
                      else {"compute_dtype": jnp.float32}))
        state = create_train_state(model, jax.random.key(0))
        serve_log = ServeLog()
        engine = InferenceEngine(model.apply, state.params,
                                 serve_log=serve_log)
        compile_log.reset()
        t0 = time.perf_counter()
        engine.warmup()
        warmup_s = time.perf_counter() - t0
        totals_after_warmup = dict(compile_log.stats()["totals"])

        images, _ = synthetic_dataset(64, seed=0)
        stacks = [engine.preprocess(images[i:i + 1]) for i in range(16)]
        # Pool drives use 8-row exact-bucket requests with max_batch=8:
        # one request == one bucket-8 batch, every time. Single-row
        # coalescing would couple batch FORMATION with the in-flight
        # window (a serialized window backs the queue up into larger,
        # better-packed batches), turning the pipeline on/off ratio into
        # a batch-size-efficiency measurement; fixed-shape requests pin
        # the device work per request so the ratio isolates pipelining.
        pool_stacks = [engine.preprocess(images[i:i + 8]) for i in range(8)]

        requests = int(os.environ.get("BENCH_SERVE_REQUESTS",
                                      SERVE_REQUESTS))
        concurrency = int(os.environ.get("BENCH_SERVE_CONCURRENCY",
                                         SERVE_CONCURRENCY))

        drive_errors: list = []

        def drive(batcher, requests_n: int, req_stacks=None) -> float:
            req_stacks = stacks if req_stacks is None else req_stacks
            counter = {"next": 0}
            lock = threading.Lock()

            def worker():
                while True:
                    with lock:
                        i = counter["next"]
                        if i >= requests_n:
                            return
                        counter["next"] = i + 1
                    try:
                        batcher.predict(req_stacks[i % len(req_stacks)])
                    except Exception as exc:  # noqa: BLE001
                        # A silently-dead worker would let the drive
                        # finish with unserved requests counted into the
                        # headline; collect and fail the line instead.
                        drive_errors.append(repr(exc))

            threads = [threading.Thread(target=worker, daemon=True)
                       for _ in range(concurrency)]
            t = time.perf_counter()
            for th in threads:
                th.start()
            for th in threads:
                th.join()
            return time.perf_counter() - t

        with MicroBatcher(engine.predict, max_batch=engine.max_batch,
                          max_wait_s=0.002, max_queue=4 * concurrency,
                          serve_log=serve_log) as batcher:
            drive(batcher, max(64, requests // 10))  # warm the path E2E
            serve_log.reset()
            # Best-of-2 (the timing protocol above): one descheduled
            # burst on a shared CI box halves a single drive's apparent
            # throughput. The ServeLog keeps both drives' samples; the
            # headline uses the cleaner wall.
            wall = min(drive(batcher, requests) for _ in range(2))

        totals_after_load = dict(compile_log.stats()["totals"])
        zero_recompiles = (
            totals_after_load["backend_compiles"]
            == totals_after_warmup["backend_compiles"])
        snap = serve_log.snapshot()

        # Baseline twin: batching off — every request runs the bucket-1
        # program alone through a max_batch=1 batcher.
        with MicroBatcher(engine.predict, max_batch=1, max_wait_s=0.0,
                          max_queue=4 * concurrency) as batcher:
            baseline_wall = min(drive(batcher, requests)
                                for _ in range(2))

        # -- multi-chip data plane: replica scaling + pipelined dispatch.
        from pytorch_distributed_mnist_tpu.serve.pool import EnginePool

        def _serve_program_compiles() -> dict:
            return {name: rec["backend_compiles"]
                    for name, rec in compile_log.stats()["programs"].items()
                    if name.startswith("serve_forward_")}

        def _recompile_delta(before: dict, after: dict) -> dict:
            """Per-program compile-count changes across one drive (empty
            == the zero-steady-state-recompiles invariant held)."""
            return {name: (count, after[name])
                    for name, count in before.items()
                    if after[name] != count}

        def drive_pool(pool, window: int, requests_n: int,
                       reps: int = 3, fixed_shape: bool = False) -> float:
            """Best-of-``reps`` wall seconds for ``requests_n`` requests
            (the shared timing protocol: best-of filters scheduler
            noise on a shared-core CI box, where one descheduled burst
            can halve a single drive's apparent throughput).
            ``fixed_shape`` drives the 8-row exact-bucket requests with
            ``max_batch=8`` — one request == one bucket-8 batch, every
            time — instead of realistic single-row coalescing."""
            req_stacks = pool_stacks if fixed_shape else stacks
            with MicroBatcher(
                    None, max_batch=8 if fixed_shape else pool.max_batch,
                    max_wait_s=0.002, max_queue=4 * concurrency,
                    dispatch_fn=pool.dispatch,
                    complete_fn=lambda h: pool.predict_complete(h)[0],
                    max_inflight=window) as pool_batcher:
                drive(pool_batcher, max(64, requests_n // 10),
                      req_stacks)  # warm E2E
                return min(drive(pool_batcher, requests_n, req_stacks)
                           for _ in range(reps))

        def drive_pool_interleaved(pool, windows, requests_n: int,
                                   reps: int = 5) -> dict:
            """``reps`` fixed-shape drives per window, INTERLEAVED in
            time with ABBA ordering (w0w1, w1w0, w0w1, ...): on a
            shares-throttled CI box the available CPU drifts with
            invisible neighbors, so the honest window-vs-window
            comparison pairs drives that ran next to each other — and
            alternating which window goes first cancels first-mover and
            linear-drift bias. Returns {window: [wall, ...]} in rep
            order."""
            walls = {w: [] for w in windows}
            for rep in range(reps):
                order = windows if rep % 2 == 0 else tuple(reversed(windows))
                for window in order:
                    walls[window].append(
                        drive_pool(pool, window=window,
                                   requests_n=requests_n, reps=1,
                                   fixed_shape=True))
            return walls

        n_devices = jax.device_count()
        # A quarter of the headline count per pool drive: the pool
        # section runs ~15 drives (3 scaling points x best-of-3 + 6
        # interleaved pipeline drives), so full-size drives would
        # quintuple the bench's wall time; 500-request drives keep the
        # ratio's sign stable (measured) at a bounded cost.
        pool_requests = int(os.environ.get("BENCH_SERVE_POOL_REQUESTS",
                                           max(400, requests // 4)))
        points = sorted({n for n in (1, 2, n_devices)
                         if 1 <= n <= n_devices})
        replica_scaling = []
        recompiled_replicas: list = []
        pipeline_speedup = 0.0
        pipeline_pairs: list = []
        for n in points:
            pool = EnginePool(model.apply, state.params,
                              devices=jax.local_devices()[:n])
            pool.warmup()
            before = _serve_program_compiles()
            pool_wall = drive_pool(pool, window=n + 1,
                                   requests_n=pool_requests)
            if n == n_devices:
                # Full pool: pipeline on (window n+1) vs off (window 1 —
                # strict dispatch->complete alternation, one busy
                # replica), on the FIXED-SHAPE drive so batch formation
                # cannot couple with the window (a serialized window
                # backs the queue up into larger, better-packed batches,
                # which would measure packing, not pipelining). The
                # speedup is the MEDIAN of the per-rep paired ratios
                # from interleaved drives: adjacent pairs see the same
                # neighbor load, so the ratio survives the CPU-share
                # drift that best-of-each-side would turn into noise.
                walls = drive_pool_interleaved(
                    pool, windows=(n + 1, 1), requests_n=pool_requests)
                pipeline_pairs = [round(off / on, 3) for on, off
                                  in zip(walls[n + 1], walls[1])]
                ratios = sorted(pipeline_pairs)
                pipeline_speedup = ratios[len(ratios) // 2]
            delta = _recompile_delta(before, _serve_program_compiles())
            if delta:
                recompiled_replicas.append(delta)
            replica_scaling.append({
                "replicas": n,
                "requests_per_sec": round(pool_requests / pool_wall, 1),
                "zero_steady_state_recompiles": not delta,
            })

        # -- sharded serving (serve/programs.py): per-mode paired
        # comparison vs replicated on the SAME chip count, and the
        # mesh-scaling curve. Fixed-shape 8-row drives throughout (the
        # pipeline block's reasoning: pin batch formation so the ratio
        # measures the data plane, not packing).
        sharded_requests = int(os.environ.get(
            "BENCH_SERVE_SHARDED_REQUESTS", pool_requests))
        sharded_block: dict = {}
        sharded_recompiles: list = []
        if n_devices < 2:
            sharded_block["skipped"] = (
                "single-device world: a serving mesh needs >= 2 chips")
        else:
            from pytorch_distributed_mnist_tpu.serve.programs import (
                get_serve_mode,
                registered_mode_models,
                validate_serve_mode,
            )

            # The LIVE registry, not a hardcoded list: a mode added via
            # register_serve_mode joins the comparison and the recompile
            # verdict automatically (the server's extension contract).
            for mode, model_name in registered_mode_models():
                if get_serve_mode(mode).engine_factory is not None:
                    # Non-SPMD modes (MPMD pipeline) are not a mesh
                    # lowering; they measure in their own block below.
                    continue
                shard_model = get_model(
                    model_name, **({} if device.platform == "tpu"
                                   else {"compute_dtype": jnp.float32}))
                shard_state = create_train_state(shard_model,
                                                 jax.random.key(0))
                # Mesh-scaling curve at FIXED chip count: mesh 1 is the
                # replicated plane (n_devices one-chip replicas), the
                # largest VALID point one spanning mesh group. A mesh a
                # sharded weight dim doesn't divide (e.g. more chips
                # than the MoE has experts) is dropped point-by-point;
                # a mode with no valid sharded point becomes a labeled
                # skip, not a traceback that loses the whole bench line.
                mesh_points, skip_reason = [1], None
                for mesh in sorted({2, n_devices}):
                    if n_devices % mesh:
                        continue
                    try:
                        validate_serve_mode(mode, model_name, mesh,
                                            shard_state.params)
                        mesh_points.append(mesh)
                    except ValueError as exc:
                        skip_reason = str(exc)
                if len(mesh_points) == 1:
                    sharded_block[mode] = {"model": model_name,
                                           "skipped": skip_reason}
                    continue
                full_mesh = mesh_points[-1]
                pools = {}
                for mesh in mesh_points:
                    if mesh == 1:
                        pools[mesh] = EnginePool(
                            shard_model.apply, shard_state.params,
                            devices=jax.local_devices()[:n_devices],
                            buckets=(1, 8))
                    else:
                        pools[mesh] = EnginePool(
                            shard_model.apply, shard_state.params,
                            devices=jax.local_devices()[:n_devices],
                            buckets=(1, 8), serve_mode=mode,
                            mesh_size=mesh, model_name=model_name)
                    pools[mesh].warmup()
                # Snapshot EVERY serve program (not just @{mode} names):
                # the replicated baseline leg drives @r{i} programs, and
                # a recompile stalling THAT side would silently skew
                # vs_replicated in the sharded mode's favor.
                before_mode = _serve_program_compiles()
                mesh_scaling = []
                for mesh in mesh_points:
                    groups = n_devices // mesh
                    wall_m = drive_pool(pools[mesh], window=groups + 1,
                                        requests_n=sharded_requests,
                                        reps=1, fixed_shape=True)
                    mesh_scaling.append({
                        "mesh_devices": mesh,
                        "mesh_groups": groups,
                        "requests_per_sec": round(
                            sharded_requests / wall_m, 1),
                    })
                # ABBA-paired sharded (full mesh, 1 group) vs replicated
                # (mesh 1, n one-chip replicas), each at its natural
                # window; adjacent pairs see the same neighbor load, so
                # the ratio survives CPU-share drift (PR 4 methodology).
                walls = {"sharded": [], "replicated": []}
                for rep in range(4):
                    order = (("sharded", "replicated") if rep % 2 == 0
                             else ("replicated", "sharded"))
                    for leg in order:
                        pool_leg = (pools[full_mesh] if leg == "sharded"
                                    else pools[1])
                        window = (n_devices // full_mesh + 1
                                  if leg == "sharded"
                                  else n_devices + 1)
                        walls[leg].append(drive_pool(
                            pool_leg, window=window,
                            requests_n=sharded_requests, reps=1,
                            fixed_shape=True))
                pairs = [round(r / s, 3) for s, r in
                         zip(walls["sharded"], walls["replicated"])]
                vs_replicated = sorted(pairs)[len(pairs) // 2]
                # Per-bucket x mode recompile verdict: every serve
                # program alive in this block — the @{mode}[.g{i}] mesh
                # programs AND the replicated baseline's @r{i} ones —
                # must show zero compiles across every drive above; a
                # violation fails the whole bench line (exit 1), same
                # as the replicated planes.
                delta_mode = _recompile_delta(
                    before_mode, _serve_program_compiles())
                if delta_mode:
                    sharded_recompiles.append({mode: delta_mode})
                full_rps = next(
                    pt["requests_per_sec"] for pt in mesh_scaling
                    if pt["mesh_devices"] == full_mesh)
                sharded_block[mode] = {
                    "model": model_name,
                    "mesh_devices": full_mesh,
                    "requests_per_sec": full_rps,
                    "vs_replicated": vs_replicated,
                    "pairs": pairs,
                    "mesh_scaling": mesh_scaling,
                    "zero_steady_state_recompiles": not delta_mode,
                }
            sharded_block["requests"] = sharded_requests
            if device.platform != "tpu":
                sharded_block["caveat"] = (
                    "CPU fallback (the BENCH_r05 convention): mesh "
                    "collectives run over host threads, not ICI, so the "
                    "sharded-vs-replicated sign is not meaningful here — "
                    "only the schema and the zero-recompile verdicts are")

        # -- MPMD pipeline serving (serve/pipeline.py): the stage-overlap
        # measurement. ONE chain of per-chip stage programs, driven with
        # the in-flight window >= stages (the pipe fills: stage k runs
        # batch N while stage k+1 runs batch N-1) vs window 1 (strict
        # dispatch->complete alternation: every batch pays the full
        # chain serially). A single chain on purpose — a multi-chain
        # pool at window>1 would conflate chain fan-out with stage
        # overlap. ABBA-paired interleaved drives, median paired ratio
        # (PR 4 methodology); fixed-shape 8-row requests pin batch
        # formation. Per-stage synchronous step walls + occupancy say
        # WHERE the pipe's clock is set (the bottleneck stage reads 1.0).
        pipeline_block: dict = {}
        pipeline_recompiles: list = []
        if n_devices < 2:
            pipeline_block["skipped"] = (
                "single-device world: a pipeline chain needs >= 2 chips")
        else:
            from pytorch_distributed_mnist_tpu.parallel.pipeline_vit import (
                split_vit_params,
            )
            from pytorch_distributed_mnist_tpu.utils.profiling import (
                stage_occupancy,
            )

            pp_model = get_model(
                "vit", **({} if device.platform == "tpu"
                          else {"compute_dtype": jnp.float32}))
            # depth must divide the stage count; the default ViT (depth
            # 2) pins the chain at 2 stages regardless of chip count.
            pp_stages = 2
            pp_params = split_vit_params(
                create_train_state(pp_model, jax.random.key(0)).params)
            pp_pool = EnginePool(
                pp_model.apply, pp_params,
                devices=jax.local_devices()[:pp_stages], buckets=(1, 8),
                serve_mode="pipeline", mesh_size=pp_stages,
                model_name="vit", model=pp_model)
            pp_pool.warmup()
            before_pp = _serve_program_compiles()
            window = pp_stages + 1
            walls = drive_pool_interleaved(
                pp_pool, windows=(window, 1), requests_n=pool_requests)
            pp_pairs = [round(off / on, 3) for on, off
                        in zip(walls[window], walls[1])]
            ratios = sorted(pp_pairs)
            overlap_speedup = ratios[len(ratios) // 2]
            delta_pp = _recompile_delta(before_pp,
                                        _serve_program_compiles())
            if delta_pp:
                pipeline_recompiles.append(delta_pp)
            stage_ms = pp_pool.replicas[0].engine.stage_step_ms(8)
            pipeline_block = {
                "model": "vit",
                "stages": pp_stages,
                "chains": 1,
                "window": window,
                "requests": pool_requests,
                "stage_overlap_speedup": overlap_speedup,
                "pairs": pp_pairs,
                "requests_per_sec": round(
                    pool_requests / min(walls[window]), 1),
                "stage_step_ms": stage_ms,
                "stage_occupancy": stage_occupancy(stage_ms),
                "zero_steady_state_recompiles": not delta_pp,
            }
            if device.platform != "tpu":
                pipeline_block["caveat"] = (
                    "CPU fallback (the BENCH_r05 convention): "
                    "host-thread transfers say nothing about ICI, so "
                    "the inter-stage hop cost is not the chip's — only "
                    "the overlap schema and the zero-recompile verdicts "
                    "are meaningful here")

        # -- precision sweep (serve/programs.py precision plane): for
        # each registered quantized precision, the ABBA-paired
        # throughput ratio vs f32 at the SAME chip (PR 4 pairing:
        # adjacent pairs see the same neighbor load, median paired
        # ratio), the eval-batch argmax-agreement + accuracy delta vs
        # f32, and per bucket x mode x precision zero-recompile
        # verdicts that fail the whole bench line (exit 1). The eval
        # batch is the synthetic stand-in (CI has no MNIST files on
        # disk); with a real checkpoint the same fields measure the
        # real test set via the serving stack.
        import numpy as np

        from pytorch_distributed_mnist_tpu.serve.programs import (
            get_serve_mode,
            registered_mode_models,
            serve_precisions,
            validate_serve_mode,
        )

        precision_requests = int(os.environ.get(
            "BENCH_SERVE_PRECISION_REQUESTS", max(200, pool_requests // 2)))
        precision_block: dict = {"requests": precision_requests,
                                 "eval_set": "synthetic(512)"}
        precision_recompiles: list = []
        quantized = [p for p in serve_precisions() if p != "f32"]
        eval_images, eval_labels = synthetic_dataset(512, seed=1)
        ref_logits = engine.logits(eval_images)
        ref_pred = np.argmax(ref_logits, axis=-1)
        acc_f32 = float((ref_pred == eval_labels).mean())
        precision_block["f32_accuracy"] = round(acc_f32, 4)

        def drive_engine(eng, requests_n: int, req_stacks=None) -> float:
            """One fixed-shape closed-loop drive through a fresh
            batcher (8-row exact-bucket requests, max_batch=8 — the
            pool blocks' reasoning: pin batch formation so the ratio
            measures the forward programs, not packing)."""
            req_stacks = pool_stacks if req_stacks is None else req_stacks
            with MicroBatcher(eng.predict, max_batch=8,
                              max_wait_s=0.002,
                              max_queue=4 * concurrency) as b:
                drive(b, max(32, requests_n // 10), req_stacks)  # warm
                return drive(b, requests_n, req_stacks)

        for prec in quantized:
            prec_engine = InferenceEngine(
                model.apply, state.params, precision=prec, name=prec)
            prec_engine.warmup()
            before_prec = _serve_program_compiles()
            lo = prec_engine.logits(eval_images)
            pred = np.argmax(lo, axis=-1)
            walls_p = {"prec": [], "f32": []}
            for rep in range(4):
                order = (("prec", "f32") if rep % 2 == 0
                         else ("f32", "prec"))
                for leg in order:
                    eng = prec_engine if leg == "prec" else engine
                    walls_p[leg].append(
                        drive_engine(eng, precision_requests))
            pairs_p = [round(f / p, 3) for p, f in
                       zip(walls_p["prec"], walls_p["f32"])]
            ratio = sorted(pairs_p)[len(pairs_p) // 2]
            delta_prec = _recompile_delta(before_prec,
                                          _serve_program_compiles())
            if delta_prec:
                precision_recompiles.append({prec: delta_prec})
            acc_p = float((pred == eval_labels).mean())
            precision_block[prec] = {
                "vs_f32": ratio,
                "pairs": pairs_p,
                "requests_per_sec": round(
                    precision_requests / min(walls_p["prec"]), 1),
                "argmax_agreement_vs_f32": round(
                    float((pred == ref_pred).mean()), 4),
                "accuracy": round(acc_p, 4),
                "accuracy_delta_vs_f32": round(acc_p - acc_f32, 4),
                "max_logit_delta_vs_f32": round(
                    float(np.abs(lo - ref_logits).max()), 5),
                "zero_steady_state_recompiles": not delta_prec,
            }

        # Per bucket x MODE x precision recompile verdicts: every
        # registered mode (the LIVE registry, SPMD and engine-factory
        # alike) x every quantized precision gets a small pool drive on
        # 2 chips; any steady-state compile fails the bench. Skipped
        # combos are labeled, never silently dropped.
        mode_verdicts: dict = {}
        if n_devices >= 2:
            from pytorch_distributed_mnist_tpu.serve.programs import (
                make_serve_template,
            )

            for mode, model_name in registered_mode_models():
                vmodel = get_model(
                    model_name, **({} if device.platform == "tpu"
                                   else {"compute_dtype": jnp.float32}))
                # The registry's template hook owns the mode's param
                # LAYOUT (pipeline restores onto the stage-stacked
                # tree) — never a hardcoded per-mode transform here.
                vparams = make_serve_template(
                    mode, vmodel, jax.random.key(0)).params
                for prec in quantized:
                    key = f"{mode}.{prec}"
                    try:
                        if get_serve_mode(mode).engine_factory is None:
                            validate_serve_mode(mode, model_name, 2,
                                                vparams)
                        vpool = EnginePool(
                            vmodel.apply, vparams,
                            devices=jax.local_devices()[:2],
                            buckets=(1, 8), serve_mode=mode, mesh_size=2,
                            model_name=model_name, model=vmodel,
                            precision=prec)
                        vpool.warmup()
                    except ValueError as exc:
                        # An unservable combo (e.g. an extension mode a
                        # 2-chip mesh can't host) is a labeled skip,
                        # never a traceback that loses the bench line.
                        mode_verdicts[key] = {"model": model_name,
                                              "skipped": str(exc)}
                        continue
                    before_mv = _serve_program_compiles()
                    drive_pool(vpool, window=2, requests_n=64, reps=1,
                               fixed_shape=True)
                    delta_mv = _recompile_delta(
                        before_mv, _serve_program_compiles())
                    if delta_mv:
                        precision_recompiles.append({key: delta_mv})
                    mode_verdicts[key] = {
                        "model": model_name,
                        "zero_steady_state_recompiles": not delta_mv,
                    }
        else:
            mode_verdicts["skipped"] = (
                "single-device world: mode x precision pools need >= 2 "
                "chips")
        precision_block["modes"] = mode_verdicts
        if device.platform != "tpu":
            precision_block["caveat"] = (
                "CPU fallback (the BENCH_r05 convention): host int8/bf16 "
                "arithmetic says little about the TPU MXU or ICI, so "
                "the per-precision throughput sign is not the chip's — "
                "only the schema, the accuracy/agreement deltas, and "
                "the zero-recompile verdicts are meaningful here")

        # -- whole-program fused serving (ISSUE 16): the fused plane
        # stages RAW uint8 bytes and runs ONE XLA program per bucket —
        # in-XLA normalize (+ activation quantize) fused ahead of the
        # forward, staging buffer DONATED — where the split plane
        # normalizes on the host and stages float32. Measured on an
        # MFU-honest config (the ViT: its matmul FLOPs the analytic
        # helper counts honestly; CNN conv FLOPs would be a made-up
        # number): the ABBA-paired fused-vs-split throughput ratio on
        # the SAME engine (only the input dtype differs, so params and
        # placement cannot skew the pair), the host-work collapse
        # (per-request preprocess wall), H2D bytes per request (staged
        # float32 vs raw uint8), and zero-recompile verdicts across
        # BOTH planes that fail the bench line (exit 1).
        from pytorch_distributed_mnist_tpu.data.mnist import (
            normalize_images,
        )

        fused_requests = int(os.environ.get(
            "BENCH_SERVE_FUSED_REQUESTS", max(200, pool_requests // 2)))
        fused_recompiles: list = []
        wp_failures: list = []
        wp_model = get_model(
            "vit", **({} if device.platform == "tpu"
                      else {"compute_dtype": jnp.float32}))
        wp_state = create_train_state(wp_model, jax.random.key(0))
        wp_engine = InferenceEngine(wp_model.apply, wp_state.params,
                                    buckets=(1, 8), fuse=True, name="wp")
        wp_engine.warmup()
        raw_stacks = [np.ascontiguousarray(images[i:i + 8])
                      for i in range(8)]
        wp_float_stacks = [normalize_images(s) for s in raw_stacks]

        # Host-work collapse: what the fused plane removes from the
        # host per request is the float conversion — raw bytes ride
        # straight into uint8 staging (the copy happens on both planes).
        host_reps = 50
        t0 = time.perf_counter()
        for r in range(host_reps):
            wp_engine.preprocess(raw_stacks[r % 8])  # raw passthrough
        fused_host_ms = (time.perf_counter() - t0) / host_reps * 1e3
        t0 = time.perf_counter()
        for r in range(host_reps):
            normalize_images(raw_stacks[r % 8])  # split plane host work
        split_host_ms = (time.perf_counter() - t0) / host_reps * 1e3

        # H2D bytes per 8-row request, from the ACTUAL staging pools
        # (the split pool's dtype is the precision plane's choice, the
        # fused pool always stages raw bytes).
        split_pool_ = wp_engine._staging
        fused_pool_ = wp_engine._fused_staging
        split_bytes = int(np.prod((8,) + split_pool_.input_shape)
                          ) * split_pool_.dtype.itemsize
        fused_bytes = int(np.prod((8,) + fused_pool_.input_shape)
                          ) * fused_pool_.dtype.itemsize

        before_wp = _serve_program_compiles()
        walls_wp = {"fused": [], "split": []}
        for rep in range(4):
            order = (("fused", "split") if rep % 2 == 0
                     else ("split", "fused"))
            for leg in order:
                leg_stacks = (raw_stacks if leg == "fused"
                              else wp_float_stacks)
                walls_wp[leg].append(
                    drive_engine(wp_engine, fused_requests, leg_stacks))
        pairs_wp = [round(s / f, 3) for f, s in
                    zip(walls_wp["fused"], walls_wp["split"])]
        fused_speedup = sorted(pairs_wp)[len(pairs_wp) // 2]
        delta_wp = _recompile_delta(before_wp, _serve_program_compiles())
        if delta_wp:
            fused_recompiles.append(delta_wp)
        speedup_holds = fused_speedup >= 1.0
        if device.platform == "tpu" and not speedup_holds:
            # On the chip the fusion must pay for itself; on the CPU
            # fallback the sign is caveated, not enforced.
            wp_failures.append(
                f"whole-program fusion slower than split on TPU: median "
                f"paired speedup {fused_speedup} < 1.0")

        # MFU at the fused drive's rate: forward-only model FLOPs (the
        # training helper counts fwd + 2x bwd, hence /3), matmuls only,
        # against the chip's peak — None off-TPU, where there is no
        # honest peak to divide by.
        wp_tokens = (28 // wp_model.patch_size) ** 2
        serve_flops_per_image = _vit_model_flops_per_image(
            wp_tokens, wp_model.embed_dim, wp_model.depth,
            wp_model.patch_size) / 3.0
        fused_rps = fused_requests / min(walls_wp["fused"])
        peak = _peak_flops(device.device_kind)
        mfu = (round(fused_rps * 8 * serve_flops_per_image / peak, 5)
               if peak else None)

        whole_program_block: dict = {
            "model": "vit",
            "requests": fused_requests,
            "images_per_request": 8,
            "fused_over_split_speedup": fused_speedup,
            "speedup_holds": speedup_holds,
            "pairs": pairs_wp,
            "requests_per_sec": round(fused_rps, 1),
            "host_preprocess_ms_per_request": {
                "split": round(split_host_ms, 4),
                "fused": round(fused_host_ms, 4),
            },
            "h2d_bytes_per_request": {
                "split": split_bytes,
                "fused": fused_bytes,
                "ratio": round(split_bytes / fused_bytes, 2),
            },
            "model_flops_per_image": serve_flops_per_image,
            "mfu": mfu,
            "donated_staging_retired": wp_engine.fused_staging_retired(),
            "zero_steady_state_recompiles": not delta_wp,
        }
        if device.platform != "tpu":
            whole_program_block["caveat"] = (
                "CPU fallback (the BENCH_r05 convention): host matmuls "
                "say nothing about the MXU and there is no real H2D "
                "hop, so the fused-vs-split sign is not the chip's and "
                "MFU is unreportable — only the schema, the host-work "
                "collapse, the staged-bytes ratio, and the "
                "zero-recompile verdicts are meaningful here")

        # -- overload (ISSUE 15): goodput vs offered load, 1x..10x of
        # measured capacity, through the PRIORITY batcher (shed policy
        # attached, mixed interactive/batch/best_effort traffic).
        # Shed-not-collapse, measured not asserted: the block FAILS the
        # bench (exit 1) when goodput at 10x drops below 70% of the
        # curve's peak (the classic signature of queueing collapse —
        # capacity spent on requests nobody will wait for) or when
        # interactive p99 is not strictly below batch p99 under
        # overload (the whole point of priority ordering + per-class
        # watermarks). Open-loop on purpose: a closed-loop driver slows
        # with the server and cannot overload anything.
        import random as _random

        from pytorch_distributed_mnist_tpu.serve.control import (
            AutoScaler,
            ShedPolicy,
        )

        overload_seconds = float(os.environ.get(
            "BENCH_OVERLOAD_SECONDS", "2.0"))
        overload_points = [int(t) for t in os.environ.get(
            "BENCH_OVERLOAD_POINTS", "1,2,5,10").split(",") if t.strip()]
        overload_mix = (("interactive", 0.6), ("batch", 0.9),
                       ("best_effort", 1.0))  # cumulative
        overload_failures: list = []
        capacity_rps = requests / wall  # the headline closed-loop rate
        overload_block: dict = {
            "capacity_rps": round(capacity_rps, 1),
            "seconds_per_point": overload_seconds,
            "mix": {"interactive": 0.6, "batch": 0.3, "best_effort": 0.1},
            "watermarks": dict(ShedPolicy().watermarks),
            "points": [],
        }

        def _drive_open(mult: int) -> dict:
            """One open-loop point: offer ``mult`` x capacity for
            ``overload_seconds`` straight into a fresh priority
            batcher, then drain. Per-class completions/sheds/latency
            come from the drive's own ServeLog."""
            olog = ServeLog(window_s=30.0)
            rng = _random.Random(1000 + mult)
            rate = capacity_rps * mult
            pendings = []
            offered = 0
            # max_batch BELOW max_queue on purpose: a saturated queue
            # must drain over several engine batches for priority order
            # to mean anything — at max_batch >= max_queue the whole
            # queue rides one forward and every class shares one wall.
            with MicroBatcher(engine.predict, max_batch=16,
                              max_wait_s=0.002, max_queue=64,
                              serve_log=olog,
                              shed_policy=ShedPolicy()) as ob:
                t_start = time.perf_counter()
                i = 0
                while True:
                    t_next = t_start + i / rate
                    now = time.perf_counter()
                    if t_next - t_start >= overload_seconds:
                        break
                    if t_next - now > 1e-3:
                        time.sleep(t_next - now)
                    r = rng.random()
                    klass = next(k for k, cum in overload_mix
                                 if r <= cum)
                    offered += 1
                    try:
                        pendings.append(ob.submit(
                            stacks[i % len(stacks)], klass=klass))
                    except Exception:  # noqa: BLE001 - shed IS the point
                        pass
                    i += 1
                for p in pendings:
                    p.event.wait(30.0)
            snap = olog.snapshot()
            classes = {
                klass: {
                    "completed": rec["requests"],
                    "shed": rec["shed"],
                    "p50_ms": rec["latency_ms"]["p50"],
                    "p99_ms": rec["latency_ms"]["p99"],
                }
                for klass, rec in snap.get("classes", {}).items()
            }
            return {
                "offered_x": mult,
                "offered_rps": round(offered / overload_seconds, 1),
                "completed": snap["requests"],
                "shed": snap["rejected"],
                "goodput_rps": round(snap["requests"] / overload_seconds,
                                     1),
                "classes": classes,
            }

        for mult in overload_points:
            overload_block["points"].append(_drive_open(mult))
        peak_goodput = max(pt["goodput_rps"]
                           for pt in overload_block["points"])
        top = overload_block["points"][-1]
        overload_block["peak_goodput_rps"] = peak_goodput
        overload_block["goodput_at_top_fraction_of_peak"] = round(
            top["goodput_rps"] / max(peak_goodput, 1e-9), 3)
        goodput_holds = top["goodput_rps"] >= 0.7 * peak_goodput
        overload_block["goodput_holds_at_overload"] = goodput_holds
        if not goodput_holds:
            overload_failures.append(
                f"goodput collapsed under overload: "
                f"{top['goodput_rps']} rps at "
                f"{top['offered_x']}x vs peak {peak_goodput} rps "
                f"(< 70%)")
        inter = top["classes"].get("interactive", {})
        batch_c = top["classes"].get("batch", {})
        tail_ordered = (inter.get("completed", 0) > 0
                        and batch_c.get("completed", 0) > 0
                        and inter["p99_ms"] < batch_c["p99_ms"])
        overload_block["interactive_p99_below_batch_p99"] = tail_ordered
        if not tail_ordered:
            overload_failures.append(
                f"priority inversion under overload: interactive p99 "
                f"{inter.get('p99_ms')}ms vs batch p99 "
                f"{batch_c.get('p99_ms')}ms at {top['offered_x']}x "
                f"(interactive must stay strictly below, with both "
                f"classes completing)")

        # Autoscaler actuation verdict: a real controller drives the
        # pool's resize path up then down (synthetic breach/calm
        # samples — this is the ACTUATION under test, not the sensor),
        # and the steady state AFTER the resizes must not recompile:
        # the acceptance criterion "zero steady-state recompiles across
        # autoscaler resizes".
        autoscale_block: dict = {}
        if n_devices >= 2:
            as_pool = EnginePool(model.apply, state.params,
                                 devices=jax.local_devices()[:1])
            as_pool.warmup()
            feed = {"p95_ms": 0.0, "queue_depth": 0}
            scaler = AutoScaler(
                as_pool, lambda: dict(feed), slo_p95_ms=50.0,
                queue_high=48, max_devices=2, cooldown_s=0.0,
                down_after=2, interval_s=60.0)
            feed["p95_ms"] = 500.0  # breach: scale 1 -> 2
            up = scaler.tick()
            feed["p95_ms"] = 1.0  # sustained calm: scale 2 -> 1
            scaler.tick()
            down = scaler.tick()
            resized_ok = (up is not None and "error" not in up
                          and down is not None and "error" not in down
                          and as_pool.n_devices == 1)
            before_as = _serve_program_compiles()
            drive_pool(as_pool, window=2, requests_n=64, reps=1,
                       fixed_shape=True)
            delta_as = _recompile_delta(before_as,
                                        _serve_program_compiles())
            autoscale_block = {
                "resizes": [up, down],
                "actuated": resized_ok,
                "zero_steady_state_recompiles_across_resizes":
                    not delta_as,
            }
            if not resized_ok:
                overload_failures.append(
                    f"autoscaler actuation failed: up={up} down={down} "
                    f"pool at {as_pool.n_devices} device(s)")
            if delta_as:
                overload_failures.append(
                    f"steady-state serving recompiled across "
                    f"autoscaler resizes: {delta_as}")
        else:
            autoscale_block["skipped"] = (
                "single-device world: an autoscaler resize needs >= 2 "
                "chips")
        overload_block["autoscale"] = autoscale_block
        if device.platform != "tpu":
            overload_block["caveat"] = (
                "CPU fallback (the BENCH_r05 convention): absolute "
                "capacity is the host's, not the chip's — the CURVE "
                "shape (goodput held at 10x, interactive < batch p99) "
                "and the recompile verdicts are the meaningful part "
                "here")
        if os.environ.get("BENCH_OVERLOAD_INJECT_FAIL"):
            # Test hook: pin the fails-loudly path without needing a
            # real collapse (mirrors BENCH_ZERO_INJECT_RECOMPILE).
            overload_failures.append(
                "BENCH_OVERLOAD_INJECT_FAIL set: injected overload "
                "verdict failure")
            overload_block["goodput_holds_at_overload"] = False

        # -- fleet (ISSUE 17): the federation tier's own cost and
        # behavior — two real loopback backends behind a real router,
        # all in-process, driven over real HTTP. Three verdicts:
        # (1) router overhead: ABBA-paired direct-vs-routed closed-loop
        #     drives (the BENCH_r04 pairing discipline — alternation
        #     cancels thermal/scheduler drift), reported as the paired
        #     median p50/p99 ratio;
        # (2) goodput at ~10x measured fleet capacity offered open-loop
        #     THROUGH the router (the ISSUE 15 overload methodology one
        #     tier up): the router must shed/refuse, never collapse —
        #     goodput at the top point holds >= 70% of the curve's
        #     peak, the same rule the single-process block enforces
        #     (96% measured there at seed time);
        # (3) zero steady-state recompiles across every routed drive
        #     (the backends share this process's compile log, so a
        #     per-backend recompile shows up in the delta).
        import shutil as _shutil
        import tempfile as _tempfile
        import urllib.request as _urlreq

        from pytorch_distributed_mnist_tpu.serve.router import (
            build_parser as _router_parser,
        )
        from pytorch_distributed_mnist_tpu.serve.router import create_router
        from pytorch_distributed_mnist_tpu.serve.server import (
            build_parser as _serve_parser,
        )
        from pytorch_distributed_mnist_tpu.serve.server import create_server
        from pytorch_distributed_mnist_tpu.train.checkpoint import (
            save_checkpoint,
        )
        from tools.loadgen import _make_images, run_closed, run_open, \
            zipf_cum
        from tools.loadgen import report as _loadgen_report

        def _drive_closed(url, n, conc, *, seed):
            t_d = time.perf_counter()
            col = run_closed(url, n, conc, bodies, timeout=30.0,
                             seed=seed)
            return _loadgen_report(col, time.perf_counter() - t_d,
                                   "closed")

        fleet_failures: list = []
        fleet_block: dict = {"backends": 2}
        fleet_seconds = float(os.environ.get("BENCH_FLEET_SECONDS", "1.0"))
        fleet_pairs = int(os.environ.get("BENCH_FLEET_PAIRS", "3"))
        fleet_reqs = int(os.environ.get("BENCH_FLEET_REQUESTS", "40"))
        fleet_dirs: list = []
        fleet_servers: list = []
        fleet_router = None

        def _boot_httpd(httpd):
            th = threading.Thread(target=httpd.serve_forever, daemon=True)
            th.start()
            host, port = httpd.server_address[:2]
            return {"httpd": httpd, "thread": th,
                    "url": f"http://{host}:{port}",
                    "name": f"{host}:{port}"}

        def _stop_httpd(srv):
            srv["httpd"].shutdown()
            srv["httpd"].ctx.close()
            srv["httpd"].server_close()
            srv["thread"].join(10.0)

        def _router_json(path):
            with _urlreq.urlopen(fleet_router["url"] + path,
                                 timeout=10) as r:
                return json.loads(r.read())

        try:
            # Linear backends on purpose: the block measures ROUTING
            # (the wire + the routing tier), not model capacity, and
            # linear keeps the two extra engines' compiles cheap.
            fleet_model = get_model("linear", compute_dtype=jnp.float32)
            fleet_state = create_train_state(fleet_model,
                                             jax.random.key(7))
            for i in range(2):
                d = _tempfile.mkdtemp(prefix=f"bench-fleet-b{i}-")
                fleet_dirs.append(d)
                save_checkpoint(fleet_state, epoch=0, best_acc=0.0,
                                is_best=False, directory=d,
                                process_index=0)
                fleet_servers.append(_boot_httpd(create_server(
                    _serve_parser().parse_args([
                        "--checkpoint-dir", d, "--model", "linear",
                        "--dtype", "f32", "--host", "127.0.0.1",
                        "--port", "0", "--buckets", "1,8",
                        "--max-wait-ms", "2", "--max-queue", "256",
                        "--poll-interval", "0.5"]))))
            fleet_router = _boot_httpd(create_router(
                _router_parser().parse_args([
                    "--backends",
                    ",".join(s["name"] for s in fleet_servers),
                    "--host", "127.0.0.1", "--port", "0",
                    "--health-interval", "0.2",
                    "--connect-timeout", "2.0"])))
            deadline = time.perf_counter() + 30.0
            while time.perf_counter() < deadline:
                try:
                    if _router_json("/healthz").get("routable") == 2:
                        break
                except OSError:
                    pass
                time.sleep(0.1)
            else:
                raise RuntimeError(
                    "router never saw both backends routable")

            bodies = _make_images(8, 8, seed=5)
            # loadgen appends /predict itself: base URLs here.
            direct_url = fleet_servers[0]["url"]
            routed_url = fleet_router["url"]
            # Warm every program (both backends, both buckets) and the
            # routed path before anything is measured.
            for url in (direct_url, routed_url, routed_url):
                warm = _drive_closed(url, 16, 4, seed=1)
                if warm["ok"] != 16:
                    raise RuntimeError(
                        f"fleet warmup failed against {url}: {warm}")
            before_fleet = _serve_program_compiles()

            # (1) Router overhead, ABBA-paired: per pair one direct and
            # one routed drive, order alternating; the overhead ratio
            # is the median of per-pair routed/direct p50 (and p99).
            pair_rows = []
            for pair in range(fleet_pairs):
                order = [("direct", direct_url), ("routed", routed_url)]
                if pair % 2:
                    order.reverse()
                row = {}
                for label, url in order:
                    rep = _drive_closed(url, fleet_reqs, 4,
                                        seed=100 + pair)
                    if rep["ok"] != fleet_reqs:
                        fleet_failures.append(
                            f"overhead drive ({label}, pair {pair}) "
                            f"lost requests: {rep}")
                    row[label] = rep["latency_ms"]
                pair_rows.append(row)

            def _median(vals):
                vals = sorted(vals)
                mid = len(vals) // 2
                return (vals[mid] if len(vals) % 2
                        else 0.5 * (vals[mid - 1] + vals[mid]))

            overhead = {
                "pairs": fleet_pairs,
                "direct_p50_ms": _median(
                    [r["direct"]["p50"] for r in pair_rows]),
                "routed_p50_ms": _median(
                    [r["routed"]["p50"] for r in pair_rows]),
                "direct_p99_ms": _median(
                    [r["direct"]["p99"] for r in pair_rows]),
                "routed_p99_ms": _median(
                    [r["routed"]["p99"] for r in pair_rows]),
                "p50_overhead_ratio": round(_median(
                    [r["routed"]["p50"] / max(r["direct"]["p50"], 1e-9)
                     for r in pair_rows]), 3),
                "p99_overhead_ratio": round(_median(
                    [r["routed"]["p99"] / max(r["direct"]["p99"], 1e-9)
                     for r in pair_rows]), 3),
            }
            fleet_block["router_overhead"] = overhead

            # (2) Goodput through the router: closed-loop capacity
            # first, then open-loop points at 1x and ~10x (offered rate
            # clamped so the thread-per-request client stays honest —
            # the EFFECTIVE multiple is recorded, not the target).
            cap = _drive_closed(routed_url, 3 * fleet_reqs, 8, seed=7)
            fleet_capacity = max(cap["throughput_rps"], 1e-9)
            goodput_points = []
            for mult in (1, 10):
                rate = min(fleet_capacity * mult, 1500.0)
                col = run_open(routed_url, rate, fleet_seconds, bodies,
                               timeout=10.0, seed=40 + mult)
                rep = _loadgen_report(col, fleet_seconds, "open")
                goodput_points.append({
                    "offered_x": round(rate / max(fleet_capacity, 1e-9),
                                       2),
                    "offered_rps": round(rate, 1),
                    "completed": rep["ok"],
                    "shed": rep["rejected"],
                    "not_launched": rep["not_launched"],
                    "goodput_rps": round(rep["ok"] / fleet_seconds, 1),
                })
                if rep["transport_errors"] or rep["conn_refused"]:
                    fleet_failures.append(
                        f"requests dropped on the floor at "
                        f"{mult}x through the router: {rep}")
            peak_fleet = max(pt["goodput_rps"] for pt in goodput_points)
            top_fleet = goodput_points[-1]
            goodput_frac = round(
                top_fleet["goodput_rps"] / max(peak_fleet, 1e-9), 3)
            fleet_block["goodput"] = {
                "capacity_rps": round(fleet_capacity, 1),
                "points": goodput_points,
                "peak_goodput_rps": peak_fleet,
                "goodput_at_top_fraction_of_peak": goodput_frac,
                "single_process_fraction_of_peak": overload_block.get(
                    "goodput_at_top_fraction_of_peak"),
            }
            goodput_holds_fleet = (
                top_fleet["goodput_rps"] >= 0.7 * peak_fleet)
            fleet_block["goodput"]["holds_at_overload"] = \
                goodput_holds_fleet
            if not goodput_holds_fleet:
                fleet_failures.append(
                    f"fleet goodput collapsed through the router: "
                    f"{top_fleet['goodput_rps']} rps at "
                    f"{top_fleet['offered_x']}x vs peak {peak_fleet} "
                    f"rps (< 70%)")

            # (3) No routed drive recompiled a backend program.
            delta_fleet = _recompile_delta(before_fleet,
                                           _serve_program_compiles())
            fleet_block["zero_steady_state_recompiles_per_backend"] = \
                not delta_fleet
            if delta_fleet:
                fleet_failures.append(
                    f"steady-state serving recompiled behind the "
                    f"router: {delta_fleet}")

            stats = _router_json("/stats")
            fleet_block["router_stats"] = {
                "routable": sum(1 for row in stats.get("backends", [])
                                if row.get("routable")),
                "failovers": stats.get("fleet", {}).get("failovers"),
                "retries": stats.get("fleet", {}).get("retries"),
            }
        except Exception as exc:  # noqa: BLE001 - the block fails loudly, the bench still emits JSON
            fleet_failures.append(f"fleet block crashed: {exc!r}")
        finally:
            if fleet_router is not None:
                try:
                    _stop_httpd(fleet_router)
                except Exception:  # noqa: BLE001 - teardown best-effort
                    pass
            for srv in fleet_servers:
                try:
                    _stop_httpd(srv)
                except Exception:  # noqa: BLE001 - teardown best-effort
                    pass
            for d in fleet_dirs:
                _shutil.rmtree(d, ignore_errors=True)
        if device.platform != "tpu":
            fleet_block["caveat"] = (
                "CPU fallback (the BENCH_r05 convention): absolute "
                "overhead and capacity are the host's loopback stack, "
                "not a real fabric — the RATIOS (routed vs direct, "
                "goodput held at the top point) and the recompile "
                "verdict are the meaningful part here")
        if os.environ.get("BENCH_FLEET_INJECT_FAIL"):
            # Test hook: pin the fails-loudly path (mirrors
            # BENCH_OVERLOAD_INJECT_FAIL).
            fleet_failures.append(
                "BENCH_FLEET_INJECT_FAIL set: injected fleet verdict "
                "failure")
        fleet_block["ok"] = not fleet_failures

        # -- economics (ISSUE 19): the request-path economics layer's
        # own cost and behavior — one loopback backend with the
        # response cache + cost-priced admission on, driven with
        # Zipf-duplicate traffic (the key-reuse workload the cache
        # exists for). Three verdicts:
        # (1) a client-observed cache hit is ~free next to compute:
        #     hit p99 <= 0.1x miss p99 on TPU (on CPU loopback the
        #     HTTP stack dominates BOTH sides, so the enforced bar
        #     relaxes to hit p99 < miss p99 and the 0.1x number is
        #     reported with the BENCH_r05 caveat);
        # (2) goodput at ~10x offered load holds >= 96% of the curve's
        #     peak (the PR 14 single-process bar, which the cache
        #     should now CLEAR rather than approach: duplicates are
        #     answered from memory, not shed);
        # (3) zero steady-state recompiles across every economics
        #     drive — a cache hit never touches a chip, so it can
        #     never compile anything.
        # The collapse ratio (followers joined / requests served) and
        # the server's measured per-bucket cost table ride along as
        # report-only provenance.
        economics_failures: list = []
        economics_block: dict = {}
        econ_seconds = float(os.environ.get("BENCH_ECONOMICS_SECONDS",
                                            "1.0"))
        econ_reqs = int(os.environ.get("BENCH_ECONOMICS_REQUESTS", "200"))
        econ_dir = None
        econ_server = None
        try:
            econ_model = get_model("linear", compute_dtype=jnp.float32)
            econ_state = create_train_state(econ_model,
                                            jax.random.key(9))
            econ_dir = _tempfile.mkdtemp(prefix="bench-economics-")
            save_checkpoint(econ_state, epoch=0, best_acc=0.0,
                            is_best=False, directory=econ_dir,
                            process_index=0)
            econ_server = _boot_httpd(create_server(
                _serve_parser().parse_args([
                    "--checkpoint-dir", econ_dir, "--model", "linear",
                    "--dtype", "f32", "--host", "127.0.0.1",
                    "--port", "0", "--buckets", "1,8",
                    "--max-wait-ms", "2", "--max-queue", "256",
                    "--poll-interval", "5", "--price-admission"])))
            econ_url = econ_server["url"]

            def _econ_json(path):
                with _urlreq.urlopen(econ_url + path, timeout=10) as r:
                    return json.loads(r.read())

            # Warm the PROGRAMS with a disjoint body set (different
            # seed -> different bytes -> different cache keys), so the
            # measured drive sees warm compiles but a COLD cache: its
            # misses are pure compute, not compile.
            warm_bodies = _make_images(4, 8, seed=11)
            col = run_closed(econ_url, 16, 4, warm_bodies, timeout=30.0,
                             seed=1)
            warm_rep = _loadgen_report(col, 1.0, "closed")
            if warm_rep["ok"] != 16:
                raise RuntimeError(
                    f"economics warmup failed: {warm_rep}")
            before_econ = _serve_program_compiles()

            # (1) The Zipf-duplicate drive: 16 templates, exponent 1.1
            # — the head template dominates, every template's first
            # touch is a measured miss (compute), every repeat a hit.
            econ_bodies = _make_images(16, 8, seed=9)
            econ_zipf = zipf_cum(16, 1.1)
            t_e = time.perf_counter()
            col = run_closed(econ_url, econ_reqs, 8, econ_bodies,
                             timeout=30.0, seed=17, zipf=econ_zipf)
            zipf_rep = _loadgen_report(col, time.perf_counter() - t_e,
                                       "closed")
            cc = zipf_rep.get("cache_client", {})
            hit_p99 = cc.get("hit_latency_ms", {}).get("p99", 0.0)
            miss_p99 = cc.get("miss_latency_ms", {}).get("p99", 0.0)
            if zipf_rep["ok"] != econ_reqs:
                economics_failures.append(
                    f"zipf drive lost requests: {zipf_rep}")
            if not cc.get("hits") or not cc.get("misses"):
                economics_failures.append(
                    f"zipf drive never split hit/miss "
                    f"(cache inactive?): {cc}")
            hit_ratio = round(hit_p99 / max(miss_p99, 1e-9), 3)
            on_tpu = device.platform == "tpu"
            hit_bar = 0.1 if on_tpu else 1.0
            hit_cheap = hit_p99 <= hit_bar * miss_p99
            economics_block["zipf_drive"] = {
                "requests": econ_reqs,
                "zipf_exponent": 1.1,
                "templates": 16,
                "hit_rate": cc.get("hit_rate"),
                "hit_p99_ms": hit_p99,
                "miss_p99_ms": miss_p99,
                "hit_over_miss_p99": hit_ratio,
                "enforced_bar": hit_bar,
                "hit_is_cheap": hit_cheap,
            }
            if not hit_cheap:
                economics_failures.append(
                    f"cache hits are not cheap: hit p99 {hit_p99}ms vs "
                    f"miss p99 {miss_p99}ms (ratio {hit_ratio} > "
                    f"{hit_bar})")

            # (2) Goodput at 10x offered, cache warm: duplicates come
            # back from memory, so the top point should HOLD the PR 14
            # 96%-of-peak single-process bar, not merely approach it.
            t_cap = time.perf_counter()
            cap = run_closed(econ_url, 3 * econ_reqs // 2, 8,
                             econ_bodies, timeout=30.0, seed=23,
                             zipf=econ_zipf)
            cap_wall = max(time.perf_counter() - t_cap, 1e-9)
            econ_capacity = max(cap.status.get(200, 0) / cap_wall, 1e-9)
            econ_points = []
            for mult in (1, 10):
                rate = min(econ_capacity * mult, 1500.0)
                col = run_open(econ_url, rate, econ_seconds,
                               econ_bodies, timeout=10.0,
                               seed=60 + mult, zipf=econ_zipf)
                rep = _loadgen_report(col, econ_seconds, "open")
                econ_points.append({
                    "offered_x": round(rate / econ_capacity, 2),
                    "offered_rps": round(rate, 1),
                    "completed": rep["ok"],
                    "shed": rep["rejected"],
                    "not_launched": rep["not_launched"],
                    "hit_rate": rep.get("cache_client", {})
                    .get("hit_rate"),
                    "goodput_rps": round(rep["ok"] / econ_seconds, 1),
                })
                if rep["transport_errors"] or rep["conn_refused"]:
                    economics_failures.append(
                        f"requests dropped on the floor at {mult}x "
                        f"on the cached path: {rep}")
            peak_econ = max(pt["goodput_rps"] for pt in econ_points)
            top_econ = econ_points[-1]
            econ_frac = round(
                top_econ["goodput_rps"] / max(peak_econ, 1e-9), 3)
            economics_block["goodput"] = {
                "capacity_rps": round(econ_capacity, 1),
                "points": econ_points,
                "peak_goodput_rps": peak_econ,
                "goodput_at_top_fraction_of_peak": econ_frac,
                "single_process_fraction_of_peak": overload_block.get(
                    "goodput_at_top_fraction_of_peak"),
                "holds_at_overload": econ_frac >= 0.96,
            }
            if econ_frac < 0.96:
                economics_failures.append(
                    f"cached-path goodput fell below the 96%-of-peak "
                    f"bar at {top_econ['offered_x']}x: "
                    f"{top_econ['goodput_rps']} rps vs peak "
                    f"{peak_econ} rps ({econ_frac})")

            # (3) Zero recompiles + the report-only provenance: the
            # collapse ratio and the measured per-bucket cost table.
            delta_econ = _recompile_delta(before_econ,
                                          _serve_program_compiles())
            economics_block["zero_steady_state_recompiles"] = \
                not delta_econ
            if delta_econ:
                economics_failures.append(
                    f"steady-state serving recompiled on the cached "
                    f"path: {delta_econ}")
            stats = _econ_json("/stats")
            served = max(stats.get("requests", 0), 1)
            collapsed = stats.get("cache", {}).get("collapsed", 0)
            economics_block["collapse_ratio"] = round(
                collapsed / served, 4)
            economics_block["server_cache"] = stats.get("cache")
            economics_block["cost_model"] = stats.get("cost_model")
        except Exception as exc:  # noqa: BLE001 - the block fails loudly, the bench still emits JSON
            economics_failures.append(f"economics block crashed: {exc!r}")
        finally:
            if econ_server is not None:
                try:
                    _stop_httpd(econ_server)
                except Exception:  # noqa: BLE001 - teardown best-effort
                    pass
            if econ_dir is not None:
                _shutil.rmtree(econ_dir, ignore_errors=True)
        if device.platform != "tpu":
            economics_block["caveat"] = (
                "CPU fallback (the BENCH_r05 convention): the HTTP "
                "loopback stack dominates both the hit and the miss "
                "path, so the 0.1x hit-vs-compute bar is reported but "
                "only hit < miss is enforced — the hit rate, goodput "
                "fraction and recompile verdict are the meaningful "
                "part here")
        if os.environ.get("BENCH_ECONOMICS_INJECT_FAIL"):
            # Test hook: pin the fails-loudly path (mirrors
            # BENCH_FLEET_INJECT_FAIL).
            economics_failures.append(
                "BENCH_ECONOMICS_INJECT_FAIL set: injected economics "
                "verdict failure")
        economics_block["ok"] = not economics_failures

        value = requests / wall
        out.update({
            "value": round(value, 1),
            "vs_baseline": round(value / (requests / baseline_wall), 3),
            "requests": requests,
            "concurrency": concurrency,
            "p50_ms": snap["latency_ms"]["p50"],
            "p95_ms": snap["latency_ms"]["p95"],
            "p99_ms": snap["latency_ms"]["p99"],
            "batch_histogram": snap["batch_histogram"],
            "buckets": list(engine.buckets),
            "rejected": snap["rejected"],
            "warmup_compile_s": round(warmup_s, 3),
            "zero_steady_state_recompiles": zero_recompiles,
            "replica_scaling": replica_scaling,
            "sharded": sharded_block,
            "pipeline_serving": pipeline_block,
            "precision_sweep": precision_block,
            "whole_program": whole_program_block,
            "overload": overload_block,
            "fleet": fleet_block,
            "economics": economics_block,
            "pipeline_speedup": round(pipeline_speedup, 3),
            "pipeline_pairs": pipeline_pairs,
            "pool_requests": pool_requests,
            "pool_images_per_request": 8,
            "cpu_serve_devices_isolated": cpu_isolated,
            "zero_steady_state_recompiles_per_replica":
                not recompiled_replicas,
            "backend": device.platform,
            "device_kind": device.device_kind,
            "n_chips": jax.device_count(),
            "compile_stats": compile_log.stats(),
        })
        # The measured drives really served every request (phantom
        # completions would inflate the headline), and nothing failed.
        served_all = snap["requests"] == 2 * requests  # best-of-2 drives
        ok = (zero_recompiles and not drive_errors and served_all
              and not recompiled_replicas and not sharded_recompiles
              and not pipeline_recompiles and not precision_recompiles
              and not fused_recompiles and not wp_failures
              and not overload_failures and not fleet_failures
              and not economics_failures)
        if overload_failures:
            out["error"] = ("overload block failed: "
                            + "; ".join(overload_failures))
        elif fleet_failures:
            out["error"] = ("fleet block failed: "
                            + "; ".join(fleet_failures))
        elif economics_failures:
            out["error"] = ("economics block failed: "
                            + "; ".join(economics_failures))
        elif fused_recompiles:
            out["error"] = ("steady-state WHOLE-PROGRAM serving "
                            "recompiled (fused plane): "
                            f"{fused_recompiles}")
        elif wp_failures:
            out["error"] = ("whole-program block failed: "
                            + "; ".join(wp_failures))
        elif not zero_recompiles:
            out["error"] = ("steady-state serving recompiled: "
                            f"{totals_after_warmup} -> {totals_after_load}")
        elif recompiled_replicas:
            out["error"] = ("steady-state pool serving recompiled: "
                            f"{recompiled_replicas}")
        elif sharded_recompiles:
            out["error"] = ("steady-state SHARDED serving recompiled "
                            f"(per bucket x mode): {sharded_recompiles}")
        elif pipeline_recompiles:
            out["error"] = ("steady-state MPMD pipeline serving "
                            "recompiled (per bucket x stage): "
                            f"{pipeline_recompiles}")
        elif precision_recompiles:
            out["error"] = ("steady-state QUANTIZED serving recompiled "
                            "(per bucket x mode x precision): "
                            f"{precision_recompiles}")
        elif drive_errors:
            out["error"] = (f"{len(drive_errors)} requests failed during "
                            f"the drive: {drive_errors[:3]}")
        elif not served_all:
            out["error"] = (f"served {snap['requests']} of {2 * requests} "
                            f"requests across the measured drives")
    except Exception as exc:  # noqa: BLE001 - bench must always emit JSON
        out.update({"value": 0.0, "vs_baseline": 0.0, "error": repr(exc)})
        ok = False
    out["measured_at"] = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
    print(json.dumps(out))
    if not ok:
        sys.exit(1)


# 24 steps x 2048 images: epochs long enough (~250ms on the CI box) that
# the paired-ratio median is stable against scheduler noise; 7 pairs.
INPUT_STEPS = 24
INPUT_BATCH = 2048
INPUT_REPS = 7


def _isolate_cpu_input_compute() -> bool:
    """Make the CPU backend's step behave like a chip for the overlap
    measurement.

    On the CPU backend a single XLA execution grabs the whole host Eigen
    threadpool, so on this box the "device" step and the feeder thread
    fight for the same cores and the pipelined-vs-synchronous comparison
    measures core contention, not overlap (the exact failure mode
    ``_isolate_cpu_serve_devices`` fixes for the replica pool). A real
    accelerator computes off-host — the host CPU is idle during the
    step, which is what gives the feeder its window; Eigen isolation
    pins the step to one core so the other models that idle host CPU.
    Skipped entirely unless the run is CPU-bound.
    """
    if "xla_cpu_multi_thread_eigen" in os.environ.get("XLA_FLAGS", ""):
        # Flag already decided (e.g. a CI wrapper pre-set it).
        return _ensure_cpu_eigen_isolation()
    if not _run_is_cpu_bound():
        return False
    return _ensure_cpu_eigen_isolation()


def main_input() -> None:
    """``--mode input``: the input data plane's BENCH line (ISSUE 6).

    Measures the feed path in isolation and end to end, emitting ONE
    JSON line whose ``input_pipeline`` block carries:

    - ``feed_images_per_sec``: feed-only throughput — the staging
      pipeline (host gather + sharded ``device_put``) driven with no
      training step consuming it. This is the ceiling the input plane
      can sustain; a chip whose step rate exceeds it starves.
    - ``pipelined_feed_speedup``: real per-batch training epochs with
      the feeder at window 2 vs window 1 (today's synchronous strict
      alternation), as the MEDIAN of per-rep paired ratios from
      ABBA-interleaved drives — the serve bench's pairing methodology,
      because on a shares-throttled CI box adjacent drives see the same
      neighbor load and the ratio survives drift that best-of-each-side
      would turn into noise. Window 1 is trajectory-bitwise-identical
      to window 2 (tests/test_staging.py), so the delta is pure
      latency.
    - ``native_preprocess_speedup`` / ``native_pad_speedup``: the serve
      dispatch path's host-side array work (normalize + the
      pad-into-staging copy) in multithreaded C++ vs the bitwise-
      identical NumPy fallbacks, same interleaved-pairs protocol.
      ``native_available: false`` labels a fallback-only environment
      honestly (the ``--mode serve`` CPU-labeling convention), with
      null speedups rather than fabricated ones.
    - zero-steady-state-recompile checks for BOTH sides: the measured
      train epochs and a serve dispatch drive after warmup.

    Never raises; failures become an ``error`` line (the
    always-emit-JSON contract every bench mode follows).
    """
    out = {
        "metric": "mnist_input_pipeline_feed_images_per_sec",
        "unit": "images/sec",
        "baseline": "synchronous (window 1) per-batch staging, same "
                    "loader and jitted step: vs_baseline is the "
                    "pipelined-feed epoch speedup",
    }
    ok = False
    try:
        import statistics

        # Must run before the first jax device query: XLA_FLAGS are read
        # once, at backend init.
        cpu_isolated = _isolate_cpu_input_compute()

        import jax

        configure_jax()

        import jax.numpy as jnp
        import numpy as np

        from pytorch_distributed_mnist_tpu.data import native as native_mod
        from pytorch_distributed_mnist_tpu.data.loader import MNISTDataLoader
        from pytorch_distributed_mnist_tpu.data.mnist import synthetic_dataset
        from pytorch_distributed_mnist_tpu.data.staging import BatchFeeder
        from pytorch_distributed_mnist_tpu.models import get_model
        from pytorch_distributed_mnist_tpu.parallel.mesh import make_mesh
        from pytorch_distributed_mnist_tpu.serve.engine import InferenceEngine
        from pytorch_distributed_mnist_tpu.train.state import create_train_state
        from pytorch_distributed_mnist_tpu.train.trainer import Trainer
        from pytorch_distributed_mnist_tpu.utils.profiling import (
            StagingLog,
            compile_log,
        )

        device = jax.devices()[0]
        _require_tpu(device.platform)
        n_chips = jax.device_count()
        mesh = make_mesh(("data",)) if n_chips > 1 else None
        steps = int(os.environ.get("BENCH_INPUT_STEPS", INPUT_STEPS))
        batch = int(os.environ.get("BENCH_INPUT_BATCH", INPUT_BATCH))
        reps = int(os.environ.get("BENCH_INPUT_REPS", INPUT_REPS))

        # Linear model on purpose: its step cost is the same order as
        # the staging cost at this batch size, which is the regime where
        # overlap is visible. (A conv step hundreds of ms long hides ANY
        # feed path; a chip fast enough to starve is the linear case.)
        n = steps * batch
        rng = np.random.default_rng(0)
        data_images = rng.standard_normal((n, 28, 28, 1)).astype(np.float32)
        data_labels = (np.arange(n) % 10).astype(np.int32)
        model = get_model("linear", compute_dtype=jnp.float32)

        def make_trainer(window: int, staging: StagingLog = None):
            state = create_train_state(model, jax.random.key(0))
            loader = MNISTDataLoader(data_images, data_labels,
                                     batch_size=batch, train=True, seed=7)
            trainer = Trainer(state, loader, loader, mesh=mesh,
                              mode="stepwise", feed_window=window,
                              staging_log=staging)
            return trainer, loader

        # -- feed-only throughput: the staging pipeline with no consumer
        # compute, inline (window 1) so the log's feed rate is the pure
        # staging wall.
        feed_log = StagingLog()
        feed_loader = MNISTDataLoader(data_images, data_labels,
                                      batch_size=batch, train=True, seed=7)
        feed_only = BatchFeeder(feed_loader, mesh, window=1,
                                staging_log=feed_log)
        t_feed = time.perf_counter()
        for staged in feed_only.epoch():
            jax.block_until_ready(staged["image"])
        feed_wall_s = time.perf_counter() - t_feed
        feed = feed_log.summary()
        # Async-dispatch honesty: the log's stage walls time the
        # device_put DISPATCH (JAX returns before the transfer lands);
        # only the block_until_ready above observes completion. The
        # headline feed rate comes from the full blocked wall so a real
        # chip's DMA time can't be silently excluded — on the CPU
        # backend the two are within noise, on a TPU they are not.
        feed["feed_images_per_sec"] = round(
            feed["images"] / max(feed_wall_s, 1e-9), 1)

        # -- pipelined vs synchronous epochs, ABBA-interleaved pairs.
        pipe_log = StagingLog()
        pipe, pipe_loader = make_trainer(2, pipe_log)
        sync, sync_loader = make_trainer(1)
        epoch_counter = {"pipe": 0, "sync": 0}

        def drive_epoch(trainer, loader, key) -> float:
            loader.set_sample_epoch(epoch_counter[key])
            epoch_counter[key] += 1
            t0 = time.perf_counter()
            loss, _acc = trainer.train()
            float(loss.average)  # host read: execution definitely done
            return time.perf_counter() - t0

        drive_epoch(pipe, pipe_loader, "pipe")  # compile + warm both
        drive_epoch(sync, sync_loader, "sync")
        totals_before = dict(compile_log.stats()["totals"])
        pipe_log.reset()
        pairs = []
        pipe_walls, sync_walls = [], []
        for rep in range(reps):
            order = ("pipe", "sync") if rep % 2 == 0 else ("sync", "pipe")
            walls = {}
            for key in order:
                trainer, loader = (pipe, pipe_loader) if key == "pipe" \
                    else (sync, sync_loader)
                walls[key] = drive_epoch(trainer, loader, key)
            pipe_walls.append(walls["pipe"])
            sync_walls.append(walls["sync"])
            pairs.append(round(walls["sync"] / walls["pipe"], 3))
        feed_speedup = statistics.median(pairs)
        train_totals_after = dict(compile_log.stats()["totals"])
        zero_recompiles_train = (
            train_totals_after["backend_compiles"]
            == totals_before["backend_compiles"])
        overlap = pipe_log.summary()

        # -- serve dispatch path: native vs NumPy preprocess + pad, and
        # the post-warmup zero-recompile check on real predicts.
        raw_images, _ = synthetic_dataset(4096, seed=1)
        serve_state = create_train_state(model, jax.random.key(0))
        engine = InferenceEngine(model.apply, serve_state.params)
        engine.warmup()
        serve_before = dict(compile_log.stats()["totals"])
        stack = engine.preprocess(raw_images[:128])
        for _ in range(8):
            engine.predict(stack)
        zero_recompiles_serve = (
            compile_log.stats()["totals"]["backend_compiles"]
            == serve_before["backend_compiles"])

        bucket = max(engine.buckets)
        pad_src = np.ascontiguousarray(
            engine.preprocess(raw_images[:bucket - 16]), np.float32)
        pad_dst = np.empty((bucket,) + pad_src.shape[1:], np.float32)

        def time_preprocess() -> float:
            t0 = time.perf_counter()
            engine.preprocess(raw_images)
            return time.perf_counter() - t0

        def time_pad(use_native: bool, iters: int = 200) -> float:
            # One pad is ~tens of microseconds — integrate over many so
            # the ratio measures the copy, not perf_counter granularity.
            t0 = time.perf_counter()
            if use_native:
                for _ in range(iters):
                    if not native_mod.pad_into(pad_dst, pad_src,
                                               workers=engine.workers):
                        # Not an assert: python -O would strip the CALL
                        # and time 200 iterations of nothing.
                        raise RuntimeError("native pad_into refused a "
                                           "layout it must accept")
            else:
                for _ in range(iters):
                    pad_dst[:len(pad_src)] = pad_src
                    pad_dst[len(pad_src):] = 0.0
            return time.perf_counter() - t0

        native_available = native_mod.available()
        pre_speedup = pad_speedup = None
        pre_pairs, pad_pairs = [], []
        if native_available:
            def numpy_only(fn):
                """Run ``fn`` with the native library switched off (the
                mandatory fallback path) in this same process."""
                prior = os.environ.get("TPUMNIST_NATIVE")
                os.environ["TPUMNIST_NATIVE"] = "0"
                native_mod._lib = None
                try:
                    return fn()
                finally:
                    if prior is None:
                        del os.environ["TPUMNIST_NATIVE"]
                    else:
                        os.environ["TPUMNIST_NATIVE"] = prior
                    native_mod._lib = None
                    # Re-warm the load NOW, outside any timed window:
                    # the next native-side measurement must not pay the
                    # filesystem probe + dlopen + argtype wiring inside
                    # its timer (it would bias every pair's native leg).
                    native_mod.available()

            time_preprocess()               # warm both paths once
            numpy_only(time_preprocess)
            time_pad(True)
            time_pad(False)  # pure slice-assign; no native switch needed
            for rep in range(reps):
                if rep % 2 == 0:
                    nat = time_preprocess()
                    np_t = numpy_only(time_preprocess)
                else:
                    np_t = numpy_only(time_preprocess)
                    nat = time_preprocess()
                pre_pairs.append(round(np_t / nat, 3))
                if rep % 2 == 0:
                    nat_p = time_pad(True)
                    np_p = time_pad(False)
                else:
                    np_p = time_pad(False)
                    nat_p = time_pad(True)
                pad_pairs.append(round(np_p / nat_p, 3))
            pre_speedup = statistics.median(pre_pairs)
            pad_speedup = statistics.median(pad_pairs)

        out.update({
            "value": feed["feed_images_per_sec"],
            "vs_baseline": round(feed_speedup, 3),
            "input_pipeline": {
                "feed_images_per_sec": feed["feed_images_per_sec"],
                "feed_host_ms": feed["host_ms"],
                "feed_h2d_ms": feed["h2d_ms"],
                "feed_steps": feed["stages"],
                "global_batch": batch,
                "pipelined_epoch_ms": round(
                    statistics.median(pipe_walls) * 1e3, 1),
                "synchronous_epoch_ms": round(
                    statistics.median(sync_walls) * 1e3, 1),
                "pipelined_feed_speedup": round(feed_speedup, 3),
                "pipeline_pairs": pairs,
                "feed_window": 2,
                "overlap_fraction": overlap["overlap_fraction"],
                "native_available": native_available,
                "native_preprocess_speedup": pre_speedup,
                "native_preprocess_pairs": pre_pairs,
                "native_pad_speedup": pad_speedup,
                "native_pad_pairs": pad_pairs,
                "preprocess_images": len(raw_images),
                "cpu_compute_isolated": cpu_isolated,
                "zero_steady_state_recompiles_train":
                    zero_recompiles_train,
                "zero_steady_state_recompiles_serve":
                    zero_recompiles_serve,
            },
            "backend": device.platform,
            "device_kind": device.device_kind,
            "n_chips": n_chips,
            "compile_stats": compile_log.stats(),
        })
        ok = zero_recompiles_train and zero_recompiles_serve
        if not zero_recompiles_train:
            out["error"] = ("measured train epochs recompiled: "
                            f"{totals_before} -> {train_totals_after}")
        elif not zero_recompiles_serve:
            out["error"] = "steady-state serve dispatch recompiled"
    except Exception as exc:  # noqa: BLE001 - bench must always emit JSON
        out.update({"value": 0.0, "vs_baseline": 0.0, "error": repr(exc)})
        ok = False
    out["measured_at"] = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
    print(json.dumps(out))
    if not ok:
        sys.exit(1)


# 16 steps x 1024 images (256 on the CPU fallback): per-step jitted
# drives long enough that the ABBA paired ratios are stable against
# scheduler noise on the CI box; 5 pairs.
ZERO_STEPS = 16
ZERO_REPS = 5


def _force_cpu_zero_world() -> dict:
    """CPU backends get a forced multi-device world for ``--mode zero``.

    ZeRO over one device has nothing to scatter: a single-chip CPU run
    would measure degenerate collectives and report a meaningless
    overlap. When the run is CPU-bound and no device count is forced
    yet, append ``--xla_force_host_platform_device_count=4`` (the
    serve bench's CI stand-in for a 4-chip host) and the Eigen isolation
    that makes one "device" stop grabbing every host core
    (``_ensure_cpu_eigen_isolation``). Must run before the first jax
    device query — XLA_FLAGS are read once, at backend init. No-op on
    real accelerators.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" in flags:
        return {"cpu_devices_forced": False,
                "cpu_compute_isolated": _ensure_cpu_eigen_isolation()}
    if not _run_is_cpu_bound():
        return {"cpu_devices_forced": False,
                "cpu_compute_isolated": False}
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=4").strip()
    return {"cpu_devices_forced": True,
            "cpu_compute_isolated": _ensure_cpu_eigen_isolation()}


def main_zero() -> None:
    """``--mode zero``: the overlapped-ZeRO weight update's BENCH line
    (ISSUE 7).

    Drives the explicit overlapped data plane
    (``parallel/zero_overlap.py``) against the propagation-scheduled
    path (``parallel/zero.py`` + GSPMD) on the same model, state layout,
    and batches, and emits ONE JSON line whose ``zero_overlap`` block
    carries the measured — not asserted — overlap story:

    - ``step_ms_overlap`` / ``step_ms_propagation``: median per-step
      walls from ABBA-interleaved paired drives (the PR 4/6 pairing
      methodology: adjacent drives see the same neighbor load, so the
      ratio survives CPU-share drift); ``vs_baseline`` is the median
      paired speedup, overlapped over propagation.
    - ``comm_ms_per_step``: a compute-free twin running EXACTLY the
      step's bucket-fenced reduce-scatter + allgather sequence
      (``make_comm_only_program``).
    - ``compute_ms_per_step``: a communication-free twin — the same
      overlapped step on a 1-device mesh with this chip's share of the
      batch (collectives degenerate to copies).
    - ``overlap_fraction``: ``comm_overlap_fraction(step, compute,
      comm)`` (utils/profiling.py) — how much of the measured
      communication the measured step actually hid.
    - train MFU via ``_peak_flops`` (the headline bench's convention,
      same >100%-of-peak sync guard), FLOPs/step from the compiled
      overlapped program's own cost analysis.
    - zero-steady-state-recompile verdicts for BOTH paths through
      ``CompileLog``: the measured drives run under per-path measures,
      so any backend compile during the steady-state window attributes
      to the path that triggered it, and a nonzero count fails the
      bench loudly (exit 1).
    - ``two_tier``: the hierarchical (DCN x ICI) schedule measured per
      tier — real slice topology when the runtime reports one, else the
      emulated 2-slice map (labelled ``dcn_emulated``). Per-tier
      compute-free comm twins (the ici-only RS+AG chain, the dcn-only
      shard all-reduces), per-tier overlap fractions
      (``per_tier_overlap_fractions``), an ABBA-paired two-tier-vs-flat
      speedup, and per-drive recompile verdicts (the two-tier step AND
      each tier twin) that fail the bench exactly like the flat ones.
      ``BENCH_ZERO_INJECT_RECOMPILE=two_tier`` poisons the hier drive
      specifically.

    A CPU run is honestly labelled (``cpu_fallback`` + caveat: XLA:CPU
    has no async communication stream, so overlap cannot manifest and
    the speedup sign is not accelerator evidence — the BENCH_r05
    CPU-fallback precedent). ``BENCH_ZERO_INJECT_RECOMPILE`` is a
    test-only hook that compiles a fresh program inside each measured
    overlap drive so the fails-loudly path is itself testable. Never
    raises; failures become an ``error`` line.
    """
    out = {
        "metric": "mnist_zero_overlap_train_images_per_sec_per_chip",
        "unit": "images/sec/chip",
        "baseline": "same model/state layout/batches with "
                    "propagation-scheduled ZeRO (XLA sharding "
                    "propagation): vs_baseline is the median ABBA-paired "
                    "overlapped-vs-propagation step-drive speedup",
    }
    ok = False
    try:
        import statistics

        world = _force_cpu_zero_world()

        import jax

        configure_jax()

        import jax.numpy as jnp
        import numpy as np

        from pytorch_distributed_mnist_tpu.data.mnist import (
            normalize_images,
            synthetic_dataset,
        )
        from pytorch_distributed_mnist_tpu.models import get_model
        from pytorch_distributed_mnist_tpu.parallel.mesh import (
            device_slice_index,
            infer_dcn_slices,
            make_hier_mesh,
            make_mesh,
        )
        from pytorch_distributed_mnist_tpu.parallel.zero import (
            shard_state_zero,
        )
        from pytorch_distributed_mnist_tpu.parallel.zero_overlap import (
            make_comm_only_program,
            make_overlap_train_step,
            make_param_gather,
        )
        from pytorch_distributed_mnist_tpu.train.state import (
            create_train_state,
        )
        from pytorch_distributed_mnist_tpu.train.steps import make_train_step
        from pytorch_distributed_mnist_tpu.utils.profiling import (
            comm_overlap_fraction,
            compile_log,
            per_tier_overlap_fractions,
        )

        device = jax.devices()[0]
        _require_tpu(device.platform)
        n_chips = jax.device_count()
        on_tpu = device.platform == "tpu"
        refused = _refuse_fakes_on_tpu(out, device.platform)
        if refused:
            raise RuntimeError(refused["error"])
        level = int(os.environ.get("BENCH_ZERO_LEVEL", "3"))
        bucket_mb = float(os.environ.get("BENCH_ZERO_BUCKET_MB", "4.0"))
        steps = int(os.environ.get("BENCH_ZERO_STEPS", ZERO_STEPS))
        reps = int(os.environ.get("BENCH_ZERO_REPS", ZERO_REPS))
        batch = int(os.environ.get("BENCH_ZERO_BATCH",
                                   "1024" if on_tpu else "256"))
        batch = max(batch - batch % n_chips, n_chips)  # exact row split
        # Test-only recompile injections: "1" (any truthy value except
        # "two_tier") poisons the flat overlap drive, "two_tier" the
        # hierarchical drive — so both fails-loudly paths are testable
        # with per-path attribution.
        inject_env = os.environ.get("BENCH_ZERO_INJECT_RECOMPILE", "")
        inject = bool(inject_env) and inject_env != "two_tier"
        inject_two_tier = inject_env == "two_tier"

        mesh = make_mesh(("data",))
        # Same backend policy as the training bench: bf16 MXU path on
        # TPU, f32 on the CPU fallback.
        model = get_model(
            "cnn", **({} if on_tpu else {"compute_dtype": jnp.float32}))
        images, labels = synthetic_dataset(batch, seed=0)
        x = np.asarray(normalize_images(images))
        y = labels.astype(np.int32)
        one = {"image": jnp.asarray(x), "label": jnp.asarray(y)}

        # -- the two paths, identical state layout, AOT-compiled.
        prop_state, sharding = shard_state_zero(
            create_train_state(model, jax.random.key(0)), mesh, level=level)
        prop_jit = make_train_step(mesh, state_sharding=sharding)
        with compile_log.measure("zero_step_propagation"):
            prop_step = prop_jit.lower(prop_state, one).compile()

        ov_state, _ = shard_state_zero(
            create_train_state(model, jax.random.key(0)), mesh, level=level)
        ov_jit = make_overlap_train_step(
            ov_state, mesh, level=level, bucket_mb=bucket_mb)
        gather = make_param_gather(mesh)  # one program, both uses below
        gathered = gather(ov_state.params) if level == 3 else None
        with compile_log.measure("zero_step_overlap"):
            ov_step = (ov_jit.lower(ov_state, gathered, one).compile()
                       if level == 3
                       else ov_jit.lower(ov_state, one).compile())

        flops_per_step = None
        try:
            cost = ov_step.cost_analysis()
            if isinstance(cost, (list, tuple)):
                cost = cost[0] if cost else {}
            total = float(cost.get("flops", 0.0))
            if total > 0:
                flops_per_step = total
        except Exception:  # noqa: BLE001 - analytic fallback below
            pass
        if not flops_per_step:
            flops_per_step = float(_CNN_STEP_FLOPS_PER_IMAGE * batch)

        # -- comm-only twin: exactly the step's collective sequence on
        # param-shaped values, no model compute between.
        comm_jit = make_comm_only_program(ov_state, mesh,
                                          bucket_mb=bucket_mb)
        params_full = gather(ov_state.params)
        with compile_log.measure("zero_comm_only"):
            comm_prog = comm_jit.lower(params_full).compile()

        # -- compute-only twin: the same overlapped step on a 1-device
        # mesh with this chip's share of the batch (collectives
        # degenerate to local copies: the step minus communication).
        mesh1 = make_mesh(("data",), devices=[jax.devices()[0]])
        c_state, _ = shard_state_zero(
            create_train_state(model, jax.random.key(0)), mesh1,
            level=level)
        c_jit = make_overlap_train_step(
            c_state, mesh1, level=level, bucket_mb=bucket_mb)
        c_gathered = (make_param_gather(mesh1)(c_state.params)
                      if level == 3 else None)
        per_chip = max(n_chips, 1)
        one_c = {"image": jnp.asarray(x[: batch // per_chip]),
                 "label": jnp.asarray(y[: batch // per_chip])}
        with compile_log.measure("zero_compute_only"):
            c_step = (c_jit.lower(c_state, c_gathered, one_c).compile()
                      if level == 3
                      else c_jit.lower(c_state, one_c).compile())

        # -- drives: per-step executables chained with ONE host sync at
        # the end (the metric-count read, the _warmup_and_time protocol).
        state_of = {"overlap": (ov_state, gathered),
                    "propagation": (prop_state, None)}
        step_of = {"overlap": ov_step, "propagation": prop_step}
        injected = {"n": 0}

        def drive(key, n_steps) -> float:
            st, gp = state_of[key]
            fn = step_of[key]
            m = None
            t0 = time.perf_counter()
            for _ in range(n_steps):
                if gp is not None:
                    st, gp, m = fn(st, gp, one)
                else:
                    st, m = fn(st, one)
            if float(m.count) != batch:  # full host roundtrip sync — a
                # plain statement, not assert: python -O would strip the
                # only sync and time async DISPATCH of the whole drive.
                raise RuntimeError(
                    f"zero drive sync: count {float(m.count)} != {batch}")
            wall = time.perf_counter() - t0
            state_of[key] = (st, gp)
            return wall

        drive("overlap", 2)       # warm end to end (donation, dispatch)
        drive("propagation", 2)
        for _ in range(3):        # warm the twins
            float(comm_prog(params_full))
        if c_gathered is not None:
            c_st, c_gp, cm = c_step(c_state, c_gathered, one_c)
        else:
            c_st, cm = c_step(c_state, one_c)
            c_gp = None
        float(cm.count)

        # -- measured ABBA pairs, each drive under its path's CompileLog
        # measure so a steady-state compile attributes to its path.
        walls = {"overlap": [], "propagation": []}
        for rep in range(reps):
            order = (("overlap", "propagation") if rep % 2 == 0
                     else ("propagation", "overlap"))
            for key in order:
                with compile_log.measure(f"zero_drive_{key}"):
                    if inject and key == "overlap":
                        # Test-only: a fresh program per rep inside the
                        # measured window — drives the fails-loudly path.
                        injected["n"] += 1
                        jax.jit(lambda v, _k=injected["n"]: v * (_k + 1))(
                            jnp.ones((2,), jnp.float32)
                        ).block_until_ready()
                    walls[key].append(drive(key, steps))
        pairs = [round(p / o, 3)
                 for o, p in zip(walls["overlap"], walls["propagation"])]
        speedup = statistics.median(pairs)

        def _per_step_ms(wall_list) -> float:
            return statistics.median(wall_list) / steps * 1e3

        step_ms_overlap = _per_step_ms(walls["overlap"])
        step_ms_prop = _per_step_ms(walls["propagation"])

        comm_walls = []
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(steps):
                r = comm_prog(params_full)
            float(r)
            comm_walls.append(time.perf_counter() - t0)
        comm_ms = min(comm_walls) / steps * 1e3

        compute_walls = []
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(steps):
                if c_gp is not None:
                    c_st, c_gp, cm = c_step(c_st, c_gp, one_c)
                else:
                    c_st, cm = c_step(c_st, one_c)
            float(cm.count)
            compute_walls.append(time.perf_counter() - t0)
        compute_ms = min(compute_walls) / steps * 1e3

        overlap_frac = comm_overlap_fraction(
            step_ms_overlap, compute_ms, comm_ms)

        # -- two-tier (DCN x ICI) twin: the hierarchical-mesh schedule
        # with a PER-TIER comm breakdown — real slice topology when the
        # runtime reports one, else the emulated slice map (2 slices by
        # default on an even chip count), honestly labelled. Each
        # tier's comm cost comes from its own compute-free twin (the
        # ici-only RS+AG chain / the dcn-only shard all-reduces), and
        # each measured drive runs under its own CompileLog measure so
        # a steady-state recompile attributes to — and fails — exactly
        # the program that triggered it.
        two_tier = None
        two_tier_verdicts = {}
        dcn_slices = infer_dcn_slices()
        if dcn_slices < 2 and n_chips >= 2 and n_chips % 2 == 0:
            dcn_slices = 2  # emulated default: the smallest hierarchy
        dcn_emulated = any(
            device_slice_index(d) is None for d in jax.devices())
        if dcn_slices < 2 or n_chips % dcn_slices:
            two_tier = {"skipped": (
                f"{n_chips} chip(s) do not split into {dcn_slices} "
                f"equal DCN slices — nothing hierarchical to measure")}
        else:
            bucket_mb_dcn = float(os.environ.get(
                "BENCH_ZERO_BUCKET_MB_DCN", str(bucket_mb)))
            hier_mesh = make_hier_mesh(dcn_slices)
            h_state, _ = shard_state_zero(
                create_train_state(model, jax.random.key(0)), hier_mesh,
                level=level)
            h_jit = make_overlap_train_step(
                h_state, hier_mesh, level=level, bucket_mb=bucket_mb,
                bucket_mb_dcn=bucket_mb_dcn)
            h_gather = make_param_gather(hier_mesh)
            h_gathered = h_gather(h_state.params) if level == 3 else None
            with compile_log.measure("zero_step_two_tier"):
                h_step = (h_jit.lower(h_state, h_gathered, one).compile()
                          if level == 3
                          else h_jit.lower(h_state, one).compile())
            state_of["two_tier"] = (h_state, h_gathered)
            step_of["two_tier"] = h_step
            # Per-tier compute-free twins on the SAME hier mesh/state.
            # h_full is a SEPARATE gather on purpose (not h_gathered):
            # the two-tier step donates its gathered carry, so the tier
            # twins need a buffer the drives can never invalidate.
            h_full = h_gather(h_state.params)
            tier_progs = {}
            for tier in ("ici", "dcn"):
                t_jit = make_comm_only_program(
                    h_state, hier_mesh, bucket_mb=bucket_mb,
                    bucket_mb_dcn=bucket_mb_dcn, tier=tier)
                with compile_log.measure(f"zero_comm_tier_{tier}"):
                    tier_progs[tier] = t_jit.lower(h_full).compile()
            drive("two_tier", 2)  # warm end to end
            for tier in ("ici", "dcn"):
                for _ in range(3):
                    float(tier_progs[tier](h_full))
            # Measured ABBA pairs: two-tier vs the flat overlapped path
            # (same chips, same batches — the "what does the hierarchy
            # cost/buy on this box" ratio).
            walls_tt, walls_fo = [], []
            for rep in range(reps):
                order = (("two_tier", "overlap") if rep % 2 == 0
                         else ("overlap", "two_tier"))
                for key in order:
                    with compile_log.measure(f"zero_drive_{key}"):
                        if inject_two_tier and key == "two_tier":
                            injected["n"] += 1
                            jax.jit(lambda v, _k=injected["n"]:
                                    v * (_k + 2))(
                                jnp.ones((3,), jnp.float32)
                            ).block_until_ready()
                        w = drive(key, steps)
                    (walls_tt if key == "two_tier"
                     else walls_fo).append(w)
            pairs_tt = [round(f / t, 3)
                        for t, f in zip(walls_tt, walls_fo)]
            step_ms_tt = statistics.median(walls_tt) / steps * 1e3
            tier_ms = {}
            for tier in ("ici", "dcn"):
                tws = []
                for _ in range(3):
                    t0 = time.perf_counter()
                    with compile_log.measure(f"zero_drive_tier_{tier}"):
                        for _ in range(steps):
                            r = tier_progs[tier](h_full)
                        float(r)
                    tws.append(time.perf_counter() - t0)
                tier_ms[tier] = min(tws) / steps * 1e3
            # The 1-device compute twin above is tier-free (collectives
            # degenerate either way), so it serves both decompositions.
            tier_fracs = per_tier_overlap_fractions(
                step_ms_tt, compute_ms, tier_ms)

        steps_per_sec = steps / min(walls["overlap"])
        peak = _peak_flops(device.device_kind)
        mfu = (flops_per_step * steps_per_sec / n_chips / peak) if peak \
            else None
        if mfu is not None and mfu > 1.0:
            raise RuntimeError(
                f"impossible zero-overlap train MFU {mfu:.3g} (>100% of "
                f"peak): device sync did not wait for execution")

        programs = compile_log.stats()["programs"]

        def _drive_compiles(key) -> int:
            return programs.get(f"zero_drive_{key}",
                                {}).get("backend_compiles", 0)

        verdicts = {key: _drive_compiles(key) == 0
                    for key in ("overlap", "propagation")}
        if two_tier is None or "skipped" not in two_tier:
            two_tier_verdicts = {
                key: _drive_compiles(key) == 0
                for key in ("two_tier", "tier_ici", "tier_dcn")}
            two_tier = {
                "dcn_slices": dcn_slices,
                "chips_per_slice": n_chips // dcn_slices,
                "dcn_emulated": dcn_emulated,
                "bucket_mb": bucket_mb,
                "bucket_mb_dcn": bucket_mb_dcn,
                "step_ms_two_tier": round(step_ms_tt, 3),
                "vs_flat_overlap_speedup": round(
                    statistics.median(pairs_tt), 3),
                "pairs": pairs_tt,
                "tiers": {
                    tier: {
                        "comm_ms_per_step": round(tier_ms[tier], 3),
                        "overlap_fraction": tier_fracs[tier],
                        "zero_steady_state_recompiles":
                            two_tier_verdicts[f"tier_{tier}"],
                    }
                    for tier in ("ici", "dcn")
                },
                "zero_steady_state_recompiles_two_tier":
                    two_tier_verdicts["two_tier"],
            }
            if dcn_emulated:
                two_tier["caveat"] = (
                    "emulated DCN slices: host-thread collectives say "
                    "nothing about real cross-slice DCN latency, so "
                    "the per-tier split shows the schedule's traffic "
                    "shape, not DCN cost, and the vs-flat sign is not "
                    "accelerator evidence (BENCH_r05 CPU-fallback "
                    "precedent)")

        value = batch * steps / min(walls["overlap"]) / n_chips
        block = {
            "level": level,
            "bucket_mb": bucket_mb,
            "steps": steps,
            "global_batch": batch,
            "step_ms_overlap": round(step_ms_overlap, 3),
            "step_ms_propagation": round(step_ms_prop, 3),
            "comm_ms_per_step": round(comm_ms, 3),
            "compute_ms_per_step": round(compute_ms, 3),
            "overlap_fraction": overlap_frac,
            "overlap_vs_propagation_speedup": round(speedup, 3),
            "pairs": pairs,
            "overlap_beats_propagation": speedup > 1.0,
            "mfu": round(mfu, 4) if mfu is not None else None,
            "flops_per_step": flops_per_step,
            "peak_flops_per_chip": peak,
            "zero_steady_state_recompiles_overlap": verdicts["overlap"],
            "zero_steady_state_recompiles_propagation":
                verdicts["propagation"],
            "cpu_devices_forced": world["cpu_devices_forced"],
            "cpu_compute_isolated": world["cpu_compute_isolated"],
            "two_tier": two_tier,
        }
        if not on_tpu:
            block["cpu_fallback"] = True
            block["caveat"] = (
                "CPU backend: XLA:CPU runs collectives and compute on "
                "the same host cores with no asynchronous communication "
                "stream, so comm/compute overlap cannot manifest here "
                "and the overlapped-vs-propagation sign is not "
                "accelerator evidence (BENCH_r05 CPU-fallback precedent)")
        elif not block["overlap_beats_propagation"]:
            out["note"] = (
                "overlapped path did not beat propagation on this TPU "
                "drive; XLA's propagation schedule may already overlap "
                "— see the zero_overlap block's per-step decomposition")
        out.update({
            "value": round(value, 1),
            "vs_baseline": round(speedup, 3),
            "zero_overlap": block,
            "backend": device.platform,
            "device_kind": device.device_kind,
            "n_chips": n_chips,
            "compile_stats": compile_log.stats(),
        })
        ok = (verdicts["overlap"] and verdicts["propagation"]
              and all(two_tier_verdicts.values()))
        if not ok:
            tier_counts = "".join(
                f", {key}={_drive_compiles(key)}"
                for key in sorted(two_tier_verdicts))
            out["error"] = (
                "steady-state recompiles during the measured zero "
                "drives: overlap="
                f"{_drive_compiles('overlap')}, propagation="
                f"{_drive_compiles('propagation')}{tier_counts} "
                "backend compile(s) "
                "(the AOT executables must be shape-stable)")
    except Exception as exc:  # noqa: BLE001 - bench must always emit JSON
        out.update({"value": 0.0, "vs_baseline": 0.0, "error": repr(exc)})
        ok = False
    out["measured_at"] = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
    print(json.dumps(out))
    if not ok:
        sys.exit(1)


def main_publish() -> None:
    """``--mode publish``: the delta-distribution BENCH line (ISSUE 18).

    Measures what a checkpoint publish COSTS and how fast a fleet
    becomes consistent, delta vs whole-file:

    - **publisher side**: whole-file npz bytes + write time vs the
      cold (first) delta publish vs an ADJACENT publish (one leaf
      changed — the training-loop steady state); the adjacent publish's
      new chunk bytes over the whole-file bytes is the headline ratio.
    - **fleet side**: three in-process ``DeltaFetcher`` "backends" over
      one published manifest — backend 0 fetches from the source
      directory and seeds a real loopback ``/chunks/<hash>`` HTTP
      server (the gossip plane); backends 1-2 list it as a peer, so
      their bytes must arrive peer-first (``bytes_source == 0``).
      Cold-start fetch (a new backend joins: every params chunk moves,
      but never the optimizer moments) and adjacent fetch (only the
      dirty leaf's chunks move) each get bytes + time-to-fleet-
      consistency, and the adjacent fleet bytes must land under 30% of
      shipping the whole file to every backend — the ISSUE 18
      acceptance bar, asserted here so it fails loudly.

    Filesystem + loopback-HTTP only (no device program in the measured
    path), so absolute times are the host's; the byte counts and
    ratios are platform-independent. ``BENCH_PUBLISH_INJECT_FAIL``
    pins the fails-loudly path for tests."""
    import shutil as _shutil
    import tempfile
    import threading
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    import jax
    import jax.numpy as jnp

    from pytorch_distributed_mnist_tpu.distrib.cas import ChunkStore
    from pytorch_distributed_mnist_tpu.distrib.fetch import DeltaFetcher
    from pytorch_distributed_mnist_tpu.distrib.publish import publish_state
    from pytorch_distributed_mnist_tpu.models import get_model
    from pytorch_distributed_mnist_tpu.train.checkpoint import (
        save_checkpoint,
    )
    from pytorch_distributed_mnist_tpu.train.state import create_train_state

    chunk_mb = float(os.environ.get("BENCH_PUBLISH_CHUNK_MB", "0.25"))
    n_backends = int(os.environ.get("BENCH_PUBLISH_BACKENDS", "3"))
    device = jax.devices()[0]
    out = {
        "metric": "mnist_delta_publish_adjacent_fleet_bytes_fraction",
        "unit": "fraction of whole-file x backends bytes",
        "baseline": "whole-file npz publish copied to every backend",
        "backend": device.platform,
        "device_kind": device.device_kind,
    }
    failures = []
    dirs = [tempfile.mkdtemp(prefix="bench-publish-") for _ in range(3)]
    whole_dir, source_dir, fleet_root = dirs
    backend_dirs = [os.path.join(fleet_root, f"b{i}")
                    for i in range(n_backends)]
    httpd = None
    try:
        model = get_model("linear", compute_dtype=jnp.float32)
        state = create_train_state(model, jax.random.key(7))
        leaves, treedef = jax.tree_util.tree_flatten(state.params)
        small = min(range(len(leaves)), key=lambda j: leaves[j].size)

        def _adjacent(epoch):
            shifted = list(leaves)
            shifted[small] = leaves[small] + epoch * 1e-3
            return state.replace(
                params=jax.tree_util.tree_unflatten(treedef, shifted))

        def _dir_bytes(d):
            chunks = os.path.join(d, "chunks")
            if not os.path.isdir(chunks):
                return 0
            return sum(os.path.getsize(os.path.join(chunks, f))
                       for f in os.listdir(chunks))

        # -- publisher side ---------------------------------------------
        t0 = time.perf_counter()
        save_checkpoint(state, epoch=1, best_acc=0.5, is_best=False,
                        directory=whole_dir, process_index=0)
        whole_s = time.perf_counter() - t0
        whole_path = os.path.join(whole_dir, "checkpoint_1.npz")
        whole_bytes = os.path.getsize(whole_path)

        t0 = time.perf_counter()
        manifest1 = publish_state(state, epoch=1, best_acc=0.5,
                                  directory=source_dir, chunk_mb=chunk_mb,
                                  process_index=0)
        cold_s = time.perf_counter() - t0
        cold_bytes = _dir_bytes(source_dir)

        t0 = time.perf_counter()
        manifest2 = publish_state(_adjacent(2), epoch=2, best_acc=0.5,
                                  directory=source_dir, chunk_mb=chunk_mb,
                                  process_index=0)
        adj_s = time.perf_counter() - t0
        adj_bytes = _dir_bytes(source_dir) - cold_bytes
        publish_ratio = adj_bytes / whole_bytes

        # -- fleet side: loopback gossip over real HTTP -----------------
        seed_store = ChunkStore(backend_dirs[0])

        class _ChunkHandler(BaseHTTPRequestHandler):
            def do_GET(self):  # noqa: N802 - BaseHTTPRequestHandler API
                digest = self.path.rsplit("/", 1)[-1]
                if not seed_store.has(digest):
                    self.send_response(404)
                    self.end_headers()
                    return
                data = seed_store.get(digest)
                self.send_response(200)
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)

            def log_message(self, *a):  # noqa: D102 - quiet bench server
                pass

        httpd = ThreadingHTTPServer(("127.0.0.1", 0), _ChunkHandler)
        httpd.daemon_threads = True
        threading.Thread(target=httpd.serve_forever, daemon=True).start()
        peer_url = f"http://127.0.0.1:{httpd.server_address[1]}"

        # Backend 0 pulls from the source dir and thereby SEEDS the
        # gossip endpoint; the rest list it as their (only) peer with
        # the source dir as fallback — peer-first is then observable as
        # bytes_source == 0 on every non-seed backend.
        fetchers = [DeltaFetcher(backend_dirs[0], source_dir=source_dir)]
        fetchers += [DeltaFetcher(d, peers=(peer_url,),
                                  source_dir=source_dir)
                     for d in backend_dirs[1:]]

        def _fleet_load(path, want_epoch):
            t0 = time.perf_counter()
            for fetcher in fetchers:
                _, epoch = fetcher.load(path, state)
                if epoch != want_epoch:
                    failures.append(
                        f"fetcher returned epoch {epoch}, want "
                        f"{want_epoch} from {path}")
            return time.perf_counter() - t0

        cold_fleet_s = _fleet_load(manifest1, 1)
        cold_fetch_bytes = sum(f.last["bytes_fetched"] for f in fetchers)
        adj_fleet_s = _fleet_load(manifest2, 2)
        adj_fetch_bytes = sum(f.last["bytes_fetched"] for f in fetchers)
        peer_bytes = sum(f.total["bytes_peer"] for f in fetchers[1:])
        source_bytes_nonseed = sum(f.total["bytes_source"]
                                   for f in fetchers[1:])
        dirty = [f.last["dirty_leaves"] for f in fetchers]
        clean = [f.last["clean_leaves"] for f in fetchers]

        fleet_ratio = adj_fetch_bytes / (whole_bytes * n_backends)
        if fleet_ratio >= 0.30:
            failures.append(
                f"adjacent delta fetch moved {adj_fetch_bytes}B to "
                f"{n_backends} backends = {fleet_ratio:.3f} of "
                f"whole-file x backends; the ISSUE 18 bar is < 0.30")
        if peer_bytes <= 0:
            failures.append(
                "gossip never moved a byte: non-seed backends should "
                "fetch from the peer endpoint")
        if source_bytes_nonseed:
            failures.append(
                f"non-seed backends pulled {source_bytes_nonseed}B from "
                f"the source dir despite a complete peer (peers must be "
                f"tried first)")
        if any(d != dirty[0] for d in dirty) or \
                any(c != clean[0] for c in clean):
            failures.append(
                f"backends disagree on the diff: dirty={dirty}, "
                f"clean={clean}")
        if os.environ.get("BENCH_PUBLISH_INJECT_FAIL"):
            # Test hook: pin the fails-loudly path (mirrors
            # BENCH_FLEET_INJECT_FAIL).
            failures.append("BENCH_PUBLISH_INJECT_FAIL set: injected "
                            "publish verdict failure")

        out.update({
            "value": round(fleet_ratio, 5),
            "vs_baseline": round(
                (whole_bytes * n_backends) / max(adj_fetch_bytes, 1), 1),
            "publish": {
                "chunk_mb": chunk_mb,
                "whole_file_bytes": whole_bytes,
                "whole_file_publish_s": round(whole_s, 4),
                "cold_chunk_bytes": cold_bytes,
                "cold_publish_s": round(cold_s, 4),
                "adjacent_new_chunk_bytes": adj_bytes,
                "adjacent_publish_s": round(adj_s, 4),
                "adjacent_publish_bytes_fraction": round(
                    publish_ratio, 5),
            },
            "fleet": {
                "backends": n_backends,
                "cold_fetch_bytes": cold_fetch_bytes,
                "cold_time_to_consistency_s": round(cold_fleet_s, 4),
                "adjacent_fetch_bytes": adj_fetch_bytes,
                "adjacent_time_to_consistency_s": round(adj_fleet_s, 4),
                "adjacent_fleet_bytes_fraction": round(fleet_ratio, 5),
                "gossip_peer_bytes": peer_bytes,
                "non_seed_source_bytes": source_bytes_nonseed,
                "dirty_leaves": dirty[0],
                "clean_leaves": clean[0],
                "delta_under_30pct_of_whole_file": fleet_ratio < 0.30,
            },
            "caveat": (
                "filesystem + loopback HTTP on this host: absolute "
                "publish/fetch times are not a fabric's (the BENCH_r05 "
                "convention) — the byte counts, the ratios, and the "
                "peer-vs-source split are the meaningful part"),
        })
        if failures:
            out["error"] = "; ".join(failures)
    except Exception as exc:  # noqa: BLE001 - bench must always emit JSON
        out.update({"value": 0.0, "vs_baseline": 0.0, "error": repr(exc)})
        failures.append(repr(exc))
    finally:
        if httpd is not None:
            httpd.shutdown()
            httpd.server_close()
        for d in dirs:
            _shutil.rmtree(d, ignore_errors=True)
    out["measured_at"] = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
    print(json.dumps(out))
    if failures:
        sys.exit(1)


def bench_torch_reference() -> float:
    """Reference-style per-batch torch loop (same CNN, Adam), CPU."""
    import torch
    import torch.nn as tnn
    import torch.nn.functional as F

    torch.set_num_threads(max(1, torch.get_num_threads()))

    class Net(tnn.Module):
        def __init__(self):
            super().__init__()
            self.conv1 = tnn.Conv2d(1, 32, 3, padding=1)
            self.conv2 = tnn.Conv2d(32, 64, 3, padding=1)
            self.fc1 = tnn.Linear(64 * 14 * 14, 128)
            self.fc2 = tnn.Linear(128, 10)

        def forward(self, x):
            x = F.relu(self.conv1(x))
            x = F.relu(self.conv2(x))
            x = F.max_pool2d(x, 2)
            x = x.flatten(1)
            return self.fc2(F.relu(self.fc1(x)))

    torch.manual_seed(0)  # same weights/data every run: the baseline-side
    model = Net()         # contribution to vs_baseline stays stable
    opt = torch.optim.Adam(model.parameters(), lr=1e-3)
    bs = 256
    data = torch.randn(bs, 1, 28, 28)
    target = torch.randint(0, 10, (bs,))
    # warmup
    for _ in range(2):
        opt.zero_grad()
        F.cross_entropy(model(data), target).backward()
        opt.step()
    t0 = time.perf_counter()
    for _ in range(TORCH_STEPS):
        opt.zero_grad()
        loss = F.cross_entropy(model(data), target)
        loss.backward()
        opt.step()
        loss.item()  # per-batch host sync, as the reference does (:94)
    dt = time.perf_counter() - t0
    return bs * TORCH_STEPS / dt


def main() -> None:
    result = bench_accelerator()
    out = {
        "metric": "mnist_cnn_train_images_per_sec_per_chip",
        "unit": "images/sec/chip",
        "baseline": "torch-CPU per-batch reference loop, same CNN (PARITY.md)",
    }
    if not result.get("ok"):
        # No chip, or the child failed: the error line, then a non-zero
        # exit — never a CPU or an old number where a device metric goes.
        out["value"] = 0.0
        out["vs_baseline"] = 0.0
        out["error"] = result.get("error", "unknown failure")
        out["measured_at"] = time.strftime(
            "%Y-%m-%dT%H:%M:%SZ", time.gmtime())
        print(json.dumps(out))
        sys.exit(1)
    try:
        baseline = bench_torch_reference()
    except Exception as exc:  # noqa: BLE001 - the ratio is advisory
        baseline = 0.0
        result.setdefault("notes", []).append(f"torch baseline failed: {exc}")

    value = result["images_per_sec_per_chip"]
    out["value"] = round(value, 1)
    out["vs_baseline"] = round(value / baseline, 2) if baseline > 0 else 0.0
    mfu = result.get("mfu")
    out["mfu"] = round(mfu, 4) if mfu is not None else None
    for key in ("backend", "device_kind", "n_chips", "global_batch",
                "steps_per_sec", "flops_per_step", "flops_source",
                "peak_flops_per_chip",
                "images_per_sec_per_chip_fused_kernels",
                "images_per_sec_per_chip_device_gather",
                "images_per_sec_per_chip_device_gather_sorted",
                "compile_cache", "compile_stats", "notes"):
        if result.get(key) is not None:
            val = result[key]
            out[key] = round(val, 2) if isinstance(val, float) else val
    if baseline > 0:
        out["baseline_images_per_sec"] = round(baseline, 1)
    out["measured_at"] = time.strftime(
        "%Y-%m-%dT%H:%M:%SZ", time.gmtime())
    print(json.dumps(out))


if __name__ == "__main__":
    if os.environ.get("BENCH_FORCE_CPU"):
        # The explicit CPU switch, applied before anything imports jax
        # (children inherit it).
        os.environ["JAX_PLATFORMS"] = "cpu"
    if len(sys.argv) > 1 and sys.argv[1] == "--child":
        steps = int(sys.argv[2]) if len(sys.argv) > 2 else 50
        reps = int(sys.argv[3]) if len(sys.argv) > 3 else 3
        try:
            if os.environ.get("BENCH_VIT"):
                result = child_bench_vit(steps, reps)
            else:
                result = child_bench(steps, reps)
        except Exception as exc:  # noqa: BLE001 - parent parses this
            result = {"ok": False, "error": repr(exc)}
        print(json.dumps(result))
        sys.exit(0 if result.get("ok") else 1)
    argv = sys.argv[1:]
    mode = None
    if "--mode" in argv:
        idx = argv.index("--mode")
        # A bare trailing --mode must error, not silently run the
        # multi-minute training bench (empty $MODE in a CI invocation).
        mode = argv[idx + 1] if idx + 1 < len(argv) else "(missing)"
    else:
        mode = next((a.split("=", 1)[1] for a in argv
                     if a.startswith("--mode=")), None)
    if mode == "serve":
        main_serve()
    elif mode == "input":
        main_input()
    elif mode == "zero":
        main_zero()
    elif mode == "publish":
        main_publish()
    elif mode not in (None, "train"):
        print(json.dumps({"error": f"unknown --mode {mode!r}; expected "
                                   f"train, serve, input, zero or "
                                   f"publish"}))
        sys.exit(2)
    elif "--vit" in argv:
        main_vit()
    else:
        main()
