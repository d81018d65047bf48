#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once, through the entry points a user would call:

  trainer   python -m pytorch_distributed_mnist_tpu            (cli.run ->
            Trainer -> train/steps.py): a few steps, one evaluation, a
            checkpoint written, then ``-e --resume`` reading it back —
            * the default ``--model cnn`` with default flags;
            * the widest model the CLI reaches, ``--model vit
              --patch-size 1`` (784 tokens, width 64, 4 heads of 16) with
              every first-party kernel the CLI can select:
              ``--attention flash --loss fused --optimizer adam_pallas``;
            * the token model, ``--model laguna --dataset
              synthetic_tokens`` at its tiny preset on sequences of 1,024
              tokens (8 steps of 8): window and full attention through
              the flash kernels, top-4 of 16 experts by grouped matmuls;
            * the state-space hybrid, ``--model sambay --dataset
              synthetic_tokens`` at its tiny preset on the same sequences:
              two selective scans, differential attention (window, full
              and cross) through the flash kernels, a Gated Memory Unit;
            * the latent-attention sparse decoder, ``--model instella
              --dataset synthetic_tokens`` at its tiny preset on the same
              sequences: latent attention through the flash kernels,
              top-4 of 16 experts under a selection bias the step moves
              (the resumed evaluation reads the bias from the
              checkpoint), the multi-token-prediction module;
            * the Mamba-2 hybrid, ``--model granite_hybrid --dataset
              synthetic_tokens`` at its tiny preset on the same sequences:
              three chunked state-space scans of four chunks (the kernel
              pair ``ssd_fwd`` and ``ssd_bwd``), position-free attention
              through the flash kernels, the four multipliers;
  server    python -m pytorch_distributed_mnist_tpu serve      (server ->
            engine -> batcher -> pool) on the checkpoint the trainer just
            wrote, answering ``tools/loadgen.py --smoke`` and a batch of
            known images, on the default fused plane and once with
            ``--serve-precision int8`` (the only route to the int8 Pallas
            matmul); SIGTERM must end it with exit 0.

Weights are random from a seed plus a few steps on ``--dataset synthetic``
(no network). Served replies are checked against a plain float32
``model.apply`` of the same checkpoint on the same preprocessing (the
check tests/test_serve_server.py makes), inside the logit bounds
tests/test_serve_precision.py holds the planes to.

This process imports neither jax nor the package: a parent that touched jax
would hold the chip, and a child that needs it would fail or hang. Every
phase is a child process; phases run one at a time and a child has exited
before the next starts. (``--reference`` is this same file run AS a child.)

Exit 0 and a last stdout line ``{"ok": true, "device": {...}}`` only when
every phase passed on TPU devices with no Pallas call interpreted and no
precompile fallback. With no chip it fails; it never passes on the CPU.
"""

from __future__ import annotations

import json
import os
import re
import shutil
import signal
import subprocess
import sys
import tempfile
import time
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
DEADLINE_S = 1100.0  # of the 1200 s the contract allows, compilation included
PHASE_CAP_S = 420.0

# Depth is cut by size flags only; every path flag stays at its default.
# 32 steps are enough that the CNN's logits separate (the reply check
# needs margins wider than bf16 rounding). BATCH is the CLI's default
# --batch-size, which divides by 1, 4 and 8 devices.
TRAIN_IMAGES, BATCH = 8192, 256
DATA = ["--dataset", "synthetic", "--synthetic-train-size", str(TRAIN_IMAGES),
        "--synthetic-test-size", "1024", "--epochs", "1", "--seed", "1"]
VIT = ["--model", "vit", "--patch-size", "1", "--attention", "flash",
       "--loss", "fused", "--optimizer", "adam_pallas"]
# Later flags win: the token model's data replaces DATA's.
LAGUNA_STEPS, LAGUNA_BATCH = 8, 8
LAGUNA = ["--model", "laguna", "--dataset", "synthetic_tokens",
          "--seq-len", "1024", "--batch-size", str(LAGUNA_BATCH),
          "--synthetic-train-size", str(LAGUNA_STEPS * LAGUNA_BATCH),
          "--synthetic-test-size", "16", "--lr", "1e-3"]
SAMBAY = ["--model", "sambay"] + LAGUNA[2:]
INSTELLA = ["--model", "instella"] + LAGUNA[2:]
GRANITE = ["--model", "granite_hybrid"] + LAGUNA[2:]
# The trainer's own warnings that a compiled program was refused or
# compiled twice (train/trainer.py): legitimate on a user's machine,
# a failure here.
PRECOMPILE_FALLBACK = ("precompile of", "no longer matches")
# Largest served-logit error each plane is held to, as a fraction of the
# logit scale (tests/test_serve_precision.py: bf16 compute 0.02, int8 0.15).
LOGIT_BOUND = {"f32": 0.02, "int8": 0.15}
REFERENCE_IMAGES = 128


class SmokeFailure(Exception):
    pass


class Smoke:
    def __init__(self) -> None:
        self.t0 = time.monotonic()
        self.work = tempfile.mkdtemp(prefix="chip_smoke-")
        self.logs = os.path.join(HERE, "chiprun_out", "chip_smoke")
        self.procs: list = []
        self.devices: list = []
        self.cache_dirs: set = set()

    # -- children ----------------------------------------------------------

    def remaining(self) -> float:
        left = DEADLINE_S - (time.monotonic() - self.t0)
        if left <= 0:
            raise SmokeFailure(f"out of time ({DEADLINE_S:.0f} s budget)")
        return min(left, PHASE_CAP_S)

    def spawn(self, name: str, argv: list) -> tuple:
        os.makedirs(self.logs, exist_ok=True)
        log_path = os.path.join(self.logs, f"{name}.log")
        log = open(log_path, "w")
        try:
            proc = subprocess.Popen(
                [sys.executable] + argv, cwd=HERE, stdout=log,
                stderr=subprocess.STDOUT, start_new_session=True)
        finally:
            log.close()
        self.procs.append(proc)
        return proc, log_path

    def run(self, name: str, argv: list, uses_jax: bool = True) -> str:
        """One child to completion; its combined output."""
        proc, log_path = self.spawn(name, argv)
        try:
            rc = proc.wait(timeout=self.remaining())
        except subprocess.TimeoutExpired:
            self.kill(proc)
            raise SmokeFailure(f"{name}: timed out\n{_tail(log_path)}")
        text = _read(log_path)
        if rc != 0:
            raise SmokeFailure(f"{name}: exit code {rc}\n{_tail(log_path)}")
        if uses_jax:
            self.note_cache_dir(name, text)
        return text

    def kill(self, proc) -> None:
        if proc.poll() is None:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            proc.wait()

    def close(self) -> None:
        for proc in self.procs:
            self.kill(proc)
        shutil.rmtree(self.work, ignore_errors=True)

    # -- checks shared by every phase --------------------------------------

    def note_cache_dir(self, name: str, text: str) -> None:
        found = set(re.findall(r"^compile cache: (.+)$", text, re.M))
        wanted = os.environ.get("JAX_COMPILATION_CACHE_DIR")
        if wanted and found != {wanted}:
            raise SmokeFailure(
                f"{name}: JAX_COMPILATION_CACHE_DIR={wanted!r} but the "
                f"child logged compile cache dir(s) {sorted(found)}")
        self.cache_dirs |= found

    def check_device(self, name: str, report: dict) -> None:
        """``report`` carries platform/device_kind/device_count/
        input_backend/pallas_lowerings as the child's own interface gave
        them (the run summary row, or /healthz)."""
        if report.get("platform") != "tpu":
            raise SmokeFailure(
                f"{name}: ran on platform {report.get('platform')!r} "
                f"({report.get('device_kind')!r}), not a TPU")
        lowered = report["pallas_lowerings"]
        if lowered["interpret"]:
            raise SmokeFailure(
                f"{name}: {lowered['interpret']} Pallas call(s) were "
                f"lowered in interpret mode on a TPU")
        self.devices.append((report["platform"], report["device_kind"],
                             report["device_count"]))

    def say(self, name: str, report: dict, totals: dict, extra: str) -> None:
        lowered = report["pallas_lowerings"]
        print(f"[{time.monotonic() - self.t0:6.1f}s] {name}: ok  "
              f"platform={report['platform']} "
              f"device_kind={report['device_kind']!r} "
              f"devices={report['device_count']} "
              f"compiled={totals['backend_compiles']} "
              f"cache_hits={totals['cache_hits']} "
              f"cache_misses={totals['cache_misses']} "
              f"input={report['input_backend']} "
              f"pallas=mosaic:{lowered['mosaic']}/"
              f"interpret:{lowered['interpret']}  {extra}", flush=True)

    # -- trainer -----------------------------------------------------------

    def train(self, name: str, model_flags: list, kernels: bool,
              steps: int = TRAIN_IMAGES // BATCH) -> dict:
        """Train, then ``-e --resume`` the checkpoint it wrote; returns
        the checkpoint dir and the eval it must reproduce."""
        ckpt = os.path.join(self.work, name)
        common = DATA + model_flags + [
            "--root", os.path.join(self.work, "data"),
            "--checkpoint-dir", ckpt]
        entry = ["-m", "pytorch_distributed_mnist_tpu"]

        metrics = os.path.join(self.work, f"{name}.train.jsonl")
        text = self.run(f"train_{name}",
                        entry + common + ["--metrics-file", metrics])
        rows = _jsonl(metrics)
        summary = _one(rows, "run_summary", f"train_{name}")
        epochs = [r for r in rows if "epoch" in r and "train_loss" in r]
        self.check_device(f"train_{name}", summary)
        for needle in PRECOMPILE_FALLBACK:
            if needle in text:
                raise SmokeFailure(
                    f"train_{name}: the trainer fell back from its "
                    f"precompiled program ({needle!r})\n"
                    + "\n".join(l for l in text.splitlines() if needle in l))
        if summary["epochs_run"] != 1 or len(epochs) != 1:
            raise SmokeFailure(f"train_{name}: expected 1 epoch, got "
                               f"{summary['epochs_run']}")
        row = epochs[0]
        for key in ("train_loss", "test_loss", "train_acc", "test_acc"):
            if not (row[key] == row[key] and abs(row[key]) < 1e6):
                raise SmokeFailure(f"train_{name}: {key}={row[key]}")
        if kernels and not summary["pallas_lowerings"]["mosaic"]:
            raise SmokeFailure(
                f"train_{name}: the kernel flags lowered no Pallas call")
        saved = os.path.join(ckpt, "checkpoint_0.npz")
        if not os.path.isfile(saved):
            raise SmokeFailure(f"train_{name}: no checkpoint at {saved}")
        programs = summary["compile_stats"]["programs"]
        self.say(f"train_{name}", summary, summary["compile_stats"]["totals"],
                 f"steps={steps} "
                 f"train_loss={row['train_loss']:.4f} "
                 f"test_acc={row['test_acc']:.4f} programs="
                 + ",".join(f"{p}:{_hit(r)}" for p, r in programs.items()))

        metrics = os.path.join(self.work, f"{name}.resume.jsonl")
        self.run(f"resume_{name}", entry + common + [
            "-e", "--resume", saved, "--metrics-file", metrics])
        resumed = _one(_jsonl(metrics), "run_summary", f"resume_{name}")
        self.check_device(f"resume_{name}", resumed)
        if resumed["start_epoch"] != 1:
            raise SmokeFailure(f"resume_{name}: checkpoint not read back "
                               f"(start_epoch {resumed['start_epoch']})")
        if resumed["test_acc"] != row["test_acc"] \
                or abs(resumed["test_loss"] - row["test_loss"]) > 1e-5:
            raise SmokeFailure(
                f"resume_{name}: the resumed evaluation "
                f"(loss {resumed['test_loss']}, acc {resumed['test_acc']}) "
                f"does not reproduce the trained one "
                f"(loss {row['test_loss']}, acc {row['test_acc']})")
        self.say(f"resume_{name}", resumed,
                 resumed["compile_stats"]["totals"],
                 f"test_acc={resumed['test_acc']:.4f} (== trained)")
        return {"dir": ckpt, "path": saved}

    # -- reference ---------------------------------------------------------

    def reference(self, ckpt: dict) -> dict:
        out = os.path.join(self.work, "reference.json")
        self.run("reference", [os.path.abspath(__file__), "--reference",
                               ckpt["path"], out])
        with open(out) as f:
            ref = json.load(f)
        self.check_device("reference", ref)
        flat = [v for row in ref["logits"] for v in row]
        if len(ref["logits"]) != REFERENCE_IMAGES \
                or any(len(row) != 10 for row in ref["logits"]) \
                or not all(v == v and abs(v) < 1e6 for v in flat):
            raise SmokeFailure("reference: logits are not finite "
                               f"({REFERENCE_IMAGES}, 10)")
        print(f"[{time.monotonic() - self.t0:6.1f}s] reference: ok  "
              f"platform={ref['platform']} float32 model.apply on "
              f"{REFERENCE_IMAGES} images, logit scale "
              f"{max(abs(v) for v in flat):.2f}", flush=True)
        return ref

    # -- server ------------------------------------------------------------

    def serve(self, ckpt: dict, ref: dict, precision: str) -> None:
        name = f"serve_{precision}"
        flags = ["--checkpoint-dir", ckpt["dir"], "--model", "cnn",
                 "--port", "0", "--require-checkpoint"]
        expect = ["--expect-fused"]
        if precision != "f32":
            flags += ["--serve-precision", precision]
            expect += ["--expect-precision", precision]
        proc, log_path = self.spawn(
            name, ["-m", "pytorch_distributed_mnist_tpu", "serve"] + flags)
        url = self.wait_for_server(name, proc, log_path)

        self.run(f"{name}.loadgen", [
            os.path.join("tools", "loadgen.py"), "--smoke", "--url", url,
            "--requests", "64", "--concurrency", "4"] + expect,
            uses_jax=False)

        health = _get(url + "/healthz")
        self.check_device(name, health)
        if precision == "int8" and not health["pallas_lowerings"]["mosaic"]:
            raise SmokeFailure(f"{name}: the int8 plane lowered no Pallas "
                               f"call (matmul_i8 not reached)")
        worst, agree = self.check_replies(name, url, ref, precision)
        stats = _get(url + "/stats")

        proc.send_signal(signal.SIGTERM)
        try:
            rc = proc.wait(timeout=min(60.0, self.remaining()))
        except subprocess.TimeoutExpired:
            self.kill(proc)
            raise SmokeFailure(f"{name}: still running 60 s after SIGTERM")
        if rc != 0:
            raise SmokeFailure(f"{name}: exit code {rc} after SIGTERM, "
                               f"expected 0\n{_tail(log_path)}")
        self.note_cache_dir(name, _read(log_path))
        self.say(name, health, stats["compile"]["totals"],
                 f"requests={stats['requests']} replies_checked="
                 f"{REFERENCE_IMAGES} agreement={agree:.3f} "
                 f"worst_logit_gap={worst:.4f} sigterm_exit=0")

    def wait_for_server(self, name: str, proc, log_path: str) -> str:
        limit = time.monotonic() + self.remaining()
        while time.monotonic() < limit:
            found = re.search(r"serving on (http://\S+)", _read(log_path))
            if found:
                return found.group(1)
            if proc.poll() is not None:
                raise SmokeFailure(f"{name}: the server exited with code "
                                   f"{proc.returncode} before it served\n"
                                   f"{_tail(log_path)}")
            time.sleep(0.5)
        self.kill(proc)
        raise SmokeFailure(f"{name}: never started serving\n"
                           f"{_tail(log_path)}")

    def check_replies(self, name: str, url: str, ref: dict,
                      precision: str) -> tuple:
        """A served label is right when the reference logit of the class
        it names is within twice the plane's logit error bound of the
        reference's best: an error of at most ``bound`` on each of two
        logits can reorder them only when they are closer than that."""
        logits = ref["logits"]
        scale = max(1.0, max(abs(v) for row in logits for v in row))
        tol = 2 * LOGIT_BOUND[precision] * scale
        got = []
        for i in range(0, REFERENCE_IMAGES, 8):  # 8 rows = one bucket
            reply = _post(url + "/predict",
                          {"images": ref["images"][i:i + 8]})
            got += reply["predictions"]
        if len(got) != REFERENCE_IMAGES:
            raise SmokeFailure(f"{name}: {len(got)} predictions for "
                               f"{REFERENCE_IMAGES} images")
        worst, agree = 0.0, 0
        for i, (row, label) in enumerate(zip(logits, got)):
            if not isinstance(label, int) or not 0 <= label < len(row):
                raise SmokeFailure(f"{name}: image {i}: reply {label!r}")
            gap = max(row) - row[label]
            worst = max(worst, gap)
            agree += gap == 0.0
            if gap > tol:
                raise SmokeFailure(
                    f"{name}: image {i} answered class {label} whose "
                    f"reference logit is {gap:.4f} below the best; the "
                    f"{precision} plane is allowed {tol:.4f}")
        return worst, agree / REFERENCE_IMAGES


# -- small stdlib helpers ----------------------------------------------------


def _read(path: str) -> str:
    with open(path, errors="replace") as f:
        return f.read()


def _tail(path: str, lines: int = 40) -> str:
    return "\n".join("    | " + l for l in _read(path).splitlines()[-lines:])


def _jsonl(path: str) -> list:
    if not os.path.isfile(path):
        return []
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def _one(rows: list, kind: str, name: str) -> dict:
    found = [r for r in rows if r.get("kind") == kind]
    if len(found) != 1:
        raise SmokeFailure(f"{name}: {len(found)} {kind!r} rows in its "
                           f"--metrics-file, expected 1")
    return found[0]


def _hit(record: dict) -> str:
    hit = record["persistent_cache_hit"]
    return "off" if hit is None else "hit" if hit else "miss"


def _get(url: str) -> dict:
    with urllib.request.urlopen(url, timeout=30) as reply:
        return json.load(reply)


def _post(url: str, payload: dict) -> dict:
    request = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(request, timeout=60) as reply:
        return json.load(reply)


# -- the reference child -----------------------------------------------------


def reference_main(checkpoint: str, out_path: str) -> None:
    """Runs AS A CHILD (it imports jax and the package): a plain float32
    ``model.apply`` of the checkpoint on seeded images, same preprocessing
    as serving — the forward pass tests/test_serve_server.py compares
    replies with. Writes images, logits and the device report."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pytorch_distributed_mnist_tpu.data.mnist import (
        normalize_images,
        synthetic_dataset,
    )
    from pytorch_distributed_mnist_tpu.models import get_model
    from pytorch_distributed_mnist_tpu.train.checkpoint import (
        load_checkpoint,
    )
    from pytorch_distributed_mnist_tpu.train.state import create_train_state
    from pytorch_distributed_mnist_tpu.utils import compile_cache
    from pytorch_distributed_mnist_tpu.utils.profiling import device_report

    cache_dir = compile_cache.configure()
    if cache_dir:
        print(f"compile cache: {cache_dir}", flush=True)
    model = get_model("cnn", compute_dtype=jnp.float32)
    state = create_train_state(model, jax.random.key(0))
    state, _, _ = load_checkpoint(checkpoint, state)
    images, _ = synthetic_dataset(REFERENCE_IMAGES, seed=7)
    with jax.default_matmul_precision("highest"):
        logits = model.apply(state.params,
                             jnp.asarray(normalize_images(images)),
                             train=False)
    report = {
        **device_report(),
        "images": images.tolist(),
        "logits": np.asarray(logits, np.float32).tolist(),
    }
    with open(out_path, "w") as f:
        json.dump(report, f)


# -- the parent ----------------------------------------------------------------


def main() -> int:
    requested = os.environ.get("JAX_PLATFORMS", "")
    if requested and "tpu" not in [p.strip() for p in requested.split(",")]:
        # Decided from the environment alone, before any child is paid
        # for: this selection cannot reach a TPU.
        print(f"chip_smoke: FAILED: JAX_PLATFORMS={requested!r} selects "
              f"platform {requested.split(',')[0].strip()!r}; this smoke "
              f"needs a TPU and never passes on the CPU", file=sys.stderr)
        return 1
    smoke = Smoke()
    try:
        cnn = smoke.train("cnn", [], kernels=False)
        smoke.train("vit", VIT, kernels=True)
        smoke.train("laguna", LAGUNA, kernels=True, steps=LAGUNA_STEPS)
        smoke.train("sambay", SAMBAY, kernels=True, steps=LAGUNA_STEPS)
        smoke.train("instella", INSTELLA, kernels=True, steps=LAGUNA_STEPS)
        smoke.train("granite_hybrid", GRANITE, kernels=True,
                    steps=LAGUNA_STEPS)
        ref = smoke.reference(cnn)
        smoke.serve(cnn, ref, "f32")
        smoke.serve(cnn, ref, "int8")
        if len(set(smoke.devices)) != 1:
            raise SmokeFailure(f"phases disagree about the device: "
                               f"{sorted(set(smoke.devices))}")
    except SmokeFailure as exc:
        print(f"chip_smoke: FAILED: {exc}", file=sys.stderr)
        return 1
    finally:
        smoke.close()
    platform, kind, count = smoke.devices[0]
    print(f"compile cache: {', '.join(sorted(smoke.cache_dirs)) or 'off'}; "
          f"child logs: {os.path.relpath(smoke.logs, HERE)}/", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": count}}), flush=True)
    return 0


if __name__ == "__main__":
    if len(sys.argv) == 4 and sys.argv[1] == "--reference":
        reference_main(sys.argv[2], sys.argv[3])
        sys.exit(0)
    # SIGTERM takes the same path as Ctrl-C, so `finally` stops every
    # child this process started.
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    sys.exit(main())
