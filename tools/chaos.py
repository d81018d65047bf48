#!/usr/bin/env python
"""Fault-injection (chaos) harness for the run-supervision subsystem.

Drives the same local N-process world as ``tpu-mnist --spawn`` with ONE
process sabotaged at a named fault point (``runtime/supervision.py``'s
``TPUMNIST_FAULT=point:host:kind[:arg]`` hook, comma-join for multiple
faults), so the agreed-exit protocol, the collective watchdogs, and the
elastic shrink/grow runtime can be exercised against real process
deaths instead of monkeypatches:

    # what can be injected, and where each point fires
    python tools/chaos.py --list

    # SIGKILL host 0 right before the checkpoint publish agreement;
    # host 1 must exit with PeerFailure within the deadline, not hang
    python tools/chaos.py --fault ckpt_publish:0:kill --nprocs 2 \\
        --agreement-timeout 10 -- \\
        --dataset synthetic --model linear --epochs 2 \\
        --optimizer-sharding zero1 --trainer-mode stepwise

    # then prove recovery: the same world, no fault, resumes
    python tools/chaos.py --nprocs 2 -- --dataset synthetic \\
        --model linear --epochs 2 --optimizer-sharding zero1 \\
        --trainer-mode stepwise --resume auto

    # ELASTIC: kill host 1 mid-run and watch the world SHRINK instead
    # of exit — the survivor is re-execed as a 1-host world resumed
    # from the last published checkpoint and trains to completion
    python tools/chaos.py --elastic --fault train_epoch:1:kill:1 \\
        --nprocs 2 -- --dataset synthetic --model linear --epochs 3 \\
        --optimizer-sharding zero1 --trainer-mode stepwise

    # mid-REBUILD second failure: host 2 dies, then host 1 stalls while
    # writing its survivor record — the supervisor's settle deadline
    # kills the straggler and the world shrinks to host 0 alone
    python tools/chaos.py --elastic --min-world 1 --nprocs 3 \\
        --fault "resume:2:kill,elastic_rebuild:1:stall" -- \\
        --dataset synthetic --model linear --epochs 3 --batch-size 48 \\
        --trainer-mode stepwise --resume auto

    # GROW (2 -> 1 -> 2): host 1 dies mid-epoch, the world shrinks to
    # host 0; --rejoin 1@1 then writes host 1's join record while
    # generation 1 runs, the next epoch-boundary grow rendezvous admits
    # it, and the job finishes back at world size 2
    python tools/chaos.py --elastic --elastic-grow --rejoin 1@1 \\
        --fault train_step:1:kill:5 --nprocs 2 -- \\
        --dataset synthetic --model linear --epochs 3 \\
        --optimizer-sharding zero1 --trainer-mode stepwise

    # SLICE LOSS on the emulated hierarchical mesh: the 2-host world
    # runs as 2 DCN slices x 1 host; killing every host of slice 1
    # shrinks it to the surviving slice, whose 1-host world the slice
    # count no longer divides — it lands on the FLAT mesh (cli.py's
    # elastic fallback) and resumes via the ordinary (W, W') reshard
    python tools/chaos.py --elastic --dcn-slices 2 --kill-slice 1 \\
        --nprocs 2 -- --dataset synthetic --model linear --epochs 3 \\
        --optimizer-sharding zero1 --trainer-mode stepwise

    # SERVE-POOL self-healing: boot a real 4-replica server, 'kill'
    # group 1 after 5 batches (TPUMNIST_SERVE_FAULT injection), hammer
    # it with loadgen — every request must answer 200 (failover, never
    # a drop), the pool must quarantine + regroup, and the final smoke
    # asserts all 4 groups active again
    python tools/chaos.py --serve --serve-devices 4 --serve-fault 0:5 \\
        --expect-groups 4 --requests 400 --cpu-devices 4

    # rolling topology change: /resize 2 -> 4 -> 2 replicas under live
    # traffic; zero dropped requests end to end
    python tools/chaos.py --serve --serve-devices 2 --resize 4,2 \\
        --expect-groups 2 --requests 400 --cpu-devices 4

    # AUTOSCALER: spike load against a 1-device pool with --autoscale.
    # Phase 1 (dry run) asserts the controller DECIDED to scale up
    # without touching the topology; phase 2 asserts the real resize
    # up during the spike and back down after it — zero dropped
    # in-flight requests, Retry-After on every shed
    python tools/chaos.py --autoscale-spike --cpu-devices 2

    # QUOTA ABUSE: one hot client at 10x --quota-rps is clipped with
    # 429 + Retry-After while the well-behaved client keeps >= 90%
    # goodput — one abuser cannot starve the rest
    python tools/chaos.py --quota-abuse --cpu-devices 2 --quota-rps 20

    # FLEET: a real router over 3 real backends; SIGKILL backend 1
    # mid-loadgen — zero DROPPED requests (failover + bounded client
    # retry), quarantine, then probation re-admission after a restart
    python tools/chaos.py --fleet 3 --kill-backend 1 --cpu-devices 1

    # fleet-wide rolling deploy under live traffic: every backend on
    # the new epoch, zero drops
    python tools/chaos.py --fleet 3 --rolling-reload --cpu-devices 1

    # a publish that fails the fleet canary rolls back with the
    # baseline weights republished and still serving
    python tools/chaos.py --fleet 2 --fleet-canary-rollback \\
        --cpu-devices 1

    # DELTA DISTRIBUTION: 3 backends watch one shared checkpoint dir;
    # 3 adjacent delta publishes under live loadgen — zero drops,
    # every backend converges, and each publish's new chunk bytes are
    # a tiny fraction of the cold (whole-state) publish
    python tools/chaos.py --fleet 3 --delta-publish 3 --cpu-devices 1

    # torn publish: a half-written manifest, then a manifest with a
    # missing chunk, then a clean one — skipped, skipped, recovered;
    # serving never stops through any of it
    python tools/chaos.py --torn-manifest --cpu-devices 1

Fault host indices are process RANKS within the world that reads the
plan — in an elastic run each rebuilt generation renumbers its ranks
0..W'-1, so a spec aimed at rank 2 cannot re-fire once the world is
smaller than 3 (the usual way to target "the first failure only").
For a shrink to happen the survivors must reach a HOST-side failure
(an agreement, or a transport error): at 3+ ranks a kill mid-device-
program parks the others in a timeout-less gloo collective — bounded
by the supervisor's settle deadline, but recordless ranks count dead
(the residual-hazard boundary in docs/DESIGN.md) — so aim elastic
faults at supervised phases (resume, ckpt_*) on worlds above 2.

Exit code: 0 when every rank exited 0 (for elastic runs: the job
trained to completion on whatever world remained; for serve runs: zero
dropped requests AND the expected post-heal topology); otherwise the
first failing rank's code (killed ranks surface as 128+signal; an
elastic shrink past --min-world exits the supervisor's floor code).
tests/test_chaos.py, tests/test_elastic_chaos.py, and
tests/test_serve_heal_server.py run these scenarios with assertions;
this tool is the operator-facing way to reproduce one interactively.

``--list`` is the drift gate: tests/test_supervision.py pins that its
output, the ``FAULT_POINTS`` registry, and the ``maybe_fault()`` call
sites in the source all agree — a hook added without registry+docs (or
vice versa) fails the suite.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import re
import shutil
import subprocess
import sys
import tempfile
import time
import urllib.request

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

from pytorch_distributed_mnist_tpu.parallel.launcher import (  # noqa: E402
    spawn_local,
)
from pytorch_distributed_mnist_tpu.runtime.elastic import (  # noqa: E402
    supervise,
)
from pytorch_distributed_mnist_tpu.runtime.supervision import (  # noqa: E402
    FAULT_ENV,
    FAULT_POINTS,
    TIMEOUT_ENV,
    parse_fault_specs,
)

# serve/pool.py::SERVE_FAULT_ENV, spelled out so the chaos CLI stays
# jax-import-free until a twin actually runs (pinned equal by
# tests/test_serve_heal_server.py).
SERVE_FAULT_ENV = "TPUMNIST_SERVE_FAULT"

# serve/canary.py::CANARY_FAULT_ENV, spelled out for the same
# jax-import-free reason (pinned equal by tests/test_serve_canary.py):
# the --canary-rollback twin sets it to "disagree" so every shadow
# comparison fails the budget.
CANARY_FAULT_ENV = "TPUMNIST_CANARY_FAULT"

# serve/router.py::FLEET_FAULT_ENV, spelled out for the same
# jax-import-free reason (pinned equal by tests/test_serve_router.py):
# the --fleet-canary-rollback twin sets it to "canary_disagree" in the
# ROUTER's environment so every fleet-canary cohort row disagrees.
FLEET_FAULT_ENV = "TPUMNIST_FLEET_FAULT"

# parallel/mesh.py::DCN_SLICES_ENV, spelled out for the same
# jax-import-free reason (pinned equal by tests/test_hier_mesh.py).
DCN_SLICES_ENV = "TPUMNIST_DCN_SLICES"


def list_fault_points(file=sys.stdout) -> None:
    """One line per injectable point: ``name<TAB>description``."""
    for name in sorted(FAULT_POINTS):
        print(f"{name}\t{FAULT_POINTS[name]}", file=file)


def _parse_rejoin(spec: str):
    """``HOST@GEN[,HOST@GEN...]`` -> [(host, generation), ...]."""
    out = []
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        try:
            host_s, gen_s = part.split("@")
            out.append((int(host_s), int(gen_s)))
        except ValueError:
            raise SystemExit(
                f"bad --rejoin spec {part!r}: expected HOST@GENERATION "
                f"(e.g. 1@1: host 1 announces a join while generation 1 "
                f"runs)") from None
    return out


def _get_json(url: str, path: str, timeout: float = 10.0) -> dict:
    with urllib.request.urlopen(url + path, timeout=timeout) as resp:
        return json.loads(resp.read())


def _post_json(url: str, path: str, payload: dict,
               timeout: float = 120.0) -> dict:
    req = urllib.request.Request(
        url + path, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


def _say(msg: str) -> None:
    print(f"chaos: {msg}", file=sys.stderr, flush=True)


def _force_cpu_devices(env: dict, cpu_devices: int) -> None:
    """``--cpu-devices N``: the twin's server runs on N virtual CPU
    devices — a CPU simulation of an N-chip host, announced on stdout
    so its numbers are never read as a chip's."""
    if not cpu_devices:
        return
    env["JAX_PLATFORMS"] = "cpu"
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   env.get("XLA_FLAGS", "")).strip()
    env["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_"
                        f"count={cpu_devices}").strip()
    print(f"chaos: CPU simulation — the server runs on {cpu_devices} "
          f"virtual CPU device(s) (JAX_PLATFORMS=cpu); no accelerator is "
          f"used", flush=True)


def _serve_env(args) -> dict:
    """Environment for a serve-twin subprocess (CPU device forcing +
    unbuffered + repo on path)."""
    env = dict(os.environ)
    _force_cpu_devices(env, args.cpu_devices)
    env["PYTHONUNBUFFERED"] = "1"
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


def _boot_serve(env: dict, flags: list, timeout: float,
                ckpt_dir: str = None, port: int = 0):
    """Boot one `tpu-mnist serve` subprocess on a fresh-init checkpoint
    dir; returns ``(server, log, ckpt_dir, url)`` (url None = never came
    up; caller prints the log tail and bails). Caller owns teardown.
    ``ckpt_dir``/``port`` let the fleet twins RESTART a killed backend
    on its old port with its old checkpoints (the re-admission leg)."""
    if ckpt_dir is None:
        ckpt_dir = tempfile.mkdtemp(prefix="tpumnist-serve-chaos-")
    log = tempfile.NamedTemporaryFile(mode="w+", suffix=".log",
                                      delete=False)
    cmd = [sys.executable, "-m", "pytorch_distributed_mnist_tpu", "serve",
           "--checkpoint-dir", ckpt_dir, "--host", "127.0.0.1",
           "--port", str(port)] + flags
    _say(f"booting serve twin: {' '.join(cmd)}")
    server = subprocess.Popen(cmd, env=env, stdout=log,
                              stderr=subprocess.STDOUT)
    url = None
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline and url is None:
        if server.poll() is not None:
            break
        log.flush()
        with open(log.name) as f:
            m = re.search(r"serving on (http://\S+)", f.read())
        if m:
            url = m.group(1).rstrip("/")
        else:
            time.sleep(0.2)
    if url is None:
        with open(log.name) as f:
            print(f.read()[-4000:], file=sys.stderr)
        _say("server never came up")
    return server, log, ckpt_dir, url


def _kill_serve(server, log, ckpt_dir) -> None:
    server.kill()
    server.wait()
    log.close()
    os.unlink(log.name)
    shutil.rmtree(ckpt_dir, ignore_errors=True)


def _boot_router(env: dict, backend_urls: list, timeout: float,
                 extra_flags: list = ()):
    """Boot one `tpu-mnist route` subprocess over the given backends;
    returns ``(router, log, url)`` (url None = never came up). Tight
    health cadence on purpose: the twins want quarantine/probation
    transitions inside their wall-clock budget, not production's."""
    log = tempfile.NamedTemporaryFile(mode="w+", suffix=".log",
                                      delete=False)
    cmd = [sys.executable, "-m", "pytorch_distributed_mnist_tpu", "route",
           "--backends", ",".join(u.split("//")[-1] for u in backend_urls),
           "--host", "127.0.0.1", "--port", "0",
           "--health-interval", "0.2", "--quarantine-after", "2",
           "--probation-successes", "2",
           "--connect-timeout", "2.0"] + list(extra_flags)
    _say(f"booting router: {' '.join(cmd)}")
    router = subprocess.Popen(cmd, env=env, stdout=log,
                              stderr=subprocess.STDOUT)
    url = None
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline and url is None:
        if router.poll() is not None:
            break
        log.flush()
        with open(log.name) as f:
            m = re.search(r"routing on (http://\S+)", f.read())
        if m:
            url = m.group(1).rstrip("/")
        else:
            time.sleep(0.2)
    if url is None:
        with open(log.name) as f:
            print(f.read()[-4000:], file=sys.stderr)
        _say("router never came up")
    return router, log, url


def _communicate_reaped(proc: subprocess.Popen, timeout: float):
    """``communicate()`` that cannot orphan: on a timeout expiry — or
    any other failure — the child is killed and waited before the error
    propagates. The original shape reaped only on the happy path, and a
    ``TimeoutExpired`` left an orphan loadgen hammering a server the
    twin was about to kill (the PR 10 incident; thread-lifecycle pins
    this)."""
    try:
        return proc.communicate(timeout=timeout)
    except BaseException:
        proc.kill()
        proc.wait()
        raise


def _loadgen_report(proc_out: str) -> dict:
    line = proc_out.strip().splitlines()[-1] if proc_out.strip() else "{}"
    print(line)
    return json.loads(line)


def _sends(report: dict) -> int:
    """Requests the loadgen actually launched: every status code plus
    transport errors (open-loop sends it could not launch count there
    too — nothing is silently skipped)."""
    return (sum(report.get("status_counts", {}).values())
            + report.get("transport_errors", 0))


def run_autoscale_spike(args) -> int:
    """The autoscaler twin (ISSUE 15): spike load must trigger a
    scale-up — FIRST proven in dry-run (the decision log fills, the
    topology does NOT move), THEN for real (the pool resizes up under
    the spike and back down after it, with zero dropped in-flight
    requests). Two server boots on purpose: the dry-run assertion is
    worthless if the same process already resized."""
    env = _serve_env(args)
    # cnn by default: its CPU forward is slow enough that an 8x spike
    # genuinely backs the queue up (linear answers 500 rps from one
    # device — nothing to scale for). --stats-window-s 5 so the
    # controller's p95 reflects the LAST seconds, not the whole run —
    # the post-spike calm must become visible within the twin's budget.
    model = args.serve_model if args.serve_model != "linear" else "cnn"
    # Buckets capped at 4: micro-batching otherwise absorbs an 8x spike
    # whole (a bucket-32 cnn batch amortizes to ~500 rps/device) and
    # there is nothing to scale for. With the cap, the spike genuinely
    # backs the queue up, so the breach fires on BOTH signals — queue
    # depth immediately, window p95 a beat later.
    base_flags = [
        "--model", model, "--buckets", "1,4",
        "--serve-devices", "1", "--max-inflight", "2",
        "--max-wait-ms", "2", "--max-queue", "64",
        "--poll-interval", "5", "--stats-window-s", "5",
        "--autoscale", "--slo-p95-ms", str(args.slo_p95_ms),
        "--autoscale-interval-s", "0.3",
        "--autoscale-cooldown-s", "1.5",
        "--autoscale-down-after", "3",
        "--autoscale-max-devices", "2",
    ]
    loadgen_spike = [
        sys.executable, os.path.join(_REPO, "tools", "loadgen.py"),
        "--mode", "open", "--shape", "spike", "--rate",
        str(args.spike_rate), "--spike-mult", "8",
        "--duration", str(args.spike_duration),
        "--mix", "interactive=0.6,batch=0.3,best_effort=0.1",
        "--timeout", "30"]

    # -- phase 1: dry run. The controller must DECIDE to scale up and
    # must NOT actuate.
    server, log, ckpt_dir, url = _boot_serve(
        env, base_flags + ["--autoscale-dry-run"], args.timeout)
    try:
        if url is None:
            return 1
        lg = subprocess.Popen(loadgen_spike + ["--url", url],
                              stdout=subprocess.PIPE,
                              stderr=subprocess.STDOUT, text=True)
        out, _ = _communicate_reaped(lg, args.timeout)
        _loadgen_report(out)
        stats = _get_json(url, "/stats")
        scaler = stats.get("autoscaler") or {}
        ups = [d for d in scaler.get("decisions", [])
               if d.get("action") == "scale_up"]
        if not ups or not all(d.get("dry_run") for d in ups):
            _say(f"dry run: expected recorded scale_up decisions, got "
                 f"{scaler.get('decisions')}")
            return 1
        if stats.get("serve_devices") != 1:
            _say(f"dry run actuated! serve_devices="
                 f"{stats.get('serve_devices')}")
            return 1
        _say(f"dry run: {len(ups)} scale_up decision(s) recorded, "
             f"topology untouched (serve_devices=1)")
    finally:
        _kill_serve(server, log, ckpt_dir)

    # -- phase 2: real. The spike must resize the pool up; the calm
    # after it must bring it back down; every accepted request answers.
    server, log, ckpt_dir, url = _boot_serve(env, base_flags,
                                             args.timeout)
    try:
        if url is None:
            return 1
        lg = subprocess.Popen(loadgen_spike + ["--url", url],
                              stdout=subprocess.PIPE,
                              stderr=subprocess.STDOUT, text=True)
        scaled_up = False
        deadline = time.monotonic() + args.spike_duration + 30
        while time.monotonic() < deadline and lg.poll() is None:
            try:
                stats = _get_json(url, "/stats", timeout=5.0)
            except Exception:  # noqa: BLE001 - server busy; retry
                time.sleep(0.3)
                continue
            if stats.get("serve_devices", 1) > 1:
                scaled_up = True
                break
            time.sleep(0.3)
        out, _ = _communicate_reaped(lg, args.timeout)
        report = _loadgen_report(out)
        if not scaled_up:
            stats = _get_json(url, "/stats")
            scaled_up = (stats.get("autoscaler", {})
                         .get("scale_ups", 0)) > 0
        if not scaled_up:
            _say("spike never scaled the pool up")
            return 1
        if report.get("transport_errors"):
            _say(f"{report['transport_errors']} transport errors — "
                 f"dropped in-flight requests during resize")
            return 1
        answered = report.get("ok", 0) + report.get("rejected", 0) \
            + report.get("quota_rejected", 0)
        if answered != _sends(report):
            _say(f"{_sends(report) - answered} request(s) unanswered")
            return 1
        # Post-spike calm: the controller must scale back DOWN.
        deadline = time.monotonic() + 30
        scaled_down = False
        while time.monotonic() < deadline:
            stats = _get_json(url, "/stats")
            if stats.get("serve_devices") == 1 and \
                    stats.get("autoscaler", {}).get("scale_downs", 0):
                scaled_down = True
                break
            time.sleep(0.5)
        if not scaled_down:
            _say("pool never scaled back down after the spike")
            return 1
        stats = _get_json(url, "/stats")
        scaler = stats["autoscaler"]
        _say(f"autoscale spike twin: {scaler['scale_ups']} up / "
             f"{scaler['scale_downs']} down, zero dropped requests "
             f"({report['ok']} ok, {report['rejected']} shed with "
             f"Retry-After on {report['retry_after_seen']})")
        return 0
    finally:
        _kill_serve(server, log, ckpt_dir)


def run_quota_abuse(args) -> int:
    """The per-client quota twin (ISSUE 15): one hot client hammering
    far past --quota-rps must be clipped with 429s while the
    well-behaved clients' goodput stays >= 90% of their offered load —
    one abuser cannot starve the rest."""
    env = _serve_env(args)
    flags = [
        "--model", args.serve_model, "--buckets", "1,8,32",
        "--serve-devices", str(args.serve_devices),
        "--max-wait-ms", "2", "--max-queue", "64",
        "--poll-interval", "5",
        "--quota-rps", str(args.quota_rps),
    ]
    server, log, ckpt_dir, url = _boot_serve(env, flags, args.timeout)
    try:
        if url is None:
            return 1
        good_rate = max(2.0, args.quota_rps / 4.0)
        duration = args.quota_duration
        hog = subprocess.Popen(
            [sys.executable, os.path.join(_REPO, "tools", "loadgen.py"),
             "--url", url, "--mode", "open", "--rate",
             str(args.quota_rps * 10), "--duration", str(duration),
             "--client-id", "hog", "--timeout", "20"],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        good = subprocess.Popen(
            [sys.executable, os.path.join(_REPO, "tools", "loadgen.py"),
             "--url", url, "--mode", "open", "--rate", str(good_rate),
             "--duration", str(duration), "--client-id", "good",
             "--timeout", "20"],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        hog_out, _ = _communicate_reaped(hog, args.timeout)
        good_out, _ = _communicate_reaped(good, args.timeout)
        hog_report = _loadgen_report(hog_out)
        good_report = _loadgen_report(good_out)
        if not hog_report.get("quota_rejected"):
            _say("the hot client was never 429'd — quotas inactive?")
            return 1
        if not hog_report.get("retry_after_seen"):
            _say("429s arrived without Retry-After")
            return 1
        good_sends = _sends(good_report)
        good_ok = good_report.get("ok", 0)
        if good_sends == 0 or good_ok < 0.9 * good_sends:
            _say(f"well-behaved client starved: {good_ok}/{good_sends} "
                 f"answered (need >= 90%)")
            return 1
        stats = _get_json(url, "/stats")
        _say(f"quota twin: hog clipped "
             f"({hog_report['quota_rejected']} x 429 of "
             f"{_sends(hog_report)} sends), good client "
             f"{good_ok}/{good_sends} "
             f"({100.0 * good_ok / good_sends:.1f}% goodput); server "
             f"tracked {stats.get('quota', {}).get('clients_tracked')} "
             f"client(s)")
        return 0
    finally:
        _kill_serve(server, log, ckpt_dir)


def _post_predict(url: str, body: bytes, timeout: float = 30.0):
    """POST one pre-serialized /predict body; returns ``(reply_dict,
    x_cache)`` where x_cache is the reply's X-Cache header verdict
    (hit/miss/None) — the cache-storm twin's staleness probe."""
    req = urllib.request.Request(
        url + "/predict", data=body,
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read()), resp.headers.get("X-Cache")


def run_cache_storm(args) -> int:
    """The response-cache invalidation twin (ISSUE 19): duplicate-heavy
    loadgen (Zipf-shaped key reuse, the cache's best case) over a LIVE
    hot reload. The bar: zero dropped requests through the swap, and
    zero stale logits after it — every post-swap reply must carry the
    new model epoch, because the swap hook bumps the cache generation
    atomically with the param install (an entry from the old params can
    never be replayed as the new model's answer)."""
    env = _serve_env(args)
    flags = ["--model", "linear", "--buckets", "1,8",
             "--serve-devices", str(args.serve_devices),
             "--max-wait-ms", "2", "--poll-interval", "0.2"]
    server, log, ckpt_dir, url = _boot_serve(env, flags, args.timeout)
    try:
        if url is None:
            return 1
        # One fixed duplicate body — the probe key the whole twin
        # replays (deterministic, so pre- and post-swap probes are
        # byte-identical and MUST collide in the cache).
        rng = random.Random(3)
        probe = json.dumps({"images": [
            [[rng.randrange(256) for _ in range(28)]
             for _ in range(28)]]}).encode()
        pre_epochs, pre_cache = set(), []
        for _ in range(3):
            reply, verdict = _post_predict(url, probe)
            pre_epochs.add(reply.get("model_epoch"))
            pre_cache.append(verdict)
        if len(pre_epochs) != 1:
            _say(f"pre-swap epochs disagree: {sorted(pre_epochs)}")
            return 1
        if "hit" not in pre_cache:
            _say(f"duplicate probe never hit the cache ({pre_cache}) — "
                 f"cache inactive?")
            return 1
        (old_epoch,) = pre_epochs
        _say(f"cache warm on epoch {old_epoch} ({pre_cache})")

        # The storm: Zipf-duplicate loadgen riding THROUGH the reload —
        # open-loop over a fixed duration (a closed burst would finish
        # before the publish subprocess even imports jax, and "zero
        # drops through the swap" would be vacuous).
        storm_s = 10.0
        storm = subprocess.Popen(
            [sys.executable, os.path.join(_REPO, "tools", "loadgen.py"),
             "--url", url, "--mode", "open",
             "--rate", str(max(20.0, args.requests / storm_s)),
             "--duration", str(storm_s), "--shape", "zipf:1.1",
             "--timeout", "20"],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        time.sleep(0.5)  # let the storm get in flight first
        new_epoch = (old_epoch or 0) + 7
        _seed_checkpoint(env, ckpt_dir, new_epoch)
        _say(f"published checkpoint_{new_epoch}.npz under the storm")
        deadline = time.monotonic() + args.timeout
        epoch = None
        while time.monotonic() < deadline:
            epoch = _get_json(url, "/healthz").get("model_epoch")
            if epoch == new_epoch:
                break
            time.sleep(0.2)
        if epoch != new_epoch:
            _say(f"hot reload never landed (model_epoch={epoch}, want "
                 f"{new_epoch})")
            return 1
        out, _ = _communicate_reaped(storm, args.timeout)
        report = _loadgen_report(out)
        sends = _sends(report)
        dropped = (report.get("transport_errors", 0)
                   + report.get("conn_refused", 0))
        if dropped or report.get("ok", 0) != sends:
            _say(f"storm dropped requests through the swap: "
                 f"{report.get('ok', 0)}/{sends} answered 200, "
                 f"{dropped} transport failures")
            return 1
        hits = report.get("cache_client", {}).get("hits", 0)
        if not hits:
            _say("the storm never observed a cache hit — the Zipf "
                 "duplicates missed the cache?")
            return 1

        # Staleness probe: the SAME bytes that were cached pre-swap.
        # Every reply must now carry the new epoch — a single old-epoch
        # reply is a stale logit replay, the exact bug the generation
        # bump exists to make impossible.
        post_cache = []
        for i in range(8):
            reply, verdict = _post_predict(url, probe)
            post_cache.append(verdict)
            if reply.get("model_epoch") != new_epoch:
                _say(f"STALE reply {i}: model_epoch="
                     f"{reply.get('model_epoch')} after swap to "
                     f"{new_epoch} (X-Cache: {verdict})")
                return 1
        if "hit" not in post_cache:
            _say(f"post-swap probe never re-cached ({post_cache})")
            return 1
        stats = _get_json(url, "/stats")
        cache_stats = stats.get("cache", {})
        _say(f"cache storm: {report['ok']}/{sends} answered through the "
             f"reload ({hits} client-observed hits), zero stale replies "
             f"after the swap to epoch {new_epoch} (cache generation "
             f"{cache_stats.get('generation')}, "
             f"{cache_stats.get('stale_drops')} stale insert(s) "
             f"dropped)")
        return 0
    finally:
        _kill_serve(server, log, ckpt_dir)


def run_serve_chaos(args) -> int:
    """The serve-plane twins: boot a REAL serve subprocess, hammer it
    with loadgen, and either sabotage a mesh group (``--serve-fault``:
    the pool must quarantine, fail requests over, and regroup under the
    live traffic) or roll the topology (``--resize``: each /resize must
    complete with zero dropped requests). Success = every loadgen
    request answered 200 AND the final /stats topology matches
    ``--expect-groups``."""
    env = dict(os.environ)
    if args.serve_fault:
        env[SERVE_FAULT_ENV] = args.serve_fault
    else:
        env.pop(SERVE_FAULT_ENV, None)
    if args.canary_rollback:
        # Rehearse the rollback-under-traffic scenario: every shadow
        # comparison is injected to disagree, so the canary must roll
        # back while loadgen hammers — and still answer EVERY request
        # from the baseline (zero drops is the twin's bar).
        env[CANARY_FAULT_ENV] = "disagree"
    else:
        env.pop(CANARY_FAULT_ENV, None)
    _force_cpu_devices(env, args.cpu_devices)
    env["PYTHONUNBUFFERED"] = "1"
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")

    ckpt_dir = tempfile.mkdtemp(prefix="tpumnist-serve-chaos-")
    log = tempfile.NamedTemporaryFile(
        mode="w+", suffix=".log", delete=False)
    cmd = [sys.executable, "-m", "pytorch_distributed_mnist_tpu", "serve",
           "--checkpoint-dir", ckpt_dir, "--model", args.serve_model,
           "--host", "127.0.0.1", "--port", "0", "--buckets", "1,8,32",
           "--serve-devices", str(args.serve_devices),
           "--serve-mode", args.serve_mode,
           "--quarantine-after", str(args.quarantine_after),
           "--max-wait-ms", "2", "--poll-interval", "1"]
    if args.serve_mesh:
        cmd += ["--serve-mesh", str(args.serve_mesh)]
    serve_precision = args.serve_precision
    if args.canary_rollback and not serve_precision:
        serve_precision = "bf16"  # the canary needs a quantized plane
    if serve_precision:
        cmd += ["--serve-precision", serve_precision]
    if args.canary_rollback:
        # Fraction 1.0 shadows every batch; a huge promotion window and
        # a zero budget make the injected disagreement the only
        # possible transition.
        cmd += ["--canary-fraction", "1.0",
                "--canary-promote-after", "100000",
                "--canary-budget", "0.0"]
    _say(f"booting serve twin: {' '.join(cmd)}"
         + (f" [{SERVE_FAULT_ENV}={args.serve_fault}]"
            if args.serve_fault else ""))
    server = subprocess.Popen(cmd, env=env, stdout=log,
                              stderr=subprocess.STDOUT)
    loadgen = None
    url = None
    try:
        deadline = time.monotonic() + args.timeout
        while time.monotonic() < deadline and url is None:
            if server.poll() is not None:
                break
            log.flush()
            with open(log.name) as f:
                m = re.search(r"serving on (http://\S+)", f.read())
            if m:
                url = m.group(1).rstrip("/")
            else:
                time.sleep(0.2)
        if url is None:
            with open(log.name) as f:
                print(f.read()[-4000:], file=sys.stderr)
            _say("server never came up")
            return 1
        _say(f"server up at {url}")

        loadgen_cmd = [
            sys.executable, os.path.join(_REPO, "tools", "loadgen.py"),
            "--smoke", "--url", url, "--requests", str(args.requests),
            "--concurrency", "8"]
        loadgen = subprocess.Popen(loadgen_cmd, stdout=subprocess.PIPE,
                                   stderr=subprocess.STDOUT, text=True)
        # Roll the topology WHILE the load runs: each /resize must
        # complete under traffic with zero dropped requests.
        for target in args.resize_targets:
            time.sleep(0.5)
            reply = _post_json(url, "/resize", {"serve_devices": target})
            _say(f"/resize -> {target} replicas: topology generation "
                 f"{reply['new']['topology_generation']}")
        out, _ = _communicate_reaped(loadgen, args.timeout)
        loadgen_rc = loadgen.returncode
        loadgen = None  # reaped; nothing left for the finally to kill
        report_line = out.strip().splitlines()[-1] if out.strip() else "{}"
        print(report_line)
        report = json.loads(report_line)
        if loadgen_rc != 0 or report.get("ok") != args.requests:
            _say(f"loadgen dropped/failed requests (rc="
                 f"{loadgen_rc}, ok={report.get('ok')}/"
                 f"{args.requests})")
            return 1
        _say(f"loadgen: {args.requests}/{args.requests} answered, zero "
             f"drops")

        if args.canary_rollback:
            # The injected disagreement must have rolled the publish
            # back — with the baseline still answering everything.
            stats = _get_json(url, "/stats")
            can = stats.get("canary") or {}
            if can.get("state") != "rolled_back":
                _say(f"expected canary state rolled_back under injected "
                     f"disagreement, got {can.get('state')!r}")
                return 1
            _say(f"canary rolled back ({can.get('disagreed_rows')} "
                 f"disagreeing rows of {can.get('compared_rows')} "
                 f"compared); baseline kept serving, zero drops")

        # Wait for the pool to finish healing (quarantine -> regroup),
        # then assert the final topology with the loadgen smoke gate.
        deadline = time.monotonic() + args.timeout
        while time.monotonic() < deadline:
            stats = _get_json(url, "/stats")
            if not stats.get("quarantined_groups"):
                break
            time.sleep(0.5)
        final = [sys.executable, os.path.join(_REPO, "tools",
                                              "loadgen.py"),
                 "--smoke", "--url", url, "--requests", "50",
                 "--concurrency", "4"]
        if args.expect_groups:
            final += ["--expect-groups", str(args.expect_groups)]
        proc = subprocess.run(final, capture_output=True, text=True,
                              timeout=args.timeout)
        print(proc.stdout.strip().splitlines()[-1]
              if proc.stdout.strip() else "{}")
        if proc.returncode != 0:
            _say("post-heal topology smoke failed")
            return 1
        stats = _get_json(url, "/stats")
        _say(f"final topology: generation "
             f"{stats.get('topology_generation')}, "
             f"{stats.get('active_groups')}/{stats.get('groups')} "
             f"groups active, regroups={stats.get('regroups')}, "
             f"failovers={stats.get('failovers')}")
        if args.serve_fault and not stats.get("regroups"):
            _say("expected at least one regroup under --serve-fault")
            return 1
        return 0
    finally:
        # A failed /resize (HTTPError) or a loadgen communicate timeout
        # propagates through here with loadgen still running against a
        # server this block is about to kill: reap it too, or it spins
        # connection errors as an orphan.
        if loadgen is not None and loadgen.poll() is None:
            loadgen.kill()
            loadgen.wait()
        server.kill()
        server.wait()
        log.close()
        os.unlink(log.name)
        shutil.rmtree(ckpt_dir, ignore_errors=True)


# Delta-publish helper run in a subprocess (chaos stays jax-free).
# Deterministic per epoch: the state is base(seed 7) with the SMALLEST
# params leaf (the bias) shifted by e*1e-3, so adjacent epochs differ in
# exactly one leaf and re-running any epoch reproduces its bytes.
# argv: directory e0 n drop_new sleep_s. drop_new=1 sabotages the
# publish by deleting every chunk it newly added — the missing-chunk
# torn-publish twin.
_DELTA_PUBLISH_CODE = """
import os, sys, time
import jax, jax.numpy as jnp
from pytorch_distributed_mnist_tpu.models import get_model
from pytorch_distributed_mnist_tpu.train.state import create_train_state
from pytorch_distributed_mnist_tpu.distrib.cas import ChunkStore
from pytorch_distributed_mnist_tpu.distrib.publish import publish_state

directory, e0, n = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
drop_new, sleep_s = sys.argv[4] == "1", float(sys.argv[5])
m = get_model("linear", compute_dtype=jnp.float32)
base = create_train_state(m, jax.random.key(7))
store = ChunkStore(directory)
leaves, treedef = jax.tree_util.tree_flatten(base.params)
small = min(range(len(leaves)), key=lambda j: leaves[j].size)
for e in range(e0, e0 + n):
    shifted = list(leaves)
    shifted[small] = leaves[small] + e * 1e-3
    state = base.replace(
        params=jax.tree_util.tree_unflatten(treedef, shifted))
    before = store.digests()
    publish_state(state, epoch=e, best_acc=0.5, directory=directory,
                  process_index=0)
    if drop_new:
        for digest in store.digests() - before:
            os.remove(store.path(digest))
    if sleep_s and e + 1 < e0 + n:
        time.sleep(sleep_s)
"""


def _delta_publish_epochs(env: dict, directory: str, e0: int, n: int,
                          drop_new: bool = False,
                          sleep_s: float = 0.0) -> None:
    subprocess.run(
        [sys.executable, "-c", _DELTA_PUBLISH_CODE, directory, str(e0),
         str(n), "1" if drop_new else "0", str(sleep_s)],
        env=env, check=True, timeout=600)


def _chunks_bytes(directory: str) -> int:
    chunk_dir = os.path.join(directory, "chunks")
    if not os.path.isdir(chunk_dir):
        return 0
    return sum(os.path.getsize(os.path.join(chunk_dir, name))
               for name in os.listdir(chunk_dir))


def _seed_checkpoint(env: dict, directory: str, epoch: int) -> str:
    """Save a real linear-model checkpoint_{epoch}.npz into
    ``directory`` via a subprocess (chaos itself stays jax-import-free)
    and return its path."""
    code = (
        "import sys, jax, jax.numpy as jnp\n"
        "from pytorch_distributed_mnist_tpu.models import get_model\n"
        "from pytorch_distributed_mnist_tpu.train.state import "
        "create_train_state\n"
        "from pytorch_distributed_mnist_tpu.train.checkpoint import "
        "save_checkpoint\n"
        "m = get_model('linear', compute_dtype=jnp.float32)\n"
        "s = create_train_state(m, jax.random.key(7))\n"
        "save_checkpoint(s, epoch=int(sys.argv[2]), best_acc=0.5,\n"
        "                is_best=False, directory=sys.argv[1],\n"
        "                process_index=0)\n")
    subprocess.run([sys.executable, "-c", code, directory, str(epoch)],
                   env=env, check=True, timeout=300)
    return os.path.join(directory, f"checkpoint_{epoch}.npz")


def run_fleet_chaos(args) -> int:
    """The fleet-federation twins (ISSUE 17): a REAL router subprocess
    over --fleet N real single-chip serve subprocesses.

    --kill-backend K: SIGKILL backend K mid-loadgen; every request must
    still be answered (router failover + the loadgen's bounded
    --retry-transport = zero DROPPED), the corpse must quarantine, and
    a restart on its old port must walk probation back to healthy.

    --rolling-reload: POST /rollout publishes a new epoch to the whole
    fleet one backend at a time under live loadgen — zero drops, every
    backend on the new epoch afterward.

    --fleet-canary-rollback: publish behind a fleet canary with
    TPUMNIST_FLEET_FAULT=canary_disagree injected into the router —
    the canary must roll back (baseline weights republished) while
    every request is still answered.

    --delta-publish E (ISSUE 18): every backend watches ONE shared
    checkpoint directory; E delta publishes land under live loadgen —
    zero drops, every backend converges to the last epoch, and the
    chunk bytes each adjacent publish adds must be a small fraction of
    the cold (whole-state) bytes."""
    env = _serve_env(args)
    router_env = dict(env)
    if args.fleet_canary_rollback:
        router_env[FLEET_FAULT_ENV] = "canary_disagree"
    else:
        router_env.pop(FLEET_FAULT_ENV, None)
    backend_flags = ["--model", "linear", "--buckets", "1,8",
                     "--max-wait-ms", "2", "--max-queue", "256",
                     "--poll-interval", "0.2"]
    backends = []  # (server, log, ckpt_dir, url)
    router = router_log = None
    staging = tempfile.mkdtemp(prefix="tpumnist-fleet-staging-")
    shared_dir = None
    try:
        if args.delta_publish:
            # One directory for the whole fleet (the shared-fs
            # scenario); seeded with a COLD delta publish so the
            # backends boot serving epoch 1 off the manifest and the
            # store holds the full-state baseline bytes to compare
            # adjacent publishes against.
            shared_dir = tempfile.mkdtemp(prefix="tpumnist-fleet-delta-")
            _delta_publish_epochs(env, shared_dir, 1, 1)
            _say("seeded epoch-1 delta publish (cold store)")
        for i in range(args.fleet):
            server, log, ckpt_dir, url = _boot_serve(
                env, backend_flags, args.timeout, ckpt_dir=shared_dir)
            if url is None:
                return 1
            backends.append([server, log, ckpt_dir, url])
        _say(f"fleet up: {[b[3] for b in backends]}")
        router, router_log, url = _boot_router(
            router_env, [b[3] for b in backends], args.timeout)
        if url is None:
            return 1
        deadline = time.monotonic() + args.timeout
        while time.monotonic() < deadline:
            if _get_json(url, "/healthz").get("routable") == args.fleet:
                break
            time.sleep(0.2)
        _say(f"router up at {url}, {args.fleet} backends routable")
        dirs_body = {b[3].split("//")[-1]: b[2] for b in backends}

        if args.kill_backend is not None:
            victim = backends[args.kill_backend]
            duration = 6.0
            loadgen = subprocess.Popen(
                [sys.executable, os.path.join(_REPO, "tools",
                                              "loadgen.py"),
                 "--mode", "open", "--rate", "80",
                 "--duration", str(duration), "--retry-transport", "2",
                 "--url", url],
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True)
            time.sleep(duration * 0.35)
            _say(f"SIGKILL backend {args.kill_backend} ({victim[3]})")
            victim[0].kill()
            victim[0].wait()
            out, _ = _communicate_reaped(loadgen, args.timeout)
            report = _loadgen_report(out)
            answered = sum(report.get("status_counts", {}).values())
            dropped = (report.get("transport_errors", 0)
                       + report.get("conn_refused", 0))
            if loadgen.returncode != 0 or dropped or \
                    report.get("ok") != answered or answered < 100:
                _say(f"DROPPED requests through the kill: ok="
                     f"{report.get('ok')}/{answered}, dropped={dropped}")
                return 1
            _say(f"{answered} requests answered through the kill, zero "
                 f"dropped ({report.get('transport_retries')} client "
                 f"retries)")
            stats = _get_json(url, "/stats")
            victim_name = victim[3].split("//")[-1]
            rows = {r["name"]: r for r in stats["backends"]}
            if rows[victim_name]["state"] != "quarantined" or \
                    not stats["fleet"]["failovers"]:
                _say(f"expected quarantine+failover, got state="
                     f"{rows[victim_name]['state']}, failovers="
                     f"{stats['fleet']['failovers']}")
                return 1
            _say(f"victim quarantined; failovers="
                 f"{stats['fleet']['failovers']}, merged fleet p99="
                 f"{stats['fleet']['window']['p99_ms']}ms")
            # Restart on the old port: probation -> healthy, no
            # operator action at the router.
            port = int(victim[3].rsplit(":", 1)[1])
            victim[1].close()
            os.unlink(victim[1].name)
            server, log, ckpt_dir, burl = _boot_serve(
                env, backend_flags, args.timeout,
                ckpt_dir=victim[2], port=port)
            victim[0], victim[1], victim[3] = server, log, burl or ""
            if burl is None:
                return 1
            deadline = time.monotonic() + args.timeout
            row = {}
            while time.monotonic() < deadline:
                stats = _get_json(url, "/stats")
                row = {r["name"]: r
                       for r in stats["backends"]}[victim_name]
                if row["state"] == "healthy":
                    break
                time.sleep(0.2)
            if row.get("state") != "healthy" or not row.get("readmissions"):
                _say(f"victim never re-admitted: {row}")
                return 1
            _say(f"victim re-admitted through probation "
                 f"(readmissions={row['readmissions']}); fleet whole "
                 f"again")
            return 0

        if args.rolling_reload:
            source = _seed_checkpoint(env, staging, epoch=1)
            loadgen = subprocess.Popen(
                [sys.executable, os.path.join(_REPO, "tools",
                                              "loadgen.py"),
                 "--mode", "open", "--rate", "60", "--duration", "8",
                 "--retry-transport", "2", "--url", url],
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True)
            time.sleep(1.0)
            reply = _post_json(url, "/rollout",
                               {"source": source, "dirs": dirs_body})
            if not reply.get("ok") or \
                    len(reply.get("updated", [])) != args.fleet:
                _say(f"rolling reload failed: {reply}")
                return 1
            _say(f"rolled epoch 1 onto {reply['updated']}")
            out, _ = _communicate_reaped(loadgen, args.timeout)
            report = _loadgen_report(out)
            answered = sum(report.get("status_counts", {}).values())
            dropped = (report.get("transport_errors", 0)
                       + report.get("conn_refused", 0))
            if loadgen.returncode != 0 or dropped or \
                    report.get("ok") != answered:
                _say(f"DROPPED requests through the rollout: ok="
                     f"{report.get('ok')}/{answered}, dropped={dropped}")
                return 1
            for _, _, _, burl in backends:
                health = _get_json(burl, "/healthz")
                if health.get("model_epoch") != 1 or health.get("draining"):
                    _say(f"backend {burl} not on epoch 1 post-rollout: "
                         f"{health}")
                    return 1
            _say(f"{answered} requests answered through the fleet-wide "
                 f"publish, zero dropped; every backend on epoch 1")
            return 0

        if args.fleet_canary_rollback:
            # Baseline first: the whole fleet on a real epoch 1, so the
            # rollback has baseline WEIGHTS to restore.
            source = _seed_checkpoint(env, staging, epoch=1)
            reply = _post_json(url, "/rollout",
                               {"source": source, "dirs": dirs_body})
            if not reply.get("ok"):
                _say(f"baseline publish failed: {reply}")
                return 1
            target = _seed_checkpoint(env, staging, epoch=2)
            canary_name = backends[0][3].split("//")[-1]
            reply = _post_json(url, "/rollout", {
                "source": target, "dirs": dirs_body,
                "canary": {"fraction": 1.0, "budget": 0.0,
                           "promote_after": 100000,
                           "backends": [canary_name]}})
            if not reply.get("ok"):
                _say(f"canary publish failed: {reply}")
                return 1
            # client_id puts every request in the (fraction-1.0)
            # cohort; the injected fault disagrees every row, so the
            # FIRST cohort reply must roll the fleet canary back.
            proc = subprocess.run(
                [sys.executable, os.path.join(_REPO, "tools",
                                              "loadgen.py"),
                 "--requests", str(args.requests), "--concurrency", "4",
                 "--retry-transport", "2", "--client-id", "canary-probe",
                 "--url", url],
                capture_output=True, text=True, timeout=args.timeout)
            report = _loadgen_report(proc.stdout)
            answered = sum(report.get("status_counts", {}).values())
            dropped = (report.get("transport_errors", 0)
                       + report.get("conn_refused", 0))
            if dropped or report.get("ok") != answered:
                _say(f"DROPPED requests during the canary: ok="
                     f"{report.get('ok')}/{answered}, dropped={dropped}")
                return 1
            deadline = time.monotonic() + args.timeout
            can = {}
            while time.monotonic() < deadline:
                can = _get_json(url, "/stats").get("fleet_canary") or {}
                if can.get("state") == "rolled_back":
                    break
                time.sleep(0.2)
            if can.get("state") != "rolled_back":
                _say(f"expected fleet canary rolled_back under injected "
                     f"disagreement, got {can.get('state')!r}")
                return 1
            # The rollback republishes the BASELINE weights (as the
            # next epoch number — epochs are publish sequence numbers);
            # wait for the canary backend to swap onto them.
            deadline = time.monotonic() + args.timeout
            epoch = None
            while time.monotonic() < deadline:
                epoch = _get_json(backends[0][3],
                                  "/healthz").get("model_epoch")
                if epoch == 3:
                    break
                time.sleep(0.2)
            if epoch != 3:
                _say(f"canary backend never restored baseline weights "
                     f"(epoch {epoch}, want 3 = baseline republished)")
                return 1
            _say(f"fleet canary rolled back "
                 f"({can.get('disagreed_rows')} disagreeing rows of "
                 f"{can.get('compared_rows')}); baseline weights "
                 f"republished, {answered} requests answered, zero "
                 f"dropped")
            return 0

        if args.delta_publish:
            n = args.delta_publish
            cold = _chunks_bytes(shared_dir)
            last_epoch = 1 + n
            duration = max(8.0, 2.0 * n + 4.0)
            loadgen = subprocess.Popen(
                [sys.executable, os.path.join(_REPO, "tools",
                                              "loadgen.py"),
                 "--mode", "open", "--rate", "60",
                 "--duration", str(duration), "--retry-transport", "2",
                 "--url", url],
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True)
            time.sleep(1.0)
            t0 = time.monotonic()
            _delta_publish_epochs(env, shared_dir, 2, n, sleep_s=1.0)
            # Fleet consistency: every backend swaps onto the LAST
            # published epoch while traffic keeps flowing.
            deadline = time.monotonic() + args.timeout
            converged = False
            while time.monotonic() < deadline and not converged:
                converged = all(
                    _get_json(burl, "/healthz").get("model_epoch")
                    == last_epoch for _, _, _, burl in backends)
                if not converged:
                    time.sleep(0.2)
            consistency_s = time.monotonic() - t0
            out, _ = _communicate_reaped(loadgen, args.timeout)
            report = _loadgen_report(out)
            answered = sum(report.get("status_counts", {}).values())
            dropped = (report.get("transport_errors", 0)
                       + report.get("conn_refused", 0))
            if loadgen.returncode != 0 or dropped or \
                    report.get("ok") != answered or answered < 100:
                _say(f"DROPPED requests through the delta publishes: "
                     f"ok={report.get('ok')}/{answered}, "
                     f"dropped={dropped}")
                return 1
            if not converged:
                epochs = [_get_json(burl, "/healthz").get("model_epoch")
                          for _, _, _, burl in backends]
                _say(f"fleet never converged to epoch {last_epoch}: "
                     f"{epochs}")
                return 1
            delta = _chunks_bytes(shared_dir) - cold
            per_publish = delta / n
            _say(f"{n} delta publishes: {per_publish:.0f}B/publish vs "
                 f"{cold}B cold ({100 * per_publish / max(cold, 1):.2f}"
                 f"%); fleet consistent in {consistency_s:.1f}s; "
                 f"{answered} requests answered, zero dropped")
            if per_publish >= 0.30 * cold:
                _say("adjacent delta publishes should move far fewer "
                     "bytes than the cold publish")
                return 1
            return 0

        _say("--fleet needs one of --kill-backend K / --rolling-reload "
             "/ --fleet-canary-rollback / --delta-publish E")
        return 2
    finally:
        if router is not None:
            router.kill()
            router.wait()
        if router_log is not None:
            router_log.close()
            os.unlink(router_log.name)
        for server, log, ckpt_dir, _ in backends:
            _kill_serve(server, log, ckpt_dir)
        shutil.rmtree(staging, ignore_errors=True)


def run_torn_manifest(args) -> int:
    """The torn-publish twin (ISSUE 18): one real serve process on a
    delta-published directory, fed three kinds of publish damage.

    1. A TORN manifest (half a JSON file under the published name —
       a publisher that died mid-write without the tmp+rename
       discipline): content damage, permanent-skip for that file.
    2. A manifest referencing a MISSING chunk (the publish's new chunks
       deleted after the rename): absence for that publish,
       permanent-skip until a newer manifest appears.
    3. A clean publish: the watcher recovers onto it with no restart.

    Through all three the server answers every request on the params it
    has — reload failures are recorded, never served."""
    env = _serve_env(args)
    ckpt_dir = tempfile.mkdtemp(prefix="tpumnist-torn-")
    server = log = None
    try:
        _delta_publish_epochs(env, ckpt_dir, 1, 1)
        server, log, ckpt_dir, url = _boot_serve(
            env, ["--model", "linear", "--buckets", "1,8",
                  "--max-wait-ms", "2", "--max-queue", "256",
                  "--poll-interval", "0.2"],
            args.timeout, ckpt_dir=ckpt_dir)
        if url is None:
            return 1
        if _get_json(url, "/healthz").get("model_epoch") != 1:
            _say("server did not boot onto the epoch-1 manifest")
            return 1
        _say("serving epoch 1 off the seeded manifest")

        def _smoke(stage: str) -> bool:
            proc = subprocess.run(
                [sys.executable, os.path.join(_REPO, "tools",
                                              "loadgen.py"),
                 "--smoke", "--url", url, "--requests", "50",
                 "--concurrency", "4"],
                capture_output=True, text=True, timeout=args.timeout)
            report = _loadgen_report(proc.stdout)
            if proc.returncode != 0 or report.get("ok") != 50:
                _say(f"requests dropped {stage}: {report}")
                return False
            return True

        def _await_failures(want: int) -> bool:
            deadline = time.monotonic() + args.timeout
            while time.monotonic() < deadline:
                if _get_json(url, "/stats").get(
                        "reload_failures", 0) >= want:
                    return True
                time.sleep(0.2)
            _say(f"watcher never recorded reload failure #{want}")
            return False

        # 1: torn JSON under the published epoch-2 name.
        with open(os.path.join(ckpt_dir,
                               "checkpoint_1.manifest"), "rb") as f:
            data = f.read()
        with open(os.path.join(ckpt_dir,
                               "checkpoint_2.manifest"), "wb") as f:
            f.write(data[:len(data) // 2])
        if not _await_failures(1):
            return 1
        if _get_json(url, "/healthz").get("model_epoch") != 1:
            _say("torn manifest must not change the serving params")
            return 1
        if not _smoke("under the torn manifest"):
            return 1
        _say("torn manifest skipped (still serving epoch 1, zero "
             "drops)")

        # 2: epoch-3 manifest whose new chunks were deleted post-rename.
        _delta_publish_epochs(env, ckpt_dir, 3, 1, drop_new=True)
        if not _await_failures(2):
            return 1
        if _get_json(url, "/healthz").get("model_epoch") != 1:
            _say("missing-chunk manifest must not change the serving "
                 "params")
            return 1
        if not _smoke("under the missing-chunk manifest"):
            return 1
        _say("missing-chunk manifest skipped (still serving epoch 1)")

        # 3: the next CLEAN publish recovers with no operator action.
        _delta_publish_epochs(env, ckpt_dir, 4, 1)
        deadline = time.monotonic() + args.timeout
        epoch = None
        while time.monotonic() < deadline:
            epoch = _get_json(url, "/healthz").get("model_epoch")
            if epoch == 4:
                break
            time.sleep(0.2)
        if epoch != 4:
            _say(f"clean publish never recovered the watcher "
                 f"(model_epoch={epoch}, want 4)")
            return 1
        if not _smoke("after the recovery publish"):
            return 1
        stats = _get_json(url, "/stats")
        _say(f"recovered onto epoch 4 (reloads={stats.get('reloads')}, "
             f"reload_failures={stats.get('reload_failures')}); zero "
             f"drops end to end")
        return 0
    finally:
        if server is not None:
            _kill_serve(server, log, ckpt_dir)
        else:
            shutil.rmtree(ckpt_dir, ignore_errors=True)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="chaos",
        description="fault-injection twins for the run-supervision layer",
    )
    p.add_argument("--list", action="store_true",
                   help="enumerate injectable fault points and exit")
    p.add_argument("--fault", type=str, default=None,
                   metavar="POINT:HOST:KIND[:ARG][,...]",
                   help="the fault(s) to inject (see --list; kinds: "
                        "kill, raise, stall; comma-join for multiple, "
                        "e.g. a host loss plus an elastic_rebuild "
                        "sabotage of a survivor). Omit for a clean "
                        "control run")
    p.add_argument("--elastic", action="store_true",
                   help="run under the elastic supervisor "
                        "(runtime/elastic.py): a host loss SHRINKS the "
                        "world — survivors re-exec at the smaller size "
                        "and resume from the last published checkpoint "
                        "— instead of ending the run")
    p.add_argument("--elastic-grow", action="store_true",
                   help="elastic: run the epoch-boundary grow "
                        "rendezvous too, so join records (--rejoin, or "
                        "announce_join) are admitted between epochs — "
                        "the shrink-then-GROW scenarios")
    p.add_argument("--rejoin", type=str, default=None,
                   metavar="HOST@GEN[,...]",
                   help="elastic: write HOST's join record while "
                        "generation GEN runs (the deterministic "
                        "simulation of a returned/replacement host "
                        "announcing itself; e.g. 1@1 for the 2->1->2 "
                        "twin)")
    p.add_argument("--dcn-slices", type=int, default=0, metavar="N",
                   help="run the world on the emulated hierarchical "
                        f"(DCN x ICI) mesh: sets {DCN_SLICES_ENV}=N for "
                        "every rank (N must divide --nprocs; each "
                        "slice is a contiguous block of ranks). The "
                        "slice-loss twins compose this with "
                        "--kill-slice")
    p.add_argument("--kill-slice", type=int, default=None, metavar="S",
                   help="elastic slice-loss twin: SIGKILL EVERY host of "
                        "emulated slice S (mid-epoch, the train_step "
                        "point, skip 5) — the survivors shrink to the "
                        "remaining slice(s), and a world the slice "
                        "count no longer divides lands on the FLAT "
                        "mesh (cli.py's elastic fallback) and resumes "
                        "through the ordinary (W, W') reshard. "
                        "Requires --elastic and --dcn-slices")
    p.add_argument("--min-world", type=int, default=1, metavar="W",
                   help="elastic floor: stop shrinking below W healthy "
                        "hosts (default 1)")
    p.add_argument("--max-world", type=int, default=0, metavar="W",
                   help="elastic ceiling for the grow direction "
                        "(0 = unbounded)")
    p.add_argument("--settle-timeout", type=float, default=60.0,
                   help="elastic: seconds the supervisor waits for the "
                        "remaining ranks to exit once one has failed, "
                        "before killing stragglers and shrinking "
                        "without them (default 60)")
    p.add_argument("--nprocs", type=int, default=2,
                   help="local host processes to spawn (default 2)")
    p.add_argument("--agreement-timeout", type=float, default=15.0,
                   help="watchdog deadline handed to every rank via "
                        f"{TIMEOUT_ENV} (default 15s: chaos runs WANT "
                        "the watchdog — a hang is the bug under test)")
    p.add_argument("--timeout", type=float, default=600.0,
                   help="whole-run wall clock bound before every rank "
                        "is killed (default 600s); for elastic runs, "
                        "the per-generation bound")
    # -- the serve-plane twins (pool self-healing / rolling resize) ----
    p.add_argument("--serve", action="store_true",
                   help="serve-plane chaos: boot a real `tpu-mnist "
                        "serve` subprocess (fresh-init params), hammer "
                        "it with loadgen, and assert zero dropped "
                        "requests through a group 'death' "
                        "(--serve-fault) or a rolling /resize "
                        "(--resize), plus the post-heal topology "
                        "(--expect-groups)")
    p.add_argument("--serve-devices", type=int, default=2,
                   help="serve twin: replicas the server boots with")
    p.add_argument("--serve-mode", type=str, default="replicated",
                   help="serve twin: the data plane to chaos "
                        "(replicated / tensor / expert / pipeline — a "
                        "pipeline group death is a whole-CHAIN "
                        "quarantine + all-stage regroup)")
    p.add_argument("--serve-mesh", type=int, default=0,
                   help="serve twin: chips per mesh group / stages per "
                        "pipeline chain (0 = server default)")
    p.add_argument("--serve-precision", type=str, default=None,
                   help="serve twin: --serve-precision handed to the "
                        "server (f32/bf16/int8w/int8 — the quantized "
                        "serving plane under chaos; defaults to the "
                        "server's f32)")
    p.add_argument("--canary-rollback", action="store_true",
                   help="serve twin: rehearse the shadow-canary "
                        "rollback-under-traffic scenario — boot with "
                        "--canary-fraction 1.0 and an injected "
                        f"disagreement ({CANARY_FAULT_ENV}=disagree), "
                        "assert the canary rolls back while EVERY "
                        "loadgen request is still answered (implies "
                        "--serve-precision bf16 unless given)")
    p.add_argument("--serve-model", type=str, default="linear",
                   help="serve twin: --model for the server (sharded/"
                        "staged modes need their model family, e.g. "
                        "vit for pipeline)")
    p.add_argument("--serve-fault", type=str, default=None,
                   metavar="GROUP[:AFTER]",
                   help=f"serve twin: {SERVE_FAULT_ENV} injection — "
                        "group GROUP's dispatch starts failing after "
                        "AFTER successful batches (its 'chips die'); "
                        "the pool must quarantine it, fail batches "
                        "over, and regroup under traffic")
    p.add_argument("--resize", type=str, default=None, metavar="N1[,N2...]",
                   help="serve twin: roll POST /resize through these "
                        "serve_devices targets while loadgen runs "
                        "(the rolling-topology-change twin)")
    p.add_argument("--expect-groups", type=int, default=0,
                   help="serve twin: require this many ACTIVE groups "
                        "in the final /stats (0 skips)")
    p.add_argument("--autoscale-spike", action="store_true",
                   help="serve twin: the SLO-autoscaler scenario — "
                        "spike loadgen against a 1-device pool with "
                        "--autoscale; phase 1 asserts the DRY-RUN "
                        "decision log (scale_up recorded, topology "
                        "untouched), phase 2 asserts the real resize "
                        "up during the spike and back down after it, "
                        "with zero dropped in-flight requests. "
                        "Needs --cpu-devices >= 2 off-TPU")
    p.add_argument("--slo-p95-ms", type=float, default=150.0,
                   help="autoscale-spike twin: the SLO handed to the "
                        "server — above the calm p95, far below the "
                        "queueing-collapse p95 the spike causes, so "
                        "breach and calm are both unambiguous")
    p.add_argument("--spike-rate", type=float, default=60.0,
                   help="autoscale-spike twin: loadgen base rate "
                        "(burst = 8x through the middle fifth)")
    p.add_argument("--spike-duration", type=float, default=8.0,
                   help="autoscale-spike twin: loadgen run seconds")
    p.add_argument("--quota-abuse", action="store_true",
                   help="serve twin: the per-client quota scenario — "
                        "one hot client at 10x --quota-rps must be "
                        "clipped with 429+Retry-After while a "
                        "well-behaved client keeps >= 90%% goodput")
    p.add_argument("--cache-storm", action="store_true",
                   help="serve twin (ISSUE 19): duplicate-heavy "
                        "(Zipf) loadgen over a LIVE hot reload — "
                        "zero dropped requests through the swap, and "
                        "zero stale logits after it (every post-swap "
                        "reply must carry the new model epoch; the "
                        "swap hook's generation bump is what makes a "
                        "stale replay impossible)")
    p.add_argument("--quota-rps", type=float, default=20.0,
                   help="quota-abuse twin: per-client requests/sec "
                        "handed to the server")
    p.add_argument("--quota-duration", type=float, default=6.0,
                   help="quota-abuse twin: loadgen run seconds")
    p.add_argument("--quarantine-after", type=int, default=3,
                   help="serve twin: consecutive-failure threshold "
                        "handed to the server (default 3)")
    p.add_argument("--requests", type=int, default=400,
                   help="serve twin: loadgen request count (default "
                        "400)")
    p.add_argument("--cpu-devices", type=int, default=0,
                   help="serve twin: force the server onto the CPU "
                        "backend with this many fake devices (local "
                        "rehearsal on accelerator-less boxes; 0 = "
                        "leave the environment alone)")
    # -- the fleet-federation twins (router over N backends) -----------
    p.add_argument("--fleet", type=int, default=0, metavar="N",
                   help="fleet twin: boot a real `tpu-mnist route` "
                        "router over N real single-chip serve "
                        "subprocesses; combine with --kill-backend / "
                        "--rolling-reload / --fleet-canary-rollback")
    p.add_argument("--kill-backend", type=int, default=None,
                   metavar="K",
                   help="fleet twin: SIGKILL backend K mid-loadgen — "
                        "zero DROPPED requests (router failover + "
                        "loadgen --retry-transport), quarantine, then "
                        "probation re-admission after a restart on the "
                        "old port")
    p.add_argument("--rolling-reload", action="store_true",
                   help="fleet twin: POST /rollout a new epoch across "
                        "the whole fleet under live loadgen — zero "
                        "drops, every backend on the new epoch after")
    p.add_argument("--fleet-canary-rollback", action="store_true",
                   help="fleet twin: publish behind a fleet canary "
                        f"with {FLEET_FAULT_ENV}=canary_disagree "
                        "injected into the router — the canary must "
                        "roll back (baseline weights republished) "
                        "while every request is still answered")
    p.add_argument("--delta-publish", type=int, default=0, metavar="E",
                   help="fleet twin (ISSUE 18): all backends watch ONE "
                        "shared checkpoint dir; E adjacent delta "
                        "publishes land under live loadgen — zero "
                        "drops, every backend converges to the last "
                        "epoch, and each publish's new chunk bytes "
                        "must be a small fraction of the cold "
                        "(whole-state) publish")
    p.add_argument("--torn-manifest", action="store_true",
                   help="delta-distribution twin (ISSUE 18): one serve "
                        "process fed a TORN manifest, then a manifest "
                        "with a missing chunk, then a clean publish — "
                        "both damaged publishes are skipped (recorded, "
                        "never served), serving never stops, and the "
                        "clean publish recovers with no restart")
    p.add_argument("cli_args", nargs=argparse.REMAINDER,
                   help="arguments after -- go to tpu-mnist verbatim")
    args = p.parse_args(argv)

    if args.list:
        list_fault_points()
        return 0

    if args.fleet:
        if args.fleet < 2:
            raise SystemExit("--fleet N needs N >= 2 (a 1-backend "
                             "fleet has no failure domain to survive)")
        return run_fleet_chaos(args)
    if args.kill_backend is not None or args.rolling_reload \
            or args.fleet_canary_rollback or args.delta_publish:
        raise SystemExit("--kill-backend/--rolling-reload/"
                         "--fleet-canary-rollback/--delta-publish are "
                         "fleet twins; add --fleet N")
    if args.torn_manifest:
        return run_torn_manifest(args)
    if args.autoscale_spike:
        return run_autoscale_spike(args)
    if args.quota_abuse:
        return run_quota_abuse(args)
    if args.cache_storm:
        return run_cache_storm(args)
    if args.serve:
        args.resize_targets = [int(t) for t in
                               (args.resize or "").split(",") if t.strip()]
        return run_serve_chaos(args)
    if args.resize or args.serve_fault or args.serve_precision \
            or args.canary_rollback:
        raise SystemExit("--serve-fault/--resize/--serve-precision/"
                         "--canary-rollback are serve-plane twins; "
                         "add --serve")

    if args.dcn_slices:
        if args.dcn_slices < 2 or args.nprocs % args.dcn_slices:
            raise SystemExit(
                f"--dcn-slices {args.dcn_slices} must divide --nprocs "
                f"{args.nprocs} into equal slices (>= 2)")
        os.environ[DCN_SLICES_ENV] = str(args.dcn_slices)
    # No flag: an exported TPUMNIST_DCN_SLICES is the documented env
    # contract and stays in force for the workers (unlike FAULT_ENV,
    # which is chaos's own channel and is cleared below when unused).
    if args.kill_slice is not None:
        if not args.elastic or not args.dcn_slices:
            raise SystemExit(
                "--kill-slice is the elastic slice-loss twin; it "
                "requires --elastic and --dcn-slices")
        per = args.nprocs // args.dcn_slices
        if not 0 <= args.kill_slice < args.dcn_slices:
            raise SystemExit(
                f"--kill-slice {args.kill_slice} is not one of the "
                f"{args.dcn_slices} slices")
        specs = [f"train_step:{h}:kill:5"
                 for h in range(args.kill_slice * per,
                                (args.kill_slice + 1) * per)]
        args.fault = ",".join(specs + ([args.fault] if args.fault else []))
    if args.fault:
        parse_fault_specs(args.fault)  # fail fast with the spec's message
        os.environ[FAULT_ENV] = args.fault
    else:
        os.environ.pop(FAULT_ENV, None)
    os.environ[TIMEOUT_ENV] = str(args.agreement_timeout)

    cli_args = list(args.cli_args)
    if cli_args and cli_args[0] == "--":
        cli_args = cli_args[1:]
    print(f"chaos: spawning {args.nprocs} ranks"
          + (" under the elastic supervisor" if args.elastic else "")
          + (f", fault {args.fault}" if args.fault else " (control run)")
          + f", agreement timeout {args.agreement_timeout:g}s",
          file=sys.stderr)
    if args.elastic:
        return supervise(
            args.nprocs, cli_args, min_world=args.min_world,
            max_world=args.max_world, grow=args.elastic_grow,
            rejoin=_parse_rejoin(args.rejoin) if args.rejoin else (),
            settle_timeout=args.settle_timeout,
            generation_timeout=args.timeout)
    if args.elastic_grow or args.rejoin:
        raise SystemExit("--elastic-grow/--rejoin require --elastic")
    return spawn_local(args.nprocs, cli_args, timeout=args.timeout)


if __name__ == "__main__":
    sys.exit(main())
