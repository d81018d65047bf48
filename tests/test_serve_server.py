"""Serving integration on a real loopback HTTP server (in-process
ThreadingHTTPServer — no subprocess jax boot): the acceptance run
(loadgen >= 1000 requests, zero steady-state recompiles, correct
predictions, /stats quantiles + histogram) and hot reload under live
traffic."""

import json
import os
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_mnist_tpu.data.mnist import synthetic_dataset
from pytorch_distributed_mnist_tpu.models import get_model
from pytorch_distributed_mnist_tpu.serve.server import build_parser, create_server
from pytorch_distributed_mnist_tpu.train.checkpoint import save_checkpoint
from pytorch_distributed_mnist_tpu.train.state import create_train_state
from pytorch_distributed_mnist_tpu.utils.profiling import compile_log

pytestmark = pytest.mark.serve

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _publish(ckpt_dir, epoch, seed):
    model = get_model("linear", compute_dtype=jnp.float32)
    state = create_train_state(model, jax.random.key(seed))
    save_checkpoint(state, epoch=epoch, best_acc=0.5, is_best=False,
                    directory=str(ckpt_dir), process_index=0)
    return state


def _serve_args(ckpt_dir, **overrides):
    argv = [
        "--checkpoint-dir", str(ckpt_dir),
        "--model", "linear", "--dtype", "f32",
        "--host", "127.0.0.1", "--port", "0",
        "--buckets", "1,8,32",
        "--max-wait-ms", "2", "--max-queue", "128",
        "--poll-interval", "0.1",
    ]
    for k, v in overrides.items():
        flag = "--" + k.replace("_", "-")
        if v is True:
            argv.append(flag)
        else:
            argv += [flag, str(v)]
    return build_parser().parse_args(argv)


class _Server:
    def __init__(self, args):
        self.httpd = create_server(args)
        host, port = self.httpd.server_address[:2]
        self.url = f"http://{host}:{port}"
        self.thread = threading.Thread(
            target=self.httpd.serve_forever, daemon=True)
        self.thread.start()

    def close(self):
        self.httpd.shutdown()
        self.httpd.ctx.close()
        self.httpd.server_close()
        self.thread.join(10.0)

    def get(self, path):
        with urllib.request.urlopen(self.url + path, timeout=30) as r:
            return json.loads(r.read())

    def post(self, path, payload):
        req = urllib.request.Request(
            self.url + path, data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=30) as r:
            return json.loads(r.read())


@pytest.fixture()
def server(tmp_path):
    ckpt = tmp_path / "ckpt"
    state = _publish(ckpt, epoch=0, seed=10)
    srv = _Server(_serve_args(ckpt))
    try:
        yield srv, state, ckpt
    finally:
        srv.close()


def test_predict_healthz_stats(server):
    srv, state, _ = server
    images, _ = synthetic_dataset(5, seed=7)

    health = srv.get("/healthz")
    assert health["ok"] and health["model_epoch"] == 0
    # What answered, in jax's own words — clients assert the device here.
    device = jax.devices()[0]
    assert (health["platform"], health["device_kind"],
            health["device_count"]) \
        == (device.platform, device.device_kind, jax.device_count())
    assert health["input_backend"] in ("native", "numpy")
    assert set(health["pallas_lowerings"]) == {"mosaic", "interpret"}
    assert {"folded_sites", "kept_results"} <= set(health["flash_schedules"])
    assert "sliced_sites" in health["dense_attention_slices"]
    assert "whole_head_sites" in health["rotary_sites"]

    reply = srv.post("/predict", {"images": images.tolist()})
    assert len(reply["predictions"]) == 5
    assert reply["model_epoch"] == 0
    # Correctness vs the direct forward pass on the SAME preprocessing.
    from pytorch_distributed_mnist_tpu.data.mnist import normalize_images

    model = get_model("linear", compute_dtype=jnp.float32)
    want = np.argmax(np.asarray(model.apply(
        state.params, jnp.asarray(normalize_images(images)), train=False)),
        axis=-1)
    assert reply["predictions"] == [int(v) for v in want]

    # Single image without the leading axis works too.
    single = srv.post("/predict", {"images": images[0].tolist()})
    assert single["predictions"] == [int(want[0])]

    stats = srv.get("/stats")
    assert stats["requests"] >= 2
    assert {"p50", "p95", "p99"} <= set(stats["latency_ms"])
    # Superset, not equality: CompileLog is a process singleton, so a
    # full-suite run sees bucket programs other serve tests compiled too.
    assert {"serve_forward_b1", "serve_forward_b8",
            "serve_forward_b32"} <= set(stats["compile"]["programs"])

    assert srv.post("/predict", {"images": images.tolist()}) is not None
    bad = urllib.request.Request(
        srv.url + "/predict", data=b'{"images": "nonsense"}',
        headers={"Content-Type": "application/json"})
    try:
        urllib.request.urlopen(bad, timeout=30)
        raised = False
    except urllib.error.HTTPError as exc:
        raised = exc.code == 400
        exc.read()
    assert raised


def test_loadgen_acceptance_zero_recompiles(server):
    """The PR's acceptance run: >= 1000 loadgen requests against a warm
    server complete with ZERO steady-state recompiles (CompileLog), and
    /stats carries the latency quantiles and batch-size histogram."""
    srv, _, _ = server
    # settle: one request through every bucket path before the snapshot
    images, _ = synthetic_dataset(3, seed=0)
    srv.post("/predict", {"images": images.tolist()})
    baseline_compiles = compile_log.stats()["totals"]["backend_compiles"]

    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "loadgen.py"),
         "--smoke", "--url", srv.url, "--requests", "1000",
         "--concurrency", "8"],
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    report = json.loads(proc.stdout.strip().splitlines()[-1])
    assert report["smoke_ok"] and report["ok"] == 1000
    assert report["transport_errors"] == 0 and report["rejected"] == 0
    assert report["latency_ms"]["p99"] >= report["latency_ms"]["p50"] > 0

    # Zero steady-state recompiles: 1000 requests did not add a single
    # XLA backend compile beyond the AOT warmup.
    assert compile_log.stats()["totals"]["backend_compiles"] \
        == baseline_compiles

    stats = srv.get("/stats")
    assert stats["requests"] >= 1001
    assert stats["latency_ms"]["p99"] >= stats["latency_ms"]["p50"] > 0
    hist = stats["batch_histogram"]
    assert hist and all(k in ("1", "8", "32") for k in hist)
    assert sum(hist.values()) == stats["batches"]
    for rec in stats["compile"]["programs"].values():
        assert rec["backend_compiles"] >= 0  # present per bucket


def test_stats_fused_flag_and_loadgen_expectation(server):
    """The whole-program plane is the server default: /stats carries
    fused=true and ``loadgen --smoke --expect-fused`` passes; a
    ``--no-fuse`` server reports fused=false and FAILS the same
    expectation (the flag has teeth)."""
    srv, _, ckpt = server
    assert srv.get("/stats")["fused"] is True
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "loadgen.py"),
         "--smoke", "--url", srv.url, "--requests", "40",
         "--concurrency", "4", "--expect-fused"],
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    report = json.loads(proc.stdout.strip().splitlines()[-1])
    assert report["fused"] is True  # the advisory shape field rode along
    # The donation lifecycle's observable (§7k): every fused dispatch
    # donated-and-retired its staging buffer, so the per-bucket counter
    # must have kept pace with the traffic just driven.
    stats = srv.get("/stats")
    assert sum(stats["donated_staging_retired"].values()) > 0

    nofuse = _Server(_serve_args(ckpt, no_fuse=True))
    try:
        nf_stats = nofuse.get("/stats")
        assert nf_stats["fused"] is False
        # Nothing donates on the split plane — the key stays absent.
        assert "donated_staging_retired" not in nf_stats
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "loadgen.py"),
             "--smoke", "--url", nofuse.url, "--requests", "8",
             "--concurrency", "2", "--expect-fused"],
            capture_output=True, text=True, timeout=300,
        )
        assert proc.returncode == 1, proc.stdout + proc.stderr
    finally:
        nofuse.close()


def test_hot_reload_under_live_traffic(server):
    """Publish a new checkpoint while clients hammer /predict: no request
    fails or returns malformed output, and predictions/epoch flip to the
    new params within a few poll intervals."""
    srv, state_a, ckpt = server
    images, _ = synthetic_dataset(4, seed=3)
    payload = {"images": images.tolist()}
    failures = []
    stop = threading.Event()

    def hammer():
        while not stop.is_set():
            try:
                reply = srv.post("/predict", payload)
                preds = reply["predictions"]
                if (len(preds) != 4
                        or not all(0 <= p <= 9 for p in preds)
                        or reply["model_epoch"] not in (0, 9)):
                    failures.append(("malformed", reply))
            except Exception as exc:  # noqa: BLE001
                failures.append(("error", repr(exc)))

    threads = [threading.Thread(target=hammer, daemon=True)
               for _ in range(4)]
    for t in threads:
        t.start()
    time.sleep(0.3)  # in-flight traffic established
    state_b = _publish(ckpt, epoch=9, seed=77)
    deadline = time.time() + 15.0
    while time.time() < deadline:
        if srv.get("/healthz")["model_epoch"] == 9:
            break
        time.sleep(0.05)
    time.sleep(0.3)  # keep hammering across the swap boundary
    stop.set()
    for t in threads:
        t.join(10.0)

    assert not failures, failures[:5]
    assert srv.get("/healthz")["model_epoch"] == 9
    # Steady state now answers with the NEW params.
    from pytorch_distributed_mnist_tpu.data.mnist import normalize_images

    model = get_model("linear", compute_dtype=jnp.float32)
    want = np.argmax(np.asarray(model.apply(
        state_b.params, jnp.asarray(normalize_images(images)),
        train=False)), axis=-1)
    assert srv.post("/predict", payload)["predictions"] \
        == [int(v) for v in want]
    assert srv.get("/stats")["reloads"] == 1


def test_overload_returns_503(tmp_path):
    """Admission control surfaces as HTTP 503, not latency: wedge the
    engine via a gated executable, fill the queue, and watch overflow
    requests bounce."""
    ckpt = tmp_path / "ckpt"
    _publish(ckpt, epoch=0, seed=10)
    srv = _Server(_serve_args(ckpt, max_queue=2, max_wait_ms=1))
    try:
        engine = srv.httpd.ctx.engine
        release = threading.Event()
        entered = threading.Event()

        def gate(fn):
            def gated(params, x):
                entered.set()
                release.wait(30.0)
                return fn(params, x)
            return gated

        # Wedge BOTH dispatch planes: raw uint8 requests ride the fused
        # bucket programs (the server default), float input the split
        # ones — the overload behavior under test is plane-independent.
        for table in (engine._compiled, engine._fused_compiled):
            for b, fn in list(table.items()):
                table[b] = gate(fn)
        images, _ = synthetic_dataset(1, seed=0)
        payload = {"images": images.tolist()}
        results = []

        def fire():
            try:
                srv.post("/predict", payload)
                results.append(200)
            except urllib.error.HTTPError as exc:
                exc.read()
                results.append(exc.code)

        threads = [threading.Thread(target=fire, daemon=True)
                   for _ in range(6)]
        threads[0].start()
        assert entered.wait(10.0)  # worker wedged inside the forward
        time.sleep(0.2)  # its batch has drained from the queue
        for t in threads[1:]:
            t.start()
        time.sleep(0.5)  # queue (2) full, the rest must be bouncing
        release.set()
        for t in threads:
            t.join(15.0)
        assert results.count(503) >= 1, results
        assert results.count(200) >= 3, results
        assert srv.get("/stats")["rejected"] >= 1
    finally:
        release.set()
        srv.close()


def test_no_checkpoint_serves_fresh_until_publish(tmp_path):
    """Boot with an empty dir: fresh-init params serve immediately, the
    first published checkpoint is hot-loaded."""
    ckpt = tmp_path / "empty"
    srv = _Server(_serve_args(ckpt))
    try:
        assert srv.get("/healthz")["model_epoch"] is None
        images, _ = synthetic_dataset(2, seed=1)
        assert len(srv.post("/predict",
                            {"images": images.tolist()})["predictions"]) == 2
        _publish(ckpt, epoch=3, seed=50)
        deadline = time.time() + 15.0
        while time.time() < deadline:
            if srv.get("/healthz")["model_epoch"] == 3:
                break
            time.sleep(0.05)
        assert srv.get("/healthz")["model_epoch"] == 3
    finally:
        srv.close()


def test_require_checkpoint_refuses_empty_dir(tmp_path):
    with pytest.raises(SystemExit, match="require-checkpoint"):
        create_server(_serve_args(tmp_path / "none",
                                  require_checkpoint=True))


def test_request_size_caps(tmp_path):
    """One giant request must not sneak past admission control: row
    count over --max-request-images is a 400, and an oversized body is
    refused (413) BEFORE being read/parsed."""
    import http.client

    ckpt = tmp_path / "ckpt"
    _publish(ckpt, epoch=0, seed=10)
    srv = _Server(_serve_args(ckpt, max_request_images=4))
    try:
        images, _ = synthetic_dataset(5, seed=0)
        try:
            srv.post("/predict", {"images": images.tolist()})
            code = 200
        except urllib.error.HTTPError as exc:
            code = exc.code
            body = json.loads(exc.read())
        assert code == 400 and "batch client-side" in body["error"]
        # 4 images (the cap) still serve fine.
        assert len(srv.post("/predict",
                            {"images": images[:4].tolist()})
                   ["predictions"]) == 4

        host, port = srv.httpd.server_address[:2]
        conn = http.client.HTTPConnection(host, port, timeout=30)
        conn.putrequest("POST", "/predict")
        conn.putheader("Content-Type", "application/json")
        conn.putheader("Content-Length", str(64 << 20))  # claimed 64 MB
        conn.endheaders()
        resp = conn.getresponse()  # refused before the body arrives
        assert resp.status == 413
        conn.close()
    finally:
        srv.close()


def test_predict_reports_epoch_of_computing_params(server):
    """The model_epoch in a /predict reply is captured WITH the params
    that computed the batch (engine tag), not read from the engine after
    the fact — a hot reload between compute and reply can't mislabel."""
    srv, _, ckpt = server
    images, _ = synthetic_dataset(2, seed=5)
    assert srv.post("/predict",
                    {"images": images.tolist()})["model_epoch"] == 0
    _publish(ckpt, epoch=4, seed=99)
    deadline = time.time() + 15.0
    while time.time() < deadline:
        if srv.get("/healthz")["model_epoch"] == 4:
            break
        time.sleep(0.05)
    assert srv.post("/predict",
                    {"images": images.tolist()})["model_epoch"] == 4


def test_drain_rejects_with_retry_after(server):
    """POST /drain closes admission: new /predict bounces 503 with a
    Retry-After header and a body naming the draining state, /healthz
    and /stats both expose draining=true, and /stats active_requests
    reaches zero (the rolling-reload wait-for-quiescent contract)."""
    srv, _, _ = server
    images, _ = synthetic_dataset(2, seed=1)
    payload = {"images": images.tolist()}
    assert len(srv.post("/predict", payload)["predictions"]) == 2

    reply = srv.post("/drain", {"drain": True})
    assert reply["ok"] and reply["draining"] and not reply["was_draining"]
    assert srv.get("/healthz")["draining"] is True
    stats = srv.get("/stats")
    assert stats["draining"] is True
    assert stats["active_requests"] == 0  # nothing in flight = quiescent

    try:
        srv.post("/predict", payload)
        code, headers, body = 200, {}, {}
    except urllib.error.HTTPError as exc:
        code = exc.code
        headers = exc.headers
        body = json.loads(exc.read())
    assert code == 503
    assert body["draining"] is True and body["error"] == "draining"
    assert int(headers["Retry-After"]) >= 1  # the back-off contract

    # Idempotent: draining an already-draining server reports it was.
    assert srv.post("/drain", {"drain": True})["was_draining"] is True


def test_drain_then_rejoin_serves_again(server):
    """Undrain reopens admission with no restart: the same server that
    just bounced traffic answers again — the rolling reload's rejoin
    step is a state flip, not a process bounce."""
    srv, _, _ = server
    images, _ = synthetic_dataset(2, seed=4)
    payload = {"images": images.tolist()}
    srv.post("/drain", {"drain": True})
    with pytest.raises(urllib.error.HTTPError) as exc_info:
        srv.post("/predict", payload)
    exc_info.value.read()
    assert exc_info.value.code == 503

    reply = srv.post("/drain", {"drain": False})
    assert reply["ok"] and not reply["draining"] and reply["was_draining"]
    assert srv.get("/healthz")["draining"] is False
    assert len(srv.post("/predict", payload)["predictions"]) == 2
    assert srv.get("/stats")["draining"] is False

    # Malformed drain bodies are a client error, not a state change.
    bad = urllib.request.Request(
        srv.url + "/drain", data=b'{"drain": "yes"}',
        headers={"Content-Type": "application/json"})
    with pytest.raises(urllib.error.HTTPError) as exc_info:
        urllib.request.urlopen(bad, timeout=30)
    exc_info.value.read()
    assert exc_info.value.code == 400
    assert srv.get("/healthz")["draining"] is False


def test_boot_falls_back_past_corrupt_latest(tmp_path):
    """A corrupt latest checkpoint must not turn a server restart into
    an outage: boot walks to the next-older epoch (the serving analog of
    --resume auto's fallback; quarantining stays the trainer's job)."""
    ckpt = tmp_path / "ckpt"
    state_good = _publish(ckpt, epoch=1, seed=10)
    with open(ckpt / "checkpoint_2.npz", "wb") as f:
        f.write(b"definitely not an npz")
    srv = _Server(_serve_args(ckpt))
    try:
        health = srv.get("/healthz")
        assert health["model_epoch"] == 1
        assert health["checkpoint"].endswith("checkpoint_1.npz")
        images, _ = synthetic_dataset(3, seed=2)
        from pytorch_distributed_mnist_tpu.data.mnist import (
            normalize_images,
        )

        model = get_model("linear", compute_dtype=jnp.float32)
        want = np.argmax(np.asarray(model.apply(
            state_good.params, jnp.asarray(normalize_images(images)),
            train=False)), axis=-1)
        got = srv.post("/predict", {"images": images.tolist()})
        assert got["predictions"] == [int(v) for v in want]
    finally:
        srv.close()
