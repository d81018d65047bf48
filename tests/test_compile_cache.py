"""Compile-latency subsystem tests: shared persistent-cache wiring
(utils/compile_cache.py), compile observability (utils/profiling.py
CompileLog), and the trainer's AOT precompile (train/steps.py +
train/trainer.py).

The persistent cache is off inside this pytest process by default
(tests/conftest.py exports an empty JAX_COMPILATION_CACHE_DIR so the suite
is deterministic and writes nothing into the checkout). Cache-ON behaviour
is exercised through an explicit directory, mostly in fresh subprocesses —
the production pattern (cold run writes, warm fresh process reads).
"""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from pytorch_distributed_mnist_tpu.utils import compile_cache  # noqa: E402
from pytorch_distributed_mnist_tpu.utils.profiling import (  # noqa: E402
    CompileLog,
    compile_log,
)


@pytest.fixture
def cache_module_state():
    """Restore the jax cache config after a test that calls configure(),
    so it can't leak into the suite (which runs with the cache off)."""
    saved_cfg = (
        jax.config.jax_compilation_cache_dir,
        jax.config.jax_persistent_cache_min_compile_time_secs,
        jax.config.jax_persistent_cache_min_entry_size_bytes,
    )
    yield
    jax.config.update("jax_compilation_cache_dir", saved_cfg[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      saved_cfg[1])
    jax.config.update("jax_persistent_cache_min_entry_size_bytes",
                      saved_cfg[2])


# -- resolution precedence --------------------------------------------------


def test_env_dir_beats_flag_dir(monkeypatch):
    """A cache placed from outside wins over a directory named in code or
    flags; only an explicit empty flag (disable) outranks it."""
    monkeypatch.setenv(compile_cache.ENV_VAR, "/env/dir")
    assert compile_cache.resolve_cache_dir("/flag/dir") == "/env/dir"
    assert compile_cache.resolve_cache_dir(None) == "/env/dir"
    assert compile_cache.resolve_cache_dir("") is None


def test_flag_beats_empty_env_and_default(monkeypatch):
    monkeypatch.setenv(compile_cache.ENV_VAR, "")
    assert compile_cache.resolve_cache_dir("/flag/dir") == "/flag/dir"
    # Set-but-empty variable, no flag: disabled (what this suite exports).
    assert compile_cache.resolve_cache_dir(None) is None
    monkeypatch.delenv(compile_cache.ENV_VAR)
    assert compile_cache.resolve_cache_dir("/flag/dir") == "/flag/dir"


def test_default_is_checkout_xla_cache(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    assert compile_cache.resolve_cache_dir(None) \
        == os.path.join(REPO, ".xla_cache")


def test_configure_keeps_env_dir(cache_module_state, monkeypatch, tmp_path):
    """With the variable set, configure() activates THAT directory
    whatever the flag says, and zeroes the thresholds (explicit dir:
    cache every program)."""
    env_dir = tmp_path / "from_env"
    monkeypatch.setenv(compile_cache.ENV_VAR, str(env_dir))
    got = compile_cache.configure(str(tmp_path / "from_flag"))
    assert got == str(env_dir) and env_dir.is_dir()
    assert compile_cache.active_cache_dir() == str(env_dir)
    assert not (tmp_path / "from_flag").exists()
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0


def test_configure_creates_dir_once(cache_module_state, tmp_path):
    target = tmp_path / "cache"
    assert not target.exists()
    got = compile_cache.configure(str(target))
    assert got == str(target) and target.is_dir()
    assert compile_cache.active_cache_dir() == str(target)
    # Idempotent: same dir again is a no-op (no reset, no error).
    assert compile_cache.configure(str(target)) == str(target)
    # Explicit disable turns it off entirely.
    assert compile_cache.configure("") is None
    assert compile_cache.active_cache_dir() is None


# -- CompileLog -------------------------------------------------------------


def test_compile_log_counts_backend_compiles():
    log = CompileLog()
    with log.measure("tiny"):
        jax.jit(lambda x: x * 2 + 1).lower(
            jax.ShapeDtypeStruct((4,), np.float32)).compile()
    log.close()
    rec = log.stats()["programs"]["tiny"]
    assert rec["backend_compiles"] >= 1
    assert rec["backend_compile_ms"] > 0
    assert rec["wall_ms"] >= rec["backend_compile_ms"] * 0.5
    # Persistent cache is off in-process: hit/miss must be None, not False.
    assert rec["persistent_cache_hit"] is None


def test_compile_log_close_detaches_listeners():
    """close() unregisters through the public jax.monitoring API: a closed
    log no longer hears compiles, and closing twice is harmless."""
    log = CompileLog()
    spec = jax.ShapeDtypeStruct((4,), np.float32)
    with log.measure("heard"):
        jax.jit(lambda x: x * 3 + 1).lower(spec).compile()
    log.close()
    heard = log.stats()["totals"]["backend_compiles"]
    assert heard >= 1
    jax.jit(lambda x: x * 5 - 1).lower(spec).compile()
    assert log.stats()["totals"]["backend_compiles"] == heard
    log.close()


def test_compile_log_thread_attribution():
    """Concurrent measures must not misfile each other's compiles: the
    listener attributes to the measuring THREAD's open record."""
    import threading

    log = CompileLog()
    done = []

    def work(name, k):
        with log.measure(name):
            jax.jit(lambda x, k=k: x + k).lower(
                jax.ShapeDtypeStruct((8, k + 1), np.float32)).compile()
        done.append(name)

    threads = [threading.Thread(target=work, args=(f"prog{k}", k))
               for k in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    log.close()
    stats = log.stats()["programs"]
    assert sorted(done) == ["prog0", "prog1", "prog2"]
    for k in range(3):
        assert stats[f"prog{k}"]["backend_compiles"] >= 1
    total = log.stats()["totals"]["backend_compiles"]
    assert total == sum(stats[f"prog{k}"]["backend_compiles"]
                        for k in range(3))


def test_compile_log_hit_miss_counters_subprocess(tmp_path):
    """Cache hit/miss counters against a REAL persistent cache — in a
    fresh child per phase (cold writes, warm reads: the safe patterns)."""
    code = """
import os, json
os.environ["JAX_PLATFORMS"] = "cpu"
import jax, numpy as np
jax.config.update("jax_platforms", "cpu")
import sys; sys.path.insert(0, {repo!r})
from pytorch_distributed_mnist_tpu.utils import compile_cache
from pytorch_distributed_mnist_tpu.utils.profiling import CompileLog
compile_cache.configure({cache!r})
log = CompileLog()
with log.measure("p"):
    jax.jit(lambda x: x @ x.T).lower(
        jax.ShapeDtypeStruct((16, 16), np.float32)).compile()
print("STATS=" + json.dumps(log.stats()["programs"]["p"]))
""".format(repo=REPO, cache=str(tmp_path / "cache"))
    out = []
    for phase in ("cold", "warm"):
        proc = subprocess.run([sys.executable, "-c", code],
                              capture_output=True, text=True, timeout=300)
        assert proc.returncode == 0, proc.stderr[-2000:]
        line = [l for l in proc.stdout.splitlines()
                if l.startswith("STATS=")][-1]
        out.append(json.loads(line[len("STATS="):]))
    cold, warm = out
    assert cold["cache_misses"] >= 1 and cold["persistent_cache_hit"] is False
    assert warm["cache_misses"] == 0 and warm["cache_hits"] >= 1
    assert warm["persistent_cache_hit"] is True


# -- AOT precompile ---------------------------------------------------------


def _build_trainer(mode="scan", gather="host", seed=0):
    from pytorch_distributed_mnist_tpu.data.loader import MNISTDataLoader
    from pytorch_distributed_mnist_tpu.data.mnist import (
        normalize_images,
        synthetic_dataset,
    )
    from pytorch_distributed_mnist_tpu.models import get_model
    from pytorch_distributed_mnist_tpu.parallel.mesh import make_mesh
    from pytorch_distributed_mnist_tpu.train.state import create_train_state
    from pytorch_distributed_mnist_tpu.train.trainer import Trainer

    images, labels = synthetic_dataset(256, seed=7)
    x = normalize_images(images)
    y = labels.astype(np.int32)
    train = MNISTDataLoader(x, y, batch_size=64, train=True, seed=seed)
    test = MNISTDataLoader(x[:128], y[:128], batch_size=64, train=False,
                           seed=seed)
    state = create_train_state(get_model("linear"), jax.random.key(seed))
    return Trainer(state, train, test, mesh=make_mesh(("data",)),
                   mode=mode, epoch_gather=gather)


def _count_backend_compiles(fn):
    """Backend-compile events fired while ``fn()`` runs on THIS thread."""
    from jax import monitoring

    events = []

    def listener(name, secs, **kw):
        if "backend_compile" in name:
            events.append(name)

    monitoring.register_event_duration_secs_listener(listener)
    try:
        fn()
    finally:
        monitoring.unregister_event_duration_listener(listener)
    return len(events)


@pytest.mark.parametrize("mode,gather", [
    ("scan", "host"), ("scan", "device"), ("stepwise", "host"),
    ("explicit", "host"),
])
def test_precompile_first_step_compiles_nothing(mode, gather):
    """The acceptance hook: after precompile(wait=True), the first real
    train+eval pass triggers ZERO further XLA compiles of the trainer's
    programs — the precompiled executable IS the one the step uses.

    (A one-time scalar-add helper for stepwise metric accumulation is
    compiled at most once per process; it is warmed here before
    measuring so the assertion pins the trainer's programs alone.)"""
    compile_log.reset()
    tr = _build_trainer(mode, gather)
    tr.precompile(wait=True)
    # Warm the scalar f32 add the stepwise meter accumulation uses: the
    # MetricState leaves are f32[] REPLICATED ON THE MESH (program
    # outputs), and that one-per-process helper program is outside what
    # precompile covers (it is not a trainer program).
    from jax.sharding import NamedSharding, PartitionSpec as P

    _z = jax.device_put(jax.numpy.zeros((), jax.numpy.float32),
                        NamedSharding(tr.mesh, P()))
    float(_z + _z)
    assert len(tr._precompiled) == 2  # both programs really built

    def first_epoch():
        tr.train()
        tr.evaluate()

    assert _count_backend_compiles(first_epoch) == 0
    # Every program the mode runs was logged with a real compile.
    programs = compile_log.stats()["programs"]
    assert all(rec["backend_compiles"] >= 1 for rec in programs.values())
    assert len(programs) == 2


def test_precompile_trajectory_identical_to_lazy():
    """Background precompile racing the host staging must not change a
    single bit of the trajectory vs the lazy path."""
    a = _build_trainer()
    a.precompile()  # background threads; train() overlaps staging + joins
    b = _build_trainer()
    rows = []
    for tr in (a, b):
        hist = []
        for epoch in range(2):
            tr.train_loader.set_sample_epoch(epoch)
            l, acc = tr.train()
            el, ea = tr.evaluate()
            hist.append((l.average, acc.accuracy, el.average, ea.accuracy))
        rows.append(hist)
    assert rows[0] == rows[1]
    pa = jax.tree_util.tree_leaves(a.state.params)
    pb = jax.tree_util.tree_leaves(b.state.params)
    for la, lb in zip(pa, pb):
        assert np.array_equal(np.asarray(la), np.asarray(lb))


def test_precompile_signature_mismatch_falls_back(capsys):
    """A loader swap after precompile must degrade to lazy compilation,
    not crash: the stale executable is dropped and jit recompiles."""
    tr = _build_trainer()
    tr.precompile(wait=True)
    # Change the epoch length out from under the precompiled program.
    from pytorch_distributed_mnist_tpu.data.loader import MNISTDataLoader
    from pytorch_distributed_mnist_tpu.data.mnist import (
        normalize_images,
        synthetic_dataset,
    )

    images, labels = synthetic_dataset(128, seed=9)
    tr.train_loader = MNISTDataLoader(
        normalize_images(images), labels.astype(np.int32),
        batch_size=64, train=True, seed=0)
    loss, acc = tr.train()  # steps_per_epoch changed: 4 -> 2
    assert acc.count == 128
    assert "no longer matches" in capsys.readouterr().err


def test_precompile_specs_match_staging():
    """The loader's spec methods must mirror exactly what staging
    produces — this is what makes AOT lowering hit the same program."""
    tr = _build_trainer()
    staged = tr.train_loader.stacked_epoch()
    spec = tr.train_loader.epoch_spec()
    assert set(staged) == set(spec)
    for k, v in staged.items():
        assert spec[k].shape == v.shape, k
        assert spec[k].dtype == v.dtype, k
    idx, mask = tr.train_loader.epoch_ticks()
    tspec = tr.train_loader.ticks_spec()
    assert tspec["idx"].shape == idx.shape
    assert tspec["mask"].shape == mask.shape


# -- shared wiring across entry points --------------------------------------


def test_cli_run_routes_its_flag_through_configure(
        cache_module_state, monkeypatch, tmp_path):
    """Acceptance: cli.run() passes its --compile-cache flag to
    utils/compile_cache.configure, the one persistent-cache wiring every
    entry point shares — no config-update code of its own.

    configure is stubbed to RECORD without applying (the suite runs with
    the cache off); the application side is covered by
    test_configure_creates_dir_once / test_configure_keeps_env_dir and the
    subprocess tests below."""
    calls = []
    monkeypatch.setattr(compile_cache, "configure",
                        lambda flag=None: calls.append(flag) or flag)

    from pytorch_distributed_mnist_tpu.cli import build_parser, run

    args = build_parser().parse_args([
        "--dataset", "synthetic", "--model", "linear",
        "--batch-size", "64", "--synthetic-train-size", "128",
        "--synthetic-test-size", "64", "--seed", "0", "--epochs", "0",
        "--compile-cache", str(tmp_path / "cli"),
        "--checkpoint-dir", str(tmp_path / "ckpt"),
    ])
    run(args)
    assert calls == [str(tmp_path / "cli")]


def test_cli_summary_carries_compile_stats(tmp_path):
    from pytorch_distributed_mnist_tpu.cli import build_parser, run

    summary = run(build_parser().parse_args([
        "--dataset", "synthetic", "--model", "linear",
        "--batch-size", "64", "--synthetic-train-size", "128",
        "--synthetic-test-size", "64", "--seed", "0", "--epochs", "1",
        "--checkpoint-dir", str(tmp_path / "ckpt"),
    ]))
    programs = summary["compile_stats"]["programs"]
    assert "train_epoch" in programs and "eval_epoch" in programs
    assert programs["train_epoch"]["backend_compiles"] >= 1


# -- warm second run (the acceptance criterion) -----------------------------


_WARM_RUN_CODE = """
import os, json
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
import sys; sys.path.insert(0, {repo!r})
from pytorch_distributed_mnist_tpu.cli import build_parser, run
summary = run(build_parser().parse_args([
    "--dataset", "synthetic", "--model", "linear",
    "--batch-size", "64", "--synthetic-train-size", "128",
    "--synthetic-test-size", "64", "--seed", "0", "--epochs", "1",
    "--checkpoint-dir", {ckpt!r}, "--compile-cache", {cache!r},
]))
print("TOTALS=" + json.dumps(summary["compile_stats"]["totals"]))
"""


def test_warm_second_run_recompiles_zero_programs(tmp_path):
    """Acceptance: with the persistent cache, a warm second run on CPU
    recompiles ZERO programs — every XLA compile request is a cache hit
    (compile-count hook == 0 misses after precompile + cache)."""
    cache = str(tmp_path / "cache")
    totals = []
    for phase in ("cold", "warm"):
        code = _WARM_RUN_CODE.format(
            repo=REPO, cache=cache, ckpt=str(tmp_path / ("ck_" + phase)))
        proc = subprocess.run([sys.executable, "-c", code],
                              capture_output=True, text=True, timeout=600)
        assert proc.returncode == 0, proc.stderr[-2000:]
        line = [l for l in proc.stdout.splitlines()
                if l.startswith("TOTALS=")][-1]
        totals.append(json.loads(line[len("TOTALS="):]))
    cold, warm = totals
    assert cold["cache_misses"] >= 2  # train + eval programs really compiled
    assert warm["cache_misses"] == 0  # the criterion: zero recompiles
    assert warm["cache_hits"] >= 2


def test_compile_report_renders_stats(tmp_path, capsys):
    """tools/compile_report.py renders the compile_stats of any JSON line
    that carries one, a --metrics-file's run_summary row among them, and
    exits nonzero when no block exists."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import compile_report

    stats = {"programs": {"train_epoch": {
        "wall_ms": 1234.0, "backend_compiles": 1,
        "backend_compile_ms": 900.0, "cache_hits": 0, "cache_misses": 1,
        "persistent_cache_hit": False}},
        "totals": {"cache_hits": 0, "cache_misses": 1,
                   "backend_compiles": 1, "backend_compile_ms": 900.0}}
    direct = tmp_path / "plain.json"
    direct.write_text(json.dumps({"compile_stats": stats}) + "\n")
    summary = tmp_path / "metrics.jsonl"
    summary.write_text(
        json.dumps({"epoch": 0, "train_loss": 1.0}) + "\n"
        + json.dumps({"kind": "run_summary", "platform": "tpu",
                      "compile_stats": stats}) + "\n")
    empty = tmp_path / "old.json"
    empty.write_text(json.dumps({"kind": "m", "value": 1.0}) + "\n")

    assert compile_report.main([str(direct), str(summary)]) == 0
    out = capsys.readouterr().out
    assert out.count("train_epoch") == 2 and "run_summary [tpu]" in out
    assert "miss" in out
    assert compile_report.main([str(empty)]) == 1
    assert compile_report.main([]) == 1
