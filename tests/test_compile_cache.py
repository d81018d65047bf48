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
    heard = log.stats()["totals"]
    assert heard["backend_compiles"] >= 1
    assert heard["trace_ms"] > 0 and heard["lower_ms"] > 0
    jax.jit(lambda x: x * 5 - 1).lower(spec).compile()
    # Backend, trace, lower and cache-load durations all come through the
    # one listener that close() took away.
    assert log.stats()["totals"] == heard
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
    # The load is told apart from a compile: none where the program was
    # compiled, and inside the backend's duration where it was loaded.
    assert cold["cache_load_ms"] == 0
    assert 0 < warm["cache_load_ms"] <= warm["backend_compile_ms"]


# -- CompileLog: trace and lower seconds, spans --------------------------------


def _heard_durations(fn):
    """``(name, seconds, fun_name)`` of every duration jax.monitoring fired
    while ``fn()`` ran: what a listener that summed would see."""
    from jax import monitoring

    events = []

    def spy(name, secs, **kw):
        events.append((name.rsplit("/", 1)[-1], secs, kw.get("fun_name")))

    monitoring.register_event_duration_secs_listener(spy)
    try:
        fn()
    finally:
        monitoring.unregister_event_duration_listener(spy)
    return events


def test_compile_log_trace_is_the_union_of_nested_traces():
    """A jitted function called inside another's trace fires its own event
    inside the outer one's interval: ``trace_ms`` is the union's length,
    not the events' sum, and ``functions`` splits it into self seconds."""
    import time

    import jax.numpy as jnp

    @jax.jit
    def inner(x):
        time.sleep(0.02)  # at trace time only: once a new shape or dtype
        return jnp.sin(x) * 2.0

    @jax.jit
    def outer(x):
        x = inner(x) + inner(x.astype(jnp.bfloat16)).astype(x.dtype)
        for _ in range(3):
            x = inner(x) + 1.0  # traced already: microseconds
        return x

    log = CompileLog()
    t0 = time.perf_counter()

    def lower():
        with log.measure("p"):
            outer.lower(jax.ShapeDtypeStruct((4,), np.float32))

    events = _heard_durations(lower)
    wall_ms = (time.perf_counter() - t0) * 1e3
    log.close()
    rec = log.stats()["programs"]["p"]
    traces = [(secs, fun) for kind, secs, fun in events
              if kind == "jaxpr_trace_duration"]
    outer_ms = max(secs for secs, fun in traces if fun == "outer") * 1e3
    summed_ms = sum(secs for secs, _ in traces) * 1e3
    # Both slow traces of inner lie inside outer's: a sum counts them twice.
    assert summed_ms >= outer_ms + 40
    assert outer_ms - 1 <= rec["trace_ms"] <= wall_ms
    assert rec["trace_ms"] < summed_ms - 30
    by_name = {f["name"]: f for f in rec["functions"]}
    assert by_name["inner"]["calls"] == 5 and by_name["outer"]["calls"] == 1
    assert by_name["inner"]["trace_ms"] >= 40
    # outer's self time is its interval less inner's: nowhere near 40 ms,
    # and lowering's "jit(outer)" is filed under the same name.
    assert by_name["outer"]["trace_ms"] < outer_ms - 35
    assert by_name["outer"]["lower_ms"] == rec["lower_ms"] > 0
    assert len(rec["functions"]) <= CompileLog.TOP_FUNCTIONS
    self_ms = sum(row[0] for row in
                  log._programs["p"]["functions"].values())
    assert self_ms == pytest.approx(rec["trace_ms"], abs=0.1)
    assert log.stats()["totals"]["trace_ms"] == rec["trace_ms"]


def test_compile_log_slow_trace_counts_as_trace_alone():
    import time

    def program(x):
        time.sleep(0.05)  # Python at trace time
        return x * 2 + 1

    spec = jax.ShapeDtypeStruct((4,), np.float32)
    log = CompileLog()
    with log.measure("slow"):
        jax.jit(program).lower(spec).compile()
    with log.measure("fast"):
        jax.jit(lambda x: x * 2 + 1).lower(spec).compile()
    log.close()
    slow, fast = (log.stats()["programs"][k] for k in ("slow", "fast"))
    assert slow["trace_ms"] >= 50 > fast["trace_ms"]
    assert slow["wall_ms"] >= slow["trace_ms"]
    # The sleep is in neither of the other two.
    for key in ("lower_ms", "backend_compile_ms"):
        assert slow[key] < fast[key] + 40
    assert slow["backend_compiles"] == fast["backend_compiles"] == 1
    assert slow["functions"][0]["name"] == "program"


def _process_start_from_proc():
    import time

    with open("/proc/self/stat") as f:
        ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return time.time() - (uptime - ticks / os.sysconf("SC_CLK_TCK"))


def test_compile_log_spans_have_parents_and_startup_comes_first():
    import time

    log = CompileLog()
    before = time.time()
    with log.measure("first"):
        pass
    with log.measure("second"):
        with log.measure("inside"):
            pass
    spans = log.stats()["spans"]
    assert [(s["name"], s["parent"]) for s in spans] == [
        ("startup", None), ("first", None), ("second", None),
        ("inside", "second")]
    startup, first, second, inside = spans
    assert abs(startup["start_unix"] - _process_start_from_proc()) < 1.0
    assert startup["end_unix"] == first["start_unix"] >= before
    assert first["end_unix"] <= second["start_unix"] <= inside["start_unix"]
    assert inside["end_unix"] <= second["end_unix"]
    assert all(s["start_unix"] <= s["end_unix"] for s in spans)

    # A span is listed from its opening, without an end while it is open.
    with log.measure("open"):
        assert log.stats()["spans"][-1] == {
            **log.stats()["spans"][-1], "name": "open", "end_unix": None}

    # reset() drops the spans; startup is taken again, from the process's
    # start to the next first measure.
    log.reset()
    assert log.stats()["spans"] == []
    with log.measure("again"):
        pass
    again = log.stats()["spans"]
    assert [s["name"] for s in again] == ["startup", "again"]
    assert again[0]["start_unix"] == startup["start_unix"]
    assert again[0]["end_unix"] == again[1]["start_unix"] > second["end_unix"]

    # Where the backend's readiness was stamped, startup is split there.
    log.backend_ready()
    log.backend_ready()  # once a process: the second call changes nothing
    log.reset()
    with log.measure("split"):
        pass
    names = {s["name"]: s for s in log.stats()["spans"]}
    assert list(names) == ["startup", "startup:imports_attach",
                           "startup:build", "split"]
    attach, build = names["startup:imports_attach"], names["startup:build"]
    assert attach["parent"] == build["parent"] == "startup"
    assert attach["start_unix"] == names["startup"]["start_unix"]
    assert attach["end_unix"] == build["start_unix"] > again[1]["end_unix"]
    assert build["end_unix"] == names["startup"]["end_unix"]

    # The list is capped; the records are not.
    for k in range(CompileLog.MAX_SPANS + 10):
        with log.measure(f"p{k}"):
            pass
    assert len(log.stats()["spans"]) == CompileLog.MAX_SPANS
    assert f"p{CompileLog.MAX_SPANS + 9}" in log.stats()["programs"]
    log.close()


def test_compile_log_thread_attribution_of_trace_and_lower():
    """As the backend's compiles: each thread's trace and lower seconds go
    to the program that thread has open, and a thread's trace is no child
    of another thread's that happens to cover it in time."""
    import threading
    import time

    log = CompileLog()
    gate = threading.Barrier(2)

    def work(name, sleep_s):
        def program(x):
            gate.wait(timeout=30)  # both traces are open at once
            time.sleep(sleep_s)
            return x + sleep_s

        with log.measure(name):
            jax.jit(program).lower(
                jax.ShapeDtypeStruct((8,), np.float32)).compile()

    threads = [threading.Thread(target=work, args=("long", 0.08)),
               threading.Thread(target=work, args=("short", 0.02))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads)
    log.close()
    stats = log.stats()
    long_, short = stats["programs"]["long"], stats["programs"]["short"]
    assert long_["trace_ms"] >= 80 and short["trace_ms"] >= 20
    assert long_["lower_ms"] > 0 and short["lower_ms"] > 0
    for key in ("trace_ms", "lower_ms"):
        assert stats["totals"][key] == pytest.approx(
            long_[key] + short[key], abs=0.2)
    assert [s["parent"] for s in stats["spans"]] == [None, None, None]


def test_compile_log_listener_is_cheap_and_its_state_bounded():
    """A deep model's pass fires on the order of 10^4 trace events: each
    costs a few microseconds, and neither separate intervals that no
    parent ever swallows nor ever new names grow without bound."""
    import time

    trace = "/jax/core/compile/jaxpr_trace_duration"
    lower = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    names = [f"f{k}" for k in range(2_000)]
    costs = []
    # The suite shares its CPU with five other workers and the machine's
    # other tenants: the cost is this thread's CPU time, the cheapest of
    # three rounds, and the wall only bounds the union below.
    for _ in range(3):
        log = CompileLog()
        with log.measure("deep"):
            t0, c0 = time.perf_counter(), time.thread_time()
            for k in range(20_000):
                log._on_duration(trace, 1e-6, fun_name=names[k % 2_000])
            log._on_duration(lower, 1e-6, fun_name="jit(deep)")
            log._on_duration("/jax/some/other_duration", 1.0)
            costs.append(time.thread_time() - c0)
            cost = time.perf_counter() - t0
        log.close()
    assert min(costs) < 0.1
    assert len(log._tls.done[0]) <= CompileLog.MAX_OPEN_INTERVALS
    rec = log._programs["deep"]
    assert len(rec["functions"]) <= CompileLog.MAX_FUNCTIONS + 1
    assert sum(row[2] for row in rec["functions"].values()) == 20_000
    assert rec["functions"]["(other)"][2] > 0
    # Intervals that follow one another add up, to no more than the wall;
    # a parent over all of them adds only what they left uncovered.
    assert 0 < rec["trace_ms"] <= cost * 1e3
    with log.measure("deep"):
        log._on_duration(trace, 60.0, fun_name="whole")
    assert len(log._tls.done[0]) == 1
    assert rec["trace_ms"] == pytest.approx(60_000, abs=1)
    assert rec["functions"]["(other)"][0] < 60_000
    log.close()


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


def test_cli_summary_carries_compile_stats(tmp_path, capsys):
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
    for rec in programs.values():
        assert rec["trace_ms"] > 0 and rec["lower_ms"] > 0
        assert rec["cache_load_ms"] == 0  # the cache is off in the suite
        assert rec["trace_ms"] + rec["lower_ms"] <= rec["wall_ms"]
        assert rec["functions"][0].keys() == {
            "name", "trace_ms", "lower_ms", "calls"}
    assert "train_epoch" in {
        f["name"] for f in programs["train_epoch"]["functions"]}
    spans = summary["compile_stats"]["spans"]
    assert spans[0]["name"] == "startup"
    assert {"train_epoch", "eval_epoch"} <= {s["name"] for s in spans}
    out = capsys.readouterr().out
    startup_line = next(
        l for l in out.splitlines() if l.startswith("startup: "))
    assert startup_line.split(" s to the first program")[0] == (
        f"startup: {spans[0]['end_unix'] - spans[0]['start_unix']:.1f}")
    assert out.index(startup_line) < out.index("compile[train_epoch]: ")
    rec = programs["train_epoch"]
    assert (f"compile[train_epoch]: {rec['wall_ms']:.0f} ms "
            f"(trace {rec['trace_ms']:.0f}, lower {rec['lower_ms']:.0f}, "
            f"load 0; {rec['backend_compiles']} XLA compile(s), cache off)"
            ) in out


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
    # A summary from before trace, lower and load were kept renders as it
    # did; one that has them gets the three columns.
    assert "trace" not in out and "lower" not in out
    split = json.loads(json.dumps(stats))
    split["programs"]["train_epoch"].update(
        trace_ms=310.0, lower_ms=240.0, cache_load_ms=23.0, functions=[])
    split["totals"].update(trace_ms=310.0, lower_ms=240.0,
                           cache_load_ms=23.0)
    direct.write_text(json.dumps({"compile_stats": split}) + "\n")
    assert compile_report.main([str(direct)]) == 0
    head, row, totals = capsys.readouterr().out.splitlines()[-3:]
    assert head.split() == ["program", "compile", "ms", "trace", "lower",
                            "load", "XLA", "cache"]
    assert row.split() == ["train_epoch", "1234", "310", "240", "23", "1",
                           "miss"]
    assert totals.endswith("trace 310 ms, lower 240 ms, load 23 ms")
    assert compile_report.main([str(empty)]) == 1
    assert compile_report.main([]) == 1
