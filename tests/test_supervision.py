"""Run-supervision unit tests: watchdog deadlines, retry/backoff, fault
plans, the agreement record protocol, and hook/docs drift gates.

Everything here is hermetic (no subprocesses): multi-host behavior is
exercised by monkeypatching the supervisor's topology probes and its raw
allgather, so the protocol logic — deadline trips, phase-report dumps,
poison idempotence, record parsing — is pinned at unit speed. The real
2-process proofs live in tests/test_chaos.py and tests/test_multiprocess.py.
"""

import io
import os
import threading
import time

import pytest

from pytorch_distributed_mnist_tpu.runtime import supervision as sup
from pytorch_distributed_mnist_tpu.utils.profiling import EventLog, failure_events
from pytorch_distributed_mnist_tpu.utils.watchdog import (
    WatchdogTimeout,
    retry_with_backoff,
    run_with_deadline,
)


@pytest.fixture(autouse=True)
def _reset_supervisor(monkeypatch):
    """Supervisor state is process-global (configured per run by cli.run);
    every test starts and ends disarmed so nothing leaks across tests."""
    monkeypatch.delenv(sup.FAULT_ENV, raising=False)
    monkeypatch.delenv(sup.TIMEOUT_ENV, raising=False)
    sup.configure(timeout=0, hard_exit_after=None)
    failure_events.reset()
    yield
    sup.configure(timeout=0, hard_exit_after=None)
    failure_events.reset()


# -- utils/watchdog.py -------------------------------------------------------


def test_deadline_zero_runs_inline():
    """timeout<=0 disables supervision entirely: fn runs on the CALLING
    thread (the production multi-host TPU default must not move
    collectives onto a worker thread for nothing)."""
    tid = {}
    out = run_with_deadline(
        lambda: tid.setdefault("t", threading.get_ident()) and 42 or 42,
        timeout=0, label="off")
    assert out == 42
    assert tid["t"] == threading.get_ident()


def test_deadline_returns_result_and_propagates_error():
    assert run_with_deadline(lambda: "ok", timeout=5, label="x") == "ok"
    with pytest.raises(ValueError, match="boom"):
        run_with_deadline(lambda: (_ for _ in ()).throw(ValueError("boom")),
                          timeout=5, label="x")


def test_deadline_trips_on_stall_and_dumps():
    """A stalled call trips the deadline, runs the diagnostic dump first,
    and raises WatchdogTimeout (marked already_agreed: no poison after)."""
    stall = threading.Event()
    dumped = []
    t0 = time.monotonic()
    with pytest.raises(WatchdogTimeout) as exc:
        run_with_deadline(lambda: stall.wait(60), timeout=0.3,
                          label="fake collective",
                          on_timeout=lambda: dumped.append(True))
    elapsed = time.monotonic() - t0
    stall.set()
    assert dumped == [True]
    assert elapsed < 30  # tripped at the deadline, not the stall length
    assert "fake collective" in str(exc.value)
    assert exc.value.already_agreed  # the agreed-exit contract


def test_retry_backoff_flaky_then_succeeds():
    calls = []
    delays = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise OSError("transient")
        return "done"

    out = retry_with_backoff(
        flaky, attempts=5, base_delay=0.5, max_delay=8.0, jitter=0.25,
        sleep=delays.append)
    assert out == "done" and len(calls) == 3
    # exponential base + bounded jitter
    assert 0.5 <= delays[0] < 0.75 and 1.0 <= delays[1] < 1.25


def test_retry_backoff_exhaustion_and_nonretryable():
    with pytest.raises(OSError):
        retry_with_backoff(lambda: (_ for _ in ()).throw(OSError("x")),
                           attempts=2, sleep=lambda _: None)
    calls = []

    def wrong_type():
        calls.append(1)
        raise KeyError("not retryable")

    with pytest.raises(KeyError):
        retry_with_backoff(wrong_type, attempts=5, retry_on=(OSError,),
                           sleep=lambda _: None)
    assert len(calls) == 1  # no retry on a non-listed exception type

    observed = []
    with pytest.raises(OSError):
        retry_with_backoff(
            lambda: (_ for _ in ()).throw(OSError("x")), attempts=3,
            sleep=lambda _: None, jitter=0.0,
            on_retry=lambda n, exc, d: observed.append((n, d)))
    assert [n for n, _ in observed] == [1, 2]  # final failure: no on_retry


# -- fault plans -------------------------------------------------------------


def test_fault_plan_parse_and_defaults():
    p = sup.FaultPlan.parse("ckpt_publish:0:kill")
    assert (p.point, p.host, p.kind, p.arg) == ("ckpt_publish", "0",
                                                "kill", 0.0)
    assert sup.FaultPlan.parse("train_epoch:*:kill:2").arg == 2.0
    assert sup.FaultPlan.parse("eval:1:stall").arg == 3600.0
    for bad in ("nope", "unknown_point:0:kill", "eval:0:explode",
                "eval:x1:kill"):
        with pytest.raises(ValueError):
            sup.FaultPlan.parse(bad)


def test_maybe_fault_raise_kind(monkeypatch):
    monkeypatch.setenv(sup.FAULT_ENV, "eval:0:raise")
    sup.configure(timeout=0, hard_exit_after=None)  # re-parse the plan
    monkeypatch.setattr(sup, "process_index", lambda: 0)
    with pytest.raises(sup.InjectedFault, match="eval:0:raise"):
        sup.maybe_fault("eval")
    # host mismatch: silent no-op
    monkeypatch.setattr(sup, "process_index", lambda: 1)
    sup.maybe_fault("eval")


def test_maybe_fault_skip_count(monkeypatch):
    """arg = hits to SKIP for kill/raise: 'the Nth epoch' selectors."""
    monkeypatch.setenv(sup.FAULT_ENV, "train_epoch:*:raise:2")
    sup.configure(timeout=0, hard_exit_after=None)
    sup.maybe_fault("train_epoch")  # hit 0: skipped
    sup.maybe_fault("train_epoch")  # hit 1: skipped
    with pytest.raises(sup.InjectedFault):
        sup.maybe_fault("train_epoch")  # hit 2: fires


def test_maybe_fault_stall(monkeypatch):
    monkeypatch.setenv(sup.FAULT_ENV, "eval:*:stall:0.2")
    sup.configure(timeout=0, hard_exit_after=None)
    t0 = time.monotonic()
    sup.maybe_fault("eval")  # sleeps, then returns
    assert time.monotonic() - t0 >= 0.2


def test_unregistered_fault_point_asserts():
    with pytest.raises(AssertionError):
        sup.maybe_fault("not_a_point")


def test_parse_fault_specs_multi():
    """Comma-joined multi-fault plans: the mid-rebuild chaos shape (a
    host loss plus an elastic_rebuild sabotage of a survivor)."""
    plans = sup.parse_fault_specs(
        "train_epoch:2:kill:1,elastic_rebuild:1:stall")
    assert [(p.point, p.host, p.kind) for p in plans] == [
        ("train_epoch", "2", "kill"), ("elastic_rebuild", "1", "stall")]
    assert plans[1].arg == 3600.0
    # single-spec back-compat and per-spec validation
    assert len(sup.parse_fault_specs("eval:0:raise")) == 1
    with pytest.raises(ValueError, match="unknown fault point"):
        sup.parse_fault_specs("eval:0:raise,bogus:0:kill")
    with pytest.raises(ValueError, match="one fault per spec"):
        sup.FaultPlan.parse("eval:0:raise,eval:1:raise")


def test_maybe_fault_multi_plan_fires_matching_point(monkeypatch):
    """With two plans configured, each point fires only its own."""
    monkeypatch.setenv(sup.FAULT_ENV,
                       "eval:*:raise,elastic_rebuild:*:raise")
    sup.configure(timeout=0, hard_exit_after=None)
    sup.maybe_fault("train_epoch")  # matches neither plan
    with pytest.raises(sup.InjectedFault, match="eval"):
        sup.maybe_fault("eval")
    with pytest.raises(sup.InjectedFault, match="elastic_rebuild"):
        sup.maybe_fault("elastic_rebuild")


def _analyzer():
    """Thin-wrapper plumbing: since ISSUE 5 the registry<->hook drift
    logic lives in tpumnist-lint (tools/analyzer, ``registry-drift``
    checker); these tests drive it through its API so the runtime
    registry, the static gate, and chaos --list can never disagree.
    conftest.py already put the repo root on sys.path."""
    import tools.analyzer as analyzer

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return analyzer, repo


def test_fault_points_registry_matches_call_sites():
    """Drift gate (now a wrapper over the analyzer): a hook without a
    registry entry, a registry entry whose hook was deleted, or a
    computed point name all fail here — so tools/chaos.py --list and the
    docs can never advertise fault points that don't exist."""
    analyzer, repo = _analyzer()
    result = analyzer.run_analysis(
        [os.path.join(repo, "pytorch_distributed_mnist_tpu"),
         os.path.join(repo, "tools")],
        checkers=["registry-drift"], baseline=None)
    assert result.ok, "\n".join(f.render() for f in result.findings)
    report = result.reports["registry-drift"]
    # The checker saw the real registry and real hooks, not a vacuous
    # empty view — and they agree with the runtime module's own dict.
    assert report["fault_points"] == sorted(sup.FAULT_POINTS)
    assert report["hook_sites"] >= len(sup.FAULT_POINTS)


def test_chaos_list_matches_registry():
    """chaos --list renders what the analyzer statically parsed as the
    registry; the spawned-tool view, the AST view, and the runtime dict
    must be one set."""
    import importlib.util

    analyzer, repo = _analyzer()
    from tools.analyzer.checkers.registry_drift import registry_entries
    from tools.analyzer.core import parse_modules

    sup_path = os.path.join(repo, "pytorch_distributed_mnist_tpu",
                            "runtime", "supervision.py")
    modules, problems = parse_modules([sup_path])
    assert not problems
    _module, keys = registry_entries(modules)
    assert set(keys) == set(sup.FAULT_POINTS)  # AST view == runtime view

    spec = importlib.util.spec_from_file_location(
        "chaos_tool", os.path.join(repo, "tools", "chaos.py"))
    chaos = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(chaos)
    buf = io.StringIO()
    chaos.list_fault_points(buf)
    listed = {line.split("\t")[0]
              for line in buf.getvalue().splitlines() if line}
    assert listed == set(keys)  # --list view == AST view


# -- agreement records -------------------------------------------------------


def _fake_world(monkeypatch, nproc=2, rank=0):
    monkeypatch.setattr(sup, "process_count", lambda: nproc)
    monkeypatch.setattr(sup, "process_index", lambda: rank)


def test_record_roundtrip():
    sup.set_phase("train@3")
    rec = sup._decode_record(sup._encode_record(sup._OK, "detail text"))
    assert rec.ok and not rec.poisoned
    assert rec.phase == "train@3" and rec.detail == "detail text"
    pill = sup._decode_record(sup._encode_record(sup._POISON, "r"))
    assert pill.poisoned and not pill.ok


def test_single_process_agree_is_local():
    """No collective machinery for one process: agree returns this host's
    failure (if any) and callers re-raise their own error."""
    failed = sup.agree("write", None)
    assert failed == []
    err = OSError("local")
    failed = sup.agree("write", err)
    assert failed and failed[0][0] == 0
    assert getattr(err, "_poison_delivered", False)  # marked as delivered


def test_agreement_exchanges_records(monkeypatch):
    """Peers' E records come back attributed (host, phase, reason)."""
    import numpy as np

    _fake_world(monkeypatch, rank=0)

    def fake_allgather(payload):
        sup_phase = sup.current_phase()
        try:
            sup.set_phase("checkpoint@1")
            peer = np.frombuffer(
                sup._encode_record(sup._ERR, "peer exploded"), np.uint8)
        finally:
            sup.set_phase(sup_phase)
        return np.stack([payload, peer])

    monkeypatch.setattr(sup, "_raw_allgather", fake_allgather)
    failed = sup.agree("ckpt_write", None)
    assert failed == [(1, "checkpoint@1", "peer exploded")]


def test_agreement_watchdog_trips_with_phase_report(monkeypatch, capsys):
    """A silent peer trips the agreement deadline: the per-host phase
    report is dumped and PeerFailure implicates every other host."""
    _fake_world(monkeypatch, nproc=3, rank=1)
    sup.configure(timeout=0.3, hard_exit_after=None)
    sup.set_phase("checkpoint@2")
    stall = threading.Event()
    monkeypatch.setattr(sup, "_raw_allgather", lambda p: stall.wait(60))
    with pytest.raises(sup.PeerFailure) as exc:
        sup.allgather_records("ckpt_publish", True)
    stall.set()
    assert exc.value.hosts == [0, 2]
    assert exc.value.phase == "ckpt_publish"
    assert exc.value.already_agreed
    err = capsys.readouterr().err
    assert "supervision watchdog report" in err
    assert "blocked in: agreement 'ckpt_publish'" in err
    assert "lifecycle phase: checkpoint@2" in err
    kinds = [e["kind"] for e in failure_events.snapshot()]
    assert "agreement_timeout" in kinds


def test_agreement_timeout_zero_disables_watchdog(monkeypatch):
    """--agreement-timeout 0: the collective runs inline on the calling
    thread, unbounded — the real multi-host TPU default."""
    import numpy as np

    _fake_world(monkeypatch)
    sup.configure(timeout=0, hard_exit_after=None)
    seen = {}

    def fake_allgather(payload):
        seen["thread"] = threading.get_ident()
        return np.stack([payload, payload])

    monkeypatch.setattr(sup, "_raw_allgather", fake_allgather)
    records = sup.allgather_records("ckpt_write", True)
    assert len(records) == 2 and all(r.ok for r in records)
    assert seen["thread"] == threading.get_ident()


def test_heartbeats_recorded_and_dumped(monkeypatch, capsys):
    """Completed agreements record each host's reported phase; the next
    watchdog trip renders them as the last-heartbeat table."""
    import numpy as np

    _fake_world(monkeypatch, rank=0)
    monkeypatch.setattr(
        sup, "_raw_allgather", lambda p: np.stack([p, p]))
    sup.set_phase("train@7")
    sup.allgather_records("ckpt_write", True)
    sup.configure(timeout=0.2, hard_exit_after=None)
    # configure() resets heartbeats; record one under the armed deadline
    sup.set_phase("train@7")
    sup.allgather_records("ckpt_write", True)
    stall = threading.Event()
    monkeypatch.setattr(sup, "_raw_allgather", lambda p: stall.wait(60))
    with pytest.raises(sup.PeerFailure):
        sup.allgather_records("ckpt_publish", True)
    stall.set()
    err = capsys.readouterr().err
    assert "host 1: phase 'train@7' at agreement #1" in err


def test_deliver_poison_idempotent_and_skips_agreed(monkeypatch):
    import numpy as np

    _fake_world(monkeypatch)
    calls = []

    def fake_allgather(payload):
        calls.append(payload)
        return np.stack([payload, payload])

    monkeypatch.setattr(sup, "_raw_allgather", fake_allgather)
    err = RuntimeError("host-local")
    sup.deliver_poison(err)
    sup.deliver_poison(err)  # second delivery for the same exception
    assert len(calls) == 1  # exactly one pill
    rec = sup._decode_record(calls[0].tobytes())
    assert rec.poisoned and "host-local" in rec.detail

    # already-agreed failures (PeerFailure, WatchdogTimeout) never poison
    sup.deliver_poison(sup.PeerFailure("x", hosts=[1], phase="p"))
    sup.deliver_poison(WatchdogTimeout("label", 1.0))
    sup.deliver_poison(KeyboardInterrupt())
    assert len(calls) == 1


def test_raise_if_poisoned(monkeypatch):
    _fake_world(monkeypatch, rank=0)
    records = [sup.Record("K", "resume", ""),
               sup.Record("P", "train@4", "OOM on host 1")]
    with pytest.raises(sup.PeerFailure) as exc:
        sup.raise_if_poisoned(records, "the resume agreement")
    assert exc.value.hosts == [1]
    assert exc.value.phase == "train@4"
    assert "OOM on host 1" in str(exc.value)
    # an E vote in the same phase is NOT a poison pill
    sup.raise_if_poisoned([sup.Record("K", "resume", ""),
                           sup.Record("E", "resume", "no file")],
                          "the resume agreement")


def test_configure_env_resolution(monkeypatch):
    monkeypatch.setenv(sup.TIMEOUT_ENV, "12.5")
    assert sup.configure() == 12.5
    assert sup.configure(timeout=3.0) == 3.0  # flag wins over env
    assert sup.configure(timeout=0) == 0.0
    monkeypatch.setenv(sup.TIMEOUT_ENV, "not-a-number")
    with pytest.raises(SystemExit):
        sup.configure()


def test_event_log_thread_safe_snapshot():
    log = EventLog()
    log.record("kind_a", "one", phase="p")
    log.record("kind_b", "two")
    snap = log.snapshot()
    assert [e["kind"] for e in snap] == ["kind_a", "kind_b"]
    assert snap[0]["phase"] == "p"
    snap[0]["kind"] = "mutated"  # snapshots are copies
    assert log.snapshot()[0]["kind"] == "kind_a"
    log.reset()
    assert log.snapshot() == []


# ---------------------------------------------------------------------------
# InjectedFault transparency through the broadened download handlers
# ---------------------------------------------------------------------------
# The tpumnist-lint audit broadened the download warn-and-continue paths to
# `except Exception` (the zlib-strand class), but `chaos --list` advertises
# `download_fetch:*:raise` — the injection must still escape both callers,
# or the harness can never drive the download-failure -> poison-pill path
# once the IDX files are on disk.


def test_mnist_download_handler_reraises_injected_fault(tmp_path, monkeypatch):
    from pytorch_distributed_mnist_tpu.data import download as dl
    from pytorch_distributed_mnist_tpu.data.mnist import load_dataset

    def boom(root, name):
        raise sup.InjectedFault("injected fault at download_fetch")

    monkeypatch.setattr(dl, "download_dataset", boom)
    with pytest.raises(sup.InjectedFault):
        load_dataset(str(tmp_path), "mnist", train=True,
                     synthesize_if_missing=True, download=True)


def test_mnist_download_handler_still_funnels_real_failures(
        tmp_path, monkeypatch, capsys):
    import zlib
    from pytorch_distributed_mnist_tpu.data import download as dl
    from pytorch_distributed_mnist_tpu.data.mnist import load_dataset

    def boom(root, name):
        raise zlib.error("Error -3 while decompressing data")

    monkeypatch.setattr(dl, "download_dataset", boom)
    images, labels = load_dataset(str(tmp_path), "mnist", train=True,
                                  synthesize_if_missing=True, download=True)
    assert images.shape[0] == labels.shape[0] > 0  # synthetic fallback
    assert "WARNING: download" in capsys.readouterr().out


def test_cli_download_stage_reraises_injected_fault(tmp_path, monkeypatch):
    import argparse

    from pytorch_distributed_mnist_tpu import cli
    from pytorch_distributed_mnist_tpu.data import download as dl

    def boom(root, name):
        raise sup.InjectedFault("injected fault at download_fetch")

    monkeypatch.setattr(dl, "download_dataset", boom)
    args = argparse.Namespace(dataset="mnist", download=True,
                              root=str(tmp_path))
    with pytest.raises(sup.InjectedFault):
        cli._build_loaders(args, seed=0, mesh=None)
