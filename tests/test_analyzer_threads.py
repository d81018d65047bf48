"""Fixture suite: the thread-lifecycle checker + the real spawn sites.

Firing fixtures pin the two incident shapes (the PR 6 feeder leak and
the PR 10 orphaned loadgen); the reversion tests re-introduce the
shipped bugs into the REAL files and assert the checker reproduces a
file:line finding — the acceptance contract for analyzer v2.
"""

import os
import pathlib

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from tools.analyzer import analyze_snippet  # noqa: E402

pytestmark = pytest.mark.lint


def _findings(src, filename="snippet.py"):
    return analyze_snippet(src, checkers=["thread-lifecycle"],
                           filename=filename)


# -- firing ------------------------------------------------------------------


def test_fires_on_unjoined_attribute_thread():
    """The PR 6 feeder-leak shape: an attribute handle with no join
    anywhere in the class — daemon=True does not excuse it."""
    src = """
import threading

class Conduit:
    def __init__(self, m):
        self._thread = threading.Thread(
            target=self._feed, args=(m,), daemon=True)
        self._thread.start()

    def _feed(self, m):
        for row in m:
            self.stage(row)
"""
    (f,) = _findings(src)
    assert f.symbol == "Conduit.__init__"
    assert "self._thread" in f.message and "PR 6" in f.message


def test_fires_on_happy_path_only_popen_reap():
    """The PR 10 orphaned-loadgen shape: communicate(timeout=) whose
    expiry raises past the only reap."""
    src = """
import subprocess

def run_twin(argv, timeout):
    lg = subprocess.Popen(argv, stdout=subprocess.PIPE)
    out, _ = lg.communicate(timeout=timeout)
    return out
"""
    (f,) = _findings(src)
    assert "happy path" in f.message and "PR 10" in f.message
    assert f.line == 5


def test_fires_on_anonymous_nondaemon_thread():
    src = """
import threading

def go(fn):
    threading.Thread(target=fn).start()
"""
    (f,) = _findings(src)
    assert "anonymous" in f.message


def test_fires_on_container_of_popens_without_protected_reap():
    """The elastic.py shape before the fix: the reap loop existed but
    only on one branch, unprotected — an exception mid-wait orphaned
    every rank."""
    src = """
import subprocess

def run_generation(cmds):
    procs = []
    for cmd in cmds:
        procs.append(subprocess.Popen(cmd))
    while True:
        if all(p.poll() is not None for p in procs):
            break
    for p in procs:
        p.wait()
"""
    (f,) = _findings(src)
    assert "'procs'" in f.message


def test_fires_on_constructed_and_discarded_popen():
    src = """
import subprocess

def fire_and_forget(cmd):
    subprocess.Popen(cmd)
"""
    (f,) = _findings(src)
    assert "discarded" in f.message


# -- non-firing --------------------------------------------------------------


def test_clean_on_joined_local_thread():
    src = """
import threading

def run(fn):
    t = threading.Thread(target=fn)
    t.start()
    t.join()
"""
    assert _findings(src) == []


def test_clean_on_popen_context_manager():
    src = """
import subprocess

def run(cmd):
    with subprocess.Popen(cmd) as p:
        return p.wait()
"""
    assert _findings(src) == []


def test_clean_on_protected_communicate():
    """The chaos.py fix shape: any failure kills and waits before
    propagating."""
    src = """
import subprocess

def run_twin(argv, timeout):
    lg = subprocess.Popen(argv, stdout=subprocess.PIPE)
    try:
        out, _ = lg.communicate(timeout=timeout)
    except BaseException:
        lg.kill()
        lg.wait()
        raise
    return out
"""
    assert _findings(src) == []


def test_clean_on_daemon_thread_with_sentinel_loop():
    src = """
import threading

def serve(interval):
    stop = threading.Event()

    def periodic():
        while not stop.wait(interval):
            tick()

    t = threading.Thread(target=periodic, daemon=True)
    t.start()
    return stop
"""
    assert _findings(src) == []


def test_clean_on_daemon_timer():
    """The watchdog hard-exit shape: a daemon Timer self-terminates."""
    src = """
import threading

def arm(deadline, fn):
    t = threading.Timer(deadline, fn)
    t.daemon = True
    t.start()
"""
    assert _findings(src) == []


def test_clean_on_comprehension_container_joined_in_loop():
    """The loadgen drive shape: a list comprehension of threads
    reaped by a for loop over the container."""
    src = """
import threading

def drive(worker, n):
    threads = [threading.Thread(target=worker) for _ in range(n)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
"""
    assert _findings(src) == []


def test_clean_on_container_with_protected_reap():
    """The elastic.py fixed shape: the sweep lives in a finally."""
    src = """
import subprocess

def run_generation(cmds):
    procs = []
    for cmd in cmds:
        procs.append(subprocess.Popen(cmd))
    try:
        poll_until_done(procs)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        for p in procs:
            p.wait()
"""
    assert _findings(src) == []


def test_clean_on_handle_handed_off():
    """Escapes — returned, or passed to a call (positionally or by
    keyword) — transfer lifecycle ownership to the recipient."""
    src = """
import subprocess, threading

def spawn(cmd):
    return subprocess.Popen(cmd)

def register(fleet, cmd):
    fleet.add(proc=subprocess.Popen(cmd))

def track(registry, fn):
    t = threading.Thread(target=fn)
    t.start()
    registry.watch(t)
"""
    assert _findings(src) == []


def test_method_use_of_handle_is_not_an_escape():
    """`out, _ = lg.communicate(...)` reads the handle's method — the
    suppressed-finding bug class this checker's escape rule had to
    dodge: a use is not a handoff."""
    src = """
import subprocess

def run(cmd, timeout):
    lg = subprocess.Popen(cmd)
    out, _ = lg.communicate(timeout=timeout)
    code = lg.returncode
    return out, code
"""
    (f,) = _findings(src)  # still fires: the reap is unprotected
    assert "happy path" in f.message


# -- reversion: re-introduce the shipped bugs into the REAL files ------------


_STAGING = pathlib.Path(_REPO) / "pytorch_distributed_mnist_tpu" / \
    "data" / "staging.py"
_CHAOS = pathlib.Path(_REPO) / "tools" / "chaos.py"


def test_removing_the_feeder_join_fails_the_gate():
    """Drop close()'s `self._thread.join()` — the exact PR 6 bug — and
    the checker must flag the feeder spawn with file:line."""
    source = _STAGING.read_text()
    assert "self._thread.join()" in source
    broken = source.replace("self._thread.join()",
                            "self._thread.is_alive()", 1)
    findings = _findings(broken, filename="staging.py")
    assert findings, "unjoined feeder thread was not flagged"
    f = findings[0]
    assert f.path == "staging.py" and f.line > 0
    assert "self._thread" in f.message


def test_pristine_staging_is_clean():
    assert _findings(_STAGING.read_text(), filename="staging.py") == []


def test_unprotecting_a_chaos_communicate_fails_the_gate():
    """Swap the cache-storm `_communicate_reaped(storm, ...)` back to
    the bare `storm.communicate(timeout=...)` — the exact PR 10 orphan
    — and the checker must flag that spawn site."""
    source = _CHAOS.read_text()
    old = "out, _ = _communicate_reaped(storm, args.timeout)"
    assert old in source
    broken = source.replace(
        old, "out, _ = storm.communicate(timeout=args.timeout)", 1)
    findings = _findings(broken, filename="chaos.py")
    assert findings, "unprotected communicate was not flagged"
    assert any("'storm'" in f.message and "PR 10" in f.message
               for f in findings)


def test_pristine_chaos_is_clean():
    assert _findings(_CHAOS.read_text(), filename="chaos.py") == []
