"""tools/northstar.py harness test (hermetic, CPU, BENCH_FORCE_CPU=1).

A latent bug in the north-star runner would otherwise surface only in a
chip run and waste it. These tests pin its JSON-line contract, the honest
dataset labelling, and the --epoch-gather flag plumbing (host default,
device selectable, identical trajectory) on tiny CPU shapes so the
on-chip run only ever measures.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_NORTHSTAR = os.path.join(REPO, "tools", "northstar.py")

_TINY = [
    "--dataset", "synthetic", "--epochs", "2", "--batch-size", "64",
    "--synthetic-train-size", "256", "--synthetic-test-size", "128",
    "--target", "0.99", "--seed", "0",
]


def _run(tmp_path, extra=()):
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_FORCE_CPU="1",
               JAX_COMPILATION_CACHE_DIR="")
    proc = subprocess.run(
        [sys.executable, _NORTHSTAR, "--root", str(tmp_path / "data")]
        + _TINY + list(extra),
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = [l for l in proc.stdout.splitlines()
            if l.strip().startswith("{")][-1]
    return json.loads(line)


@pytest.mark.slow
def test_northstar_json_contract_and_labelling(tmp_path):
    out = _run(tmp_path)
    # The fields a reader of the line relies on.
    assert out["target_acc"] == 0.99
    assert isinstance(out["reached"], bool)
    assert out["backend"] == "cpu"
    assert out["n_chips"] >= 1
    assert out["seconds_total"] > 0
    # Honest labelling: an explicit synthetic run says synthetic.
    assert out["dataset"] == "synthetic"
    assert len(out["epoch_log"]) >= 1
    row = out["epoch_log"][0]
    assert set(row) == {"epoch", "seconds", "test_acc", "train_loss"}
    # Cumulative seconds are monotone (the compile-vs-train split the
    # cold/warm captures read off this log).
    secs = [r["seconds"] for r in out["epoch_log"]]
    assert secs == sorted(secs)


@pytest.mark.slow
def test_northstar_epoch_gather_flag(tmp_path):
    """Round-5: host is the default; device stays selectable and must be
    trajectory-identical (same programs modulo the gather path — the
    equivalence tests/test_device_gather.py pins at step level)."""
    host = _run(tmp_path)
    dev = _run(tmp_path, ["--epoch-gather", "device"])
    assert [r["test_acc"] for r in dev["epoch_log"]] == \
        [r["test_acc"] for r in host["epoch_log"]]
    assert [r["train_loss"] for r in dev["epoch_log"]] == \
        [r["train_loss"] for r in host["epoch_log"]]
