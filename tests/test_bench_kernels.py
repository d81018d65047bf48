"""tools/bench_kernels.py rot guard: the MXU-bound kernel benchmark must
always produce its JSON (the watcher runs it unattended the moment the
chip answers — a bitrotted tool would silently burn that rare window).
Perf numbers are meaningless on CPU; only the harness contract is pinned.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow
def test_bench_kernels_quick_emits_json():
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_FORCE_CPU="1",
               JAX_COMPILATION_CACHE_DIR="")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "bench_kernels.py"),
         "--quick", "--reps", "1", "--iters", "1"],
        capture_output=True, text=True, timeout=420, env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = [l for l in proc.stdout.splitlines()
            if l.strip().startswith("{")][-1]
    out = json.loads(line)
    assert out["metric"] == "pallas_kernel_vs_xla"
    assert "attention_error" not in out, out
    assert "adam_error" not in out, out
    rows = out["attention_fwd_bwd"]
    assert len(rows) == 2 and all(r["flash_ms"] > 0 for r in rows)
    assert out["adam_update"]["n_params"] > 0


@pytest.mark.slow
def test_bench_kernels_impossible_mfu_fails_loudly():
    """Round-4 guard: a measurement faster than the chip's peak FLOPs
    (sync failure — how round 3's kernels.json went bad) must exit
    nonzero, stamp "invalid", and NOT carry the "sync": "host_read"
    validity marker. Peak is faked to 1 FLOP/s so any real timing
    violates it."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_FORCE_CPU="1",
               JAX_COMPILATION_CACHE_DIR="",
               BENCH_FAKE_PEAK_FLOPS="1.0")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "bench_kernels.py"),
         "--quick", "--reps", "1", "--iters", "1"],
        capture_output=True, text=True, timeout=420, env=env, cwd=REPO)
    assert proc.returncode == 1, (proc.stdout, proc.stderr[-2000:])
    line = [l for l in proc.stdout.splitlines()
            if l.strip().startswith("{")][-1]
    out = json.loads(line)
    assert "impossible" in out["invalid"]
    assert "sync" not in out


@pytest.mark.slow
def test_bench_kernels_adam_hbm_guard_fails_loudly():
    """Same contract for the HBM-bandwidth bound on the (attention-MFU-
    blind) Adam rows: faked 1 byte/s bandwidth makes any timing
    impossible."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_FORCE_CPU="1",
               JAX_COMPILATION_CACHE_DIR="",
               BENCH_FAKE_HBM_BW="1.0")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "bench_kernels.py"),
         "--quick", "--reps", "1", "--iters", "1"],
        capture_output=True, text=True, timeout=420, env=env, cwd=REPO)
    assert proc.returncode == 1, (proc.stdout, proc.stderr[-2000:])
    out = json.loads([l for l in proc.stdout.splitlines()
                      if l.strip().startswith("{")][-1])
    assert "impossible adam" in out["invalid"]
    assert "sync" not in out


@pytest.mark.slow
def test_sweep_flash_impossible_mfu_fails_loudly():
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_FORCE_CPU="1",
               JAX_COMPILATION_CACHE_DIR="",
               BENCH_FAKE_PEAK_FLOPS="1.0")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "sweep_flash.py"),
         "--quick", "--reps", "1", "--iters", "1"],
        capture_output=True, text=True, timeout=420, env=env, cwd=REPO)
    assert proc.returncode == 1, (proc.stdout, proc.stderr[-2000:])
    out = json.loads([l for l in proc.stdout.splitlines()
                      if l.strip().startswith("{")][-1])
    assert "impossible" in out["invalid"]
    assert "sync" not in out


@pytest.mark.slow
def test_sweep_flash_quick_emits_json():
    """Same rot guard for the flash block-size sweep: the follow-up
    watcher runs it unattended in a rare chip-recovery window, and it
    imports across modules by path hack (bench.configure_jax,
    bench_kernels._timeit) — drift there must fail here, not there."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_FORCE_CPU="1",
               JAX_COMPILATION_CACHE_DIR="")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "sweep_flash.py"),
         "--quick", "--reps", "1", "--iters", "1"],
        capture_output=True, text=True, timeout=420, env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = [l for l in proc.stdout.splitlines()
            if l.strip().startswith("{")][-1]
    out = json.loads(line)
    assert out["metric"] == "flash_block_sweep_fwd_bwd"
    (row,) = out["rows"]
    assert row["dense_ms"] > 0 and row["flash_b32_ms"] > 0
    assert "flash_b32_speedup" in row
