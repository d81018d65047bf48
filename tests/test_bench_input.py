"""End-to-end smoke of ``bench.py --mode input`` on the CPU backend: the
report must carry the ``input_pipeline`` block — feed-only throughput,
the pipelined-vs-synchronous paired speedup, the native-vs-NumPy
preprocess deltas, and BOTH zero-recompile verdicts — so the input-plane
BENCH schema can't silently rot while CI only exercises the in-process
pieces."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.slow


def test_bench_input_reports_pipeline_and_native_fields():
    env = os.environ.copy()
    env.update({
        "JAX_PLATFORMS": "cpu",
        "BENCH_FORCE_CPU": "1",  # the explicit CPU schema switch
        # Small drives: this asserts SCHEMA, not throughput. The compile
        # cache stays off — the bench both writes and re-reads entries
        # in one process, the exact pattern DESIGN.md 6c bans.
        "BENCH_INPUT_STEPS": "4",
        "BENCH_INPUT_BATCH": "256",
        "BENCH_INPUT_REPS": "3",
        "JAX_COMPILATION_CACHE_DIR": "",
    })
    env.pop("XLA_FLAGS", None)  # let the bench pick its own isolation
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--mode", "input"],
        capture_output=True, text=True, timeout=540, env=env, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    report = json.loads(proc.stdout.strip().splitlines()[-1])

    assert report["metric"] == "mnist_input_pipeline_feed_images_per_sec"
    assert report.get("error") is None
    assert report["value"] > 0
    # CPU-fallback labeling, the --mode serve convention: the line says
    # what backend it measured.
    assert report["backend"] == "cpu"

    ip = report["input_pipeline"]
    # Feed-only throughput and its decomposition.
    assert ip["feed_images_per_sec"] > 0
    assert ip["feed_host_ms"] >= 0 and ip["feed_h2d_ms"] >= 0
    assert ip["feed_steps"] == 4 and ip["global_batch"] == 256

    # Pipelined vs synchronous epochs: positive walls, a positive median
    # speedup, and one paired ratio per rep (the ABBA methodology).
    assert ip["pipelined_epoch_ms"] > 0
    assert ip["synchronous_epoch_ms"] > 0
    assert isinstance(ip["pipelined_feed_speedup"], (int, float))
    assert ip["pipelined_feed_speedup"] > 0
    assert len(ip["pipeline_pairs"]) == 3
    assert ip["feed_window"] == 2
    assert 0.0 <= ip["overlap_fraction"] <= 1.0

    # Native-vs-NumPy on the serve dispatch path. With the library built
    # the speedups are numbers with one pair per rep; without it they
    # are labelled null — never fabricated.
    if ip["native_available"]:
        assert ip["native_preprocess_speedup"] > 0
        assert ip["native_pad_speedup"] > 0
        assert len(ip["native_preprocess_pairs"]) == 3
        assert len(ip["native_pad_pairs"]) == 3
    else:
        assert ip["native_preprocess_speedup"] is None
        assert ip["native_pad_speedup"] is None

    # The acceptance invariants: zero steady-state recompiles on BOTH
    # sides of the data plane.
    assert ip["zero_steady_state_recompiles_train"] is True
    assert ip["zero_steady_state_recompiles_serve"] is True
    assert isinstance(ip["cpu_compute_isolated"], bool)

    # vs_baseline is the pipelined-feed speedup (the headline ratio).
    assert report["vs_baseline"] == ip["pipelined_feed_speedup"]


def test_bench_input_numpy_fallback_labelled():
    """TPUMNIST_NATIVE=0: the same line runs fallback-only and must say
    so (native_available false, null speedups) instead of inventing a
    comparison it could not measure."""
    env = os.environ.copy()
    env.update({
        "JAX_PLATFORMS": "cpu",
        "BENCH_FORCE_CPU": "1",  # the explicit CPU schema switch
        "TPUMNIST_NATIVE": "0",
        "BENCH_INPUT_STEPS": "2",
        "BENCH_INPUT_BATCH": "128",
        "BENCH_INPUT_REPS": "2",
        "JAX_COMPILATION_CACHE_DIR": "",
    })
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--mode", "input"],
        capture_output=True, text=True, timeout=540, env=env, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    report = json.loads(proc.stdout.strip().splitlines()[-1])
    ip = report["input_pipeline"]
    assert ip["native_available"] is False
    assert ip["native_preprocess_speedup"] is None
    assert ip["native_pad_speedup"] is None
    assert report.get("error") is None
