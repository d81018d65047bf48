"""``bench.py --mode zero`` on the CPU backend: schema smoke for the
overlapped-ZeRO BENCH block — per-step comm/compute decomposition, the
ABBA-paired overlapped-vs-propagation speedup, overlap fraction, train
MFU, the CPU fallback honestly labelled, and the fails-loudly contract
when steady-state recompiles are nonzero — so the zero-mode BENCH schema
can't silently rot while CI only exercises the in-process pieces."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_comm_overlap_fraction_math():
    """The decomposition helper (utils/profiling.py): fully hidden,
    fully exposed, clamped edges, and the no-comm None."""
    from pytorch_distributed_mnist_tpu.utils.profiling import (
        comm_overlap_fraction,
    )

    # step == compute: every comm ms was hidden.
    assert comm_overlap_fraction(100.0, 100.0, 40.0) == 1.0
    # step == compute + comm: fully serialized.
    assert comm_overlap_fraction(140.0, 100.0, 40.0) == 0.0
    # half the comm extended the step.
    assert comm_overlap_fraction(120.0, 100.0, 40.0) == 0.5
    # noise pushing past the edges clamps instead of lying.
    assert comm_overlap_fraction(90.0, 100.0, 40.0) == 1.0
    assert comm_overlap_fraction(500.0, 100.0, 40.0) == 0.0
    # no measurable communication: nothing to overlap, never 0/0.
    assert comm_overlap_fraction(100.0, 100.0, 0.0) is None
    assert comm_overlap_fraction(None, 100.0, 40.0) is None


def test_per_tier_overlap_fractions_math():
    """The two-tier decomposition helper: each tier's entry is the
    guaranteed-hidden LOWER bound (the whole exposure charged against
    that tier alone), None propagating per tier."""
    from pytorch_distributed_mnist_tpu.utils.profiling import (
        per_tier_overlap_fractions,
    )

    # 30 ms exposed: at least 10 of ici's 40 must have been hidden
    # (0.25) no matter the attribution; dcn's 30 could all be exposed.
    fr = per_tier_overlap_fractions(130.0, 100.0, {"ici": 40.0, "dcn": 30.0})
    assert fr["ici"] == 0.25
    assert fr["dcn"] == 0.0
    # step == compute: every tier fully hidden.
    fr = per_tier_overlap_fractions(100.0, 100.0, {"ici": 40.0, "dcn": 30.0})
    assert fr == {"ici": 1.0, "dcn": 1.0}
    # a zero-comm tier has nothing to overlap; the other still scores.
    fr = per_tier_overlap_fractions(100.0, 100.0, {"ici": 40.0, "dcn": 0.0})
    assert fr["ici"] == 1.0 and fr["dcn"] is None
    # unknown compute: nothing can be attributed.
    fr = per_tier_overlap_fractions(100.0, None, {"ici": 40.0})
    assert fr["ici"] is None


def _run_zero_bench(env_extra, timeout=540):
    env = os.environ.copy()
    env.update({
        "JAX_PLATFORMS": "cpu",
        "BENCH_FORCE_CPU": "1",  # the explicit CPU schema switch
        # Small drives: this asserts SCHEMA, not throughput. The compile
        # cache stays off — the bench both writes and re-reads entries
        # in one process, the exact pattern DESIGN.md 6c bans.
        "BENCH_ZERO_STEPS": "3",
        "BENCH_ZERO_BATCH": "128",
        "BENCH_ZERO_REPS": "3",
        "JAX_COMPILATION_CACHE_DIR": "",
        # Exercises the MFU math on CPU (the _peak_flops test hook the
        # training bench uses); stamped into the line as fake_bounds.
        "BENCH_FAKE_PEAK_FLOPS": "1e12",
    })
    env.update(env_extra)
    env.pop("XLA_FLAGS", None)  # let the bench force its own CPU world
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--mode", "zero"],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO,
    )
    report = json.loads(proc.stdout.strip().splitlines()[-1])
    return proc, report


@pytest.mark.slow
def test_bench_zero_reports_overlap_block():
    proc, report = _run_zero_bench({})
    assert proc.returncode == 0, proc.stdout + proc.stderr

    assert report["metric"] == "mnist_zero_overlap_train_images_per_sec_per_chip"
    assert report.get("error") is None
    assert report["value"] > 0
    # CPU-fallback labeling, the --mode serve/input convention.
    assert report["backend"] == "cpu"
    assert report["n_chips"] >= 2  # the bench forced a multi-device world

    z = report["zero_overlap"]
    assert z["level"] == 3 and z["bucket_mb"] == 4.0
    assert z["steps"] == 3 and z["global_batch"] == 128
    # The measured decomposition: positive walls for the step and both
    # twins, a paired speedup with one ratio per rep, and an overlap
    # fraction inside [0, 1].
    assert z["step_ms_overlap"] > 0 and z["step_ms_propagation"] > 0
    assert z["comm_ms_per_step"] > 0 and z["compute_ms_per_step"] > 0
    assert len(z["pairs"]) == 3
    assert z["overlap_vs_propagation_speedup"] > 0
    assert report["vs_baseline"] == z["overlap_vs_propagation_speedup"]
    assert z["overlap_fraction"] is None or 0.0 <= z["overlap_fraction"] <= 1.0
    assert isinstance(z["overlap_beats_propagation"], bool)

    # Train MFU through _peak_flops (fake peak -> real number on CPU).
    assert z["mfu"] is not None and z["mfu"] >= 0
    assert z["flops_per_step"] > 0
    assert report["fake_bounds"] == {"BENCH_FAKE_PEAK_FLOPS": "1e12"}

    # The acceptance invariant: zero steady-state recompiles, BOTH paths.
    assert z["zero_steady_state_recompiles_overlap"] is True
    assert z["zero_steady_state_recompiles_propagation"] is True

    # CPU fallback honestly labelled (the BENCH_r05 precedent): the
    # caveat says overlap cannot manifest here, so the sign of the
    # speedup is not accelerator evidence.
    assert z["cpu_fallback"] is True
    assert "not" in z["caveat"] and "accelerator" in z["caveat"]

    # The two-tier (DCN x ICI) block: the forced 4-chip CPU world
    # emulates 2 slices by default, honestly labelled, with a per-tier
    # comm breakdown and per-drive recompile verdicts.
    tt = z["two_tier"]
    assert tt["dcn_slices"] == 2 and tt["chips_per_slice"] == 2
    assert tt["dcn_emulated"] is True
    assert "DCN" in tt["caveat"]
    assert tt["bucket_mb_dcn"] == tt["bucket_mb"] == 4.0
    assert tt["step_ms_two_tier"] > 0
    assert len(tt["pairs"]) == 3 and tt["vs_flat_overlap_speedup"] > 0
    assert set(tt["tiers"]) == {"ici", "dcn"}
    for tier in ("ici", "dcn"):
        row = tt["tiers"][tier]
        assert row["comm_ms_per_step"] > 0
        assert row["overlap_fraction"] is None \
            or 0.0 <= row["overlap_fraction"] <= 1.0
        assert row["zero_steady_state_recompiles"] is True
    assert tt["zero_steady_state_recompiles_two_tier"] is True


@pytest.mark.slow
def test_bench_zero_fails_loudly_on_steady_state_recompiles():
    """A backend compile inside the measured drive window (injected via
    the test-only hook) must flip the verdict, put the recompile in the
    error, and exit nonzero — the bench can never greenwash a
    shape-unstable steady state."""
    proc, report = _run_zero_bench({"BENCH_ZERO_INJECT_RECOMPILE": "1"})
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "recompile" in report["error"]
    assert report["zero_overlap"]["zero_steady_state_recompiles_overlap"] \
        is False
    # The uninjected path's verdict stays clean: attribution is per path.
    assert report["zero_overlap"][
        "zero_steady_state_recompiles_propagation"] is True


@pytest.mark.slow
def test_bench_zero_fails_loudly_on_hier_mesh_recompiles():
    """The fails-loudly contract re-pinned on the HIERARCHICAL mesh: a
    compile injected into the two-tier drive flips that verdict and
    exits 1 while the flat paths — and the per-tier comm twins — stay
    clean, so attribution survives the hierarchy."""
    proc, report = _run_zero_bench(
        {"BENCH_ZERO_INJECT_RECOMPILE": "two_tier"})
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "recompile" in report["error"] and "two_tier" in report["error"]
    z = report["zero_overlap"]
    assert z["two_tier"]["zero_steady_state_recompiles_two_tier"] is False
    for tier in ("ici", "dcn"):
        assert z["two_tier"]["tiers"][tier][
            "zero_steady_state_recompiles"] is True
    assert z["zero_steady_state_recompiles_overlap"] is True
    assert z["zero_steady_state_recompiles_propagation"] is True
