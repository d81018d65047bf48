"""bench.py units (hermetic, CPU): a run that finds no TPU fails instead of
printing a CPU number, the explicit BENCH_FORCE_CPU schema path, and the
compile-cache plumbing — all without any accelerator.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import bench  # noqa: E402


def test_peak_flops_raises_on_unknown_device_kind(monkeypatch):
    """A device that is not in the table is an error, not a null MFU;
    the declared CPU schema run is the one kind with no peak."""
    monkeypatch.delenv("BENCH_FAKE_PEAK_FLOPS", raising=False)
    assert bench._peak_flops("TPU v5 lite") == 197e12
    assert bench._peak_flops("cpu") is None
    with pytest.raises(ValueError, match="TPU v9 imaginary"):
        bench._peak_flops("TPU v9 imaginary")


def _fake_child(result):
    return lambda env_extra, steps, reps, timeout: (result, None)


def test_main_exits_nonzero_when_child_not_on_tpu(monkeypatch, capsys):
    """No TPU and no BENCH_FORCE_CPU: the child's CPU number must not
    become the line — error, value 0, exit 1, and no torch baseline run."""
    monkeypatch.delenv("BENCH_FORCE_CPU", raising=False)
    monkeypatch.setattr(bench, "_run_child", _fake_child(
        {"ok": True, "backend": "cpu", "images_per_sec_per_chip": 287.0}))
    monkeypatch.setattr(bench, "bench_torch_reference",
                        lambda: pytest.fail("baseline ran for a failed line"))
    with pytest.raises(SystemExit) as exc_info:
        bench.main()
    assert exc_info.value.code == 1
    out = json.loads(capsys.readouterr().out.strip())
    assert out["value"] == 0.0 and "'cpu', not a TPU" in out["error"]
    assert "backend" not in out


def test_main_forced_cpu_line_says_cpu(monkeypatch, capsys):
    """BENCH_FORCE_CPU=1 is the explicit schema switch: exit 0, and the
    line keeps ``"backend": "cpu"``."""
    monkeypatch.setenv("BENCH_FORCE_CPU", "1")
    monkeypatch.setattr(bench, "_run_child", _fake_child(
        {"ok": True, "backend": "cpu", "device_kind": "cpu", "mfu": None,
         "images_per_sec_per_chip": 287.0, "flops_source": "analytic"}))
    monkeypatch.setattr(bench, "bench_torch_reference", lambda: 100.0)
    bench.main()
    out = json.loads(capsys.readouterr().out.strip())
    assert out["backend"] == "cpu" and out["value"] == 287.0
    assert out["mfu"] is None and out["flops_source"] == "analytic"


def test_bench_modes_refuse_a_non_tpu_platform(monkeypatch, capsys):
    monkeypatch.delenv("BENCH_FORCE_CPU", raising=False)
    bench._require_tpu("tpu")
    with pytest.raises(SystemExit) as exc_info:
        bench._require_tpu("cpu")
    assert exc_info.value.code == 1
    assert "'cpu', not a TPU" in json.loads(
        capsys.readouterr().out.strip())["error"]
    monkeypatch.setenv("BENCH_FORCE_CPU", "1")
    bench._require_tpu("cpu")


def test_run_is_cpu_bound_reads_only_the_environment(monkeypatch):
    """The decision must not start a process that touches jax: a chip
    belongs to one process at a time."""
    monkeypatch.setattr(bench.subprocess, "run",
                        lambda *a, **k: pytest.fail("spawned a probe child"))
    monkeypatch.delenv("BENCH_FORCE_CPU", raising=False)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    assert not bench._run_is_cpu_bound()
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert bench._run_is_cpu_bound()


def test_vit_main_exits_nonzero_on_full_failure(monkeypatch, capsys):
    """A failed --vit run must not exit 0, so an rc gate rejects it
    without parsing — the bench_kernels.py / sweep_flash.py convention."""
    monkeypatch.setattr(bench, "bench_vit_accelerator",
                        lambda: {"ok": False, "error": "all children died"})
    with pytest.raises(SystemExit) as exc_info:
        bench.main_vit()
    assert exc_info.value.code == 1
    out = json.loads(capsys.readouterr().out.strip())
    assert out["value"] == 0.0 and "all children died" in out["error"]


@pytest.mark.slow
def test_forced_cpu_child_stepwise():
    """The CPU schema path end-to-end in a real child process: the
    stepwise program, a throughput number, and which FLOPs source fed
    the (null) MFU."""
    env = dict(os.environ, BENCH_FORCE_CPU="1", JAX_COMPILATION_CACHE_DIR="")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--child", "2", "1"],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO)
    line = [l for l in proc.stdout.splitlines() if l.strip().startswith("{")][-1]
    result = json.loads(line)
    assert result["ok"], result
    assert result["backend"] == "cpu" and result["mfu"] is None
    assert result["flops_source"] in ("cost_analysis", "analytic")
    assert result["images_per_sec_per_chip"] > 0


@pytest.mark.slow
def test_secondary_measurements_plumbing_cpu():
    """The fused-kernels and device-gather secondaries end-to-end on CPU
    (BENCH_FORCE_SECONDARIES): a broken secondary fails the child, and
    this is where that shows before a chip run does."""
    env = dict(os.environ, BENCH_FORCE_CPU="1", BENCH_FORCE_SECONDARIES="1",
               JAX_COMPILATION_CACHE_DIR="")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--child", "1", "1"],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO)
    line = [l for l in proc.stdout.splitlines()
            if l.strip().startswith("{")][-1]
    result = json.loads(line)
    assert result["ok"], result
    assert result["images_per_sec_per_chip_fused_kernels"] > 0
    assert result["images_per_sec_per_chip_device_gather"] > 0
    assert result["images_per_sec_per_chip_device_gather_sorted"] > 0


@pytest.mark.slow
def test_vit_child_tpu_branch_smoke_cpu():
    """The --vit child's exact TPU branch (flash attention + remat +
    bf16 + dense-attention secondary) at tiny interpret-mode shapes
    (BENCH_VIT_TPU_SMOKE): a latent bug there must surface here, not in
    a chip run."""
    env = dict(os.environ, BENCH_FORCE_CPU="1", BENCH_VIT="1",
               BENCH_VIT_TPU_SMOKE="1", JAX_COMPILATION_CACHE_DIR="")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--child", "2", "1"],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO)
    line = [l for l in proc.stdout.splitlines()
            if l.strip().startswith("{")][-1]
    result = json.loads(line)
    assert result["ok"], result
    assert result["attention"] == "flash" and result["remat"]
    assert result["sync"] == "host_read"
    assert result["images_per_sec_per_chip_dense_attn"] > 0
    assert result["flash_over_dense_speedup"] > 0


@pytest.mark.slow
def test_vit_main_line_cpu():
    """bench.py --vit end-to-end under the explicit CPU switch: the
    one-child parent, JSON-line contract, and field pass-through
    (value/mfu/model_config/sync)."""
    env = dict(os.environ, BENCH_FORCE_CPU="1", JAX_COMPILATION_CACHE_DIR="")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--vit"],
        capture_output=True, text=True, timeout=900, env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = [l for l in proc.stdout.splitlines()
            if l.strip().startswith("{")][-1]
    out = json.loads(line)
    assert out["metric"] == "mnist_vit_train_images_per_sec_per_chip"
    assert out["value"] > 0
    assert out["sync"] == "host_read"
    assert out["model_config"]["embed_dim"] > 0
    assert out["measured_at"].endswith("Z")


def test_refuse_fake_bounds_on_tpu(monkeypatch):
    """A test-only peak override leaking into a real-TPU child must
    refuse the run (an evidence line with fake physical bounds would
    still carry the host_read marker); on other backends it is stamped
    into the output so the line can never pass as evidence."""
    monkeypatch.setenv("BENCH_FAKE_PEAK_FLOPS", "1.0")
    result = {}
    refused = bench._refuse_fakes_on_tpu(result, "tpu")
    assert refused is not None and not refused["ok"]
    assert "BENCH_FAKE_PEAK_FLOPS" in refused["error"]
    result = {}
    assert bench._refuse_fakes_on_tpu(result, "cpu") is None
    assert result["fake_bounds"] == {"BENCH_FAKE_PEAK_FLOPS": "1.0"}
    monkeypatch.delenv("BENCH_FAKE_PEAK_FLOPS")
    result = {}
    assert bench._refuse_fakes_on_tpu(result, "tpu") is None
    assert result == {}


def test_vit_model_flops_count():
    """Pin the analytic ViT FLOPs count against a hand-derived value so a
    future edit can't silently change the MFU denominator: one block at
    T=4, C=8, r=4 is (8+16)*4*64 + 4*16*8 = 6656; embed (p=14: 2*4*196*8
    = 12544) and head (2*8*10 = 160) add, x3 for the step."""
    got = bench._vit_model_flops_per_image(4, 8, 1, 14)
    assert got == 3.0 * (6656 + 12544 + 160)


@pytest.mark.slow
def test_vit_impossible_mfu_rejected(monkeypatch):
    """The ViT child's MFU guard: a fake 1-FLOP/s peak makes any timing
    impossible; the child must return ok=False, never a number."""
    import subprocess as sp
    env = dict(os.environ, BENCH_FORCE_CPU="1", BENCH_VIT="1",
               BENCH_VIT_TPU_SMOKE="1", JAX_COMPILATION_CACHE_DIR="",
               BENCH_FAKE_PEAK_FLOPS="1.0")
    proc = sp.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--child", "1", "1"],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO)
    line = [l for l in proc.stdout.splitlines()
            if l.strip().startswith("{")][-1]
    result = json.loads(line)
    assert not result["ok"]
    assert "impossible ViT MFU" in result["error"]


@pytest.mark.slow
def test_compile_cache_config_plumbing(tmp_path):
    """JAX_COMPILATION_CACHE_DIR reaches the child: it logs that directory,
    names it in its line, and writes its entries there."""
    cache = tmp_path / "cache"
    env = dict(os.environ, BENCH_FORCE_CPU="1",
               JAX_COMPILATION_CACHE_DIR=str(cache))
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--child", "1", "1"],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert f"compile cache: {cache}" in proc.stderr
    line = [l for l in proc.stdout.splitlines()
            if l.strip().startswith("{")][-1]
    assert json.loads(line)["compile_cache"] == str(cache)
    assert any(cache.iterdir())
