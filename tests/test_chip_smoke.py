"""chip_smoke.py units (hermetic, CPU, compiling nothing): the smoke never
passes without a TPU, its parent process stays off jax, and its verdicts
on a phase's device report and on served replies."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TPU_REPORT = {"platform": "tpu", "device_kind": "TPU v5 lite",
              "device_count": 1, "input_backend": "numpy",
              "pallas_lowerings": {"mosaic": 3, "interpret": 0}}


def test_refuses_to_pass_without_a_tpu():
    """JAX_PLATFORMS=cpu: non-zero exit, a message naming the platform it
    found, and no result line on stdout."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=60,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr and "TPU" in proc.stderr
    assert proc.stdout.strip() == ""


def test_parent_imports_neither_jax_nor_the_package():
    """A parent that touched jax would hold the chip its children need."""
    code = ("import sys; sys.path.insert(0, %r); import chip_smoke; "
            "bad = [m for m in sys.modules if m.split('.')[0] in "
            "('jax', 'jaxlib', 'pytorch_distributed_mnist_tpu')]; "
            "assert not bad, bad" % REPO)
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr


@pytest.fixture
def smoke():
    s = chip_smoke.Smoke()
    yield s
    s.close()


def test_phase_on_a_non_tpu_device_fails(smoke):
    smoke.check_device("phase", dict(TPU_REPORT))
    assert smoke.devices == [("tpu", "TPU v5 lite", 1)]
    with pytest.raises(chip_smoke.SmokeFailure, match="'cpu'.*not a TPU"):
        smoke.check_device("phase", dict(TPU_REPORT, platform="cpu",
                                         device_kind="cpu"))


def test_interpreted_pallas_call_fails(smoke):
    with pytest.raises(chip_smoke.SmokeFailure, match="interpret mode"):
        smoke.check_device("phase", dict(
            TPU_REPORT, pallas_lowerings={"mosaic": 2, "interpret": 1}))


def test_cache_dir_other_than_the_variable_fails(smoke, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/outside")
    smoke.note_cache_dir("phase", "compile cache: /placed/outside\n")
    with pytest.raises(chip_smoke.SmokeFailure, match="/repo/.xla_cache"):
        smoke.note_cache_dir("phase", "compile cache: /repo/.xla_cache\n")


def test_wrong_served_reply_fails(smoke, monkeypatch):
    """A label whose reference logit trails the best by more than twice
    the plane's bound is wrong; a near-tie inside the bound is not."""
    n = chip_smoke.REFERENCE_IMAGES
    logits = [[0.0] * 10 for _ in range(n)]
    for row in logits:
        row[3], row[4] = 2.0, 1.99  # near-tie: inside the f32 plane's bound
    ref = {"logits": logits, "images": [[0] * 784] * n}
    answers = {"label": 4}
    monkeypatch.setattr(
        chip_smoke, "_post",
        lambda url, payload: {"predictions":
                              [answers["label"]] * len(payload["images"])})
    worst, agree = smoke.check_replies("serve", "http://x", ref, "f32")
    assert worst == pytest.approx(0.01) and agree == 0.0
    answers["label"] = 7  # 2.0 below the best: wrong on any plane
    with pytest.raises(chip_smoke.SmokeFailure, match="answered class 7"):
        smoke.check_replies("serve", "http://x", ref, "f32")
