"""Test environment: 8 virtual CPU devices, hermetic and TPU-free.

Must run before jax initializes its backend, hence env vars at module import
(pytest imports conftest before test modules). This is the simulated-mesh
strategy from SURVEY.md section 4: ``xla_force_host_platform_device_count=8``
lets every mesh/psum/sharded-loader property run on CPU without a pod.
"""

import os
import sys

# The suite is hermetic: exactly 8 virtual CPU devices, whatever
# accelerator the environment points JAX at.
os.environ["JAX_PLATFORMS"] = "cpu"

# Persistent compile cache: off inside the pytest process, through the one
# variable every entry point resolves (utils/compile_cache.py: set-but-empty
# disables). The suite asserts cache-off fields (``persistent_cache_hit is
# None``), must write nothing into the checkout's .xla_cache, and must not
# inherit a cache directory exported by whoever runs it; tests that
# exercise the cache pass their own --compile-cache dir (a flag beats the
# empty variable) or export the variable to a child. Set before jax
# imports, because jax binds the variable at import.
os.environ["JAX_COMPILATION_CACHE_DIR"] = ""

# Repo root on sys.path: the analyzer suites import the uninstalled
# ``tools`` package (conftest imports before every test module, so no
# per-file bootstrap is needed).
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    xla_flags = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()
if "xla_cpu_collective_call_terminate_timeout_seconds" not in xla_flags:
    # 8 virtual devices timeshare a few host cores: XLA:CPU's default 40s
    # in-process collective rendezvous termination can fire from pure
    # scheduling starvation (observed: collective-permute rendezvous
    # abort, 5 of 8 threads arrived, same program passes when the cores
    # are idle). Starvation is not deadlock — give it time. Both flags
    # are accepted by the installed XLA (jaxlib 0.9.0).
    xla_flags += (" --xla_cpu_collective_call_terminate_timeout_seconds=600"
                  " --xla_cpu_collective_timeout_seconds=600")
os.environ["XLA_FLAGS"] = xla_flags

# Agreement watchdogs default ON in tests (off in production): any
# multi-process child a test spawns inherits this via _child_env, so a
# protocol regression that re-introduces a strand fails as a loud
# PeerFailure near this deadline instead of idling until the test's
# communicate() timeout. 300s is far above any legitimate skew between
# healthy ranks (whole 2-rank runs finish in well under that); chaos
# twins override with a tight per-test value.
os.environ.setdefault("TPUMNIST_AGREEMENT_TIMEOUT", "300")

import numpy as np
import pytest


def pytest_collection_modifyitems(config, items):
    """Default fast profile: deselect ``@pytest.mark.slow`` unless the
    caller passed ``-m`` (their expression wins) or named a test
    explicitly by node id (``pytest tests/x.py::test_y`` must run it, not
    report '1 deselected' and exit green having run nothing — the failure
    mode an ``addopts = -m 'not slow'`` filter has)."""
    if config.option.markexpr:
        return
    named = []
    for arg in config.invocation_params.args:
        if "::" in str(arg):
            a = str(arg)
            # Normalize to the rootdir-relative node id form.
            tail = a[a.index("tests/"):] if "tests/" in a else a
            named.append(tail)
    kept, dropped = [], []
    for item in items:
        if "slow" in item.keywords and not any(
                item.nodeid == n or item.nodeid.startswith(n + "::")
                or item.nodeid.startswith(n + "[")  # param id omitted
                for n in named):
            dropped.append(item)
        else:
            kept.append(item)
    if dropped:
        config.hook.pytest_deselected(items=dropped)
        items[:] = kept


@pytest.fixture(scope="session")
def mesh8():
    import jax

    from pytorch_distributed_mnist_tpu.parallel.mesh import make_mesh

    assert jax.device_count() == 8, "virtual 8-device CPU mesh not active"
    return make_mesh(("data",))


@pytest.fixture(scope="session")
def tiny_data():
    """Small deterministic synthetic dataset, normalized, shared across tests."""
    from pytorch_distributed_mnist_tpu.data.mnist import normalize_images, synthetic_dataset

    images, labels = synthetic_dataset(512, seed=42)
    return normalize_images(images), labels.astype(np.int32)


@pytest.fixture(autouse=True)
def _reset_loss_impl():
    """The loss impl is a process-global trace-time switch (ops/loss.py);
    a test that sets 'fused' must not leak it into later-collected tests
    (which would silently stop exercising the XLA path — including the
    bf16 optimization-barrier regression coverage)."""
    yield
    from pytorch_distributed_mnist_tpu.ops.loss import set_loss_impl

    set_loss_impl("xla")
