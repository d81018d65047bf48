"""On-hardware TPU test suite (run separately from the hermetic tests/).

``tests/`` forces 8 virtual CPU devices so every sharding property is
checkable without a pod — but that leaves the Pallas kernels' real Mosaic
compile path unexercised: interpret mode cannot see block-tiling rules,
SMEM refs or lane alignment. This suite is the hardware half.

Run it on the chip:  python -m pytest tests_tpu/ -q
With no TPU every test FAILS (it does not skip): a missing chip is a
broken run, not a reason to report green.

One process holds the chip, so nothing here starts a child that touches
jax. The persistent compile cache comes from the shared wiring
(``utils/compile_cache.configure``: ``JAX_COMPILATION_CACHE_DIR`` when set,
else ``<checkout>/.xla_cache``).
"""

import jax
import pytest

from pytorch_distributed_mnist_tpu.utils import compile_cache

compile_cache.configure()


@pytest.fixture(scope="session", autouse=True)
def tpu():
    """Every test depends on this: the device jax found must be a TPU."""
    device = jax.devices()[0]
    if device.platform != "tpu":
        pytest.fail(
            f"tests_tpu/ needs a TPU; jax found platform "
            f"{device.platform!r} ({device.device_kind})", pytrace=False)
    return device
