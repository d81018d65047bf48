"""Which lowering a Pallas call gets: Mosaic on a TPU, the interpreter on CPU.

One decision for every kernel in this package. The CPU interpreter exists
so the hermetic suite runs the same kernel bodies the chip compiles; any
other platform is an error, never a quiet interpret — a backend that is not
spelled ``tpu`` (a plug-in, a GPU) would otherwise run every kernel through
the interpreter and report its speed as the kernel's.
"""

from __future__ import annotations

import jax

from pytorch_distributed_mnist_tpu.utils.profiling import pallas_lowerings


def should_interpret() -> bool:
    """``interpret=`` for a ``pallas_call`` being traced now. The decision
    is counted (``utils.profiling.pallas_lowerings``) so a run summary or
    ``/healthz`` can show that a chip run interpreted nothing."""
    platform = jax.default_backend()
    if platform == "tpu":
        pallas_lowerings.record("mosaic")
        return False
    if platform == "cpu":
        pallas_lowerings.record("interpret")
        return True
    raise RuntimeError(
        f"Pallas kernels here lower through Mosaic on 'tpu' or run "
        f"interpreted on 'cpu'; the default backend is {platform!r}")
