"""Share of its roofline the flash forward kernel reaches (``flash_fwd``,
every call in the traced passes: forward and recomputed forward, full and
window layers): what the calls require (``benchmark/flash_cost.py``: the
larger of operations over the bf16 peak and bytes over the HBM peak) over
the kernel's device time (``benchmark/scopes_lm.py``). Layer: Kernels."""

from benchmark import flash_cost


def read(run):
    return flash_cost.roofline_share(
        run, ("flash_fwd",), flash_cost.forward)
