"""Share of its roofline the flash backward reaches (``flash_bwd_dq`` and
``flash_bwd_dkv`` together, a call of each a layer): what the backward
requires (``benchmark/flash_cost.py``: five matmuls a pair; the two kernels
run seven) over their device time (``benchmark/scopes_lm.py``). Layer:
Kernels."""

from benchmark import flash_cost


def read(run):
    return flash_cost.roofline_share(
        run, ("flash_bwd_dq", "flash_bwd_dkv"), flash_cost.backward)
