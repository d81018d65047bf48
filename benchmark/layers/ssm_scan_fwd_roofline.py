"""Share of its roofline the selective scan's forward reaches: what the
traced steps' scans require (``benchmark/ssm_cost.py``: the larger of
operations over the bf16 peak and bytes over the HBM peak, here the bytes)
over the device time of the ops under ``ssm/scan`` whose scope is not a
``transpose(`` (``benchmark/scopes_ssm.py``). Layer: Kernels."""

from benchmark import ssm_cost


def read(run):
    return ssm_cost.roofline_share(run, "forward")
