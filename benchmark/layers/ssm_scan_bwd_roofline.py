"""Share of its roofline the selective scan's backward reaches: what the
traced steps' scans require (``benchmark/ssm_cost.py``) over the device
time of the ops under ``ssm/scan`` whose scope is a ``transpose(``: the
backward and, under per-block recomputation, the forward run again, which
the requirement does not count (``benchmark/scopes_ssm.py``). Layer:
Kernels."""

from benchmark import ssm_cost


def read(run):
    return ssm_cost.roofline_share(run, "backward")
