"""Share of its roofline the chunked scan's backward reaches: what the
traced steps' scans require (``benchmark/ssd_cost.py``) over the device
time of the ops under ``ssd/scan`` whose scope is a ``transpose(``: the
backward and, under per-block recomputation, the forward run again, which
the requirement does not count (``benchmark/scopes_ssd.py``). Layer:
Kernels."""

from benchmark import ssd_cost


def read(run):
    return ssd_cost.roofline_share(run, "backward")
