"""Share of its roofline the chunked scan's forward reaches: what the
traced steps' scans require (``benchmark/ssd_cost.py``: the larger of
operations over the bf16 peak and bytes over the HBM peak) over the device
time of the ops under ``ssd/scan`` whose scope is not a ``transpose(``
(``benchmark/scopes_ssd.py``). Layer: Kernels."""

from benchmark import ssd_cost


def read(run):
    return ssd_cost.roofline_share(run, "forward")
