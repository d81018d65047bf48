"""Milliseconds a step spends on the device in the chunked state-space
scans (scope ``block*/ssd/scan``: forward, recomputed forward and backward,
the kernels and what XLA does round them, whatever implements them): device
trace, ``benchmark/scopes_ssd.py``. Layer: Step."""

from benchmark import scopes_ssd


def read(run):
    return scopes_ssd.class_ms_per_step(run, "ssd_scan")
