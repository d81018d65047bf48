"""Milliseconds a step spends on the device in the selective scans (scope
``block*/ssm/scan``: forward, recomputed forward and backward, whatever
implements them): device trace, ``benchmark/scopes_ssm.py``. Layer: Step."""

from benchmark import scopes_ssm


def read(run):
    return scopes_ssm.class_ms_per_step(run, "ssm_scan")
