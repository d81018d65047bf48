"""Milliseconds a step spends on the device in the Mamba-2 layers outside
the scan (scopes ``block*/ssd/{in_proj, conv, dt, norm, out_proj}`` and the
gate between them: every op under ``ssd`` that is not under ``ssd/scan``):
device trace, ``benchmark/scopes_ssd.py``. Layer: Step."""

from benchmark import scopes_ssd


def read(run):
    return scopes_ssd.class_ms_per_step(run, "ssd_proj")
