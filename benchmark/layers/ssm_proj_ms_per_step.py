"""Milliseconds a step spends on the device in the Mamba layers outside the
scan (scopes ``block*/ssm/{in_proj, conv, x_proj, dt, gate, out_proj}``:
every op under ``ssm`` that is not under ``ssm/scan``): device trace,
``benchmark/scopes_ssm.py``. Layer: Step."""

from benchmark import scopes_ssm


def read(run):
    return scopes_ssm.class_ms_per_step(run, "ssm_proj")
