"""Milliseconds a step spends on the device in the Gated Memory Units
(scope ``block*/gmu``: two projections and the gate over the published
scan output): device trace, ``benchmark/scopes_ssm.py``. Layer: Step."""

from benchmark import scopes_ssm


def read(run):
    return scopes_ssm.class_ms_per_step(run, "gmu")
