"""Milliseconds a step spends on the device in the attention core of the
cross layers (scope ``attn_core/cross``: the flash kernels over an earlier
layer's keys and values, and what XLA does round them): device trace,
``benchmark/scopes_ssm.py``. Layer: Step."""

from benchmark import scopes_ssm


def read(run):
    return scopes_ssm.class_ms_per_step(run, "attn_cross")
