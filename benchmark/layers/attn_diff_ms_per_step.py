"""Milliseconds a step spends on the device in differential attention's
combine (scope ``block*/attn/diff``: ``A_1 v - lambda A_2 v`` and the
sub-norm, outside the kernels): device trace, ``benchmark/scopes_ssm.py``.
Layer: Step."""

from benchmark import scopes_ssm


def read(run):
    return scopes_ssm.class_ms_per_step(run, "attn_diff")
