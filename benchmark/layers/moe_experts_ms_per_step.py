"""Milliseconds a step spends on the device in the experts' matmuls: the
grouped ones of the held experts and the shared expert (scopes
``block*/moe/experts`` and ``block*/moe/shared``; forward and backward):
device trace, ``benchmark/scopes_lm.py``. Layer: Step."""

from benchmark import scopes_lm


def read(run):
    return scopes_lm.class_ms_per_step(run, "moe_experts")
