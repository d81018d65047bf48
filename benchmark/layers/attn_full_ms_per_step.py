"""Milliseconds a step spends on the device in the attention core of the
full-attention layers (scope ``attn_core/full``; forward, recomputed
forward and backward, the kernels and what XLA does round them): device
trace, ``benchmark/scopes_lm.py``. Layer: Step."""

from benchmark import scopes_lm


def read(run):
    return scopes_lm.class_ms_per_step(run, "attn_full")
