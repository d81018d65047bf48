"""Milliseconds a step spends on the device in the attention core (scope
``attn_core``: scores, softmax, weighted sum; forward and backward): self
time by class of scope from the device trace (``benchmark/scopes.py``), mean
over the chips. Layer: Step."""

from benchmark import scopes


def read(run):
    return scopes.class_ms_per_step(run, "attn_core")
