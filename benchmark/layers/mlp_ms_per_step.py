"""Milliseconds a step spends on the device in the blocks' MLPs (scope
``mlp``: ``mlp1``, GELU, ``mlp2``): self time by class of scope from the
device trace (``benchmark/scopes.py``), mean over the chips. Layer: Step."""

from benchmark import scopes


def read(run):
    return scopes.class_ms_per_step(run, "mlp")
