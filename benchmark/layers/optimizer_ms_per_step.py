"""Milliseconds a step spends on the device in the optimizer (scope
``optimizer``: the optax update and ``apply_updates``): self time by class
of scope from the device trace (``benchmark/scopes.py``), mean over the
chips. Layer: Step."""

from benchmark import scopes


def read(run):
    return scopes.class_ms_per_step(run, "optimizer")
