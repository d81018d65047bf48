"""Milliseconds of a step on the device: total duration of the
``jit_train_epoch*`` events of the trace's ``XLA Modules`` line inside the
window (``benchmark/scopes.py``), mean over the chips, over the traced steps.
What ``step_ms`` times from outside; the difference is the host's. Layer:
Step."""

from benchmark import scopes


def read(run):
    return scopes.module_ms_per_step(run)
