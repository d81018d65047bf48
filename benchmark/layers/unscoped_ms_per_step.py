"""Milliseconds a step spends on the device in ops that belong to no part of
the model: no ``op_name``, or only the loop's own (copies the compiler put
in, the ``while`` itself). The guard: if it grows, attribution is decaying.
Self time by class of scope from the device trace (``benchmark/scopes.py``),
mean over the chips. Layer: Step."""

from benchmark import scopes


def read(run):
    return scopes.class_ms_per_step(run, "unscoped")
