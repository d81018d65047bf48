"""Milliseconds a step spends on the device in the expert layers' routers
(scope ``block*/moe/router``: the score matmul, sigmoid, top-k and weights;
forward and backward): self time by class of scope from the device trace
(``benchmark/scopes_lm.py``), mean over the chips. Layer: Step."""

from benchmark import scopes_lm


def read(run):
    return scopes_lm.class_ms_per_step(run, "moe_router")
