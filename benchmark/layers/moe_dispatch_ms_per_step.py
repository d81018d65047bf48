"""Milliseconds a step spends on the device ordering the (token, choice)
pairs by expert, gathering their rows and adding the weighted results back
(scopes ``block*/moe/dispatch`` and ``block*/moe/combine``; forward and
backward): device trace, ``benchmark/scopes_lm.py``. Layer: Step."""

from benchmark import scopes_lm


def read(run):
    return scopes_lm.class_ms_per_step(run, "moe_dispatch")
