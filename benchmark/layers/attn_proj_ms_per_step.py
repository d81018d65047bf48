"""Milliseconds a step spends on the device in the attention module less its
core (scopes ``attn/qkv``, ``attn/proj`` and the split into heads between
them): self time by class of scope from the device trace
(``benchmark/scopes.py``), mean over the chips. Layer: Step."""

from benchmark import scopes


def read(run):
    return scopes.class_ms_per_step(run, "attn_proj")
