"""Milliseconds a step spends on the device in the attention core of the
window layers (scope ``attn_core/window``), as ``attn_full_ms_per_step``
for the full layers: device trace, ``benchmark/scopes_lm.py``. Layer:
Step."""

from benchmark import scopes_lm


def read(run):
    return scopes_lm.class_ms_per_step(run, "attn_window")
