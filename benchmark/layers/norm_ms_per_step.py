"""Milliseconds a step spends on the device in ops whose scope is a LayerNorm
(``ln1``, ``ln2``, ``ln_f``); a norm that XLA fused into a neighbouring
matmul is counted with that matmul. Self time by class of scope from the
device trace (``benchmark/scopes.py``), mean over the chips. Layer: Step."""

from benchmark import scopes


def read(run):
    return scopes.class_ms_per_step(run, "norm")
