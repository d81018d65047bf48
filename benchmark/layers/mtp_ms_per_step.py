"""Milliseconds a step spends on the device in the multi-token-prediction
module (scope ``mtp``: the merge of the trunk's output with the next
token's embedding, the module's block, its pass over the head and its loss;
forward, recomputed forward and backward): device trace,
``benchmark/scopes_mla.py``. Layer: Step."""

from benchmark import scopes_mla


def read(run):
    return scopes_mla.class_ms_per_step(run, "mtp")
