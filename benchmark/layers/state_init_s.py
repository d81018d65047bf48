"""Seconds set-up spends making the weights, Adam's moments and the
buffers from the seed: every top-level span named ``init`` or
``init_state`` (``train_lm_mtp`` opens ``init`` twice), each a jitted call
with its trace, its lowering, its compile or load and its execution.
Layer: Entry and compile."""

from benchmark import setup_spans


def read(run):
    return setup_spans.seconds(run, "init", "init_state")
