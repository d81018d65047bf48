"""Milliseconds a step spends on the device in the latent attention's own
ops (scope ``attn/mla``: the query, latent and up projections, the norms
over the latent and over each head's query and key, the rotary, the output
gate and the output projection; forward, recomputed forward and backward;
the core under ``attn_core/full`` is not in it): device trace,
``benchmark/scopes_mla.py``. Layer: Step."""

from benchmark import scopes_mla


def read(run):
    return scopes_mla.class_ms_per_step(run, "mla_proj")
