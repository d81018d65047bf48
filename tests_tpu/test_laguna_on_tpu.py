"""The limits of the benchmark's ``train_laguna_ep8_8k`` cell, on the chip
at the timed size (``laguna-xs2-ep8`` at its published widths, one packed
sequence of 8,192 tokens from the cell's traffic): the model in bfloat16
is inside them, and the reference with its weights in float8, the nearest
precision below, is refused by the runner's own comparison. Each side's
errors are printed (``pytest -s``): they are the two readings the limits
in ``benchmark/reference/laguna.py`` lie between."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from benchmark import run as harness
from pytorch_distributed_mnist_tpu.data.tokens import synthetic_token_corpus
from pytorch_distributed_mnist_tpu.models import get_model
from pytorch_distributed_mnist_tpu.ops.loss import cross_entropy

BENCH = os.path.dirname(os.path.abspath(harness.__file__))


@pytest.mark.parametrize("seed", [2**31 + 1027, 2**31 + 1028])
def test_bf16_is_correct_and_float8_is_not_at_the_timed_size(seed):
    config = harness.load_json(
        os.path.join(BENCH, "configs", "laguna-xs2-ep8.json"))
    job = harness.load_json(
        os.path.join(BENCH, "traffic", "train_lm_packed_8k_b2.json"))
    ref = harness.load_module(
        os.path.join(BENCH, "reference", "laguna.py"), "reference/laguna")
    lm = harness.load_module(
        os.path.join(BENCH, "runners", "train_lm.py"), "runners/train_lm")
    kwargs = ref.model_kwargs(config["kwargs"])
    model = get_model("laguna", compute_dtype=jnp.bfloat16, **kwargs)
    tokens, labels = synthetic_token_corpus(
        1, job["seq_len"], kwargs["vocab_size"], seed=seed,
        **job["documents"])
    params = jax.jit(model.init)(
        jax.random.key(seed), jnp.zeros((1, job["seq_len"]), jnp.float32))
    sound = lm.check_against_reference(
        ref, config, lm.model_forward(model),
        lambda logits, y: cross_entropy(logits, y, None),
        params, tokens, labels)
    low = lm.check_lower_precision(ref, config, params, tokens, labels)
    for side, check in (("bf16 model", sound), ("float8 reference", low)):
        print(json.dumps({"seed": seed, "side": side, **check}), flush=True)
    assert sound["ok"], sound
    assert not low["ok"]
    over = {k for k in low["errors"] if low["errors"][k] > low["limits"][k]}
    assert over >= set(low["errors"]) - {"loss"}, low
