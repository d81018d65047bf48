"""The limits of the benchmark's ``train_granite4h_vp8_8k`` cell, on the
chip at the timed size (``granite-4.0-h-micro-vp8`` at its published widths,
one packed sequence of 8,192 tokens from the cell's traffic): the model in
bfloat16 is inside them, and the reference with its weights in float8, the
nearest precision below, is refused by the runner's own comparison. Each
side's errors are printed (``pytest -s``): they are the two readings the
limits in ``benchmark/reference/granite4h.py`` lie between."""

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


def _module(kind, name):
    return harness.load_module(
        os.path.join(BENCH, kind, f"{name}.py"), f"{kind}/{name}")


@pytest.mark.parametrize("seed", [2**31 + 3701, 2**31 + 3702])
def test_bf16_is_correct_and_float8_is_not_at_the_timed_size(seed):
    config = harness.load_json(
        os.path.join(BENCH, "configs", "granite-4.0-h-micro-vp8.json"))
    job = harness.load_json(
        os.path.join(BENCH, "traffic", "train_lm_packed_8k_b1.json"))
    ref = _module("reference", "granite4h")
    lm, plain = _module("runners", "train_lm"), \
        _module("runners", "train_lm_plain")
    kwargs = ref.model_kwargs(config["kwargs"])
    model = get_model("granite_hybrid", compute_dtype=jnp.bfloat16, **kwargs)
    tokens, labels = synthetic_token_corpus(
        1, job["seq_len"], kwargs["vocab_size"], seed=seed,
        **job["documents"])
    params = jax.jit(model.init)(
        jax.random.key(seed), jnp.zeros((1, job["seq_len"]), jnp.float32))
    sound = plain.check_against_reference(
        lm, ref, config, lambda p, x: model.apply(p, x, train=True),
        lambda logits, y: cross_entropy(logits, y, None),
        params, tokens, labels)
    low = plain.check_lower_precision(lm, ref, config, params, tokens, labels)
    for side, check in (("bf16 model", sound), ("float8 reference", low)):
        print(json.dumps({"seed": seed, "side": side, **check}), flush=True)
    assert sound["ok"], sound
    assert not low["ok"], low
