"""The limits of the benchmark's ``train_instella_ep8_8k`` cell, on the
chip at the timed size (``instella-moe-16b-ep8`` at its published widths,
one step's batch of two packed sequences of 8,192 tokens from the cell's
traffic, a seeded non-zero selection bias): the model in bfloat16 and one
step of the trainer's own pass are inside them, and the reference with its
weights in float8, the nearest precision below, is refused by the runner's
own comparison. Each side's errors are printed (``pytest -s``): they are
the two readings the limits in ``benchmark/reference/instella.py`` lie
between."""

import json
import os
from functools import partial

import jax
import jax.numpy as jnp
import pytest

from benchmark import run as harness
from pytorch_distributed_mnist_tpu.data.loader import MNISTDataLoader
from pytorch_distributed_mnist_tpu.data.tokens import synthetic_token_corpus
from pytorch_distributed_mnist_tpu.models import get_model
from pytorch_distributed_mnist_tpu.parallel.mesh import make_mesh
from pytorch_distributed_mnist_tpu.train.state import train_state_from_params
from pytorch_distributed_mnist_tpu.train.trainer import Trainer
from pytorch_distributed_mnist_tpu.utils.profiling import routing_log

BENCH = os.path.dirname(os.path.abspath(harness.__file__))


def _module(kind, name):
    return harness.load_module(
        os.path.join(BENCH, kind, f"{name}.py"), f"{kind}/{name}")


@pytest.mark.parametrize("seed", [2**31 + 1033, 2**31 + 1034])
def test_bf16_is_correct_and_float8_is_not_at_the_timed_size(seed):
    config = harness.load_json(
        os.path.join(BENCH, "configs", "instella-moe-16b-ep8.json"))
    job = harness.load_json(
        os.path.join(BENCH, "traffic", "train_lm_mtp_packed_8k_b2.json"))
    ref = _module("reference", "instella")
    lm, mtp = _module("runners", "train_lm"), _module("runners",
                                                      "train_lm_mtp")
    kwargs = ref.model_kwargs(config["kwargs"])
    model = get_model("instella", compute_dtype=jnp.bfloat16, **kwargs)
    tokens, labels = synthetic_token_corpus(
        job["batch_per_chip"], job["seq_len"], kwargs["vocab_size"],
        seed=seed, **job["documents"])
    init = jax.jit(model.init)

    def variables():
        return mtp.with_seeded_bias(init(
            jax.random.key(seed),
            jnp.zeros((1, job["seq_len"]), jnp.float32)), seed)

    def trainer_of(variables):  # as the runner builds the timed one
        loader = MNISTDataLoader(tokens, labels, batch_size=len(tokens),
                                 train=True, seed=seed)
        state = jax.jit(
            partial(train_state_from_params, model, lr=job["lr"]),
            donate_argnums=0)(variables)
        return Trainer(
            state, loader, loader, mode=job["trainer_mode"],
            mesh=make_mesh(("data",), devices=jax.devices()[:1]),
            aux_weight=job["aux_weight"], mtp_weight=job["mtp_weight"],
            bias_rate=job["bias_rate"])

    routing_log.reset()
    sound = mtp.check_against_reference(
        lm, ref, config, job, model, variables(), tokens, labels,
        trainer_of, routing_log)
    low = mtp.check_lower_precision(
        lm, ref, config, job, variables(), tokens, labels)
    for side, check in (("bf16 model", sound), ("float8 reference", low)):
        print(json.dumps({"seed": seed, "side": side, **check}), flush=True)
    assert sound["ok"], sound
    assert not low["ok"]
    over = {k for k in low["errors"] if low["errors"][k] > low["limits"][k]}
    # every array and every gradient but the routed leaves'; those, the
    # scalars, the bias and the step (Adam's of its own gradient) may pass
    assert over >= {k for k in low["errors"]
                    if k.endswith("logits") or k.startswith("grad:")}, low
