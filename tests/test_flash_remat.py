"""What a recomputed block keeps of its flash attention call
(``models/decoder.py recomputed``, ``ops/pallas/flash.py``): the forward
kernel's result and row statistics, so that the gradient's program holds
one ``flash_fwd`` an attention layer and not two, with the gradients it had.
On the CPU, interpreter kernels, the three token models' tiny presets."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_mnist_tpu.data.tokens import synthetic_token_corpus
from pytorch_distributed_mnist_tpu.models import decoder, get_model
from pytorch_distributed_mnist_tpu.ops.loss import cross_entropy
from pytorch_distributed_mnist_tpu.ops.metrics import LOAD_COLLECTION
from pytorch_distributed_mnist_tpu.ops.pallas.flash import flash_attention
from pytorch_distributed_mnist_tpu.parallel.moe_dispatch import CHOICE_NAME
from pytorch_distributed_mnist_tpu.utils.profiling import flash_schedules

from test_instella import TINY as INSTELLA  # noqa: E402
from test_laguna import TINY as LAGUNA  # noqa: E402
from test_sambay import TINY as SAMBAY  # noqa: E402

T = 64
# (the tiny preset, its flash attention calls: ``instella``'s three blocks
# and its multi-token-prediction module's)
MODELS = {"laguna": (LAGUNA, 5), "sambay": (SAMBAY, 3),
          "instella": (INSTELLA, 4)}


def _rms(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.sum((got - want) ** 2)
                         / max(np.sum(want ** 2), 1e-60)))


def _kernels(fn, *args):
    """The names of the ``pallas_call`` equations in the program of ``fn``
    that are flash kernels, in order."""
    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn.params["name"]
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from walk(sub)

    return [name for name in walk(jax.make_jaxpr(fn)(*args).jaxpr)
            if name.startswith("flash_")]


def _loss_and_variables(name, **model_kw):
    kwargs, _ = MODELS[name]
    model = get_model(name, compute_dtype=jnp.float32, attention="flash",
                      **kwargs, **model_kw)
    tokens, labels = (jnp.asarray(x) for x in synthetic_token_corpus(
        2, T, kwargs["vocab_size"], seed=0, median_len=16, min_len=4))
    variables = jax.jit(model.init)(jax.random.key(0), jnp.zeros((1, T)))
    rest = {k: v for k, v in variables.items() if k != "params"}

    def loss(params):
        out = model.apply({"params": params, **rest}, tokens, train=True,
                          mutable=[LOAD_COLLECTION])[0]
        # ``instella``: the trunk's logits and the module's
        return sum(cross_entropy(logits, labels, None)
                   for logits in (out if isinstance(out, tuple) else (out,)))

    return loss, variables["params"]


@pytest.mark.parametrize("name", sorted(MODELS))
def test_a_recomputed_block_runs_the_flash_forward_once(name, monkeypatch):
    layers = MODELS[name][1]
    before = flash_schedules.snapshot()["kept_results"]
    loss, params = _loss_and_variables(name)
    plain = _kernels(jax.grad(loss), params)
    assert plain.count("flash_fwd") == layers
    assert plain.count("flash_bwd_dq_dkv") == layers
    assert flash_schedules.snapshot()["kept_results"] == before

    remat_loss, _ = _loss_and_variables(name, remat=True)
    assert sorted(_kernels(jax.grad(remat_loss), params)) == sorted(plain)
    # the result and the row statistics of every call
    kept = flash_schedules.snapshot()["kept_results"] - before
    assert kept == 2 * layers

    # The names are what does it: a policy without them (the parent's)
    # recomputes the kernel, one ``flash_fwd`` more a layer.
    monkeypatch.setattr(
        decoder, "_KEPT_NAMES",
        jax.checkpoint_policies.save_only_these_names(CHOICE_NAME))
    again = _kernels(jax.grad(remat_loss), params)
    assert again.count("flash_fwd") == 2 * layers
    assert again.count("flash_bwd_dq_dkv") == layers
    assert flash_schedules.snapshot()["kept_results"] - before == kept


@pytest.mark.parametrize("name", sorted(MODELS))
def test_kept_results_give_the_gradients_of_no_recomputation(name):
    (loss, params), (remat_loss, _) = (
        _loss_and_variables(name), _loss_and_variables(name, remat=True))
    want = jax.jit(jax.value_and_grad(loss))(params)
    got = jax.jit(jax.value_and_grad(remat_loss))(params)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-6)
    errors = jax.tree_util.tree_map(_rms, got[1], want[1])
    assert max(jax.tree_util.tree_leaves(errors)) < 1e-5, errors


def test_a_recomputed_block_with_no_policy_runs_the_forward_twice():
    """``nn.remat(TransformerBlock)`` (the ViT under ``--remat --attention
    flash``) names no policy: a named value is an identity there and the
    block recomputes its kernel, as it did."""
    before = flash_schedules.snapshot()["kept_results"]
    x = jnp.ones((2, 28, 28, 1))
    for remat, forwards in ((False, 2), (True, 4)):
        model = get_model("vit", attention_fn=flash_attention, remat=remat,
                          compute_dtype=jnp.float32)
        params = jax.jit(model.init)(jax.random.key(0), x)
        names = _kernels(jax.grad(
            lambda p: jnp.sum(model.apply(p, x, train=True))), params)
        assert names.count("flash_fwd") == forwards
        assert names.count("flash_bwd_dq_dkv") == 2
    assert flash_schedules.snapshot()["kept_results"] == before
