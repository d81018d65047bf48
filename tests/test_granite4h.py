"""The ``granite_hybrid`` model against its plain reference
(``benchmark/reference/granite4h.py``) on the CPU at the tiny preset (hidden
64; Mamba-2 layers of 4 heads of 32 with 16 states, one attention layer of
4 query on 2 key-value heads of 16), and what it is made of: the four
multipliers, the vocabulary slice, recomputation, the initialisation, the
controls of the reference's limits; and that the three other token models
lower to what they lowered to before this one came."""

import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import granite4h as ref
from pytorch_distributed_mnist_tpu.data.tokens import synthetic_token_corpus
from pytorch_distributed_mnist_tpu.models import get_model
from pytorch_distributed_mnist_tpu.ops.loss import cross_entropy
from pytorch_distributed_mnist_tpu.train.state import create_train_state
from pytorch_distributed_mnist_tpu.train.steps import (
    make_train_epoch,
    make_train_step,
)
from pytorch_distributed_mnist_tpu.utils.profiling import scan_log

T = 48
# The tiny preset as a configuration file's kwargs would carry it.
TINY = {
    "vocab_size": 256, "hidden_size": 64, "num_heads": 4, "num_kv_heads": 2,
    "head_dim": 16, "mlp_size": 128,
    "layer_types": ["mamba", "mamba", "attention", "mamba"],
    "mamba_n_heads": 4, "mamba_d_head": 32, "mamba_d_state": 16,
    "mamba_d_conv": 4, "embedding_multiplier": 12.0,
    "residual_multiplier": 0.22, "attention_multiplier": 0.015625,
    "logits_scaling": 8.0, "rms_eps": 1e-5,
}
MULTIPLIERS = ("embedding_multiplier", "residual_multiplier",
               "attention_multiplier", "logits_scaling")


def _rms(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.sum((got - want) ** 2)
                         / max(np.sum(want ** 2), 1e-60)))


def _seeded(seed=0, dtype=jnp.float32, **model_kw):
    """The model, parameters with every leaf moved off its initial value
    (biases start at zero, scales at one), tokens and labels."""
    model = get_model("granite_hybrid", compute_dtype=dtype,
                      **{**TINY, **model_kw})
    tokens, labels = synthetic_token_corpus(
        2, T, TINY["vocab_size"], seed=seed, median_len=16, min_len=4)
    params = jax.jit(model.init)(jax.random.key(seed), jnp.zeros((1, T)))
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.key(seed + 1), len(leaves))
    params = jax.tree_util.tree_unflatten(tree, [
        x + 0.05 * jax.random.normal(k, x.shape)
        for x, k in zip(leaves, keys)])
    return model, params, jnp.asarray(tokens), jnp.asarray(labels)


def _loss_logits_grads(forward, loss_of, params):
    def loss_fn(p):
        logits = forward(p)
        return loss_of(logits), logits

    (loss, logits), grads = jax.jit(
        jax.value_and_grad(loss_fn, has_aux=True))(params)
    return loss, logits, grads


@functools.lru_cache(maxsize=None)
def _system(**model_kw):
    """(loss, logits, gradients) of the seeded model on the seeded batch;
    computed once a variant, several tests read it."""
    model, params, tokens, labels = _seeded(**model_kw)
    return _loss_logits_grads(
        lambda p: model.apply(p, tokens, train=True),
        lambda lg: cross_entropy(lg, labels, None), params)


@functools.lru_cache(maxsize=None)
def _reference(rounded=()):
    _, params, tokens, labels = _seeded()
    shape = ref.shape_from_kwargs(TINY)
    return _loss_logits_grads(
        lambda p: ref.forward(p, tokens, rounded=rounded, **shape),
        lambda lg: ref.cross_entropy(lg, labels), params)


def _leaf(tree, path):
    for key in path.split("/"):
        tree = tree[key]
    return tree


def _errors(got, want):
    """The runner's three measures (``runners/train_lm.py``) of a system's
    (loss, logits, gradients) against the reference's, over the reference's
    named leaves."""
    out = {"loss": abs(float(got[0]) - float(want[0])) / float(want[0]),
           "logits": _rms(got[1], want[1])}
    for path in ref.grad_leaves(TINY):
        out[f"grad:{path}"] = _rms(_leaf(got[2], path), _leaf(want[2], path))
    return out


def _refused(errors, tol):
    return [k for k, v in errors.items()
            if v > tol["grad" if k.startswith("grad:") else k]]


@pytest.mark.parametrize("variant", [
    {}, {"remat": True}, {"attention": "flash"},
    {"remat": True, "attention": "flash"}],
    ids=["dense", "remat", "flash_interpreted", "remat_and_flash"])
def test_model_matches_reference_logits_loss_and_every_leaf(variant):
    got, want = _system(**variant), _reference()
    errors = _errors(got, want)
    assert not _refused(errors, ref.TOLERANCES["f32"]), errors
    assert errors["loss"] < 1e-6 and errors["logits"] < 1e-5
    named = ref.grad_leaves(TINY)
    # W_in, the convolution, dt_bias, A_log, D, the gated norm, W_out, W_q,
    # W_k, an MLP and the embedding
    assert {p.split("/", 2)[2] for p in named} >= {
        "ssd/in_proj", "ssd/conv_kernel", "ssd/dt_bias", "ssd/A_log",
        "ssd/D", "ssd/norm/scale", "ssd/out_proj/kernel", "attn/q/kernel",
        "attn/k/kernel", "mlp/down/kernel", "embedding"}
    # and every other leaf of the tree
    every = jax.tree_util.tree_map(_rms, got[2], want[2])
    assert max(jax.tree_util.tree_leaves(every)) < 1e-4, every


@pytest.mark.parametrize("what", ref.ROUNDABLE)
def test_a_bfloat16_value_in_the_recurrence_or_the_head_breaks_a_limit(what):
    """The control of ``TOLERANCES['f32']``: the reference itself with
    ``dt``, the running decay, the carried state or the logits rounded
    through bfloat16 is refused by at least one limit that the system
    keeps by a factor of ten."""
    tol = ref.TOLERANCES["f32"]
    low = _errors(_reference(rounded=(what,)), _reference())
    assert _refused(low, tol), low
    kept = _errors(_system(), _reference())
    for key in _refused(low, tol):
        assert kept[key] < 0.1 * tol["grad" if key.startswith("grad:")
                                     else key], key


def test_the_slice_is_the_uncut_vocabularys_rows_and_the_loss_is_over_it():
    """One chip's share of vocabulary parallelism: with the embedding's
    first 64 rows as the whole embedding, the logits are the uncut model's
    logits on those ids (the stream never reads a row it does not look up),
    and the loss is the cross entropy over those 64 alone."""
    model, params, _, _ = _seeded()
    held = 64
    tokens, labels = synthetic_token_corpus(2, T, held, seed=4,
                                            median_len=16, min_len=4)
    part = get_model("granite_hybrid", compute_dtype=jnp.float32,
                     **{**TINY, "vocab_size": held})
    cut = jax.tree_util.tree_map(lambda x: x, params)
    cut["params"]["embed"] = {
        "embedding": params["params"]["embed"]["embedding"][:held]}
    whole = model.apply(params, tokens)
    sliced = part.apply(cut, tokens)
    assert sliced.shape == (2, T, held)
    np.testing.assert_allclose(sliced, whole[..., :held], rtol=1e-5,
                               atol=1e-6)
    over_slice = cross_entropy(sliced, labels, None)
    logp = jax.nn.log_softmax(whole[..., :held], axis=-1)
    counted = labels >= 0
    want = -jnp.sum(jnp.where(counted, jnp.take_along_axis(
        logp, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0], 0.0)) \
        / jnp.sum(counted)
    assert float(over_slice) == pytest.approx(float(want), rel=1e-5)
    assert float(over_slice) < float(cross_entropy(whole, labels, None))


@pytest.mark.parametrize("name", MULTIPLIERS)
def test_each_multiplier_moved_alone_changes_the_output(name):
    model, params, tokens, _ = _seeded()
    base = model.apply(params, tokens)
    moved = get_model("granite_hybrid", compute_dtype=jnp.float32, **{
        **TINY, name: TINY[name] * 1.5}).apply(params, tokens)
    assert float(jnp.max(jnp.abs(moved - base))) > 1e-4
    shape = ref.shape_from_kwargs({**TINY, name: TINY[name] * 1.5})
    assert _rms(moved, ref.forward(params, tokens, **shape)) < 1e-5


def test_the_multipliers_are_where_the_equations_put_them():
    """``logits_scaling`` divides the logits and nothing else; the
    ``embedding_multiplier`` scales the stream's start, which the first
    norm undoes in the branches but not in the stream they are added to."""
    model, params, tokens, _ = _seeded()
    base = model.apply(params, tokens)
    halved = get_model("granite_hybrid", compute_dtype=jnp.float32, **{
        **TINY, "logits_scaling": 16.0}).apply(params, tokens)
    np.testing.assert_allclose(halved, base / 2, rtol=1e-6, atol=1e-7)
    defaults = get_model("granite_hybrid")
    assert [getattr(defaults, m) for m in MULTIPLIERS] \
        == [12.0, 0.22, 0.015625, 8.0]


def test_initialisation_is_mamba2s_own():
    model, _, _, _ = _seeded()
    params = jax.jit(model.init)(
        jax.random.key(3), jnp.zeros((1, T)))["params"]
    ssd = params["block0"]["ssd"]
    a = jnp.exp(ssd["A_log"])
    assert ssd["A_log"].shape == ssd["D"].shape == ssd["dt_bias"].shape \
        == (4,)
    assert 1.0 <= float(a.min()) and float(a.max()) <= 16.0
    dt = jax.nn.softplus(ssd["dt_bias"])
    assert 1e-3 * 0.99 < float(dt.min()) and float(dt.max()) < 1e-1 * 1.01
    assert float(jnp.min(ssd["D"])) == float(jnp.max(ssd["D"])) == 1.0
    assert ssd["in_proj"].shape == (64, 128 + (128 + 2 * 16) + 4)
    assert ssd["conv_kernel"].shape == (4, 128 + 2 * 16)
    assert 0.01 < float(jnp.std(params["embed"]["embedding"])) < 0.03
    assert "attn" in params["block2"] and "ssd" not in params["block2"]


def test_scans_are_counted_as_chunked_and_an_unknown_kind_is_refused():
    before = scan_log.snapshot()
    model = get_model("granite_hybrid", compute_dtype=jnp.float32, **TINY)
    jax.eval_shape(model.init, jax.random.key(0), jnp.zeros((1, T)))
    after = scan_log.snapshot()
    assert after["chunked_sites"] == before["chunked_sites"] + 3
    assert after["sites"] == before["sites"]
    wrong = dict(TINY, layer_types=["mamba", "gmu"])
    with pytest.raises(ValueError, match="unknown layer kind 'gmu'"):
        jax.eval_shape(get_model("granite_hybrid", **wrong).init,
                       jax.random.key(0), jnp.zeros((1, T)))


def test_trains_from_the_command_line_on_token_data(tmp_path):
    import json

    from pytorch_distributed_mnist_tpu import cli

    args = cli.build_parser().parse_args([
        "--model", "granite_hybrid", "--dataset", "synthetic_tokens",
        "--seq-len", "32", "--synthetic-train-size", "32",
        "--synthetic-test-size", "8", "--batch-size", "8", "--epochs", "2",
        "--dtype", "f32", "--seed", "1",
        "--checkpoint-dir", str(tmp_path / "ckpt"),
        "--root", str(tmp_path / "data"),
        "--metrics-file", str(tmp_path / "m.jsonl")])
    cli.run(args)
    rows = [json.loads(x) for x in open(tmp_path / "m.jsonl")]
    epochs = [r for r in rows if "train_loss" in r]
    assert len(epochs) == 2
    assert epochs[1]["train_loss"] < epochs[0]["train_loss"]
    scans = next(r for r in rows
                 if r.get("kind") == "run_summary")["state_scans"]
    # the process's count: three Mamba-2 layers a traced program
    assert scans["chunked_sites"] >= 3
    assert scans["chunked_chunks_per_site"] >= 1
    assert scans["chunked_state_bytes_kept_per_site"] > 0


# -- the other models' programs are what they were --------------------------

# Taken with ``_lowered`` on PR 37's parent (commit 686a357): the donated
# train step and the scanned pass of the tiny presets, with recomputation,
# the dense core and the interpreted flash kernels (tests/test_instella.py
# holds ``laguna``'s and ``sambay``'s dense step and pass). ``laguna``'s is
# PR 39's tree's, whose gate a head works on the packed view.
PARENT_LOWERED = {
    ("laguna", "step", "flash"):
        "696309295e20fceddcc539b21b0fe1ae7e273752f08bb062811195b511d71748",
    ("sambay", "step", "flash"):
        "1b9a09df7d4f52ddac486fd4f9514e173e8ac2811dfa3fb7c83573d61d696520",
    ("instella", "step", "flash"):
        "aaaad8ec1011c05e45490f9d1d0e215b35f325b772bee6121f81754836184852",
    ("instella", "step", "dense"):
        "141fca65df42e8eb1f8e8be5c099fb5cb44b3ae9c6f47d726a42aa6b93dbc235",
    ("instella", "epoch", "dense"):
        "cb7f39d4e14ce6eab55f4bf759dd3b5cbf95a7f65a0b4163192f97ac24794fbc",
}


def _lowered(name, program, attention):
    model = get_model(name, remat=True, attention=attention)
    state = jax.eval_shape(lambda: create_train_state(
        model, jax.random.key(0), input_shape=(1, 32)))
    batch = {"image": jax.ShapeDtypeStruct((2, 32), jnp.int32),
             "label": jax.ShapeDtypeStruct((2, 32), jnp.int32),
             "mask": jax.ShapeDtypeStruct((2,), jnp.float32)}
    if program == "step":
        return make_train_step().lower(state, batch).as_text()
    batches = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct((2,) + s.shape, s.dtype), batch)
    return make_train_epoch().lower(state, batches).as_text()


@pytest.mark.parametrize("name,program,attention", sorted(PARENT_LOWERED))
def test_the_other_token_models_lower_to_the_parents_text(
        name, program, attention):
    """This model imports ``causal_conv``, ``GatedMLP`` and Mamba's
    initialisers from ``models/sambay.py`` and ``RMSNorm``, ``attend`` and
    ``recomputed`` from ``models/decoder.py``, and ``ScanLog`` learnt a
    second kind of scan; what ``laguna``, ``sambay`` and ``instella``
    trace lowers to the text it lowered to on the parent."""
    text = _lowered(name, program, attention)
    assert len(text) > 100_000
    assert hashlib.sha256(text.encode()).hexdigest() \
        == PARENT_LOWERED[(name, program, attention)]
