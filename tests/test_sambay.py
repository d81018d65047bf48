"""The ``sambay`` model against its plain reference
(``benchmark/reference/phi4flash.py``) on the CPU at the tiny preset (hidden
64, 4 query and 2 key-value heads of 16, 4 states a channel, window 8, one
layer of every kind), and the pieces it is made of: the differential
combine, who reads what layers publish, recomputation, the convolution."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import phi4flash as ref
from pytorch_distributed_mnist_tpu.data.tokens import synthetic_token_corpus
from pytorch_distributed_mnist_tpu.models import get_model, sambay
from pytorch_distributed_mnist_tpu.ops.loss import cross_entropy
from pytorch_distributed_mnist_tpu.utils.profiling import scan_log

T = 48
# The tiny preset as a configuration file's kwargs would carry it.
TINY = {
    "vocab_size": 256, "hidden_size": 64, "num_heads": 4, "num_kv_heads": 2,
    "head_dim": 16, "mlp_size": 128,
    "layer_types": ["mamba", "sliding_attention", "mamba", "full_attention",
                    "gmu", "cross_attention"],
    "layer_ids": [0, 1, 16, 17, 18, 19], "window": 8, "d_inner": 128,
    "d_state": 4, "d_conv": 4, "dt_rank": 4, "layer_norm_eps": 1e-5,
}


def _rms(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.sum((got - want) ** 2)
                         / max(np.sum(want ** 2), 1e-60)))


def _seeded(seed=0, dtype=jnp.float32, **model_kw):
    """The model, tokens and labels, and parameters with every leaf moved
    off its initial value (biases start at zero, scales at one)."""
    model = get_model("sambay", compute_dtype=dtype, **TINY, **model_kw)
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


def _leaf(tree, path):
    for key in path.split("/"):
        tree = tree[key]
    return tree


def test_model_matches_reference_logits_loss_and_every_kind_of_leaf():
    _, params, tokens, labels = _seeded()
    shape = ref.shape_from_kwargs(TINY)
    got = _system()
    want = _loss_logits_grads(
        lambda p: ref.forward(p, tokens, **shape),
        lambda lg: ref.cross_entropy(lg, labels), params)
    assert abs(float(got[0]) - float(want[0])) < 1e-5 * float(want[0])
    assert _rms(got[1], want[1]) < 1e-5
    named = ref.grad_leaves(TINY)
    # W_in, A_log, W_dt, conv, W_1g, lq1, sub-norm scale, the cross W_q, E
    assert {p.split("/", 2)[2] for p in named} >= {
        "ssm/in_proj/kernel", "ssm/A_log", "ssm/dt_proj/kernel",
        "ssm/conv_kernel", "gmu/in_proj/kernel", "attn/lq1", "attn/subln",
        "attn/q/kernel", "embedding"}
    for path in named:
        assert _rms(_leaf(got[2], path), _leaf(want[2], path)) < 1e-4, path
    # and every other leaf of the tree
    errors = jax.tree_util.tree_map(_rms, got[2], want[2])
    assert max(jax.tree_util.tree_leaves(errors)) < 1e-4, errors


def test_remat_on_and_off_give_the_same_gradients():
    outs = [_system(), _system(remat=True)]
    assert float(outs[0][0]) == pytest.approx(float(outs[1][0]), rel=1e-6)
    errors = jax.tree_util.tree_map(_rms, outs[0][2], outs[1][2])
    assert max(jax.tree_util.tree_leaves(errors)) < 1e-5


def test_flash_kernels_give_the_dense_models_numbers():
    outs = [_system(), _system(attention="flash")]
    assert _rms(outs[1][1], outs[0][1]) < 1e-5
    errors = jax.tree_util.tree_map(_rms, outs[1][2], outs[0][2])
    assert max(jax.tree_util.tree_leaves(errors)) < 1e-4


def test_differential_combine_against_two_explicit_softmaxes():
    """One attention layer against ``(1 - l0) RMSNorm(A_1 v - lambda A_2
    v)`` written out with two softmaxes a query pair."""
    b, t, d, heads, kv_heads, layer_id = 2, 12, 8, 4, 2, 17
    layer = sambay.DiffAttention(
        num_heads=heads, num_kv_heads=kv_heads, head_dim=d, window=None,
        layer_id=layer_id, depth=6, attention="dense",
        compute_dtype=jnp.float32)
    u = jax.random.normal(jax.random.key(0), (b, t, 24))
    params = layer.init(jax.random.key(1), u)
    p = params["params"]
    p = {**p, "subln": p["subln"] + 0.3,
         "qkv": {**p["qkv"], "bias": p["qkv"]["bias"] + 0.2}}
    got, (k_pub, v_pub) = layer.apply({"params": p}, u)

    qkv = u @ p["qkv"]["kernel"] + p["qkv"]["bias"]
    q = qkv[..., :heads * d].reshape(b, t, heads, d)
    k = qkv[..., heads * d:(heads + kv_heads) * d].reshape(b, t, kv_heads, d)
    v = qkv[..., (heads + kv_heads) * d:].reshape(b, t, kv_heads // 2, 2 * d)
    np.testing.assert_allclose(k_pub, k.reshape(b, t, -1), rtol=1e-6)
    np.testing.assert_allclose(v_pub, v.reshape(b, t, -1), rtol=1e-6)
    start = sambay.lambda_init(layer_id)
    assert start == pytest.approx(0.8 - 0.6 * np.exp(-0.3 * 17))
    lam = np.exp(np.sum(p["lq1"] * p["lk1"])) \
        - np.exp(np.sum(p["lq2"] * p["lk2"])) + start
    mask = np.tril(np.ones((t, t), bool))
    outs = []
    for pair in range(heads // 2):  # both query pairs read key-value pair 0
        maps = []
        for i in (0, 1):
            s = np.einsum("bqd,bkd->bqk", q[:, :, 2 * pair + i], k[:, :, i]) \
                / np.sqrt(d)
            s = np.where(mask, s, -np.inf)
            a = np.exp(s - s.max(-1, keepdims=True))
            maps.append(a / a.sum(-1, keepdims=True))
        o = np.einsum("bqk,bke->bqe", maps[0] - lam * maps[1], v[:, :, 0])
        o = o / np.sqrt(np.mean(o ** 2, -1, keepdims=True) + 1e-5)
        outs.append((1 - start) * o * p["subln"])
    want = np.concatenate(outs, -1) @ p["proj"]["kernel"] + p["proj"]["bias"]
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-6)


def _without(params, *paths):
    """``params`` with the named kernels (and biases beside them) zero."""
    params = jax.tree_util.tree_map(lambda x: x, params)
    for path in paths:
        node = params["params"]
        *parents, last = path.split("/")
        for key in parents:
            node = node[key]
        node[last] = jax.tree_util.tree_map(jnp.zeros_like, node[last])
    return params


def _moved(params, path, seed=7):
    params = jax.tree_util.tree_map(lambda x: x, params)
    node = params["params"]
    *parents, last = path.split("/")
    for key in parents:
        node = node[key]
    node[last] = node[last] + jax.random.normal(
        jax.random.key(seed), node[last].shape)
    return params


@functools.lru_cache(maxsize=None)
def _jitted_apply():
    model, params, tokens, _ = _seeded()
    return jax.jit(model.apply), params, tokens


# With the mixers of layers 0-3 cut off from the residual stream (output
# projection zero), what they compute reaches the logits only through what
# a later layer reads of it.
CUT = ("block0/ssm/out_proj", "block1/attn/proj", "block2/ssm/out_proj",
       "block3/attn/proj")


@pytest.mark.parametrize("moved, reader, reads", [
    ("block2/ssm/in_proj/kernel", "block4/gmu/out_proj", True),
    ("block0/ssm/in_proj/kernel", None, False),
    ("block3/attn/qkv/kernel", "block5/attn/proj", True),
    ("block1/attn/qkv/kernel", None, False),
], ids=["gmu_reads_the_last_mamba_layer", "nobody_reads_the_first",
        "cross_reads_the_full_layer", "nobody_reads_the_sliding_layer"])
def test_who_reads_what_a_layer_publishes(moved, reader, reads):
    apply, params, tokens = _jitted_apply()
    base = _without(params, *CUT)
    before = apply(base, tokens)
    after = apply(_moved(base, moved), tokens)
    assert (float(jnp.max(jnp.abs(after - before))) > 1e-4) == reads
    if reader:  # and through that reader alone
        cut = _without(base, reader)
        assert float(jnp.max(jnp.abs(
            apply(_moved(cut, moved), tokens) - apply(cut, tokens)))) == 0.0


def test_readers_are_counted_and_a_reader_before_its_source_is_refused():
    before = scan_log.snapshot()
    model = get_model("sambay", compute_dtype=jnp.float32, **TINY)
    jax.eval_shape(model.init, jax.random.key(0), jnp.zeros((1, T)))
    after = scan_log.snapshot()
    assert after["memory_readers"] == before["memory_readers"] + 1
    assert after["kv_readers"] == before["kv_readers"] + 1
    assert after["sites"] == before["sites"] + 2  # two Mamba layers
    wrong = dict(TINY, layer_types=["gmu", "mamba"], layer_ids=[0, 1])
    with pytest.raises(ValueError, match="reads what the last mamba layer"):
        jax.eval_shape(get_model("sambay", **wrong).init,
                       jax.random.key(0), jnp.zeros((1, T)))


def test_causal_convolution_sees_the_past_only():
    x = jax.random.normal(jax.random.key(0), (1, 10, 3))
    kernel = jax.random.normal(jax.random.key(1), (4, 3))
    bias = jnp.arange(3.0)
    y = sambay.causal_conv(x, kernel, bias)
    for t in (0, 2, 9):
        want = bias + sum(kernel[j] * x[0, t - 3 + j]
                          for j in range(4) if t - 3 + j >= 0)
        np.testing.assert_allclose(y[0, t], want, rtol=1e-5, atol=1e-6)
    moved = sambay.causal_conv(x.at[0, 6].add(1.0), kernel, bias)
    assert float(jnp.max(jnp.abs((moved - y)[0, :6]))) == 0.0


def test_mamba_initialisation_is_its_own():
    model, _, _, _ = _seeded()
    params = jax.jit(model.init)(
        jax.random.key(3), jnp.zeros((1, T)))["params"]
    ssm = params["block0"]["ssm"]
    np.testing.assert_allclose(
        -jnp.exp(ssm["A_log"]), -jnp.broadcast_to(jnp.arange(1.0, 5.0),
                                                  (128, 4)), rtol=1e-6)
    dt = jax.nn.softplus(ssm["dt_bias"])
    assert 1e-3 * 0.99 < float(dt.min()) and float(dt.max()) < 1e-1 * 1.01
    assert float(jnp.min(ssm["D"])) == float(jnp.max(ssm["D"])) == 1.0
    assert 0.05 < float(jnp.std(params["block1"]["attn"]["lq1"])) < 0.2


def test_trains_from_the_command_line_on_token_data(tmp_path):
    from pytorch_distributed_mnist_tpu import cli

    args = cli.build_parser().parse_args([
        "--model", "sambay", "--dataset", "synthetic_tokens", "--seq-len",
        "32", "--synthetic-train-size", "32", "--synthetic-test-size", "8",
        "--batch-size", "8", "--epochs", "2", "--dtype", "f32", "--seed",
        "1", "--checkpoint-dir", str(tmp_path / "ckpt"),
        "--root", str(tmp_path / "data"),
        "--metrics-file", str(tmp_path / "m.jsonl")])
    cli.run(args)
    import json

    rows = [json.loads(x) for x in open(tmp_path / "m.jsonl")]
    epochs = [r for r in rows if "train_loss" in r]
    assert len(epochs) == 2
    assert epochs[1]["train_loss"] < epochs[0]["train_loss"]
    summary = next(r for r in rows if r.get("kind") == "run_summary")
    assert summary["state_scans"]["sites"] > 0
    assert summary["state_scans"]["memory_readers"] > 0
