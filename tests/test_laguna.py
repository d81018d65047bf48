"""The ``laguna`` decoder against its plain reference
(``benchmark/reference/laguna.py``) on the CPU at a tiny size (hidden 64, 2
key-value heads, 16 experts top-4, window 8, T = 64, a dense layer and one
period), and the pieces it is made of: rotary frequencies, grouped
key-value heads, the expert layer's share and its routing extremes, the
token corpus, and the generalised loss and metrics (whose numbers for the
image models are bit-for-bit what they were)."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import laguna as ref
from pytorch_distributed_mnist_tpu.data.tokens import (
    EOD,
    IGNORE,
    synthetic_token_corpus,
)
from pytorch_distributed_mnist_tpu.models import get_model
from pytorch_distributed_mnist_tpu.models import decoder
from pytorch_distributed_mnist_tpu.models.moe import SparseExperts
from pytorch_distributed_mnist_tpu.ops.loss import cross_entropy
from pytorch_distributed_mnist_tpu.parallel import moe_dispatch
from pytorch_distributed_mnist_tpu.ops.metrics import (
    metrics_init,
    metrics_update,
)
from pytorch_distributed_mnist_tpu.parallel.moe_dispatch import (
    held_experts_forward,
    route_topk,
)
from pytorch_distributed_mnist_tpu.utils.profiling import (
    device_report,
    head_gate_sites,
)

T = 64
# The tiny preset as a configuration file's kwargs would carry it.
TINY = {
    "vocab_size": 256, "hidden_size": 64, "head_dim": 16, "num_kv_heads": 2,
    "layer_types": ["full_attention"] + ["sliding_attention"] * 3
    + ["full_attention"],
    "heads_per_layer": [4, 6, 6, 6, 4],
    "mlp_layer_types": ["dense"] + ["sparse"] * 4,
    "window": 8, "rope": decoder.TINY_ROPE, "dense_mlp_size": 256,
    "expert_size": 32, "shared_expert_size": 32, "num_experts": 16,
    "top_k": 4, "experts_held": [4, 8], "routed_scale": 2.5,
    "rms_eps": 1e-6,
}


def _rel_err(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                  1e-30))


def _system_and_reference(kwargs, dtype=jnp.float32, seed=0, **model_kw):
    model = get_model("laguna", compute_dtype=dtype, **kwargs, **model_kw)
    tokens, labels = synthetic_token_corpus(
        2, T, kwargs["vocab_size"], seed=seed, median_len=16, min_len=4)
    params = model.init(jax.random.key(seed), jnp.zeros((1, T)))
    leaves = ref.grad_leaves(kwargs)

    def pick(grads):
        out = {}
        for path in leaves:
            node = grads
            for key in path.split("/"):
                node = node[key]
            out[path] = node
        return out

    def both(forward, loss_of):
        def loss_fn(p):
            logits = forward(p)
            return loss_of(logits), logits

        (loss, logits), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        return logits, loss, pick(grads)

    shape = ref.shape_from_kwargs(kwargs)
    got = both(lambda p: model.apply(p, tokens, train=True),
               lambda lg: cross_entropy(lg, jnp.asarray(labels), None))
    want = both(lambda p: ref.forward(p, jnp.asarray(tokens), **shape),
                lambda lg: ref.cross_entropy(lg, jnp.asarray(labels)))
    return got, want, leaves


@pytest.mark.parametrize("held", [[4, 8], None], ids=["share", "all"])
def test_model_matches_reference_logits_loss_and_every_kind_of_leaf(held):
    kwargs = {**TINY, "experts_held": held}
    got, want, leaves = _system_and_reference(kwargs)
    tol = ref.TOLERANCES["f32"]
    assert got[0].shape == (2, T, 256) and got[0].dtype == jnp.float32
    assert _rel_err(got[0], want[0]) < tol["logits"]
    assert _rel_err(got[1], want[1]) < tol["loss"]
    assert len(leaves) == 11
    for path in leaves:
        assert got[2][path].shape == want[2][path].shape
        assert float(jnp.max(jnp.abs(want[2][path]))) > 0, path
        assert _rel_err(got[2][path], want[2][path]) < tol["grad"], path


def test_flash_and_remat_give_the_dense_models_numbers():
    got, want, leaves = _system_and_reference(
        TINY, attention="flash", remat=True)
    assert _rel_err(got[0], want[0]) < ref.TOLERANCES["f32"]["logits"]
    for path in leaves:
        assert _rel_err(got[2][path], want[2][path]) < 1e-2, path


@pytest.mark.parametrize("remat", [False, True])
def test_a_recomputed_block_keeps_its_experts_choice(remat):
    """The backward pass of a recomputed block reads the choice its forward
    made (``route_topk``'s ``CHOICE_NAME``, the blocks' ``remat`` policy)
    and does not choose again: XLA rounds a recomputed block's bfloat16
    values at other places, and a nearly tied score then names another
    expert than the one the forward result came from. So the gradient's
    program holds one ``top_k`` a sparse layer, with and without
    recomputation (without the policy, two)."""
    model = get_model("laguna", compute_dtype=jnp.float32, remat=remat,
                      **TINY)
    tokens = jnp.zeros((1, T), jnp.int32)
    params = model.init(jax.random.key(0), tokens)
    program = str(jax.make_jaxpr(jax.grad(
        lambda p: jnp.sum(model.apply(p, tokens))))(params))
    assert program.count(" top_k[") == TINY["mlp_layer_types"].count("sparse")


def test_a_lower_precision_than_the_file_states_is_not_correct():
    """A configuration that states f32 is held to f32's tolerances; the
    same model computing in bfloat16 fails at least one of them."""
    got, want, leaves = _system_and_reference(TINY, dtype=jnp.bfloat16)
    tol = ref.TOLERANCES["f32"]
    errors = {"logits": _rel_err(got[0], want[0]),
              "loss": _rel_err(got[1], want[1])}
    errors.update({p: _rel_err(got[2][p], want[2][p]) for p in leaves})
    failed = [k for k, v in errors.items()
              if v > tol.get(k, tol["grad"])]
    assert failed, errors


def test_eight_shares_add_up_to_the_uncut_layer():
    """The expert layer run once for each of the 8 shares, the routed
    parts added and the shared expert counted once, is the uncut
    reference layer."""
    e, k, c, f = 16, 4, 64, 32
    whole = SparseExperts(num_experts=e, top_k=k, width=f, shared_width=f,
                          depth=1, routed_scale=2.5,
                          compute_dtype=jnp.float32)
    x = jax.random.normal(jax.random.key(1), (2, 24, c))
    params = whole.init(jax.random.key(2), x)["params"]
    shared = ref._swiglu(x, *(params["shared"][n]["kernel"]
                              for n in ("gate", "up", "down")))
    total = jnp.zeros_like(x)
    for share in range(8):
        first = 2 * share
        part = SparseExperts(
            num_experts=e, top_k=k, width=f, shared_width=f, depth=1,
            experts_held=(first, 2), routed_scale=2.5,
            compute_dtype=jnp.float32)
        held = {**params, **{n: params[n][first:first + 2]
                             for n in ("w_gate", "w_up", "w_down")}}
        total = total + part.apply({"params": held}, x) - shared
    with jax.default_matmul_precision("highest"):
        want = ref._experts(x.reshape(-1, c), params, top_k=k, first=0,
                            routed_scale=2.5)[0].reshape(x.shape)
    np.testing.assert_allclose(total + shared, want, atol=2e-5, rtol=2e-5)


def _experts(count=4, c=16, f=8, seed=3):
    ks = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(ks[0], (count, c, f)) * 0.3,
            jax.random.normal(ks[1], (count, c, f)) * 0.3,
            jax.random.normal(ks[2], (count, f, c)) * 0.3)


def test_every_pair_sent_to_one_held_expert_drops_nothing():
    n, k, c = 24, 4, 16
    w_gate, w_up, w_down = _experts()
    x = jax.random.normal(jax.random.key(4), (n, c))
    idx = jnp.full((n, k), 9, jnp.int32)  # held: experts 8 .. 11
    weight = jnp.full((n, k), 0.25)
    out, counters = held_experts_forward(
        x, idx, weight, w_gate, w_up, w_down, first=8)
    want = ref._swiglu(x, w_gate[1], w_up[1], w_down[1])  # 4 x 0.25
    np.testing.assert_allclose(out, want, atol=1e-5, rtol=1e-5)
    landed, routed, dropped, max_over_mean, summands = np.asarray(counters)
    assert (landed, routed, dropped, summands) == (n * k, n * k, 0, 1)
    assert max_over_mean == pytest.approx(4.0)  # one of four holds all


def test_no_pair_sent_here_gives_zeros_and_zero_gradients():
    n, k, c = 24, 4, 16
    weights = _experts()
    x = jax.random.normal(jax.random.key(5), (n, c))
    idx = jnp.tile(jnp.arange(k, dtype=jnp.int32), (n, 1))  # experts 0..3
    weight = jnp.full((n, k), 0.25)

    def fn(x, weight, *w):
        out, counters = held_experts_forward(x, idx, weight, *w, first=8)
        return jnp.sum(out ** 2) + jnp.sum(out), (out, counters)

    grads, (out, counters) = jax.grad(fn, (0, 1, 2, 3, 4), has_aux=True)(
        x, weight, *weights)
    assert not np.asarray(out).any()
    assert np.asarray(counters)[0] == 0 and np.asarray(counters)[2] == 0
    for g in grads:
        assert np.isfinite(np.asarray(g)).all() and not np.asarray(g).any()


@pytest.mark.parametrize("fault", ["none", "a_group_one_row_short",
                                   "two_slots_swapped"])
def test_dropped_counts_the_pairs_that_were_not_served(monkeypatch, fault):
    """``dropped`` is read off what the dispatch, the grouped matmuls and
    the combine index by, so a pair whose slot lies in another expert's
    group, or was filled from another token, counts."""
    sort_pairs = moe_dispatch._sort_pairs

    def faulty(idx, first, count):
        order, inv, sizes, local = sort_pairs(idx, first, count)
        if fault == "a_group_one_row_short":
            sizes = sizes.at[0].add(-1)  # its last row gets expert 1's weights
        if fault == "two_slots_swapped":  # two tokens' rows of one expert
            order = order.at[jnp.array([0, 1])].set(order[jnp.array([1, 0])])
        return order, inv, sizes, local

    monkeypatch.setattr(moe_dispatch, "_sort_pairs", faulty)
    n, k = 24, 2
    w_gate, w_up, w_down = _experts()
    x = jax.random.normal(jax.random.key(4), (n, 16))
    idx = jnp.stack([jnp.arange(n) % 4 + 8, jnp.arange(n) % 3], 1).astype(
        jnp.int32)  # first choice held (8 .. 11), second elsewhere
    _, counters = held_experts_forward(
        x, idx, jnp.full((n, k), 0.5), w_gate, w_up, w_down, first=8)
    dropped = float(counters[2])
    # every group's last row falls to the next group, the last to none
    assert dropped == {"none": 0, "a_group_one_row_short": 4,
                       "two_slots_swapped": 2}[fault]


def test_route_topk_normalises_over_all_chosen():
    scores = jax.nn.sigmoid(jax.random.normal(jax.random.key(6), (10, 16)))
    idx, weight = route_topk(scores, 4, 2.5)
    np.testing.assert_allclose(jnp.sum(weight, -1), 2.5, rtol=1e-6)
    top = np.sort(np.asarray(scores), -1)[:, -4:]
    np.testing.assert_allclose(np.sort(np.asarray(weight), -1),
                               top / top.sum(-1, keepdims=True) * 2.5,
                               rtol=1e-6)
    assert idx.dtype == jnp.int32


def test_routed_gradients_match_the_masked_loop():
    """The grouped, sorted path against the reference's loop over experts
    with a mask: the result and the gradients of the tokens, the weights'
    source (router scores) and the three expert matrices."""
    n, c, e, k, first, count = 40, 16, 16, 4, 4, 4
    w_gate, w_up, w_down = _experts(count)
    x = jax.random.normal(jax.random.key(7), (n, c))
    router = jax.random.normal(jax.random.key(8), (c, e))

    def system(x, router, w_gate, w_up, w_down):
        idx, weight = route_topk(jax.nn.sigmoid(x @ router), k, 2.5)
        out, _ = held_experts_forward(x, idx, weight, w_gate, w_up, w_down,
                                      first=first)
        return jnp.sum(jnp.sin(out))

    def reference(x, router, w_gate, w_up, w_down):
        p = {"router": {"kernel": router}, "w_gate": w_gate, "w_up": w_up,
             "w_down": w_down,
             "shared": {m: {"kernel": jnp.zeros((c, 1) if m != "down"
                                                else (1, c))}
                        for m in ("gate", "up", "down")}}
        return jnp.sum(jnp.sin(ref._experts(
            x, p, top_k=k, first=first, routed_scale=2.5)[0]))

    args = (x, router, w_gate, w_up, w_down)
    got = jax.grad(system, range(5))(*args)
    want = jax.grad(reference, range(5))(*args)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=2e-5, rtol=2e-4)


def test_yarn_frequencies_against_hand_computed_values():
    """theta 10000, rot 8 (half of a head of 16), factor 4, original
    context 16, beta 4 / 1: the ramp runs from dimension floor(d(4)) to
    ceil(d(1)) with d(r) = rot ln(16 / (2 pi r)) / (2 ln theta)."""
    rope = decoder.TINY_ROPE["full_attention"]
    inv_freq, factor = decoder.rope_frequencies(16, rope)
    base = [1.0, 10000 ** -0.25, 10000 ** -0.5, 10000 ** -0.75]
    d_fast = 8 * math.log(16 / (4 * 2 * math.pi)) / (2 * math.log(10000))
    d_slow = 8 * math.log(16 / (1 * 2 * math.pi)) / (2 * math.log(10000))
    assert (math.floor(d_fast), math.ceil(d_slow)) == (-1, 1)
    low, high = 0, 1  # clamped at 0
    want = []
    for i, f in enumerate(base):
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        want.append(f / 4.0 * ramp + f * (1 - ramp))
    # dimension 0 keeps its frequency, the others are divided by 4
    assert want == [1.0, base[1] / 4, base[2] / 4, base[3] / 4]
    np.testing.assert_allclose(inv_freq, want, rtol=1e-12)
    assert factor == 1.1
    ref_freq, ref_factor = ref.rope_frequencies(16, rope)
    np.testing.assert_allclose(ref_freq, inv_freq, rtol=1e-12)
    assert ref_factor == factor
    plain, one = decoder.rope_frequencies(
        16, decoder.TINY_ROPE["sliding_attention"])
    np.testing.assert_allclose(plain, 100.0 ** (-np.arange(8) / 8))
    assert one == 1.0


def test_published_yarn_frequencies_blend_between_the_two_ends():
    rope = {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
            "original_max_position_embeddings": 4096, "beta_slow": 1,
            "beta_fast": 64, "attention_factor": 1.4158883083359672,
            "partial_rotary_factor": 0.5}
    inv_freq, factor = decoder.rope_frequencies(128, rope)
    base = 500000.0 ** (-np.arange(0, 64, 2) / 64)
    assert inv_freq.shape == (32,) and factor == rope["attention_factor"]
    assert inv_freq[0] == base[0]  # fastest: extrapolated, kept
    assert inv_freq[-1] == pytest.approx(base[-1] / 64)  # slowest: /factor
    assert np.all(np.diff(inv_freq) < 0)
    assert np.all(inv_freq <= base) and np.all(inv_freq >= base / 64)


def test_rope_rotates_the_leading_dimensions_only_and_keeps_norms():
    x = jax.random.normal(jax.random.key(9), (1, 12, 3, 16))
    inv_freq, _ = decoder.rope_frequencies(
        16, {"rope_theta": 100.0, "partial_rotary_factor": 0.5})
    y = decoder.apply_rope(x, inv_freq, 1.0)
    np.testing.assert_array_equal(y[..., 8:], x[..., 8:])
    np.testing.assert_allclose(
        jnp.linalg.norm(y[..., :8], axis=-1),
        jnp.linalg.norm(x[..., :8], axis=-1), rtol=1e-5)
    np.testing.assert_allclose(y[:, 0], x[:, 0], rtol=1e-6)  # position 0


def test_grouped_heads_read_their_key_value_head():
    """Query head i reads key-value head i // (H / KV)."""
    from pytorch_distributed_mnist_tpu.ops.attention import full_attention

    ks = jax.random.split(jax.random.key(10), 3)
    q = jax.random.normal(ks[0], (1, 10, 6, 8))
    k = jax.random.normal(ks[1], (1, 10, 2, 8))
    v = jax.random.normal(ks[2], (1, 10, 2, 8))
    got = full_attention(q, k, v, causal=True)
    for head in range(6):
        one = full_attention(q[:, :, head:head + 1],
                             k[:, :, head // 3:head // 3 + 1],
                             v[:, :, head // 3:head // 3 + 1], causal=True)
        np.testing.assert_allclose(got[:, :, head], one[:, :, 0],
                                   atol=1e-6, rtol=1e-6)


def _per_head_gate(o, gate):
    """The parent's gate: ``gate`` broadcast over each head's lanes on the
    ``(B, T, H, D)`` view."""
    b, t, h, d = o.shape
    return (o * gate[..., None]).reshape(b, t, h * d)


def _gate(u, w_g):
    return jax.nn.sigmoid(u @ w_g.astype(jnp.bfloat16))


@pytest.mark.parametrize("heads, d", [(64, 128), (48, 128), (6, 16), (4, 16)],
                         ids=["cell_window", "cell_full", "cli_window",
                              "cli_full"])
def test_the_packed_gate_is_the_per_head_gate(heads, d):
    """``decoder.gate_heads`` against ``o * sigmoid(u W_g)[..., None]`` at
    the cell's layers (64 and 48 heads of 128) and the CLI's decoder's (6
    and 4 of 16). The value and the gradient to ``o`` are the same
    bfloat16 products, bit for bit. The gradient to the gate is each head's
    sum of the same products rounded once, and the gradients to ``u`` and
    ``W_g`` are that sum's; the parent's reduction summed in bfloat16 on
    the CPU, and is within its own rounding of it."""
    b, t, c = 2, 16, 32
    ks = jax.random.split(jax.random.key(heads), 4)
    o = jax.random.normal(ks[0], (b, t, heads, d), jnp.bfloat16)
    u = jax.random.normal(ks[1], (b, t, c), jnp.bfloat16)
    w_g = 0.2 * jax.random.normal(ks[2], (c, heads))
    cot = jax.random.normal(ks[3], (b, t, heads * d), jnp.bfloat16)
    got, vjp = jax.vjp(
        lambda o, u, w: decoder.gate_heads(o, _gate(u, w)), o, u, w_g)
    want, vjp_want = jax.vjp(
        lambda o, u, w: _per_head_gate(o, _gate(u, w)), o, u, w_g)
    np.testing.assert_array_equal(got, want)
    d_o, d_u, d_w = vjp(cot)
    want_o, parent_u, parent_w = vjp_want(cot)
    np.testing.assert_array_equal(d_o, want_o)
    sums = jnp.sum((cot.reshape(o.shape) * o).astype(jnp.float32), -1)
    want_u, want_w = jax.vjp(_gate, u, w_g)[1](sums.astype(jnp.bfloat16))
    assert _rel_err(d_u, want_u) < 2 ** -8
    assert _rel_err(d_w, want_w) < 2 ** -8
    assert _rel_err(d_u, parent_u) < 2 ** -5
    assert _rel_err(d_w, parent_w) < 2 ** -5


def test_a_recomputed_layer_gates_as_the_per_head_formula(monkeypatch):
    """A window layer of heads of 128 under ``decoder.recomputed``: its
    value is the per-head formula's bit for bit, its gradients are the
    plain layer's bit for bit and the formula's within the formula's
    bfloat16 sums."""
    layer = decoder.GatedAttention(
        num_heads=4, num_kv_heads=2, head_dim=128, window=8,
        rope=decoder._frozen(decoder.TINY_ROPE[decoder.WINDOW]), depth=2,
        attention="dense")
    recomputed = decoder.recomputed(decoder.GatedAttention)(
        **{f: getattr(layer, f) for f in (
            "num_heads", "num_kv_heads", "head_dim", "window", "rope",
            "depth", "attention")})
    ks = jax.random.split(jax.random.key(7), 3)
    u = jax.random.normal(ks[0], (2, 32, 64), jnp.bfloat16)
    cot = jax.random.normal(ks[1], (2, 32, 64), jnp.bfloat16)
    params = layer.init(ks[2], u)

    def run(module):
        y, vjp = jax.vjp(module.apply, params, u)
        return y, vjp(cot)

    got, plain = run(recomputed), run(layer)
    monkeypatch.setattr(decoder, "gate_heads", _per_head_gate)
    want = run(recomputed)
    np.testing.assert_array_equal(got[0], want[0])
    for g, p, w in zip(*(jax.tree.leaves(x[1]) for x in (got, plain, want))):
        np.testing.assert_array_equal(g, p)
        assert _rel_err(g, w) < 2 ** -5


@pytest.mark.parametrize("head_dim", [128, 16])
def test_every_traced_gate_is_counted_with_its_head_width(head_dim):
    """Five layers gate their heads, one site a layer and traced forward
    call: ``init`` counts 5, and so does a traced gradient of the
    recomputed model, whose recomputed forward is the traced forward's
    jaxpr and whose backward is the gate's own rule."""
    model = decoder.Decoder(remat=True, attention="dense", head_dim=head_dim)
    tokens = jnp.zeros((2, 32), jnp.int32)
    before = head_gate_sites.snapshot()["sites"]
    params = jax.eval_shape(model.init, jax.random.key(0), tokens)
    assert head_gate_sites.snapshot()["sites"] == before + 5
    jax.make_jaxpr(jax.grad(lambda p: jnp.sum(model.apply(p, tokens))))(
        params)
    report = device_report()["head_gate_sites"]
    assert report == head_gate_sites.snapshot()
    assert report["sites"] == before + 10
    assert head_dim in report["head_widths"]
    assert report["head_widths"] == sorted(report["head_widths"])


def test_token_corpus_is_seeded_packed_and_labelled():
    tokens, labels = synthetic_token_corpus(8, 128, 500, seed=11,
                                            median_len=32, min_len=4)
    again, _ = synthetic_token_corpus(8, 128, 500, seed=11, median_len=32,
                                      min_len=4)
    other, _ = synthetic_token_corpus(8, 128, 500, seed=12, median_len=32,
                                      min_len=4)
    np.testing.assert_array_equal(tokens, again)
    assert (tokens != other).any()
    assert tokens.shape == labels.shape == (8, 128)
    assert tokens.dtype == labels.dtype == np.int32
    assert tokens.min() >= 0 and tokens.max() < 500
    np.testing.assert_array_equal(labels[:, :-1], tokens[:, 1:])
    assert (labels[:, -1] == IGNORE).all()
    ends = np.flatnonzero(tokens.reshape(-1) == EOD)
    lengths = np.diff(np.concatenate([[-1], ends]))
    assert lengths.min() >= 4 and lengths.max() <= 128
    # Zipf: the most frequent content id is far more frequent than the
    # median one.
    counts = np.bincount(tokens.reshape(-1), minlength=500)[1:]
    assert counts.max() > 20 * max(np.median(counts), 1)


def test_a_huge_seed_is_taken():
    tokens, _ = synthetic_token_corpus(2, 32, 64, seed=2**31 + 12345,
                                       median_len=8, min_len=2)
    assert tokens.shape == (2, 32)


def _old_cross_entropy(logits, labels):
    """ops/loss.py's mean cross-entropy as it was before it took further
    leading axes (PR 26), written out."""
    logits = jax.lax.optimization_barrier(logits.astype(jnp.float32))
    logz = jax.nn.logsumexp(logits, axis=-1)
    label_logits = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(jnp.maximum(logz - label_logits, 0.0))


def test_image_loss_and_metrics_are_bit_for_bit_what_they_were():
    model = get_model("vit", patch_size=7, embed_dim=32, depth=2,
                      num_heads=2)
    images = jax.random.normal(jax.random.key(13), (8, 28, 28, 1))
    labels = jax.random.randint(jax.random.key(14), (8,), 0, 10)
    params = model.init(jax.random.key(15), images[:1])
    logits = jax.jit(model.apply)(params, images)
    new = jax.jit(cross_entropy)(logits, labels)
    old = jax.jit(_old_cross_entropy)(logits, labels)
    assert np.asarray(new).tobytes() == np.asarray(old).tobytes()
    mask = jnp.array([1, 1, 0, 1, 1, 1, 0, 1], jnp.float32)
    m = metrics_update(metrics_init(), new, logits, labels, mask)
    hit = (jnp.argmax(logits, -1) == labels).astype(jnp.float32) * mask
    assert m.routing is None and len(jax.tree.leaves(m)) == 3
    assert float(m.count) == 6.0
    assert float(m.correct) == float(jnp.sum(hit))
    assert np.asarray(m.loss_sum).tobytes() == np.asarray(
        new.astype(jnp.float32) * 6.0).tobytes()


def test_token_loss_leaves_ignored_positions_and_masked_examples_out():
    logits = jax.random.normal(jax.random.key(16), (3, 5, 7))
    labels = jnp.array([[1, 2, 3, 4, IGNORE]] * 3)
    per = -jax.nn.log_softmax(logits)[
        jnp.arange(3)[:, None], jnp.arange(5)[None, :],
        jnp.maximum(labels, 0)]
    np.testing.assert_allclose(cross_entropy(logits, labels),
                               jnp.mean(per[:, :4]), rtol=1e-6)
    mask = jnp.array([1.0, 0.0, 1.0])
    np.testing.assert_allclose(
        cross_entropy(logits, labels, mask),
        jnp.mean(per[jnp.array([0, 2]), :4]), rtol=1e-6)
    m = metrics_update(metrics_init(), jnp.float32(2.0), logits, labels,
                       mask)
    assert float(m.count) == 8.0 and float(m.loss_sum) == 16.0
    want = ref.cross_entropy(logits, labels)
    np.testing.assert_allclose(cross_entropy(logits, labels), want,
                               rtol=1e-6)


def test_reference_counts_parameters_and_flops_as_the_model_has_them():
    model = get_model("laguna", **TINY)
    params = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, T))))
    n = sum(math.prod(x.shape) for x in jax.tree.leaves(params))
    assert ref.param_count(TINY) == n
    # One token more than the window adds the window's pairs.
    assert ref.causal_pairs(9, 8) - ref.causal_pairs(8, 8) == 8
    assert ref.causal_pairs(8) == 36
    flops = ref.train_flops_per_image({**TINY, "seq_len": T})
    assert flops == 3 * ref.forward_flops_per_sequence(TINY, T)
    # With every expert held the routed part costs 8 x the share's.
    held_all = ref.forward_flops_per_sequence(
        {**TINY, "experts_held": None}, T)
    routed = 6 * T * 64 * 32 * 4 * 4  # four sparse layers, top-4
    assert held_all - ref.forward_flops_per_sequence(TINY, T) \
        == pytest.approx(routed * (1 - 8 / 16))


def test_expert_parallel_rules_cover_the_decoders_expert_layer():
    """The same layer under an ``expert`` mesh axis is the deployment the
    benchmark's cut stands for: the three expert matrices shard on their
    leading expert dim, the router and the shared expert replicate."""
    from jax.sharding import PartitionSpec as P

    from pytorch_distributed_mnist_tpu.parallel.expert import moe_ep_rules
    from pytorch_distributed_mnist_tpu.parallel.tensor import leaf_spec

    model = get_model("laguna", **TINY)
    params = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, T))))
    specs = {jax.tree_util.keystr(path): leaf_spec(path, moe_ep_rules())
             for path, _ in jax.tree_util.tree_leaves_with_path(params)}
    sharded = {k for k, v in specs.items() if v != P()}
    assert sharded == {
        f"['params']['block{i}']['moe']['{name}']"
        for i in range(1, 5) for name in ("w_gate", "w_up", "w_down")}
    assert all(specs[k] == P("expert", None, None) for k in sharded)
