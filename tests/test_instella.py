"""The ``instella`` decoder against its plain reference
(``benchmark/reference/instella.py``) on the CPU at a tiny size (hidden 64,
4 heads of 12 + 4, latent 32, 16 experts top-4, T = 64, a dense layer, two
sparse ones and the module), and the pieces this model brought: latent
attention, the interleaved rotary, the selection bias and its update, the
balance term, the state no gradient moves, and that the programs of
``laguna`` and ``sambay`` are what they were."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import instella as ref
from benchmark.reference import laguna as laguna_ref
from pytorch_distributed_mnist_tpu.data.tokens import (
    IGNORE,
    synthetic_token_corpus,
)
from pytorch_distributed_mnist_tpu.models import decoder, get_model, instella
from pytorch_distributed_mnist_tpu.models.moe import SparseExperts
from pytorch_distributed_mnist_tpu.ops.loss import cross_entropy
from pytorch_distributed_mnist_tpu.ops.metrics import (
    BIAS_COLLECTION,
    LOAD_COLLECTION,
    ROUTING_COUNTERS,
    STEP_COUNTERS,
)
from pytorch_distributed_mnist_tpu.parallel.moe_dispatch import (
    expert_load,
    route_topk,
    sequence_balance_loss,
)
from pytorch_distributed_mnist_tpu.train.state import create_train_state
from pytorch_distributed_mnist_tpu.train.steps import (
    make_train_epoch,
    make_train_step,
    move_selection_bias,
    mtp_labels,
)

T = 64
WEIGHTS = {"mtp_weight": 0.3, "aux_weight": 1e-4}
RATE = 1e-3
# The tiny preset as a configuration file's kwargs would carry it.
TINY = {
    "vocab_size": 256, "hidden_size": 64, "num_heads": 4, "nope_dim": 12,
    "rope_dim": 4, "v_dim": 16, "kv_rank": 32,
    "mlp_layer_types": ["dense", "sparse", "sparse"],
    "rope": instella.TINY_ROPE, "dense_mlp_size": 256, "expert_size": 32,
    "shared_expert_size": 64, "num_experts": 16, "top_k": 4,
    "experts_held": [4, 8], "routed_scale": 2.5, "rms_eps": 1e-6,
}


def _rel_err(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                  1e-30))


def _pick(tree, paths):
    out = {}
    for path in paths:
        node = tree
        for key in path.split("/"):
            node = node[key]
        out[path] = node
    return out


def _variables(model, seed=0, bias_scale=0.05):
    """Seeded weights and a seeded non-zero selection bias."""
    variables = model.init(jax.random.key(seed), jnp.zeros((1, T)))
    flat, treedef = jax.tree_util.tree_flatten(variables[BIAS_COLLECTION])
    keys = jax.random.split(jax.random.key(seed + 100), len(flat))
    bias = [bias_scale * jax.random.normal(k, b.shape)
            for k, b in zip(keys, flat)]
    return {**variables,
            BIAS_COLLECTION: jax.tree_util.tree_unflatten(treedef, bias)}


def _system_and_reference(kwargs, dtype=jnp.float32, seed=0, **model_kw):
    """``(got, want, leaves)``: both logit arrays, the two losses, the
    objective, the named gradients and the bias after one step, as the
    system and as the reference compute them on the same variables."""
    model = get_model("instella", compute_dtype=dtype,
                      **{"attention": "dense", **kwargs, **model_kw})
    tokens, labels = synthetic_token_corpus(
        2, T, kwargs["vocab_size"], seed=seed, median_len=16, min_len=4)
    tokens, labels = jnp.asarray(tokens), jnp.asarray(labels)
    reference_model = get_model("instella", compute_dtype=jnp.float32,
                                **{"attention": "dense", **kwargs})
    variables = _variables(reference_model, seed)
    bias = variables[BIAS_COLLECTION]
    params = {"params": variables["params"]}
    leaves = ref.grad_leaves(kwargs)

    def system(p):
        (logits, mtp_logits), mods = model.apply(
            {**p, BIAS_COLLECTION: bias}, tokens, train=True,
            mutable=["intermediates", LOAD_COLLECTION])
        aux = sum(jnp.sum(leaf) for path, leaf
                  in jax.tree_util.tree_leaves_with_path(
                      mods["intermediates"])
                  if "aux_loss" in jax.tree_util.keystr(path))
        loss = cross_entropy(logits, labels, None)
        mtp_loss = cross_entropy(mtp_logits, mtp_labels(labels), None)
        total = loss + WEIGHTS["mtp_weight"] * mtp_loss \
            + WEIGHTS["aux_weight"] * aux
        moved = move_selection_bias(bias, mods[LOAD_COLLECTION], RATE)
        return total, (logits, mtp_logits, loss, mtp_loss, moved)

    def reference(p):
        outputs, _, loads, aux = ref.forward_all(
            {**p, BIAS_COLLECTION: bias}, tokens,
            **ref.shape_from_kwargs(kwargs))
        total, loss, mtp_loss = ref.objective(outputs, aux, labels,
                                              **WEIGHTS)
        return total, (*outputs, loss, mtp_loss,
                       ref.bias_after_step(bias, loads, RATE))

    def both(fn):
        (total, rest), grads = jax.value_and_grad(fn, has_aux=True)(params)
        return {"objective": total, "logits": rest[0], "mtp_logits": rest[1],
                "loss": rest[2], "mtp_loss": rest[3], "bias": rest[4],
                "grads": _pick(grads, leaves)}

    return both(system), both(reference), leaves


def _assert_within(got, want, leaves, tol):
    for key in ("logits", "mtp_logits", "loss", "mtp_loss", "objective"):
        assert _rel_err(got[key], want[key]) < tol[key], key
    for path in leaves:
        assert got["grads"][path].shape == want["grads"][path].shape
        assert float(jnp.max(jnp.abs(want["grads"][path]))) > 0, path
        assert _rel_err(got["grads"][path], want["grads"][path]) \
            < tol["grad"], path
    moved = jax.tree_util.tree_leaves(got["bias"])
    for a, b in zip(moved, jax.tree_util.tree_leaves(want["bias"])):
        assert float(jnp.max(jnp.abs(a - b))) / RATE <= tol["bias"]


@pytest.mark.parametrize("attention", ["dense", "flash"])
@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_model_matches_reference_heads_objective_gradients_and_bias(
        remat, attention):
    got, want, leaves = _system_and_reference(
        TINY, remat=remat, attention=attention)
    assert got["logits"].shape == got["mtp_logits"].shape == (2, T, 256)
    assert got["logits"].dtype == got["mtp_logits"].dtype == jnp.float32
    assert len(leaves) == 17
    _assert_within(got, want, leaves, ref.TOLERANCES["f32"])
    # Three sparse layers moved their bias, every entry by one rate or none.
    moved = jax.tree_util.tree_leaves(got["bias"])
    assert len(moved) == 3


@pytest.mark.parametrize("switch", ["farskip", "qk_norm", "gated"])
def test_each_reading_is_one_switch_on_both_sides(switch):
    """The three readings of the source's flags are one field each; with
    the field off both sides compute the other form, and the two forms
    differ."""
    on, _, _ = _system_and_reference(TINY)
    got, want, leaves = _system_and_reference({**TINY, switch: False})
    _assert_within(got, want, leaves, ref.TOLERANCES["f32"])
    assert _rel_err(got["logits"], on["logits"]) > 1e-2


def test_all_experts_held_is_the_uncut_model():
    got, want, leaves = _system_and_reference(
        {**TINY, "experts_held": None})
    _assert_within(got, want, leaves, ref.TOLERANCES["f32"])


@pytest.mark.parametrize("what", ["bf16", "bf16_router"])
def test_a_lower_precision_than_the_file_states_is_not_correct(
        what, monkeypatch):
    """A configuration that states f32 is held to f32's tolerances: the
    same model computing in bfloat16, or with bfloat16 router scores alone
    (float32 is stated for the router, the norms, the rotary and the
    logits), fails at least one of them."""
    if what == "bf16":
        got, want, leaves = _system_and_reference(TINY, dtype=jnp.bfloat16)
    else:
        import flax.linen as nn

        sigmoid = nn.sigmoid
        monkeypatch.setattr(
            nn, "sigmoid", lambda x: sigmoid(
                x.astype(jnp.bfloat16)).astype(jnp.float32))
        got, want, leaves = _system_and_reference({**TINY, "gated": False})
    tol = ref.TOLERANCES["f32"]
    errors = {k: _rel_err(got[k], want[k]) for k in (
        "logits", "mtp_logits", "loss", "mtp_loss", "objective")}
    errors.update({p: _rel_err(got["grads"][p], want["grads"][p])
                   for p in leaves})
    failed = [k for k, v in errors.items() if v > tol.get(k, tol["grad"])]
    assert failed, errors


def test_latent_attention_against_a_per_head_loop():
    """Every head's keys built from the latent, one head after the other:
    the shared rotary key is rotated once and joined to each head's own
    un-rotated dimensions; values and keys come from one up-projection of
    the normed latent."""
    b, t, c, h, nope, rot, dv, rank = 2, 24, 32, 3, 6, 4, 8, 16
    rope = dict(instella.TINY_ROPE)
    layer = decoder.LatentAttention(
        num_heads=h, nope_dim=nope, rope_dim=rot, v_dim=dv, kv_rank=rank,
        rope=decoder._frozen(rope), depth=1, attention="dense",
        compute_dtype=jnp.float32)
    u = jax.random.normal(jax.random.key(0), (b, t, c))
    params = layer.init(jax.random.key(1), u)["params"]
    # scales away from one, so that the norms' places show
    params = jax.tree_util.tree_map_with_path(
        lambda path, x: x * (1.0 + 0.3 * jnp.arange(x.shape[0])
                             / x.shape[0]) if x.ndim == 1 else x * 8.0,
        params)
    got = layer.apply({"params": params}, u)

    def rms(x, scale):
        return x / np.sqrt(np.mean(x * x, -1, keepdims=True) + 1e-6) * scale

    p = jax.tree_util.tree_map(lambda x: np.asarray(x, np.float64), params)
    u64 = np.asarray(u, np.float64)
    inv_freq, _ = decoder.rope_frequencies(rot, rope)

    def rotate(x):  # (t, rot), pairs (2i, 2i + 1) as complex numbers
        z = (x[:, 0::2] + 1j * x[:, 1::2]) * np.exp(
            1j * np.arange(t)[:, None] * inv_freq[None, :])
        out = np.empty_like(x)
        out[:, 0::2], out[:, 1::2] = z.real, z.imag
        return out

    scale = decoder.yarn_softmax_scale(nope + rot, rope)
    assert scale == pytest.approx(
        (nope + rot) ** -0.5 * (0.1 * np.log(4.0) + 1) ** 2)
    want = np.zeros((b, t, c))
    for n in range(b):
        latent = u64[n] @ p["kv_a"]["kernel"]
        c_kv, k_r = latent[:, :rank], latent[:, rank:]
        up = rms(c_kv, p["kv_norm"]["scale"]) @ p["kv_b"]["kernel"]
        gate = 1 / (1 + np.exp(-(u64[n] @ p["gate"]["kernel"])))
        heads = []
        for i in range(h):
            q = (u64[n] @ p["q"]["kernel"])[:, i * (nope + rot):
                                            (i + 1) * (nope + rot)]
            kv = up[:, i * (nope + dv):(i + 1) * (nope + dv)]
            k = np.concatenate([kv[:, :nope], k_r], axis=-1)
            q, k = rms(q, p["q_norm"]["scale"]), rms(k, p["k_norm"]["scale"])
            q = np.concatenate([q[:, :nope], rotate(q[:, nope:])], -1)
            k = np.concatenate([k[:, :nope], rotate(k[:, nope:])], -1)
            scores = q @ k.T * scale
            scores = np.where(np.tril(np.ones((t, t), bool)), scores, -np.inf)
            prob = np.exp(scores - scores.max(-1, keepdims=True))
            prob /= prob.sum(-1, keepdims=True)
            heads.append(prob @ kv[:, nope:])
        want[n] = (np.concatenate(heads, -1) * gate) @ p["proj"]["kernel"]
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_interleaved_rope_pairs_neighbours_and_keeps_norms():
    x = jax.random.normal(jax.random.key(0), (1, 5, 2, 8))
    inv_freq = np.array([1.0, 0.5, 0.25, 0.125])
    y = decoder.apply_rope_interleaved(x, inv_freq, 1.0)
    np.testing.assert_allclose(y[:, 0], x[:, 0], atol=1e-6)  # position 0
    np.testing.assert_allclose(
        jnp.linalg.norm(y.reshape(1, 5, 2, 4, 2), axis=-1),
        jnp.linalg.norm(x.reshape(1, 5, 2, 4, 2), axis=-1), rtol=1e-5)
    a, b2 = np.asarray(x[0, 3, 1, 2:4])
    angle = 3 * 0.5
    np.testing.assert_allclose(
        y[0, 3, 1, 2:4],
        [a * np.cos(angle) - b2 * np.sin(angle),
         b2 * np.cos(angle) + a * np.sin(angle)], rtol=1e-5)


def test_the_bias_steers_the_choice_and_enters_no_weight():
    scores = jnp.array([[0.9, 0.8, 0.3, 0.2], [0.6, 0.5, 0.4, 0.1]])
    plain_idx, plain_w = route_topk(scores, 2, 2.5)
    bias = jnp.array([0.0, 0.0, 1.0, 0.0])
    idx, weight = route_topk(scores, 2, 2.5, bias)
    assert sorted(np.asarray(plain_idx[0])) == [0, 1]
    assert sorted(np.asarray(idx[0])) == [0, 2]
    assert sorted(np.asarray(idx[1])) == [0, 2]
    # weights: the chosen experts' scores alone, normalised, times 2.5
    np.testing.assert_allclose(jnp.sum(weight, -1), 2.5, rtol=1e-6)
    order = np.argsort(np.asarray(idx[0]))
    np.testing.assert_allclose(
        np.asarray(weight[0])[order],
        2.5 * np.array([0.9, 0.3]) / 1.2, rtol=1e-6)
    np.testing.assert_allclose(jnp.sum(plain_w, -1), 2.5, rtol=1e-6)


def test_bias_update_on_a_hand_made_load():
    """``b_e += rate * sign(mean n - n_e)``: an overloaded expert's bias
    falls, an underloaded one's rises, one at the mean stays; a layer
    called twice in a step is moved by the sum of its loads."""
    bias = {"a": {"moe": {"select": jnp.array([0.5, 0.0, -0.25, 0.0])}},
            "b": {"moe": {"select": jnp.zeros((4,))}}}
    load = {"a": {"moe": {"select": (jnp.array([10.0, 2.0, 4.0, 0.0]),)}},
            "b": {"moe": {"select": (jnp.array([1.0, 1.0, 3.0, 3.0]),
                                     jnp.array([3.0, 3.0, 1.0, 1.0]))}}}
    moved = move_selection_bias(bias, load, 0.01)
    np.testing.assert_allclose(moved["a"]["moe"]["select"],
                               [0.49, 0.01, -0.25, 0.01], atol=1e-7)
    np.testing.assert_allclose(moved["b"]["moe"]["select"], 0.0)
    want = ref.bias_after_step(
        bias, {"a": load["a"]["moe"]["select"][0],
               "b": sum(load["b"]["moe"]["select"])}, 0.01)
    for a, b2 in zip(jax.tree_util.tree_leaves(moved),
                     jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(a, b2)


def test_balance_term_is_one_when_uniform_and_follows_the_equation():
    e, k, t = 8, 2, 16
    idx = (jnp.arange(t)[:, None] * k + jnp.arange(k)[None, :]) % e
    load = expert_load(idx[None], e)
    np.testing.assert_array_equal(load, np.full((1, e), t * k / e))
    uniform = jnp.full((1, t, e), 0.3)
    assert float(sequence_balance_loss(uniform, load, k)) \
        == pytest.approx(1.0)
    scores = jax.random.uniform(jax.random.key(0), (2, t, e)) + 0.1
    _, chosen = jax.lax.top_k(scores, k)
    loads = expert_load(chosen, e)
    want = 0.0
    s = np.asarray(scores, np.float64)
    for b in range(2):
        f = np.array([(np.asarray(chosen[b]) == i).sum() for i in range(e)]) \
            * e / (k * t)
        p = (s[b] / s[b].sum(-1, keepdims=True)).mean(0)
        want += (f * p).sum() / 2
    assert float(sequence_balance_loss(scores, loads, k)) \
        == pytest.approx(want, rel=1e-5)
    # the gradient reaches the scores through P alone
    grad = jax.grad(lambda x: sequence_balance_loss(x, loads, k))(scores)
    assert float(jnp.max(jnp.abs(grad))) > 0


def test_eight_shares_add_up_to_the_uncut_layer():
    """The share test: the expert layer run once for each of the 8 shares
    under one seeded non-zero bias, the routed parts added and the shared
    experts counted once, is the uncut reference layer; every share sows
    the same load over all experts and the same balance term."""
    e, k, c, f = 16, 4, 64, 32
    kw = dict(num_experts=e, top_k=k, width=f, shared_width=2 * f, depth=1,
              routed_scale=2.5, selection_bias=True, balance=True,
              compute_dtype=jnp.float32)
    x = jax.random.normal(jax.random.key(1), (2, 24, c))
    variables = SparseExperts(**kw).init(jax.random.key(2), x)
    params = variables["params"]
    bias = 0.1 * jax.random.normal(jax.random.key(3), (e,))
    shared = laguna_ref._swiglu(x, *(params["shared"][n]["kernel"]
                                     for n in ("gate", "up", "down")))
    total = jnp.zeros_like(x)
    sown = []
    for share in range(8):
        first = 2 * share
        held = {**params, **{n: params[n][first:first + 2]
                             for n in ("w_gate", "w_up", "w_down")}}
        part, mods = SparseExperts(**kw, experts_held=(first, 2)).apply(
            {"params": held, BIAS_COLLECTION: {"select": bias}}, x,
            mutable=["intermediates", LOAD_COLLECTION])
        total = total + part - shared
        sown.append((mods[LOAD_COLLECTION]["select"][0],
                     mods["intermediates"]["aux_loss"][0]))
    with jax.default_matmul_precision("highest"):
        want, _, load, balance = ref.experts(
            x, params, bias, top_k=k, first=0, routed_scale=2.5)
    np.testing.assert_allclose(total + shared, want, atol=2e-5, rtol=2e-5)
    assert float(jnp.sum(load)) == 2 * 24 * k
    for got_load, got_balance in sown:
        np.testing.assert_array_equal(got_load, load)
        assert float(got_balance) == pytest.approx(float(balance), rel=1e-5)


def test_evaluation_reads_the_main_logits_and_builds_no_second_head():
    model = get_model("instella", compute_dtype=jnp.float32, **TINY)
    variables = _variables(model)
    tokens = jnp.zeros((1, T), jnp.int32)
    out = model.apply(variables, tokens)
    assert out.shape == (1, T, 256)
    program = str(jax.make_jaxpr(lambda v: model.apply(v, tokens))(variables))
    training = str(jax.make_jaxpr(
        lambda v: model.apply(v, tokens, train=True))(variables))
    assert program.count("256]") < training.count("256]")
    assert " top_k[" in program and program.count(" top_k[") == 2
    assert training.count(" top_k[") == 3  # the module's sparse block


def test_the_second_label_is_the_label_shifted_once_more():
    labels = jnp.array([[5, 6, 7, IGNORE]])
    np.testing.assert_array_equal(mtp_labels(labels),
                                  [[6, 7, IGNORE, IGNORE]])


def _state_and_batches(n_steps=2, **model_kw):
    model = get_model("instella", compute_dtype=jnp.float32,
                      attention="dense", **model_kw)
    state = create_train_state(model, jax.random.key(0), input_shape=(1, T))
    tokens, labels = synthetic_token_corpus(
        2 * n_steps, T, 256, seed=1, median_len=16, min_len=4)
    batches = {"image": jnp.asarray(tokens).reshape(n_steps, 2, T),
               "label": jnp.asarray(labels).reshape(n_steps, 2, T),
               "mask": jnp.ones((n_steps, 2), jnp.float32)}
    return state, batches


def test_the_state_carries_the_bias_beside_the_parameters():
    state, _ = _state_and_batches()
    assert set(state.params) == {"params"}
    assert set(state.buffers) == {BIAS_COLLECTION}
    biases = jax.tree_util.tree_leaves(state.buffers)
    assert [b.shape for b in biases] == [(16,)] * 3
    assert set(state.variables) == {"params", BIAS_COLLECTION}
    plain = create_train_state(get_model("linear"), jax.random.key(0))
    assert plain.buffers is None and plain.variables is plain.params

    # Adam carries two moments a parameter and none for the bias: what its
    # state holds beside them is what the linear model's holds.
    def beside_moments(s):
        return len(jax.tree_util.tree_leaves(s.opt_state)) \
            - 2 * len(jax.tree_util.tree_leaves(s.params))

    assert beside_moments(state) == beside_moments(plain)


def test_a_scanned_pass_is_its_steps_and_reports_the_step_counters():
    """The scan-mode pass threads the bias like the step does: two steps
    one after the other and one scanned pass of the two give the same
    parameters, the same bias and the same counters."""
    knobs = dict(aux_weight=1e-4, mtp_weight=0.3, bias_rate=RATE)
    state, batches = _state_and_batches()
    step = make_train_step(**knobs)
    stepped, routing = state, 0.0
    losses = []
    for i in range(2):
        stepped, metrics = step(
            stepped, jax.tree_util.tree_map(lambda x: x[i], batches))
        routing = routing + metrics.routing
        losses.append(float(metrics.loss_sum / metrics.count))
    state2, _ = _state_and_batches()
    scanned, metrics = make_train_epoch(**knobs)(state2, batches)
    assert metrics.routing.shape == (
        len(ROUTING_COUNTERS) + len(STEP_COUNTERS),)
    np.testing.assert_allclose(metrics.routing, routing, rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(scanned.buffers),
                    jax.tree_util.tree_leaves(stepped.buffers)):
        np.testing.assert_allclose(a, b, atol=1e-9)
    for a, b in zip(jax.tree_util.tree_leaves(scanned.params),
                    jax.tree_util.tree_leaves(stepped.params)):
        np.testing.assert_allclose(a, b, atol=1e-6)
    counters = dict(zip(ROUTING_COUNTERS + STEP_COUNTERS,
                        np.asarray(metrics.routing)))
    assert counters["steps"] == 2 and counters["dropped"] == 0
    assert counters["summands"] == 2 * 3  # sparse layers x steps
    assert 0 < counters["bias_range"] / 2 <= 4 * RATE
    assert counters["mtp_loss"] / 2 > losses[-1] * 0.5  # a cross-entropy
    # the biases moved by whole rates
    moved = np.concatenate([np.asarray(b).ravel() for b in
                            jax.tree_util.tree_leaves(scanned.buffers)])
    np.testing.assert_allclose(moved / RATE, np.round(moved / RATE),
                               atol=1e-4)
    assert np.abs(moved).max() > 0


def test_a_frozen_bias_and_a_weightless_module_leave_both_alone():
    state, batches = _state_and_batches()
    batch = jax.tree_util.tree_map(lambda x: x[0], batches)
    before = jax.tree_util.tree_map(np.asarray, state.params["params"])
    new_state, _ = make_train_step()(state, batch)  # all three knobs 0
    for b in jax.tree_util.tree_leaves(new_state.buffers):
        np.testing.assert_array_equal(b, 0.0)
    after = new_state.params["params"]
    # no gradient reached the module; the trunk moved
    np.testing.assert_array_equal(after["mtp_merge"]["kernel"],
                                  before["mtp_merge"]["kernel"])
    assert float(jnp.max(jnp.abs(
        after["block1"]["moe"]["w_up"] - before["block1"]["moe"]["w_up"]))) > 0


def test_what_cannot_carry_the_bias_refuses_it():
    from pytorch_distributed_mnist_tpu.train.steps import (
        make_accum_train_step_fn,
    )

    state, batches = _state_and_batches()
    batch = jax.tree_util.tree_map(lambda x: x[0], batches)
    with pytest.raises(ValueError, match="grad-accum"):
        make_accum_train_step_fn(2)(state, batch)


def _cli(tmp_path, *extra):
    from pytorch_distributed_mnist_tpu import cli

    return cli.run(cli.build_parser().parse_args([
        "--model", "instella", "--dataset", "synthetic_tokens", "--seq-len",
        "32", "--synthetic-train-size", "32", "--synthetic-test-size", "8",
        "--batch-size", "8", "--dtype", "f32", "--seed", "1",
        "--checkpoint-dir", str(tmp_path / "ckpt"),
        "--root", str(tmp_path / "data"), *extra]))


def test_trains_from_the_command_line_and_reports_bias_and_module(tmp_path):
    import json

    _cli(tmp_path, "--epochs", "2",
         "--metrics-file", str(tmp_path / "m.jsonl"))
    rows = [json.loads(x) for x in open(tmp_path / "m.jsonl")]
    epochs = [r for r in rows if "train_loss" in r]
    assert len(epochs) == 2
    assert epochs[1]["train_loss"] < epochs[0]["train_loss"]
    routing = next(r for r in rows
                   if r.get("kind") == "run_summary")["expert_routing"]
    assert routing["dropped"] == 0 and routing["steps"] == 8
    # the source's rate 1e-3 moved the bias; its loss is the module's own
    assert 0 < routing["bias_range"] <= 2 * 8 * 1e-3
    assert routing["mtp_loss"] > epochs[1]["train_loss"] * 0.5
    assert routing["local_pair_share"] == 1.0  # every expert held
    from pytorch_distributed_mnist_tpu.utils.profiling import device_report
    assert device_report()["expert_routing"] == routing  # /healthz's


def test_killed_and_resumed_the_next_epoch_is_the_uninterrupted_one(
        tmp_path):
    """Two epochs in one run against one epoch, a new process's resume
    from its checkpoint and the second: the same second epoch to the last
    digit, because the checkpoint carried the bias that chose its
    experts."""
    straight = _cli(tmp_path / "a", "--epochs", "2")
    _cli(tmp_path / "b", "--epochs", "1")
    resumed = _cli(tmp_path / "b", "--epochs", "2", "--resume",
                   str(tmp_path / "b" / "ckpt" / "checkpoint_0.npz"))
    assert resumed["epochs_run"] == 1
    assert resumed["history"][-1]["train_loss"] \
        == straight["history"][-1]["train_loss"]
    assert resumed["history"][-1]["test_loss"] \
        == straight["history"][-1]["test_loss"]


def test_the_command_line_can_freeze_the_bias_and_drop_the_module(tmp_path):
    _cli(tmp_path, "--epochs", "1", "--bias-rate", "0", "--mtp-weight", "0",
         "--moe-aux-weight", "0")
    from pytorch_distributed_mnist_tpu.utils.profiling import routing_log
    assert routing_log.summary()["bias_range"] == 0.0


# -- the other models' programs are what they were --------------------------

# Taken with ``_lowered`` on PR 33's parent (commit b73cd06): the donated
# train step and the scanned pass of the tiny presets, with recomputation.
# ``laguna``'s are PR 39's tree's, whose gate a head works on the packed view.
PARENT_LOWERED = {
    ("laguna", "step"):
        "7eefb8d33980e8cdb11d3b0d1ba5091b24c3a03d8feddec95bbe3875d858b654",
    ("laguna", "epoch"):
        "ae1a02212e3636df8129b3005d6802f4bd7d0f98c226101f81608380d661f7e5",
    ("sambay", "step"):
        "e776bfc34234b71bfeffd3893e2d3609bda5318697eef2fc4a116f758264bec0",
    ("sambay", "epoch"):
        "80e414d798649a39c4d73e47370d21955c4bd8d92850f3ac6471513e01b5afd7",
}


def _lowered(name, program):
    model = get_model(name, remat=True, attention="dense")
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


@pytest.mark.parametrize("name,program", sorted(PARENT_LOWERED))
def test_the_other_token_models_lower_to_the_parents_text(name, program):
    """``route_topk``, ``SparseExperts``, the train step and the state
    learnt a bias, a second head and a field for this model; what
    ``laguna`` and ``sambay`` trace lowers to the text it lowered to on the
    parent."""
    text = _lowered(name, program)
    assert len(text) > 100_000
    assert hashlib.sha256(text.encode()).hexdigest() \
        == PARENT_LOWERED[(name, program)]
