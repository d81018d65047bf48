"""Plain reference of the Instella-MoE decoder (Instella-MoE-16B-A3B-Base,
https://huggingface.co/amd/Instella-MoE-16B-A3B-Base/blob/main/config.json,
``model_type: deepseek_v3``), as one chip of an expert-parallel deployment
computes it, with both of its heads, its training objective and the update
of its selection bias.

Straightforward ``jax.numpy`` in float32 with every matrix multiplication
at ``highest`` precision, no kernel, no flax module and no code of the
program under test; written from the equations of arXiv:2405.04434 (latent
attention) and arXiv:2412.19437 (sigmoid routing, eq. 12-16; the selection
bias, section 2.1.2; the sequence-wise balance term, eq. 17-20; the
multi-token-prediction module, eq. 21-25). It reads the variables the
system trains (flax names: ``params/{embed/embedding, head/kernel,
ln_f/scale}``, ``params/block<i>/{ln1, ln2}/scale``,
``params/block<i>/attn/{q, kv_a, kv_b, gate, proj}/kernel`` and
``attn/{kv_norm, q_norm, k_norm}/scale``, ``params/block0/mlp/{gate, up,
down}/kernel``, ``params/block<i>/moe/{router/kernel, w_gate, w_up, w_down,
shared/{gate, up, down}/kernel}``, ``params/{mtp_merge/kernel, mtp_ln_h,
mtp_ln_e, mtp_ln_f, mtp_block/...}`` and the biases
``router_bias/{block<i>, mtp_block}/moe/select``), so both sides compute on
the same seeded weights and the same bias.

With ``n`` = RMSNorm (eps 1e-6, learnt scale) and ``u`` a sub-layer's
normed input:

*Latent attention*, ``H`` heads. ``q = u W_q`` (C -> H x (nope + rot));
``[c | k_r] = u W_kva`` (C -> rank + rot); ``[k_nope | v] = n_rank(c)
W_kvb`` (rank -> H x (nope + dv)); ``q_h = n_qk([q_nope,h | q_rope,h])``,
``k_h = n_qk'([k_nope,h | k_r])`` with the one ``k_r`` of a token given to
every head and one scale (nope + rot) for all heads; then the last ``rot``
dimensions of both are rotated by position, pairing dimension ``2i`` with
``2i + 1`` (``rope_interleave``), frequencies YaRN over the ``rot``
dimensions; ``o_h = softmax_causal(q_h k_h^T s) v_h`` with ``s = (nope +
rot)^-0.5 (0.1 mscale_all_dim ln factor + 1)^2``; ``Attn(u) = concat_h(o_h
* sigmoid(u W_g)_h) W_o`` with ``W_g``: C -> H x dv, a gate an element.

*Experts.* ``s = sigmoid(u W_r)`` over all ``E``; ``S`` = the ``k``
largest of ``s + b``; ``w_e = scale * s_e / sum_S s`` (the bias ``b``
enters no weight); ``F(u) = sum_{e in S, e held here} w_e E_e(u) +
E_shared(u)``, every ``E`` a SwiGLU, the shared one of the two shared
experts' widths together. The chip holds experts ``first .. first +
count``. The balance term of one sequence of ``T`` tokens: ``sum_e f_e
P_e``, ``f_e = E / (k T) * #{t: e in S_t}``, ``P_e = mean_t s_e,t / sum_e'
s_e',t``; its mean over the sequences and sum over the sparse layers is
``aux``. After a step ``b_e += gamma * sign(mean_e' n_e' - n_e)``, ``n``
the step's (token, choice) pairs of each of all ``E`` experts.

*FarSkip* (a reading of the source's ``farskip: true``: the configuration
file's ``assumed``). Sub-layers ``j = 1 .. 2L`` (attention and MLP of
block 0, of block 1, ...), ``o_j = f_j(n_j(x_j))``, ``s_0`` the embedding,
``s_j = s_{j-1} + o_j``; ``x_1 = s_0`` and ``x_j = s_{j-2}`` for ``j >=
2``. ``h = n_f(s_2L)``, ``logits = h W_head``.

*The module.* ``h'_i = W_eh [n(h_i) ; n'(Emb(t_{i+1}))]``, one sparse
block under the same rule from its own input (``x_1 = x_2 = h'``), ``n''``
and the trunk's head: logits for ``t_{i+2}``. ``t_{i+1}`` at the last
position, which has none, is id 0 and counts nothing.

*Objective*: ``CE(logits, t_{i+1}) + mtp_weight * CE(mtp logits, t_{i+2})
+ aux_weight * aux``, each cross entropy a mean over the positions whose
label is not negative; ``t_{i+2}`` is the label shifted by one more, with
-1 at the last position.

So that 8,192 tokens fit, attention runs in query blocks, every sub-layer
under ``jax.checkpoint`` and the experts in a loop (``lax.scan``) over the
held ones with a one-hot product: none of that changes a number.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.flash_cost import causal_pairs
from benchmark.reference.laguna import (
    _rms_norm,
    _swiglu,
    cross_entropy,
    lower_precision,
    rope_frequencies,
)

__all__ = ["cross_entropy", "lower_precision"]  # the harness reads them here

RMS_EPS = 1e-6
QUERY_BLOCK = 256


def _rotate_pairs(x, inv_freq, factor):
    """x (B, T, H, R): rotate the pair (2i, 2i + 1) by ``t * inv_freq[i]``."""
    pos = jnp.arange(x.shape[1], dtype=jnp.float32)
    angles = pos[:, None] * jnp.asarray(inv_freq, jnp.float32)[None, :]
    cos = (jnp.cos(angles) * factor)[None, :, None, :]
    sin = (jnp.sin(angles) * factor)[None, :, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def softmax_scale(qk_dim: int, rope: dict) -> float:
    mscale = 0.1 * rope.get("mscale_all_dim", 0.0) \
        * math.log(rope.get("factor", 1.0)) + 1.0
    return mscale * mscale / math.sqrt(qk_dim)


def _attention_core(q, k, v, scale):
    """q, k (B, T, H, D), v (B, T, H, Dv) -> (B, T, H, Dv); causal, in
    query blocks, one block's (B, H, size, T) scores alive at a time."""
    b, t, h, d = q.shape
    key_pos = jnp.arange(t)[None, :]

    @jax.checkpoint
    def block(q_blk, start):
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k) * scale
        query_pos = start + jnp.arange(q_blk.shape[1])[:, None]
        scores = jnp.where(key_pos <= query_pos, scores, -jnp.inf)
        return jnp.einsum(
            "bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)

    size = next(s for s in range(min(QUERY_BLOCK, t), 0, -1) if t % s == 0)
    blocks = q.reshape(b, t // size, size, h, d).transpose(1, 0, 2, 3, 4)
    out = jax.lax.map(lambda xs: block(*xs),
                      (blocks, jnp.arange(0, t, size)))
    return out.transpose(1, 0, 2, 3, 4).reshape(b, t, h, v.shape[-1])


def latent_attention(u, p, *, heads, nope, rot, dv, rope, eps,
                     qk_norm=True, gated=True):
    """``Attn(u)`` of the module docstring, u (B, T, C)."""
    b, t, _ = u.shape
    q = (u @ p["q"]["kernel"]).reshape(b, t, heads, nope + rot)
    latent = u @ p["kv_a"]["kernel"]
    c, k_r = latent[..., :-rot], latent[..., -rot:]
    up = (_rms_norm(c, p["kv_norm"], eps) @ p["kv_b"]["kernel"]).reshape(
        b, t, heads, nope + dv)
    k_nope, v = up[..., :nope], up[..., nope:]
    k = jnp.concatenate(
        [k_nope, jnp.repeat(k_r[:, :, None, :], heads, axis=2)], axis=-1)
    if qk_norm:
        q = _rms_norm(q, p["q_norm"], eps)
        k = _rms_norm(k, p["k_norm"], eps)
    inv_freq, factor = rope_frequencies(rot, rope)
    q, k = (jnp.concatenate(
        [x[..., :nope], _rotate_pairs(x[..., nope:], inv_freq, factor)],
        axis=-1) for x in (q, k))
    o = _attention_core(q, k, v, softmax_scale(nope + rot, rope))
    o = o.reshape(b, t, heads * dv)
    if gated:
        o = o * jax.nn.sigmoid(u @ p["gate"]["kernel"])
    return o @ p["proj"]["kernel"]


def experts(u, p, bias, *, top_k, first, routed_scale, choices=None):
    """``(F(u), own, load, balance)`` for u (B, T, C): the held experts'
    part plus the shared expert; ``own`` (B * T, k) the experts that ``s +
    b`` chooses here; ``load`` (E,) the pairs each of all experts is given
    and ``balance`` the mean over the sequences of ``sum_e f_e P_e``. With
    ``choices`` (B * T, k) the sum, the load and the balance term read
    those experts instead, each with its score here."""
    b, t, c = u.shape
    count = p["w_gate"].shape[0]
    scores = jax.nn.sigmoid(u @ p["router"]["kernel"])  # (B, T, E)
    e = scores.shape[-1]
    _, own = jax.lax.top_k(scores + bias, top_k)
    chosen = own if choices is None else choices.reshape(b, t, top_k)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = routed_scale * picked / jnp.sum(picked, axis=-1, keepdims=True)
    onehot = (chosen[..., None] == jnp.arange(e)).astype(jnp.float32)
    # w_e where token (b, t) chose expert e, else 0: (B, T, E)
    gates = jnp.einsum("btk,btke->bte", weights, onehot)
    flat = u.reshape(b * t, c)

    @jax.checkpoint
    def one(acc, expert):
        w, gate, up, down = expert
        return acc + w[:, None] * _swiglu(flat, gate, up, down), None

    held = gates.reshape(b * t, e)[:, first:first + count].T  # (count, N)
    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(flat),
        (held, p["w_gate"], p["w_up"], p["w_down"]))
    shared = _swiglu(flat, *(p["shared"][n]["kernel"]
                             for n in ("gate", "up", "down")))
    per_seq = jax.lax.stop_gradient(jnp.sum(onehot, axis=(1, 2)))  # (B, E)
    f = per_seq * e / (top_k * t)
    prob = jnp.mean(scores / jnp.sum(scores, axis=-1, keepdims=True), axis=1)
    balance = jnp.mean(jnp.sum(f * prob, axis=-1))
    return ((routed + shared).reshape(b, t, c), own.reshape(b * t, top_k),
            jnp.sum(per_seq, axis=0), balance)


def _sub_layers(p, bias, names_and_kinds, shape):
    """The sub-layers of the named blocks in order: ``(f, None)`` with ``f:
    x -> o`` for an attention or a dense MLP, ``(f, name)`` with ``f: (x,
    choices) -> experts(...)`` for a sparse one."""
    eps = shape["rms_eps"]
    for name, mlp_kind in names_and_kinds:
        blk = p[name]

        @jax.checkpoint
        def attn(x, blk=blk):
            return latent_attention(
                _rms_norm(x, blk["ln1"], eps), blk["attn"],
                heads=shape["num_heads"], nope=shape["nope_dim"],
                rot=shape["rope_dim"], dv=shape["v_dim"],
                rope=shape["rope"], eps=eps, qk_norm=shape["qk_norm"],
                gated=shape["gated"])

        yield attn, None
        if mlp_kind == "dense":
            @jax.checkpoint
            def mlp(x, blk=blk):
                return _swiglu(_rms_norm(x, blk["ln2"], eps),
                               *(blk["mlp"][n]["kernel"]
                                 for n in ("gate", "up", "down")))

            yield mlp, None
        else:
            @jax.checkpoint
            def moe(x, given, blk=blk, b=bias[name]["moe"]["select"]):
                return experts(
                    _rms_norm(x, blk["ln2"], eps), blk["moe"], b,
                    top_k=shape["top_k"], first=shape["experts_held"][0],
                    routed_scale=shape["routed_scale"], choices=given)

            yield moe, name


def _stream(s0, subs, forced, farskip):
    """``s_2L`` from ``s_0`` through the sub-layers, and what the sparse
    ones report, by block name."""
    streams = [s0]
    reports = {}
    for j, (f, sparse) in enumerate(subs, start=1):
        x = streams[max(j - 2, 0)] if farskip else streams[j - 1]
        if sparse is None:
            o = f(x)
        else:
            o, own, load, balance = f(x, next(forced, None))
            reports[sparse] = (own, load, balance)
        streams.append(streams[j - 1] + o)
    return streams[-1], reports


def forward_all(variables, tokens, *, choices=None, **shape):
    """``((logits, mtp_logits), chosen, loads, aux)`` for ``tokens`` (B, T)
    int: both heads' logits (B, T, V) in float32; for each sparse layer in
    order (the trunk's, then the module's) the experts its router chooses,
    (B * T, k); ``loads`` ``{block: (E,)}`` the pairs given to each expert;
    ``aux`` the balance terms summed over the sparse layers. With
    ``choices`` (of ``chosen``'s form) every sparse layer computes with the
    experts given."""
    p = variables["params"]
    trunk = [(f"block{i}", kind)
             for i, kind in enumerate(shape["mlp_layer_types"])]
    module = [("mtp_block", "sparse")]
    bias = variables.get("router_bias")
    if bias is None:  # a model without a selection bias
        zero = jnp.zeros((shape["num_experts"],), jnp.float32)
        bias = {name: {"moe": {"select": zero}} for name, _ in trunk + module}
    forced = iter(choices or ())
    eps = shape["rms_eps"]
    tokens = tokens.astype(jnp.int32)
    with jax.default_matmul_precision("highest"):
        emb = p["embed"]["embedding"]
        s, reports = _stream(
            emb[tokens], _sub_layers(p, bias, trunk, shape), forced,
            shape["farskip"])
        h = _rms_norm(s, p["ln_f"], eps)
        logits = h @ p["head"]["kernel"]
        # t_{i+1}; the last position has none
        nxt = jnp.concatenate(
            [tokens[:, 1:], jnp.zeros_like(tokens[:, :1])], axis=1)
        merged = jnp.concatenate(
            [_rms_norm(h, p["mtp_ln_h"], eps),
             _rms_norm(emb[nxt], p["mtp_ln_e"], eps)],
            axis=-1) @ p["mtp_merge"]["kernel"]
        m, mtp_reports = _stream(
            merged, _sub_layers(p, bias, module, shape), forced,
            shape["farskip"])
        mtp_logits = _rms_norm(m, p["mtp_ln_f"], eps) @ p["head"]["kernel"]
    reports.update(mtp_reports)
    chosen = tuple(r[0] for r in reports.values())
    loads = {name: r[1] for name, r in reports.items()}
    aux = sum(r[2] for r in reports.values())
    return (logits, mtp_logits), chosen, loads, aux


def forward(variables, tokens, **shape):
    """The next token's logits (B, T, V): what evaluation reads."""
    return forward_all(variables, tokens, **shape)[0][0]


def mtp_labels(labels):
    """``t_{i+2}``: the labels shifted by one more, -1 at the end."""
    return jnp.concatenate(
        [labels[:, 1:], jnp.full_like(labels[:, :1], -1)], axis=1)


def objective(outputs, aux, labels, *, mtp_weight, aux_weight):
    """``(objective, next-token loss, module's loss)``."""
    loss = cross_entropy(outputs[0], labels)
    mtp_loss = cross_entropy(outputs[1], mtp_labels(labels))
    return loss + mtp_weight * mtp_loss + aux_weight * aux, loss, mtp_loss


def bias_after_step(bias, loads, rate):
    """``b_e + rate * sign(mean n - n_e)`` for every sparse layer: ``bias``
    the ``router_bias`` tree, ``loads`` ``{block: (E,)}``."""
    return {name: {"moe": {"select": sub["moe"]["select"] + rate * jnp.sign(
        jnp.mean(loads[name]) - loads[name])}} for name, sub in bias.items()}


def adam_first_step(grad, lr, eps=1e-8):
    """A parameter's change in Adam's first step from zero moments
    (arXiv:1412.6980, algorithm 1: with the bias corrections ``m^ = g`` and
    ``v^ = g^2``): ``-lr g / (|g| + eps)``."""
    return -lr * grad / (jnp.abs(grad) + eps)


# -- what the harness asks of a configuration's reference module ------------

def model_kwargs(kwargs: dict) -> dict:
    """The registry model's kwargs: the file's, less the keys that only
    the benchmark reads."""
    return {k: v for k, v in kwargs.items() if k != "seq_len"}


def shape_from_kwargs(kwargs: dict) -> dict:
    """``forward_all``'s keyword arguments from a configuration's kwargs."""
    held = kwargs.get("experts_held") or (0, kwargs["num_experts"])
    return {
        "mlp_layer_types": tuple(kwargs["mlp_layer_types"]),
        "num_heads": kwargs["num_heads"],
        "nope_dim": kwargs["nope_dim"],
        "rope_dim": kwargs["rope_dim"],
        "v_dim": kwargs["v_dim"],
        "rope": kwargs["rope"],
        "num_experts": kwargs["num_experts"],
        "top_k": kwargs["top_k"],
        "experts_held": tuple(held),
        "routed_scale": kwargs["routed_scale"],
        "qk_norm": kwargs.get("qk_norm", True),
        "gated": kwargs.get("gated", True),
        "farskip": kwargs.get("farskip", True),
        "rms_eps": kwargs.get("rms_eps", RMS_EPS),
    }


def grad_leaves(kwargs: dict) -> list:
    """One leaf of every kind: the embedding (it gets gradient from the
    trunk's lookup, from the module's lookup and, through ``h``, from both
    losses), the four projections and a norm of a latent attention, the
    dense MLP, in the last sparse layer of the trunk the router, the held
    experts' three matrices and the shared expert, the module's merge, its
    router and a held expert of its block, and the head both losses
    read."""
    kinds = list(kwargs["mlp_layer_types"])
    sparse = len(kinds) - 1 - kinds[::-1].index("sparse")
    dense = kinds.index("dense")
    attn, moe = f"params/block{sparse}/attn", f"params/block{sparse}/moe"
    return [
        "params/embed/embedding",
        f"{attn}/q/kernel", f"{attn}/kv_a/kernel", f"{attn}/kv_b/kernel",
        *([f"{attn}/gate/kernel"] if kwargs.get("gated", True) else []),
        *([f"{attn}/k_norm/scale"] if kwargs.get("qk_norm", True) else []),
        f"params/block{dense}/attn/proj/kernel",
        f"params/block{dense}/mlp/up/kernel",
        f"{moe}/router/kernel",
        f"{moe}/w_gate", f"{moe}/w_up", f"{moe}/w_down",
        f"{moe}/shared/down/kernel",
        "params/mtp_merge/kernel",
        "params/mtp_block/moe/router/kernel",
        "params/mtp_block/moe/w_up",
        "params/head/kernel",
    ]


def _attention_params(kw: dict) -> int:
    c, h = kw["hidden_size"], kw["num_heads"]
    nope, rot, dv, rank = (kw["nope_dim"], kw["rope_dim"], kw["v_dim"],
                           kw["kv_rank"])
    norms = rank + (2 * (nope + rot) if kw.get("qk_norm", True) else 0)
    return (c * h * (nope + rot) + c * (rank + rot) + rank * h * (nope + dv)
            + (c * h * dv if kw.get("gated", True) else 0) + h * dv * c
            + norms)


def _mlp_params(kw: dict, kind: str, held: int) -> int:
    c = kw["hidden_size"]
    if kind == "dense":
        return 3 * c * kw["dense_mlp_size"]
    return (c * kw["num_experts"] + 3 * c * kw["expert_size"] * held
            + 3 * c * kw["shared_expert_size"])


def param_count(kwargs: dict, held=None) -> int:
    """Parameters of the model as cut (or with ``held`` experts a sparse
    layer): the trunk's blocks, the module (merge, three norms, one sparse
    block), the embedding, the head and ``ln_f``. The selection biases (E
    a sparse layer) are no parameters and are not counted."""
    kw = kwargs
    c = kw["hidden_size"]
    if held is None:
        held = (kw.get("experts_held") or (0, kw["num_experts"]))[1]
    block = _attention_params(kw) + 2 * c
    total = 2 * kw["vocab_size"] * c + c
    for kind in kw["mlp_layer_types"]:
        total += block + _mlp_params(kw, kind, held)
    if kw.get("mtp", True):
        total += 2 * c * c + 3 * c + block + _mlp_params(kw, "sparse", held)
    return total


def _block_flops(kw: dict, kind: str, t: int, held: int) -> float:
    c, h = kw["hidden_size"], kw["num_heads"]
    nope, rot, dv, rank = (kw["nope_dim"], kw["rope_dim"], kw["v_dim"],
                           kw["kv_rank"])
    total = 2.0 * t * (c * h * (nope + rot) + c * (rank + rot)
                       + rank * h * (nope + dv) + h * dv * c
                       + (c * h * dv if kw.get("gated", True) else 0))
    total += 2.0 * causal_pairs(t, None) * h * (nope + rot + dv)
    if kind == "dense":
        return total + 6.0 * t * c * kw["dense_mlp_size"]
    return total + 2.0 * t * c * kw["num_experts"] \
        + 6.0 * t * c * kw["expert_size"] * kw["top_k"] * held \
        / kw["num_experts"] + 6.0 * t * c * kw["shared_expert_size"]


def forward_flops_per_sequence(kwargs: dict, tokens: int) -> float:
    """Matmul operations (a multiply-add is two) that the cut model's
    training forward pass requires for one sequence of ``tokens``: the
    latent attention's five projections, its core at the causal key count
    (``nope + rot`` wide scores, ``dv`` wide values), the dense MLP, the
    router, the routed experts at the expected ``top_k * held /
    num_experts`` pairs a token and the shared expert, the module's merge
    and block, and the head twice. Norms, rotary, softmax, the gate's
    sigmoid, the balance term and the embedding lookups are not counted."""
    kw, t = kwargs, tokens
    c = kw["hidden_size"]
    held = (kw.get("experts_held") or (0, kw["num_experts"]))[1]
    total = sum(_block_flops(kw, kind, t, held)
                for kind in kw["mlp_layer_types"])
    heads = 1
    if kw.get("mtp", True):
        total += 2.0 * t * 2 * c * c + _block_flops(kw, "sparse", t, held)
        heads = 2
    return total + heads * 2.0 * t * c * kw["vocab_size"]


def tokens_per_image(kwargs: dict) -> int:
    """A packed sequence counts as one image in this harness; its length
    is the configuration's ``seq_len``."""
    return kwargs["seq_len"]


def train_flops_per_image(kwargs: dict) -> float:
    """Forward plus backward (two matmuls for each of the forward's) for
    one packed sequence; nothing for recomputation."""
    return 3.0 * forward_flops_per_sequence(
        model_kwargs(kwargs), tokens_per_image(kwargs))


# Tolerances, by the compute type the configuration states
# (``runners/train_lm_mtp.py`` has the comparison: the model's forward
# gives both logit arrays and its choices, this reference computes one step
# with those choices, and one step of the trainer's own pass is held to
# that). Both logit arrays and each named gradient (the step's, read off
# Adam's first moment) are held by their relative root-mean-square error
# (``runners/train_lm.py rms_err``), the two losses and the objective the
# pass reports by their relative difference, ``choice_flips`` is the share
# of the model's (token, choice) pairs that are not among the reference's
# own k. ``update``: the named leaves' change against ``adam_first_step``
# of this reference's gradient, each entry weighed by that gradient's size,
# as a relative root-mean-square error: a state left unchanged reads 1, a
# step twice as long 1. ``bias``: the mean over all entries of the distance
# between the bias the state carries out and ``bias_after_step``, in
# updates: a bias that never moved reads about 1, one moved the wrong way
# 2; an entry whose load lies within a few pairs of the mean may go either
# way where the step's program chooses a few pairs otherwise than the
# forward's.
#
# bf16: readings on the v5e at the published widths on one step's batch (2
# sequences of 8,192 tokens), under a seeded bias (my chip runs, PR 33;
# PERF.md section 6 has every number). The system over 15 seeds: logits
# 0.00669-0.00673, the module's 0.00650-0.00656, choice_flips
# 0.0040-0.0052, loss at most 1.5e-5, the module's loss 2.3e-5, the
# objective 1.2e-5, the 11 named gradients outside the routed experts
# 0.0043-0.0141 (the largest ``q/kernel``'s and ``k_norm/scale``'s),
# ``update`` 0.00025-0.030, the bias equal in every entry on every seed.
# The control, this reference with its weights rounded to float8 e4m3
# (``lower_precision``) as the system of the same comparison and its step
# Adam's of its own gradient (``runners/train_lm_mtp.py
# check_lower_precision``; tests_tpu/test_instella_on_tpu.py runs it at this
# size, tests/test_instella_bench.py at a tiny one) over 2 seeds: logits
# 0.0476-0.0478, the module's 0.0515-0.0516, choice_flips 0.0415-0.0418,
# those 11 gradients 0.0406-0.127, loss 7.5e-5-1.7e-4, the module's loss
# 6.0e-5-1.4e-4, the objective 9.0e-5-1.4e-4, ``update`` 0.0071-0.064, the
# bias equal (both sides move it from one load). It is refused by every
# limit on an array or on one of those gradients, by the choices, the loss
# and the objective at once. What precision hardly moves is held against a
# wrong formula, not against float8, and says so here:
#
# - ``grad_routed``, the 6 named gradients of routers and routed experts.
#   The step's program rounds the stream otherwise than the forward program
#   whose choices this reference is given, and sends a few pairs of a
#   thousand elsewhere; such a pair moves these gradients by all of its
#   part, so they read 0.021-0.099 (the module's router the largest: it
#   lies deepest), where the control reads 0.066-0.118. The limit lies
#   between that and 0.67, what the module's leaves read when its term is
#   weighed 0.1 for 0.3. (Computed in one program with the choices, as
#   this PR's first check did on one sequence, all 17 gradients read
#   0.0043-0.0147 over 10 seeds: the layer's own arithmetic is bf16's.)
# - the module's loss: the control's smallest reading (6.0e-5) is under
#   three times the system's largest (2.3e-5, also the first), so no limit
#   separates them; it takes 1e-4, four times that reading. ``loss`` and
#   ``objective`` take the limit of the harness's accepted token cell
#   (``reference/laguna.py``, 6e-5): 20 times their first readings (2.8e-6,
#   3.1e-6), 4 times their largest, under the control's smallest (7.5e-5,
#   9.0e-5) by a fifth only: the arrays and gradients are what holds it.
# - ``update``: between its first reading (0.020 at the module's router,
#   under 0.002 outside the routed experts; the largest of 15 seeds 0.030)
#   and 1, with the more room above.
# - ``bias``: between 0 (every seed) and 1.
#
# f32 (the CPU tests' preset): the system and the reference differ in the
# order of summation only, and the tests' seeds have no tied score. A bf16
# router, norm, rotary or logit (1% and more at the tiny size) fails these.
TOLERANCES = {
    "bf16": {"logits": 0.02, "mtp_logits": 0.02, "loss": 6e-5,
             "mtp_loss": 1e-4, "objective": 6e-5, "grad": 0.022,
             "grad_routed": 0.25, "update": 0.2, "choice_flips": 0.015,
             "bias": 0.05},
    "f32": {"logits": 1e-3, "mtp_logits": 1e-3, "loss": 1e-4,
            "mtp_loss": 1e-4, "objective": 1e-4, "grad": 1e-2,
            "grad_routed": 1e-2, "update": 1e-2, "choice_flips": 0.0,
            "bias": 1e-3},
}
