"""Plain reference of the Laguna decoder (Laguna-XS.2,
https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json), as
one chip of an expert-parallel deployment computes it.

Straightforward ``jax.numpy`` in float32 with every matrix multiplication
at ``highest`` precision, no kernel, no flax module and no code of the
program under test. It reads the parameter tree the system trains (flax
names: ``embed/embedding``, ``block<i>/{ln1,ln2}/scale``,
``block<i>/attn/{q,kv,gate,proj}/kernel``, ``block0/mlp/{gate,up,down}``,
``block<i>/moe/{router/kernel, w_gate, w_up, w_down,
shared/{gate,up,down}/kernel}``, ``ln_f/scale``, ``head/kernel``), so both
sides compute on the same seeded weights. For layer ``l`` with input ``x``
(B, T, C) and ``n`` = RMSNorm (eps 1e-6, learned scale):

    h = x + Attn_l(n(x));   y = h + F_l(n(h));   logits = n(y_L) W_head

``Attn_l``: ``H_l`` query heads (by layer), ``KV`` key-value heads, head
size ``D``; ``q = u W_q``, ``[k | v] = u W_kv``; rotary positions by kind
of layer on the leading ``rot`` dimensions of each head (half-split
pairing): window layers plain (theta 10,000, all of the head), full layers
YaRN (theta 500,000, factor 64, original context 4,096, beta 64 / 1, half
of the head, cos and sin times 1.41589); query head ``i`` reads key-value
head ``i // (H_l / KV)``; scores ``q k^T / sqrt(D)``, causal, and on a
window layer only keys ``t - window < s <= t``; softmax; one output gate a
head, ``W_g`` (C, H_l): ``Attn = concat_i(o_i * sigmoid(u W_g)_i) W_o``.

``F_0``: SwiGLU ``W_d(silu(W_g u) * W_u u)``. ``F_l``, l >= 1: ``s =
sigmoid(u W_r)`` over all ``E`` experts, ``S`` the ``k`` largest, ``w_e =
s_e / sum_S s``; ``F = scale * sum_{e in S, e held here} w_e E_e(u) +
E_shared(u)``, each ``E`` a SwiGLU. The chip holds experts ``first ..
first + count``: what the absent experts would add is left out, as in the
program, while ``w_e`` is normalised over all ``k`` chosen. With ``first =
0, count = E`` this is the uncut layer.

Loss: mean next-token cross entropy over the vocabulary (slice), every
position whose label is not negative (the last of a sequence is -1).

Departures and readings, each because the source's ``config.json`` names a
switch and gives no equation (they are the configuration file's
``assumed``): ``gating: true`` is a sigmoid gate on the attention output,
one a head, from the normed layer input (ISSUE 27 read it as one gate an
element, ``W_g`` (C, H_l x D); the source's 33.4B parameters, 3B active,
are what the per-head gate gives, 33.44B and 3.02B, and not the 34.07B and
3.64B of the other, and the sibling Laguna-S-2.1 says ``per-head``);
router scores are sigmoid,
the top-k weights normalised and times ``moe_routed_scaling_factor``, no
selection bias; pre-norm residual blocks; no QK norm; no gate on the shared
expert; the window counts the current position; documents packed into one
sequence attend to each other.

So that 8,192 tokens fit beside the training state, attention runs in
query blocks and each layer under ``jax.checkpoint``, and the experts in a
loop (``lax.scan``) over the held ones with a mask: none of that changes a
number.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.flash_cost import causal_pairs

RMS_EPS = 1e-6
QUERY_BLOCK = 256
FULL, WINDOW = "full_attention", "sliding_attention"


def _rms_norm(x, p, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * p["scale"]


def _swiglu(u, gate, up, down):
    return (jax.nn.silu(u @ gate) * (u @ up)) @ down


def rope_frequencies(head_dim: int, rope: dict):
    """Inverse frequencies (rot/2,) and the factor on cos and sin."""
    rot = int(head_dim * rope.get("partial_rotary_factor", 1.0))
    theta = float(rope["rope_theta"])
    freqs = np.array([theta ** (-2.0 * i / rot) for i in range(rot // 2)])
    if rope.get("rope_type", "default") == "default":
        return freqs, 1.0
    original = rope["original_max_position_embeddings"]

    def dimension_making(rotations):
        # the (fractional) index i at which original * freqs[i] / 2pi
        # equals ``rotations``
        return rot * math.log(original / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(dimension_making(rope["beta_fast"])), 0)
    high = min(math.ceil(dimension_making(rope["beta_slow"])), rot - 1)
    span = (high - low) or 0.001
    out = []
    for i, f in enumerate(freqs):
        ramp = min(max((i - low) / span, 0.0), 1.0)
        out.append(f / rope["factor"] * ramp + f * (1.0 - ramp))
    return np.array(out), float(rope.get("attention_factor", 1.0))


def _rotate(x, inv_freq, factor):
    """x (B, T, H, D): rotate dims [0, rot) pairing i with i + rot/2."""
    half = inv_freq.shape[0]
    pos = jnp.arange(x.shape[1], dtype=jnp.float32)
    angles = pos[:, None] * jnp.asarray(inv_freq, jnp.float32)[None, :]
    cos = (jnp.cos(angles) * factor)[None, :, None, :]
    sin = (jnp.sin(angles) * factor)[None, :, None, :]
    a, b, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin, rest], axis=-1)


def _attention_core(q, k, v, window):
    """q (B, T, H, D), k and v (B, T, KV, D) -> (B, T, H, D); causal, and
    with ``window`` only the last ``window`` keys; in query blocks."""
    b, t, h, d = q.shape
    group = h // k.shape[2]
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    key_pos = jnp.arange(t)[None, :]

    @jax.checkpoint
    def block(q_blk, start):
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k) / math.sqrt(d)
        query_pos = start + jnp.arange(q_blk.shape[1])[:, None]
        seen = key_pos <= query_pos
        if window is not None:
            seen &= key_pos > query_pos - window
        scores = jnp.where(seen, scores, -jnp.inf)
        return jnp.einsum(
            "bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)

    # One block after the other (``lax.map``), forward and backward, so
    # that one block's (B, H, size, T) scores are alive at a time.
    size = next(s for s in range(min(QUERY_BLOCK, t), 0, -1) if t % s == 0)
    blocks = q.reshape(b, t // size, size, h, d).transpose(1, 0, 2, 3, 4)
    out = jax.lax.map(lambda xs: block(*xs),
                      (blocks, jnp.arange(0, t, size)))
    return out.transpose(1, 0, 2, 3, 4).reshape(b, t, h, d)


def _attention(u, p, *, heads, kv_heads, head_dim, window, rope):
    b, t, _ = u.shape
    q = (u @ p["q"]["kernel"]).reshape(b, t, heads, head_dim)
    kv = (u @ p["kv"]["kernel"]).reshape(b, t, 2 * kv_heads, head_dim)
    k, v = kv[:, :, :kv_heads], kv[:, :, kv_heads:]
    inv_freq, factor = rope_frequencies(head_dim, rope)
    q, k = _rotate(q, inv_freq, factor), _rotate(k, inv_freq, factor)
    o = _attention_core(q, k, v, window) \
        * jax.nn.sigmoid(u @ p["gate"]["kernel"])[..., None]
    return o.reshape(b, t, heads * head_dim) @ p["proj"]["kernel"]


def _experts(u, p, *, top_k, first, routed_scale, choices=None):
    """The held experts' part plus the shared expert, u (N, C), and the
    ``top_k`` experts a token that the scores here choose, (N, k). With
    ``choices`` (N, k) the sum runs over those experts instead, each with
    its score here, normalised over the k given."""
    count = p["w_gate"].shape[0]
    scores = jax.nn.sigmoid(u @ p["router"]["kernel"])  # (N, E)
    top, own = jax.lax.top_k(scores, top_k)
    chosen = own if choices is None else choices
    if choices is not None:
        top = jnp.take_along_axis(scores, choices, axis=-1)
    weights = top / jnp.sum(top, axis=-1, keepdims=True)  # over all k

    @jax.checkpoint
    def one(acc, expert):
        e, gate, up, down = expert
        # w_e where token n chose expert e, else 0
        w = jnp.sum(jnp.where(chosen == e, weights, 0.0), axis=-1)
        return acc + w[:, None] * _swiglu(u, gate, up, down), None

    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(u),
        (first + jnp.arange(count), p["w_gate"], p["w_up"], p["w_down"]))
    shared = _swiglu(u, *(p["shared"][n]["kernel"]
                          for n in ("gate", "up", "down")))
    return routed_scale * routed + shared, own


def forward_and_choices(params, tokens, *, layer_types, heads_per_layer,
                        mlp_layer_types, num_kv_heads, head_dim, window,
                        rope, top_k, experts_held, routed_scale,
                        rms_eps=RMS_EPS, choices=None):
    """``(logits, chosen)``: logits (B, T, V) in float32 for ``tokens``
    (B, T) int, and for each sparse layer in order the experts its router
    chooses, (B * T, k). With ``choices`` (of that form) every sparse layer
    computes with the experts given, not with its own: a comparison that
    hands over the other side's choices holds the arithmetic apart from
    the choice, which one rounding of a nearly tied score moves."""
    p = params["params"]
    given = iter(choices or ())
    chosen = []
    with jax.default_matmul_precision("highest"):
        x = p["embed"]["embedding"][tokens.astype(jnp.int32)]
        for i, (kind, heads, mlp_kind) in enumerate(zip(
                layer_types, heads_per_layer, mlp_layer_types)):

            @jax.checkpoint
            def layer(x, blk, forced, kind=kind, heads=heads,
                      mlp_kind=mlp_kind):
                h = x + _attention(
                    _rms_norm(x, blk["ln1"], rms_eps), blk["attn"],
                    heads=heads, kv_heads=num_kv_heads, head_dim=head_dim,
                    window=window if kind == WINDOW else None,
                    rope=rope[kind])
                u = _rms_norm(h, blk["ln2"], rms_eps)
                if mlp_kind == "dense":
                    return h + _swiglu(u, *(blk["mlp"][n]["kernel"]
                                            for n in ("gate", "up", "down")))
                y, own = _experts(
                    u.reshape(-1, u.shape[-1]), blk["moe"], top_k=top_k,
                    first=experts_held[0], routed_scale=routed_scale,
                    choices=forced)
                return h + y.reshape(u.shape), own

            if mlp_kind == "dense":
                x = layer(x, p[f"block{i}"], None)
            else:
                x, own = layer(x, p[f"block{i}"], next(given, None))
                chosen.append(own)
        logits = _rms_norm(x, p["ln_f"], rms_eps) @ p["head"]["kernel"]
    return logits, tuple(chosen)


def forward(params, tokens, **shape):
    """Logits (B, T, V) in float32 for ``tokens`` (B, T) int."""
    return forward_and_choices(params, tokens, **shape)[0]


def lower_precision(params):
    """``params`` with every matrix rounded to float8 e4m3 under one scale
    a tensor: the nearest precision below bfloat16, and the least an fp8
    computation loses (the activations stay float32). The reference on
    these weights is what ``TOLERANCES['bf16']`` has to refuse. A gradient
    taken through this is the gradient at the rounded weights."""
    def one(x):
        if x.ndim < 2:
            return x
        scale = jnp.max(jnp.abs(x)) / 240.0  # e4m3's largest finite value
        # reduce_precision: XLA folds a pair of converts away
        low = jax.lax.reduce_precision(
            x / scale, exponent_bits=4, mantissa_bits=3) * scale
        return x + jax.lax.stop_gradient(low - x)

    return jax.tree_util.tree_map(one, params)


def cross_entropy(logits, labels):
    """Mean softmax cross-entropy over the positions whose label is not
    negative."""
    counted = labels >= 0
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(
        logp, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    return -jnp.sum(jnp.where(counted, picked, 0.0)) / jnp.sum(counted)


# -- what the harness asks of a configuration's reference module ------------

def shape_from_kwargs(kwargs: dict) -> dict:
    """``forward``'s keyword arguments from a configuration's kwargs."""
    experts = kwargs.get("experts_held") or (0, kwargs["num_experts"])
    return {
        "layer_types": tuple(kwargs["layer_types"]),
        "heads_per_layer": tuple(kwargs["heads_per_layer"]),
        "mlp_layer_types": tuple(kwargs["mlp_layer_types"]),
        "num_kv_heads": kwargs["num_kv_heads"],
        "head_dim": kwargs["head_dim"],
        "window": kwargs["window"],
        "rope": kwargs["rope"],
        "top_k": kwargs["top_k"],
        "experts_held": tuple(experts),
        "routed_scale": kwargs["routed_scale"],
        "rms_eps": kwargs.get("rms_eps", RMS_EPS),
    }


def grad_leaves(kwargs: dict) -> list:
    """One leaf of every kind: the embedding (the whole backward pass lies
    between it and the loss), ``W_q`` of a full and of a window layer, an
    attention gate, the dense MLP, and in the last sparse layer the
    router, the held experts' three matrices and the shared expert; the
    head."""
    kinds = list(kwargs["layer_types"])
    full, win = kinds.index(FULL), kinds.index(WINDOW)
    sparse = len(kinds) - 1 - list(
        reversed(kwargs["mlp_layer_types"])).index("sparse")
    dense = list(kwargs["mlp_layer_types"]).index("dense")
    moe = f"params/block{sparse}/moe"
    return [
        "params/embed/embedding",
        f"params/block{full}/attn/q/kernel",
        f"params/block{win}/attn/q/kernel",
        f"params/block{win}/attn/gate/kernel",
        f"params/block{dense}/mlp/up/kernel",
        f"{moe}/router/kernel",
        f"{moe}/w_gate", f"{moe}/w_up", f"{moe}/w_down",
        f"{moe}/shared/down/kernel",
        "params/head/kernel",
    ]


def _layer_sizes(kwargs: dict):
    c, d = kwargs["hidden_size"], kwargs["head_dim"]
    kv = kwargs["num_kv_heads"]
    held = (kwargs.get("experts_held") or (0, kwargs["num_experts"]))[1]
    for kind, heads, mlp_kind in zip(
            kwargs["layer_types"], kwargs["heads_per_layer"],
            kwargs["mlp_layer_types"]):
        yield c, d, kv, held, kind, heads, mlp_kind


def forward_flops_per_sequence(kwargs: dict, tokens: int) -> float:
    """Matmul operations (a multiply-add is two) that the cut model's
    forward pass requires for one sequence of ``tokens``: projections and
    gates, the attention core at the causal and banded key counts, the
    dense MLP, the router, the routed experts at the expected ``top_k *
    held / num_experts`` pairs a token and the shared expert, the head.
    Norms, rotary, softmax and the embedding lookup are not counted."""
    t = tokens
    total = 0.0
    for c, d, kv, held, kind, heads, mlp_kind in _layer_sizes(kwargs):
        # q and o; k and v; a gate a head
        total += 2 * t * c * (2 * heads * d + 2 * kv * d + heads)
        pairs = causal_pairs(t, kwargs["window"] if kind == WINDOW else None)
        total += 4 * pairs * heads * d  # q k^T and p v
        if mlp_kind == "dense":
            total += 6 * t * c * kwargs["dense_mlp_size"]
        else:
            total += 2 * t * c * kwargs["num_experts"]  # router
            total += 6 * t * c * kwargs["expert_size"] \
                * kwargs["top_k"] * held / kwargs["num_experts"]
            total += 6 * t * c * kwargs["shared_expert_size"]
    return total + 2 * t * kwargs["hidden_size"] * kwargs["vocab_size"]


def tokens_per_image(kwargs: dict) -> int:
    """A packed sequence counts as one image in this harness; its length
    is the configuration's ``seq_len``."""
    return kwargs["seq_len"]


def train_flops_per_image(kwargs: dict) -> float:
    """Forward plus backward (two matmuls for each of the forward's) for
    one packed sequence; nothing for recomputation."""
    return 3.0 * forward_flops_per_sequence(
        model_kwargs(kwargs), tokens_per_image(kwargs))


def model_kwargs(kwargs: dict) -> dict:
    """The registry model's kwargs: the file's, less the keys that only
    the benchmark reads."""
    return {k: v for k, v in kwargs.items() if k != "seq_len"}


def param_count(kwargs: dict) -> int:
    c = kwargs["hidden_size"]
    total = 2 * kwargs["vocab_size"] * c + c  # embedding, head, ln_f
    for c, d, kv, held, _kind, heads, mlp_kind in _layer_sizes(kwargs):
        total += c * (2 * heads * d + 2 * kv * d + heads) + 2 * c
        if mlp_kind == "dense":
            total += 3 * c * kwargs["dense_mlp_size"]
        else:
            total += c * kwargs["num_experts"] \
                + 3 * c * kwargs["expert_size"] * held \
                + 3 * c * kwargs["shared_expert_size"]
    return total


# Tolerances, by the compute type the configuration states. The logits and
# each named gradient are held by their relative root-mean-square error
# (runners/train_lm.py rms_err), the loss by its relative difference; both
# sides compute with the experts the system chose, and ``choice_flips`` is
# the share of those (token, choice) pairs that are not among the
# reference's own k (a nearly tied score names another expert after one
# bfloat16 rounding: that is held apart from the arithmetic).
#
# bf16: each limit lies between two readings on the v5e at the published
# widths on one sequence of 8,192 tokens (my chip runs, PR 27; PERF.md
# section 6 has every number). The system over 8 seeds: logits 0.0064,
# loss 1e-7-1.6e-5 (at most 3.9e-5 in 52 runs of every version of the
# program), the eleven named gradients 0.0038-0.0116, choice_flips
# 0.0059-0.0065. The control, this reference with its weights rounded to
# float8 e4m3 (``lower_precision``) as the system of the same comparison
# (``runners/train_lm.py check_lower_precision``; tests_tpu/
# test_laguna_on_tpu.py runs it at this size, tests/test_laguna_bench.py
# at a tiny one): logits 0.042, loss 8.8e-5-2.4e-4, named gradients
# 0.037-0.098, choice_flips 0.045. It is refused by every limit at once.
#
# f32 (the CPU tests' preset): the system and the reference differ in the
# order of summation only, and the tests' seeds have no tied score. A bf16
# forward (1% and more at the tiny size) fails these.
TOLERANCES = {
    "bf16": {"logits": 0.02, "loss": 6e-5, "grad": 0.022,
             "choice_flips": 0.02},
    "f32": {"logits": 1e-3, "loss": 1e-4, "grad": 1e-2,
            "choice_flips": 0.0},
}
