"""Plain reference of Phi-4-mini-flash-reasoning's architecture, SambaY
(https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/blob/main/config.json,
``model_type: phi4flash``; arXiv:2507.06607), as one chip of the
configuration's deployment computes it.

Straightforward ``jax.numpy`` in float32 with every matrix multiplication
at ``highest`` precision, no kernel, no flax module and no code of the
program under test. It reads the parameter tree the system trains (flax
names: ``embed/embedding``, ``block<i>/{ln1,ln2}/{scale,bias}``,
``block<i>/mlp/{gate_up,down}/kernel``, ``block<i>/ssm/{in_proj, x_proj,
dt_proj, out_proj}/kernel`` and ``ssm/{conv_kernel, conv_bias, dt_bias,
A_log, D}``, ``block<i>/attn/{qkv | q, proj}/{kernel,bias}`` and
``attn/{lq1, lk1, lq2, lk2, subln}``, ``block<i>/gmu/{in_proj,
out_proj}/kernel``, ``ln_f/{scale,bias}``), so both sides compute on the
same seeded weights. With ``d`` the hidden size, ``LN`` LayerNorm with
scale and bias:

    h = x + Mix_l(LN1(x));   y = h + MLP(LN2(h));   logits = LN_f(y_L) E^T
    MLP(u) = (silu(g) * v) W_2,   [g, v] = u W_1

``Mix_l`` by the kind of layer ``l``:

- ``mamba``: ``[a, z] = u W_in``; ``a = silu(conv(a))``, ``conv`` a causal
  depthwise convolution of width 4 with bias; ``[r, B, C] = a W_x``; ``dt =
  softplus(r W_dt + b_dt)``; ``A = -exp(A_log)``; for every channel ``c``
  and state ``n``: ``s_t = exp(dt_t[c] A[c, n]) s_{t-1} + dt_t[c] B_t[n]
  a_t[c]``, ``s_0 = 0``; ``m_t[c] = sum_n C_t[n] s_t[c, n] + D[c]
  a_t[c]``; ``Mix = (m * silu(z)) W_out``. The last Mamba layer's ``m`` is
  the memory ``M``.
- ``gmu``: ``Mix = (M * silu(u W_1g)) W_2g``.
- ``sliding_attention``, ``full_attention``: ``[q, k, v] = u W_qkv + b``;
  query heads in pairs ``(q1, q2)`` (adjacent heads), key heads in pairs
  ``(k1, k2)``, a value pair ``v = [v1; v2]`` of twice the head size; two
  query pairs read one key-value pair. ``A_i = softmax(q_i k_i^T / sqrt(D)
  + mask)``, causal, and in a sliding layer only keys ``t - window < s <=
  t``; ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init``,
  ``lambda_init = 0.8 - 0.6 exp(-0.3 l)`` at the published layer index
  ``l``; ``o = (1 - lambda_init) RMSNorm(A_1 v - lambda A_2 v)`` (learned
  scale, eps 1e-5, over the value pair's width); ``Mix = concat(o) W_o +
  b_o``. The last full layer's ``k`` and ``v`` are ``K*`` and ``V*``.
- ``cross_attention``: the same with ``q = u W_q + b`` only and ``k, v =
  K*, V*``, causal over the same positions.

Loss: mean next-token cross entropy over the vocabulary (slice), every
position whose label is not negative (the last of a sequence is -1).

What the source's ``config.json`` does not give is the configuration
file's ``assumed`` (state size 16, convolution 4, expansion 2, ``dt`` rank
``ceil(d / 16)``; which layer is of which kind and what layers 16 and 17
publish; the differential form; biases on the attention projections only;
no rotary; the window counts the current position; documents attend, and
the convolution and the state run, across document boundaries).
``param_count`` ties these readings to the source: uncut they give the
card's 3.8B.

So that 16,384 tokens fit beside the weights, attention runs in query
blocks, each layer under ``jax.checkpoint``, and the scan, a ``lax.scan``
over the positions, in chunks whose gradient recomputes them (a plain
``grad`` of a 16,384-step scan keeps 5.4 GB of states a Mamba layer): none
of that changes a number.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.flash_cost import causal_pairs

LN_EPS = 1e-5
QUERY_BLOCK = 256
SCAN_CHUNK = 128
MAMBA, WINDOW, FULL = "mamba", "sliding_attention", "full_attention"
GMU, CROSS = "gmu", "cross_attention"


def _layer_norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _mlp(u, p):
    g, v = jnp.split(u @ p["gate_up"]["kernel"], 2, axis=-1)
    return (jax.nn.silu(g) * v) @ p["down"]["kernel"]


def _conv(x, kernel, bias):
    """x (B, T, C), kernel (W, C): y_t = sum_j kernel[j] x_{t-W+1+j}."""
    width = kernel.shape[0]
    y = bias
    for j in range(width):
        back = width - 1 - j  # positions behind t
        shifted = jnp.pad(x, ((0, 0), (back, 0), (0, 0)))[:, :x.shape[1]]
        y = y + shifted * kernel[j]
    return y


def _scan(a, dt, A, B, C):
    """One sequence: a, dt (T, C); A (C, N); B, C (T, N) -> (T, C), the
    recurrence position by position."""

    def step(s, x):
        a_t, dt_t, b_t, c_t = x
        s = jnp.exp(dt_t[:, None] * A) * s \
            + (dt_t * a_t)[:, None] * b_t[None, :]
        return s, jnp.sum(s * c_t[None, :], axis=-1)

    @jax.checkpoint
    def chunk(s, xs):
        return jax.lax.scan(step, s, xs)

    t = a.shape[0]
    size = next(s for s in range(min(SCAN_CHUNK, t), 0, -1) if t % s == 0)
    xs = tuple(x.reshape(t // size, size, *x.shape[1:])
               for x in (a, dt, B, C))
    _, m = jax.lax.scan(chunk, jnp.zeros(A.shape, jnp.float32), xs)
    return m.reshape(t, -1)


def _mamba(u, p, *, d_state, dt_rank):
    a, z = jnp.split(u @ p["in_proj"]["kernel"], 2, axis=-1)
    a = jax.nn.silu(_conv(a, p["conv_kernel"], p["conv_bias"]))
    rbc = a @ p["x_proj"]["kernel"]
    r, b = rbc[..., :dt_rank], rbc[..., dt_rank:dt_rank + d_state]
    c = rbc[..., dt_rank + d_state:]
    dt = jax.nn.softplus(r @ p["dt_proj"]["kernel"] + p["dt_bias"])
    m = jax.vmap(_scan, in_axes=(0, 0, None, 0, 0))(
        a, dt, -jnp.exp(p["A_log"]), b, c) + p["D"] * a
    return (m * jax.nn.silu(z)) @ p["out_proj"]["kernel"], m


def _softmax_maps(q, k, v, window):
    """q (B, T, P, D) one of a query pair's two heads for every pair, k (B,
    T, G, D) the like key heads, v (B, T, G, 2D) -> softmax(q k^T) v (B, T,
    P, 2D); causal, banded by ``window``; in query blocks."""
    b, t, pairs, d = q.shape
    per_group = pairs // k.shape[2]
    k = jnp.repeat(k, per_group, axis=2)
    v = jnp.repeat(v, per_group, axis=2)
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
            "bhqk,bkhe->bqhe", jax.nn.softmax(scores, axis=-1), v)

    size = next(s for s in range(min(QUERY_BLOCK, t), 0, -1) if t % s == 0)
    blocks = q.reshape(b, t // size, size, pairs, d).transpose(1, 0, 2, 3, 4)
    out = jax.lax.map(lambda xs: block(*xs),
                      (blocks, jnp.arange(0, t, size)))
    return out.transpose(1, 0, 2, 3, 4).reshape(b, t, pairs, 2 * d)


def _attention(u, p, kv, *, heads, kv_heads, head_dim, window, layer_id,
               eps):
    """``(Mix, (k, v))``; ``kv`` given makes it a cross layer."""
    b, t, _ = u.shape
    d, pairs, groups = head_dim, heads // 2, kv_heads // 2
    if kv is None:
        qkv = u @ p["qkv"]["kernel"] + p["qkv"]["bias"]
        q, k, v = (qkv[..., :heads * d],
                   qkv[..., heads * d:(heads + kv_heads) * d],
                   qkv[..., (heads + kv_heads) * d:])
    else:
        q = u @ p["q"]["kernel"] + p["q"]["bias"]
        k, v = kv
    q = q.reshape(b, t, pairs, 2, d)
    kk = k.reshape(b, t, groups, 2, d)
    vv = v.reshape(b, t, groups, 2 * d)
    first, second = (_softmax_maps(q[:, :, :, i], kk[:, :, :, i], vv, window)
                     for i in (0, 1))
    start = 0.8 - 0.6 * math.exp(-0.3 * layer_id)
    lam = jnp.exp(jnp.sum(p["lq1"] * p["lk1"])) \
        - jnp.exp(jnp.sum(p["lq2"] * p["lk2"])) + start
    o = first - lam * second
    o = o * jax.lax.rsqrt(
        jnp.mean(jnp.square(o), axis=-1, keepdims=True) + eps) * p["subln"]
    o = (1.0 - start) * o
    return (o.reshape(b, t, heads * d) @ p["proj"]["kernel"]
            + p["proj"]["bias"]), (k, v)


def forward(params, tokens, *, layer_types, layer_ids, num_heads,
            num_kv_heads, head_dim, window, d_state, dt_rank,
            layer_norm_eps=LN_EPS):
    """Logits (B, T, V) in float32 for ``tokens`` (B, T) int."""
    p = params["params"]
    kinds = list(layer_types)
    last = {kind: max((i for i, k in enumerate(kinds) if k == kind),
                      default=None) for kind in (MAMBA, FULL)}
    memory = shared_kv = None
    with jax.default_matmul_precision("highest"):
        x = p["embed"]["embedding"][tokens.astype(jnp.int32)]
        for i, (kind, layer_id) in enumerate(zip(kinds, layer_ids)):

            @jax.checkpoint
            def layer(x, blk, memory, shared_kv, kind=kind,
                      layer_id=layer_id):
                u = _layer_norm(x, blk["ln1"], layer_norm_eps)
                out = None
                if kind == MAMBA:
                    mix, out = _mamba(u, blk["ssm"], d_state=d_state,
                                      dt_rank=dt_rank)
                elif kind == GMU:
                    g = blk["gmu"]
                    mix = (memory * jax.nn.silu(u @ g["in_proj"]["kernel"])
                           ) @ g["out_proj"]["kernel"]
                else:
                    mix, out = _attention(
                        u, blk["attn"], shared_kv if kind == CROSS else None,
                        heads=num_heads, kv_heads=num_kv_heads,
                        head_dim=head_dim,
                        window=window if kind == WINDOW else None,
                        layer_id=layer_id, eps=layer_norm_eps)
                h = x + mix
                return h + _mlp(_layer_norm(h, blk["ln2"], layer_norm_eps),
                                blk["mlp"]), out

            x, out = layer(x, p[f"block{i}"], memory, shared_kv)
            if last[MAMBA] == i:
                memory = out
            if last[FULL] == i:
                shared_kv = out
        return _layer_norm(x, p["ln_f"], layer_norm_eps) \
            @ p["embed"]["embedding"].T


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

def model_kwargs(kwargs: dict) -> dict:
    """The registry model's kwargs: the file's, less the keys that only
    the benchmark reads."""
    return {k: v for k, v in kwargs.items() if k != "seq_len"}


def shape_from_kwargs(kwargs: dict) -> dict:
    """``forward``'s keyword arguments from a configuration's kwargs."""
    kinds = tuple(kwargs["layer_types"])
    return {
        "layer_types": kinds,
        "layer_ids": tuple(kwargs.get("layer_ids") or range(len(kinds))),
        "num_heads": kwargs["num_heads"],
        "num_kv_heads": kwargs["num_kv_heads"],
        "head_dim": kwargs["head_dim"],
        "window": kwargs["window"],
        "d_state": kwargs["d_state"],
        "dt_rank": kwargs["dt_rank"],
        "layer_norm_eps": kwargs.get("layer_norm_eps", LN_EPS),
    }


def grad_leaves(kwargs: dict) -> list:
    """Leaves of every kind of layer: the embedding (which is also the
    head: the whole backward pass lies between its two uses); of the last
    Mamba layer ``W_in``, ``A_log``, ``W_dt`` and the convolution; ``W_1``
    of a GMU; ``lq1`` and the sub-norm's scale of a sliding layer and
    ``W_qkv`` of the full one; ``W_q`` of a cross layer; an MLP."""
    kinds = list(kwargs["layer_types"])

    def block(kind):
        return f"params/block{len(kinds) - 1 - kinds[::-1].index(kind)}"

    ssm = f"{block(MAMBA)}/ssm"
    return [
        "params/embed/embedding",
        f"{ssm}/in_proj/kernel", f"{ssm}/A_log", f"{ssm}/dt_proj/kernel",
        f"{ssm}/conv_kernel",
        f"{block(GMU)}/gmu/in_proj/kernel",
        f"{block(WINDOW)}/attn/lq1", f"{block(WINDOW)}/attn/subln",
        f"{block(FULL)}/attn/qkv/kernel",
        f"{block(CROSS)}/attn/q/kernel",
        f"{block(CROSS)}/mlp/down/kernel",
    ]


def forward_flops_per_sequence(kwargs: dict, tokens: int) -> float:
    """Matrix-multiplication operations (a multiply-add is two) that the
    cut model's forward pass requires for one sequence of ``tokens``:
    projections, MLPs, the head, and for attention one score map a query
    head at the keys' width and the value pair's (twice that) over the
    exact causal and banded pairs. The scan's elementwise work (7
    operations a position, channel and state), the convolution, norms,
    softmax and the embedding lookup are not counted."""
    t, d = tokens, kwargs["hidden_size"]
    di, n, r = kwargs["d_inner"], kwargs["d_state"], kwargs["dt_rank"]
    h, kv, hd = kwargs["num_heads"], kwargs["num_kv_heads"], \
        kwargs["head_dim"]
    total = 0.0
    for kind in kwargs["layer_types"]:
        total += 6 * t * d * kwargs["mlp_size"]
        if kind == MAMBA:
            total += 2 * t * (d * 2 * di + di * (r + 2 * n) + r * di
                              + di * d)
        elif kind == GMU:
            total += 4 * t * d * di
        else:
            own_kv = 0 if kind == CROSS else 2 * kv * hd
            total += 2 * t * d * (2 * h * hd + own_kv)
            pairs = causal_pairs(
                t, kwargs["window"] if kind == WINDOW else None)
            total += 2 * pairs * h * (hd + 2 * hd)  # q k^T and p v
    return total + 2 * t * d * kwargs["vocab_size"]


def tokens_per_image(kwargs: dict) -> int:
    """A packed sequence counts as one image in this harness; its length
    is the configuration's ``seq_len``."""
    return kwargs["seq_len"]


def train_flops_per_image(kwargs: dict) -> float:
    """Forward plus backward (two matmuls for each of the forward's) for
    one packed sequence; nothing for recomputation."""
    return 3.0 * forward_flops_per_sequence(
        model_kwargs(kwargs), tokens_per_image(kwargs))


def param_count(kwargs: dict) -> int:
    d, f = kwargs["hidden_size"], kwargs["mlp_size"]
    di, n, r = kwargs["d_inner"], kwargs["d_state"], kwargs["dt_rank"]
    h, kv, hd = kwargs["num_heads"], kwargs["num_kv_heads"], \
        kwargs["head_dim"]
    total = kwargs["vocab_size"] * d + 2 * d  # tied embedding, ln_f
    for kind in kwargs["layer_types"]:
        total += 3 * d * f + 4 * d  # MLP, two LayerNorms
        if kind == MAMBA:
            total += d * 2 * di + kwargs["d_conv"] * di + di \
                + di * (r + 2 * n) + r * di + di + di * n + di + di * d
        elif kind == GMU:
            total += 2 * d * di
        else:
            own_kv = 0 if kind == CROSS else 2 * kv * hd
            total += d * (h * hd + own_kv) + (h * hd + own_kv)  # and bias
            total += h * hd * d + d  # W_o, b_o
            total += 4 * hd + 2 * hd  # lambda vectors, sub-norm
    return total


# Tolerances, by the compute type the configuration states. The logits and
# each named gradient are held by their relative root-mean-square error
# (runners/train_lm.py rms_err), the loss by its relative difference; a
# leaf may have a limit of its own under ``grad:<its last name>``.
#
# bf16: each limit lies between two readings on the v5e at the published
# widths on one sequence of 16,384 tokens (my chip runs, PR 31; PERF.md
# section 6 has every number). The system over 11 seeds: logits
# 0.0228-0.0246, loss 4e-6-3.4e-5, the ten named gradients but ``lq1``
# 0.0079-0.0446 (``dt_proj``'s the largest), ``lq1`` 0.0035-0.247. The
# control, this reference with its weights rounded to float8 e4m3
# (``lower_precision``) as the system of the same comparison
# (``runners/train_lm_plain.py check_lower_precision``;
# tests_tpu/test_phi4flash_on_tpu.py runs it at this size,
# tests/test_phi4flash_bench.py at a tiny one), over 2 seeds: logits
# 0.2254-0.2262, loss 3.3e-5-4.2e-4, those gradients 0.119-0.412, ``lq1``
# 0.20-1.11. It is refused by the logits and by every named gradient but
# ``lq1`` on both seeds, and by the loss on one.
#
# The readings are nearly four times Laguna's (0.0064 on the logits) because
# the head is the embedding at normal(0.02): what a block writes, not the
# token's embedding, is most of the residual stream, so every block's
# bfloat16 roundings reach the logits whole; and a Mamba layer rounds eight
# times between its input and its output, with ``dt``'s rounding going
# through an ``exp`` and a sum over hundreds of positions.
#
# ``lq1`` (and its three siblings) has a limit of its own because its
# gradient is ill-conditioned in any 16-bit arithmetic at the seed:
# ``dL/dlambda = -sum(g . A_2 v)`` where ``g``, the gradient behind the
# sub-norm, is orthogonal to ``A_1 v - lambda A_2 v``; at the seed both
# maps are near-uniform averages of the same values, ``A_2 v`` is nearly
# parallel to that difference, and what is left is the small part of a
# kernel output rounded to bfloat16 (one part in 256) that is not: a
# fifth of the gradient's norm on some seeds and a hundredth on others,
# where every other leaf reads 1-4% on all, and the control's reading of
# it overlaps the system's. Its limit of 0.5 is what a wrong formula
# breaks (a sign or a pairing wrong reads 1 and more) and no rounding.
#
# f32 (the CPU tests' preset): the system and the reference differ in the
# order of summation only. A bf16 forward (1% and more at the tiny size)
# fails these.
TOLERANCES = {
    "bf16": {"logits": 0.07, "loss": 1.2e-4, "grad": 0.075,
             "grad:lq1": 0.5},
    "f32": {"logits": 1e-3, "loss": 1e-4, "grad": 1e-2},
}
