"""Plain reference of granite-4.0-h-micro's architecture
(https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json,
``model_type: granitemoehybrid`` with ``num_local_experts: 0``; the
Mamba-2 layer is arXiv:2405.21060's), as one chip of the configuration's
deployment computes it.

Straightforward ``jax.numpy`` in float32 with every matrix multiplication
at ``highest`` precision, no kernel, no flax module and no code of the
program under test. It reads the parameter tree the system trains (flax
names: ``embed/embedding``, ``block<i>/{ln1,ln2}/scale``,
``block<i>/mlp/{gate_up,down}/kernel``, ``block<i>/ssd/{in_proj,
conv_kernel, conv_bias, dt_bias, A_log, D}``, ``ssd/norm/scale``,
``ssd/out_proj/kernel``, ``block<i>/attn/{q,k,v,proj}/kernel``,
``ln_f/scale``), so both sides compute on the same seeded weights. With
``n(.)`` an RMSNorm with a learnt scale (eps 1e-5), ``e`` = 12 the
``embedding_multiplier``, ``r`` = 0.22 the ``residual_multiplier``, ``a`` =
1/64 the ``attention_multiplier`` and ``s`` = 8 the ``logits_scaling``:

    x_0 = e E[t];   x <- x + r Mix_l(n(x));   x <- x + r MLP(n(x))
    logits = n(x_L) E^T / s;   MLP(u) = (silu(g) * v) W_down, [g | v] = u W_up

``Mix_l`` by the kind of layer ``l``:

- ``mamba``: ``[z | c | r] = u W_in`` (4096 | 4352 | 64); ``c =
  silu(conv(c))``, a causal depthwise convolution of width 4 with bias;
  ``[x | B | C] = c`` (4096 | 128 | 128), ``x`` as 64 heads of 64; ``dt =
  softplus(r + dt_bias)``; ``A = -exp(A_log)``; for every head ``h``, from
  ``S = 0`` (64 x 128): ``S_t = exp(dt_t[h] A[h]) S_{t-1} + dt_t[h] x_t[h]
  B_t^T``; ``y_t[h] = S_t C_t + D[h] x_t[h]``; ``Mix = n_4096(y * silu(z))
  W_out``. The recurrence is computed **as the recurrence**, one position
  after the other, with no chunk algebra.
- ``attention``: ``q, k, v = u W_q, u W_k, u W_v`` (32, 8 and 8 heads of
  64), no positional term; ``o = softmax_causal(a q k^T) v``; ``Mix = o
  W_o``.

Loss: mean next-token cross entropy over the vocabulary (slice), every
position whose label is not negative (the last of a sequence is -1).

What the source's ``config.json`` does not give is the configuration
file's ``assumed`` (the initialisation; no clamp on ``dt``; documents
attend, and the convolution and the state run, across document
boundaries). ``param_count`` ties the equations to the source: uncut they
give 3,191,396,096, the card's 3B.

So that 8,192 tokens fit beside the weights, attention runs in query
blocks, each layer under ``jax.checkpoint``, and the recurrence, a
``lax.scan`` over the positions, in stretches whose gradient recomputes
them (a plain ``grad`` of an 8,192-step scan keeps 17 GB of states a Mamba
layer): none of that changes a number.

``forward(..., rounded=...)`` is the control's hook and nothing the
harness passes: it names values (``dt``, ``decay``: the running sum of
``dt A`` a stretch, ``state``, ``logits``) to round through bfloat16, so
that a test can show which of the limits below such a rounding breaks.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.flash_cost import causal_pairs

RMS_EPS = 1e-5
QUERY_BLOCK = 256
STRETCH = 128  # positions the recurrence's gradient recomputes at a time
CHUNK = 256  # the source's mamba_chunk_size: what the FLOPs are counted at
MAMBA, ATTENTION = "mamba", "attention"
ROUNDABLE = ("dt", "decay", "state", "logits")


def _bf16(x, name, rounded):
    """``x`` rounded through bfloat16 where the control names it; the
    gradient passes as if it had not been."""
    if name not in rounded:
        return x
    return x + jax.lax.stop_gradient(
        x.astype(jnp.bfloat16).astype(jnp.float32) - x)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


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


def _recurrence(x, dt, A, B, C, rounded=()):
    """One sequence: x (T, H, P), dt (T, H), A (H,), B, C (T, N) -> (T, H,
    P), position by position. The control's ``decay`` keeps the running sum
    of ``dt A`` since the stretch's start in bfloat16 and multiplies the
    state by the exponential of its increments, as an implementation that
    held the cumulative decay in that type would."""

    def step(carry, xs):
        s, run = carry
        x_t, dt_t, b_t, c_t = xs
        a_t = dt_t * A
        if "decay" in rounded:
            ahead = _bf16(run + a_t, "decay", rounded)
            a_t, run = ahead - _bf16(run, "decay", rounded), run + a_t
        s = jnp.exp(a_t)[:, None, None] * s \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        s = _bf16(s, "state", rounded)
        return (s, run), jnp.einsum("hpn,n->hp", s, c_t)

    @jax.checkpoint
    def stretch(s, xs):
        (s, _), y = jax.lax.scan(step, (s, jnp.zeros_like(A)), xs)
        return s, y

    t, h, p = x.shape
    size = next(s for s in range(min(STRETCH, t), 0, -1) if t % s == 0)
    xs = tuple(v.reshape(t // size, size, *v.shape[1:])
               for v in (x, dt, B, C))
    _, y = jax.lax.scan(
        stretch, jnp.zeros((h, p, B.shape[-1]), jnp.float32), xs)
    return y.reshape(t, h, p)


def _mamba(u, p, *, n_heads, d_head, d_state, eps, rounded=()):
    b, t, _ = u.shape
    inner = n_heads * d_head
    zcr = u @ p["in_proj"]
    z, c, r = (zcr[..., :inner], zcr[..., inner:2 * inner + 2 * d_state],
               zcr[..., 2 * inner + 2 * d_state:])
    c = jax.nn.silu(_conv(c, p["conv_kernel"], p["conv_bias"]))
    x = c[..., :inner].reshape(b, t, n_heads, d_head)
    bm, cm = c[..., inner:inner + d_state], c[..., inner + d_state:]
    dt = _bf16(jax.nn.softplus(r + p["dt_bias"]), "dt", rounded)
    y = jax.vmap(lambda *xs: _recurrence(*xs, rounded=rounded),
                 in_axes=(0, 0, None, 0, 0))(
        x, dt, -jnp.exp(p["A_log"]), bm, cm) + p["D"][:, None] * x
    gated = y.reshape(b, t, inner) * jax.nn.silu(z)
    return _rms_norm(gated, p["norm"]["scale"], eps) \
        @ p["out_proj"]["kernel"]


def _attention(u, p, *, heads, kv_heads, head_dim, scale):
    """Causal grouped-query attention, explicit masked scores in query
    blocks; no positional term."""
    b, t, _ = u.shape
    q = (u @ p["q"]["kernel"]).reshape(b, t, heads, head_dim)
    k, v = ((u @ p[name]["kernel"]).reshape(b, t, kv_heads, head_dim)
            for name in ("k", "v"))
    k = jnp.repeat(k, heads // kv_heads, axis=2)
    v = jnp.repeat(v, heads // kv_heads, axis=2)
    key_pos = jnp.arange(t)[None, :]

    @jax.checkpoint
    def block(q_blk, start):
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k) * scale
        query_pos = start + jnp.arange(q_blk.shape[1])[:, None]
        scores = jnp.where(key_pos <= query_pos, scores, -jnp.inf)
        return jnp.einsum(
            "bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)

    size = next(s for s in range(min(QUERY_BLOCK, t), 0, -1) if t % s == 0)
    blocks = q.reshape(b, t // size, size, heads, head_dim).transpose(
        1, 0, 2, 3, 4)
    out = jax.lax.map(lambda xs: block(*xs),
                      (blocks, jnp.arange(0, t, size)))
    return out.transpose(1, 0, 2, 3, 4).reshape(b, t, heads * head_dim) \
        @ p["proj"]["kernel"]


def forward(params, tokens, *, layer_types, num_heads, num_kv_heads,
            head_dim, mamba_n_heads, mamba_d_head, mamba_d_state,
            embedding_multiplier, residual_multiplier, attention_multiplier,
            logits_scaling, rms_eps=RMS_EPS, rounded=()):
    """Logits (B, T, V) in float32 for ``tokens`` (B, T) int."""
    p = params["params"]
    with jax.default_matmul_precision("highest"):
        x = embedding_multiplier * p["embed"]["embedding"][
            tokens.astype(jnp.int32)]
        for i, kind in enumerate(layer_types):

            @jax.checkpoint
            def layer(x, blk, kind=kind):
                u = _rms_norm(x, blk["ln1"]["scale"], rms_eps)
                if kind == MAMBA:
                    mix = _mamba(u, blk["ssd"], n_heads=mamba_n_heads,
                                 d_head=mamba_d_head, d_state=mamba_d_state,
                                 eps=rms_eps, rounded=rounded)
                else:
                    mix = _attention(u, blk["attn"], heads=num_heads,
                                     kv_heads=num_kv_heads,
                                     head_dim=head_dim,
                                     scale=attention_multiplier)
                x = x + residual_multiplier * mix
                return x + residual_multiplier * _mlp(
                    _rms_norm(x, blk["ln2"]["scale"], rms_eps), blk["mlp"])

            x = layer(x, p[f"block{i}"])
        logits = _rms_norm(x, p["ln_f"]["scale"], rms_eps) \
            @ p["embed"]["embedding"].T / logits_scaling
        return _bf16(logits, "logits", rounded)


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
    names = ("num_heads", "num_kv_heads", "head_dim", "mamba_n_heads",
             "mamba_d_head", "mamba_d_state", "embedding_multiplier",
             "residual_multiplier", "attention_multiplier",
             "logits_scaling")
    return {"layer_types": tuple(kwargs["layer_types"]),
            **{name: kwargs[name] for name in names},
            "rms_eps": kwargs.get("rms_eps", RMS_EPS)}


def grad_leaves(kwargs: dict) -> list:
    """The embedding (which is also the head: it gets gradient through the
    factor 12 at one end and the 1/8 at the other, and the whole backward
    pass lies between); every parameter of the first Mamba layer, under all
    the others (``W_in``, the convolution's kernel, ``dt_bias``, ``A_log``,
    ``D``, the gated norm's scale, ``W_out``) and ``A_log`` and ``W_in`` of
    the last; ``W_q`` and ``W_k`` of the attention layer; an MLP."""
    kinds = list(kwargs["layer_types"])
    first, last = kinds.index(MAMBA), len(kinds) - 1 - kinds[::-1].index(
        MAMBA)
    attn = f"params/block{kinds.index(ATTENTION)}/attn"
    ssd = f"params/block{first}/ssd"
    return [
        "params/embed/embedding",
        f"{ssd}/in_proj", f"{ssd}/conv_kernel", f"{ssd}/dt_bias",
        f"{ssd}/A_log", f"{ssd}/D", f"{ssd}/norm/scale",
        f"{ssd}/out_proj/kernel",
        f"params/block{last}/ssd/in_proj", f"params/block{last}/ssd/A_log",
        f"{attn}/q/kernel", f"{attn}/k/kernel",
        f"params/block{last}/mlp/down/kernel",
    ]


def ssd_flops_per_sequence(*, tokens: int, heads: int, d_head: int,
                           d_state: int, chunk: int = CHUNK) -> float:
    """Matrix-multiplication operations of one Mamba-2 layer's recurrence
    in its chunked form at the published chunk, causal half (a multiply-add
    is two): a chunk's scores ``C B^T`` once for all heads and the masked
    product a head over the ``Q (Q + 1) / 2`` pairs ``j <= i``, the chunk's
    state a head and its use (``benchmark/ssd_cost.py`` counts the same)."""
    q = min(chunk, tokens)
    pairs = q * (q + 1) // 2
    per_chunk = 2 * pairs * d_state + heads * (
        2 * pairs * d_head + 4 * q * d_head * d_state)
    return float(tokens // q * per_chunk)


def forward_flops_per_sequence(kwargs: dict, tokens: int) -> float:
    """Matrix-multiplication operations that the cut model's forward pass
    requires for one sequence of ``tokens``: projections, MLPs, the head,
    attention's two products over the exact causal pairs, and the
    recurrence's chunked products (``ssd_flops_per_sequence``). The decay
    masks' exponentials, the convolution, norms, softmax and the embedding
    lookup are not counted."""
    t, d = tokens, kwargs["hidden_size"]
    h, kv, hd = kwargs["num_heads"], kwargs["num_kv_heads"], \
        kwargs["head_dim"]
    mh, mp, n = kwargs["mamba_n_heads"], kwargs["mamba_d_head"], \
        kwargs["mamba_d_state"]
    inner = mh * mp
    total = 0.0
    for kind in kwargs["layer_types"]:
        total += 6 * t * d * kwargs["mlp_size"]
        if kind == MAMBA:
            total += 2 * t * d * (2 * inner + 2 * n + mh) + 2 * t * inner * d
            total += ssd_flops_per_sequence(
                tokens=t, heads=mh, d_head=mp, d_state=n)
        else:
            total += 2 * t * d * (2 * h * hd + 2 * kv * hd)
            total += 4 * causal_pairs(t) * h * hd  # q k^T and p v
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
    h, kv, hd = kwargs["num_heads"], kwargs["num_kv_heads"], \
        kwargs["head_dim"]
    mh, n = kwargs["mamba_n_heads"], kwargs["mamba_d_state"]
    inner = mh * kwargs["mamba_d_head"]
    conv = inner + 2 * n
    total = kwargs["vocab_size"] * d + d  # tied embedding, ln_f
    for kind in kwargs["layer_types"]:
        total += 3 * d * f + 2 * d  # MLP, two norms
        if kind == MAMBA:
            total += d * (inner + conv + mh) \
                + kwargs["mamba_d_conv"] * conv + conv \
                + 3 * mh + inner + inner * d
        else:
            total += d * (h * hd + 2 * kv * hd) + h * hd * d
    return total


# Tolerances, by the compute type the configuration states. The logits and
# each named gradient are held by their relative root-mean-square error
# (runners/train_lm.py rms_err), the loss by its relative difference; a
# leaf may have a limit of its own under ``grad:<its last name>``.
#
# bf16: each limit lies between two readings on the v5e at the published
# widths on one sequence of 8,192 tokens (my chip runs, PR 37; PERF.md
# section 6 has every number). The system over 10 seeds: logits
# 0.01058-0.01064, loss 1e-7-4.3e-6, the thirteen named gradients
# 0.0060-0.0208 (``dt_bias`` of the first Mamba layer the largest, its
# ``D`` and ``A_log`` 0.015). The control, this reference with its weights
# rounded to float8 e4m3 (``lower_precision``) as the system of the same
# comparison (``runners/train_lm_plain.py check_lower_precision``;
# tests_tpu/test_granite4h_on_tpu.py runs it at this size,
# tests/test_granite4h_bench.py at a tiny one), over 2 seeds: logits
# 0.0676-0.0679, loss 7e-6-9e-6, those gradients 0.0619-0.129 (the
# embedding's the smallest). The logits' limit and the gradients' are the
# geometric means of the two readings, a factor of 1.7 to 2.8 from either:
# the control is refused by the logits and by every named gradient on both
# seeds. The readings are half Phi-4's (0.023 on the logits) because the
# embedding enters the stream times 12 and every branch times 0.22: a
# token's own embedding, which no block rounds, is most of the stream.
#
# The loss cannot tell the two apart (a mean over 8,191 positions of
# errors that largely cancel: 4.3e-6 against 7e-6), so it has the limit of
# the runner's accepted cell, ``phi4flash.py``'s 1.2e-4, which leaves the
# system's first reading (1.7e-6) seventy times of room and still refuses
# a loss that is wrong (another reduction, a missing mask: 1e-3 and more).
#
# f32 (the CPU tests' preset): the system and the reference differ in the
# order of summation only (the chunked products against the recurrence a
# position at a time). A bfloat16 ``dt``, running decay, carried state or
# logit in the reference itself (``forward``'s ``rounded``) fails these
# (tests/test_granite4h.py).
TOLERANCES = {
    "bf16": {"logits": 0.03, "loss": 1.2e-4, "grad": 0.036},
    "f32": {"logits": 2e-5, "loss": 1e-5, "grad": 5e-4},
}
