"""Decoder-only token model family: registry model ``laguna``.

A causal language model whose layers differ by index, as Laguna-XS.2's do
(https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json): the
kind of attention (``full_attention`` or ``sliding_attention`` with a
window), the number of query heads (48 on full layers, 64 on window layers
there), the rotary frequencies (YaRN on part of the head for full layers,
plain on all of it for window layers) and the kind of MLP (``dense`` SwiGLU
or ``sparse``: top-k routed experts and a shared one, ``models/moe.py``).
Pre-norm residual blocks with RMSNorm, grouped key-value heads, no biases,
one output gate a head on attention (``W_g``: hidden -> heads), untied
embedding and head:

    h = x + Attn_l(n(x));  y = h + F_l(n(h));  logits = n(y_L) W_head
    Attn_l(u) = concat_h(softmax_causal,window(rope(q_h) rope(k)^T / sqrt(D))
                         v * sigmoid(u W_g)_h) W_o

The defaults are a tiny preset (hidden 64, one dense layer then one period
of three window layers and a full one, 16 experts top-4) that trains on the
CPU from the command line (``--model laguna --dataset synthetic_tokens``);
the benchmark's configuration passes the published widths. ``experts_held``
gives the range of experts this chip holds in every sparse layer (one
chip's share of an expert-parallel deployment); ``vocab_size`` may be the
chip's slice of the vocabulary.

Attention goes through the flash kernels (``ops/pallas/flash.py``) on a TPU
and through the dense oracle ``ops.attention.full_attention`` elsewhere
(``attention='auto'``): at the benchmark's 8,192 tokens the dense scores of
one sequence of one layer would be 12.9 GB. bfloat16 compute, float32
parameters, router and logits; ``remat`` recomputes per block.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from pytorch_distributed_mnist_tpu.models.moe import (
    SparseExperts,
    SwiGLU,
    residual_init,
)
from pytorch_distributed_mnist_tpu.models.registry import register_model
from pytorch_distributed_mnist_tpu.ops.attention import (
    CORE_SCOPE,
    FLASH_LSE_NAME,
    FLASH_OUT_NAME,
    full_attention,
)
from pytorch_distributed_mnist_tpu.ops.pallas.rope import (
    rotate_heads,
    whole_heads,
)
from pytorch_distributed_mnist_tpu.parallel.moe_dispatch import CHOICE_NAME
from pytorch_distributed_mnist_tpu.utils.profiling import (
    flash_schedules,
    head_gate_sites,
    rotary_sites,
)

FULL, WINDOW = "full_attention", "sliding_attention"
DENSE, SPARSE = "dense", "sparse"
# The tiny preset's rotary settings: the published kinds at small numbers.
TINY_ROPE = {
    FULL: {"rope_type": "yarn", "rope_theta": 10000.0, "factor": 4.0,
           "original_max_position_embeddings": 16, "beta_fast": 4.0,
           "beta_slow": 1.0, "attention_factor": 1.1,
           "partial_rotary_factor": 0.5},
    WINDOW: {"rope_type": "default", "rope_theta": 100.0,
             "partial_rotary_factor": 1.0},
}


def rope_frequencies(head_dim: int, params: dict) -> Tuple[np.ndarray, float]:
    """``(inverse frequencies (rot/2,), factor on cos and sin)`` of one kind
    of layer, where ``rot = partial_rotary_factor * head_dim`` leading
    dimensions of a head are rotated.

    ``default``: ``theta ** (-2i / rot)``. ``yarn`` (arXiv:2309.00071, as
    the transformers library computes it): the interpolated frequency
    ``f_i / factor`` and the extrapolated ``f_i`` blended by a linear ramp
    over the dimension index, from the dimension that makes ``beta_fast``
    rotations over the original context (and above: extrapolated, kept) to
    the one that makes ``beta_slow`` (and below: interpolated); cos and sin
    are scaled by ``attention_factor``."""
    rot = int(head_dim * params.get("partial_rotary_factor", 1.0))
    theta = float(params["rope_theta"])
    freqs = theta ** (-np.arange(0, rot, 2, dtype=np.float64) / rot)
    if params.get("rope_type", "default") == "default":
        return freqs, 1.0
    if params["rope_type"] != "yarn":
        raise ValueError(f"unknown rope_type {params['rope_type']!r}")
    original = params["original_max_position_embeddings"]

    def dim_of(rotations):
        return rot * math.log(original / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(dim_of(params["beta_fast"])), 0)
    high = min(math.ceil(dim_of(params["beta_slow"])), rot - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(rot // 2) - low) / (high - low), 0.0, 1.0)
    blended = freqs / params["factor"] * ramp + freqs * (1.0 - ramp)
    return blended, float(params.get("attention_factor", 1.0))


def rope_tables(t: int, head_dim: int, inv_freq: np.ndarray, factor: float):
    """``(C, S)``, float32 ``(t, head_dim)``, with which the half-split
    rotary of a whole head is ``x * C + swap(x) * S`` (``swap`` exchanges
    lanes ``i`` and ``i + rot/2``): ``C`` is ``factor * cos`` on the ``rot``
    leading lanes and 1 behind them, ``S`` is ``-factor * sin`` on lanes
    ``[0, rot/2)``, ``+factor * sin`` on ``[rot/2, rot)`` and 0 behind."""
    rot = 2 * inv_freq.shape[0]
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv_freq, jnp.float32)[None, :]  # (t, rot/2)
    cos, sin = jnp.cos(angles) * factor, jnp.sin(angles) * factor
    rest = (t, head_dim - rot)
    return (jnp.concatenate([cos, cos, jnp.ones(rest, jnp.float32)], axis=-1),
            jnp.concatenate([-sin, sin, jnp.zeros(rest, jnp.float32)],
                            axis=-1))


def apply_rope(x: jnp.ndarray, inv_freq: np.ndarray, factor: float):
    """Rotate the leading ``2 * len(inv_freq)`` dimensions of every head of
    ``x`` (B, T, H, D) by position (0 .. T-1), pairing dimension ``i`` with
    ``i + rot/2`` (the half-split convention); float32 inside.

    One path a shape, chosen by the shape. Where a head is whole registers
    of 128 lanes (``D % 128 == 0``, the benchmark's widths) the head is
    never sliced: ``x * C + swap(x) * S`` over :func:`rope_tables`, one
    kernel that reads and writes ``x`` once (``ops/pallas/rope.py``), whose
    backward is the same kernel at the negated angle and not autodiff's
    transpose of slices and a concatenate, which cost the compiled step
    layout copies of every half. Any other head size (the tiny preset's
    16) is sliced, as before that kernel. The same products in the same
    order either way. ``utils.profiling.rotary_sites`` counts the calls."""
    rot = 2 * inv_freq.shape[0]
    if whole_heads(x.shape[-1]):
        c, s = rope_tables(x.shape[1], x.shape[-1], inv_freq, factor)
        return rotate_heads(x, c, s, rot)
    rotary_sites.record(rot, whole_head=False)
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv_freq, jnp.float32)[None, :]  # (T, rot/2)
    cos = (jnp.cos(angles) * factor)[None, :, None, :]
    sin = (jnp.sin(angles) * factor)[None, :, None, :]
    xf = x.astype(jnp.float32)
    x1, x2, rest = xf[..., :rot // 2], xf[..., rot // 2:rot], xf[..., rot:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)
    return out.astype(x.dtype)


def apply_rope_interleaved(x: jnp.ndarray, inv_freq: np.ndarray,
                           factor: float):
    """Rotate all ``2 * len(inv_freq)`` dimensions of every head of ``x``
    (B, T, H, R) by position, pairing dimension ``2i`` with ``2i + 1`` (the
    interleaved convention, ``rope_interleave: true`` of a ``deepseek_v3``
    configuration); float32 inside."""
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv_freq, jnp.float32)[None, :]  # (T, R/2)
    cos = (jnp.cos(angles) * factor)[None, :, None, :]
    sin = (jnp.sin(angles) * factor)[None, :, None, :]
    xf = x.astype(jnp.float32).reshape(x.shape[:-1] + (-1, 2))
    a, b = xf[..., 0], xf[..., 1]
    out = jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


class RMSNorm(nn.Module):
    eps: float = 1e-6
    compute_dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        xf = x.astype(jnp.float32)
        xf = xf * jax.lax.rsqrt(
            jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + self.eps)
        return (xf * scale).astype(self.compute_dtype)


def attend(q, k, v, *, window: Optional[int], attention: str,
           scale: Optional[float] = None, cross: bool = False):
    """Causal attention of one layer under ``attn_core/<kind>``: ``window``,
    ``full`` or, where the keys and values are an earlier layer's
    (``cross``), ``cross``. ``scale`` defaults to ``D ** -0.5``."""
    if attention == "auto":
        attention = "flash" if jax.default_backend() == "tpu" else "dense"
    if attention == "flash":
        from pytorch_distributed_mnist_tpu.ops.pallas.flash import (
            flash_attention as fn,
        )
    elif attention == "dense":
        fn = full_attention
    else:
        raise ValueError(f"unknown attention {attention!r}")
    kind = "cross" if cross else "full" if window is None else "window"
    with jax.named_scope(f"{CORE_SCOPE}/{kind}"):
        return fn(q, k, v, causal=True, window=window, scale=scale)


_KEPT_NAMES = jax.checkpoint_policies.save_only_these_names(
    CHOICE_NAME, FLASH_OUT_NAME, FLASH_LSE_NAME)


def _kept_by_a_recomputed_block(prim, *avals, **params) -> bool:
    kept = _KEPT_NAMES(prim, *avals, **params)
    if kept and params["name"] in (FLASH_OUT_NAME, FLASH_LSE_NAME):
        flash_schedules.record_kept()
    return kept


def recomputed(block_cls):
    """``block_cls`` under per-block recomputation (``nn.remat``): the
    backward pass runs the block's forward again and keeps nothing of it
    but what the block names, wherever it has them: its experts' choice
    (``route_topk``: chosen again on values rounded elsewhere it is not
    always the same choice) and the flash forward kernel's result and row
    statistics (``ops/pallas/flash.py``: all its backward needs beside q, k
    and v, so the kernel runs once a layer and step; 0.4-1.2 GB a step in
    the benchmark's cells). The names are values of the block's own
    program, outside every ``custom_vjp``: the policy is asked about the
    equations of that program alone and never sees inside a forward rule.
    ``flash_schedules`` counts the flash results kept."""
    return nn.remat(block_cls, policy=_kept_by_a_recomputed_block)


def _head_lanes(h: int, d: int, dtype) -> jnp.ndarray:
    """``(h, h * d)``: 1 where lane ``j`` of the packed view belongs to
    head ``h``, else 0. A product with it repeats each head's value over
    the head's ``d`` lanes; one with its transpose sums each head's lanes.
    A single 1 a column, so both are exact in bfloat16."""
    lanes = jax.lax.broadcasted_iota(jnp.int32, (h, h * d), 1) // d
    heads = jax.lax.broadcasted_iota(jnp.int32, (h, h * d), 0)
    return (lanes == heads).astype(dtype)


@jax.custom_vjp
def _gated(o: jnp.ndarray, gate: jnp.ndarray) -> jnp.ndarray:
    b, t, h, d = o.shape
    return o.reshape(b, t, h * d) * (gate @ _head_lanes(h, d, gate.dtype))


def _gated_fwd(o, gate):
    return _gated(o, gate), (o, gate)


def _gated_bwd(residuals, g):
    o, gate = residuals
    b, t, h, d = o.shape
    lanes = _head_lanes(h, d, gate.dtype)
    do = g * (gate @ lanes)
    dgate = jax.lax.dot_general(g * o.reshape(b, t, h * d), lanes,
                                (((2,), (1,)), ((), ())))
    # The flash backward reads ``do`` packed in its kernel and as (B, T, H,
    # D) in ``delta``, a re-tile apart. Behind the barrier that re-tile is
    # of the bfloat16 ``do``; without it the compiler cast ``do`` to
    # float32 for ``delta`` first and re-tiled twice the bytes.
    return jax.lax.optimization_barrier(do.reshape(b, t, h, d)), dgate


_gated.defvjp(_gated_fwd, _gated_bwd)


def gate_heads(o: jnp.ndarray, gate: jnp.ndarray) -> jnp.ndarray:
    """``o`` (B, T, H, D), the attention's result, times one gate a head
    ``gate`` (B, T, H), as the packed ``(B, T, H * D)`` that the output
    projection reads. The gate is repeated over its head's lanes by a
    product with :func:`_head_lanes`, and its gradient is the same
    product's transpose: neither builds a ``(B, T, H, D)`` value, which cost
    the compiled step a re-tile of the result, a broadcast of the gate and
    a multiply of their own, forward, recomputed and backward. The same
    bfloat16 products as ``o * gate[..., None]``.
    ``utils.profiling.head_gate_sites`` counts the calls."""
    head_gate_sites.record(o.shape[-1])
    return _gated(o, gate)


class GatedAttention(nn.Module):
    """q, kv projections -> rotary -> causal (windowed) grouped-query
    attention -> one sigmoid output gate a head -> output projection."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    window: Optional[int]
    rope: Any  # one kind's rope parameters (a dict)
    depth: int  # of the model: models/moe.py residual_init
    attention: str = "auto"
    compute_dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, u: jnp.ndarray) -> jnp.ndarray:
        b, t, c = u.shape
        h, kv, d = self.num_heads, self.num_kv_heads, self.head_dim

        def dense(n, name, **kw):
            return nn.Dense(n, use_bias=False, dtype=self.compute_dtype,
                            name=name, **kw)

        q = dense(h * d, "q")(u).reshape(b, t, h, d)
        k, v = jnp.split(
            dense(2 * kv * d, "kv")(u).reshape(b, t, 2 * kv, d), 2, axis=2)
        with jax.named_scope("rope"):
            inv_freq, factor = rope_frequencies(d, dict(self.rope))
            q = apply_rope(q, inv_freq, factor)
            k = apply_rope(k, inv_freq, factor)
        o = attend(q, k, v, window=self.window, attention=self.attention)
        o = gate_heads(o.astype(self.compute_dtype),
                       nn.sigmoid(dense(h, "gate")(u)))
        return dense(c, "proj", kernel_init=residual_init(self.depth))(o)


def yarn_softmax_scale(qk_dim: int, rope: dict) -> float:
    """``qk_dim ** -0.5 * mscale ** 2`` with ``mscale = 0.1 * mscale_all_dim
    * ln(factor) + 1``: the softmax scale of latent attention under YaRN
    (the ``deepseek_v3`` modelling code folds the factor that YaRN puts on
    cos and sin of both sides into the scale, where a whole head is not
    rotated)."""
    mscale = 0.1 * rope.get("mscale_all_dim", 0.0) \
        * math.log(rope.get("factor", 1.0)) + 1.0
    return qk_dim ** -0.5 * mscale * mscale


class LatentAttention(nn.Module):
    """Multi-head latent attention (arXiv:2405.04434, section 2.1) with no
    query latent: keys and values come from one ``kv_rank``-wide latent a
    token, normed and projected up per head; one ``rope_dim``-wide rotary
    key a token is given to every head beside the head's ``nope_dim``
    un-rotated dimensions; an optional RMSNorm over each head's query and
    key (one scale for all heads) before the rotary; an element-wise
    sigmoid output gate. Keys and values of the core are ``nope_dim +
    rope_dim`` and ``v_dim`` wide, one key-value head a query head: where
    the two widths are equal the flash kernels take it as it is.

    Scopes ``mla/{q, kv_a, kv_b, rope, gate, proj}`` hold what is not the
    core, which stays under ``attn_core/full``."""

    num_heads: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    kv_rank: int
    rope: Any  # rope parameters (a dict), over the ``rope_dim`` dimensions
    depth: int  # of the model: models/moe.py residual_init
    qk_norm: bool = True
    gated: bool = True
    rms_eps: float = 1e-6
    attention: str = "auto"
    compute_dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, u: jnp.ndarray) -> jnp.ndarray:
        b, t, c = u.shape
        h, nope, rot, dv = (self.num_heads, self.nope_dim, self.rope_dim,
                            self.v_dim)
        rope = dict(self.rope)

        def dense(n, name, **kw):
            return nn.Dense(n, use_bias=False, dtype=self.compute_dtype,
                            name=name, **kw)

        norm = partial(RMSNorm, self.rms_eps, self.compute_dtype)
        with jax.named_scope("mla/q"):
            q = dense(h * (nope + rot), "q")(u).reshape(b, t, h, nope + rot)
        with jax.named_scope("mla/kv_a"):
            c_kv, k_rope = jnp.split(
                dense(self.kv_rank + rot, "kv_a")(u), [self.kv_rank], axis=-1)
            c_kv = norm(name="kv_norm")(c_kv)
        with jax.named_scope("mla/kv_b"):
            k_nope, v = jnp.split(
                dense(h * (nope + dv), "kv_b")(c_kv).reshape(
                    b, t, h, nope + dv), [nope], axis=-1)
            k = jnp.concatenate([k_nope, jnp.broadcast_to(
                k_rope[:, :, None, :], (b, t, h, rot))], axis=-1)
        if self.qk_norm:
            with jax.named_scope("mla/q"):
                q = norm(name="q_norm")(q)
            with jax.named_scope("mla/kv_b"):
                k = norm(name="k_norm")(k)
        with jax.named_scope("mla/rope"):
            inv_freq, factor = rope_frequencies(rot, rope)
            q, k = (jnp.concatenate(
                [x[..., :nope],
                 apply_rope_interleaved(x[..., nope:], inv_freq, factor)],
                axis=-1) for x in (q, k))
        o = attend(q, k, v, window=None, attention=self.attention,
                   scale=yarn_softmax_scale(nope + rot, rope))
        o = o.astype(self.compute_dtype).reshape(b, t, h * dv)
        if self.gated:
            with jax.named_scope("mla/gate"):
                o = o * nn.sigmoid(dense(h * dv, "gate")(u))
        with jax.named_scope("mla/proj"):
            return dense(c, "proj", kernel_init=residual_init(self.depth))(o)


class DecoderBlock(nn.Module):
    """Pre-norm block: RMSNorm -> attention -> residual; RMSNorm -> dense
    or sparse MLP -> residual."""

    attn: Any  # GatedAttention's fields, as (name, value) pairs
    mlp_kind: str
    dense_mlp_size: int
    experts: Any  # SparseExperts' fields, as pairs
    depth: int  # of the model
    rms_eps: float
    compute_dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        norm = partial(RMSNorm, self.rms_eps, self.compute_dtype)
        x = x + GatedAttention(
            **dict(self.attn), depth=self.depth,
            compute_dtype=self.compute_dtype, name="attn",
        )(norm(name="ln1")(x))
        u = norm(name="ln2")(x)
        if self.mlp_kind == DENSE:
            y = SwiGLU(self.dense_mlp_size, self.depth, self.compute_dtype,
                       name="mlp")(u)
        elif self.mlp_kind == SPARSE:
            y = SparseExperts(
                **dict(self.experts), depth=self.depth,
                compute_dtype=self.compute_dtype, name="moe")(u)
        else:
            raise ValueError(f"unknown mlp kind {self.mlp_kind!r}")
        return x + y


def _frozen(value):
    """Lists and dicts of a JSON configuration as hashable tuples, which a
    flax module's fields have to be."""
    if isinstance(value, dict):
        return tuple((k, _frozen(v)) for k, v in value.items())
    if isinstance(value, (list, tuple)):
        return tuple(_frozen(v) for v in value)
    return value


@register_model("laguna")
class Decoder(nn.Module):
    """tokens (B, T) -> logits (B, T, vocab_size) in float32."""

    vocab_size: int = 256
    hidden_size: int = 64
    head_dim: int = 16
    num_kv_heads: int = 2
    layer_types: Sequence[str] = (FULL, WINDOW, WINDOW, WINDOW, FULL)
    heads_per_layer: Sequence[int] = (4, 6, 6, 6, 4)
    mlp_layer_types: Sequence[str] = (DENSE, SPARSE, SPARSE, SPARSE, SPARSE)
    window: int = 8
    rope: Any = None  # {kind: rope parameters}; None = TINY_ROPE
    dense_mlp_size: int = 256
    expert_size: int = 32
    shared_expert_size: int = 32
    num_experts: int = 16
    top_k: int = 4
    experts_held: Optional[Sequence[int]] = None  # (first, count); None: all
    routed_scale: float = 2.5
    rms_eps: float = 1e-6
    attention: str = "auto"  # 'flash', 'dense', or flash on a TPU
    compute_dtype: jnp.dtype = jnp.bfloat16
    remat: bool = False
    # The train step reads the expert layers' routing counters
    # (train/state.py ``TrainState.counters``).
    counters = True

    def __post_init__(self):
        for name in ("layer_types", "heads_per_layer", "mlp_layer_types",
                     "rope", "experts_held"):
            object.__setattr__(self, name, _frozen(getattr(self, name)))
        super().__post_init__()

    @nn.compact
    def __call__(self, tokens: jnp.ndarray, *, train: bool = False):
        del train
        depth = len(self.layer_types)
        if not depth == len(self.heads_per_layer) == len(self.mlp_layer_types):
            raise ValueError(
                "layer_types, heads_per_layer and mlp_layer_types give the "
                f"depth {depth}, {len(self.heads_per_layer)} and "
                f"{len(self.mlp_layer_types)}")
        rope = dict(self.rope or _frozen(TINY_ROPE))
        # Initialisation: embeddings of unit variance and small matrices
        # where a block writes into the residual stream (``residual_init``),
        # so that at the seed a token's hidden state is mostly its own
        # embedding: the routers then spread tokens over the experts as a
        # trained model's do.
        x = nn.Embed(self.vocab_size, self.hidden_size,
                     embedding_init=nn.initializers.normal(stddev=1.0),
                     dtype=self.compute_dtype, name="embed")(
            tokens.astype(jnp.int32))
        # A recomputed block keeps its experts' choice and its flash
        # kernel's results (``recomputed``).
        block_cls = recomputed(DecoderBlock) if self.remat else DecoderBlock
        experts = _frozen(dict(
            num_experts=self.num_experts, top_k=self.top_k,
            width=self.expert_size, shared_width=self.shared_expert_size,
            experts_held=self.experts_held, routed_scale=self.routed_scale))
        for i, (kind, heads, mlp_kind) in enumerate(zip(
                self.layer_types, self.heads_per_layer,
                self.mlp_layer_types)):
            attn = _frozen(dict(
                num_heads=heads, num_kv_heads=self.num_kv_heads,
                head_dim=self.head_dim,
                window=self.window if kind == WINDOW else None,
                rope=rope[kind], attention=self.attention))
            x = block_cls(
                attn=attn, mlp_kind=mlp_kind,
                dense_mlp_size=self.dense_mlp_size, experts=experts,
                depth=depth, rms_eps=self.rms_eps,
                compute_dtype=self.compute_dtype, name=f"block{i}")(x)
        x = RMSNorm(self.rms_eps, self.compute_dtype, name="ln_f")(x)
        # bf16 operands, float32 result: the loss reads float32 logits.
        return nn.Dense(
            self.vocab_size, use_bias=False, dtype=self.compute_dtype,
            dot_general=partial(jax.lax.dot_general,
                                preferred_element_type=jnp.float32),
            name="head")(x)
