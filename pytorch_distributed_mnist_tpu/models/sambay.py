"""SambaY decoder-hybrid-decoder token model (arXiv:2507.06607): registry
model ``sambay``, the architecture of Phi-4-mini-flash-reasoning
(https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/blob/main/config.json).

Every block is ``h = x + Mix(LN1(x)); y = h + MLP(LN2(h))`` with LayerNorm
(scale and bias) and a SwiGLU MLP whose gate and value come out of one
matrix; the mixer is one of five kinds, chosen by ``layer_types``:

- ``mamba``: Mamba-1. ``[a, z] = u W_in``; ``a = silu(conv(a))``, a causal
  depthwise convolution with bias; ``[r, B, C] = a W_x``; ``dt =
  softplus(r W_dt + b_dt)``; ``m = selective_scan(a, dt, -exp(A_log), B, C,
  D)`` (``ops/ssm.py``); ``Mix = (m * silu(z)) W_out``. The **last** Mamba
  layer also publishes ``m``, the memory ``M``.
- ``sliding_attention`` and ``full_attention``: differential attention.
  ``[q, k, v] = u W_qkv + b``; heads are taken in pairs, ``(q1, q2)``,
  ``(k1, k2)``, and a value pair is one head of twice the width; two query
  pairs read one key-value pair. ``A_i = softmax(q_i k_i^T / sqrt(D) +
  mask)``, causal and, sliding, within ``window`` positions; ``o = (1 -
  lambda_init) RMSNorm(A_1 v - lambda A_2 v)`` with ``lambda = exp(lq1 .
  lk1) - exp(lq2 . lk2) + lambda_init`` and ``lambda_init = 0.8 - 0.6
  exp(-0.3 l)`` at the **published** layer index ``l`` (``layer_ids``);
  ``Mix = concat(o) W_o + b_o``. The last full layer also publishes its
  keys and values, ``K*`` and ``V*``.
- ``gmu``: a Gated Memory Unit, ``Mix = (M * silu(u W_1)) W_2``; no scan
  runs here.
- ``cross_attention``: the same differential attention with queries of its
  own only, over ``K*`` and ``V*``, causal over the same positions.

No positional encoding of any kind; the head is the embedding, tied.

``M``, ``K*`` and ``V*`` leave their block as outputs and enter later
blocks as inputs, so per-block recomputation (``remat``) keeps them and
recomputes nothing across blocks.

One score map a query head over values twice as wide as the keys is what
the flash kernels compute where queries and keys are laid out at the
values' width, the upper half zeros (``_at_value_width``): a head of 128
is the lane-blocked layout ``ops/pallas/flash.py`` reads without a
transpose, and a contraction of 64 leaves half of the v5e's 128-wide
matrix unit idle anyway. The scale stays ``1 / sqrt(D)``.

The defaults are a tiny preset of six layers, one of every kind in the
published order, that trains on the CPU from the command line (``--model
sambay --dataset synthetic_tokens``); the benchmark's configuration passes
the published widths. bfloat16 compute, float32 parameters and logits, and
in the scan float32 ``dt``, ``A``, decay and state.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Optional, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

from pytorch_distributed_mnist_tpu.models.decoder import (
    _frozen,
    attend,
    recomputed,
)
from pytorch_distributed_mnist_tpu.models.moe import residual_init
from pytorch_distributed_mnist_tpu.models.registry import register_model
from pytorch_distributed_mnist_tpu.ops.ssm import selective_scan
from pytorch_distributed_mnist_tpu.utils.profiling import scan_log

MAMBA, WINDOW, FULL = "mamba", "sliding_attention", "full_attention"
GMU, CROSS = "gmu", "cross_attention"
KINDS = (MAMBA, WINDOW, FULL, GMU, CROSS)
# Whose published values a kind of layer reads: the last such layer's.
READS = {GMU: MAMBA, CROSS: FULL}


def lambda_init(layer_id: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * layer_id)


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """``softplus(bias)`` log-uniform in [1e-3, 1e-1] (Mamba's own)."""
    dt = jnp.exp(jax.random.uniform(key, shape, dtype)
                 * (math.log(1e-1) - math.log(1e-3)) + math.log(1e-3))
    return dt + jnp.log(-jnp.expm1(-dt))  # softplus's inverse


def _a_log_init(key, shape, dtype=jnp.float32):
    """``A[c, n] = -(n + 1)``."""
    del key
    return jnp.broadcast_to(
        jnp.log(jnp.arange(1, shape[1] + 1, dtype=dtype)), shape)


def _conv_init(key, shape, dtype=jnp.float32):
    bound = shape[0] ** -0.5  # a depthwise filter's fan-in is its width
    return jax.random.uniform(key, shape, dtype, -bound, bound)


def causal_conv(x, kernel, bias):
    """Depthwise causal convolution of ``x`` (B, T, C) with ``kernel`` (W,
    C): ``y_t = sum_j kernel[j] x_{t - W + 1 + j} + bias``."""
    width, t = kernel.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    return sum(padded[:, j:j + t] * kernel[j].astype(x.dtype)
               for j in range(width)) + bias.astype(x.dtype)


class Mamba(nn.Module):
    """``u`` (B, T, hidden) -> ``(Mix, m)``: the block's mixer output and
    the scan's output before the gate."""

    d_inner: int
    d_state: int
    d_conv: int
    dt_rank: int
    depth: int  # of the model: residual_init
    compute_dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, u):
        n, r = self.d_state, self.dt_rank

        def dense(size, name, **kw):
            return nn.Dense(size, use_bias=False, dtype=self.compute_dtype,
                            name=name, **kw)

        a, z = jnp.split(dense(2 * self.d_inner, "in_proj")(u), 2, axis=-1)
        with jax.named_scope("conv"):
            a = nn.silu(causal_conv(
                a,
                self.param("conv_kernel", _conv_init,
                           (self.d_conv, self.d_inner)),
                self.param("conv_bias", nn.initializers.zeros,
                           (self.d_inner,))))
        rbc = dense(r + 2 * n, "x_proj")(a)
        with jax.named_scope("dt"):
            # bf16 operands, float32 result, bias and softplus
            dt = nn.softplus(dense(
                self.d_inner, "dt_proj",
                dot_general=partial(jax.lax.dot_general,
                                    preferred_element_type=jnp.float32),
            )(rbc[..., :r]).astype(jnp.float32)
                + self.param("dt_bias", _dt_bias_init, (self.d_inner,)))
        a_log = self.param("A_log", _a_log_init, (self.d_inner, n))
        skip = self.param("D", nn.initializers.ones, (self.d_inner,))
        with jax.named_scope("scan"):
            m = selective_scan(a, dt, -jnp.exp(a_log), rbc[..., r:r + n],
                               rbc[..., r + n:], skip)
        with jax.named_scope("gate"):
            gated = m * nn.silu(z)
        return dense(u.shape[-1], "out_proj",
                     kernel_init=residual_init(self.depth))(gated), m


def _at_value_width(x, width: int):
    """Heads of ``x`` (B, T, H, D) laid out ``width`` wide, zeros behind
    (module docstring)."""
    return jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, width - x.shape[-1])))


class DiffAttention(nn.Module):
    """Differential attention, self or cross (module docstring). ``u`` (B,
    T, hidden) and, for a cross layer, ``kv`` = ``(K*, V*)`` each (B, T,
    kv_heads * head_dim) -> ``(Mix, (k, v))``."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    window: Optional[int]
    layer_id: int  # published index: lambda_init
    depth: int  # of the model: residual_init
    cross: bool = False
    attention: str = "auto"
    eps: float = 1e-5
    compute_dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, u, kv=None):
        b, t, c = u.shape
        h, g, d = self.num_heads, self.num_kv_heads // 2, self.head_dim
        per_group = h // 2 // g  # query pairs a key-value pair

        def dense(size, name, **kw):
            return nn.Dense(size, dtype=self.compute_dtype, name=name, **kw)

        if self.cross:
            q = dense(h * d, "q")(u)
            k, v = kv
        else:
            q, k, v = jnp.split(dense((h + 4 * g) * d, "qkv")(u),
                                (h * d, (h + 2 * g) * d), axis=-1)
        published = (k, v)
        # One score map a query head: maps (g, i, p), i = which of the pair,
        # read key head (g, i) and value pair g (repeated for both i), so
        # that map h' reads key-value head h' // per_group.
        q = q.reshape(b, t, g, per_group, 2, d).transpose(0, 1, 2, 4, 3, 5)
        q = _at_value_width(q.reshape(b, t, h, d), 2 * d)
        k = _at_value_width(k.reshape(b, t, 2 * g, d), 2 * d)
        v = jnp.repeat(v.reshape(b, t, g, 2 * d), 2, axis=2)
        o = attend(q, k, v, window=self.window, attention=self.attention,
                   scale=d ** -0.5, cross=self.cross)
        with jax.named_scope("diff"):
            o = o.reshape(b, t, g, 2, per_group, 2 * d).astype(jnp.float32)
            lam = [self.param(name, nn.initializers.normal(stddev=0.1), (d,))
                   for name in ("lq1", "lk1", "lq2", "lk2")]
            start = lambda_init(self.layer_id)
            weight = jnp.exp(jnp.sum(lam[0] * lam[1])) \
                - jnp.exp(jnp.sum(lam[2] * lam[3])) + start
            o = o[:, :, :, 0] - weight * o[:, :, :, 1]  # (B,T,g,pairs,2D)
            scale = self.param("subln", nn.initializers.ones, (2 * d,))
            o = o * jax.lax.rsqrt(
                jnp.mean(jnp.square(o), axis=-1, keepdims=True) + self.eps)
            o = ((1.0 - start) * scale * o).astype(self.compute_dtype)
        return dense(c, "proj", kernel_init=residual_init(self.depth))(
            o.reshape(b, t, h * d)), published


class GatedMLP(nn.Module):
    """``(silu(g) * v) W_2`` with ``[g, v] = u W_1``, no biases."""

    width: int
    depth: int
    compute_dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, u):
        def dense(size, name, **kw):
            return nn.Dense(size, use_bias=False, dtype=self.compute_dtype,
                            name=name, **kw)

        g, v = jnp.split(dense(2 * self.width, "gate_up")(u), 2, axis=-1)
        return dense(u.shape[-1], "down",
                     kernel_init=residual_init(self.depth))(nn.silu(g) * v)


class GatedMemoryUnit(nn.Module):
    """``(M * silu(u W_1)) W_2``."""

    depth: int
    compute_dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, u, memory):
        def dense(size, name, **kw):
            return nn.Dense(size, use_bias=False, dtype=self.compute_dtype,
                            name=name, **kw)

        gate = nn.silu(dense(memory.shape[-1], "in_proj")(u))
        return dense(u.shape[-1], "out_proj",
                     kernel_init=residual_init(self.depth))(memory * gate)


class SambaYBlock(nn.Module):
    """``(x, shared) -> (y, published)``: ``shared`` is what the mixer
    reads of earlier layers (``(M,)`` for ``gmu``, ``(K*, V*)`` for
    ``cross_attention``, else ``()``) and ``published`` what it hands to
    later ones where ``publishes`` (``(m,)`` or ``(k, v)``, else ``()``)."""

    kind: str
    publishes: bool
    layer_id: int
    depth: int
    mixer: Any  # the kind's own fields, as pairs
    mlp_size: int
    eps: float
    compute_dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x, shared):
        norm = partial(nn.LayerNorm, epsilon=self.eps,
                       dtype=self.compute_dtype)
        common = dict(depth=self.depth, compute_dtype=self.compute_dtype)
        u = norm(name="ln1")(x)
        published = ()
        if self.kind == MAMBA:
            mix, m = Mamba(**dict(self.mixer), **common, name="ssm")(u)
            published = (m,)
        elif self.kind in (WINDOW, FULL, CROSS):
            cross = self.kind == CROSS
            if cross:
                scan_log.record_reader("kv")
            mix, published = DiffAttention(
                **dict(self.mixer), layer_id=self.layer_id, cross=cross,
                eps=self.eps, **common, name="attn",
            )(u, shared if cross else None)
        elif self.kind == GMU:
            scan_log.record_reader("memory")
            mix = GatedMemoryUnit(**common, name="gmu")(u, *shared)
        else:
            raise ValueError(
                f"unknown layer kind {self.kind!r}; known: {KINDS}")
        h = x + mix
        y = h + GatedMLP(self.mlp_size, **common, name="mlp")(
            norm(name="ln2")(h))
        return y, published if self.publishes else ()


@register_model("sambay")
class SambaY(nn.Module):
    """tokens (B, T) -> logits (B, T, vocab_size) in float32."""

    vocab_size: int = 256
    hidden_size: int = 64
    num_heads: int = 4
    num_kv_heads: int = 2
    head_dim: int = 16
    mlp_size: int = 128
    layer_types: Sequence[str] = (MAMBA, WINDOW, MAMBA, FULL, GMU, CROSS)
    layer_ids: Optional[Sequence[int]] = None  # published indices; 0, 1, ..
    window: int = 8
    d_inner: int = 128
    d_state: int = 4
    d_conv: int = 4
    dt_rank: int = 4
    layer_norm_eps: float = 1e-5
    attention: str = "auto"  # 'flash', 'dense', or flash on a TPU
    compute_dtype: jnp.dtype = jnp.bfloat16
    remat: bool = False

    def __post_init__(self):
        for name in ("layer_types", "layer_ids"):
            object.__setattr__(self, name, _frozen(getattr(self, name)))
        super().__post_init__()

    @nn.compact
    def __call__(self, tokens: jnp.ndarray, *, train: bool = False):
        del train
        kinds = tuple(self.layer_types)
        depth = len(kinds)
        ids = tuple(self.layer_ids or range(depth))
        if len(ids) != depth:
            raise ValueError(f"layer_ids names {len(ids)} layers, "
                             f"layer_types {depth}")
        # Who publishes: the last Mamba layer its scan output, the last
        # full layer its keys and values.
        last = {kind: max((i for i, k in enumerate(kinds) if k == kind),
                          default=None) for kind in (MAMBA, FULL)}
        # GPT-2's initialisation: the head is the embedding, so an embedding
        # of unit variance (the ``laguna`` decoder's) would give every
        # token's own id a logit of ``hidden_size`` at the seed.
        embed = nn.Embed(self.vocab_size, self.hidden_size,
                         embedding_init=nn.initializers.normal(stddev=0.02),
                         dtype=self.compute_dtype, name="embed")
        x = embed(tokens.astype(jnp.int32))
        # A recomputed block keeps its flash kernel's results
        # (``decoder.recomputed``); the scan, the combine and the MLP are
        # computed again.
        block_cls = recomputed(SambaYBlock) if self.remat else SambaYBlock
        attn = dict(num_heads=self.num_heads, num_kv_heads=self.num_kv_heads,
                    head_dim=self.head_dim, attention=self.attention)
        mixers = {
            MAMBA: dict(d_inner=self.d_inner, d_state=self.d_state,
                        d_conv=self.d_conv, dt_rank=self.dt_rank),
            WINDOW: dict(attn, window=self.window),
            FULL: dict(attn, window=None),
            CROSS: dict(attn, window=None),
            GMU: {},
        }
        shared = {MAMBA: None, FULL: None}
        for i, (kind, layer_id) in enumerate(zip(kinds, ids)):
            reads = READS.get(kind)
            if reads is not None and shared[reads] is None:
                raise ValueError(
                    f"layer {i} ({kind}) reads what the last {reads} layer "
                    f"publishes, and that layer comes later or not at all")
            x, published = block_cls(
                kind=kind, publishes=last.get(kind) == i, layer_id=layer_id,
                depth=depth, mixer=_frozen(mixers[kind]),
                mlp_size=self.mlp_size, eps=self.layer_norm_eps,
                compute_dtype=self.compute_dtype, name=f"block{i}",
            )(x, shared[reads] if reads else ())
            if published:
                shared[kind] = published
        x = nn.LayerNorm(epsilon=self.layer_norm_eps,
                         dtype=self.compute_dtype, name="ln_f")(x)
        # bf16 operands, float32 result: the loss reads float32 logits.
        with jax.named_scope("head"):
            return jax.lax.dot_general(
                x, embed.embedding.astype(self.compute_dtype),
                (((x.ndim - 1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
