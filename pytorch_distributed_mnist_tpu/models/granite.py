"""Granite 4.0 hybrid token model: registry model ``granite_hybrid``, the
architecture of granite-4.0-h-micro
(https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json,
``model_type: granitemoehybrid`` with no experts): Mamba-2 layers
(arXiv:2405.21060) and, one in ten, grouped-query attention with no
positional encoding, a SwiGLU MLP in every layer, RMSNorm, and the
family's four multipliers.

    x_0 = e Emb(t);   x <- x + r Mix_l(n(x));   x <- x + r MLP(n(x))
    logits = n(x_L) Emb^T / s

with ``e`` the ``embedding_multiplier`` (12), ``r`` the
``residual_multiplier`` (0.22), ``s`` the ``logits_scaling`` (8), ``n`` an
RMSNorm with a learnt scale and ``MLP(u) = (silu(g) * v) W_down``, ``[g |
v] = u W_up``. ``Mix_l`` by ``layer_types``:

- ``mamba``: ``[z | c | r] = u W_in`` (inner | inner + 2 N | heads); ``c =
  silu(conv(c))``, a causal depthwise convolution with bias over ``x``,
  ``B`` and ``C`` together; ``[x | B | C] = c``, ``x`` as heads of
  ``mamba_d_head``, ``B`` and ``C`` one group for all heads; ``dt =
  softplus(r + dt_bias)``, ``A = -exp(A_log)``, one number a head; ``y =
  ssd_scan(x, dt, A, B, C, D)`` (``ops/ssd.py``: the recurrence in its
  chunked matrix-product form); ``Mix = n_inner(y * silu(z)) W_out``: the
  gate comes before the norm, which runs over all inner channels.
- ``attention``: ``q, k, v = u W_q, u W_k, u W_v``, no bias, no rotary and
  no positional term of any kind; ``o = softmax_causal(a q k^T) v`` with
  ``a`` the ``attention_multiplier`` (1/64 at heads of 64: not ``D ** -0.5``)
  through ``decoder.attend`` (the flash kernels on a TPU); ``Mix = o W_o``.

The head is the embedding, tied. ``vocab_size`` may be the chip's slice of
the vocabulary.

It is a file of its own beside ``models/sambay.py``, whose block is one of
five mixers round LayerNorms with values crossing blocks: what the two
share is imported, not written twice (``causal_conv``, ``GatedMLP`` and
Mamba's initialisers from there; ``RMSNorm``, ``attend`` and ``recomputed``
from ``models/decoder.py``).

The defaults are a tiny preset of four layers, one of them attention, that
trains on the CPU from the command line (``--model granite_hybrid
--dataset synthetic_tokens``); the benchmark's configuration passes the
published widths. bfloat16 compute, float32 parameters, norms and logits,
and in the scan float32 ``dt``, ``A``, decay and state (``r`` is its own
float32-accumulated product, not a slice of the bfloat16 one).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

from pytorch_distributed_mnist_tpu.models.decoder import (
    RMSNorm,
    _frozen,
    attend,
    recomputed,
)
from pytorch_distributed_mnist_tpu.models.moe import residual_init
from pytorch_distributed_mnist_tpu.models.registry import register_model
from pytorch_distributed_mnist_tpu.models.sambay import (
    GatedMLP,
    _conv_init,
    _dt_bias_init,
    causal_conv,
)
from pytorch_distributed_mnist_tpu.ops.ssd import ssd_scan

MAMBA, ATTENTION = "mamba", "attention"
KINDS = (MAMBA, ATTENTION)


def _a_log_init(key, shape, dtype=jnp.float32):
    """``A = -a`` with ``a`` uniform in [1, 16] (Mamba-2's own)."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


class Mamba2(nn.Module):
    """``u`` (B, T, hidden) -> ``Mix`` (module docstring), scopes
    ``ssd/{in_proj, conv, dt, scan, norm, out_proj}``."""

    n_heads: int
    d_head: int
    d_state: int
    d_conv: int
    depth: int  # of the model: residual_init
    eps: float
    compute_dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, u):
        b, t, hidden = u.shape
        h, n = self.n_heads, self.d_state
        inner = h * self.d_head
        conv = inner + 2 * n
        with jax.named_scope("in_proj"):
            w_in = self.param("in_proj", nn.initializers.lecun_normal(),
                              (hidden, inner + conv + h))
            w_in = w_in.astype(self.compute_dtype)
            zc = u @ w_in[:, :inner + conv]
            # dt's own columns: bf16 operands, float32 result
            r = jax.lax.dot_general(
                u, w_in[:, inner + conv:], (((2,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        z, c = zc[..., :inner], zc[..., inner:]
        with jax.named_scope("conv"):
            c = nn.silu(causal_conv(
                c, self.param("conv_kernel", _conv_init, (self.d_conv, conv)),
                self.param("conv_bias", nn.initializers.zeros, (conv,))))
        with jax.named_scope("dt"):
            dt = nn.softplus(r + self.param("dt_bias", _dt_bias_init, (h,)))
        a_log = self.param("A_log", _a_log_init, (h,))
        skip = self.param("D", nn.initializers.ones, (h,))
        with jax.named_scope("scan"):
            y = ssd_scan(c[..., :inner].reshape(b, t, h, self.d_head), dt,
                         -jnp.exp(a_log), c[..., inner:inner + n],
                         c[..., inner + n:], skip)
        gated = y.reshape(b, t, inner) * nn.silu(z)
        gated = RMSNorm(self.eps, self.compute_dtype, name="norm")(gated)
        return nn.Dense(hidden, use_bias=False, dtype=self.compute_dtype,
                        kernel_init=residual_init(self.depth),
                        name="out_proj")(gated)


class NopeAttention(nn.Module):
    """Causal grouped-query attention with no positional encoding and a
    softmax scale of its own: q, k, v projections -> ``attend`` -> output
    projection."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    scale: float
    depth: int  # of the model: residual_init
    attention: str = "auto"
    compute_dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, u):
        b, t, hidden = u.shape
        h, kv, d = self.num_heads, self.num_kv_heads, self.head_dim

        def dense(size, name, **kw):
            return nn.Dense(size, use_bias=False, dtype=self.compute_dtype,
                            name=name, **kw)

        q = dense(h * d, "q")(u).reshape(b, t, h, d)
        k = dense(kv * d, "k")(u).reshape(b, t, kv, d)
        v = dense(kv * d, "v")(u).reshape(b, t, kv, d)
        o = attend(q, k, v, window=None, attention=self.attention,
                   scale=self.scale)
        return dense(hidden, "proj", kernel_init=residual_init(self.depth))(
            o.astype(self.compute_dtype).reshape(b, t, h * d))


class HybridBlock(nn.Module):
    """``x <- x + r Mix(n(x)); x <- x + r MLP(n(x))``."""

    kind: str
    mixer: Any  # the kind's own fields, as pairs
    mlp_size: int
    depth: int
    residual_multiplier: float
    eps: float
    compute_dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        norm = partial(RMSNorm, self.eps, self.compute_dtype)
        common = dict(depth=self.depth, compute_dtype=self.compute_dtype)
        u = norm(name="ln1")(x)
        if self.kind == MAMBA:
            mix = Mamba2(**dict(self.mixer), eps=self.eps, **common,
                         name="ssd")(u)
        else:  # ATTENTION: the model refuses any other kind
            mix = NopeAttention(**dict(self.mixer), **common, name="attn")(u)

        def branch(y):  # r is no bfloat16 number (0.22 would be 0.2197)
            return (y.astype(jnp.float32) * self.residual_multiplier).astype(
                self.compute_dtype)

        x = x + branch(mix)
        return x + branch(GatedMLP(self.mlp_size, **common, name="mlp")(
            norm(name="ln2")(x)))


@register_model("granite_hybrid")
class GraniteHybrid(nn.Module):
    """tokens (B, T) -> logits (B, T, vocab_size) in float32."""

    vocab_size: int = 256
    hidden_size: int = 64
    num_heads: int = 4
    num_kv_heads: int = 2
    head_dim: int = 16
    mlp_size: int = 128
    layer_types: Sequence[str] = (MAMBA, MAMBA, ATTENTION, MAMBA)
    mamba_n_heads: int = 4
    mamba_d_head: int = 32
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0
    rms_eps: float = 1e-5
    attention: str = "auto"  # 'flash', 'dense', or flash on a TPU
    compute_dtype: jnp.dtype = jnp.bfloat16
    remat: bool = False

    def __post_init__(self):
        object.__setattr__(self, "layer_types", _frozen(self.layer_types))
        super().__post_init__()

    @nn.compact
    def __call__(self, tokens: jnp.ndarray, *, train: bool = False):
        del train
        kinds = tuple(self.layer_types)
        depth = len(kinds)
        # The head is the embedding (``models/sambay.py`` has why 0.02).
        embed = nn.Embed(self.vocab_size, self.hidden_size,
                         embedding_init=nn.initializers.normal(stddev=0.02),
                         dtype=self.compute_dtype, name="embed")
        x = embed(tokens.astype(jnp.int32)) * jnp.asarray(
            self.embedding_multiplier, self.compute_dtype)
        # A recomputed block keeps its flash kernel's results
        # (``decoder.recomputed``); the scan and the MLP are computed again.
        block_cls = recomputed(HybridBlock) if self.remat else HybridBlock
        mixers = {
            MAMBA: dict(n_heads=self.mamba_n_heads, d_head=self.mamba_d_head,
                        d_state=self.mamba_d_state, d_conv=self.mamba_d_conv),
            ATTENTION: dict(num_heads=self.num_heads,
                            num_kv_heads=self.num_kv_heads,
                            head_dim=self.head_dim,
                            scale=self.attention_multiplier,
                            attention=self.attention),
        }
        for i, kind in enumerate(kinds):
            if kind not in mixers:
                raise ValueError(
                    f"unknown layer kind {kind!r}; known: {KINDS}")
            x = block_cls(
                kind=kind, mixer=_frozen(mixers[kind]),
                mlp_size=self.mlp_size, depth=depth,
                residual_multiplier=self.residual_multiplier,
                eps=self.rms_eps, compute_dtype=self.compute_dtype,
                name=f"block{i}")(x)
        x = RMSNorm(self.rms_eps, self.compute_dtype, name="ln_f")(x)
        # bf16 operands, float32 result: the loss reads float32 logits.
        with jax.named_scope("head"):
            logits = jax.lax.dot_general(
                x, embed.embedding.astype(self.compute_dtype),
                (((x.ndim - 1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            return logits / self.logits_scaling

