"""Latent-attention sparse decoder with a multi-token-prediction module:
registry model ``instella``.

A causal language model of the ``deepseek_v3`` kind as
Instella-MoE-16B-A3B-Base configures it
(https://huggingface.co/amd/Instella-MoE-16B-A3B-Base/blob/main/config.json;
arXiv:2405.04434 for the attention, arXiv:2412.19437 for the routing, the
selection bias and the module), built from the decoder family's parts:
``models/decoder.py LatentAttention`` (keys and values from one latent a
token, a rotary key shared by the heads, QK norm, an element-wise output
gate), ``models/moe.py SparseExperts`` (top-k of ``score + bias``, weights
from the scores alone, the sequence-wise balance term sown as
``aux_loss``) and ``SwiGLU``, ``RMSNorm``, untied embedding and head.

What is this model's own is how the blocks are joined, and that is why it
is a sibling of ``Decoder`` and not a third kind of its attention:

*FarSkip.* With the sub-layers of the trunk numbered ``j = 1 .. 2L``
(attention and MLP of block 0, of block 1, ...), ``o_j = f_j(n_j(x_j))``
and ``s_j = s_{j-1} + o_j`` from the embedding ``s_0``, a sub-layer reads
``x_1 = s_0`` and ``x_j = s_{j-2}``: the stream without the output of the
sub-layer just ahead of it (whose collective a deployment may still have in
flight). So a block takes and hands on two values, ``(s, s_before)``: the
stream and the stream before its last sub-layer wrote, across the
recomputation boundary. ``ln_f`` reads the whole ``s_2L``.
``farskip=False`` is the published residual, ``x_j = s_{j-1}``.

*The module* (``mtp``; one, ``num_nextn_predict_layers`` 1). With ``h_i``
the trunk's output after ``ln_f``: ``h'_i = W_eh [RMSNorm(h_i) ;
RMSNorm(Emb(t_{i+1}))]``, one sparse block of the trunk's kind applying the
rule above to its own two sub-layers from its own input, a final norm of
its own, then the trunk's embedding and head: logits for ``t_{i+2}``.
``t_{i+1}`` is the batch's own tokens shifted by one (the last position,
which has no next token, reads id 0 and is labelled ``IGNORE`` twice over).
In training the model returns ``(logits, mtp_logits)`` and the train step
weighs the second cross-entropy (``train/steps.py``); evaluation and
serving (``train=False``) return the main logits and never build the
module's head.

The selection bias of every sparse layer is a variable of the collection
``router_bias``: ``model.init`` returns it beside ``params``, the train
state carries it as ``buffers`` and the step moves it.

The defaults are a tiny preset that trains on the CPU (``--model instella
--dataset synthetic_tokens``); the benchmark's configuration passes the
published widths. bfloat16 compute, float32 parameters, router, norms,
rotary and logits; ``remat`` recomputes per block.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Optional, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

from pytorch_distributed_mnist_tpu.models.decoder import (
    DENSE,
    SPARSE,
    LatentAttention,
    RMSNorm,
    _frozen,
    recomputed,
)
from pytorch_distributed_mnist_tpu.models.moe import SparseExperts, SwiGLU
from pytorch_distributed_mnist_tpu.models.registry import register_model

# The tiny preset's rotary settings: the published kind at small numbers.
TINY_ROPE = {"rope_type": "yarn", "rope_theta": 10000.0, "factor": 4.0,
             "original_max_position_embeddings": 16, "beta_fast": 4.0,
             "beta_slow": 1.0, "mscale": 1.0, "mscale_all_dim": 1.0,
             "attention_factor": 1.0}


class LatentBlock(nn.Module):
    """``(s, s_before) -> (s', s'_before)``: latent attention and a dense
    or sparse MLP, each adding to the stream ``s``. Under ``farskip`` the
    attention reads ``s_before`` (the stream before the sub-layer ahead of
    it wrote) and the MLP reads ``s`` as it came in; without, each reads
    the stream as it stands."""

    attn: Any  # LatentAttention's fields, as (name, value) pairs
    mlp_kind: str
    dense_mlp_size: int
    experts: Any  # SparseExperts' fields, as pairs
    depth: int  # of the model
    rms_eps: float
    farskip: bool = True
    compute_dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, s: jnp.ndarray, s_before: jnp.ndarray):
        norm = partial(RMSNorm, self.rms_eps, self.compute_dtype)
        after_attn = s + LatentAttention(
            **dict(self.attn), depth=self.depth, rms_eps=self.rms_eps,
            compute_dtype=self.compute_dtype, name="attn",
        )(norm(name="ln1")(s_before if self.farskip else s))
        u = norm(name="ln2")(s if self.farskip else after_attn)
        if self.mlp_kind == DENSE:
            y = SwiGLU(self.dense_mlp_size, self.depth, self.compute_dtype,
                       name="mlp")(u)
        elif self.mlp_kind == SPARSE:
            y = SparseExperts(
                **dict(self.experts), depth=self.depth,
                compute_dtype=self.compute_dtype, name="moe")(u)
        else:
            raise ValueError(f"unknown mlp kind {self.mlp_kind!r}")
        return after_attn + y, after_attn


@register_model("instella")
class Instella(nn.Module):
    """tokens (B, T) -> logits (B, T, vocab_size) in float32; with
    ``train=True`` and the module, ``(logits, mtp_logits)``."""

    vocab_size: int = 256
    hidden_size: int = 64
    num_heads: int = 4
    nope_dim: int = 12
    rope_dim: int = 4
    v_dim: int = 16
    kv_rank: int = 32
    mlp_layer_types: Sequence[str] = (DENSE, SPARSE, SPARSE)
    rope: Any = None  # rope parameters over ``rope_dim``; None = TINY_ROPE
    dense_mlp_size: int = 256
    expert_size: int = 32
    shared_expert_size: int = 64
    num_experts: int = 16
    top_k: int = 4
    experts_held: Optional[Sequence[int]] = None  # (first, count); None: all
    routed_scale: float = 2.5
    selection_bias: bool = True
    balance: bool = True
    qk_norm: bool = True
    gated: bool = True
    farskip: bool = True
    mtp: bool = True  # one multi-token-prediction module
    rms_eps: float = 1e-6
    attention: str = "auto"  # 'flash', 'dense', or flash on a TPU
    compute_dtype: jnp.dtype = jnp.bfloat16
    remat: bool = False
    # The train step reads the expert layers' routing counters
    # (train/state.py ``TrainState.counters``).
    counters = True
    # The source's training settings (arXiv:2412.19437, section 4.2: the
    # balance weight alpha, the module's weight lambda, the bias's rate
    # gamma), which the command line passes to the train step unless told
    # otherwise (``models/registry.py model_objective``).
    objective = {"aux_weight": 1e-4, "mtp_weight": 0.3, "bias_rate": 1e-3}

    def __post_init__(self):
        for name in ("mlp_layer_types", "rope", "experts_held"):
            object.__setattr__(self, name, _frozen(getattr(self, name)))
        super().__post_init__()

    @nn.compact
    def __call__(self, tokens: jnp.ndarray, *, train: bool = False):
        depth = len(self.mlp_layer_types)
        tokens = tokens.astype(jnp.int32)
        # Initialisation as ``Decoder``'s: embeddings of unit variance and
        # small matrices where a block writes into the stream.
        embed = nn.Embed(self.vocab_size, self.hidden_size,
                         embedding_init=nn.initializers.normal(stddev=1.0),
                         dtype=self.compute_dtype, name="embed")
        # bf16 operands, float32 result: the loss reads float32 logits.
        head = nn.Dense(
            self.vocab_size, use_bias=False, dtype=self.compute_dtype,
            dot_general=partial(jax.lax.dot_general,
                                preferred_element_type=jnp.float32),
            name="head")
        norm = partial(RMSNorm, self.rms_eps, self.compute_dtype)
        # A recomputed block keeps its experts' choice and its flash
        # kernel's results (``decoder.recomputed``).
        block_cls = recomputed(LatentBlock) if self.remat else LatentBlock
        block = partial(
            block_cls,
            attn=_frozen(dict(
                num_heads=self.num_heads, nope_dim=self.nope_dim,
                rope_dim=self.rope_dim, v_dim=self.v_dim,
                kv_rank=self.kv_rank, rope=self.rope or _frozen(TINY_ROPE),
                qk_norm=self.qk_norm, gated=self.gated,
                attention=self.attention)),
            dense_mlp_size=self.dense_mlp_size,
            experts=_frozen(dict(
                num_experts=self.num_experts, top_k=self.top_k,
                width=self.expert_size, shared_width=self.shared_expert_size,
                experts_held=self.experts_held,
                routed_scale=self.routed_scale,
                selection_bias=self.selection_bias, balance=self.balance)),
            depth=depth, rms_eps=self.rms_eps, farskip=self.farskip,
            compute_dtype=self.compute_dtype)

        s = s_before = embed(tokens)
        for i, mlp_kind in enumerate(self.mlp_layer_types):
            s, s_before = block(mlp_kind=mlp_kind, name=f"block{i}")(
                s, s_before)
        h = norm(name="ln_f")(s)
        logits = head(h)
        # ``init`` builds the module whatever ``train`` says.
        if not (self.mtp and (train or self.is_initializing())):
            return logits

        with jax.named_scope("mtp"):
            with jax.named_scope("merge"):
                # t_{i+1}; the last position has none and counts nothing.
                shifted = jnp.pad(tokens[:, 1:], ((0, 0), (0, 1)))
                merged = nn.Dense(
                    self.hidden_size, use_bias=False,
                    dtype=self.compute_dtype, name="mtp_merge")(
                    jnp.concatenate([norm(name="mtp_ln_h")(h),
                                     norm(name="mtp_ln_e")(embed(shifted))],
                                    axis=-1))
            m, _ = block(mlp_kind=SPARSE, name="mtp_block")(merged, merged)
            with jax.named_scope("head"):
                mtp_logits = head(norm(name="mtp_ln_f")(m))
        return logits, mtp_logits
