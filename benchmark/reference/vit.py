"""Plain reference of the Vision Transformer encoder (arXiv:2010.11929).

Straightforward ``jax.numpy`` in float32 with every matrix multiplication
at ``highest`` precision (on a TPU a float32 matmul otherwise runs in
bfloat16 passes), written from the paper's equations (1)-(4), with no
kernel, no flax module and no code of the program under test:

    z_0   = [x_p^1 E; ...; x_p^N E] + E_pos                        (1)
    z'_l  = MSA(LN(z_{l-1})) + z_{l-1}                             (2)
    z_l   = MLP(LN(z'_l)) + z'_l                                   (3)
    y     = LN(z_L) pooled                                         (4)

with MSA the paper's appendix A: ``softmax(q k^T / sqrt(D_h)) v`` per
head, heads concatenated and projected, and the MLP two layers with a GELU
between. It reads the same parameter tree the system trains (flax names:
``embed``, ``pos_embed``, ``block<i>/{ln1,attn/{qkv,proj},ln2,mlp1,mlp2}``,
``ln_f``, ``head``), so both sides compute on the same seeded weights.

Departures from the paper, each the repo's own ViT's (models/attention.py),
kept so that the two sides define the same function:

- no class token: the head reads the mean over the N patch tokens of
  LN(z_L) (global average pooling, as arXiv:2205.01580 does), so N tokens,
  not N+1;
- the input is 28x28x1 and a patch is ``patch_size`` squared values wide
  (4 at patch 2), not 16*16*3 = 768;
- GELU is the tanh approximation (flax's default ``nn.gelu``), not erf;
- the fused qkv projection is laid out as [q|k|v] x heads x head size;
- layer norm's epsilon is flax's default 1e-6.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark import flops

LN_EPS = 1e-6


def _layer_norm(x, p):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LN_EPS) * p["scale"] + p["bias"]


def _dense(x, p):
    return x @ p["kernel"] + p["bias"]


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x ** 3)))


def _patches(images, patch_size):
    """(B, H, W, C) -> (B, N, P*P*C), rows of a patch before its columns."""
    b, h, w, c = images.shape
    p = patch_size
    x = images.reshape(b, h // p, p, w // p, p, c)
    x = jnp.transpose(x, (0, 1, 3, 2, 4, 5))
    return x.reshape(b, (h // p) * (w // p), p * p * c)


def _msa(x, p, num_heads):
    b, n, c = x.shape
    d = c // num_heads
    qkv = _dense(x, p["qkv"]).reshape(b, n, 3, num_heads, d)
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(float(d))
    weights = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", weights, v).reshape(b, n, c)
    return _dense(out, p["proj"])


def forward(params, images, *, patch_size: int, num_heads: int, depth: int):
    """Logits (B, classes) in float32 for ``images`` (B, 28, 28, 1)."""
    p = params["params"]
    with jax.default_matmul_precision("highest"):
        z = _dense(_patches(images.astype(jnp.float32), patch_size),
                   p["embed"]) + p["pos_embed"]
        for i in range(depth):
            blk = p[f"block{i}"]
            z = z + _msa(_layer_norm(z, blk["ln1"]), blk["attn"], num_heads)
            y = _dense(_layer_norm(z, blk["ln2"]), blk["mlp1"])
            z = z + _dense(_gelu_tanh(y), blk["mlp2"])
        pooled = jnp.mean(_layer_norm(z, p["ln_f"]), axis=1)
        return _dense(pooled, p["head"])


def cross_entropy(logits, labels):
    """Mean softmax cross-entropy against integer labels."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    return -jnp.mean(picked)


def loss(params, images, labels, **shape):
    return cross_entropy(forward(params, images, **shape), labels)


# -- what the harness asks of a configuration's reference module ------------

def shape_from_kwargs(kwargs: dict) -> dict:
    """``forward``'s keyword arguments from a configuration's kwargs."""
    return {"patch_size": kwargs["patch_size"],
            "num_heads": kwargs["num_heads"], "depth": kwargs["depth"]}


def grad_leaves(kwargs: dict) -> list:
    """The parameter leaves whose gradients the system is held to: the
    fused qkv kernel of the first block (the whole backward pass lies
    between it and the loss) and of the last, and the head."""
    last = kwargs["depth"] - 1
    return ["params/block0/attn/qkv/kernel",
            f"params/block{last}/attn/qkv/kernel",
            "params/head/kernel"]


def train_flops_per_image(kwargs: dict) -> float:
    return flops.vit_train_flops_per_image(
        **flops.vit_shape_from_kwargs(kwargs))


def forward_flops_per_image(kwargs: dict) -> float:
    return flops.vit_forward_flops_per_image(
        **flops.vit_shape_from_kwargs(kwargs))


# Tolerances: the largest absolute difference over the largest absolute
# reference value (runners/train.py rel_err), per quantity, by the compute
# type the configuration states.
#
# bf16, measured on the v5e at the published widths on 8 seeded images
# (chip runs of PR 22, 28 seeds over vit-b16 and vit-l16): logits 0.4-1.4%,
# loss 0.001-0.27%, gradients of the three named leaves 0.6-2.2%. The
# limits are about twice the worst of those, so that rounding that differs
# with the seed passes. They are tight enough that a lower precision
# fails: the same forward of vit-b16 with every matmul's operands rounded
# to int8 (per-tensor activations, per-channel weights) lands at 3.1% on
# the logits and with fp8 (e4m3) operands at 16%, against 0.7% for bf16
# operands (arithmetic on the CPU with this file's forward, PR 22) - both
# above the 2.5% limit.
#
# f32 (the CPU tests' preset): the system and the reference differ only in
# the order of summation; a bf16 forward (0.3% and more at the tiny size)
# fails these by a wide margin.
TOLERANCES = {
    "bf16": {"logits": 0.025, "loss": 0.006, "grad": 0.05},
    "f32": {"logits": 1e-3, "loss": 1e-4, "grad": 1e-2},
}
