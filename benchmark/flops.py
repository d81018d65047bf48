"""Analytic model FLOPs, from shapes alone (matrix multiplications only).

The MFU convention: what the forward and backward passes *require*, so a
rematerialised or padded program is not credited for what it recomputes,
and never ``compiled.cost_analysis()``, which counts what was compiled.
A multiply-add is two operations.
"""

from __future__ import annotations


def vit_tokens(image_size: int, patch_size: int) -> int:
    """Tokens of a square image cut into non-overlapping square patches."""
    side = image_size // patch_size
    return side * side


def vit_forward_flops_per_image(*, tokens: int, width: int, depth: int,
                                mlp_ratio: int, patch_values: int,
                                num_classes: int) -> float:
    """One forward pass of a ViT encoder on one image.

    Per block, with T tokens of width C and an MLP of r*C: the qkv
    projection is 2*T*C*3C, the output projection 2*T*C*C, the two MLP
    matmuls 2*2*T*C*rC, and attention's QK^T and PV 2*2*T*T*C (summed over
    heads, whatever their number). Outside the blocks: the patch embedding
    2*T*P*C for P values a patch, and the head 2*C*classes on the pooled
    token. Layer norms, softmax, GELU and biases are not counted.
    """
    per_block = (8 + 4 * mlp_ratio) * tokens * width * width \
        + 4 * tokens * tokens * width
    embed = 2 * tokens * patch_values * width
    head = 2 * width * num_classes
    return float(depth * per_block + embed + head)


def vit_train_flops_per_image(**shape) -> float:
    """Forward plus backward: the backward pass is two matmuls (towards the
    input and towards the weight) for each one of the forward pass."""
    return 3.0 * vit_forward_flops_per_image(**shape)


def vit_shape_from_kwargs(kwargs: dict, image_size: int = 28,
                          channels: int = 1) -> dict:
    """The arguments of the functions above from the registry model's
    constructor kwargs as a configuration file carries them."""
    patch = kwargs["patch_size"]
    return {
        "tokens": vit_tokens(image_size, patch),
        "width": kwargs["embed_dim"],
        "depth": kwargs["depth"],
        "mlp_ratio": kwargs.get("mlp_ratio", 4),
        "patch_values": patch * patch * channels,
        "num_classes": kwargs.get("num_classes", 10),
    }


def vit_param_count(*, tokens: int, width: int, depth: int, mlp_ratio: int,
                    patch_values: int, num_classes: int) -> int:
    """Parameters of the same encoder (weights, biases, layer norms,
    learned position embedding)."""
    c, r = width, mlp_ratio
    per_block = (3 * c * c + 3 * c) + (c * c + c) \
        + (r * c * c + r * c) + (r * c * c + c) + 4 * c
    embed = patch_values * c + c + tokens * c
    head = c * num_classes + num_classes
    return depth * per_block + embed + 2 * c + head
