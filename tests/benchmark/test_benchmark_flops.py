"""benchmark/flops.py and peaks.py against counts made by hand."""

import pytest

from benchmark import flops, peaks

# ViT-L/16 and ViT-B/16 at 196 tokens (arXiv:2010.11929, Table 1), a patch
# of 2x2x1 values, 10 classes.
L16 = dict(tokens=196, width=1024, depth=24, mlp_ratio=4, patch_values=4,
           num_classes=10)
B16 = dict(tokens=196, width=768, depth=12, mlp_ratio=4, patch_values=4,
           num_classes=10)


def by_hand(tokens, width, depth, mlp, patch_values, classes):
    """Every matmul of the forward pass written out, 2 FLOPs a multiply-add."""
    t, c = tokens, width
    qkv = 2 * t * c * (3 * c)
    scores = 2 * t * t * c
    weighted = 2 * t * t * c
    proj = 2 * t * c * c
    mlp1 = 2 * t * c * mlp
    mlp2 = 2 * t * mlp * c
    block = qkv + scores + weighted + proj + mlp1 + mlp2
    return depth * block + 2 * t * patch_values * c + 2 * c * classes


@pytest.mark.parametrize("shape,mlp", [(L16, 4096), (B16, 3072)])
def test_forward_matches_the_hand_count(shape, mlp):
    want = by_hand(shape["tokens"], shape["width"], shape["depth"], mlp,
                   shape["patch_values"], shape["num_classes"])
    assert flops.vit_forward_flops_per_image(**shape) == want
    assert flops.vit_train_flops_per_image(**shape) == 3 * want


def test_published_sizes_in_round_numbers():
    # 24 blocks x (24*T*C^2 + 4*T^2*C): 122 GFLOP forward, 366 a step.
    assert flops.vit_train_flops_per_image(**L16) == pytest.approx(
        366.1e9, rel=2e-3)
    assert flops.vit_train_flops_per_image(**B16) == pytest.approx(
        104.1e9, rel=2e-3)
    # 197 TFLOP/s over 366 GFLOP: 538 images a second is 100% MFU.
    assert peaks.peak("TPU v5 lite", "bf16_flops") \
        / flops.vit_train_flops_per_image(**L16) == pytest.approx(538, abs=1)


@pytest.mark.parametrize("shape,published", [(L16, 307e6), (B16, 86e6)])
def test_param_count_is_the_papers(shape, published):
    # The paper counts a 768-wide patch embedding, a class token and a
    # 1000-class head; without them the encoder is within 2% of its figure.
    assert flops.vit_param_count(**shape) == pytest.approx(published,
                                                           rel=0.02)


def test_shape_from_kwargs():
    kwargs = {"patch_size": 2, "embed_dim": 1024, "depth": 24,
              "num_heads": 16, "mlp_ratio": 4, "num_classes": 10}
    assert flops.vit_shape_from_kwargs(kwargs) == L16
    assert flops.vit_tokens(28, 7) == 16


def test_param_count_matches_the_model():
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_mnist_tpu.models import get_model

    kwargs = {"patch_size": 7, "embed_dim": 32, "depth": 2, "num_heads": 2}
    model = get_model("vit", **kwargs)
    params = jax.eval_shape(
        lambda r: model.init(r, jnp.zeros((1, 28, 28, 1))), jax.random.key(0))
    n = sum(x.size for x in jax.tree.leaves(params))
    assert flops.vit_param_count(**flops.vit_shape_from_kwargs(kwargs)) == n


def test_peaks_know_the_v5e_and_nothing_else():
    assert peaks.peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9
    assert peaks.peak("TPU v5 lite", "int8_ops") == 393e12
    with pytest.raises(KeyError, match="no published peak"):
        peaks.peak("TPU v9", "bf16_flops")
    with pytest.raises(KeyError, match="no published peak"):
        peaks.peak("cpu", "bf16_flops")
