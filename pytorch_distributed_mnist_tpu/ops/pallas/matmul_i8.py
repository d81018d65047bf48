"""int8 MXU-native matmul as a Pallas TPU kernel.

The serving ``int8``/``int8w`` precision planes (``serve/programs.py``)
quantize WEIGHTS to int8 for the HBM/H2D byte win, then dequantize
on-chip and run the matmul in f32 — int8 buys memory, not MXU clock. On
TPU the MXU natively multiplies int8 x int8 into an int32 accumulator at
a multiple of the f32 issue rate; this kernel makes that the int8
plane's forward matmul: both operands quantize to symmetric per-tensor
int8 (round-to-nearest-even, the same rounding ``tm_quant_i8`` and the
fused plane's in-XLA twin use), one Pallas pass contracts them on the
MXU with ``preferred_element_type=jnp.int32`` (guide rule: never let the
accumulator dtype be inferred), and the int32 result rescales by the two
scales' product.

``int8_dot_general`` is a drop-in for ``lax.dot_general`` on the plain
Dense contraction — ``(..., K) x (K, N)``, no batch dims — which is
every ``nn.Dense`` in the model zoo; any other dimension_numbers falls
back to ``lax.dot_general`` unchanged, so wiring it through a model's
``dot_general`` field can never miscompute an einsum it wasn't built
for. It reaches the models through their ``dot_general`` constructor
field (``models/registry.py::model_accepts`` gates the injection), which
the server turns on for the ``int8`` serving plane only — the f32
baseline a canary shadows against never sees the kernel.

Numerics: dynamic per-tensor activation scales (``max|x| / 127``,
computed inside the jitted program — no host round-trip) on BOTH
operands. The weight operand arrives already dequantized by the int8
plane (per-leaf scales); re-quantizing per-tensor here costs one extra
rounding relative to the dequant path, which is why the kernel is
allclose-pinned against ``lax.dot_general`` rather than bitwise. On the
CPU backend the identical kernel runs in Pallas interpret mode (the
``should_interpret`` rule every kernel in this package follows).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from pytorch_distributed_mnist_tpu.ops.pallas.backend import should_interpret

# int8 operands tile at (32, 128) on the MXU (int32 accumulators at
# (8, 128)); padding every dim up to these keeps Mosaic's layout happy
# and costs only zero rows/lanes, which contribute nothing to the
# integer accumulation.
_LANES = 128
_SUBLANE_I8 = 32
_BLOCK_M = 128

__all__ = ["int8_dot_general", "matmul_i8", "quantize_dynamic_i8"]


def _matmul_i8_kernel(a_ref, b_ref, out_ref):
    """One (bm, K) x (K, N) block product: int8 x int8 contracted on
    the MXU into the int32 accumulator — the whole point of the kernel;
    an inferred accumulator would silently round in f32.

    The precision is pinned to DEFAULT: an integer contraction is exact,
    so there is nothing for a higher setting to buy, and an ambient
    ``jax.default_matmul_precision("highest")`` would otherwise reach the
    kernel as an fp32 contract precision that Mosaic refuses on int8
    operands ("Bad lhs type", v5e, PR 21)."""
    out_ref[:] = jnp.dot(a_ref[:], b_ref[:],
                         preferred_element_type=jnp.int32,
                         precision=jax.lax.Precision.DEFAULT)


def _pad_to(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def matmul_i8(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """``(M, K) int8 x (K, N) int8 -> (M, N) int32`` on the MXU.

    Shapes pad up to the int8 tile grid (M to the 32-sublane multiple,
    K and N to 128 lanes) outside the kernel; the grid runs one program
    instance per M block with the full K and N resident in VMEM —
    MNIST-scale operands (K <= a few thousand, N <= a few hundred) fit
    with room to spare, so no K-loop accumulation pass is needed.
    """
    if a.dtype != jnp.int8 or b.dtype != jnp.int8:
        raise ValueError(
            f"matmul_i8 takes int8 operands, got {a.dtype}/{b.dtype}")
    m, k = a.shape
    k2, n = b.shape
    if k != k2:
        raise ValueError(f"contraction mismatch: {a.shape} x {b.shape}")
    bm = min(_BLOCK_M, _pad_to(m, _SUBLANE_I8))
    mp = _pad_to(m, bm)
    kp = _pad_to(k, _LANES)
    np_ = _pad_to(n, _LANES)
    ap = jnp.zeros((mp, kp), jnp.int8).at[:m, :k].set(a)
    bp = jnp.zeros((kp, np_), jnp.int8).at[:k, :n].set(b)
    out = pl.pallas_call(
        _matmul_i8_kernel,
        grid=(mp // bm,),
        in_specs=[
            pl.BlockSpec((bm, kp), lambda i: (i, 0)),
            pl.BlockSpec((kp, np_), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, np_), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.int32),
        interpret=should_interpret(),
    )(ap, bp)
    return out[:m, :n]


def quantize_dynamic_i8(x: jnp.ndarray):
    """Symmetric per-tensor dynamic quantization: ``(q_int8, scale)``
    with ``scale = max|x| / 127`` and round-to-nearest-even — the same
    rounding contract as the static-scale host/XLA quantizers
    (``serve/programs.py``), so the kernel's only numeric deltas vs the
    dequant path are the per-tensor scale granularity and the int32
    contraction."""
    x = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), jnp.float32(1e-12)) / 127.0
    q = jax.lax.round(x / scale, jax.lax.RoundingMethod.TO_NEAREST_EVEN)
    return jnp.clip(q, -127.0, 127.0).astype(jnp.int8), scale


def int8_dot_general(lhs, rhs, dimension_numbers, precision=None,
                     preferred_element_type=None):
    """``lax.dot_general`` drop-in running the plain Dense contraction
    — ``(..., K) x (K, N)``, last-dim vs first-dim, no batch dims — as
    quantize + int8 MXU matmul + rescale. Every other contraction
    shape falls back to ``lax.dot_general`` verbatim.
    """
    (lc, rc), (lb, rb) = dimension_numbers
    plain = (not lb and not rb and rhs.ndim == 2
             and tuple(lc) == (lhs.ndim - 1,) and tuple(rc) == (0,))
    if not plain:
        return jax.lax.dot_general(
            lhs, rhs, dimension_numbers, precision=precision,
            preferred_element_type=preferred_element_type)
    out_dtype = preferred_element_type or jnp.result_type(lhs, rhs)
    lead = lhs.shape[:-1]
    a2 = lhs.reshape((-1, lhs.shape[-1]))
    qa, sa = quantize_dynamic_i8(a2)
    qb, sb = quantize_dynamic_i8(rhs)
    acc = matmul_i8(qa, qb)
    out = acc.astype(jnp.float32) * (sa * sb)
    return out.reshape(lead + (rhs.shape[-1],)).astype(out_dtype)
