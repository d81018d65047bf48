"""On-chip flash-attention block-size sweep.

Hypothesis under test: at long T a small fixed tile (128) turns the
flash kernel into many small fori_loop matmuls per q-block, which may
lose to one huge fused XLA matmul while the (T, T) scores still fit
HBM comfortably — larger tiles amortize better. No flash-vs-dense ratio
is established either way: not measured on today's code (ROADMAP S3). ``flash_attention(block=...)``
exposes the tile edge; this sweep measures fwd+bwd wall-clock per
(T, block) pair against the dense path so ``_block_sizes``'s heuristic
becomes a measured choice (the hermetic suite pins numerics for
non-default blocks — tests/test_pallas_kernels.py
``test_flash_attention_block_override``).

Prints ONE JSON line; runs on the chip (with no TPU it exits non-zero; a
physically impossible row exits 1).
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))  # tools/
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--reps", type=int, default=3)
    p.add_argument("--iters", type=int, default=10)
    p.add_argument("--quick", action="store_true",
                   help="tiny shapes for the hermetic CPU smoke test")
    args = p.parse_args()

    import jax
    import jax.numpy as jnp

    from bench import _peak_flops, _require_tpu, configure_jax
    from bench_kernels import (
        MeasurementInvalid,
        _fake_bounds,
        _timeit,
        check_mfu,
    )
    from pytorch_distributed_mnist_tpu.ops.attention import full_attention
    from pytorch_distributed_mnist_tpu.ops.pallas.flash import flash_attention

    configure_jax()
    device = jax.devices()[0]
    _require_tpu(device.platform)
    peak = _peak_flops(device.device_kind)
    fakes = _fake_bounds()
    if fakes and device.platform == "tpu":
        print(json.dumps({
            "metric": "flash_block_sweep_fwd_bwd",
            "backend": device.platform,
            "invalid": f"test-only bound overrides set on a real TPU "
                       f"run: {sorted(fakes)}"}))
        sys.exit(1)

    # Same constant ~8k-token budget as bench_kernels.py so rows are
    # directly comparable.
    configs = [(64, 2)] if args.quick else [(1024, 8), (2048, 4), (4096, 2)]
    blocks = [32] if args.quick else [128, 256, 512]
    heads, dim = (2, 16) if args.quick else (8, 128)

    def make_grad(attn):
        def loss(q, k, v):
            return jnp.sum(attn(q, k, v).astype(jnp.float32))
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

    out = {
        "metric": "flash_block_sweep_fwd_bwd",
        "backend": device.platform,
        "device_kind": device.device_kind,
        "heads": heads, "head_dim": dim,
        "quick": args.quick,
        "rows": [],
    }
    if fakes:
        out["fake_bounds"] = fakes  # test-only run, never evidence
    try:
        for t, b in configs:
            kq, kk, kv = jax.random.split(jax.random.key(0), 3)
            shape = (b, t, heads, dim)
            q = jax.random.normal(kq, shape, jnp.bfloat16)
            k = jax.random.normal(kk, shape, jnp.bfloat16)
            v = jax.random.normal(kv, shape, jnp.bfloat16)
            dense_s = _timeit(make_grad(full_attention), (q, k, v),
                              args.reps, args.iters)
            # Same analytic fwd+bwd matmul count as bench_kernels.py.
            flops = 12.0 * b * heads * t * t * dim
            row = {"seq_len": t, "batch": b,
                   "dense_ms": round(dense_s * 1e3, 3),
                   "dense_mfu": check_mfu(f"dense T={t}", dense_s, flops, peak)}
            for blk in blocks:
                if blk > ((t + 7) // 8) * 8:
                    continue
                fn = make_grad(
                    functools.partial(flash_attention, block=blk))
                s = _timeit(fn, (q, k, v), args.reps, args.iters)
                row[f"flash_b{blk}_ms"] = round(s * 1e3, 3)
                row[f"flash_b{blk}_speedup"] = round(dense_s / s, 3)
                row[f"flash_b{blk}_mfu"] = check_mfu(
                    f"flash_b{blk} T={t}", s, flops, peak)
            out["rows"].append(row)
    except MeasurementInvalid as exc:
        out["invalid"] = str(exc)  # rows measured pre-violation retained
        print(json.dumps(out))
        sys.exit(1)
    out["sync"] = "host_read"  # via bench_kernels._timeit (round-4 fix)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
