"""MXU-bound kernel benchmarks: flash vs dense attention, fused Adam vs optax.

The CNN headline bench (bench.py) is HBM-bound at 1.9 MFLOP/image — its MFU
is a rounding error by construction and says nothing about the Pallas
kernels. This runner measures the kernels on workloads where the MXU is the
bottleneck, answering the only question that matters for them: do the
first-party kernels beat (or match) XLA's own lowering?

- Attention: ``ops.pallas.flash.flash_attention`` vs the dense XLA path
  (``ops.attention.full_attention``) at T in {256, 1024, 4096}, fwd+bwd
  (the training configuration), constant token budget so every row fits
  HBM. Reports per-config times, speedup, and analytic-FLOPs MFU.
- Optimizer: ``ops.pallas.adam.pallas_adam`` vs ``optax.adam`` on a ~13M
  parameter pytree (transformer-block-shaped leaves), update step only.

Prints ONE JSON line. Runs on the chip; with no TPU it exits non-zero.
``--quick`` with ``BENCH_FORCE_CPU=1`` shrinks shapes for the hermetic CPU
harness test (flash runs in interpret mode on the CPU backend, so only
correctness-of-the-harness is asserted there, never perf).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


class MeasurementInvalid(RuntimeError):
    """A timing that violates a physical bound (MFU or HBM-bandwidth
    utilization above 100%): the device sync did not actually wait for
    execution, so every number in the run is garbage. Raised past the
    partial-result handlers in ``main`` — the process exits nonzero and
    the output carries ``"invalid"`` instead of the ``"sync":
    "host_read"`` validity marker, so a consumer gating on rc==0 can
    never take the run as evidence."""


# Per-chip peak HBM bandwidth, bytes/sec, by TPU generation (public spec
# sheets). Used only as an impossibility bound for HBM-bound kernels
# (the Adam update): measured time below bytes_moved/peak_bw is garbage.
_PEAK_HBM_BW = [
    ("v6", 1638e9),  # Trillium
    ("v5p", 2765e9),
    ("v5 lite", 819e9),
    ("v5e", 819e9),
    ("v4", 1228e9),
    ("v3", 900e9),
    ("v2", 700e9),
]


def _peak_hbm_bw(device_kind: str):
    fake = os.environ.get("BENCH_FAKE_HBM_BW")
    if fake:  # test-only: see bench._peak_flops
        return float(fake)
    kind = device_kind.lower()
    for key, bw in _PEAK_HBM_BW:
        if key in kind:
            return bw
    return None


def check_mfu(label: str, secs: float, flops: float, peak):
    """MFU for a row, guarded: >100% of peak is physically impossible —
    it means the device sync did not wait for execution (exactly how
    round 3's kernels.json capture went bad). Shared by this file and
    tools/sweep_flash.py so the bound and its message can never
    diverge. Returns None when the device kind has no known peak."""
    if not peak:
        return None
    mfu = flops / secs / peak
    if mfu > 1.0:
        raise MeasurementInvalid(
            f"impossible {label} MFU {mfu:.4g} (>100% of peak): "
            f"device sync did not wait for execution")
    return round(mfu, 4)


from bench import _fake_bounds  # noqa: E402 - single source for the
# test-only bound-override set (bench.py's children use the same one)


def _host_read(out) -> float:
    """Force a device→host roundtrip on one element of ``out``.

    ``jax.block_until_ready`` once returned early in this harness and
    recorded times 4-120× too small (up to 11,793% MFU), so the sync is a
    read.  A scalar read back to the host can only
    complete after every program queued ahead of it on the device stream
    has executed — the device runs programs in order — so a timestamp
    taken after this call is a true upper bound on execution end.  The
    scalar-index op is compiled during warmup (``_timeit`` calls this on
    the warmup output too), leaving only the ~2-byte transfer in the
    timed region.
    """
    import jax

    leaf = jax.tree_util.tree_leaves(out)[0]
    return float(leaf[(0,) * leaf.ndim])


def _timeit(fn, args, reps: int, iters: int) -> float:
    """Seconds per call: warmup (compile) then best-of-``reps`` means.

    Sync protocol is a host read of the last output (see ``_host_read``),
    never ``block_until_ready`` alone.
    """
    out = fn(*args)
    _host_read(out)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        _host_read(out)
        best = min(best, (time.perf_counter() - t0) / iters)
    return best


def bench_attention(quick: bool, reps: int, iters: int) -> list:
    import jax
    import jax.numpy as jnp

    from bench import _peak_flops
    from pytorch_distributed_mnist_tpu.ops.attention import full_attention
    from pytorch_distributed_mnist_tpu.ops.pallas.flash import flash_attention

    # Constant ~8k-token budget: T grows, B shrinks, HBM footprint stays
    # bounded (the dense path still materializes (B,H,T,T) f32 scores —
    # 0.5 GB at the 4k row, the largest tensor in this file).
    configs = [(64, 2), (128, 1)] if quick else [(256, 32), (1024, 8), (4096, 2)]
    heads, dim = (2, 64) if quick else (8, 128)
    peak = _peak_flops(jax.devices()[0].device_kind)

    def make_loss(attn):
        def loss(q, k, v):
            return jnp.sum(attn(q, k, v).astype(jnp.float32))
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

    flash_g = make_loss(flash_attention)
    dense_g = make_loss(full_attention)

    rows = []
    for t, b in configs:
        key = jax.random.key(0)
        kq, kk, kv = jax.random.split(key, 3)
        shape = (b, t, heads, dim)
        q = jax.random.normal(kq, shape, jnp.bfloat16)
        k = jax.random.normal(kk, shape, jnp.bfloat16)
        v = jax.random.normal(kv, shape, jnp.bfloat16)
        flash_s = _timeit(flash_g, (q, k, v), reps, iters)
        dense_s = _timeit(dense_g, (q, k, v), reps, iters)
        # Analytic matmul FLOPs: fwd QK^T + PV = 4*B*H*T^2*D; bwd recomputes
        # scores and forms dV, dP, dQ, dK — 4 more T^2 matmuls plus the
        # recompute = ~12*B*H*T^2*D total for fwd+bwd.
        flops = 12.0 * b * heads * t * t * dim
        rows.append({
            "seq_len": t, "batch": b, "heads": heads, "head_dim": dim,
            "flash_ms": round(flash_s * 1e3, 3),
            "dense_ms": round(dense_s * 1e3, 3),
            "flash_over_dense_speedup": round(dense_s / flash_s, 3),
            "flash_mfu": check_mfu(f"flash T={t}", flash_s, flops, peak),
            "dense_mfu": check_mfu(f"dense T={t}", dense_s, flops, peak),
        })
    return rows


def bench_adam(quick: bool, reps: int, iters: int) -> dict:
    import jax
    import jax.numpy as jnp
    import optax

    from pytorch_distributed_mnist_tpu.ops.pallas.adam import pallas_adam

    # Transformer-block-shaped leaves, ~13.6M params (>=10M per VERDICT):
    # one big square projection, an MLP up/down pair, and small vectors so
    # the kernel's ragged-tail path is exercised too.
    shapes = ([(256, 256), (256, 512), (512, 256), (256,)] if quick else
              [(3072, 3072), (3072, 680), (680, 3072), (3072,), (680,)])
    key = jax.random.key(1)
    params = {}
    grads = {}
    for i, s in enumerate(shapes):
        key, k1, k2 = jax.random.split(key, 3)
        params[f"w{i}"] = jax.random.normal(k1, s, jnp.float32) * 0.02
        grads[f"w{i}"] = jax.random.normal(k2, s, jnp.float32)
    n_params = sum(int(jnp.size(p)) for p in params.values())

    def step_time(tx):
        state = tx.init(params)

        @jax.jit
        def step(state, grads, params):
            updates, state = tx.update(grads, state, params)
            return optax.apply_updates(params, updates), state

        return _timeit(step, (state, grads, params), reps, iters)

    optax_s = step_time(optax.adam(1e-3))
    fused_s = step_time(pallas_adam(1e-3))
    out = {
        "n_params": n_params,
        "optax_ms": round(optax_s * 1e3, 3),
        "fused_ms": round(fused_s * 1e3, 3),
        "fused_over_optax_speedup": round(optax_s / fused_s, 3),
    }
    # Impossibility bound for this HBM-bound kernel (the attention MFU
    # check can't see it): any correct f32 Adam step must move at least
    # reads of p,g,m,v plus writes of p,m,v = 7 arrays x 4 bytes/param
    # through HBM. Faster than peak bandwidth allows = the sync lied.
    bw = _peak_hbm_bw(jax.devices()[0].device_kind)
    if bw:
        floor_s = 28.0 * n_params / bw
        for name, secs in (("optax", optax_s), ("fused", fused_s)):
            frac = floor_s / secs  # fraction of peak HBM bw; must be <= 1
            out[f"{name}_hbm_frac"] = round(frac, 4)
            if frac > 1.0:
                raise MeasurementInvalid(
                    f"impossible adam {name} time {secs * 1e3:.3f} ms: "
                    f"{frac:.2f}x peak HBM bandwidth for the minimum "
                    f"{28 * n_params} bytes moved; sync did not wait")
    return out


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--quick", action="store_true",
                   help="tiny shapes for the hermetic CPU smoke test")
    p.add_argument("--reps", type=int, default=3)
    p.add_argument("--iters", type=int, default=10)
    args = p.parse_args()

    import jax

    from bench import _require_tpu, configure_jax

    configure_jax()

    device = jax.devices()[0]
    _require_tpu(device.platform)
    fakes = _fake_bounds()
    if fakes and device.platform == "tpu":
        # A leaked test override would make a real capture's physical
        # bounds meaningless while still carrying the validity marker.
        print(json.dumps({
            "metric": "pallas_kernel_vs_xla", "backend": device.platform,
            "invalid": f"test-only bound overrides set on a real TPU "
                       f"run: {sorted(fakes)}"}))
        sys.exit(1)
    out = {
        "metric": "pallas_kernel_vs_xla",
        "backend": device.platform,
        "device_kind": device.device_kind,
        "quick": args.quick,
        # Provenance: which sync protocol produced these times. host_read
        # = a scalar fetched from device per rep (cannot complete before
        # execution does); block_until_ready alone once returned early
        # (see _host_read).
        "sync": "host_read",
    }
    if fakes:
        out["fake_bounds"] = fakes  # test-only run, never evidence
    try:
        try:
            out["attention_fwd_bwd"] = bench_attention(
                args.quick, args.reps, args.iters)
        except MeasurementInvalid:
            raise  # physical-bound violation: whole run is garbage
        except Exception as exc:  # noqa: BLE001 - partial results still print
            out["attention_error"] = repr(exc)
        try:
            out["adam_update"] = bench_adam(args.quick, args.reps, args.iters)
        except MeasurementInvalid:
            raise
        except Exception as exc:  # noqa: BLE001
            out["adam_error"] = repr(exc)
    except MeasurementInvalid as exc:
        # Strip the validity marker, stamp the diagnosis, exit nonzero:
        # a consumer that gates on rc==0 can never take this run as
        # evidence, and even a raw stdout redirect carries "invalid"
        # instead of "sync": "host_read".
        out.pop("sync", None)
        out["invalid"] = str(exc)
        print(json.dumps(out))
        sys.exit(1)
    print(json.dumps(out))
    if "attention_error" in out or "adam_error" in out:
        # Partial results printed for diagnosis, but a run missing rows
        # must not pass an rc==0 gate.
        sys.exit(2)


if __name__ == "__main__":
    main()
