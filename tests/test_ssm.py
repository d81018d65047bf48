"""The chunked selective scan (``ops/ssm.py``) against the recurrence
written position by position: values and all six gradients, at lengths that
are and are not multiples of the chunk, at two chunk lengths and at the one
the shape gives; the chunk length as a function of the shape; the counter.
And, because this is the one file that describes the chip, the compiles
for a described v5e of every kernel of the token cells' main path."""

import jax
import jax.numpy as jnp
import pytest

from pytorch_distributed_mnist_tpu.ops.pallas import ssm as kernels
from pytorch_distributed_mnist_tpu.ops.pallas.ssm import chunk_length
from pytorch_distributed_mnist_tpu.ops.ssm import selective_scan
from pytorch_distributed_mnist_tpu.utils.profiling import (
    device_report,
    scan_log,
)

NAMES = ("a", "dt", "A", "B", "C", "D")


def step_by_step(a, dt, A, B, C, D):
    """``s_t = exp(dt_t A) s_{t-1} + dt_t B_t a_t``; ``m_t = C_t . s_t + D
    a_t``, one position after the other."""
    def step(s, x):
        a_t, dt_t, b_t, c_t = x
        s = jnp.exp(dt_t[:, None] * A) * s + (dt_t * a_t)[:, None] * b_t[None]
        return s, jnp.sum(s * c_t[None], axis=-1) + D * a_t

    def one(a, dt, B, C):
        return jax.lax.scan(step, jnp.zeros(A.shape), (a, dt, B, C))[1]

    return jax.vmap(one)(a, dt, B, C)


def operands(t, seed=0, b=2, c=24, n=4):
    k = jax.random.split(jax.random.key(seed), 7)
    return (jax.random.normal(k[0], (b, t, c)),
            jax.nn.softplus(jax.random.normal(k[1], (b, t, c))),
            -jnp.exp(jax.random.normal(k[2], (c, n))),
            jax.random.normal(k[3], (b, t, n)),
            jax.random.normal(k[4], (b, t, n)),
            jax.random.normal(k[5], (c,))), jax.random.normal(k[6], (b, t, c))


def rel(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("t, chunk", [
    (32, 8), (32, 16), (37, 8), (37, 16), (5, None), (37, None)])
def test_values_and_all_six_gradients_match_the_loop(t, chunk):
    xs, weight = operands(t, seed=t)
    got = selective_scan(*xs, chunk=chunk)
    want = step_by_step(*xs)
    assert got.shape == want.shape and rel(got, want) < 1e-5
    grads = [jax.grad(lambda *xs: jnp.sum(f(*xs) * weight),
                      argnums=range(6))(*xs)
             for f in (lambda *xs: selective_scan(*xs, chunk=chunk),
                       step_by_step)]
    for name, g, w in zip(NAMES, *grads):
        assert g.shape == w.shape and rel(g, w) < 1e-5, name


def test_the_state_crosses_chunk_boundaries():
    """With a decay near 1 the last position's output depends on the first
    position's input, eight chunks back."""
    xs, _ = operands(64)
    a, dt, A, B, C, D = xs
    slow = (a, dt * 1e-2, A, B, C, D)
    moved = (a.at[:, 0].add(1.0),) + slow[1:]
    delta = selective_scan(*moved, chunk=8) - selective_scan(*slow, chunk=8)
    assert float(jnp.max(jnp.abs(delta[:, -1]))) > 1e-4
    assert rel(selective_scan(*moved, chunk=8), step_by_step(*moved)) < 1e-5


def test_bfloat16_operands_keep_a_float32_state():
    xs, _ = operands(32)
    low = tuple(x.astype(jnp.bfloat16) if i in (0, 3, 4) else x
                for i, x in enumerate(xs))
    got = selective_scan(*low)
    assert got.dtype == jnp.bfloat16
    want = step_by_step(*(x.astype(jnp.float32) for x in low))
    assert rel(got.astype(jnp.float32), want) < 1e-2


def test_chunk_length_follows_from_the_shape():
    # the benchmark's shape: pieces of 512 channels, 256 positions of
    # (16, 512) float32 are the 8 MiB of states the backward recomputes
    assert kernels.piece_width(5120) == 512
    assert chunk_length(16384, 5120, 16) == 256
    assert 256 * 16 * 512 * 4 == kernels.CHUNK_STATE_BYTES
    assert kernels.piece_width(128) == 128 and kernels.piece_width(768) == 256
    assert chunk_length(64, 128, 4) == 64  # no longer than the sequence
    assert chunk_length(5, 128, 4) == 8 and chunk_length(37, 24, 4) == 64
    assert chunk_length(1 << 20, 128, 4) == 4096


def test_a_scan_is_counted_with_its_chunks_and_kept_state():
    xs, _ = operands(37)
    before = scan_log.snapshot()
    selective_scan(*xs, chunk=8)
    after = scan_log.snapshot()
    assert after["sites"] == before["sites"] + 1
    # 5 chunks of 8 cover 37 positions; 2 sequences x 5 x (24, 4) float32
    def totals(s):
        return (s["sites"] * (s["chunks_per_site"] or 0),
                s["sites"] * (s["state_bytes_kept_per_site"] or 0))

    assert totals(after)[0] - totals(before)[0] == pytest.approx(5)
    assert totals(after)[1] - totals(before)[1] \
        == pytest.approx(2 * 5 * 24 * 4 * 4)
    assert device_report()["state_scans"]["sites"] == after["sites"]


@pytest.fixture(scope="module")
def one_chip():
    """A described v5e chip (the TPU's compiler is installed here and
    compiles for a chip that is not attached); skipped where it cannot be
    described. Only this file's worker loads the library."""
    import os

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:  # no compiler, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
    return SingleDeviceSharding(topo.devices[0])


def test_the_kernels_compile_at_the_cells_shape_and_hold_no_states(
        one_chip, monkeypatch):
    """Both kernels through Mosaic at (1, 16384, 5120), N = 16: what the
    interpreter cannot refuse (tiling, VMEM), and that the compiled
    gradient's temporaries are a fraction of the 5.4 GB of states."""
    monkeypatch.setattr(kernels, "should_interpret", lambda: False)
    t, c, n = 16384, 5120, 16

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    args = (shape((1, t, c), jnp.bfloat16), shape((1, t, c), jnp.float32),
            shape((c, n), jnp.float32), shape((1, t, n), jnp.bfloat16),
            shape((1, t, n), jnp.bfloat16), shape((c,), jnp.float32))

    def loss(*xs):
        return jnp.sum(selective_scan(*xs).astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=range(6))).lower(
        *args).compile()
    text = compiled.as_text()
    assert "selective_scan_fwd" in text and "selective_scan_bwd" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2e9
    assert f"[1,{t},{n},{c}]" not in text and f"[1,{t},{c},{n}]" not in text


def test_the_chunked_scans_kernels_compile_at_the_cells_shape(one_chip,
                                                              monkeypatch):
    """In this file because it is the one that describes the chip. Both
    kernels of ``ops/pallas/ssd.py`` through Mosaic at (1, 8192, 64, 64),
    N = 128, chunk 256: what the interpreter cannot refuse (tiling, VMEM),
    and that the compiled gradient holds the chunk-start states (67 MB)
    and their like, not the 17 GB of per-position states nor a decay
    tensor of ``(heads, chunks, 256, 256)`` (0.54 GB)."""
    from pytorch_distributed_mnist_tpu.ops import ssd
    from pytorch_distributed_mnist_tpu.ops.ssd import ssd_scan

    monkeypatch.setattr(ssd, "should_interpret", lambda: False)
    t, h, p, n = 8192, 64, 64, 128

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    args = (shape((1, t, h, p), jnp.bfloat16), shape((1, t, h), jnp.float32),
            shape((h,), jnp.float32), shape((1, t, n), jnp.bfloat16),
            shape((1, t, n), jnp.bfloat16), shape((h,), jnp.float32))

    def loss(*xs):
        return jnp.sum(ssd_scan(*xs).astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=range(6))).lower(
        *args).compile()
    text = compiled.as_text()
    assert "ssd_fwd" in text and "ssd_bwd" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 0.5e9
    assert f"f32[1,{t // 256},{h * p // 128},128,{n}]" in text  # the starts
    assert f"[1,{t},{h},{p},{n}]" not in text
    # the (256, 256) tiles that exist are a chunk's, in the kernels' VMEM
    import math
    import re

    tiles = [math.prod(int(d) for d in dims.split(","))
             for dims in re.findall(r"\[([\d,]*256,256)\]", text)]
    assert all(size <= 256 * 256 for size in tiles), max(tiles)


def test_no_op_of_xlas_carries_the_flash_backwards_scope(one_chip,
                                                         monkeypatch):
    """In this file because it is the one that describes the chip. The
    benchmark counts a backward call for every device op whose scope names
    the backward kernel (``benchmark/scopes_lm.py kernel_of``), and a copy
    that XLA puts behind a kernel inherits the kernel's scope: with dK and
    dV viewed as ``(H/G, G, D)`` for the group's sum, a group of 6 cost two
    such copies a call and ``flash_bwd_roofline`` read three calls for
    one. Compiled among free layouts (projections before and behind, as
    in ``models/decoder.py``), the kernel is alone under its name."""
    import re

    from pytorch_distributed_mnist_tpu.ops.pallas import flash

    monkeypatch.setattr(flash, "should_interpret", lambda: False)
    b, t, h, kv, d, width = 1, 1024, 12, 2, 128, 256

    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.bfloat16, sharding=one_chip)

    def loss(x, wq, wk, wv, wo):
        q, k, v = ((x @ w).reshape(b, t, -1, d) for w in (wq, wk, wv))
        o = flash.flash_attention(q, k, v, causal=True)
        return jnp.sum((o.reshape(b, t, h * d) @ wo).astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=range(5))).lower(
        shape(b, t, width), shape(width, h * d), shape(width, kv * d),
        shape(width, kv * d), shape(h * d, width)).compile().as_text()
    under = re.findall(
        r"= \S+ ([\w-]+)\([^\n]*op_name=\"[^\"]*flash_bwd", text)
    assert under and set(under) <= {"custom-call", "get-tuple-element"}


@pytest.mark.parametrize("heads, kind", [(64, "sliding_attention"),
                                         (48, "full_attention")])
def test_the_rotary_is_one_kernel_on_whole_heads_in_the_compiled_layer(
        one_chip, monkeypatch, heads, kind):
    """In this file because it is the one that describes the chip.
    ``GatedAttention`` at the training cell's ``(2, 8192)``, 64 heads of
    128 with all 128 lanes rotated and 48 with 64, forward and gradient
    under ``remat``. Sliced into halves of a head the rotary cost the step
    an array of its own a half (``copy f32[2,8192,64,64]``,
    ``bf16[2,8192,48,32]``); on whole heads nothing under the ``rope``
    scope and no copy anywhere is such a half, the kernel is alone under
    its name, four calls (q and k, forward and backward: XLA shares the
    recomputed forward with the first here), and q goes from its
    projection through the kernel into ``flash_fwd`` with no copy. The one
    copy the scope keeps is k's: k is a slice of the ``kv`` projection's
    result, and a custom call takes no slice."""
    import json
    import math
    import os
    import re

    import flax.linen as nn

    from pytorch_distributed_mnist_tpu.models import decoder
    from pytorch_distributed_mnist_tpu.ops.pallas import flash, rope

    monkeypatch.setattr(flash, "should_interpret", lambda: False)
    monkeypatch.setattr(rope, "should_interpret", lambda: False)
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmark",
                           "configs", "laguna-xs2-ep8.json")) as f:
        params = json.load(f)["rope_parameters"][kind]
    b, t, c, kv, d = 2, 8192, 2048, 8, 128
    layer = nn.remat(decoder.GatedAttention)(
        num_heads=heads, num_kv_heads=kv, head_dim=d,
        window=512 if kind == "sliding_attention" else None,
        rope=decoder._frozen(params), depth=5, attention="flash")

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    u = jax.ShapeDtypeStruct((b, t, c), jnp.bfloat16)
    weights = jax.eval_shape(layer.init, jax.random.key(0), u)

    def loss(p, u):
        return jnp.sum(layer.apply(p, u).astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        on_chip(weights), on_chip(u)).compile().as_text()
    ops = []  # (result's dimensions, op, scope) of every instruction
    for line in text.splitlines():
        head = re.match(r"\s*(?:ROOT )?%\S+ = \w+\[([\d,]*)\]\S* ([\w-]+)\(",
                        line)
        if head:
            scope = re.search(r'op_name="([^"]*)"', line)
            ops.append((*head.groups(), scope.group(1) if scope else ""))

    def last(dims):
        return int(dims.split(",")[-1]) if dims else 1

    def size(dims):
        return math.prod(int(n) for n in dims.split(",")) if dims else 1

    under_rope = [(op, dims) for dims, op, scope in ops
                  if "/rope/" in scope]
    assert under_rope
    half_of_k = b * t * kv * 32  # the smallest half there was; a table
    # is (8192, 64) and the gate (2, 8192, heads): an eighth of it

    def halves(found):
        return [dims for dims in found
                if last(dims) in (32, 64) and size(dims) >= half_of_k]

    assert not halves(dims for _, dims in under_rope)
    assert not halves(dims for dims, op, _ in ops if op == "copy")
    copied = sum(size(dims) for op, dims in under_rope if op == "copy")
    assert copied <= b * t * kv * d  # k's slice, no more
    named = [op for _, op, scope in ops if "rope_whole_head" in scope]
    assert named == ["custom-call"] * 4
    # q's forward call reads a projection's matmul and feeds the core
    calls = re.findall(
        r"%(rope_whole_head\S*) = bf16\[2,8192,(\d+)\]\S* custom-call\("
        r"%(\S+?),", text)
    fed = {operand for name, width, operand in calls
           if int(width) == heads * d}
    assert any(re.search(rf"%{re.escape(o)} = \S+ fusion\(", text)
               for o in fed)
    flash_fwd = re.search(r"custom-call\(([^)]*)\)[^\n]*flash_fwd", text)
    assert {name for name, width, _ in calls if int(width) == heads * d} \
        & set(re.findall(r"%([\w.-]+)", flash_fwd.group(1)))


def _instructions(text):
    """``(op, result type, scope)`` of every instruction of a compiled
    program. An instruction inside a fusion that names no scope of its own
    takes the scope of the fusion that calls it."""
    import re

    caller_scope, found = {}, []
    for line in text.splitlines():
        call = re.search(r"calls=%([\w.-]+)", line)
        scope = re.search(r'op_name="([^"]*)"', line)
        if call and scope:
            caller_scope[call.group(1)] = scope.group(1)
    computation = ""
    for line in text.splitlines():
        head = re.match(r"(ENTRY )?%([\w.-]+) \(", line)
        if head:
            computation = "" if head.group(1) else head.group(2)
        inst = re.match(r"\s*(?:ROOT )?%\S+ = (\w+\[[\d,]*\])\S* ([\w-]+)\(",
                        line)
        if inst:
            scope = re.search(r'op_name="([^"]*)"', line)
            found.append((inst.group(2), inst.group(1),
                          scope.group(1) if scope
                          else caller_scope.get(computation, "")))
    return found


@pytest.mark.parametrize("heads, kind", [(64, "sliding_attention"),
                                         (48, "full_attention")])
def test_the_gate_a_head_builds_no_head_view_in_the_compiled_layer(
        one_chip, monkeypatch, heads, kind):
    """In this file because it is the one that describes the chip.
    ``GatedAttention`` at the training cell's ``(2, 8192)``, 64 heads of
    128 and 48, forward and gradient of a recomputed block. Written on the
    ``(B, T, H, D)`` view, the gate cost the step a broadcast of itself and
    a multiply there, and re-tiles of the result, forward, recomputed and
    backward (``broadcast bf16[2,8192,64,128]``, ``copy
    bf16[2,8192,64,128]`` under ``attn/reshape``). On the packed view no
    broadcast or multiply of the program has a result of rank 4 ending in
    ``[heads,128]`` but the flash backward's own ``delta`` (``attn_core``),
    and nothing under the ``gate`` or ``proj`` scopes has one."""
    import json
    import os

    from pytorch_distributed_mnist_tpu.models import decoder
    from pytorch_distributed_mnist_tpu.ops.pallas import flash, rope

    monkeypatch.setattr(flash, "should_interpret", lambda: False)
    monkeypatch.setattr(rope, "should_interpret", lambda: False)
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmark",
                           "configs", "laguna-xs2-ep8.json")) as f:
        params = json.load(f)["rope_parameters"][kind]
    b, t, c, kv, d = 2, 8192, 2048, 8, 128
    layer = decoder.recomputed(decoder.GatedAttention)(
        num_heads=heads, num_kv_heads=kv, head_dim=d,
        window=512 if kind == "sliding_attention" else None,
        rope=decoder._frozen(params), depth=5, attention="flash")

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    u = jax.ShapeDtypeStruct((b, t, c), jnp.bfloat16)
    weights = jax.eval_shape(layer.init, jax.random.key(0), u)

    def loss(p, u):
        return jnp.sum(jnp.square(layer.apply(p, u).astype(jnp.float32)))

    ops = _instructions(jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        on_chip(weights), on_chip(u)).compile().as_text())
    head_view = [(op, result, scope) for op, result, scope in ops
                 if result.endswith(f",{heads},128]")
                 and result.count(",") == 3
                 and op not in ("bitcast", "parameter", "get-tuple-element")]
    assert [(op, scope) for op, _, scope in head_view
            if op in ("broadcast", "multiply")
            and "/attn_core/" not in scope] == []
    assert [(op, scope) for op, _, scope in head_view
            if "/gate/" in scope or "/proj/" in scope] == []
    assert any("/gate/" in scope for _, _, scope in ops)


@pytest.mark.parametrize("policy, forwards", [(True, 2), (False, 3)])
def test_recomputed_layers_compile_to_one_flash_forward_each(
        one_chip, monkeypatch, policy, forwards):
    """In this file because it is the one that describes the chip. A
    jaxpr without the recomputed ``flash_fwd`` equation
    (tests/test_flash_remat.py) is not yet a compiled program without the
    kernel: compiled for the chip, two ``GatedAttention`` layers under
    ``decoder.recomputed`` hold two ``flash_fwd`` custom calls and two
    backward kernels. Under ``nn.remat`` with no policy they hold three:
    the first layer's recomputed call, which waits for the second's
    gradient (XLA shares the last layer's with its forward here; in a
    model every layer but the last pays)."""
    import re

    import flax.linen as nn

    from pytorch_distributed_mnist_tpu.models import decoder
    from pytorch_distributed_mnist_tpu.ops.pallas import flash, rope

    monkeypatch.setattr(flash, "should_interpret", lambda: False)
    monkeypatch.setattr(rope, "should_interpret", lambda: False)
    layer_cls = (decoder.recomputed if policy else nn.remat)(
        decoder.GatedAttention)

    class Layers(nn.Module):
        @nn.compact
        def __call__(self, u):
            for i in range(2):
                u = u + layer_cls(
                    num_heads=4, num_kv_heads=2, head_dim=128, window=None,
                    rope=decoder._frozen(decoder.TINY_ROPE[decoder.FULL]),
                    depth=2, attention="flash", name=f"attn{i}")(u)
            return u

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    u = jax.ShapeDtypeStruct((1, 1024, 256), jnp.bfloat16)
    weights = jax.eval_shape(Layers().init, jax.random.key(0), u)

    def loss(p, u):
        return jnp.sum(Layers().apply(p, u).astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        on_chip(weights), on_chip(u)).compile().as_text()
    kernels_called = re.findall(
        r"%(flash_\w+?)[.\d]* = [^\n=]* custom-call\(", text)
    assert kernels_called.count("flash_fwd") == forwards
    assert kernels_called.count("flash_bwd_dq_dkv") == 2
