"""Forward-program registry: model x serve-mode -> mesh-lowered programs.

The single-device engine can only REPLICATE a forward per chip
(``serve/pool.py``): a model too big or too slow for one chip has no
serving path, and the repo's parallel-mode assets — the tensor-parallel
rule table (``parallel/tensor.py``) and the expert-parallel one
(``parallel/expert.py``) — are unservable. This registry is the missing
seam: given a model name and a serve mode, it builds the serving mesh,
derives the param/input/output shardings from the SAME rule tables
training uses (serving can never disagree with training on layout), and
hands the engine a :class:`MeshPlacement` it AOT-lowers its bucket
programs against — one pjit program per bucket over the mesh, same
zero-steady-state-recompile discipline, ``CompileLog`` names
``serve_forward_b{b}@{mode}``, params still an ARGUMENT of the compiled
programs so checkpoint hot-reload stays an atomic reference swap.

Modes (``SERVE_MODES``; extensible via :func:`register_serve_mode`):

- ``replicated`` — the PR 3/4 plane: one full forward per chip, fanned
  out by the pool. Servable by every model; the default, and built
  exactly as it always was (no placement object involved).
- ``tensor`` — Megatron column/row-parallel forward over a ``model``
  mesh axis (``vit_tp_rules``): qkv/mlp1 shard their output features,
  proj/mlp2 their input, XLA inserts the partial-sum AllReduce. One
  request's batch stays whole; the WEIGHTS and the per-token FLOPs
  split across the mesh — intra-request parallelism.
- ``expert`` — expert-parallel MoE forward over an ``expert`` mesh axis
  (``moe_ep_rules``): each device holds and computes only its local
  experts; the one-hot combine's sum over experts is the AllReduce.

Inputs and logits stay replicated over the mesh (every mesh device sees
the whole batch; MNIST batches are KBs — the win is weight/FLOP
placement, not activation sharding), which also keeps the engine's
host-side staging/bucketing machinery mode-agnostic: ``complete()``
reads a fully-replicated output exactly as it reads a single-device one.

A sharded engine SPANS its mesh devices, so the pool partitions local
chips into mesh GROUPS (``build_group_placements``) instead of
one-replica-per-device: 8 chips at ``--serve-mesh 2`` = 4 two-chip
engines behind the same least-loaded dispatcher.

**The precision plane** (``--serve-precision``; ``SERVE_PRECISIONS``,
extensible via :func:`register_precision`) is the registry's second
axis, orthogonal to the mode axis above: every bucket x mode pair can
lower at ``f32`` (the default — byte-identical to the pre-precision
engine), ``bf16`` (weights stored bfloat16; compute follows the
model's own compute-dtype policy — bf16 on the TPU default), ``int8w``
(weight-only int8: per-leaf symmetric scales, weights dequantized
on-chip, f32 compute), or ``int8`` (int8w plus int8 activations: the
HOST quantizes the staged batch with the fixed normalize-range scale —
quartering the H2D bytes — and the program dequantizes on-chip). Quantization happens at param-INSTALL time
(:meth:`ServePrecision.quantize`, host-side, outside every engine
lock): the per-leaf scales are computed once per publish and stored
alongside the int8 values in :class:`QuantLeaf` pytree nodes, so the
quantized tree — scales included — remains an ARGUMENT of every
compiled program (never a baked constant: a new publish's scales must
not recompile anything) and hot-reload stays the same atomic reference
swap. ``CompileLog`` names gain the precision suffix
(``serve_forward_b{b}@{mode}.{prec}``; f32 keeps the historical names).

**The fused (whole-program) plane** (ISSUE 16): every bucket x mode x
precision pair can ALSO lower a fused program taking the raw staged
uint8 bytes — normalize (and, on int8, activation quantization) runs
inside XLA via :func:`fused_normalize`/:func:`quant_i8_traced`, both
bitwise-pinned to their host twins, and the staged buffer is DONATED
(:meth:`MeshPlacement.jit_fused_forward`). ``CompileLog`` names gain a
``.fused`` tag after the bucket (``serve_forward_b{b}.fused@{mode}``),
keeping every ``serve_forward_`` prefix filter working. The split plane
stays compiled alongside as the bitwise reference (``--no-fuse``).
"""

from __future__ import annotations

from typing import Callable, Dict, List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from pytorch_distributed_mnist_tpu.parallel.expert import moe_ep_rules
from pytorch_distributed_mnist_tpu.parallel.pipeline_vit import (
    pipeline_stage_rules,
)
from pytorch_distributed_mnist_tpu.parallel.tensor import leaf_spec, vit_tp_rules

REPLICATED = "replicated"


class ServeMode:
    """One registered parallel serving mode: the mesh axis it shards
    over and, per model family, the rule table deriving every param
    leaf's ``PartitionSpec`` (the SAME table training's state sharding
    uses — ``parallel/tensor.py`` / ``parallel/expert.py``).

    Three optional hooks extend the registry beyond the one-pjit-over-
    the-mesh (SPMD) lowering, so a mode whose programs are NOT one mesh
    program — MPMD pipeline serving (``serve/pipeline.py``) compiles one
    independent program PER chip — still rides every generic path
    (layout gate, divisibility walk, pool groups, ``/stats``)
    without special-casing:

    - ``engine_factory``: builds the group's engine instead of the
      default ``MeshPlacement`` + ``InferenceEngine`` pair
      (:func:`build_group_engine` routes).
    - ``make_template(model, rng) -> TrainState``: the template state
      checkpoints restore onto, for modes whose TRAINING param layout is
      not the standard flax tree (pipeline's ``{embed, blocks, head}``).
    - ``staged``: the mode's mesh axis is a PIPELINE of stages, not a
      spanning shard — the auto in-flight window sizes per CHIP (the
      pipe needs >= stages batches to fill) and ``/stats`` reports
      ``pipeline_stages``.
    """

    def __init__(self, name: str, axis: str,
                 rules_by_model: Dict[str, Callable],
                 engine_factory: Optional[Callable] = None,
                 make_template: Optional[Callable] = None,
                 staged: bool = False) -> None:
        self.name = name
        self.axis = axis
        self.rules_by_model = dict(rules_by_model)
        self.engine_factory = engine_factory
        self.make_template = make_template
        self.staged = staged

    def rules_for(self, model_name: str):
        try:
            rules_fn = self.rules_by_model[model_name]
        except KeyError:
            raise ValueError(
                f"--serve-mode {self.name} has no sharding rule table for "
                f"--model {model_name!r} (servable modes for it: "
                f"{servable_modes(model_name)})"
            ) from None
        return rules_fn(self.axis)


_MODES: Dict[str, ServeMode] = {}


def register_serve_mode(name: str, axis: str,
                        rules_by_model: Dict[str, Callable],
                        engine_factory: Optional[Callable] = None,
                        make_template: Optional[Callable] = None,
                        staged: bool = False) -> ServeMode:
    """Register a parallel serving mode (the extension point: a new
    parallel module's rule table becomes servable by adding one entry,
    no engine/pool/server change). See :class:`ServeMode` for the
    optional hooks non-SPMD modes use."""
    if name == REPLICATED or name in _MODES:
        raise ValueError(f"serve mode {name!r} already registered")
    mode = ServeMode(name, axis, rules_by_model,
                     engine_factory=engine_factory,
                     make_template=make_template, staged=staged)
    _MODES[name] = mode
    return mode


register_serve_mode("tensor", "model", {"vit": vit_tp_rules})
register_serve_mode("expert", "expert", {"moe_mlp": moe_ep_rules})


def serve_modes() -> List[str]:
    """Every registered mode, ``replicated`` first (the default)."""
    return [REPLICATED] + sorted(_MODES)


def staged_mode(mode: str) -> bool:
    """Whether ``mode`` is a registered STAGED (pipeline-of-programs)
    mode — the ``/stats`` ``pipeline_stages`` field and the per-chip
    auto-window read this; replicated and unknown names are simply not
    staged."""
    spec = _MODES.get(mode)
    return spec is not None and spec.staged


def make_serve_template(mode: str, model, rng):
    """The template STATE checkpoints restore onto under ``mode``.

    Modes whose TRAINING param layout is not the standard flax tree
    (pipeline's stage-stacked ``{embed, blocks, head}``) override via
    the registry's ``make_template`` hook; everything else — replicated
    included — uses the standard ``create_train_state`` template, byte
    for byte the pre-registry boot path."""
    if mode != REPLICATED:
        spec = _get_mode(mode)
        if spec.make_template is not None:
            return spec.make_template(model, rng)
    from pytorch_distributed_mnist_tpu.train.state import create_train_state

    return create_train_state(model, rng)


def servable_modes(model_name: str) -> List[str]:
    """The serve modes with a rule table for ``model_name`` (always
    includes ``replicated``) — the vocabulary every rejection message
    speaks."""
    return [REPLICATED] + sorted(
        name for name, mode in _MODES.items()
        if model_name in mode.rules_by_model
    )


def _get_mode(mode: str) -> ServeMode:
    try:
        return _MODES[mode]
    except KeyError:
        raise ValueError(
            f"unknown serve mode {mode!r}; registered: {serve_modes()}"
        ) from None


# -- the precision plane -----------------------------------------------------

F32 = "f32"


class QuantLeaf(NamedTuple):
    """One int8-quantized param leaf: the int8 values (original shape)
    and the f32 symmetric scale, TOGETHER as one pytree node — so the
    scale rides the quantized tree through ``device_put``, the sharding
    derivation, and into the compiled programs as an ARGUMENT. Baking a
    publish's scales into the lowered program as constants would force a
    recompile per hot reload (the recompile-hazard the analyzer fixtures
    encode); keeping them leaf-shaped keeps reload a reference swap."""

    q: object  # int8 values, the original leaf's shape
    s: object  # f32 scalar scale (dequant: q.astype(f32) * s)


def _act_scale() -> np.float32:
    """The FIXED int8 activation scale: normalized MNIST pixels live in
    the closed, data-independent range ``[(0-mean)/std, (1-mean)/std]``
    (max |x| at pixel 255), so one symmetric scale covers every request
    — no per-batch calibration, no per-batch scale argument, nothing
    that could vary a compiled program's inputs. Computed in f32 ops so
    the host quantizer and the on-chip dequant agree bitwise."""
    from pytorch_distributed_mnist_tpu.data.mnist import MNIST_MEAN, MNIST_STD

    max_abs = ((np.float32(1.0) - np.float32(MNIST_MEAN))
               / np.float32(MNIST_STD))
    return np.float32(max_abs / np.float32(127.0))


ACT_SCALE = _act_scale()


def _quant_i8_host(x: np.ndarray, scale: np.float32,
                   workers: int) -> np.ndarray:
    """The ONE host-side f32 -> int8 quantizer (weight leaves and the
    int8 activation staging both go through here): the native v4
    ``tm_quant_i8`` kernel when built, else the bitwise-identical NumPy
    expression — both round-to-nearest-even after multiplying by the
    SAME precomputed f32 reciprocal (never a division: divide vs
    multiply-by-reciprocal round differently, and the native-vs-
    fallback equivalence is pinned bitwise)."""
    from pytorch_distributed_mnist_tpu.data import native

    x = np.ascontiguousarray(x, np.float32)
    q = native.quant_i8(x, float(scale), workers=workers)
    if q is None:
        inv = np.float32(1.0) / scale
        scaled = np.rint(x * inv)
        # NaN -> 0 explicitly (astype(int8) of NaN is platform-defined,
        # and the native kernel pins 0); ±inf clip like any overflow.
        scaled = np.where(np.isnan(scaled), np.float32(0.0), scaled)
        q = np.clip(scaled, -127, 127).astype(np.int8)
    return q


def quantize_leaf_i8(leaf, workers: int = 4) -> QuantLeaf:
    """Symmetric per-leaf int8 quantization (host-side, install-time):
    ``scale = max|leaf| / 127``, ``q = clip(rne(leaf / scale), ±127)``
    via :func:`_quant_i8_host`. An all-zero leaf gets scale 1.0
    (quantizes to zeros either way)."""
    x = np.ascontiguousarray(np.asarray(leaf), np.float32)
    max_abs = float(np.max(np.abs(x))) if x.size else 0.0
    scale = np.float32(max_abs) / np.float32(127.0) \
        if max_abs > 0.0 else np.float32(1.0)
    return QuantLeaf(q=_quant_i8_host(x, scale, workers), s=scale)


def dequantize_params(tree):
    """In-program dequantization of a :meth:`ServePrecision.quantize`'d
    tree: every :class:`QuantLeaf` becomes its f32 leaf (``q * s``),
    everything else passes through. Pure jnp ops — this runs INSIDE the
    jitted bucket programs, on tracers."""
    return jax.tree_util.tree_map(
        lambda leaf: leaf.q.astype(jnp.float32) * leaf.s
        if isinstance(leaf, QuantLeaf) else leaf,
        tree, is_leaf=lambda x: isinstance(x, QuantLeaf))


def fused_normalize(raw):
    """In-XLA MNIST normalize, BITWISE-equal to the host
    ``normalize_images`` path: raw uint8 ``(N, 28, 28)`` tracer ->
    normalized f32 ``(N, 28, 28, 1)``.

    The constants hide behind ``optimization_barrier`` because XLA's
    algebraic simplifier otherwise rewrites ``x / const`` into
    ``x * (1/const)`` — a ~1-ulp-different result that would break the
    fused-vs-split bitwise pin. With the barrier the divides are genuine
    IEEE divides, matching the host's NumPy expression (and the native
    ``tm_normalize`` kernel, which is pinned bitwise to it) over the
    entire uint8 domain."""
    from pytorch_distributed_mnist_tpu.data.mnist import MNIST_MEAN, MNIST_STD

    c255, mean, std = jax.lax.optimization_barrier(
        (jnp.float32(255.0), jnp.float32(MNIST_MEAN),
         jnp.float32(MNIST_STD)))
    y = raw.astype(jnp.float32) / c255
    y = (y - mean) / std
    return y[..., None]


def quant_i8_traced(x):
    """In-XLA int8 activation quantization, BITWISE-equal to the host
    :func:`_quant_i8_host` staging path: multiply by the SAME
    precomputed f32 reciprocal (barrier-hidden, so XLA cannot re-derive
    it), round-to-nearest-even, clip to ±127. Normalized pixels are
    always finite, so the host quantizer's NaN pin has nothing to do
    here."""
    inv = jax.lax.optimization_barrier(
        jnp.float32(np.float32(1.0) / ACT_SCALE))
    scaled = jax.lax.round(x * inv, jax.lax.RoundingMethod.TO_NEAREST_EVEN)
    return jnp.clip(scaled, -127.0, 127.0).astype(jnp.int8)


def _floating_leaf(leaf) -> bool:
    return jnp.issubdtype(jnp.result_type(leaf), jnp.floating)


class ServePrecision:
    """One registered serving precision: how params quantize at install
    time, how the forward program transforms, and what dtype the staged
    activations ride.

    The hooks the engines call:

    - ``quantize(params, workers)`` — host-side, once per param install
      (boot, hot reload, regroup), OUTSIDE every engine lock: the slow
      part rides the same slow-part-outside-the-lock discipline as the
      ``device_put`` it precedes.
    - ``wrap_forward(forward)`` — the full-model program transform
      (dequantize weights / cast activations / cast logits back to f32
      so ``complete()`` stays precision-agnostic).
    - ``wrap_stage_forward(forward, first, last)`` — the MPMD per-stage
      transform: the first stage consumes the host-staged input dtype,
      inter-stage D2D hops ride ``hop_dtype`` (bf16 stays bf16; the
      int8 plane hops bf16 — half the hop bytes; re-quantizing
      activations per boundary would need per-publish calibration), and
      only the last stage casts logits back to f32.
    - ``stage_host(images, workers)`` — host-side activation transform
      before staging (int8: native ``tm_quant_i8`` with the fixed
      normalize-range scale; the staged batch and the H2D transfer are
      int8, a quarter of the f32 bytes).
    - ``expand_shardings(params, shardings, replicated)`` — the sharded
      plane's tree expansion: a :class:`QuantLeaf`'s values shard
      exactly as the f32 leaf would, its scalar scale replicates.

    ``f32`` is the identity on every hook — the engines' default path
    stays byte-identical to the pre-precision plane."""

    def __init__(self, name: str, *, weight_cast=None, int8_weights=False,
                 int8_activations=False, act_cast=None,
                 hop_dtype=None) -> None:
        self.name = name
        self.weight_cast = weight_cast  # host-side dtype cast (bf16)
        self.int8_weights = int8_weights
        self.int8_activations = int8_activations
        self.act_cast = act_cast  # in-program activation dtype (bf16)
        self.hop_dtype = hop_dtype if hop_dtype is not None else act_cast
        self.input_dtype = np.int8 if int8_activations else np.float32

    @property
    def identity(self) -> bool:
        """True only for f32: every hook is a no-op and the engines take
        their historical code paths bit-for-bit."""
        return not (self.weight_cast is not None or self.int8_weights
                    or self.int8_activations or self.act_cast is not None)

    def quantize(self, params, workers: int = 4):
        """IDEMPOTENT by design: a pool quantizes ONCE per publish and
        fans the quantized tree to its engines, whose ``_place`` runs
        quantize again — already-``QuantLeaf`` nodes pass through (an
        unguarded tree_map would descend into them and 'quantize' the
        f32 scale leaves), already-cast bf16 leaves re-cast copy-free."""
        if self.int8_weights:
            return jax.tree_util.tree_map(
                lambda leaf: leaf if isinstance(leaf, QuantLeaf)
                else (quantize_leaf_i8(leaf, workers)
                      if _floating_leaf(leaf) else leaf),
                params, is_leaf=lambda x: isinstance(x, QuantLeaf))
        if self.weight_cast is not None:
            cast = self.weight_cast
            return jax.tree_util.tree_map(
                lambda leaf: np.asarray(leaf).astype(cast, copy=False)
                if _floating_leaf(leaf) else leaf, params)
        return params

    def wrap_forward(self, forward):
        if self.identity:
            return forward
        spec = self

        def precision_forward(params, images):
            x = images
            if spec.int8_activations:
                x = x.astype(jnp.float32) * ACT_SCALE
            if spec.act_cast is not None:
                x = x.astype(spec.act_cast)
            p = dequantize_params(params) if spec.int8_weights else params
            return forward(p, x).astype(jnp.float32)

        return precision_forward

    def wrap_stage_forward(self, forward, first: bool, last: bool):
        if self.identity:
            return forward
        spec = self

        def stage_forward(params, x):
            if first:
                if spec.int8_activations:
                    x = x.astype(jnp.float32) * ACT_SCALE
                if spec.act_cast is not None:
                    x = x.astype(spec.act_cast)
            else:
                # The hop arrived at hop_dtype; restore the compute dtype.
                x = x.astype(spec.act_cast if spec.act_cast is not None
                             else jnp.float32)
            p = dequantize_params(params) if spec.int8_weights else params
            y = forward(p, x)
            if last:
                return y.astype(jnp.float32)
            return y.astype(spec.hop_dtype) \
                if spec.hop_dtype is not None else y

        return stage_forward

    def wrap_fused_forward(self, forward):
        """The WHOLE-program transform (ISSUE 16 tentpole): raw staged
        uint8 bytes -> f32 logits in ONE compiled program. The host
        preprocess (``tm_normalize``) and the int8 activation staging
        (``tm_quant_i8``) move into XLA via the bitwise-pinned
        :func:`fused_normalize` / :func:`quant_i8_traced`, then the math
        continues through the SAME :meth:`wrap_forward` transform the
        split plane compiles — the two planes share every op after the
        normalize, which is what makes the fused-vs-split logit pins
        bitwise at exact-fit buckets."""
        spec = self
        split = self.wrap_forward(forward)

        def fused_forward(params, raw):
            x = fused_normalize(raw)
            if spec.int8_activations:
                x = quant_i8_traced(x)
            return split(params, x)

        return fused_forward

    def wrap_fused_stage_forward(self, forward, first: bool, last: bool):
        """The MPMD fusion seam: only stage 0 consumes staged bytes, so
        only its program prepends the in-XLA normalize (+ int8 quant);
        later stages keep their :meth:`wrap_stage_forward` programs
        byte-identical to the split chain."""
        base = self.wrap_stage_forward(forward, first, last)
        if not first:
            return base
        spec = self

        def fused_stage(params, raw):
            x = fused_normalize(raw)
            if spec.int8_activations:
                x = quant_i8_traced(x)
            return base(params, x)

        return fused_stage

    def stage_host(self, images: np.ndarray, workers: int = 4) -> np.ndarray:
        if not self.int8_activations:
            return images
        return _quant_i8_host(images, ACT_SCALE, workers)

    def expand_shardings(self, params, shardings, replicated):
        if not self.int8_weights:
            return shardings
        return jax.tree_util.tree_map(
            lambda leaf, sh: QuantLeaf(q=sh, s=replicated)
            if _floating_leaf(leaf) else sh,
            params, shardings)


_PRECISIONS: Dict[str, ServePrecision] = {}


def register_precision(spec: ServePrecision) -> ServePrecision:
    """Register a serving precision (the extension point mirroring
    :func:`register_serve_mode`: a new quantization scheme becomes a
    ``--serve-precision`` choice by adding one
    :class:`ServePrecision`, no engine/pool/server change)."""
    if spec.name in _PRECISIONS:
        raise ValueError(f"serve precision {spec.name!r} already registered")
    _PRECISIONS[spec.name] = spec
    return spec


register_precision(ServePrecision(F32))
# bf16 stores the WEIGHTS in bfloat16 (half the HBM at rest, half the
# reload bytes); the compute dtype stays the MODEL's own policy — the
# models already cast per-layer to their compute_dtype (bf16 by default
# on TPU, the training --dtype flag), so forcing activations from
# outside would fight that policy (and break e.g. the ViT block scan,
# whose carry dtype the model owns). On the TPU-default models this IS
# full bf16 inference; on a --dtype f32 model it is weight-only bf16.
register_precision(ServePrecision("bf16", weight_cast=jnp.bfloat16))
register_precision(ServePrecision("int8w", int8_weights=True))
register_precision(ServePrecision(
    "int8", int8_weights=True, int8_activations=True,
    hop_dtype=jnp.bfloat16))


def serve_precisions() -> List[str]:
    """Every registered precision, ``f32`` first (the default)."""
    return [F32] + sorted(n for n in _PRECISIONS if n != F32)


def get_precision(name: Optional[str]) -> ServePrecision:
    """The registered :class:`ServePrecision` for ``name`` (``None``
    means f32), raising with the registry's vocabulary for unknown
    names."""
    try:
        return _PRECISIONS[name or F32]
    except KeyError:
        raise ValueError(
            f"unknown serve precision {name!r}; registered: "
            f"{serve_precisions()}"
        ) from None


def precision_engine_name(name: Optional[str],
                          precision: Optional[str]) -> Optional[str]:
    """Compose an engine/CompileLog name with its precision suffix —
    ``serve_forward_b{b}@{mode}.{prec}`` per the registry contract. f32
    keeps the historical (suffix-free) names, so every pre-precision
    compile-stats pin and recompile verdict is untouched. A multi-model
    server (``--model-set``) prefixes the MODEL as the name's first
    dotted segment (``linear.r0``, ``cnn.tensor.g0`` — the pool's
    ``name_prefix``), which is how per-plane /stats compile blocks
    attribute programs per model."""
    if not precision or precision == F32:
        return name
    return f"{name}.{precision}" if name else precision


class MeshPlacement:
    """How one sharded engine commits params and lowers its programs.

    Built once per engine (per mesh group) by :func:`build_placement`;
    the engine calls ``place_params`` at construction and on every
    hot-reload swap, ``place_input`` per dispatched bucket, and
    ``jit_forward`` once to get the pjit the bucket programs AOT-lower
    from. The param sharding TREE is precomputed from the template
    params — swap_params installs checkpoints with identical tree
    structure (the template-load contract), so one tree serves the
    engine's whole life.
    """

    def __init__(self, mode: str, mesh: Mesh, param_shardings,
                 name: str) -> None:
        self.mode = mode
        self.mesh = mesh
        self.name = name  # engine/CompileLog suffix: mode, or mode.g{i}
        self.devices = tuple(mesh.devices.flat)
        self.param_shardings = param_shardings
        self.input_sharding = NamedSharding(mesh, P())
        self.output_sharding = NamedSharding(mesh, P())

    def place_params(self, tree):
        return jax.device_put(tree, self.param_shardings)

    def place_input(self, arr):
        return jax.device_put(arr, self.input_sharding)

    def jit_forward(self, forward):
        return jax.jit(
            forward,
            in_shardings=(self.param_shardings, self.input_sharding),
            out_shardings=self.output_sharding,
        )

    def jit_fused_forward(self, forward):
        """The fused (whole-program) pjit: same shardings, but the raw
        staged batch is DONATED — its buffer belongs to XLA after the
        call, which is why the engine retires (never re-pins) the
        staging buffer it copied from."""
        return jax.jit(
            forward,
            in_shardings=(self.param_shardings, self.input_sharding),
            out_shardings=self.output_sharding,
            donate_argnums=(1,),
        )


def _sharded_leaf_dims(params, rules) -> Dict[str, list]:
    """leaf-path -> [(dim, size), ...] for every param leaf the rule
    table actually shards; empty means the mode is a no-op for this
    model."""
    out: Dict[str, list] = {}

    def visit(path, leaf):
        spec = leaf_spec(path, rules)
        shape = jax.numpy.shape(leaf)
        dims = [(dim, shape[dim]) for dim, axis in enumerate(spec)
                if axis is not None]
        if dims:
            out[jax.tree_util.keystr(path)] = dims

    jax.tree_util.tree_map_with_path(visit, params)
    return out


def validate_serve_mode(mode: str, model_name: str, mesh_devices: int,
                        params=None) -> None:
    """Reject unservable model x mode x mesh combinations with flag
    language BEFORE any mesh or program is built.

    Checks: the mode is registered and has a rule table for the model,
    and (with ``params``) every sharded weight dim divides by the mesh
    size — e.g. ``--serve-mesh 8`` over a ViT whose qkv features don't
    split 8 ways, or more experts' worth of mesh than the MoE has
    experts, fails here with the leaf named, not as a pjit trace error.
    """
    if mode == REPLICATED:
        if mesh_devices != 1:
            raise ValueError(
                f"--serve-mode replicated serves one engine per chip; a "
                f"{mesh_devices}-device mesh needs a sharded mode "
                f"({servable_modes(model_name)[1:] or 'none for this model'})"
            )
        return
    spec = _get_mode(mode)
    rules = spec.rules_for(model_name)  # raises for unservable models
    if mesh_devices < 1:
        raise ValueError(f"serve mesh needs >= 1 device, got {mesh_devices}")
    if params is not None:
        sharded = _sharded_leaf_dims(params, rules)
        if not sharded:
            raise ValueError(
                f"--serve-mode {mode}: no param leaf of model "
                f"{model_name!r} matches the {mode} rule table — the mesh "
                f"would replicate everything; use --serve-mode replicated"
            )
        for path, dims in sorted(sharded.items()):
            for dim, size in dims:
                if size % mesh_devices:
                    raise ValueError(
                        f"--serve-mode {mode} over {mesh_devices} devices: "
                        f"param {path} dim {dim} (size {size}) does not "
                        f"divide evenly; pick a mesh size dividing {size}"
                    )


def build_placement(mode: str, model_name: str, devices: Sequence,
                    params, name: Optional[str] = None,
                    precision: Optional[str] = None) -> MeshPlacement:
    """Mesh + sharding derivation for ONE engine spanning ``devices``.

    ``name`` defaults to the mode itself, giving the ISSUE-specified
    ``serve_forward_b{b}@{mode}`` CompileLog names on a single-group
    plane; multi-group pools pass ``{mode}.g{i}`` so compile stats and
    the zero-recompile verdicts stay attributable per group.

    ``precision``: the sharding derivation always walks the RAW f32
    param tree (the rule tables speak the training layout), then
    :meth:`ServePrecision.expand_shardings` maps the result onto the
    quantized tree the engine will actually install — a
    :class:`QuantLeaf`'s int8 values shard exactly as the f32 leaf
    would (same shape), its scalar scale replicates over the mesh.
    """
    devices = list(devices)
    validate_serve_mode(mode, model_name, len(devices), params)
    spec = _get_mode(mode)
    rules = spec.rules_for(model_name)
    mesh = Mesh(_device_array(devices), (spec.axis,))
    param_shardings = jax.tree_util.tree_map_with_path(
        lambda path, _: NamedSharding(mesh, leaf_spec(path, rules)), params
    )
    param_shardings = get_precision(precision).expand_shardings(
        params, param_shardings, NamedSharding(mesh, P()))
    return MeshPlacement(mode, mesh, param_shardings, name or mode)


def _device_array(devices):
    import numpy as np

    return np.asarray(devices, dtype=object).reshape(len(devices))


def partition_groups(devices: Sequence, mesh_size: int) -> List[list]:
    """Partition ``devices`` into ``mesh_size``-chip groups (the pool's
    sharded/staged plane: one spanning engine per group), rejecting
    indivisible shapes with flag language.

    Slice-aligned: when a DCN slice topology exists (real
    ``device.slice_index`` or the emulated ``TPUMNIST_DCN_SLICES``
    map), chips are ordered slice-major before chunking, so each
    group's intra-mesh collectives ride one slice's ICI whenever the
    mesh size fits in a slice — a group straddles slices only when it
    cannot fit, and the pool's ``/stats`` topology flags exactly those
    groups (``slice_straddling_groups``)."""
    from pytorch_distributed_mnist_tpu.parallel.mesh import (
        device_slice_map,
    )

    devices = list(devices)
    if mesh_size < 1:
        raise ValueError(f"mesh size must be >= 1, got {mesh_size}")
    if len(devices) % mesh_size:
        raise ValueError(
            f"{len(devices)} serve device(s) do not partition into "
            f"{mesh_size}-device mesh groups; --serve-mesh must divide "
            f"--serve-devices"
        )
    smap = device_slice_map(devices)
    if smap is not None:
        order = sorted(range(len(devices)), key=lambda i: (smap[i], i))
        devices = [devices[i] for i in order]
    return [devices[i:i + mesh_size]
            for i in range(0, len(devices), mesh_size)]


def group_name(mode: str, index: int, n_groups: int) -> str:
    """One group's engine/CompileLog name: the bare mode when a single
    group spans the whole pool, ``{mode}.g{i}`` otherwise — so compile
    stats and the zero-recompile verdicts stay attributable per group
    (and, for staged modes, per stage under ``{name}.s{k}``)."""
    return mode if n_groups == 1 else f"{mode}.g{index}"


def build_group_placements(mode: str, model_name: str, devices: Sequence,
                           mesh_size: int, params) -> List[MeshPlacement]:
    """Partition ``devices`` into ``mesh_size``-chip groups, one
    :class:`MeshPlacement` per group — the pool's sharded plane: a
    sharded engine SPANS its mesh, so an 8-chip host at mesh 2 runs 4
    two-chip engines, not 8 one-chip replicas."""
    groups = partition_groups(devices, mesh_size)
    return [
        build_placement(mode, model_name, group, params,
                        name=group_name(mode, i, len(groups)))
        for i, group in enumerate(groups)
    ]


def build_group_engine(mode: str, model_name: str, devices: Sequence,
                       params, name: str, *, apply_fn, buckets,
                       input_shape, serve_log, params_epoch, workers,
                       model=None, precision: Optional[str] = None,
                       fuse: bool = False):
    """ONE engine spanning ``devices`` for ``mode`` — the single builder
    the pool's boot, regroup, and resize paths all share, which is what
    keeps a registered mode's engine construction from drifting between
    them. SPMD modes get the default ``MeshPlacement`` +
    ``InferenceEngine`` lowering; a mode with an ``engine_factory``
    (MPMD pipeline) builds its own engine behind the same surface.
    ``name`` arrives with its precision suffix already composed
    (:func:`precision_engine_name`); ``precision`` selects the program/
    quantization plane; ``fuse`` turns on the whole-program (raw-bytes
    -> logits, donated staging) dispatch plane on whatever engine the
    mode lowers to."""
    spec = _get_mode(mode)
    if spec.engine_factory is not None:
        return spec.engine_factory(
            model=model, model_name=model_name, apply_fn=apply_fn,
            params=params, devices=list(devices), name=name,
            buckets=buckets, input_shape=input_shape, serve_log=serve_log,
            params_epoch=params_epoch, workers=workers,
            precision=precision, fuse=fuse)
    from pytorch_distributed_mnist_tpu.serve.engine import InferenceEngine

    placement = build_placement(mode, model_name, list(devices), params,
                                name=name, precision=precision)
    return InferenceEngine(
        apply_fn, params, buckets=buckets, input_shape=input_shape,
        serve_log=serve_log, params_epoch=params_epoch,
        placement=placement, name=name, workers=workers,
        precision=precision, fuse=fuse)


def check_checkpoint_layout(layout: Optional[dict], mode: str,
                            model_name: str) -> None:
    """Boot/reload gate: the checkpoint's recorded training parallel
    layout must match the serving mode.

    Training stamps ``parallel_layout`` (tensor/sequence/expert/pipeline
    widths) into checkpoint meta; a checkpoint trained with expert or
    tensor sharding served ``replicated`` silently loses the very
    parallelism the operator trained for (or, for a model that only fits
    sharded, fails outright) — reject with the valid ``--serve-mode``
    choices named. ``None`` (pre-layout checkpoints, unit-test saves)
    passes: no provenance, nothing to contradict.

    Sequence parallelism is activation-only (identical params), so it
    never constrains serving. Pipeline-trained checkpoints — whose
    stage-stacked param tree no SPMD serving template matches, and which
    PR 8 therefore rejected by name — now name ``--serve-mode pipeline``
    as the valid choice: the MPMD plane (``serve/pipeline.py``) restores
    onto the pipelined template and splits by stage itself.
    """
    if not layout:
        return
    trained_axis = {"tensor": "tensor", "expert": "expert",
                    "pipeline": "pipeline"}
    for key, want_mode in trained_axis.items():
        if int(layout.get(key, 1)) > 1 and mode != want_mode:
            raise ValueError(
                f"checkpoint was trained with {key}-parallel "
                f"{layout[key]}; serve it with --serve-mode {want_mode} "
                f"(valid modes for --model {model_name}: "
                f"{servable_modes(model_name)})"
            )


# MODE: pipeline (MPMD, serve/pipeline.py). Registered HERE like every
# built-in mode so the registry is complete whenever it is importable —
# regardless of whether anything imported serve.pipeline first — with
# the heavy hooks imported lazily on first USE (an engine build / a
# template make), not at registry import.
def _pipeline_factory(**kwargs):
    from pytorch_distributed_mnist_tpu.serve.pipeline import (
        pipeline_engine_factory,
    )

    return pipeline_engine_factory(**kwargs)


def _pipeline_template(model, rng):
    from pytorch_distributed_mnist_tpu.serve.pipeline import (
        make_pipeline_template,
    )

    return make_pipeline_template(model, rng)


register_serve_mode(
    "pipeline", "stage", {"vit": pipeline_stage_rules},
    engine_factory=_pipeline_factory,
    make_template=_pipeline_template,
    staged=True,
)

# Import-time snapshots for docs/tests; anything validating a mode or
# precision must call serve_modes()/serve_precisions() (the live
# registries) so entries registered after import — the extension seam —
# are honored.
SERVE_MODES = serve_modes()
SERVE_PRECISIONS = serve_precisions()
