"""Fixture suite: the recompile-hazard checker."""


import pytest


from tools.analyzer import analyze_snippet  # noqa: E402

pytestmark = pytest.mark.lint


def _findings(src):
    return analyze_snippet(src, checkers=["recompile-hazard"])


# -- firing ------------------------------------------------------------------


def test_fires_on_scalar_into_precompile_product():
    src = """
def serve(fn, params_spec, image_spec, x):
    exe = precompile(fn, params_spec, image_spec, program="fwd")
    return exe(x, 0.5)
"""
    (f,) = _findings(src)
    assert "argument 1" in f.message and "AOT-compiled" in f.message


def test_fires_on_scalar_into_lower_compile_product():
    src = """
def bench(step, state_spec, batch_spec):
    compiled = step.lower(state_spec, batch_spec).compile()
    return compiled(-1, batch_spec)
"""
    (f,) = _findings(src)
    assert "argument 0" in f.message


def test_fires_on_scalar_into_self_attribute_executable():
    src = """
class Engine:
    def warm(self, fn, spec):
        self._fwd = precompile(fn, spec)

    def infer(self, params):
        return self._fwd(params, 3)
"""
    (f,) = _findings(src)
    assert f.symbol.endswith("infer")


def test_fires_on_jit_without_static_declaration():
    src = """
import jax

def forward(params, x, train=False, impl="xla"):
    return x

prog = jax.jit(forward)
"""
    (f,) = _findings(src)
    assert "train" in f.message and "impl" in f.message
    assert "static_argnums" in f.message


def test_fires_on_bare_jit_decorator_with_config_default():
    src = """
import jax

@jax.jit
def kernel(x, interpret=False):
    return x
"""
    (f,) = _findings(src)
    assert "interpret" in f.message


# -- non-firing --------------------------------------------------------------


def test_silent_when_statics_are_declared():
    src = """
import functools, jax

@functools.partial(jax.jit, static_argnames=("interpret",))
def kernel(x, interpret=False):
    return x

def forward(params, x, train=False):
    return x

prog = jax.jit(forward, static_argnames=("train",))
"""
    assert _findings(src) == []


def test_silent_on_array_variables_into_executables():
    """The trainer/engine idiom: staged arrays and specs, never bare
    literals."""
    src = """
def serve(fn, params_spec, image_spec, params, staged):
    exe = precompile(fn, params_spec, image_spec)
    return exe(params, staged)
"""
    assert _findings(src) == []


def test_silent_on_partial_bound_config():
    """functools.partial binding before jit is the steps.py idiom — the
    bound value is baked in at trace time, nothing to declare."""
    src = """
import functools, jax

def step(state, batch, aux_weight=0.0):
    return state

step_fn = functools.partial(step, aux_weight=0.5)
prog = jax.jit(step_fn, donate_argnums=(0,))
"""
    assert _findings(src) == []


def test_silent_on_float_default_without_static():
    """Float defaults are weight-like (aux_weight), not config flags —
    jit traces them fine; only hashable bool/str config is flagged."""
    src = """
import jax

def step(state, batch, aux_weight=0.0):
    return state

prog = jax.jit(step)
"""
    assert _findings(src) == []


# -- the shard_map-reduce-scatter shape (ISSUE 7, parallel/zero_overlap.py) --


def test_fires_on_jit_of_rs_step_with_config_default():
    """An overlapped-ZeRO step whose body takes a bool config flag
    (interpret/debug toggles) jitted without statics: each distinct
    value re-traces the whole bucket chain — the recompile class
    tests/test_zero_overlap.py's steady-state cases exist to catch."""
    src = """
import jax
from jax import lax

def zero_step(state, batch, debug_buckets=False):
    g = compute_grads(state, batch)
    return lax.psum_scatter(g, "data", scatter_dimension=0, tiled=True)

prog = jax.jit(zero_step, donate_argnums=(0,))
"""
    (f,) = _findings(src)
    assert "debug_buckets" in f.message


def test_fires_on_scalar_into_compiled_zero_step():
    """The AOT-compiled overlapped step's spec holds committed arrays;
    a raw literal where the batch belongs either fails the argument
    check or silently re-keys a compile through a fallback wrapper."""
    src = """
import jax

def bench(step_jit, state, batch):
    compiled = step_jit.lower(state, batch).compile()
    return compiled(state, 128)
"""
    (f,) = _findings(src)
    assert "scalar" in f.message


def test_silent_on_clean_zero_step_factory():
    """The sanctioned zero_overlap factory: plan/level/bucket budget are
    closure-bound at build time (no config params on the traced body),
    and the compiled executable is called with arrays only."""
    src = """
import jax
from jax import lax

def make_zero_step(mesh, plan):
    def body(state, batch):
        g = compute_grads(state, batch)
        return lax.psum_scatter(g, "data", scatter_dimension=0, tiled=True)

    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=None,
                                 out_specs=None), donate_argnums=(0,))

def drive(step_jit, state, batch):
    compiled = step_jit.lower(state, batch).compile()
    return compiled(state, batch)
"""
    assert _findings(src) == []


def test_silent_on_static_declared_rs_config():
    src = """
import jax
from jax import lax

def zero_step(state, batch, debug_buckets=False):
    g = compute_grads(state, batch)
    return lax.psum_scatter(g, "data", scatter_dimension=0, tiled=True)

prog = jax.jit(zero_step, static_argnames=("debug_buckets",))
"""
    assert _findings(src) == []


# -- the serving-mesh lowering shape (ISSUE 8, serve/programs.py) ------------


def test_fires_on_literal_into_compiled_mesh_bucket():
    """The sharded engine's bucket executables take (params, staged
    batch); a raw literal where the batch belongs re-keys a compile
    through the jit fallback — a steady-state recompile."""
    src = """
import jax

def warm_and_drive(pjit_forward, params_spec, image_spec, params):
    compiled = pjit_forward.lower(params_spec, image_spec).compile()
    return compiled(params, 128)
"""
    (f,) = _findings(src)
    assert "scalar" in f.message


def test_fires_on_mode_config_default_on_mesh_forward():
    """A debug/interpret toggle with a default on the pjit-lowered
    serve forward, jitted without statics: each distinct value
    re-traces every bucket program of the mesh group."""
    src = """
import jax

def make_serve_forward(apply_fn):
    def forward(params, images, interpret=False):
        return apply_fn(params, images, train=False)

    return jax.jit(forward, in_shardings=None, out_shardings=None)
"""
    (f,) = _findings(src)
    assert "interpret" in f.message


def test_silent_on_clean_bucket_lowering_loop():
    """The sanctioned programs/engine shape: one lower().compile() per
    bucket against ShapeDtypeStruct specs, the compiled product called
    with arrays only; serve mode and rules are closure-bound at build
    time."""
    src = """
import jax
import numpy as np

def warm_buckets(pjit_forward, params_spec, buckets, input_shape):
    compiled = {}
    for bucket in buckets:
        spec = jax.ShapeDtypeStruct((bucket,) + input_shape, np.float32)
        compiled[bucket] = pjit_forward.lower(params_spec, spec).compile()
    return compiled

def drive(compiled, params, staged):
    return compiled[staged.shape[0]](params, staged)
"""
    assert _findings(src) == []


def test_silent_on_closure_bound_mode_rules():
    """Mode/axis/rule-table configuration bound in the factory closure
    (never a parameter of the traced forward) cannot re-key a compile."""
    src = """
import jax

def make_serve_forward(apply_fn, mode, rules, shardings):
    axis = rules[mode]

    def forward(params, images):
        return apply_fn(params, images, train=False)

    return jax.jit(forward, in_shardings=shardings, out_shardings=None)
"""
    assert _findings(src) == []


# -- the quantize plane (ISSUE 14) -------------------------------------------


def test_fires_on_scale_constant_into_quantized_bucket_program():
    """The precision plane's cardinal hazard: a PER-PUBLISH quantization
    scale baked into a compiled bucket program as a literal — every hot
    reload's new scales would re-key (recompile) every bucket program.
    Scales must ride the quantized tree as ARGUMENTS."""
    src = """
class QuantEngine:
    def warm(self, fn, qparams_spec, image_spec):
        self._fwd = precompile(fn, qparams_spec, image_spec, program="q")

    def infer(self, qvalues, staged):
        return self._fwd(qvalues, staged, 0.0078125)
"""
    (f,) = _findings(src)
    assert f.symbol.endswith("infer") and "argument 2" in f.message


def test_silent_on_scales_as_arguments_of_the_bucket_program():
    """The shipped shape: the quantized tree — int8 values AND their
    f32 scales — is one pytree argument of the compiled program; a new
    publish swaps the argument, never the executable."""
    src = """
def serve(fn, qparams_spec, image_spec, qparams, staged):
    exe = precompile(fn, qparams_spec, image_spec, program="fwd")
    return exe(qparams, staged)
"""
    assert _findings(src) == []


# -- the whole-program plane (ISSUE 16) --------------------------------------


def test_fires_on_bucket_literal_into_fused_executable():
    """The fused plane is ONE AOT program per bucket; threading the
    bucket size through the compiled program as a scalar argument would
    re-key it per request — the exact steady-state recompile the fusion
    exists to delete. Bucket selection belongs OUTSIDE the executable
    (the per-bucket program table)."""
    src = """
class Engine:
    def warm(self, fused, params_spec, raw_spec):
        self._fused_fwd = precompile(fused, params_spec, raw_spec,
                                     program="fwd.fused")

    def dispatch_fused(self, params, staged):
        return self._fused_fwd(params, staged, 8)
"""
    (f,) = _findings(src)
    assert f.symbol.endswith("dispatch_fused") and "argument 2" in f.message


def test_silent_on_donated_fused_dispatch():
    """The shipped shape: the donated fused program takes arrays only —
    params tree and the staged raw batch; donation changes buffer
    ownership, never shapes, so nothing re-keys."""
    src = """
import jax

def wrap_fused_forward(fused):
    return jax.jit(fused, donate_argnums=(1,))

class Engine:
    def warm(self, fused):
        self._fused_fwd = wrap_fused_forward(fused)

    def dispatch_fused(self, params, staged):
        return self._fused_fwd(params, staged)
"""
    assert _findings(src) == []
