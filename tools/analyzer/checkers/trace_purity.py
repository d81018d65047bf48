"""Checker: traced/lowered functions must be pure and AOT-stable.

Serving and the AOT precompile path assert ZERO steady-state recompiles
(serve/engine.py, the window of benchmark/run.py's runners) and the
trainer calls compiled executables directly — which only holds if the
traced program is a pure function of its array arguments. Host side effects inside a traced body either
silently run once at trace time (print/logging/time/random: debugging
lies, nondeterminism baked into the program) or force a host sync /
retrace (``.item()``, ``float()``, ``np.asarray`` on a tracer).

Discovery: a function is *traced* when it is

- decorated with ``jit``/``shard_map``/``pallas_call`` (bare, dotted, or
  via ``functools.partial(jax.jit, ...)``),
- passed by name to a ``jit(...)``/``shard_map(...)``/``pallas_call(...)``
  call in the same module (the factory idiom train/steps.py uses), or
- called by name from an already-traced function in the same module
  (call-graph walk; nested defs of a traced function are traced too).

The walk is module-local and name-based by design: cross-module calls
(``cross_entropy`` from ops/loss.py) are each module's own business —
their traced roots are discovered when THAT module is analyzed.

``static_argnames``/``static_argnums`` declared at the jit site exempt
those parameters from the tracer-leak rules (``float(static_cfg)`` is
resolved at trace time, which is the point of declaring it static).
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from tools.analyzer._ast_util import (
    call_name,
    defs_by_name,
    dotted_name,
    function_param_names,
    head_segment,
    int_constants,
    last_segment,
    str_constants,
    walk_in_scope,
)
from tools.analyzer.core import CheckerResult, Finding, Module

CHECKER_ID = "trace-purity"

TRACE_ENTRY_POINTS = {"jit", "shard_map", "pallas_call"}
_LOG_METHODS = {"debug", "info", "warning", "warn", "error", "critical",
                "exception", "log"}
_TIME_FUNCS = {"time", "sleep", "monotonic", "perf_counter",
               "process_time", "time_ns", "monotonic_ns",
               "perf_counter_ns"}
#: numpy-module aliases whose ``asarray`` materializes on the host.
_HOST_NUMPY = {"np", "numpy"}


def _static_names_from_call(call: ast.Call, fn: ast.AST) -> Set[str]:
    """Parameters declared static at a jit site (names or argnums)."""
    static: Set[str] = set()
    params = function_param_names(fn) if fn is not None else []
    for kw in call.keywords:
        if kw.arg == "static_argnames":
            static.update(str_constants(kw.value))
        elif kw.arg == "static_argnums":
            for idx in int_constants(kw.value):
                if 0 <= idx < len(params):
                    static.add(params[idx])
    return static


def _decorator_trace_info(fn: ast.AST) -> Optional[Set[str]]:
    """None if the decorators don't trace ``fn``; else the set of static
    parameter names the tracing decorator declares."""
    for dec in fn.decorator_list:
        if isinstance(dec, (ast.Name, ast.Attribute)):
            if last_segment(dotted_name(dec)) in TRACE_ENTRY_POINTS:
                return set()
        elif isinstance(dec, ast.Call):
            name = last_segment(call_name(dec))
            if name in TRACE_ENTRY_POINTS:
                return _static_names_from_call(dec, fn)
            if name == "partial" and dec.args:
                inner = dec.args[0]
                if last_segment(dotted_name(inner)) in TRACE_ENTRY_POINTS:
                    return _static_names_from_call(dec, fn)
    return None


def _find_roots(tree: ast.Module, defs) -> List[Tuple[ast.AST, Set[str]]]:
    roots: List[Tuple[ast.AST, Set[str]]] = []
    for name, nodes in defs.items():
        for fn in nodes:
            static = _decorator_trace_info(fn)
            if static is not None:
                roots.append((fn, static))
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        if last_segment(call_name(node)) not in TRACE_ENTRY_POINTS:
            continue
        if not node.args or not isinstance(node.args[0], ast.Name):
            continue  # partials/attributes: statics untrackable, skip
        target = node.args[0].id
        for fn in defs.get(target, []):
            roots.append((fn, _static_names_from_call(node, fn)))
    return roots


def _called_local_names(fn: ast.AST) -> Set[str]:
    names: Set[str] = set()
    for node in ast.walk(fn):  # nested defs included: they share tracing
        if isinstance(node, ast.Call) and isinstance(node.func, ast.Name):
            names.add(node.func.id)
    return names


def _traced_closure(tree, defs, roots) -> Dict[int, Tuple[ast.AST, Set[str]]]:
    """Transitive closure over same-module calls; id(fn) -> (fn, static)."""
    traced: Dict[int, Tuple[ast.AST, Set[str]]] = {}
    work = list(roots)
    while work:
        fn, static = work.pop()
        if id(fn) in traced:
            continue
        traced[id(fn)] = (fn, static)
        for callee in _called_local_names(fn):
            for target in defs.get(callee, []):
                if id(target) not in traced:
                    work.append((target, set()))
        for node in ast.walk(fn):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and node is not fn and id(node) not in traced:
                work.append((node, set()))
    return traced


def _check_traced_fn(module: Module, fn, static: Set[str],
                     findings: List[Finding]) -> None:
    tracer_params = {p for p in function_param_names(fn)
                     if p not in static and p != "self"}

    def report(node, message, hint):
        findings.append(Finding(
            checker=CHECKER_ID, path=module.path, line=node.lineno,
            col=node.col_offset, symbol=fn.name, message=message,
            hint=hint))

    for node in walk_in_scope(fn):  # nested defs are their own entries
        if isinstance(node, (ast.Global, ast.Nonlocal)):
            kind = "global" if isinstance(node, ast.Global) else "nonlocal"
            report(node,
                   f"traced function declares `{kind} "
                   f"{', '.join(node.names)}`: mutating enclosing state "
                   f"under trace runs once at trace time and never again "
                   f"in the compiled program",
                   "return the value instead; traced programs must be "
                   "pure functions of their arguments")
            continue
        if not isinstance(node, ast.Call):
            continue
        name = call_name(node)
        last = last_segment(name)
        head = head_segment(name)
        if isinstance(node.func, ast.Name) and node.func.id == "print":
            report(node,
                   "print() inside a traced function executes once at "
                   "trace time, not per step — and a callback-based "
                   "print would block AOT stability",
                   "drop it, or use jax.debug.print for traced values")
        elif head == "logging" or (head in {"logger", "log"}
                                   and last in _LOG_METHODS):
            report(node,
                   f"{name}() inside a traced function fires at trace "
                   f"time only; per-step logging belongs on the host "
                   f"side of the step boundary",
                   "log outside the traced program (trainer/engine own "
                   "the host loop)")
        elif head == "time" and last in _TIME_FUNCS:
            report(node,
                   f"{name}() under trace bakes the trace-time value "
                   f"into the compiled program (and sleep would stall "
                   f"compilation, not execution)",
                   "measure on the host around the compiled call")
        elif head == "random":
            report(node,
                   f"Python {name}() under trace freezes one sample "
                   f"into the program — every execution reuses it",
                   "use jax.random with an explicit key argument")
        elif last == "item" and isinstance(node.func, ast.Attribute) \
                and isinstance(node.func.value, ast.Name) \
                and node.func.value.id in tracer_params \
                and not node.args:
            report(node,
                   f"`.item()` on tracer-typed argument "
                   f"{node.func.value.id!r}: forces a host sync under "
                   f"trace (ConcretizationTypeError at best, a hidden "
                   f"device round-trip at worst)",
                   "keep the value on device; reduce with jnp and "
                   "fetch after the compiled call returns")
        elif isinstance(node.func, ast.Name) and node.func.id == "float" \
                and len(node.args) == 1 \
                and isinstance(node.args[0], ast.Name) \
                and node.args[0].id in tracer_params:
            report(node,
                   f"float() on tracer-typed argument "
                   f"{node.args[0].id!r} concretizes under trace",
                   "use jnp.asarray(..., jnp.float32) to stay abstract, "
                   "or declare the parameter static at the jit site")
        elif head in _HOST_NUMPY and last == "asarray" and node.args \
                and isinstance(node.args[0], ast.Name) \
                and node.args[0].id in tracer_params:
            report(node,
                   f"{name}() on tracer-typed argument "
                   f"{node.args[0].id!r} materializes the tracer on the "
                   f"host (concretization error / silent device sync)",
                   "use jnp.asarray inside traced code; np.asarray "
                   "belongs on the host side")


def run(modules: List[Module]) -> CheckerResult:
    findings: List[Finding] = []
    n_traced = 0
    for module in modules:
        defs = defs_by_name(module.tree)
        roots = _find_roots(module.tree, defs)
        traced = _traced_closure(module.tree, defs, roots)
        n_traced += len(traced)
        for fn, static in traced.values():
            _check_traced_fn(module, fn, static, findings)
    return CheckerResult(findings=findings,
                         report={"traced_functions": n_traced})
