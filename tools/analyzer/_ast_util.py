"""Shared AST helpers for the tpumnist-lint checkers.

Pure stdlib ``ast`` — no imports of the analyzed code. Everything here is
syntactic: dotted-name rendering, scope walks that respect function
boundaries, and small predicates the checkers share so their notion of
"a call to X" cannot drift from one another.
"""

from __future__ import annotations

import ast
from typing import Iterator, List, Optional, Sequence, Tuple

#: Node types that open a new runtime scope — traversals that reason about
#: "code executed here" must not descend into these.
SCOPE_NODES = (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda, ast.ClassDef)


def dotted_name(node: ast.AST) -> Optional[str]:
    """Render ``a.b.c`` for a Name/Attribute chain; None for anything
    dynamic (subscripts, call results) — callers treat None as 'unknown',
    never as a match."""
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        base = dotted_name(node.value)
        return f"{base}.{node.attr}" if base else None
    return None


def call_name(node: ast.Call) -> Optional[str]:
    return dotted_name(node.func)


def last_segment(name: Optional[str]) -> str:
    return name.rsplit(".", 1)[-1] if name else ""


def head_segment(name: Optional[str]) -> str:
    return name.split(".", 1)[0] if name else ""


def walk_in_scope(node: ast.AST) -> Iterator[ast.AST]:
    """Yield ``node``'s subtree WITHOUT entering nested function/class
    scopes: the statements that actually execute when this scope runs.
    ``node`` itself is yielded (unless it is a scope node being entered
    from outside — callers pass a function's *body* items, not the def)."""
    stack: List[ast.AST] = [node]
    while stack:
        cur = stack.pop()
        yield cur
        for child in ast.iter_child_nodes(cur):
            if isinstance(child, SCOPE_NODES):
                continue
            stack.append(child)


def walk_body_in_scope(body: Sequence[ast.stmt]) -> Iterator[ast.AST]:
    for stmt in body:
        yield from walk_in_scope(stmt)


def iter_functions(
    tree: ast.AST,
) -> Iterator[Tuple[ast.AST, str, Optional[str]]]:
    """Yield ``(funcnode, qualname, classname)`` for every function in the
    module, nested ones included. ``qualname`` is dotted through the
    enclosing defs/classes; ``classname`` is the nearest enclosing class
    (None at module level) — the lock checker keys lock objects by it."""

    def visit(node: ast.AST, prefix: str, classname: Optional[str]):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                qual = f"{prefix}.{child.name}" if prefix else child.name
                yield child, qual, classname
                yield from visit(child, qual, classname)
            elif isinstance(child, ast.ClassDef):
                qual = f"{prefix}.{child.name}" if prefix else child.name
                yield from visit(child, qual, child.name)
            else:
                yield from visit(child, prefix, classname)

    yield from visit(tree, "", None)


def defs_by_name(tree: ast.AST) -> dict:
    """``{name: [def nodes]}`` over the whole module, nested defs included
    — the shared "resolve a bare callee name" index (trace-purity's call
    graph and recompile-hazard's jit-site lookup must agree on it)."""
    defs: dict = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            defs.setdefault(node.name, []).append(node)
    return defs


def function_param_names(fn: ast.AST) -> List[str]:
    args = fn.args
    names = [a.arg for a in args.posonlyargs + args.args + args.kwonlyargs]
    if args.vararg:
        names.append(args.vararg.arg)
    if args.kwarg:
        names.append(args.kwarg.arg)
    return names


def handler_type_names(handler: ast.ExceptHandler) -> List[str]:
    """The dotted names an ``except`` clause catches; ``[]`` for a bare
    ``except:``. Unresolvable entries (dynamic expressions) render as
    ``"<dynamic>"`` so breadth checks stay conservative."""
    t = handler.type
    if t is None:
        return []
    elts = t.elts if isinstance(t, ast.Tuple) else [t]
    return [dotted_name(e) or "<dynamic>" for e in elts]


def contains_call_to(node: ast.AST, last_segments: set) -> bool:
    """True when ``node``'s in-scope subtree calls any function whose last
    dotted segment is in ``last_segments``."""
    for sub in walk_in_scope(node):
        if isinstance(sub, ast.Call) and \
                last_segment(call_name(sub)) in last_segments:
            return True
    return False


def body_contains_any_call(body: Sequence[ast.stmt]) -> bool:
    for sub in walk_body_in_scope(body):
        if isinstance(sub, ast.Call):
            return True
    return False


def body_contains_raise(body: Sequence[ast.stmt]) -> bool:
    for sub in walk_body_in_scope(body):
        if isinstance(sub, ast.Raise):
            return True
    return False


def str_constants(node: ast.AST) -> List[str]:
    """String literals inside a tuple/list/single-constant expression."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return [node.value]
    if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
        out = []
        for e in node.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, str):
                out.append(e.value)
        return out
    return []


def int_constants(node: ast.AST) -> List[int]:
    if isinstance(node, ast.Constant) and isinstance(node.value, int) \
            and not isinstance(node.value, bool):
        return [node.value]
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for e in node.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, int) \
                    and not isinstance(e.value, bool):
                out.append(e.value)
        return out
    return []


# ---------------------------------------------------------------------------
# Project-wide def/call index (analyzer v2)
# ---------------------------------------------------------------------------


def module_name(path: str) -> str:
    """Dotted module name for a repo-relative posix path.
    ``pkg/serve/engine.py`` -> ``pkg.serve.engine``; ``pkg/__init__.py``
    -> ``pkg``; ``chip_smoke.py`` -> ``chip_smoke``."""
    p = path.replace("\\", "/")
    if p.endswith(".py"):
        p = p[: -len(".py")]
    if p.endswith("/__init__"):
        p = p[: -len("/__init__")]
    return p.strip("/").replace("/", ".")


class FunctionInfo:
    """One indexed function/method: its AST, owner module, and names."""

    __slots__ = ("node", "module", "modname", "qualname", "name",
                 "classname")

    def __init__(self, node, module, modname, qualname, name, classname):
        self.node = node
        self.module = module
        self.modname = modname
        self.qualname = qualname  # "<modname>.<Class>.<method>"
        self.name = name
        self.classname = classname


class ProjectIndex:
    """ONE def/call index over every analyzed module.

    The PR 5 checkers resolved calls per-module (lock-discipline's
    ``self._attr = fn`` factory trick, trace-purity's bare-name def map);
    the incidents of PRs 4/10/19 broke across module seams those maps
    cannot see (engine -> pool -> watcher, server handler -> helper).
    This index is the whole-program version: qualified names for every
    def, ``from x import y`` / ``import x.y as z`` resolution, the same
    ``self._attr = fn`` factory-assignment resolution lock-discipline
    does locally, a call graph over all of it, and reachability queries
    with memoization. It is still purely syntactic — nothing under
    analysis is ever imported.

    Resolution is deliberately *over*-approximate at dynamic seams: an
    attribute call we cannot resolve exactly (``replica.engine.foo()``)
    falls back to matching every project def with that bare name, capped
    at ``_FALLBACK_CAP`` candidates so generic names (``get``, ``read``)
    do not connect everything to everything. More edges means MORE
    reachability, which for every v2 checker means FEWER findings — the
    fallback can only ever make the analyzer quieter, never noisier.
    """

    _FALLBACK_CAP = 6

    def __init__(self, modules) -> None:
        self.modules = list(modules)
        self.functions: dict = {}     # qualname -> FunctionInfo
        self.by_name: dict = {}       # bare name -> [qualname]
        self._modnames: dict = {}     # dotted module name -> Module
        self._imports: dict = {}      # module path -> {alias: dotted target}
        self._methods: dict = {}      # (modname, class) -> {method: qual}
        self._factories: dict = {}    # (modname, class) -> {attr: dotted}
        self._class_nodes: dict = {}  # (modname, class) -> ast.ClassDef
        self.import_graph: dict = {}  # module path -> set(module path)
        self._fq_by_node: dict = {}   # id(funcnode) -> qualname
        self._edges: dict = {}        # qualname -> frozenset(qualname)
        self._direct_memo: dict = {}  # qualname -> frozenset(call segments)
        self._reach_memo: dict = {}
        for m in self.modules:
            self._modnames[module_name(m.path)] = m
        for m in self.modules:
            self._index_module(m)
        for m in self.modules:
            self._link_imports(m)

    # -- construction -------------------------------------------------------

    def _index_module(self, module) -> None:
        modname = module_name(module.path)
        imports: dict = {}
        for node in ast.walk(module.tree):
            if isinstance(node, ast.Import):
                for alias in node.names:
                    if alias.asname:
                        imports[alias.asname] = alias.name
                    else:
                        imports[head_segment(alias.name)] = \
                            head_segment(alias.name)
            elif isinstance(node, ast.ImportFrom):
                base = node.module or ""
                if node.level:
                    parts = modname.split(".")
                    anchor = parts[: max(0, len(parts) - node.level)]
                    if node.module:
                        anchor.append(node.module)
                    base = ".".join(anchor)
                for alias in node.names:
                    if alias.name == "*":
                        continue
                    imports[alias.asname or alias.name] = (
                        f"{base}.{alias.name}" if base else alias.name)
        self._imports[module.path] = imports

        for node in ast.walk(module.tree):
            if isinstance(node, ast.ClassDef):
                self._class_nodes.setdefault((modname, node.name), node)

        for fn, qual, classname in iter_functions(module.tree):
            fq = f"{modname}.{qual}"
            info = FunctionInfo(fn, module, modname, fq, fn.name, classname)
            self.functions[fq] = info
            self._fq_by_node[id(fn)] = fq
            self.by_name.setdefault(fn.name, []).append(fq)
            if classname is not None:
                self._methods.setdefault((modname, classname), {}) \
                    .setdefault(fn.name, fq)
            if classname is None:
                continue
            # `self._attr = fn` factory assignment: record the dotted RHS
            # so self._attr(...) resolves like lock-discipline does.
            for sub in walk_body_in_scope(fn.body):
                if isinstance(sub, ast.Assign) and len(sub.targets) == 1:
                    t = sub.targets[0]
                    if isinstance(t, ast.Attribute) and \
                            isinstance(t.value, ast.Name) and \
                            t.value.id == "self":
                        rhs = dotted_name(sub.value)
                        if rhs and head_segment(rhs) != "self":
                            self._factories.setdefault(
                                (modname, classname), {}) \
                                .setdefault(t.attr, rhs)

    def _modpath_for(self, dotted: str) -> Optional[str]:
        cur = dotted
        while cur:
            m = self._modnames.get(cur)
            if m is not None:
                return m.path
            if "." not in cur:
                return None
            cur = cur.rsplit(".", 1)[0]
        return None

    def _link_imports(self, module) -> None:
        deps = set()
        for target in self._imports.get(module.path, {}).values():
            path = self._modpath_for(target)
            if path and path != module.path:
                deps.add(path)
        self.import_graph[module.path] = deps

    # -- resolution ---------------------------------------------------------

    def fq_of(self, funcnode) -> Optional[str]:
        return self._fq_by_node.get(id(funcnode))

    def class_node(self, modname: str, classname: str):
        return self._class_nodes.get((modname, classname))

    def resolve(self, dotted: Optional[str], modname: str,
                classname: Optional[str], module_path: str,
                _depth: int = 0) -> List[str]:
        """Qualnames a dotted callee may denote, [] when unresolvable.
        Exact where the name is local, imported, a method of the current
        class, or a ``self._attr = fn`` factory product."""
        if not dotted or _depth > 4:
            return []
        parts = dotted.split(".")
        head = parts[0]
        if head == "self":
            if classname is None or len(parts) < 2:
                return []
            attr = parts[1]
            methods = self._methods.get((modname, classname), {})
            if len(parts) == 2 and attr in methods:
                return [methods[attr]]
            factories = self._factories.get((modname, classname), {})
            if attr in factories:
                inner = ".".join([factories[attr]] + parts[2:])
                return self.resolve(inner, modname, classname,
                                    module_path, _depth + 1)
            return []
        fq = f"{modname}.{dotted}"
        if fq in self.functions:
            return [fq]
        imports = self._imports.get(module_path, {})
        if head in imports:
            target = ".".join([imports[head]] + parts[1:])
            if target in self.functions:
                return [target]
            # imported module alias: its own module-level def
            mpath = self._modpath_for(target)
            if mpath is not None and target in self.functions:
                return [target]
        return []

    def resolve_call(self, call: ast.Call, module, classname: Optional[str],
                     fallback: bool = True) -> List[str]:
        """Candidate qualnames for one call site. Unresolvable attribute
        calls fall back to bare-name matching (capped) when ``fallback``."""
        name = call_name(call)
        modname = module_name(module.path)
        resolved = self.resolve(name, modname, classname, module.path)
        if resolved:
            return resolved
        if fallback and name and "." in name:
            cands = self.by_name.get(last_segment(name), [])
            if 0 < len(cands) <= self._FALLBACK_CAP:
                return list(cands)
        return []

    # -- reachability -------------------------------------------------------

    def _direct_calls(self, fq: str) -> frozenset:
        cached = self._direct_memo.get(fq)
        if cached is not None:
            return cached
        segs = set()
        info = self.functions[fq]
        for sub in walk_body_in_scope(info.node.body):
            if isinstance(sub, ast.Call):
                segs.add(last_segment(call_name(sub)))
        out = frozenset(segs)
        self._direct_memo[fq] = out
        return out

    def _callees(self, fq: str) -> frozenset:
        cached = self._edges.get(fq)
        if cached is not None:
            return cached
        edges = set()
        info = self.functions[fq]
        for sub in walk_body_in_scope(info.node.body):
            if isinstance(sub, ast.Call):
                edges.update(self.resolve_call(
                    sub, info.module, info.classname))
        out = frozenset(edges)
        self._edges[fq] = out
        return out

    def reaches(self, fq: str, targets, depth: int = 5) -> bool:
        """True when ``fq`` (or anything it can call, ``depth`` hops of
        the call graph deep) makes a direct call whose last dotted
        segment is in ``targets``."""
        targets = frozenset(targets)
        key = (fq, targets, depth)
        cached = self._reach_memo.get(key)
        if cached is not None:
            return cached
        seen = {fq}
        frontier = [fq]
        hit = False
        for _ in range(depth + 1):
            if hit or not frontier:
                break
            nxt: List[str] = []
            for cur in frontier:
                if cur not in self.functions:
                    continue
                if self._direct_calls(cur) & targets:
                    hit = True
                    break
                for callee in self._callees(cur):
                    if callee not in seen:
                        seen.add(callee)
                        nxt.append(callee)
            frontier = nxt
        self._reach_memo[key] = hit
        return hit

    def call_hits(self, node: ast.AST, module, classname: Optional[str],
                  targets, depth: int = 4) -> int:
        """How many in-scope calls under ``node`` hit ``targets`` —
        directly, or through any resolvable callee (cross-module)."""
        targets = frozenset(targets)
        n = 0
        for sub in walk_in_scope(node):
            if not isinstance(sub, ast.Call):
                continue
            if last_segment(call_name(sub)) in targets:
                n += 1
                continue
            for fq in self.resolve_call(sub, module, classname):
                if self.reaches(fq, targets, depth):
                    n += 1
                    break
        return n

    # -- import graph queries ----------------------------------------------

    def reverse_dependencies(self, paths) -> set:
        """``paths`` plus every module that (transitively) imports one of
        them — the blast radius of a change, for ``--changed`` runs."""
        rev: dict = {}
        for src, deps in self.import_graph.items():
            for d in deps:
                rev.setdefault(d, set()).add(src)
        out = set(paths)
        frontier = list(out)
        while frontier:
            p = frontier.pop()
            for src in rev.get(p, ()):
                if src not in out:
                    out.add(src)
                    frontier.append(src)
        return out
