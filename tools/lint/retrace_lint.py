"""retrace-lint: functions handed to `jax.jit` must keep the
(plan-struct, shape-bucket) signature contract that makes AOT warmup
(search/warmup.py) work — a jitted function that silently retraces
turns the warmed executable cache into a lie.

Three lexical checks on every jit target the checker can resolve:

1. no closure over MUTABLE module globals: reading a module-level list/
   dict/set from inside a jitted body bakes the value at trace time
   while the name keeps mutating — the classic silent-staleness bug
   (closures over enclosing-function locals are fine: those are
   per-trace constants by construction);
2. no branching on tracer values: a Python `if`/`while` on a non-static
   parameter raises TracerBoolConversionError at best and forces a
   retrace per value at worst (params named in `static_argnums`/
   `static_argnames` are exempt);
3. no data-dependent shapes: `nonzero`/`unique`/`compress`/`.item()`
   and Python scalar casts (`int`/`float`/`bool`) of a parameter
   produce value-dependent shapes/values that cannot be traced.

Resolution is best effort and lexical: `jax.jit(name)` resolves through
enclosing scopes to a local def; `jax.jit(builder(...))` resolves one
level into module-level builders that `return <local def>` (the
executor's `build_*_query_phase` family); decorator forms `@jax.jit`
and `@functools.partial(jax.jit, ...)` are checked directly. Unresolvable
targets are skipped, not guessed at. Discharge with `# retrace-ok:
<reason>` on the flagged line.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from .core import (SourceFile, Violation, func_params, load_files,
                   module_mutable_globals, name_of, package_files)

RULE = "retrace-lint"

SHAPE_DEP_METHODS = {"nonzero", "unique", "compress", "item"}
SCALAR_CASTS = {"int", "float", "bool"}


def _is_jit_func(node: ast.expr) -> bool:
    # jit_family(fn, family) is jax.jit(fn) under the family's name
    # (telemetry/kernels.py): the same first argument, the same checks
    return name_of(node) in ("jax.jit", "jit", "jit_family")


def _static_names(call: Optional[ast.Call], fn) -> Set[str]:
    """Parameter names excluded from tracing via static_argnums /
    static_argnames literals on the jit call (or partial)."""
    if call is None:
        return set()
    params = func_params(fn)
    out: Set[str] = set()
    for kw in call.keywords:
        vals: List[ast.expr] = []
        if isinstance(kw.value, (ast.Tuple, ast.List)):
            vals = list(kw.value.elts)
        else:
            vals = [kw.value]
        if kw.arg == "static_argnums":
            for v in vals:
                if isinstance(v, ast.Constant) and isinstance(v.value, int) \
                        and 0 <= v.value < len(params):
                    out.add(params[v.value])
        elif kw.arg == "static_argnames":
            for v in vals:
                if isinstance(v, ast.Constant) and isinstance(v.value, str):
                    out.add(v.value)
    return out


def _resolve_name(sf: SourceFile, at: ast.AST, name: str):
    """A FunctionDef named `name` visible from `at`: enclosing function
    bodies innermost-first, then module top level."""
    scopes = [f for f in sf.enclosing_functions(at)
              if not isinstance(f, ast.Lambda)]
    for scope in scopes + [sf.tree]:
        body = scope.body if not isinstance(scope, ast.Module) \
            else scope.body
        for stmt in body:
            if isinstance(stmt, ast.FunctionDef) and stmt.name == name:
                return stmt
    return None


def _resolve_builder(sf: SourceFile, call: ast.Call):
    """`jax.jit(builder(...))`: when `builder` is a module-level def whose
    return statement returns a locally defined closure, check THAT
    closure (the executor's build_*_query_phase family)."""
    if not isinstance(call.func, ast.Name):
        return None
    builder = None
    for stmt in sf.tree.body:
        if isinstance(stmt, ast.FunctionDef) and stmt.name == call.func.id:
            builder = stmt
            break
    if builder is None:
        return None
    local_defs = {s.name: s for s in builder.body
                  if isinstance(s, ast.FunctionDef)}
    for node in ast.walk(builder):
        if isinstance(node, ast.Return) and isinstance(node.value, ast.Name):
            if node.value.id in local_defs:
                return local_defs[node.value.id]
    return None


def _jit_targets(sf: SourceFile):
    """Yield (target_fn, jit_call_or_None, report_node) triples."""
    for node in ast.walk(sf.tree):
        # call form: jax.jit(target, ...)
        if isinstance(node, ast.Call) and _is_jit_func(node.func) \
                and node.args:
            arg = node.args[0]
            if isinstance(arg, (ast.Lambda,)):
                yield arg, node, node
            elif isinstance(arg, ast.Name):
                fn = _resolve_name(sf, node, arg.id)
                if fn is not None:
                    yield fn, node, node
            elif isinstance(arg, ast.Call):
                fn = _resolve_builder(sf, arg)
                if fn is not None:
                    yield fn, node, node
        # decorator forms: @jax.jit / @functools.partial(jax.jit, ...)
        if isinstance(node, ast.FunctionDef):
            for dec in node.decorator_list:
                if _is_jit_func(dec):
                    yield node, None, node
                elif isinstance(dec, ast.Call):
                    if _is_jit_func(dec.func):
                        yield node, dec, node
                    elif name_of(dec.func).endswith("partial") and \
                            dec.args and _is_jit_func(dec.args[0]):
                        yield node, dec, node


def _local_names(fn) -> Set[str]:
    """Names bound inside the function (params, assignments, loop vars,
    comprehension vars, nested defs) — these shadow module globals."""
    out = set(func_params(fn))

    def _bound_names(t):
        # only names the statement BINDS: `x = ...`, `x, y = ...` — NOT
        # the container of `x[0] = ...` / `x.attr = ...`, which reads x
        if isinstance(t, ast.Name):
            yield t.id
        elif isinstance(t, (ast.Tuple, ast.List)):
            for e in t.elts:
                yield from _bound_names(e)
        elif isinstance(t, ast.Starred):
            yield from _bound_names(t.value)

    for node in ast.walk(fn):
        if isinstance(node, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
            targets = node.targets if isinstance(node, ast.Assign) \
                else [node.target]
            for t in targets:
                out.update(_bound_names(t))
        elif isinstance(node, (ast.For, ast.comprehension)):
            t = node.target
            for n in ast.walk(t):
                if isinstance(n, ast.Name):
                    out.add(n.id)
        elif isinstance(node, ast.FunctionDef) and node is not fn:
            out.add(node.name)
    return out


def _check_target(sf: SourceFile, fn, jit_call, report) -> List[Violation]:
    out: List[Violation] = []
    mutable_globals = sf._lint_mutable_globals  # type: ignore[attr-defined]
    statics = _static_names(jit_call, fn)
    params = set(func_params(fn)) - statics
    locals_ = _local_names(fn)

    def _flag(node, msg):
        if sf.annotation_for(node, "retrace-ok") is None and \
                sf.annotation_for(report, "retrace-ok") is None:
            out.append(Violation(RULE, sf.rel, node.lineno, msg))

    body = fn.body if not isinstance(fn, ast.Lambda) else [fn.body]
    for stmt in body:
        for node in ast.walk(stmt):
            if isinstance(node, ast.Name) and \
                    isinstance(node.ctx, ast.Load) and \
                    node.id in mutable_globals and node.id not in locals_:
                _flag(node,
                      f"jitted function closes over mutable module "
                      f"global [{node.id}] (defined at line "
                      f"{mutable_globals[node.id]}): its value is baked "
                      f"at trace time while the name keeps mutating")
            tests: List[ast.expr] = []
            if isinstance(node, (ast.If, ast.While)):
                tests = [node.test]
            elif isinstance(node, ast.IfExp):
                tests = [node.test]
            for test in tests:
                hit = [n.id for n in ast.walk(test)
                       if isinstance(n, ast.Name) and n.id in params]
                if hit:
                    _flag(node,
                          f"jitted function branches on tracer "
                          f"value(s) {sorted(set(hit))}: data-dependent "
                          f"Python control flow forces a retrace per "
                          f"value (hoist to static_argnums or use "
                          f"lax.cond/jnp.where)")
            if isinstance(node, ast.Call):
                if isinstance(node.func, ast.Attribute) and \
                        node.func.attr in SHAPE_DEP_METHODS:
                    _flag(node,
                          f".{node.func.attr}() inside a jitted "
                          f"function produces a value/shape that "
                          f"depends on tracer data")
                elif isinstance(node.func, ast.Name) and \
                        node.func.id in SCALAR_CASTS and node.args and \
                        isinstance(node.args[0], ast.Name) and \
                        node.args[0].id in params:
                    _flag(node,
                          f"{node.func.id}() of tracer parameter "
                          f"[{node.args[0].id}] forces a concrete "
                          f"value inside a traced function")
    return out


def run(root: str) -> List[Violation]:
    out: List[Violation] = []
    for sf in load_files(root, package_files(root)):
        sf._lint_mutable_globals = module_mutable_globals(  # type: ignore
            sf.tree)
        seen = set()
        for fn, jit_call, report in _jit_targets(sf):
            key = (id(fn), getattr(report, "lineno", 0))
            if key in seen:
                continue
            seen.add(key)
            out.extend(_check_target(sf, fn, jit_call, report))
    return out
