"""AST extraction of jit declarations: the recompile axes, from source.

The compile contract pins "the set of static arguments" per hot-path
executable. Runtime jit objects don't expose static_argnames publicly
(and an internal attribute would drift across jax versions), so the
auditor reads the declaration the same way a reviewer does — straight
from the decorator / wrapping call in the source file:

    @partial(jax.jit, static_argnames=("steps", "mesh", ...))
    def anneal_sharded(...): ...

    def _merge_fn():
        def merge(prob, assignment, ...): ...
        return jax.jit(merge, donate_argnums=(0, 1),
                       static_argnames=("has_demand", "has_eligible",
                                        "has_conflict", "has_price"))

Both shapes resolve to a :class:`JitDecl` carrying the static argnames
and the donated *parameter names* (donate_argnums indices mapped through
the wrapped function's signature — the names are what the contract file
pins, indices would silently re-bind on a signature shuffle).

This is ground truth for the contract check: a PR that adds a static
axis or drops a donate_argnums changes the extracted declaration, which
diffs against tests/goldens/compile_contract.json in CI.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass, field

__all__ = ["JitDecl", "extract_jit_decl"]


@dataclass
class JitDecl:
    """One jit declaration, as written in source."""
    fn_name: str                          # the wrapped function's name
    static_args: list[str] = field(default_factory=list)   # sorted
    donated_params: list[str] = field(default_factory=list)  # by name
    params: list[str] = field(default_factory=list)        # full signature


def _dotted(node: ast.AST) -> str:
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    return ".".join(reversed(parts))


def _is_jit_name(node: ast.AST) -> bool:
    name = _dotted(node)
    return name in ("jax.jit", "jit") or name.endswith(".jit")


def _str_tuple(node: ast.AST) -> list[str]:
    if isinstance(node, (ast.Tuple, ast.List)):
        return [e.value for e in node.elts
                if isinstance(e, ast.Constant) and isinstance(e.value, str)]
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return [node.value]
    return []


def _int_tuple(node: ast.AST) -> list[int]:
    if isinstance(node, (ast.Tuple, ast.List)):
        return [e.value for e in node.elts
                if isinstance(e, ast.Constant) and isinstance(e.value, int)]
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return [node.value]
    return []


def _fn_params(fn: ast.FunctionDef) -> list[str]:
    a = fn.args
    return [p.arg for p in (*a.posonlyargs, *a.args)]


def _all_params(fn: ast.FunctionDef) -> list[str]:
    a = fn.args
    return [p.arg for p in (*a.posonlyargs, *a.args, *a.kwonlyargs)]


def _decl_from_call(call: ast.Call, fn: ast.FunctionDef) -> JitDecl:
    """Fill a JitDecl from the keyword args of a jit(...) /
    partial(jax.jit, ...) call wrapping `fn`."""
    decl = JitDecl(fn_name=fn.name, params=_all_params(fn))
    positional = _fn_params(fn)
    for kw in call.keywords:
        if kw.arg == "static_argnames":
            decl.static_args.extend(_str_tuple(kw.value))
        elif kw.arg == "static_argnums":
            decl.static_args.extend(
                positional[i] for i in _int_tuple(kw.value)
                if i < len(positional))
        elif kw.arg == "donate_argnums":
            decl.donated_params.extend(
                positional[i] for i in _int_tuple(kw.value)
                if i < len(positional))
        elif kw.arg == "donate_argnames":
            decl.donated_params.extend(_str_tuple(kw.value))
    decl.static_args = sorted(set(decl.static_args))
    decl.donated_params = sorted(set(decl.donated_params))
    return decl


def extract_jit_decl(source: str, qualname: str,
                     filename: str = "<source>") -> JitDecl:
    """Extract the jit declaration for `qualname` from `source`.

    `qualname` is a dotted lexical path of function names, e.g.
    ``"_refine"`` (a decorated module-level def) or ``"_merge_fn.merge"``
    (an inner def wrapped by a ``jax.jit(merge, ...)`` call inside
    ``_merge_fn``). Raises LookupError when the function or its jit
    declaration cannot be found — an audit must fail loudly when its
    anchor moved, not pass vacuously.
    """
    tree = ast.parse(source, filename=filename)
    scope: ast.AST = tree
    parts = qualname.split(".")
    for name in parts:
        nxt = None
        for node in ast.walk(scope):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and node.name == name:
                nxt = node
                break
        if nxt is None:
            raise LookupError(
                f"{filename}: no function {name!r} on path {qualname!r}")
        scope = nxt
    fn = scope
    assert isinstance(fn, ast.FunctionDef)

    # decorator form: @jax.jit / @partial(jax.jit, ...)
    for dec in fn.decorator_list:
        if isinstance(dec, (ast.Name, ast.Attribute)) and _is_jit_name(dec):
            return JitDecl(fn_name=fn.name, params=_all_params(fn))
        if isinstance(dec, ast.Call):
            if _is_jit_name(dec.func):
                return _decl_from_call(dec, fn)
            if _dotted(dec.func) in ("partial", "functools.partial") \
                    and dec.args and _is_jit_name(dec.args[0]):
                return _decl_from_call(dec, fn)

    # call form: jax.jit(fn, ...) in the enclosing scope (or module)
    enclosing = tree if len(parts) == 1 else _resolve(tree, parts[:-1])
    for node in ast.walk(enclosing):
        if isinstance(node, ast.Call) and _is_jit_name(node.func) \
                and node.args and isinstance(node.args[0], ast.Name) \
                and node.args[0].id == fn.name:
            return _decl_from_call(node, fn)

    raise LookupError(f"{filename}: {qualname!r} found but carries no jit "
                      f"declaration (decorator or jax.jit call)")


def _resolve(tree: ast.Module, parts: list[str]) -> ast.AST:
    scope: ast.AST = tree
    for name in parts:
        for node in ast.walk(scope):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and node.name == name:
                scope = node
                break
    return scope
