"""Find jit-traced regions in a module, without importing it.

A "traced region" is a function body that jax will TRACE rather than
run: Python side effects inside one silently execute once at trace time
and never again (CL001), and host syncs inside one either error out or
force a device round-trip per call (CL006).

Detection is per-file and name-based (no cross-module resolution — a
linter that imported jax to resolve objects would drag device init into
a gate that must stay CPU-only and fast):

- decorators: ``@jax.jit``, ``@jit``, ``@jax.pmap``, ``@pmap``,
  ``@partial(jax.jit, ...)`` / ``@functools.partial(jax.jit, ...)``;
- call sites: ``jax.jit(f)``, ``jit(f)``, ``pmap(f)``,
  ``shard_map(f, ...)`` / ``jax.shard_map(f, ...)`` — where ``f`` is a
  lambda or a Name that resolves to a function defined in this file;
- nesting: everything lexically inside a traced function is traced.
"""

from __future__ import annotations

import ast
from typing import Iterator

TRACER_NAMES = {"jit", "pmap", "shard_map"}


def _call_traces(func: ast.expr) -> bool:
    """Does this call expression's callee name a tracing transform?"""
    if isinstance(func, ast.Name):
        return func.id in TRACER_NAMES
    if isinstance(func, ast.Attribute):
        # jax.jit / jax.pmap / jax.shard_map / jax.experimental...
        return func.attr in TRACER_NAMES
    return False


def _decorator_traces(dec: ast.expr) -> bool:
    if isinstance(dec, (ast.Name, ast.Attribute)):
        return _call_traces(dec)
    if isinstance(dec, ast.Call):
        if _call_traces(dec.func):                 # @jax.jit(static_...)
            return True
        # @partial(jax.jit, ...) / @functools.partial(jax.jit, ...)
        callee = dec.func
        is_partial = (
            (isinstance(callee, ast.Name) and callee.id == "partial")
            or (isinstance(callee, ast.Attribute)
                and callee.attr == "partial")
        )
        if is_partial and dec.args:
            return _call_traces(dec.args[0])
    return False


def _function_defs_by_name(tree: ast.AST) -> dict:
    """Every def in the file, keyed by name (all scopes flattened — good
    enough for single-file heuristics; a false merge only widens the
    scanned region)."""
    out: dict = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            out.setdefault(node.name, []).append(node)
    return out


def traced_regions(tree: ast.AST) -> list:
    """The function/lambda nodes whose bodies jax traces in this file."""
    defs = _function_defs_by_name(tree)
    regions: list = []
    seen: set = set()

    def add(node: ast.AST) -> None:
        if id(node) not in seen:
            seen.add(id(node))
            regions.append(node)

    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if any(_decorator_traces(d) for d in node.decorator_list):
                add(node)
        elif isinstance(node, ast.Call) and _call_traces(node.func):
            if not node.args:
                continue
            target = node.args[0]
            if isinstance(target, ast.Lambda):
                add(target)
            elif isinstance(target, ast.Name):
                for fn in defs.get(target.id, ()):
                    add(fn)
            elif isinstance(target, ast.Call) and _call_traces(target.func):
                # jax.jit(shard_map(inner, ...)) — handled when the inner
                # call is visited by the walk; nothing extra here.
                pass
    return regions


def walk_region(region: ast.AST) -> Iterator[ast.AST]:
    """Every node lexically inside a traced function body (decorators and
    default expressions run eagerly at def time, so they are skipped)."""
    if isinstance(region, ast.Lambda):
        yield from ast.walk(region.body)
        return
    for stmt in region.body:
        yield from ast.walk(stmt)


def dotted_name(node: ast.expr) -> str:
    """``a.b.c`` for Name/Attribute chains, "" otherwise."""
    parts: list = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""
