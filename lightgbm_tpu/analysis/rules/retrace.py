"""Rule: retrace-hazard — patterns that make XLA recompile more than once.

Three sub-patterns, all observed (and paid for) in this codebase's history
(the r5 compile-time regression was exactly an executable-variant explosion):

1. **jit-in-function**: ``jax.jit(...)`` / ``partial(jax.jit, ...)`` executed
   inside a function body builds a FRESH wrapper per call; jax's trace cache
   is keyed by function identity, so a closure or lambda created on each call
   retraces (and recompiles) every time. Hoist the wrapper to module level or
   cache it on the instance — when the caching is deliberate and guarded,
   suppress with a justification.
2. **unhashable-static**: a parameter declared via ``static_argnames`` /
   ``static_argnums`` whose default is a list/dict/set literal — static args
   are hash-keyed, so an unhashable default raises at call time, and a
   mutable one silently keys the cache by identity (retrace per instance).
   Also flags ``static_argnames`` naming a parameter the function does not
   have, and ``static_argnums`` indices outside the function's positional
   parameter range (both are the undeclared-static case: jax either errors
   late or the intended arg simply stays traced, and every distinct value
   retraces — e.g. a ``pack_k`` guard-bit width meant to be a compile-time
   constant would quietly become a per-value executable variant).
3. **traced-branch**: an ``if``/``while`` test built from a ``jnp``/
   ``jax.lax`` call inside a jitted function — Python control flow on traced
   values fails at trace time; shape-based branching is fine (shapes are
   static) and is not flagged.
"""
from __future__ import annotations

import ast

from ..astwalk import walk
from typing import Optional, Set

from ..core import (ModuleContext, Rule, decorator_jit_call, is_jit_expr,
                    jit_call_info, register, static_names_from_call)


@register
class RetraceHazard(Rule):
    name = "retrace-hazard"
    severity = "error"
    description = ("jit wrapper built per call, unhashable/undeclared "
                   "static args, or Python branching on traced values")
    rationale = ("every retrace is a full trace+lower+compile (seconds for "
                 "the tree grower) and a new executable variant in the "
                 "cache")

    def check_module(self, ctx: ModuleContext) -> None:
        for node in walk(ctx.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self._check_decorators(ctx, node)
        self._check_jit_calls(ctx)
        self._check_traced_branches(ctx)

    def _check_decorators(self, ctx: ModuleContext, fn: ast.AST) -> None:
        for dec in fn.decorator_list:
            call = decorator_jit_call(dec)
            if call is None and not is_jit_expr(dec):
                continue
            self._check_static_args(ctx, call, fn)

    def _check_static_args(self, ctx: ModuleContext,
                           call: Optional[ast.Call], fn: ast.AST) -> None:
        if call is None:
            return
        a = fn.args
        params = [p.arg for p in a.posonlyargs + a.args + a.kwonlyargs]
        defaults = dict(zip([p.arg for p in a.posonlyargs + a.args]
                            [len(a.posonlyargs) + len(a.args)
                             - len(a.defaults):], a.defaults))
        declared: Set[str] = set()
        for kw in call.keywords:
            if kw.arg == "static_argnames":
                for sub in walk(kw.value):
                    if isinstance(sub, ast.Constant) and \
                            isinstance(sub.value, str):
                        declared.add(sub.value)
        for name in declared:
            if name not in params:
                ctx.report(self, call,
                           f"static_argnames names {name!r} but "
                           f"{getattr(fn, 'name', '<lambda>')}() has no "
                           "such parameter; the real arg stays traced and "
                           "every distinct value retraces")
        # static_argnums past the positional parameter list: the index maps
        # to nothing, so the arg it was meant to pin stays traced (and in a
        # *args function jax may only fail at call time, if at all)
        pos_params = [p.arg for p in a.posonlyargs + a.args]
        for kw in call.keywords:
            if kw.arg != "static_argnums":
                continue
            for sub in walk(kw.value):
                if isinstance(sub, ast.Constant) and \
                        isinstance(sub.value, int) and \
                        not isinstance(sub.value, bool) and \
                        not 0 <= sub.value < len(pos_params):
                    ctx.report(self, call,
                               f"static_argnums index {sub.value} is out of "
                               "range for "
                               f"{getattr(fn, 'name', '<lambda>')}()'s "
                               f"{len(pos_params)} positional parameter(s); "
                               "the intended arg stays traced and every "
                               "distinct value retraces")
        for name in declared | static_names_from_call(call, fn):
            d = defaults.get(name)
            if isinstance(d, (ast.List, ast.Dict, ast.Set)):
                ctx.report(self, d,
                           f"static arg {name!r} defaults to an unhashable "
                           f"{type(d).__name__.lower()} literal; static "
                           "args are hash-keyed — use a tuple or a frozen "
                           "dataclass")

    def _check_jit_calls(self, ctx: ModuleContext) -> None:
        """Flag jit-wrapper construction that re-executes per call: a plain
        ``jax.jit(...)`` call inside a function body, or a jit-decorated def
        nested inside another function (fresh function object per outer
        call => fresh trace-cache key => retrace)."""
        fdefs = (ast.FunctionDef, ast.AsyncFunctionDef)
        deco_nodes: Set[int] = set()       # ids of decorator-subtree nodes
        for fn in walk(ctx.tree):
            if not isinstance(fn, fdefs):
                continue
            jit_deco = any(is_jit_expr(d) or jit_call_info(d) is not None
                           for d in fn.decorator_list)
            for dec in fn.decorator_list:
                for sub in walk(dec):
                    deco_nodes.add(id(sub))
            if jit_deco and any(isinstance(anc, fdefs)
                                for anc in ctx.ancestors(fn)):
                ctx.report(self, fn,
                           f"jit-decorated def {fn.name}() nested inside a "
                           "function is re-created (and retraced) on every "
                           "outer call; hoist it or cache the wrapper")
        for node in walk(ctx.tree):
            call = jit_call_info(node)
            if call is None or id(call) in deco_nodes:
                continue
            if any(isinstance(anc, fdefs) for anc in ctx.ancestors(call)):
                ctx.report(self, call,
                           "jax.jit(...) executed inside a function builds "
                           "a fresh wrapper (and retraces) on every call; "
                           "hoist it to module level or cache it on the "
                           "instance")

    def _check_traced_branches(self, ctx: ModuleContext) -> None:
        # jitted defs: decorated only (wrapped-by-name bodies are usually
        # shared with non-jit callers, where host branching is legal)
        for fn in walk(ctx.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if not any(is_jit_expr(d) or jit_call_info(d) is not None
                       for d in fn.decorator_list):
                continue
            for node in walk(fn):
                if not isinstance(node, (ast.If, ast.While)):
                    continue
                for sub in walk(node.test):
                    if isinstance(sub, ast.Call) and (
                            ctx.is_jnp_attr(sub.func)
                            or _is_lax_attr(ctx, sub.func)):
                        ctx.report(self, node,
                                   "Python branch on a traced value inside "
                                   "a jitted function fails at trace time; "
                                   "use jnp.where / lax.cond")
                        break


def _is_lax_attr(ctx: ModuleContext, node: ast.AST) -> bool:
    return (isinstance(node, ast.Attribute)
            and isinstance(node.value, ast.Attribute)
            and node.value.attr == "lax")
