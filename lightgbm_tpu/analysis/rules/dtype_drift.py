"""Rule: dtype-drift — float64 host values flowing toward device code.

TPUs have no float64 units and jax runs with x64 disabled: a ``np.float64``
array crossing ``jnp.asarray`` / ``device_put`` is silently downcast to
float32 — which either wastes the host-side double-precision work, or (the
dangerous case) breaks bit-parity with LightGBM's histogram semantics (Ke et
al. 2017) when one code path accumulates in f64 and a supposedly-identical
device path accumulates in f32. The drift is invisible at the call site; this
rule makes it a reviewable decision.

Two sub-patterns, both scoped to functions that actually touch the device API
(a pure-host f64 helper is fine and common — model text I/O is f64 on
purpose):

1. an explicit float64 construction (``dtype=np.float64`` / ``"float64"`` /
   ``.astype(np.float64)``) in a function that also calls ``jnp.*`` /
   ``jax.device_put`` — either route it through an explicit f32 cast before
   upload or suppress with a comment stating the precision requirement;
2. ``jnp.asarray(x)`` where ``x`` was built in the same function by a numpy
   constructor with NO dtype (numpy defaults to float64): the implicit-
   default version of the same drift.
3. a WIDE-INT device request: a ``jnp`` constructor asked for
   ``int64``/``uint64`` (or ``.astype(jnp.int64)``) — with x64 disabled jax
   silently narrows the result to int32. For plain indices that truncation
   is usually survivable; for a word whose high bits carry a payload (a
   hash, several fields packed into one integer) it corrupts them with no
   error anywhere. Host-side ``np.int64``
   is NOT flagged — numpy keeps 64 bits; only the jnp-side request lies.

An f64 construction immediately wrapped in ``.astype(np.float32)`` is not
flagged (the precision is transient and the device dtype is explicit); the
same for a wide-int construction immediately ``.astype``-narrowed to int32.
"""
from __future__ import annotations

import ast

from ..astwalk import walk
from typing import Dict, Set

from ..core import ModuleContext, Rule, register

_NP_CTORS = {"zeros", "ones", "empty", "full", "array", "asarray", "arange"}
_DTYPELESS_EXEMPT = {"arange"}   # int result for int args; rarely the hazard


@register
class DtypeDrift(Rule):
    name = "dtype-drift"
    severity = "error"
    description = ("np.float64 (explicit or numpy-default) constructed in a "
                   "function that uploads to device, or a jnp int64/uint64 "
                   "request that x64-disabled jax silently narrows")
    rationale = ("TPU f64 is silently downcast at jnp.asarray; split f64/f32 "
                 "accumulation breaks histogram parity with the reference, "
                 "and narrowed int64 corrupts words packed past bit 31")

    def check_module(self, ctx: ModuleContext) -> None:
        if not ctx.jnp_aliases and not ctx.jax_aliases:
            return   # module never touches the device API
        for fn in walk(ctx.tree):
            if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self._check_function(ctx, fn)

    def _check_function(self, ctx: ModuleContext, fn: ast.AST) -> None:
        if not ctx.mentions_device_api(fn):
            return
        dtypeless_np_vars: Dict[str, int] = {}
        reported: Set[int] = set()
        for node in walk(fn):
            if not isinstance(node, ast.Call):
                continue
            # explicit float64 construction near device code
            if self._is_f64_call(ctx, node) and \
                    not self._astype_cast_parent(ctx, node, _is_f32_expr) and \
                    id(node) not in reported:
                reported.add(id(node))
                ctx.report(self, node,
                           "float64 constructed in a function that touches "
                           "the device API; TPU downcasts to f32 at upload "
                           "— cast explicitly, or suppress with a comment "
                           "stating the precision requirement")
            # wide-int device request: jnp ctor dtype=int64/uint64 (or
            # .astype(jnp.int64)) — x64-disabled jax narrows to int32
            # silently, which shears the high bits off any packed word
            if self._is_i64_call(ctx, node) and \
                    not self._astype_cast_parent(ctx, node, _is_i32_expr) and \
                    id(node) not in reported:
                reported.add(id(node))
                ctx.report(self, node,
                           "int64/uint64 requested for a device array; "
                           "x64-disabled jax silently narrows to int32 — "
                           "packed words lose their high bits "
                           "with no error; build in int32 (numpy keeps "
                           "64-bit host-side), or suppress with a comment "
                           "stating why the width survives")
            # record dtype-less numpy ctor assignments (implicit float64)
            if isinstance(node.func, ast.Attribute) and \
                    ctx.is_np_attr(node.func) and \
                    node.func.attr in (_NP_CTORS - _DTYPELESS_EXEMPT) and \
                    not any(kw.arg == "dtype" for kw in node.keywords) and \
                    len(node.args) < _dtype_pos(node.func.attr) + 1:
                parent = ctx.parents.get(node)
                if isinstance(parent, ast.Assign):
                    for t in parent.targets:
                        if isinstance(t, ast.Name):
                            dtypeless_np_vars[t.id] = node.lineno
            # jnp.asarray(x) on an implicit-f64 local
            if ctx.is_jnp_attr(node.func) and \
                    node.func.attr in ("asarray", "array") and node.args:
                arg = node.args[0]
                if isinstance(arg, ast.Name) and \
                        arg.id in dtypeless_np_vars and \
                        not any(kw.arg == "dtype" for kw in node.keywords):
                    ctx.report(self, node,
                               f"jnp.{node.func.attr}({arg.id}) uploads a "
                               "numpy array built with the float64 default "
                               f"(line {dtypeless_np_vars[arg.id]}); pass "
                               "an explicit dtype at one end",
                               severity="warning")

    def _is_f64_call(self, ctx: ModuleContext, node: ast.Call) -> bool:
        f = node.func
        # .astype(np.float64 / "float64")
        if isinstance(f, ast.Attribute) and f.attr == "astype" and node.args:
            return _is_f64_expr(ctx, node.args[0])
        # np/jnp ctor with dtype=float64 (kwarg or the positional slot)
        is_ctor = ((ctx.is_np_attr(f) or ctx.is_jnp_attr(f))
                   and f.attr in _NP_CTORS)
        if not is_ctor:
            return False
        for kw in node.keywords:
            if kw.arg == "dtype" and _is_f64_expr(ctx, kw.value):
                return True
        pos = _dtype_pos(f.attr)
        if len(node.args) > pos and _is_f64_expr(ctx, node.args[pos]):
            return True
        return False

    def _is_i64_call(self, ctx: ModuleContext, node: ast.Call) -> bool:
        """A construction that asks the DEVICE for a 64-bit integer: a jnp
        constructor with dtype int64/uint64, or ``.astype(jnp.int64)`` (the
        jnp attribute specifically — ``x.astype(np.int64)`` stays host-side
        numpy and keeps its 64 bits, so it is not flagged)."""
        f = node.func
        if isinstance(f, ast.Attribute) and f.attr == "astype" and node.args:
            a = node.args[0]
            return ctx.is_jnp_attr(a) and a.attr in ("int64", "uint64")
        if not (ctx.is_jnp_attr(f) and f.attr in _NP_CTORS):
            return False
        for kw in node.keywords:
            if kw.arg == "dtype" and _is_i64_expr(ctx, kw.value):
                return True
        pos = _dtype_pos(f.attr)
        if len(node.args) > pos and _is_i64_expr(ctx, node.args[pos]):
            return True
        return False

    def _astype_cast_parent(self, ctx: ModuleContext, node: ast.AST,
                            pred) -> bool:
        """True when the value is immediately ``.astype(<narrow dtype>)``'d
        (``pred`` matches the target) — transient width, no drift."""
        parent = ctx.parents.get(node)
        attr = parent if isinstance(parent, ast.Attribute) else None
        if attr is not None and attr.attr == "astype":
            call = ctx.parents.get(attr)
            if isinstance(call, ast.Call) and call.args and \
                    pred(ctx, call.args[0]):
                return True
        return False


def _dtype_pos(ctor: str) -> int:
    """Positional index of ``dtype`` for the numpy constructors we match."""
    return {"full": 2, "arange": 3}.get(ctor, 1)


def _is_f64_expr(ctx: ModuleContext, node: ast.AST) -> bool:
    if isinstance(node, ast.Constant) and node.value == "float64":
        return True
    return isinstance(node, ast.Attribute) and node.attr == "float64"


def _is_f32_expr(ctx: ModuleContext, node: ast.AST) -> bool:
    if isinstance(node, ast.Constant) and node.value == "float32":
        return True
    return isinstance(node, ast.Attribute) and node.attr in ("float32",
                                                             "bfloat16")


def _is_i64_expr(ctx: ModuleContext, node: ast.AST) -> bool:
    if isinstance(node, ast.Constant) and node.value in ("int64", "uint64"):
        return True
    return isinstance(node, ast.Attribute) and node.attr in ("int64",
                                                             "uint64")


def _is_i32_expr(ctx: ModuleContext, node: ast.AST) -> bool:
    if isinstance(node, ast.Constant) and node.value in ("int32", "uint32"):
        return True
    return isinstance(node, ast.Attribute) and node.attr in ("int32",
                                                             "uint32")
