"""JAX trace-safety rules (JAX1xx).

The hazards this pack catches compile fine and pass a green test run:
a Python ``if`` on a tracer raises only on the shapes that reach it, a
``print`` inside a jitted body fires once at trace time and never
again, ``np.`` on a tracer silently falls back to host transfers, an
unhashable static arg or an f-string/``id()`` cache key recompiles per
call.  The PR 1 ``core/temporal.py`` shard_map miscompile hid behind
exactly this opacity — the program *ran*, it just didn't run the code
everyone read.

Scope: functions *reachable from a jit/shard_map/pallas_call wrap
site within the same file* — decorated functions, functions passed to
``jax.jit(...)`` / ``shard_map(...)`` / ``pallas_call(...)``, their
nested ``def``s, and local functions they call (fixed point).  Data
params are the wrapped function's params minus its declared
``static_argnames``/``static_argnums``; a light forward taint pass
follows assignments so derived values count too.

Rules::

  JAX101  Python branch (`if`/`while`/`assert`) on a traced value
  JAX102  Python side effect inside a traced body (print / global /
          mutation of closure or module state)
  JAX103  np.* called on a traced value (host round-trip per call)
  JAX104  static arg with an unhashable (list/dict/set) default
  JAX105  f-string or id() used as a cache key (silent recompiles:
          id() is reused after GC, f-strings hash by text, dicts by
          insertion order)
  JAX106  host callback (jax.debug.print / io_callback / pure_callback)
          inside a traced hot path
"""
from __future__ import annotations

import ast
from typing import Iterable, Optional

from repro.analysis.engine import (
    FileContext, Finding, Rule, call_name, const_str, dotted_name,
)

_TARGETS = (
    "src/repro/engine/**",
    "src/repro/kernels/**",
    "src/repro/core/**",
)

_JIT_WRAPPERS = {
    "jax.jit", "jit", "jax.pjit", "pjit",
}
_TRACE_WRAPPERS = _JIT_WRAPPERS | {
    "shard_map", "jax.shard_map",
    "pallas_call", "pl.pallas_call", "jax.experimental.pallas.pallas_call",
    "jax.vmap", "vmap", "jax.grad", "grad", "jax.value_and_grad",
    "jax.lax.scan", "lax.scan", "jax.lax.fori_loop", "lax.fori_loop",
    "jax.lax.while_loop", "lax.while_loop",
}

# attribute reads that are static under tracing (shape metadata, config)
_SAFE_ATTRS = {
    "shape", "ndim", "dtype", "size", "sharding", "aval", "weak_type",
}
_SAFE_CALLS = {
    "len", "isinstance", "hasattr", "getattr", "type", "issubclass",
    "callable", "repr", "str",
}
_TRACED_PRODUCERS = ("jnp.", "jax.numpy.", "jax.lax.", "lax.", "jax.nn.",
                     "jnn.")

_HOST_CALLBACKS = {
    "jax.debug.print", "jax.debug.callback", "jax.debug.breakpoint",
    "jax.experimental.io_callback", "io_callback",
    "jax.pure_callback", "pure_callback",
    "jax.experimental.host_callback.call", "host_callback.call",
    "jax.experimental.host_callback.id_tap", "host_callback.id_tap",
}

_MUTATORS = {"append", "extend", "insert", "add", "update", "setdefault",
             "pop", "popitem", "clear", "remove", "discard"}

# annotations that mark a param as trace-time Python config, never a tracer
_STATIC_ANNOTATIONS = {"bool", "str", "bytes", "int"}


# ---------------------------------------------------------------------------
# traced-function discovery
# ---------------------------------------------------------------------------


def _static_names_from_call(call: ast.Call,
                            fn: Optional[ast.FunctionDef]) -> set:
    """Param names declared static at a wrap site."""
    static: set = set()
    for kw in call.keywords:
        if kw.arg == "static_argnames":
            for n in ast.walk(kw.value):
                s = const_str(n)
                if s:
                    static.add(s)
        elif kw.arg == "static_argnums" and fn is not None:
            params = [a.arg for a in fn.args.args]
            for n in ast.walk(kw.value):
                if isinstance(n, ast.Constant) and isinstance(n.value, int):
                    if 0 <= n.value < len(params):
                        static.add(params[n.value])
    return static


class _TracedSet:
    """Functions reachable from a trace-wrap site, with their data params."""

    def __init__(self, tree: ast.AST):
        # every def in the file, by name (best effort on shadowing: last
        # definition wins, which matches runtime for module-level defs)
        self.defs: dict[str, ast.AST] = {}
        self.parents: dict[ast.AST, ast.AST] = {}
        for node in ast.walk(tree):
            for child in ast.iter_child_nodes(node):
                self.parents[child] = node
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.defs[node.name] = node
        # name -> static param names (from wrap sites / decorators)
        self.static: dict[str, set] = {}
        roots: set = set()

        def mark(name: Optional[str], static: set) -> None:
            if name and name in self.defs:
                roots.add(name)
                self.static.setdefault(name, set()).update(static)

        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    dn = dotted_name(dec)
                    if dn in _TRACE_WRAPPERS:
                        mark(node.name, set())
                    elif isinstance(dec, ast.Call):
                        cn = call_name(dec)
                        if cn in _TRACE_WRAPPERS:
                            mark(node.name,
                                 _static_names_from_call(dec, node))
                        elif cn in ("partial", "functools.partial") and \
                                dec.args and \
                                dotted_name(dec.args[0]) in _TRACE_WRAPPERS:
                            mark(node.name,
                                 _static_names_from_call(dec, node))
            elif isinstance(node, ast.Call):
                if call_name(node) in _TRACE_WRAPPERS and node.args:
                    target = node.args[0]
                    if isinstance(target, ast.Name):
                        fn = self.defs.get(target.id)
                        mark(target.id, _static_names_from_call(
                            node, fn if isinstance(
                                fn, ast.FunctionDef) else None))
        # fixed point: local functions *called from* a traced function are
        # traced too (their bodies inline into the trace)
        self.traced: set = set(roots)
        changed = True
        while changed:
            changed = False
            for name in list(self.traced):
                fn = self.defs[name]
                for sub in ast.walk(fn):
                    if isinstance(sub, ast.Call) and \
                            isinstance(sub.func, ast.Name) and \
                            sub.func.id in self.defs and \
                            sub.func.id not in self.traced:
                        self.traced.add(sub.func.id)
                        changed = True
        # nested defs inside a traced function are traced (closures the
        # trace runs); record them as AST nodes rather than names
        self.traced_nodes: list = []
        for name in self.traced:
            fn = self.defs[name]
            self.traced_nodes.append(fn)

    def data_params(self, fn: ast.AST) -> set:
        static = self.static.get(getattr(fn, "name", ""), set())
        args = fn.args
        params = (list(args.posonlyargs) + list(args.args)
                  + list(args.kwonlyargs))
        # a param annotated with a plain-Python static type is trace-time
        # config, not a tracer (e.g. `def _acts(pwl: bool)`)
        names = [a.arg for a in params
                 if a.annotation is None
                 or dotted_name(a.annotation) not in _STATIC_ANNOTATIONS]
        if args.vararg:
            names.append(args.vararg.arg)
        return {n for n in names if n not in static and n != "self"}


# ---------------------------------------------------------------------------
# taint within one traced function
# ---------------------------------------------------------------------------


def _taint(fn: ast.AST, seeds: set) -> set:
    """Names carrying traced values: the data params plus anything
    assigned from a tainted expression or a jnp/lax producer call.  Two
    passes are enough for the straight-line bodies this repo writes."""
    tainted = set(seeds)
    for _ in range(2):
        for node in ast.walk(fn):
            value = None
            targets: list = []
            if isinstance(node, ast.Assign):
                value, targets = node.value, node.targets
            elif isinstance(node, ast.AugAssign):
                value, targets = node.value, [node.target]
            elif isinstance(node, ast.AnnAssign) and node.value is not None:
                value, targets = node.value, [node.target]
            elif isinstance(node, (ast.For, ast.AsyncFor)):
                value, targets = node.iter, [node.target]
            if value is None or not _expr_traced(value, tainted):
                continue
            for t in targets:
                for n in ast.walk(t):
                    if isinstance(n, ast.Name):
                        tainted.add(n.id)
    return tainted


def _expr_traced(expr: ast.AST, tainted: set) -> bool:
    """Does ``expr`` (likely) evaluate to a traced value?"""
    if isinstance(expr, ast.Name):
        return expr.id in tainted
    if isinstance(expr, ast.Constant):
        return False
    if isinstance(expr, ast.Attribute):
        if expr.attr in _SAFE_ATTRS:
            return False
        return _expr_traced(expr.value, tainted)
    if isinstance(expr, ast.Call):
        name = call_name(expr)
        if name in _SAFE_CALLS:
            return False
        if any(name.startswith(p) for p in _TRACED_PRODUCERS):
            return True
        return any(_expr_traced(a, tainted) for a in expr.args) or any(
            _expr_traced(kw.value, tainted) for kw in expr.keywords)
    if isinstance(expr, ast.Compare):
        comparators = [expr.left] + list(expr.comparators)
        if all(isinstance(c, ast.Constant) and c.value is None
               for c in comparators[1:]):
            return False  # `x is None` is a static (weak-type) check
        return any(_expr_traced(c, tainted) for c in comparators)
    if isinstance(expr, (ast.BoolOp, ast.BinOp, ast.UnaryOp, ast.IfExp,
                         ast.Subscript, ast.Tuple, ast.List, ast.Starred)):
        return any(_expr_traced(c, tainted)
                   for c in ast.iter_child_nodes(expr)
                   if isinstance(c, ast.expr))
    return False


def _local_bindings(fn: ast.AST) -> set:
    """Names bound inside ``fn``: params, assignments, loop vars, withitems,
    comprehension vars, nested defs — mutation of anything else leaks a
    side effect (and possibly a tracer) out of the trace."""
    bound: set = set()
    args = fn.args
    for a in (list(args.posonlyargs) + list(args.args)
              + list(args.kwonlyargs)
              + ([args.vararg] if args.vararg else [])
              + ([args.kwarg] if args.kwarg else [])):
        bound.add(a.arg)
    for node in ast.walk(fn):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)) and node is not fn:
            bound.add(node.name)
        elif isinstance(node, ast.Name) and isinstance(
                node.ctx, (ast.Store, ast.Del)):
            bound.add(node.id)
        elif isinstance(node, ast.comprehension):
            for n in ast.walk(node.target):
                if isinstance(n, ast.Name):
                    bound.add(n.id)
    return bound


# ---------------------------------------------------------------------------
# the rules
# ---------------------------------------------------------------------------


def _iter_traced(ctx: FileContext):
    ts = _TracedSet(ctx.tree)
    for fn in ts.traced_nodes:
        yield ts, fn


def check_tracer_branch(ctx: FileContext) -> Iterable[Finding]:
    for ts, fn in _iter_traced(ctx):
        tainted = _taint(fn, ts.data_params(fn))
        for node in ast.walk(fn):
            if isinstance(node, (ast.If, ast.While)):
                test = node.test
            elif isinstance(node, ast.Assert):
                test = node.test
            else:
                continue
            if _expr_traced(test, tainted):
                kind = type(node).__name__.lower()
                yield ctx.finding(
                    "JAX101", node,
                    f"`{kind}` on a traced value inside `{fn.name}` "
                    f"(reachable from a jit/shard_map wrap site): "
                    f"concrete boolean on a tracer raises "
                    f"TracerBoolConversionError on some inputs and "
                    f"silently specializes on others — use jnp.where/"
                    f"lax.cond, or declare the arg static",
                )


def check_side_effect(ctx: FileContext) -> Iterable[Finding]:
    for ts, fn in _iter_traced(ctx):
        local = _local_bindings(fn)
        for node in ast.walk(fn):
            if isinstance(node, ast.Call) and call_name(node) == "print":
                yield ctx.finding(
                    "JAX102", node,
                    f"print() inside traced `{fn.name}`: fires once at "
                    f"trace time, never per call — use jax.debug.print "
                    f"deliberately or hoist it out of the jitted body",
                )
            elif isinstance(node, (ast.Global, ast.Nonlocal)):
                yield ctx.finding(
                    "JAX102", node,
                    f"`{type(node).__name__.lower()}` inside traced "
                    f"`{fn.name}`: rebinding outer state from a jitted "
                    f"body runs at trace time only and can leak tracers",
                )
            elif isinstance(node, ast.Call) and \
                    isinstance(node.func, ast.Attribute) and \
                    node.func.attr in _MUTATORS and \
                    isinstance(node.func.value, ast.Name) and \
                    node.func.value.id not in local:
                yield ctx.finding(
                    "JAX102", node,
                    f"`{node.func.value.id}.{node.func.attr}(...)` "
                    f"mutates non-local state inside traced `{fn.name}`: "
                    f"runs once at trace time and leaks tracers into "
                    f"`{node.func.value.id}`",
                )


def check_np_on_tracer(ctx: FileContext) -> Iterable[Finding]:
    for ts, fn in _iter_traced(ctx):
        tainted = _taint(fn, ts.data_params(fn))
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            name = call_name(node)
            if not (name.startswith("np.") or name.startswith("numpy.")):
                continue
            if any(_expr_traced(a, tainted) for a in node.args) or any(
                    _expr_traced(kw.value, tainted)
                    for kw in node.keywords):
                yield ctx.finding(
                    "JAX103", node,
                    f"`{name}` called on a traced value inside "
                    f"`{fn.name}`: forces a host round-trip per call "
                    f"(or a ConcretizationTypeError) — use the jnp "
                    f"equivalent",
                )


def check_unhashable_static(ctx: FileContext) -> Iterable[Finding]:
    defs = {n.name: n for n in ast.walk(ctx.tree)
            if isinstance(n, ast.FunctionDef)}

    def bad_default(expr: ast.AST) -> bool:
        if isinstance(expr, (ast.List, ast.Dict, ast.Set, ast.ListComp,
                             ast.DictComp, ast.SetComp)):
            return True
        return (isinstance(expr, ast.Call)
                and call_name(expr) in ("list", "dict", "set"))

    def check_wrap(call: ast.Call, fn: Optional[ast.FunctionDef]):
        if fn is None:
            return
        static = _static_names_from_call(call, fn)
        if not static:
            return
        args = fn.args
        positional = [a.arg for a in
                      list(args.posonlyargs) + list(args.args)]
        pairs = list(zip(positional[len(positional) - len(args.defaults):],
                         args.defaults))
        pairs += [(a.arg, d) for a, d in
                  zip(args.kwonlyargs, args.kw_defaults) if d is not None]
        for pname, d in pairs:
            if pname in static and bad_default(d):
                yield ctx.finding(
                    "JAX104", d,
                    f"static arg `{pname}` of `{fn.name}` defaults to an "
                    f"unhashable {type(d).__name__.lower()}: jit static "
                    f"args key the compile cache by hash (dicts also by "
                    f"insertion order) — use a tuple/frozen value",
                )

    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Call) and call_name(node) in _JIT_WRAPPERS:
            target = node.args[0] if node.args else None
            fn = (defs.get(target.id)
                  if isinstance(target, ast.Name) else None)
            yield from check_wrap(node, fn)
        elif isinstance(node, ast.FunctionDef):
            for dec in node.decorator_list:
                if isinstance(dec, ast.Call):
                    cn = call_name(dec)
                    if cn in _JIT_WRAPPERS or (
                            cn in ("partial", "functools.partial")
                            and dec.args
                            and dotted_name(dec.args[0]) in _JIT_WRAPPERS):
                        yield from check_wrap(dec, node)


def _is_cachey(expr: ast.AST) -> bool:
    name = dotted_name(expr)
    last = name.rsplit(".", 1)[-1].lower()
    return "cache" in last


def _unstable_key(expr: ast.AST) -> Optional[str]:
    for node in ast.walk(expr):
        if isinstance(node, ast.JoinedStr):
            return "an f-string"
        if isinstance(node, ast.Call) and call_name(node) == "id":
            return "id(...)"
    return None


def check_cache_key(ctx: FileContext) -> Iterable[Finding]:
    for node in ast.walk(ctx.tree):
        key_expr = None
        base = None
        if isinstance(node, ast.Subscript) and _is_cachey(node.value):
            key_expr, base = node.slice, node.value
        elif isinstance(node, ast.Call) and \
                isinstance(node.func, ast.Attribute) and \
                node.func.attr in ("get", "setdefault", "pop") and \
                _is_cachey(node.func.value) and node.args:
            key_expr, base = node.args[0], node.func.value
        if key_expr is None:
            continue
        what = _unstable_key(key_expr)
        if what:
            yield ctx.finding(
                "JAX105", node,
                f"{what} used as a key into `{dotted_name(base)}`: "
                f"id() values are recycled after GC and f-strings hash "
                f"by rendered text — both silently miss (and recompile) "
                f"where a structural tuple key would hit",
            )


def check_host_callback(ctx: FileContext) -> Iterable[Finding]:
    for ts, fn in _iter_traced(ctx):
        for node in ast.walk(fn):
            if isinstance(node, ast.Call) and \
                    call_name(node) in _HOST_CALLBACKS:
                yield ctx.finding(
                    "JAX106", node,
                    f"host callback `{call_name(node)}` inside traced "
                    f"`{fn.name}`: synchronizes device->host every call "
                    f"— keep it out of serving hot paths (or gate it "
                    f"behind a debug flag)",
                )


FILE_RULES = [
    Rule("JAX101", "Python branch on a traced value",
         check_tracer_branch, _TARGETS),
    Rule("JAX102", "Python side effect inside a traced body",
         check_side_effect, _TARGETS),
    Rule("JAX103", "np.* on a traced value", check_np_on_tracer, _TARGETS),
    Rule("JAX104", "unhashable static arg default",
         check_unhashable_static, _TARGETS),
    Rule("JAX105", "f-string / id() cache key", check_cache_key, _TARGETS),
    Rule("JAX106", "host callback in a traced hot path",
         check_host_callback, _TARGETS),
]
