"""Retrace-hazard checker: shard_map routing and jit cache hygiene.

Three finding ids, all rooted in incidents from PRs 1-2:

* ``retrace-shard-map`` — any direct use of ``jax.shard_map`` /
  ``jax.experimental.shard_map`` outside ``parallel/mesh.py``.  PR 2's
  ``shard_map_compat`` is the ONE call site of that API (its spelling
  has changed between JAX releases: ``check_rep`` became ``check_vma``);
  a second direct call site reintroduces the exact class of breakage
  that un-failed fifteen tier-1 tests when it was fixed.
* ``retrace-jit-in-loop`` — ``jax.jit(...)`` (or ``shard_map_compat``)
  invoked lexically inside a ``for``/``while`` body.  Each call builds
  a fresh callable with an empty compilation cache, so every iteration
  recompiles — the "silent retrace" the budget accountant flags at
  runtime (PR 1), caught before it ships.  Hoist the jit (or cache it
  like ``_ring_kernel``'s ``lru_cache``).
* ``retrace-static-unhashable`` — a jitted function whose
  ``static_argnums``/``static_argnames`` designates a parameter with a
  mutable default (list/dict/set literal or constructor).  Static
  arguments are hashed into the jit cache key; an unhashable default
  raises at first call, and a freshly-constructed one can never hit the
  cache.
"""

from __future__ import annotations

import ast

from .core import dotted_name, register

_SHARD_MAP_HOME = "parallel/mesh.py"
_MUTABLE_CTORS = {"list", "dict", "set"}


def _is_jit_call(node):
    if not isinstance(node, ast.Call):
        return False
    name = dotted_name(node.func)
    return name in ("jax.jit", "jit", "shard_map_compat",
                    "mesh.shard_map_compat")


def _jit_target_and_kwargs(node):
    """For a ``jax.jit``/``partial(jax.jit, ...)`` call or decorator:
    ``(wrapped function expression or None, {kw: value})``."""
    if not isinstance(node, ast.Call):
        if dotted_name(node) in ("jax.jit", "jit"):
            return None, {}
        return None, None
    name = dotted_name(node.func)
    if name in ("jax.jit", "jit"):
        target = node.args[0] if node.args else None
        return target, {k.arg: k.value for k in node.keywords if k.arg}
    if name in ("functools.partial", "partial") and node.args:
        inner = dotted_name(node.args[0])
        if inner in ("jax.jit", "jit"):
            return None, {k.arg: k.value for k in node.keywords if k.arg}
    return None, None


def _mutable_default(node):
    if isinstance(node, (ast.List, ast.Dict, ast.Set)):
        return True
    if isinstance(node, ast.Call):
        return dotted_name(node.func) in _MUTABLE_CTORS
    return False


def _static_params(fn, kwargs):
    """Parameter names designated static by ``static_argnums``/
    ``static_argnames`` (best-effort: literal ints/strs only)."""
    names = set()
    args = fn.args.posonlyargs + fn.args.args
    nums = kwargs.get("static_argnums")
    for lit in _iter_literals(nums):
        if isinstance(lit, int) and 0 <= lit < len(args):
            names.add(args[lit].arg)
    for lit in _iter_literals(kwargs.get("static_argnames")):
        if isinstance(lit, str):
            names.add(lit)
    return names


def _iter_literals(node):
    if node is None:
        return
    if isinstance(node, ast.Constant):
        yield node.value
    elif isinstance(node, (ast.Tuple, ast.List, ast.Set)):
        for el in node.elts:
            if isinstance(el, ast.Constant):
                yield el.value


def _defaults_by_param(fn):
    """``{param name: default expression}`` (positional + kw-only)."""
    out = {}
    args = fn.args.posonlyargs + fn.args.args
    for arg, default in zip(reversed(args), reversed(fn.args.defaults)):
        out[arg.arg] = default
    for arg, default in zip(fn.args.kwonlyargs, fn.args.kw_defaults):
        if default is not None:
            out[arg.arg] = default
    return out


@register
class RetraceChecker:
    id = "retrace"
    ids = ("retrace-shard-map", "retrace-jit-in-loop",
           "retrace-static-unhashable")

    def check(self, ctx):
        out = []
        out.extend(self._shard_map(ctx))
        out.extend(self._jit_in_loop(ctx))
        out.extend(self._static_unhashable(ctx))
        return out

    # -- direct shard_map outside the compat seam ---------------------------

    def _shard_map(self, ctx):
        if ctx.pkgpath == _SHARD_MAP_HOME:
            return []
        out = []
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.ImportFrom):
                if node.module == "jax.experimental.shard_map" or (
                        node.module == "jax" and any(
                            a.name == "shard_map" for a in node.names)):
                    out.append(ctx.finding(
                        node, "retrace-shard-map",
                        "direct shard_map import — route through "
                        "parallel.mesh.shard_map_compat (the one call "
                        "site of that API)"))
            elif isinstance(node, ast.Attribute):
                name = dotted_name(node)
                if name in ("jax.shard_map",
                            "jax.experimental.shard_map.shard_map"):
                    out.append(ctx.finding(
                        node, "retrace-shard-map",
                        f"direct {name} use — route through "
                        "parallel.mesh.shard_map_compat"))
        return out

    # -- jit built per loop iteration ---------------------------------------

    def _jit_in_loop(self, ctx):
        out = []
        reported = set()  # nested loops revisit the same call node
        for loop in ast.walk(ctx.tree):
            if not isinstance(loop, (ast.For, ast.While, ast.AsyncFor)):
                continue
            for node in ast.walk(loop):
                if (node is loop or id(node) in reported
                        or not _is_jit_call(node)):
                    continue
                reported.add(id(node))
                callee = dotted_name(node.func)
                out.append(ctx.finding(
                    node, "retrace-jit-in-loop",
                    f"{callee}(...) inside a loop builds a fresh "
                    "callable (empty jit cache) every iteration — "
                    "hoist it, or cache per geometry like "
                    "_ring_kernel's lru_cache"))
        return out

    # -- unhashable static defaults -----------------------------------------

    def _static_unhashable(self, ctx):
        out = []
        fns = {n.name: n for n in ast.walk(ctx.tree)
               if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))}
        for node in ast.walk(ctx.tree):
            fn = None
            kwargs = None
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    target, kw = _jit_target_and_kwargs(dec)
                    if kw is not None:
                        fn, kwargs = node, kw
                        break
            elif _is_jit_call(node) and dotted_name(node.func) in (
                    "jax.jit", "jit"):
                target, kwargs = _jit_target_and_kwargs(node)
                if isinstance(target, ast.Name):
                    fn = fns.get(target.id)
            if fn is None or not kwargs:
                continue
            static = _static_params(fn, kwargs)
            if not static:
                continue
            defaults = _defaults_by_param(fn)
            for pname in sorted(static):
                default = defaults.get(pname)
                if default is not None and _mutable_default(default):
                    out.append(ctx.finding(
                        default, "retrace-static-unhashable",
                        f"static argument {pname!r} of jitted "
                        f"{fn.name}() has a mutable (unhashable) "
                        "default — jit hashes statics into its cache "
                        "key; use a tuple/frozen value"))
        return out
