"""Dtype checkers: ``float64-leak`` and ``bf16-cast``.

float64-leak
------------

Device code is float32/bfloat16/integer by design: ``jax_enable_x64``
stays off, accumulation dtypes are chosen per kernel (PR 4's review
explicitly removed full-size float64 temporaries), and a double-
precision array sneaking into a jitted program silently doubles HBM
traffic — the hot kernels are memory bound (the benchmark's
``fdmt_roofline`` reads them against the HBM roof), so a float64 leak
is a straight ~2x slowdown where it hurts most.  Host-side float64 (offset planning, reference-semantics numpy
paths, threshold math) is correct and deliberately common — so the
checker only flags **jnp/jax expressions**, where a 64-bit dtype is
either dead (x64 off: silently downcast, a lie in the source) or a
real widening:

* ``jnp.float64`` / ``jnp.int64`` / ``jnp.complex128`` attributes;
* ``jnp.*(..., dtype=<64-bit>)`` constructors (including string dtypes
  ``"float64"`` etc.) and ``.astype(<64-bit>)`` where the operand
  chain roots in ``jnp``/``jax``;
* ``jax.lax.convert_element_type(..., <64-bit>)``;
* ``jax.config.update("jax_enable_x64", True)`` in library modules —
  a process-global flag no kernel module may flip.

Scope: ``ops/`` and ``parallel/`` (the device-code layers).

bf16-cast
---------

Half-precision is allowed in device code ONLY through the
:mod:`~pulsarutils_tpu.precision` policy seam
(:func:`~pulsarutils_tpu.precision.cast_operand` plus the strategy
registry): an ad-hoc ``.astype(jnp.bfloat16)`` in a kernel silently
trades 16 significand bits for bandwidth with no declared error bound,
no autotuner equivalence gate and no byte-identity escape hatch — the
exact failure mode ISSUE 17's policy engine exists to prevent.  The
checker flags, in the same ``ops/``/``parallel/`` scope:

* ``.astype(<bf16/f16>)`` and ``jnp.*(..., dtype=<bf16/f16>)``
  (attribute, bare-name or string dtype spellings);
* ``jax.lax.convert_element_type(..., <bf16/f16>)``.

Dtype *comparisons* (``x.dtype == jnp.bfloat16``) are not casts and do
not fire.  A policy-gated cast inside a kernel that cannot call the
seam (a Pallas body tracing both variants) carries an inline
``putpu-lint: disable=bf16-cast`` waiver naming the policy that gates
it.
"""

from __future__ import annotations

import ast

from .core import dotted_name, name_root, register

_WIDE = {"float64", "int64", "uint64", "complex128", "double"}
_JAX_ROOTS = {"jnp", "jax"}


def _is_wide_dtype(node):
    """Does this expression denote a 64-bit dtype?  Covers
    ``jnp.float64``/``np.float64`` attributes, bare names and string
    constants."""
    if isinstance(node, ast.Constant):
        return isinstance(node.value, str) and node.value in _WIDE
    if isinstance(node, ast.Attribute):
        return node.attr in _WIDE
    if isinstance(node, ast.Name):
        return node.id in _WIDE
    return False


@register
class Float64LeakChecker:
    id = "float64-leak"
    ids = ("float64-leak",)

    def check(self, ctx):
        pkg = ctx.pkgpath or ""
        if not (pkg.startswith("ops/") or pkg.startswith("parallel/")):
            return []
        out = []
        for node in ast.walk(ctx.tree):
            msg = self._leak(node)
            if msg:
                out.append(ctx.finding(
                    node, "float64-leak",
                    msg + " — device code is float32/bf16/integer by "
                    "design (x64 is off; a widened array doubles HBM "
                    "traffic on memory-bound kernels)"))
        return out

    def _leak(self, node):
        # jnp.float64 attribute anywhere (jnp only: np.float64 is host)
        if isinstance(node, ast.Attribute) and node.attr in _WIDE \
                and isinstance(node.value, ast.Name) \
                and node.value.id == "jnp":
            return f"jnp.{node.attr}"
        if not isinstance(node, ast.Call):
            return None
        callee = dotted_name(node.func) or ""
        root = name_root(node.func)
        # jax.config.update("jax_enable_x64", True)
        if callee.endswith("config.update") and node.args:
            flag = node.args[0]
            if isinstance(flag, ast.Constant) \
                    and flag.value == "jax_enable_x64":
                return "jax_enable_x64 flipped in a kernel module"
        # jax.lax.convert_element_type(x, float64)
        if callee.endswith("convert_element_type") \
                and len(node.args) >= 2 and _is_wide_dtype(node.args[1]):
            return "convert_element_type to a 64-bit dtype"
        # jnp.<ctor>(..., dtype=wide) / jnp.asarray(x, wide)
        if root in _JAX_ROOTS:
            for kw in node.keywords:
                if kw.arg == "dtype" and _is_wide_dtype(kw.value):
                    return f"{callee}(dtype=64-bit)"
            if callee.endswith(("asarray", "array", "zeros", "ones",
                                "full", "empty", "arange", "linspace")) \
                    and len(node.args) >= 2 \
                    and _is_wide_dtype(node.args[1]):
                return f"{callee}(..., 64-bit dtype)"
        # <jnp-chain>.astype(wide)
        if isinstance(node.func, ast.Attribute) \
                and node.func.attr == "astype" and node.args \
                and _is_wide_dtype(node.args[0]) \
                and name_root(node.func.value) in _JAX_ROOTS:
            return ".astype(64-bit) on a jnp expression"
        return None


_HALF = {"bfloat16", "float16", "half"}


def _is_half_dtype(node):
    """Does this expression denote a sub-f32 float dtype?  Covers
    ``jnp.bfloat16`` attributes, bare names and string constants."""
    if isinstance(node, ast.Constant):
        return isinstance(node.value, str) and node.value in _HALF
    if isinstance(node, ast.Attribute):
        return node.attr in _HALF
    if isinstance(node, ast.Name):
        return node.id in _HALF
    return False


@register
class Bf16CastChecker:
    id = "bf16-cast"
    ids = ("bf16-cast",)

    def check(self, ctx):
        pkg = ctx.pkgpath or ""
        if not (pkg.startswith("ops/") or pkg.startswith("parallel/")):
            return []
        out = []
        for node in ast.walk(ctx.tree):
            msg = self._cast(node)
            if msg:
                out.append(ctx.finding(
                    node, "bf16-cast",
                    msg + " — half precision enters device code only "
                    "through the precision-policy seam "
                    "(precision.cast_operand + a registered strategy "
                    "with a declared error bound); ad-hoc casts dodge "
                    "the bound, the autotuner equivalence gate and the "
                    "f32 byte-identity escape hatch"))
        return out

    def _cast(self, node):
        if not isinstance(node, ast.Call):
            return None
        callee = dotted_name(node.func) or ""
        root = name_root(node.func)
        # jax.lax.convert_element_type(x, bfloat16)
        if callee.endswith("convert_element_type") \
                and len(node.args) >= 2 and _is_half_dtype(node.args[1]):
            return "convert_element_type to a sub-f32 float dtype"
        # jnp.<ctor>(..., dtype=half) / jnp.asarray(x, half)
        if root in _JAX_ROOTS:
            for kw in node.keywords:
                if kw.arg == "dtype" and _is_half_dtype(kw.value):
                    return f"{callee}(dtype=bf16/f16)"
            if callee.endswith(("asarray", "array", "zeros", "ones",
                                "full", "empty", "arange", "linspace")) \
                    and len(node.args) >= 2 \
                    and _is_half_dtype(node.args[1]):
                return f"{callee}(..., bf16/f16 dtype)"
        # <anything>.astype(half): unlike the float64 rule this fires on
        # ANY operand chain — a local-variable cast is still a device
        # cast in these layers, and host numpy has no bfloat16 anyway
        if isinstance(node.func, ast.Attribute) \
                and node.func.attr == "astype" and node.args \
                and _is_half_dtype(node.args[0]):
            return ".astype(bf16/f16) outside the precision seam"
        return None
