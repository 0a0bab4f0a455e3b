"""Metric/span name-drift checker (``metric-name-*``).

The ``putpu_*`` namespace is an external contract: the observability
docs and any deployed Prometheus scrape configs reference these names
by string.  PR 3 grew them
organically as literals; :mod:`pulsarutils_tpu.obs.names` is now the
single source of truth, and this checker enforces both directions:

* ``metric-name-unknown`` (per file) — a ``putpu_*`` literal passed to
  ``counter()``/``gauge()``/``histogram()`` that is not declared in the
  manifest.  Adding a metric means declaring it.
* ``metric-name-dynamic`` (per file) — an f-string metric name.  The
  checker cannot resolve it; the ONE sanctioned seam (the budget
  accountant's counter mirror) is inline-waived and its names are
  enumerated as ``BUDGET_COUNTERS`` in the manifest.
* ``metric-name-unemitted`` (finalize) — a manifest name no scanned
  file emits: a stale entry, or a renamed metric whose manifest row was
  left behind.
* ``metric-name-unknown-ref`` (finalize) — a ``putpu_*`` token in the
  docs or README that the manifest does not declare: the doc references
  a series nothing emits.

* ``kernel-name-unknown`` (per file) — a ``pallas_call(...)`` without a
  literal ``name=`` declared in the manifest's ``KERNEL_NAMES``: device
  traces are reduced by kernel name, so an unnamed kernel (the trace
  then calls it after whatever closure built it) or an undeclared one
  silently drops out of the per-kernel metrics.

The manifest is read by **parsing** ``obs/names.py`` (AST literal
extraction), not importing it — the linter must run without the package
importable, e.g. from a bare CI checkout.
"""

from __future__ import annotations

import ast
import os
import re

from .core import dotted_name, register

_METRIC_CALLS = {"counter", "gauge", "histogram"}
_NAME_RE = re.compile(r"putpu_[A-Za-z0-9_]+")
#: project artifacts whose putpu_* references must resolve
_REFERENCE_GLOBS = ("README.md", "docs")
#: non-metric putpu_ identifiers (contextvars, file prefixes) that may
#: appear in prose — never emitted, never an error
_PROSE_ALLOWED = {"putpu_budget", "putpu_trace_track", "putpu_plane_",
                  "putpu_plane", "putpu_lint", "putpu_lint_baseline"}


def _manifest_tree(root):
    """The parsed ``obs/names.py`` under ``root``, or ``None``."""
    path = os.path.join(root or ".", "pulsarutils_tpu", "obs", "names.py")
    try:
        with open(path, encoding="utf-8") as fh:
            return ast.parse(fh.read(), filename=path)
    except (OSError, SyntaxError):
        return None


def _assigned(tree, name):
    """The value nodes assigned to the module-level ``name``."""
    return [node.value for node in ast.walk(tree)
            if isinstance(node, ast.Assign)
            and any(isinstance(t, ast.Name) and t.id == name
                    for t in node.targets)]


def load_manifest(root):
    """``(static names, dynamic counter suffixes)`` parsed from
    ``obs/names.py`` under ``root``; empty sets when absent."""
    tree = _manifest_tree(root)
    if tree is None:
        return set(), set()
    names, dynamic = set(), set()
    for value in _assigned(tree, "METRIC_NAMES"):
        if isinstance(value, ast.Dict):
            names = {k.value for k in value.keys
                     if isinstance(k, ast.Constant)
                     and isinstance(k.value, str)}
    for call in _assigned(tree, "BUDGET_COUNTERS"):
        for arg in (call.args if isinstance(call, ast.Call) else [call]):
            if isinstance(arg, (ast.Set, ast.List, ast.Tuple)):
                dynamic = {e.value for e in arg.elts
                           if isinstance(e, ast.Constant)}
    return names, dynamic


def load_kernel_names(root):
    """``KERNEL_NAMES`` keys parsed from ``obs/names.py`` under ``root``;
    empty when absent (then the kernel-name check stays silent)."""
    tree = _manifest_tree(root)
    if tree is None:
        return set()
    return {k.value for value in _assigned(tree, "KERNEL_NAMES")
            if isinstance(value, ast.Dict) for k in value.keys
            if isinstance(k, ast.Constant)}


def _kernel_names(project):
    key = "name-drift/kernels"
    if key not in project.state:
        project.state[key] = load_kernel_names(project.root)
    return project.state[key]


def _manifest(project):
    key = "name-drift/manifest"
    if key not in project.state:
        if project.manifest_names is not None:
            static = set(project.manifest_names)
            dynamic = set(project.dynamic_names or ())
        else:
            static, dynamic = load_manifest(project.root)
        project.state[key] = (static, dynamic)
    return project.state[key]


def _known(name, static, dynamic):
    if name in static:
        return True
    return (name.startswith("putpu_") and name.endswith("_total")
            and name[len("putpu_"):-len("_total")] in dynamic)


@register
class NameDriftChecker:
    id = "metric-name"
    ids = ("metric-name-unknown", "metric-name-dynamic",
           "metric-name-unemitted", "metric-name-unknown-ref",
           "kernel-name-unknown")

    def check(self, ctx):
        project = ctx.project
        if project is None:
            return []
        static, dynamic = _manifest(project)
        emitted = project.state.setdefault("name-drift/emitted", set())
        out = []
        kernels = _kernel_names(project)
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call) or not node.args:
                continue
            callee = (dotted_name(node.func) or "").rsplit(".", 1)[-1]
            if callee == "pallas_call" and kernels:
                name = next((kw.value for kw in node.keywords
                             if kw.arg == "name"), None)
                if not (isinstance(name, ast.Constant)
                        and name.value in kernels):
                    out.append(ctx.finding(
                        node, "kernel-name-unknown",
                        "pallas_call needs a literal name= declared in "
                        "obs/names.py KERNEL_NAMES — trace reductions "
                        "find a kernel by that name"))
            if callee not in _METRIC_CALLS:
                continue
            arg = node.args[0]
            if isinstance(arg, ast.Constant) and isinstance(arg.value,
                                                           str):
                name = arg.value
                if not name.startswith("putpu_"):
                    continue
                emitted.add(name)
                if not _known(name, static, dynamic):
                    out.append(ctx.finding(
                        node, "metric-name-unknown",
                        f"metric {name!r} is not declared in "
                        "obs/names.py METRIC_NAMES — the manifest is "
                        "the single source the gate/docs check against"))
            elif isinstance(arg, ast.JoinedStr):
                head = arg.values[0] if arg.values else None
                if isinstance(head, ast.Constant) and str(
                        head.value).startswith("putpu_"):
                    emitted.add("<dynamic>")
                    out.append(ctx.finding(
                        node, "metric-name-dynamic",
                        "dynamically formatted putpu_* metric name — "
                        "the checker cannot verify it against the "
                        "manifest; enumerate the possible names in "
                        "obs/names.py and waive this one seam"))
        return out

    # -- cross-file coverage -------------------------------------------------

    def finalize(self, project):
        static, dynamic = _manifest(project)
        if not static and project.manifest_names is None:
            return []  # no manifest in scope (fixture runs)
        emitted = project.state.get("name-drift/emitted", set())
        dynamic_metrics = {f"putpu_{s}_total" for s in dynamic}
        out = []
        # the every-manifest-name-is-emitted direction is only sound on
        # a full-tree scan: require every emitting layer in the scan
        layers = {("pulsarutils_tpu/" + sub) for sub in
                  ("obs/", "parallel/", "pipeline/", "faults/", "io/")}
        scanned_pkg = all(any(p.startswith(layer) for p in project.files)
                          for layer in layers)
        if scanned_pkg:
            # direction 1: every manifest name is emitted somewhere
            for name in sorted(static):
                if name not in emitted and name not in dynamic_metrics:
                    out.append(self._proj_finding(
                        project, "metric-name-unemitted",
                        f"manifest declares {name!r} but no scanned "
                        "file emits it — stale entry or renamed metric"))
        # direction 2: docs references resolve
        for path, line, name in self._references(project):
            if name in _PROSE_ALLOWED:
                continue
            if not _known(name, static, dynamic):
                out.append(
                    type(self)._ref_finding(path, line, name))
        return out

    def _proj_finding(self, project, checker, message):
        from .core import Finding

        return Finding(path="pulsarutils_tpu/obs/names.py", line=1,
                       col=0, checker=checker, message=message)

    @staticmethod
    def _ref_finding(path, line, name):
        from .core import Finding

        return Finding(
            path=path, line=line, col=0, checker="metric-name-unknown-ref",
            message=f"{name!r} referenced here is not declared in "
                    "obs/names.py — the doc names a series nothing emits")

    def _references(self, project):
        root = project.root
        if not root or project.manifest_names is not None:
            return
        targets = []
        for entry in _REFERENCE_GLOBS:
            path = os.path.join(root, entry)
            if os.path.isfile(path):
                targets.append(path)
            elif os.path.isdir(path):
                for name in sorted(os.listdir(path)):
                    if name.endswith(".md"):
                        targets.append(os.path.join(path, name))
        for path in targets:
            rel = os.path.relpath(path, root).replace(os.sep, "/")
            try:
                with open(path, encoding="utf-8") as fh:
                    for lineno, text in enumerate(fh, 1):
                        for m in _NAME_RE.finditer(text):
                            yield rel, lineno, m.group(0)
            except OSError:
                continue
