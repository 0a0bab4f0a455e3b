"""putpu-lint: project-specific static analysis over Python ``ast``.

Five PRs of hardening established load-bearing conventions that lived
only in reviewer memory; this package makes them machine-checked
(ISSUE 6).  Six checker families ship today:

=====================  =====================================================
``retrace-*``          shard_map routed through ``shard_map_compat`` only;
                       no jit built per loop iteration; no unhashable
                       static-argument defaults (PRs 1-2)
``device-trip``        device readbacks in ``ops/``/``parallel/`` happen
                       inside budget buckets or sanctioned seams (PR 1)
``lock-discipline``    classes owning ``self._lock`` mutate shared state
                       only under it (PRs 3-5)
``metric-name-*``      every ``putpu_*`` literal resolves against the
                       ``obs/names.py`` manifest, and the manifest covers
                       the docs (PR 3); every
                       ``pallas_call`` is named from its ``KERNEL_NAMES``
                       (``kernel-name-unknown``, ISSUE 25)
``broad-except``       broad handlers only in the reviewed containment-seam
                       allowlist (PR 4)
``float64-leak``       no 64-bit dtypes in jnp expressions in device code
=====================  =====================================================

Stdlib-only and jax-free by design: the linter runs on bare CI
checkouts and as a tier-1 test.  See
``docs/static_analysis.md`` for the workflow (inline waivers,
committed baseline, adding a checker).
"""

from .baseline import load as load_baseline
from .baseline import save as save_baseline
from .core import (Finding, FileContext, LintProject, all_finding_ids,
                   lint_paths, lint_source, register,
                   registered_checkers)
from .cli import main as cli_main
from .cli import run_lint

__all__ = [
    "Finding",
    "FileContext",
    "LintProject",
    "all_finding_ids",
    "cli_main",
    "lint_paths",
    "lint_source",
    "load_baseline",
    "register",
    "registered_checkers",
    "run_lint",
    "save_baseline",
]
