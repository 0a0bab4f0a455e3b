"""Broad-exception checker (``broad-except``).

PR 4's review rounds repeatedly narrowed ``except Exception`` handlers
to the exact failure sets the containment design means to contain
(``(OSError, ValueError, KeyError, BadZipFile)`` at resume-restore,
OSError-only persist retries) — because a broad handler that swallows a
``TypeError`` turns a deterministic configuration bug into silent data
loss or a permanent silent fallback.  This checker makes the narrowing
stick: bare ``except:``, ``except Exception`` and ``except
BaseException`` are findings unless the handler sits in a declared
containment seam.

The seam allowlist (:data:`CONTAINMENT_SEAMS`) names the places whose
*job* is to contain arbitrary failure, reviewed once and recorded here:

* observability must never take down a survey (HTTP scrape handlers,
  trace/profiler shutdown, report writers, the end-of-run audit);
* jax runtime errors share no common base class, so the
  device-dispatch fallback/retry seams catch Exception by necessity —
  each one re-raises ``(ValueError, TypeError)`` first (deterministic
  configuration errors), a convention this checker cannot fully prove
  but the seam list keeps auditable;
* capability probes at import/startup (monitoring listener, memory
  stats, backend probes) where any failure means "feature absent".

A handler outside the list needs an inline waiver with a reason — or,
usually better, a narrower tuple.
"""

from __future__ import annotations

import ast

from .core import register

#: (package-relative path, qualname prefix) pairs whose broad handlers
#: are the reviewed containment seams.  A qualname prefix of "" covers
#: the whole file (reserve for observability-only modules).
CONTAINMENT_SEAMS = {
    # -- observability must never take down a run --------------------------
    ("obs/server.py", "_Handler.do_GET"),
    ("obs/server.py", "_Handler.do_POST"),  # job API request containment
    ("obs/server.py", "ObsServer.progress_snapshot"),  # user progress_fn
    ("obs/trace.py", "trace_session"),
    ("obs/memory.py", "device_memory_snapshot"),
    # alert fan-out is observability-only (ISSUE 18): a dead webhook,
    # a failing lineage hook or a full disk must be counted,
    # dead-lettered and contained — never raised into the search loop
    ("obs/push.py", ""),
    # -- capability probes: failure == feature absent ----------------------
    ("utils/logging_utils.py", "_install_compile_listener"),
    ("utils/logging_utils.py", "measure_device_rtt"),
    ("cli/search_main.py", "_enable_compile_cache"),
    # -- jax errors share no base class: dispatch fallback/retry seams -----
    # (each re-raises deterministic (ValueError, TypeError) first, and
    # search_by_chunks' BaseException handler re-raises after pool
    # shutdown — the convention this checker cannot prove but this list
    # keeps auditable)
    ("parallel/stream.py", "stream_search"),
    ("pipeline/search_pipeline.py", "_search_with_fallback"),
    ("pipeline/search_pipeline.py", "search_by_chunks"),
    ("faults/policy.py", "call_with_deadline"),  # watchdog-thread relay
    # OOM degradation-ladder catch sites (ISSUE 12): each classifies
    # with resilience.ladder.is_resource_exhausted and RE-RAISES
    # everything that is not RESOURCE_EXHAUSTED (after the usual
    # (ValueError, TypeError) re-raise) — jax errors share no base
    # class, so the broad handler is the only way to catch the OOM
    ("ops/search.py", "_search_jax"),
    ("parallel/sharded_fdmt.py", "sharded_hybrid_search"),
    ("beams/batcher.py", "BeamBatcher.search"),
    # one failed tenant batch marks its jobs FAILED; the service worker
    # thread must survive to run the next batch (jax errors share no
    # base class here either)
    ("beams/service.py", "SurveyService._run_batch"),
    # one failed periodicity job likewise (ISSUE 13)
    ("beams/service.py", "SurveyService._run_periodicity"),
    # a poisoned leased unit reports its error string and the
    # coordinator requeues (bounded by max_attempts); the fleet worker
    # must survive to lease the next unit (jax errors again) — the
    # reviewed fleet containment seam (ISSUE 9; the coordinator's HTTP
    # handlers ride the already-seamed obs/server do_GET/do_POST, and
    # the drain path catches only (OSError, ValueError) narrowly)
    ("fleet/worker.py", "FleetWorker._run_unit_inner"),
    # the time-series sampler's spill/hook and its background loop:
    # metric history is observability — a failed sample, JSONL spill or
    # SLO evaluation hook must log and move on, never kill a run
    # (ISSUE 14)
    ("obs/timeseries.py", "TimeSeriesSampler.sample"),
    ("obs/timeseries.py", "TimeSeriesSampler._loop"),
    # the periodicity trial sweep's device->host fallback (ISSUE 13):
    # re-raises (ValueError, TypeError) first, then degrades a failed
    # jax dispatch to the numpy reference path — the same ladder-floor
    # convention as _search_with_fallback (jax errors, no base class);
    # the driver's report writer shares search_by_chunks' never-fatal
    # observability rule
    ("periodicity/driver.py", "periodicity_search"),
    # -- CLI report amendment: observability never fails the run -----------
    ("cli/search_main.py", "main"),
}

_BROAD = {"Exception", "BaseException"}


def _broad_names(handler):
    """Broad exception names this handler catches (empty if narrow)."""
    if handler.type is None:
        return ["<bare>"]
    nodes = (handler.type.elts if isinstance(handler.type, ast.Tuple)
             else [handler.type])
    out = []
    for node in nodes:
        if isinstance(node, ast.Name) and node.id in _BROAD:
            out.append(node.id)
        elif isinstance(node, ast.Attribute) and node.attr in _BROAD:
            out.append(node.attr)
    return out


@register
class BroadExceptChecker:
    id = "broad-except"
    ids = ("broad-except",)

    def check(self, ctx):
        out = []
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.ExceptHandler):
                continue
            broad = _broad_names(node)
            if not broad:
                continue
            qualname = ctx.qualname(node)
            if self._sanctioned(ctx.pkgpath, qualname):
                continue
            what = ("bare except:" if broad == ["<bare>"]
                    else f"except {'/'.join(broad)}")
            where = qualname or "<module>"
            out.append(ctx.finding(
                node, "broad-except",
                f"{what} in {where} outside the containment-seam "
                "allowlist — narrow it to the failures this site "
                "contains (PR 4 convention: deterministic "
                "ValueError/TypeError must propagate), or add the seam "
                "to CONTAINMENT_SEAMS / waive with a reason"))
        return out

    def _sanctioned(self, pkgpath, qualname):
        if pkgpath is None:
            return False
        for path, prefix in CONTAINMENT_SEAMS:
            if pkgpath != path:
                continue
            if prefix == "" or qualname == prefix \
                    or qualname.startswith(prefix + "."):
                return True
        return False
