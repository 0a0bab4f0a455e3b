"""Survey telemetry: span tracing, metrics registry and memory accounting.

Three pillars (ISSUE 3):

* :mod:`.trace` — lightweight wall-clock **spans** (context manager +
  explicit async completion), exported as Chrome trace-event JSON
  (loadable in Perfetto).  The
  :class:`~pulsarutils_tpu.utils.logging_utils.BudgetAccountant` is a
  *consumer* of span durations — one timing primitive, two views
  (per-chunk budget buckets and the event timeline);
* :mod:`.metrics` — process-wide counters / gauges / histograms with
  JSONL and Prometheus-textfile exporters;
* :mod:`.memory` — device-memory watermarks per chunk.

The **live surface** (ISSUE 5) builds on those pillars:

* :mod:`.canary` — continuous synthetic-pulse injection-recovery:
  detection efficiency (recall, S/N recovery, DM error) as live
  metrics, byte-inert when disabled;
* :mod:`.health` — rolling anomaly engine folding per-chunk telemetry
  into one OK/DEGRADED/CRITICAL verdict with an incident log;
* :mod:`.server` — stdlib HTTP endpoints ``/metrics`` (live Prometheus
  scrape), ``/healthz`` (503 on CRITICAL), ``/progress``;
* :mod:`.report` — the end-of-run self-contained survey report
  (markdown + single-file HTML).

The **distributed layer** (ISSUE 14) extends them across processes:

* :mod:`.timeseries` — a bounded ring-buffer sampler over the registry
  (counters→rates, histograms→p50/p95/p99) behind ``/metrics/history``;
* :mod:`.slo` — declarative SLOs with multi-window burn-rate alerting
  (``/alerts``, ``ALERTS_JSON``, HealthEngine conditions);
* :mod:`.collector` — coordinator + N workers stitched into ONE
  clock-skew-corrected Perfetto trace (trace ids ride the fleet wire).

Everything here is dependency-light (stdlib + lazy jax) and safe to
import before a JAX backend exists.
"""

from . import memory, metrics, trace
from .metrics import REGISTRY
from .trace import (begin_span, is_tracing, set_track, span, start_tracing,
                    stop_tracing, trace_context, trace_session)
# the live surface imports utils.logging_utils (which imports .metrics /
# .trace) — keep these AFTER the pillar imports above so the partially
# initialised package already exposes what the cycle re-enters for
from . import canary, collector, health, report, server, slo, timeseries
from .canary import CanaryController
from .collector import TraceCollector
from .health import HealthEngine
from .server import ObsServer, start_obs_server
from .slo import SLOEngine, SLOSpec
from .timeseries import TimeSeriesSampler

__all__ = [
    "CanaryController",
    "HealthEngine",
    "ObsServer",
    "REGISTRY",
    "SLOEngine",
    "SLOSpec",
    "TimeSeriesSampler",
    "TraceCollector",
    "begin_span",
    "canary",
    "collector",
    "health",
    "is_tracing",
    "memory",
    "metrics",
    "report",
    "server",
    "set_track",
    "slo",
    "span",
    "start_obs_server",
    "start_tracing",
    "stop_tracing",
    "timeseries",
    "trace",
    "trace_context",
    "trace_session",
]
