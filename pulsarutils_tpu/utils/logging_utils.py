"""Framework logger + per-stage timing + the streaming budget accountant.

The reference's observability was ``astropy.log.info`` milestones, bare
prints and tqdm bars (SURVEY §5).  Here: one stdlib logger, a tiny
stage profiler that also hooks ``jax.profiler`` traces when requested,
and — round 6 — :class:`BudgetAccountant`, the hierarchical per-chunk
wall-clock budget the survey rehearsal was missing (its round-5 stage
table explained ~6% of wall; VERDICT r5 #1): every second of a chunk's
wall is assigned to a named bucket, with an explicit ``unattributed``
residual per chunk and in the run footer.

Round 7: the accountant's buckets and chunks are measured by
:mod:`pulsarutils_tpu.obs.trace` **spans** — one timing primitive whose
completed intervals feed both the budget ledger (same rounding, same
``BUDGET_JSON`` bytes) and, when a tracer is active, the Perfetto/Chrome
trace timeline; counters are mirrored into the process metrics registry
(:mod:`pulsarutils_tpu.obs.metrics`).
"""

from __future__ import annotations

import contextlib
import contextvars
import json
import logging
import re
import threading
import time

logger = logging.getLogger("pulsarutils_tpu")
if not logger.handlers:
    _h = logging.StreamHandler()
    _h.setFormatter(logging.Formatter(
        "%(asctime)s %(name)s %(levelname)s: %(message)s", "%H:%M:%S"))
    logger.addHandler(_h)
    logger.setLevel(logging.INFO)

# after ``logger``: importing the obs package runs its live surface,
# which imports ``logger`` from this (then partially initialised) module
from ..obs import metrics as _metrics  # noqa: E402
from ..obs import trace as _trace  # noqa: E402


class StageTimer:
    """Accumulates wall-clock per named stage; ``report()`` logs a table."""

    def __init__(self):
        self.totals = {}
        self.counts = {}

    @contextlib.contextmanager
    def stage(self, name):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            dt = time.perf_counter() - t0
            self.totals[name] = self.totals.get(name, 0.0) + dt
            self.counts[name] = self.counts.get(name, 0) + 1

    def report(self, log=logger):
        for name, total in sorted(self.totals.items(), key=lambda kv: -kv[1]):
            n = self.counts[name]
            log.info("stage %-20s %8.3fs total, %6d calls, %8.4fs/call",
                     name, total, n, total / n)


# ---------------------------------------------------------------------------
# Budget accountant (round 6)
# ---------------------------------------------------------------------------

#: the accountant deep code attributes to without API threading: kernel
#: facades call :func:`budget_bucket`/:func:`budget_count`, which no-op
#: unless a chunk budget is active on this (main) thread.  A ContextVar,
#: not a bare global, so overlapped worker threads (reader, persist)
#: never misattribute into the main thread's serial buckets.
_ACTIVE_BUDGET = contextvars.ContextVar("putpu_budget", default=None)

#: version of the ``BUDGET_JSON`` footer (its first key) and of the
#: ``--metrics-out`` JSONL header; bumped whenever a record's meaning
#: changes, so a parser fails loudly on a drifted schema.  v2 (ISSUE 14):
#: the ``chunk_wall_s`` p50/p95/p99 block.  v3 (ISSUE 17): no footer
#: change.  v4 (ISSUE 25): ``call_s``, per-chunk ``on_disk_lag_s``, the
#: compile-phase counters, and ``async_s`` splits ``persist``.
BUDGET_SCHEMA_VERSION = 4

#: chunk-wall histogram edges: decade-ish coverage from sub-100ms CPU
#: test chunks to multi-minute chunks
_CHUNK_WALL_EDGES = (0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0,
                     60.0, 120.0)


def _percentile(sorted_values, q):
    """Linear-interpolation percentile of an already-sorted list (the
    numpy default rule, reimplemented so the ledger stays stdlib-only
    and byte-deterministic)."""
    n = len(sorted_values)
    if n == 0:
        return None
    if n == 1:
        return float(sorted_values[0])
    pos = q * (n - 1)
    lo = int(pos)
    frac = pos - lo
    if lo + 1 >= n:
        return float(sorted_values[-1])
    return float(sorted_values[lo] * (1.0 - frac)
                 + sorted_values[lo + 1] * frac)


#: process-wide XLA compile observation (jax.monitoring events); installed
#: lazily, once — the listener registry has no deregister, so the counts
#: are cumulative and consumers take deltas.  Beside the backend compile
#: (on a persistent-cache hit that IS the retrieval), the phases before
#: it: tracing, lowering to MLIR, and reading the executable back.
_COMPILE = {"count": 0, "secs": 0.0, "trace_s": 0.0, "lower_s": 0.0,
            "cache_load_s": 0.0, "installed": False}
_COMPILE_LOCK = threading.Lock()

#: jax.monitoring build-phase event (by its last path component) -> the
#: phase's name in a ``build/<phase>:<program>`` span.  JAX 0.9 fires the
#: event twice: as a scalar when the phase begins, as a duration from the
#: phase's ``__exit__`` (also when it raises), both with ``fun_name=``.
_BUILD_PHASES = {"jaxpr_trace_duration": "trace",
                 "jaxpr_to_mlir_module_duration": "lower",
                 "backend_compile_duration": "compile"}

class _BuildPhases(threading.local):
    """``.open``: this thread's build phases that have begun and not
    ended, innermost last, as ``(phase, span or None)``.  JAX fires
    ``jaxpr_trace_duration`` for every nested ``jit`` inside the phase of
    the one that encloses it (each jitted ``jnp`` helper the clean program
    calls is one), so only a tracing phase that no other encloses adds to
    ``trace_s``."""

    def __init__(self):
        self.open = []


_BUILD = _BuildPhases()


def _program_name(phase, fun_name):
    """The program as the device trace names it: tracing is told ``fn``,
    lowering and compiling ``jit(fn)``; all three become ``jit_fn``."""
    name = f"jit_{fun_name}" if phase == "trace" else str(fun_name)
    return re.sub(r"[^\w.-]+", "_", name).strip("_")


def _on_build_phase_begin(event, _value, fun_name="", **_kw):
    phase = _BUILD_PHASES.get(event.rsplit("/", 1)[-1])
    if phase is None:
        return
    span = None
    if _trace.is_tracing():
        span = _trace.open_span(
            _trace.build_span_name(phase, _program_name(phase, fun_name)),
            {"cache": "miss"} if phase == "compile" else None)
    _BUILD.open.append((phase, span))


def _on_build_event(event, secs, **_kw):
    name = event.rsplit("/", 1)[-1]
    secs = float(secs)
    open_phases = _BUILD.open
    if name == "cache_retrieval_time_sec":
        # fires inside the compile phase whose executable it read back
        if open_phases and open_phases[-1][1] is not None:
            open_phases[-1][1].attrs.update(cache="hit",
                                            cache_load_s=round(secs, 6))
        with _COMPILE_LOCK:
            _COMPILE["cache_load_s"] += secs
        return
    phase = _BUILD_PHASES.get(name)
    if phase is None:
        return
    # a phase that began before the listeners were installed is not here
    if open_phases and open_phases[-1][0] == phase:
        span = open_phases.pop()[1]
        if span is not None:
            _trace.close_span(span)
    with _COMPILE_LOCK:
        if phase == "compile":
            _COMPILE["count"] += 1
            _COMPILE["secs"] += secs
        elif phase == "lower":
            _COMPILE["lower_s"] += secs
        elif all(p != "trace" for p, _ in open_phases):
            _COMPILE["trace_s"] += secs


def _install_compile_listener():
    with _COMPILE_LOCK:
        if _COMPILE["installed"]:
            return
        _COMPILE["installed"] = True
        from jax import monitoring

        monitoring.register_scalar_listener(_on_build_phase_begin)
        monitoring.register_event_duration_secs_listener(_on_build_event)


@contextlib.contextmanager
def kernel_build_span(kernel, kind="kernel", **attrs):
    """``build/kernel:<kernel>`` around the Python that binds a Pallas
    kernel and so traces its body, with the geometry that keys it
    (``kind="levels"``: ``build/levels:<kernel>`` around a sweep's whole
    walk of per-level merges).  Recorded under a tracer, inside a
    program's tracing phase: a warm call never reaches this Python, and
    an eager call of the same code (no phase open) is no build."""
    if not (_trace.is_tracing() and _BUILD.open):
        yield
        return
    with _trace.span(_trace.build_span_name(kind, kernel), **attrs):
        yield


def compile_snapshot():
    """Cumulative ``(count, seconds)`` of XLA backend compiles observed
    so far (0, 0.0 until JAX emits its first monitored compile)."""
    _install_compile_listener()
    with _COMPILE_LOCK:
        return _COMPILE["count"], _COMPILE["secs"]


def compile_phase_snapshot():
    """Cumulative seconds of the phases before the backend compile:
    ``{"trace_s", "lower_s", "cache_load_s"}`` (outermost tracing only,
    lowering to MLIR, retrieval from the persistent cache)."""
    _install_compile_listener()
    with _COMPILE_LOCK:
        return {k: _COMPILE[k]
                for k in ("trace_s", "lower_s", "cache_load_s")}


def measure_device_rtt(n=5):
    """Median seconds for one trivial dispatch + one-element readback.

    The per-trip floor every device round trip pays: a host sync.
    One warmup call absorbs the compile, so
    the median measures steady-state trips.  Returns ``None`` when no
    jax backend is importable.
    """
    try:
        import numpy as np

        import jax.numpy as jnp
    except Exception:
        return None
    x = jnp.float32(1.0)
    np.asarray(x + jnp.float32(1.0))  # warm (compile + session)
    times = []
    for _ in range(n):
        t0 = time.perf_counter()
        np.asarray(x + jnp.float32(1.0))
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


class BudgetAccountant(StageTimer):
    """Per-chunk wall-clock budget: buckets + counters + residual.

    Drop-in superset of :class:`StageTimer` (``stage``/``report`` keep
    working, and every bucket second also lands in the stage totals, so
    the rehearsal's stage-table parsers see the same rows).  On top:

    * :meth:`chunk` opens a per-chunk budget; within it,
      :meth:`bucket`/:func:`budget_bucket` attribute **main-thread,
      serial** time to named buckets and :meth:`count` bumps counters
      (``dispatches``, ``readbacks``, ...).  Bucket names may nest with
      ``/`` (``search/coarse``): the residual math uses top-level names
      only, so instrumented sub-phases never double-count;
    * XLA compiles are observed via ``jax.monitoring`` and recorded per
      chunk (``compiles``/``compile_s`` counters; and, where non-zero,
      the phases around them: ``trace_s``, ``lower_s``,
      ``cache_load_s`` — see :func:`compile_phase_snapshot`).  A compile
      in any chunk after the first is flagged as a **retrace** in that
      chunk's record; the log escalates to a WARNING once retraces appear in 3+
      chunks (true shape drift recompiles everywhere, while a lazily
      built kernel's first use legitimately compiles once).  NOTE the
      compile listener is process-global: a concurrent JAX compile from
      another thread lands in whichever chunk is open;
    * work overlapped onto other threads (prefetch decode, persist) is
      recorded via :meth:`add_async` — reported, but deliberately NOT
      part of any chunk's serial budget (it does not occupy the chunk's
      critical path);
    * ``unattributed`` = chunk wall − Σ top-level buckets, per chunk and
      summed in :meth:`footer`; :meth:`to_json` emits the whole ledger
      for artifacts;
    * a :meth:`bucket` that no chunk encloses (the drivers' ``call/*``
      phases, the bad-channel pre-scan, the persist drain) is a cost of
      the call, not of a chunk: it lands in ``call_s``, outside the
      chunk sums.

    ``rtt_s`` (see :func:`measure_device_rtt`) prices the per-trip
    floor: the footer reports ``dispatches+readbacks × rtt`` so the
    round-trip cost is attributable even though each trip's wait is
    already inside the bucket that blocked on it.
    """

    def __init__(self, rtt_s=None):
        super().__init__()
        self.rtt_s = rtt_s
        self.chunks = []
        self.call_buckets = {}
        #: ``perf_counter`` at the end of the last closed chunk's span
        self.last_chunk_end = None
        self.async_totals = {}
        self.counters_total = {}
        self._async_lock = threading.Lock()
        self._active = None
        self._retrace_chunks = 0
        self._stream_chunks = 0
        self._truncation_warned = False
        self._autotune_mark = self._autotune_seq()
        _install_compile_listener()

    @staticmethod
    def _autotune_seq():
        """Current position in the process autotune-decision ledger
        (lazy import: the tuning package consumes this module)."""
        from ..tuning.autotune import decision_seq

        return decision_seq()

    def begin_stream(self):
        """Mark the start of a new stream/run on a reused accountant.

        Retrace detection keys off the first chunk OF A STREAM (first-use
        compiles are normal there); a caller aggregating several runs
        into one accountant calls this per run so the second run's
        initial compiles are not misflagged as shape drift.  The drivers
        (``search_by_chunks``, ``stream_search``) call it for you.
        """
        self._stream_chunks = 0
        self._retrace_chunks = 0  # warning escalation is per stream too
        # per-key kernel-autotune decisions are reported per run too:
        # the footer shows THIS stream's resolutions, not the whole
        # process history (a reused accountant would otherwise repeat
        # the previous run's table)
        self._autotune_mark = self._autotune_seq()

    # -- per-chunk budget ----------------------------------------------------

    @contextlib.contextmanager
    def chunk(self, label):
        if self._active is not None:
            raise RuntimeError("budget chunks cannot nest")
        c0, s0 = compile_snapshot()
        phases0 = compile_phase_snapshot()
        rec = {"chunk": label, "wall_s": 0.0, "buckets": {}, "counters": {}}
        self._active = rec
        token = _ACTIVE_BUDGET.set(self)
        # chunk wall is a span: the tracer (when active) gets one "chunk"
        # event, and every nested span lands on this chunk's own track
        track_token = _trace.push_track(f"chunk {label}")
        s = _trace.open_span("chunk", {"chunk": label})
        try:
            yield rec
        finally:
            _trace.close_span(s)
            _trace.pop_track(track_token)
            rec["wall_s"] = s.dur
            self.last_chunk_end = s.t1
            _ACTIVE_BUDGET.reset(token)
            self._active = None
            self._stream_chunks += 1
            c1, s1 = compile_snapshot()
            if c1 > c0:
                rec["counters"]["compiles"] = c1 - c0
                rec["counters"]["compile_s"] = round(s1 - s0, 4)
                if self._stream_chunks > 1:
                    # a compile after chunk 0 is a retrace.  A FEW are
                    # expected — lazily-built kernels compiling on first
                    # use (the hybrid's rescore buckets on the first hit
                    # chunk, a ragged final chunk) — so the flag is
                    # recorded per chunk but the WARNING only escalates
                    # on the pattern first-use compiles cannot produce:
                    # retracing across several chunks (true shape drift
                    # recompiles on EVERY chunk; code-review r6)
                    rec["retrace"] = True
                    self._retrace_chunks += 1
                    _metrics.counter("putpu_retraces_total").inc()
                    log = (logger.warning if self._retrace_chunks >= 3
                           else logger.info)
                    log("retrace in chunk %s: %d XLA compile(s), %.2fs "
                        "(%s)", label, c1 - c0, s1 - s0,
                        "repeated retracing — shape drift? interior "
                        "chunks should reuse one compiled executable"
                        if self._retrace_chunks >= 3 else
                        "expected for a kernel's first use; repeated "
                        "occurrences escalate to a warning")
            for key, total in compile_phase_snapshot().items():
                # chunks that compile nothing keep their bytes
                delta = round(total - phases0[key], 4)
                if delta > 0:
                    rec["counters"][key] = delta
            top = sum(v for k, v in rec["buckets"].items() if "/" not in k)
            rec["unattributed_s"] = round(rec["wall_s"] - top, 4)
            rec["wall_s"] = round(rec["wall_s"], 4)
            # chunk-wall distribution (ISSUE 14): the SLO engine's
            # latency indicator — the histogram feeds the time-series
            # p95, the ledger below quotes exact percentiles
            _metrics.histogram("putpu_chunk_wall_seconds",
                               edges=_CHUNK_WALL_EDGES).observe(
                rec["wall_s"])
            rec["buckets"] = {k: round(v, 4)
                              for k, v in rec["buckets"].items()}
            self.chunks.append(rec)
            _metrics.counter("putpu_chunks_total").inc()
            logger.debug("chunk %s budget: wall=%.3fs %s "
                         "unattributed=%.3fs counters=%s", label,
                         rec["wall_s"],
                         " ".join(f"{k}={v:.3f}" for k, v in
                                  sorted(rec["buckets"].items(),
                                         key=lambda kv: -kv[1])
                                  if "/" not in k),
                         rec["unattributed_s"], rec["counters"])

    @contextlib.contextmanager
    def bucket(self, name):
        """Serial main-thread time bucket (also feeds the stage table).

        Measured as ONE span (:mod:`..obs.trace`): the budget consumes
        the span's duration, and an active tracer records the same
        interval as a timeline event — never two clocks for one block.
        """
        s = _trace.open_span(name)
        try:
            yield
        finally:
            _trace.close_span(s)
            self.add(name, s.dur)

    def add(self, name, dt):
        b = (self._active["buckets"] if self._active is not None
             else self.call_buckets)
        b[name] = b.get(name, 0.0) + dt
        self.totals[name] = self.totals.get(name, 0.0) + dt
        self.counts[name] = self.counts.get(name, 0) + 1

    def count(self, name, n=1):
        if self._active is not None:
            c = self._active["counters"]
            c[name] = c.get(name, 0) + n
        self.counters_total[name] = self.counters_total.get(name, 0) + n
        # mirror into the process metrics registry (Prometheus/JSONL
        # exporters); the budget ledger stays the per-run source of truth
        # the ONE sanctioned dynamic-name seam; the possible names are
        # enumerated as BUDGET_COUNTERS in obs/names.py
        # putpu-lint: disable=metric-name-dynamic — enumerated manifest seam
        _metrics.counter(f"putpu_{name}_total").inc(n)

    def add_async(self, name, dt):
        """Overlapped (off-critical-path) seconds, any thread."""
        with self._async_lock:
            self.async_totals[name] = self.async_totals.get(name, 0.0) + dt

    def trips(self):
        """Total device round trips counted so far (``dispatches`` +
        ``readbacks`` over all chunks) — the quantity the RTT floor
        prices, and the number the mesh fused-hybrid A/B pins (one
        fused ``shard_map`` program per typical hit chunk vs one coarse
        dispatch plus one per rescore bucket)."""
        return (self.counters_total.get("dispatches", 0)
                + self.counters_total.get("readbacks", 0))

    # -- reporting -----------------------------------------------------------

    def to_json(self, max_per_chunk=32):
        nchunks = len(self.chunks)
        wall = sum(c["wall_s"] for c in self.chunks)
        buckets = {}
        for c in self.chunks:
            for k, v in c["buckets"].items():
                buckets[k] = buckets.get(k, 0.0) + v
        top = sum(v for k, v in buckets.items() if "/" not in k)
        unattributed = wall - top
        walls = sorted(c["wall_s"] for c in self.chunks)
        out = {
            # versioned footer (ISSUE 5 satellite): parsers key off this
            # instead of silently reading records whose meaning drifted
            "schema_version": BUDGET_SCHEMA_VERSION,
            "chunks": nchunks,
            "wall_s": round(wall, 3),
            "chunk_wall_s": ({
                "p50": round(_percentile(walls, 0.50), 4),
                "p95": round(_percentile(walls, 0.95), 4),
                "p99": round(_percentile(walls, 0.99), 4)}
                if walls else None),
            "buckets_s": {k: round(v, 3) for k, v in sorted(
                buckets.items(), key=lambda kv: -kv[1])},
            "unattributed_s": round(unattributed, 3),
            "attributed_pct": round(100.0 * top / wall, 1) if wall else None,
            "counters": dict(self.counters_total),
            "async_s": {k: round(v, 3)
                        for k, v in self.async_totals.items()},
            # what the call cost outside its chunks, as recorded so far
            # (v4): the drivers' ``call/*`` phases under their bare
            # names, ``badchans``, ``persist_drain``
            "call_s": {k.removeprefix("call/"): round(v, 3)
                       for k, v in self.call_buckets.items()},
            # long streams: keep the JSON line bounded — head + tail
            # chunks (the aggregates above always cover every chunk);
            # max_per_chunk=0 drops the per-chunk detail entirely
            "per_chunk": (self.chunks if nchunks <= max_per_chunk
                          else self.chunks[:max_per_chunk // 2]
                          + self.chunks[nchunks - max_per_chunk // 2:]),
        }
        if nchunks > max_per_chunk:
            out["per_chunk_truncated"] = True
            # how many interior chunk records the head+tail window drops
            # (the aggregates above still cover every chunk) — recorded,
            # not silent, so long surveys know detail was elided
            out["truncated_chunks"] = nchunks - 2 * (max_per_chunk // 2)
            # max_per_chunk=0 is an explicit "no per-chunk detail"
            # request — record the count but don't warn about it
            if max_per_chunk > 0 and not self._truncation_warned:
                self._truncation_warned = True
                logger.warning(
                    "budget JSON truncated: per-chunk detail for %d of %d "
                    "chunks dropped (head+tail of %d kept; aggregates "
                    "cover all chunks — raise max_per_chunk for the full "
                    "ledger)", out["truncated_chunks"], nchunks,
                    max_per_chunk)
        if self.rtt_s is not None:
            out["rtt_s"] = round(self.rtt_s, 6)
            out["trips"] = self.trips()
            out["trips_x_rtt_s"] = round(self.trips() * self.rtt_s, 3)
        # per-key kernel-autotune decisions since this run's
        # begin_stream (ISSUE 7) — key absent when kernel="auto" never
        # resolved anything this run, so pre-tuner ledgers (and the
        # byte-pinned goldens) are unchanged
        from ..tuning.autotune import decisions_since

        decisions = decisions_since(self._autotune_mark)
        if decisions:
            out["autotune"] = decisions
        return out

    def footer(self, log=logger):
        """Log the run-level budget: every bucket's share of the summed
        chunk wall, the residual, trip pricing and overlapped work."""
        if not self.chunks:
            return
        j = self.to_json()
        wall = j["wall_s"] or 1.0
        log.info("chunk budget over %d chunks, %.2fs wall "
                 "(%.1f%% attributed):", j["chunks"], j["wall_s"],
                 j["attributed_pct"] or 0.0)
        cw = j.get("chunk_wall_s")
        if cw:
            log.info("  chunk wall p50/p95/p99: %.3f / %.3f / %.3f s",
                     cw["p50"], cw["p95"], cw["p99"])
        # group children under their PARENT (a flat sort-by-total can
        # interleave a child below an unrelated small bucket and
        # misrepresent the hierarchy — code-review r6)
        buckets = j["buckets_s"]
        tops = sorted((k for k in buckets if "/" not in k),
                      key=lambda k: -buckets[k])
        for top in tops:
            log.info("  %-22s %8.3fs  %5.1f%%", top, buckets[top],
                     100.0 * buckets[top] / wall)
            kids = sorted((k for k in buckets
                           if k.startswith(top + "/")),
                          key=lambda k: -buckets[k])
            for k in kids:
                log.info("    %-20s %8.3fs  %5.1f%%",
                         k[len(top) + 1:], buckets[k],
                         100.0 * buckets[k] / wall)
        log.info("  %-22s %8.3fs  %5.1f%%", "unattributed",
                 j["unattributed_s"], 100.0 * j["unattributed_s"] / wall)
        if j.get("counters"):
            log.info("  counters: %s", json.dumps(j["counters"]))
        for d in j.get("autotune", ()):
            log.info("  autotune %s -> %s (%s%s)", d["key"], d["kernel"],
                     d["source"],
                     f", {d['speedup_vs_static']}x vs static"
                     if d.get("speedup_vs_static") is not None else "")
        if self.rtt_s is not None:
            log.info("  device RTT %.4fs x %d trips = %.2fs (floor "
                     "inside the blocking buckets)", j["rtt_s"],
                     j["trips"], j["trips_x_rtt_s"])
        for k, v in sorted(j["async_s"].items(), key=lambda kv: -kv[1]):
            log.info("  overlapped %-17s %8.3fs (off critical path)", k, v)
        for k, v in sorted(j["call_s"].items(), key=lambda kv: -kv[1]):
            log.info("  outside chunks %-13s %8.3fs", k, v)
        if j["wall_s"]:
            _metrics.gauge("putpu_chunks_per_s").set(
                round(j["chunks"] / j["wall_s"], 4))


def current_budget():
    """The :class:`BudgetAccountant` whose chunk context encloses this
    call on this thread, or ``None``."""
    return _ACTIVE_BUDGET.get()


@contextlib.contextmanager
def budget_bucket(name):
    """Attribute the block to ``name`` in the active chunk budget, if
    any — and, when a tracer is active, record the same interval as a
    span (kernel code calls this unconditionally; with neither consumer
    present it degrades to a plain yield)."""
    acct = _ACTIVE_BUDGET.get()
    if acct is None and not _trace.is_tracing():
        yield
        return
    s = _trace.open_span(name)
    try:
        yield
    finally:
        _trace.close_span(s)
        if acct is not None:
            acct.add(name, s.dur)


def budget_count(name, n=1):
    """Bump a counter (``dispatches``, ``readbacks``, ...) in the active
    chunk budget, if any."""
    acct = _ACTIVE_BUDGET.get()
    if acct is not None:
        acct.count(name, n)


@contextlib.contextmanager
def device_trace(trace_dir=None):
    """Wrap a block in a ``jax.profiler`` trace when ``trace_dir`` is set;
    no-op otherwise (safe on any backend).

    Round 7: one mechanism, two spellings — this delegates to
    :func:`pulsarutils_tpu.obs.trace.trace_session`, the session driver
    that can emit the span JSON and the XLA device trace together from a
    single flag (the CLI's ``--trace``); ``device_trace`` remains the
    device-only form the benches use.
    """
    if not trace_dir:
        yield
        return
    with _trace.trace_session(device_trace_dir=trace_dir):
        yield
