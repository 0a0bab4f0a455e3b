"""Span tracing: the one wall-clock timing primitive of the framework.

A **span** is a named interval measured with ``time.perf_counter``.
Spans are cheap enough for hot paths (two clock reads; nothing else when
no tracer is active) and serve two consumers at once:

* the :class:`~pulsarutils_tpu.utils.logging_utils.BudgetAccountant`
  reads each span's measured duration for its per-chunk bucket ledger
  (the budget layer is a *consumer* of span events, not a parallel
  bookkeeping system — round 7);
* an active :class:`Tracer` records every completed span as a Chrome
  trace event (``{"traceEvents": [...]}`` JSON), loadable in Perfetto /
  ``chrome://tracing``, with one track per chunk (see :func:`set_track`)
  and one per worker thread.

Synchronous nesting is the common case (:func:`span`); device work that
*completes* later than the call that launched it gets an **async span**
(:func:`begin_span` → ``handle.end()``), which may finish on another
thread and out of stack order — exactly how an async device dispatch
relates to its block-until-ready readback.

Distributed tracing (ISSUE 14): a **trace context** — a ``trace_id``
plus the parent span id that caused this work — binds via
:func:`trace_context` and is stamped onto every span recorded while
bound, so one fleet lease's spans on the coordinator and on the worker
that ran it share one ``trace_id`` across the process boundary (the
ids ride the fleet wire; :mod:`.collector` stitches the per-process
traces into one clock-aligned Perfetto file).  In-process fleet
workers each get their OWN tracer via :func:`push_tracer` (a
contextvar override of the process-wide default), so a worker's spans
drain over the wire under its identity even when coordinator and
workers share one process.

Span identity (ISSUE 25): while a tracer is active every recorded event
carries a ``span_id`` and the ``parent_id`` of the span that was open
when it started (:data:`_OPEN_SPAN`; an async span takes the span open
at ``begin``), beside the bound context's ``trace_id`` — so one
``PUsearchfrb`` call is one tree across the main, reader and persist
threads.  Each synchronous span also enters a
``jax.profiler.TraceAnnotation`` of the same name, which puts the
program's spans in the profiler's own host plane, on the device trace's
clock.  With no tracer active none of this runs: no id is allocated and
no annotation is constructed.

A cold start (ISSUE 38): where a program is built, the ``build/*``
spans hang under whatever span was open (:func:`build_span_name`; the
``jax.monitoring`` listener of ``utils/logging_utils.py`` opens one per
build phase, the kernels' call sites one per Pallas kernel body), and
while the process-wide tracer is on every pause of the garbage
collector is counted, the long ones recorded as ``gc`` spans
(:class:`_GcWatch`).

The module imports nothing outside the standard library and this
package, and never imports jax (the annotation class
is looked up through ``sys.modules``: a process that never imported jax
has no profiler to annotate); :func:`trace_session` drives
``jax.profiler`` lazily so one flag can emit both the span JSON and the
XLA device trace into the same run directory.
"""

from __future__ import annotations

import contextlib
import contextvars
import gc
import itertools
import json
import logging
import sys
import threading
import time
import uuid

from . import metrics as _metrics

logger = logging.getLogger("pulsarutils_tpu")

#: the process-wide active tracer (None = tracing off).  A bare module
#: global on purpose: reads must stay cheap in hot paths, and
#: start/stop happen at run granularity, not per span.
_TRACER = None

#: per-context tracer OVERRIDE (ISSUE 14): an in-process fleet worker
#: pushes its own :class:`Tracer` here so its spans — including every
#: driver span recorded on the worker's thread — land on the worker's
#: tracer, not the process default.  Threads the worker spawns do not
#: inherit the contextvar, but the spans that matter there are
#: :class:`AsyncSpan` handles whose tracer was captured at ``begin``.
_TRACER_VAR = contextvars.ContextVar("putpu_tracer", default=None)

#: the bound distributed-trace context: ``{"trace_id": str,
#: "parent_span_id": str|None}`` or None.  Read once per recorded span.
_TRACE_CTX = contextvars.ContextVar("putpu_trace_ctx", default=None)

#: logical track for spans on this (logical) thread of control — set per
#: chunk by the budget accountant so each chunk renders as its own
#: Perfetto track.  ContextVar, not thread-local: worker threads started
#: per chunk inherit the chunk's context.
_TRACK = contextvars.ContextVar("putpu_trace_track", default=None)


#: id of the innermost span open in this context: the ``parent_id`` of
#: whatever starts next.  Set only while a tracer is active.
_OPEN_SPAN = contextvars.ContextVar("putpu_trace_open_span", default=None)


def new_trace_id():
    """A fresh 16-hex-char distributed trace id (no central allocator:
    collision odds over a survey's unit count are negligible)."""
    return uuid.uuid4().hex[:16]


@contextlib.contextmanager
def trace_context(trace_id, parent_span_id=None):
    """Bind a distributed-trace context: every span recorded in this
    context carries ``trace_id`` (and ``parent_span_id`` when given) in
    its args, so cross-process consumers can stitch one causal timeline
    per job/lease.  Free when no tracer is active; nestable (the inner
    binding wins)."""
    ctx = {"trace_id": str(trace_id)}
    if parent_span_id is not None:
        ctx["parent_span_id"] = str(parent_span_id)
    token = _TRACE_CTX.set(ctx)
    try:
        yield ctx
    finally:
        _TRACE_CTX.reset(token)


def current_trace_context():
    """The bound trace context dict, or ``None``."""
    return _TRACE_CTX.get()


def push_tracer(tracer):
    """Install ``tracer`` as this context's tracer (overrides the
    process-wide one set by :func:`start_tracing`).  Pair with
    :func:`pop_tracer`.  The fleet worker's seam: N in-process workers
    each trace under their own identity."""
    return _TRACER_VAR.set(tracer)


def pop_tracer(token):
    _TRACER_VAR.reset(token)


class Span:
    """One timed interval, started by :func:`open_span`.  ``dur`` is
    valid after :func:`close_span`."""

    __slots__ = ("name", "attrs", "t0", "t1", "dur", "span_id",
                 "parent_id", "_token", "_annotation")

    def __init__(self, name, attrs=None):
        self.name = name
        self.attrs = attrs
        self.t0 = self.t1 = self.dur = None
        self.span_id = self.parent_id = self._token = None
        self._annotation = None


def _annotation_class():
    """``jax.profiler.TraceAnnotation`` when this process has imported
    jax, else ``None`` — this module never imports it."""
    jax = sys.modules.get("jax")
    return getattr(getattr(jax, "profiler", None), "TraceAnnotation", None)


def open_span(name, attrs=None):
    """Start a span NOW.  Pair with :func:`close_span` in a finally."""
    s = Span(name, attrs)
    tr = _TRACER_VAR.get() or _TRACER
    if tr is not None:
        # identity and the profiler annotation first, the clock last:
        # the bookkeeping stays outside the interval the span measures
        annotation = _annotation_class()
        if annotation is not None:
            s._annotation = annotation(name)
            s._annotation.__enter__()
        s.span_id = tr.next_id()
        s.parent_id = _OPEN_SPAN.get()
        s._token = _OPEN_SPAN.set(s.span_id)
    s.t0 = time.perf_counter()
    return s


def close_span(s, track=None):
    """End ``s``; record it on the active tracer (if any).  Returns ``s``
    with ``dur`` set — consumers (the budget accountant) read it from
    there, so there is exactly one measurement per interval."""
    s.t1 = time.perf_counter()
    s.dur = s.t1 - s.t0
    if s._token is not None:
        _OPEN_SPAN.reset(s._token)
        if s._annotation is not None:
            s._annotation.__exit__(None, None, None)
    tr = _TRACER_VAR.get() or _TRACER
    if tr is not None:
        tr.complete(s, track)
    return s


@contextlib.contextmanager
def span(name, track=None, **attrs):
    """Context manager form: ``with span("search", chunk=3): ...``.

    Yields the :class:`Span` (its ``dur`` is set on exit).  ``track``
    overrides the contextvar track for this one event.
    """
    s = open_span(name, attrs or None)
    try:
        yield s
    finally:
        close_span(s, track=track)


class _NullAsync:
    """Returned by :func:`begin_span` when tracing is off: free to end."""

    __slots__ = ()

    def end(self, **attrs):
        pass

    def follow(self, name, **attrs):
        return self


_NULL_ASYNC = _NullAsync()


class AsyncSpan:
    """A span completed explicitly — possibly later, possibly on another
    thread (device dispatch → readback, persist submit → worker done).
    Emitted as a Chrome async ``b``/``e`` pair so it need not nest."""

    __slots__ = ("name", "attrs", "track", "t0", "_tracer", "_id",
                 "parent_id", "_ctx", "_done")

    def __init__(self, name, attrs, track, tracer, after=None):
        self.name = name
        self.attrs = attrs
        self.track = track
        self._tracer = tracer
        self._id = tracer.next_id()
        # caused by the span open where it begins; it may end on a
        # thread that inherits no context, so parent and trace context
        # are taken here (or from the span it follows, see ``follow``)
        self.parent_id = (_OPEN_SPAN.get() if after is None
                          else after.parent_id)
        self._ctx = _TRACE_CTX.get() if after is None else after._ctx
        self._done = False
        self.t0 = time.perf_counter()
        tracer.async_begin(self)

    def follow(self, name, **attrs):
        """Begin NOW the span of what a worker does next on this span's
        track (a chunk's read, then its upload): same tracer, track,
        parent and trace context, from whichever thread calls — a
        worker thread has none of them to take."""
        return AsyncSpan(name, attrs or None, self.track, self._tracer,
                         after=self)

    def end(self, **attrs):
        """Complete the span (idempotent; safe after the tracer stopped)."""
        if self._done:
            return
        self._done = True
        self._tracer.async_end(self, time.perf_counter(), attrs or None)


def begin_span(name, track=None, **attrs):
    """Open an async span on the active tracer; no-op handle when
    tracing is off (callers hold the handle and ``end()`` it blindly)."""
    tr = _TRACER_VAR.get() or _TRACER
    if tr is None:
        return _NULL_ASYNC
    return AsyncSpan(name, attrs or None, track or _TRACK.get(), tr)


@contextlib.contextmanager
def set_track(name):
    """Route spans in this context onto the named Perfetto track."""
    token = _TRACK.set(name)
    try:
        yield
    finally:
        _TRACK.reset(token)


def push_track(name):
    """Non-contextmanager :func:`set_track` (pair with :func:`pop_track`)."""
    return _TRACK.set(name)


def pop_track(token):
    _TRACK.reset(token)


def _jsonable(v):
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    return str(v)


class Tracer:
    """Collects completed spans; exports Chrome trace-event JSON."""

    def __init__(self):
        self._lock = threading.Lock()
        self._events = []
        self._tracks = {}       # track name -> tid (1-based, stable order)
        self._seq = itertools.count(1)
        self._closed = False
        # both clocks anchored back-to-back: ``epoch`` is the event
        # timescale (perf_counter, monotonic), ``epoch_unix`` is the
        # same instant on the wall clock — the anchor the distributed
        # collector uses to place this process's events on a shared,
        # skew-corrected timeline (ISSUE 14)
        self.epoch = time.perf_counter()
        self.epoch_unix = time.time()

    def next_id(self):
        return next(self._seq)

    def _tid(self, track):
        if track is None:
            t = threading.current_thread()
            track = ("main" if t is threading.main_thread()
                     else t.name or f"thread-{t.ident}")
        # locked check-then-insert: two threads first-using new tracks
        # concurrently must not be assigned the same tid (merged rows)
        with self._lock:
            tid = self._tracks.get(track)
            if tid is None:
                tid = len(self._tracks) + 1
                self._tracks[track] = tid
        return tid

    def _append(self, ev):
        with self._lock:
            if not self._closed:
                self._events.append(ev)

    def _ts(self, t):
        return round((t - self.epoch) * 1e6, 3)  # perf_counter s -> us

    @staticmethod
    def _stamp(ev, span_id, parent_id, ctx):
        """Merge the span's identity and its distributed-trace context
        (``trace_id``, the cross-process ``parent_span_id``) into ``ev``
        args, so a worker's unit spans carry the lease's ``trace_id``
        across the wire."""
        args = ev.setdefault("args", {})
        if span_id is not None:
            args["span_id"] = span_id
        if parent_id is not None:
            args["parent_id"] = parent_id
        if ctx is not None:
            args.update(ctx)
        if not args:
            del ev["args"]
        return ev

    def complete(self, s, track=None):
        # track and context bound on the recording thread, read at
        # record time
        self.record(s.name, s.t0, s.t1, s.attrs, s.span_id, s.parent_id,
                    track if track is not None else _TRACK.get(),
                    _TRACE_CTX.get())

    def record(self, name, t0, t1, attrs, span_id, parent_id, track, ctx):
        """One ``X`` event from an interval measured elsewhere
        (``perf_counter`` seconds), its identity and context given."""
        ev = {"name": name, "ph": "X", "pid": 1, "tid": self._tid(track),
              "ts": self._ts(t0), "dur": round((t1 - t0) * 1e6, 3)}
        if attrs:
            ev["args"] = {k: _jsonable(v) for k, v in attrs.items()}
        self._append(self._stamp(ev, span_id, parent_id, ctx))

    def async_begin(self, a):
        ev = {"name": a.name, "ph": "b", "cat": "async", "id": a._id,
              "pid": 1, "tid": self._tid(a.track), "ts": self._ts(a.t0)}
        if a.attrs:
            ev["args"] = {k: _jsonable(v) for k, v in a.attrs.items()}
        self._append(self._stamp(ev, a._id, a.parent_id, a._ctx))

    def async_end(self, a, t1, attrs=None):
        ev = {"name": a.name, "ph": "e", "cat": "async", "id": a._id,
              "pid": 1, "tid": self._tid(a.track), "ts": self._ts(t1)}
        if attrs:
            ev["args"] = {k: _jsonable(v) for k, v in attrs.items()}
        self._append(self._stamp(ev, a._id, a.parent_id, a._ctx))

    def close(self):
        with self._lock:
            self._closed = True

    # -- export --------------------------------------------------------------

    def events_since(self, mark=0):
        """``(events, new_mark)`` — the span events recorded at index
        ``mark`` onward plus the cursor for the next call.  The fleet
        worker's incremental drain: each ``complete`` message ships only
        the events since the previous one, while the full list stays in
        place for an end-of-run :meth:`export`."""
        with self._lock:
            return list(self._events[mark:]), len(self._events)

    def tracks(self):
        """``{track name: tid}`` snapshot (ships beside drained events
        so the collector can name the worker's rows)."""
        with self._lock:
            return dict(self._tracks)

    def to_chrome(self):
        """The Chrome trace-event dict (metadata + recorded events).
        The extra top-level ``putpu`` key (Perfetto ignores unknown
        keys) carries the wall-clock anchor :mod:`.collector` and
        ``tools/trace_merge.py`` need for post-hoc cross-process
        stitching."""
        with self._lock:
            events = list(self._events)
            tracks = dict(self._tracks)
        meta = [{"name": "process_name", "ph": "M", "pid": 1,
                 "args": {"name": "pulsarutils_tpu"}}]
        for track, tid in tracks.items():
            meta.append({"name": "thread_name", "ph": "M", "pid": 1,
                         "tid": tid, "args": {"name": track}})
            meta.append({"name": "thread_sort_index", "ph": "M", "pid": 1,
                         "tid": tid, "args": {"sort_index": tid}})
        return {"traceEvents": meta + events, "displayTimeUnit": "ms",
                "putpu": {"epoch_unix": self.epoch_unix}}

    def export(self, path, extra_meta=None):
        """Write the trace JSON; returns the number of span events.
        ``extra_meta`` merges into the ``putpu`` stitching envelope —
        the fleet worker records its measured ``clock_offset_s`` there
        so an offline ``tools/trace_merge.py`` corrects skew exactly as
        the live collector would."""
        doc = self.to_chrome()
        if extra_meta:
            doc["putpu"].update(extra_meta)
        with open(path, "w") as f:
            json.dump(doc, f)
        n = sum(ev.get("ph") in ("X", "b") for ev in doc["traceEvents"])
        logger.info("trace: %d spans on %d tracks -> %s",
                    n, len(self._tracks), path)
        return n


def build_span_name(kind, what):
    """``build/<kind>:<what>``: the name of a span of a cold start.
    ``kind`` is a build phase (``trace``, ``lower``, ``compile``; ``what``
    the program as the device trace names it, ``jit_fn``), ``kernel``
    (the Python that binds one Pallas kernel and traces its body, inside
    its program's tracing), ``levels`` (the walk of a sweep's per-level
    merges where no fused head runs, around their ``kernel`` spans) or
    ``plan`` (host work before ``jax.jit``)."""
    return f"build/{kind}:{what}"


#: a pause at least this long is recorded as a ``gc`` span
GC_SPAN_MIN_S = 1e-3


class _GcWatch:
    """The ``gc.callbacks`` entry of the process-wide tracer.

    A collection holds the interpreter lock, so its pause stalls the
    main thread whichever thread set it off.  The callback runs inside
    the collector, under whatever lock the interrupted code holds, so
    it takes none: pauses are kept here and handed to the tracer and
    the counter by :meth:`flush`, when tracing stops.
    """

    def __init__(self, tracer):
        self.tracer = tracer
        self.total_s = 0.0
        self._t0 = None
        self._long = []

    def __call__(self, phase, info):
        now = time.perf_counter()
        if phase == "start":
            self._t0 = now
        elif self._t0 is not None:  # None: installed inside a collection
            self.total_s += now - self._t0
            if now - self._t0 >= GC_SPAN_MIN_S:
                self._long.append(
                    (self._t0, now, {"generation": info["generation"],
                                     "collected": info["collected"]},
                     self.tracer.next_id(), _OPEN_SPAN.get(), _TRACK.get(),
                     _TRACE_CTX.get()))
            self._t0 = None

    def flush(self):
        for pause in self._long:
            self.tracer.record("gc", *pause)
        _metrics.counter("putpu_gc_pause_seconds_total").inc(self.total_s)


_GC_WATCH = None


def _swap_gc_watch(tracer):
    """Remove the active tracer's collector callback, its pauses flushed,
    and install one for ``tracer`` (``None``: none)."""
    global _GC_WATCH
    watch, _GC_WATCH = _GC_WATCH, None
    if watch is not None:
        gc.callbacks.remove(watch)
        watch.flush()
    if tracer is not None:
        _GC_WATCH = _GcWatch(tracer)
        gc.callbacks.append(_GC_WATCH)


def start_tracing():
    """Install a fresh process-wide tracer and return it (replaces any
    active one — the replaced tracer keeps its recorded events).  While
    it is active the collector's pauses are counted
    (``putpu_gc_pause_seconds_total``) and the long ones recorded as
    ``gc`` spans."""
    global _TRACER
    tracer = Tracer()
    _swap_gc_watch(tracer)
    _TRACER = tracer
    return tracer


def stop_tracing():
    """Deactivate and return the current tracer (``None`` if inactive).
    Late ``AsyncSpan.end()`` calls against it are dropped safely."""
    global _TRACER
    tracer = _TRACER
    _TRACER = None
    _swap_gc_watch(None)
    if tracer is not None:
        tracer.close()
    return tracer


def active_tracer():
    """This context's tracer: the :func:`push_tracer` override when one
    is bound, else the process-wide tracer."""
    return _TRACER_VAR.get() or _TRACER


def is_tracing():
    return (_TRACER_VAR.get() or _TRACER) is not None


@contextlib.contextmanager
def trace_session(path=None, device_trace_dir=None):
    """One flag, both traces (ISSUE 3 satellite): wraps a block in the
    span tracer (exported to ``path`` as Chrome/Perfetto JSON) and — when
    ``device_trace_dir`` is set — a ``jax.profiler`` device trace into
    the same run directory.  Either side may be used alone;
    ``utils.logging_utils.device_trace`` is the device-only spelling.

    Yields the :class:`Tracer` (or ``None`` when ``path`` is unset).
    Profiler failures degrade to a warning — observability must never
    take down a survey run.
    """
    tracer = start_tracing() if path else None
    profiling = False
    if device_trace_dir:
        try:
            import jax

            jax.profiler.start_trace(str(device_trace_dir))
            profiling = True
        except Exception as exc:
            logger.warning("jax.profiler trace unavailable (%r); span "
                           "trace unaffected", exc)
    try:
        yield tracer
    finally:
        if profiling:
            try:
                import jax

                jax.profiler.stop_trace()
                logger.info("device trace -> %s", device_trace_dir)
            except Exception as exc:
                logger.warning("jax.profiler stop_trace failed: %r", exc)
        if tracer is not None:
            stop_tracing()
            tracer.export(path)
