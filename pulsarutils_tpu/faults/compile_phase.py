"""Tell a failure to BUILD a program from a failure to RUN one.

The survey driver's retry -> NumPy policy exists for transient device
faults (a lost worker, a wedged dispatch).  An error raised while JAX
traces, lowers or compiles a program is not one of those: a Mosaic
refusal or a compile-time out-of-memory fails identically on every
retry and on every later chunk, and falling back to the ~1000x slower
host path turns it into a hang or an empty "successful" run.  Such
errors must propagate like the configuration errors do.

JAX's exception types do not separate the two (both a compile-time and
a run-time failure surface as ``jax.errors.JaxRuntimeError``, and a
lowering refusal is a plain ``NotImplementedError``), and its traceback
filtering drops the frames that would.  What the installed JAX (0.9)
does provide: the three build phases each run inside a
``jax.monitoring`` duration event whose listeners are called from the
phase's ``__exit__`` — while a failing phase's exception is still
propagating, so ``sys.exception()`` names it.  One listener marks that
exception object; :func:`failed_phase` reads the mark back wherever
the exception is caught.  (Compile *seconds* are counted elsewhere:
:func:`pulsarutils_tpu.utils.logging_utils.compile_snapshot`.)
"""

from __future__ import annotations

import sys
import threading

__all__ = ["install", "failed_phase"]

#: jax.monitoring duration events that wrap the three build phases
_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}

_MARK = "_putpu_failed_phase"
_lock = threading.Lock()
_installed = False


def _listener(event, duration, **_kwargs):
    phase = _PHASES.get(event)
    exc = sys.exception() if phase is not None else None
    if exc is not None and getattr(exc, _MARK, None) is None:
        # innermost phase wins: a lowering failure also unwinds through
        # the enclosing trace phases of nested jits
        setattr(exc, _MARK, phase)


def install():
    """Register the listener (idempotent; needs JAX importable)."""
    global _installed
    with _lock:
        if _installed:
            return
        _installed = True
    from jax import monitoring

    monitoring.register_event_duration_secs_listener(_listener)


def failed_phase(exc):
    """``"trace"``/``"lower"``/``"compile"`` when ``exc`` (or an
    exception it was raised from) escaped one of JAX's build phases,
    else ``None`` — a run-time failure, or :func:`install` never ran."""
    seen = set()
    while exc is not None and id(exc) not in seen:
        seen.add(id(exc))
        phase = getattr(exc, _MARK, None)
        if phase is not None:
            return phase
        exc = exc.__cause__ or exc.__context__
    return None

