"""Per-dispatch roofline accounting: measured wall vs XLA's own cost model.

For every instrumented kernel dispatch the facades record the measured
dispatch→readback wall next to the FLOPs and bytes-accessed XLA reports
for the *compiled executable* (``compiled.cost_analysis()``), giving:

* achieved GFLOP/s and GB/s per kernel;
* the **achieved fraction of ideal**: ``ideal_wall / measured_wall``
  where ``ideal_wall = max(flops / peak_flops, bytes / peak_bw)`` — the
  classic roofline bound for the current backend's peaks.

Cost: obtaining ``cost_analysis`` requires an AOT ``lower().compile()``
of the already-jitted callable — one extra XLA compile per (kernel,
shape signature).  That is why roofline accounting is **opt-in**
(:func:`enable`, the CLI's ``--trace`` flag, or ``PUTPU_ROOFLINE=1``)
and cached per signature; with the persistent compilation cache on, the
extra compile is a disk hit.  When disabled, the call-site hooks
(:func:`begin` / :func:`end`) are a single global read.

Peaks are keyed by the device's own ``device_kind`` (one table,
:data:`DEVICE_PEAKS`, each entry with its source; override with
``PUTPU_PEAK_FLOPS`` / ``PUTPU_PEAK_BYTES_PER_S`` or :func:`set_peaks`).
A device that is not in the table — every CPU, any accelerator nobody
has looked up — gets no fraction, never a guess: achieved rates are
still reported, the fraction column reads ``-``.
"""

from __future__ import annotations

import os
import threading
import time

from . import metrics

__all__ = ["enable", "disable", "enabled", "set_peaks", "begin", "end",
           "record", "table", "log_table", "reset"]

_LOCK = threading.Lock()
_ENABLED = None          # tri-state: None = consult env once
_PEAKS = None            # (flops/s, bytes/s) or (None, None)
_COSTS = {}              # (name, signature) -> {"flops","bytes"} | None
_STATS = {}              # name -> accumulated dict

#: published single-chip peaks by ``jax.devices()[0].device_kind``:
#: ``(FLOP/s, HBM bytes/s, source)``.  The FLOP/s figure is the chip's
#: bf16 matrix peak — the roof XLA's cost model counts against; this
#: package's f32 VPU kernels sit far below it by construction, so their
#: fraction is bound by the bytes term.
DEVICE_PEAKS = {
    "TPU v5 lite": (1.97e14, 8.19e11,
                    "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
                    "bf16, 819 GB/s HBM per chip"),
}


def enable():
    global _ENABLED
    _ENABLED = True


def disable():
    global _ENABLED
    _ENABLED = False


def enabled():
    global _ENABLED
    if _ENABLED is None:
        _ENABLED = os.environ.get("PUTPU_ROOFLINE", "") not in ("", "0")
    return _ENABLED


def set_peaks(peak_flops=None, peak_bytes_per_s=None):
    """Pin the roofline peaks (FLOP/s, bytes/s) instead of the backend
    defaults; ``None`` leaves the corresponding bound unset."""
    global _PEAKS
    _PEAKS = (peak_flops, peak_bytes_per_s)


def _peaks():
    global _PEAKS
    if _PEAKS is None:
        env_f = os.environ.get("PUTPU_PEAK_FLOPS")
        env_b = os.environ.get("PUTPU_PEAK_BYTES_PER_S")
        if env_f or env_b:
            _PEAKS = (float(env_f) if env_f else None,
                      float(env_b) if env_b else None)
        else:
            import jax

            _PEAKS = DEVICE_PEAKS.get(jax.devices()[0].device_kind,
                                      (None, None))[:2]
    return _PEAKS


def reset():
    """Clear accumulated stats and the cost cache (tests)."""
    global _PEAKS
    with _LOCK:
        _COSTS.clear()
        _STATS.clear()
    _PEAKS = None


def _signature(args):
    sig = []
    for a in args:
        shape = getattr(a, "shape", ())
        dtype = str(getattr(a, "dtype", type(a).__name__))
        sig.append((tuple(shape), dtype))
    return tuple(sig)


def _analyze(fn, args):
    """FLOPs + bytes accessed of the compiled executable, or ``None``
    when the callable cannot be AOT-lowered (non-jit, API drift)."""
    try:
        lowered = fn.lower(*args)
        compiled = lowered.compile()
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):  # one entry per device program
            ca = ca[0] if ca else {}
        flops = float(ca.get("flops", 0.0))
        nbytes = float(ca.get("bytes accessed", 0.0))
        return {"flops": flops, "bytes": nbytes}
    except Exception:
        return None


# -- call-site hooks ---------------------------------------------------------

def begin():
    """Start a roofline measurement; returns ``None`` when disabled (the
    matching :func:`end` is then free).  Call OUTSIDE the dispatch so
    the wall covers dispatch + block-until-ready readback."""
    if not enabled():
        return None
    return time.perf_counter()


def end(token, name, fn, args):
    """Finish a measurement started by :func:`begin` and record it."""
    if token is None:
        return
    record(name, fn, args, time.perf_counter() - token)


def record(name, fn, args, wall_s):
    """Attribute one completed dispatch of ``fn(*args)`` (``wall_s``
    measured dispatch→ready) to kernel ``name``.  No-op when disabled."""
    if not enabled():
        return
    key = (name, _signature(args))
    with _LOCK:
        have = key in _COSTS
        cost = _COSTS.get(key)
    if not have:
        cost = _analyze(fn, args)
        with _LOCK:
            _COSTS[key] = cost
    with _LOCK:
        st = _STATS.setdefault(name, {"calls": 0, "wall_s": 0.0,
                                      "flops": 0.0, "bytes": 0.0,
                                      "uncosted": 0})
        st["calls"] += 1
        st["wall_s"] += wall_s
        if cost is None:
            st["uncosted"] += 1
        else:
            st["flops"] += cost["flops"]
            st["bytes"] += cost["bytes"]
    # gauges: last-dispatch achieved rates per kernel (the table holds
    # the aggregate view)
    if cost is not None and wall_s > 0:
        metrics.gauge("putpu_roofline_gflops", kernel=name).set(
            round(cost["flops"] / wall_s / 1e9, 3))
        metrics.gauge("putpu_roofline_gbytes_per_s", kernel=name).set(
            round(cost["bytes"] / wall_s / 1e9, 3))
        frac = _fraction(cost["flops"], cost["bytes"], wall_s)
        if frac is not None:
            metrics.gauge("putpu_roofline_frac_of_ideal", kernel=name).set(
                round(frac, 4))


def _fraction(flops, nbytes, wall_s):
    peak_f, peak_b = _peaks()
    bounds = [flops / peak_f if peak_f else None,
              nbytes / peak_b if peak_b else None]
    bounds = [b for b in bounds if b is not None]
    if not bounds or wall_s <= 0:
        return None
    return max(bounds) / wall_s


def table():
    """Aggregated per-kernel rows: calls, wall, FLOPs/bytes, achieved
    rates and fraction-of-ideal (``None`` when no peak is known)."""
    with _LOCK:
        stats = {k: dict(v) for k, v in _STATS.items()}
    rows = []
    for name, st in sorted(stats.items(), key=lambda kv: -kv[1]["wall_s"]):
        wall = st["wall_s"]
        row = {"kernel": name, "calls": st["calls"],
               "wall_s": round(wall, 4),
               "gflops_total": round(st["flops"] / 1e9, 3),
               "gbytes_total": round(st["bytes"] / 1e9, 3),
               "achieved_gflops": (round(st["flops"] / wall / 1e9, 3)
                                   if wall > 0 else None),
               "achieved_gbytes_per_s": (round(st["bytes"] / wall / 1e9, 3)
                                         if wall > 0 else None),
               "frac_of_ideal": None,
               "uncosted_calls": st["uncosted"]}
        frac = _fraction(st["flops"], st["bytes"], wall)
        if frac is not None and st["flops"] + st["bytes"] > 0:
            row["frac_of_ideal"] = round(frac, 4)
        rows.append(row)
    return rows


def log_table(log=None):
    """Log the roofline table (one line per kernel); no-op when empty."""
    rows = table()
    if not rows:
        return rows
    if log is None:
        import logging

        log = logging.getLogger("pulsarutils_tpu")
    log.info("roofline (measured wall vs compiled.cost_analysis):")
    for r in rows:
        frac = ("-" if r["frac_of_ideal"] is None
                else f"{100.0 * r['frac_of_ideal']:.1f}%")
        log.info("  %-24s %4d calls %8.3fs  %10.2f GF/s %10.2f GB/s  "
                 "ideal %s", r["kernel"], r["calls"], r["wall_s"],
                 r["achieved_gflops"] or 0.0,
                 r["achieved_gbytes_per_s"] or 0.0, frac)
    return rows
