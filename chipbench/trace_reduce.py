"""From a ``jax.profiler`` trace to device busy time, kernel time and gaps.

The one reduction every PR's numbers go through.  Reads the ``.xplane.pb``
with ``jax.profiler.ProfileData`` (nothing but JAX), takes the device
planes (``/device:TPU:n``) and, on each, the line that holds the executed
operations (``XLA Ops``):

* busy: the union of the operations' intervals, clipped to the window,
  averaged over the device planes;
* an operation's time: the sum of its events' durations, under the name
  ``<program>/<operation>``: the trace names an operation by its whole HLO
  text, of which the result's name is kept (``fn.2``), and the program is
  the ``XLA Modules`` event that contains it, without its hash
  (``jit_fn``); a kernel is a regular expression over these names;
* idle gaps: the complement of the union inside the window, longest first,
  each named by the innermost host span (the program's span tracer, same
  ``perf_counter`` clock as the harness) that covers its middle.

The trace's clock and ``perf_counter`` are tied by a marker: the harness
opens a ``jax.profiler.TraceAnnotation`` named :data:`SYNC_NAME` and notes
``perf_counter`` at that moment; the marker's start in the trace gives the
offset.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

SYNC_NAME = "chipbench_sync"
DEVICE_PLANE = r"^/device:TPU:\d+$"
OPS_LINE = r"^XLA Ops$"
MODULES_LINE = r"^XLA Modules$"


def find_xplane(trace_dir):
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def short_name(name):
    """``%fn.2 = f32[...] custom-call(...)`` -> ``fn.2``;
    ``jit_fn(8996191183167308178)`` -> ``jit_fn``."""
    name = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\(\d+\)$", "", name)


def _line_events(plane, line_re):
    evs = []
    for line in plane.lines:
        if re.search(line_re, line.name):
            for ev in line.events:
                start = float(ev.start_ns)
                evs.append((start, start + float(ev.duration_ns),
                            short_name(ev.name)))
    evs.sort()
    return evs


def op_events(profile, plane_re=DEVICE_PLANE, line_re=OPS_LINE,
              module_re=MODULES_LINE):
    """``{plane name: [(start_ns, end_ns, "<program>/<operation>")]}``
    sorted by start."""
    out = {}
    for plane in profile.planes:
        if not re.search(plane_re, plane.name):
            continue
        modules = _line_events(plane, module_re)
        starts = [m[0] for m in modules]
        evs = []
        for s, e, name in _line_events(plane, line_re):
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and s < modules[i][1]:
                name = f"{modules[i][2]}/{name}"
            evs.append((s, e, name))
        out[plane.name] = evs
    return out


def sync_offset_ns(profile, sync_perf_counter_s, name=SYNC_NAME):
    """``trace_ns - perf_counter_ns``, or None when the marker is absent."""
    for plane in profile.planes:
        if re.search(DEVICE_PLANE, plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == name:
                    return float(ev.start_ns) - sync_perf_counter_s * 1e9
    return None


def union(intervals, lo=None, hi=None):
    """Merged, clipped ``[(start, end)]`` of ``(start, end, ...)`` tuples."""
    merged = []
    for iv in sorted(intervals):
        s, e = iv[0], iv[1]
        if lo is not None:
            s = max(s, lo)
        if hi is not None:
            e = min(e, hi)
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def gaps(merged, lo, hi):
    out, cur = [], lo
    for s, e in merged:
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        out.append((cur, hi))
    return out


def name_gap(mid_s, spans):
    """Innermost (shortest) host span covering ``mid_s`` (perf_counter
    seconds); ``spans`` is ``[(start_s, end_s, name)]``."""
    best = None
    for s, e, name in spans:
        if s <= mid_s <= e and (best is None or e - s < best[0]):
            best = (e - s, name)
    return best[1] if best else "unattributed"


def reduce_trace(profile, window_perf_s=None, sync_perf_counter_s=None,
                 host_spans=(), top=10, plane_re=DEVICE_PLANE,
                 line_re=OPS_LINE):
    """The reduction.  ``window_perf_s`` is ``(start, end)`` on the
    ``perf_counter`` clock; without a clock tie the window is the span of
    the device events themselves and gaps go unnamed."""
    events = op_events(profile, plane_re, line_re)
    offset = (sync_offset_ns(profile, sync_perf_counter_s)
              if sync_perf_counter_s is not None else None)
    flat = [ev for evs in events.values() for ev in evs]
    if not flat:
        return {"planes": sorted(events), "busy_s": 0.0, "window_s": 0.0,
                "device_ops": [], "idle_gaps": [], "op_seconds": {},
                "longest_gaps_s": [],
                "clock_tied": offset is not None}
    if window_perf_s is not None and offset is not None:
        lo = window_perf_s[0] * 1e9 + offset
        hi = window_perf_s[1] * 1e9 + offset
    else:
        lo = min(e[0] for e in flat)
        hi = max(e[1] for e in flat)
    busy, all_gaps = [], []
    for plane, evs in events.items():
        merged = union(evs, lo, hi)
        busy.append(sum(e - s for s, e in merged))
        all_gaps += gaps(merged, lo, hi)
    op_seconds = {}
    for s, e, name in flat:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            op_seconds[name] = op_seconds.get(name, 0.0) + (e - s) / 1e9
    nplanes = max(len(events), 1)
    op_seconds = {k: v / nplanes for k, v in op_seconds.items()}
    all_gaps.sort(key=lambda g: g[0] - g[1])
    named = {}
    for s, e in all_gaps:
        if offset is not None:
            label = name_gap(((s + e) / 2 - offset) / 1e9, host_spans)
        else:
            label = "unattributed"
        named[label] = named.get(label, 0.0) + (e - s) / 1e9 / nplanes
    return {
        "planes": sorted(events),
        "busy_s": sum(busy) / nplanes / 1e9,
        "window_s": (hi - lo) / 1e9,
        "device_ops": [[k, v] for k, v in sorted(
            op_seconds.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, v] for k, v in sorted(
            named.items(), key=lambda kv: -kv[1])[:top]],
        "longest_gaps_s": [(e - s) / 1e9 for s, e in all_gaps[:top]],
        "op_seconds": op_seconds,
        "clock_tied": offset is not None,
    }


def kernel_seconds(op_seconds, pattern):
    """Summed device seconds of the operations whose name matches."""
    rx = re.compile(pattern)
    hit = {k: v for k, v in op_seconds.items() if rx.search(k)}
    return sum(hit.values()), sorted(hit)
