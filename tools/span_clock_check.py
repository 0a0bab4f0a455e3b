"""Are the program's spans and the device trace on one clock?

While a span tracer is active every synchronous span of the program also
enters a ``jax.profiler.TraceAnnotation`` of its name (``obs/trace.py``),
so a profile holds the spans twice: as ``perf_counter`` intervals in the
span tracer and as annotations in the profiler's host plane.  This runs a
cell of the benchmark the way ``chipbench/run.py --trace 1`` does — cold
pass, then a few passes inside ``jax.profiler`` with the harness's marker
annotation tying the two clocks — and prints, per span name, how far the
annotations' starts lie from the spans' after the marker's offset.

    python tools/span_clock_check.py --workload <config>.<traffic> \
        --seed N [--passes 2] [--rehearsal]

It also prints where each ``call`` spent the time that no span names (the
longest stretches of the call's self time, with the spans on either side):
what ``call_unattributed_ms_per_pass`` is made of.

One process (whoever imports JAX holds the chip).  Exit status 0 when the
largest difference over the ``chunk`` spans is under ``--limit-ms``.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def annotation_starts(profile, names):
    """``{name: [start_ns]}`` of the host planes' events with these
    names, sorted."""
    import re

    from chipbench import trace_reduce

    out = {}
    for plane in profile.planes:
        if re.search(trace_reduce.DEVICE_PLANE, plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in names:
                    out.setdefault(ev.name, []).append(float(ev.start_ns))
    return {k: sorted(v) for k, v in out.items()}


def unnamed_stretches(spans, top=5):
    """The longest stretches of each ``call`` span that no other span
    covers: ``[(ms, span that ended before, span that starts after)]``."""
    from chipbench import trace_reduce

    out = []
    for lo, hi, name in spans:
        if name != "call":
            continue
        others = [(s, e, n) for s, e, n in spans
                  if (s, e, n) != (lo, hi, name)]
        covered = trace_reduce.union(others, lo, hi)
        for s, e in trace_reduce.gaps(covered, lo, hi):
            before = max((x for x in others if x[1] <= s + 1e-9),
                         key=lambda x: x[1], default=(0, 0, "call start"))
            after = min((x for x in others if x[0] >= e - 1e-9),
                        key=lambda x: x[0], default=(0, 0, "call end"))
            out.append(((e - s) * 1e3, before[2], after[2]))
    return sorted(out, reverse=True)[:top]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--passes", type=int, default=2)
    ap.add_argument("--limit-ms", type=float, default=1.0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="any backend, any pair of config and traffic files")
    opts = ap.parse_args(argv)

    import jax

    from chipbench import generate, trace_reduce
    from chipbench import run as harness
    from pulsarutils_tpu.utils.compile_cache import enable_compile_cache

    _, _, cfg, traffic = harness.resolve_cell(opts.workload, opts.rehearsal)
    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind}", flush=True)
    if dev.platform != "tpu" and not opts.rehearsal:
        return 2
    enable_compile_cache()
    work = tempfile.mkdtemp(prefix="clockcheck_")
    try:
        path = os.path.join(work, "obs.fil")
        generate.generate(path, cfg, traffic, opts.seed)
        cold = harness.run_pass(path, os.path.join(work, "cold"), cfg)
        print(f"cold pass: exit {cold['rc']}, {cold['wall_s']:.1f} s",
              flush=True)
        trace_dir = os.path.join(work, "trace")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        sync_t = time.perf_counter()
        with jax.profiler.TraceAnnotation(trace_reduce.SYNC_NAME):
            time.sleep(0.001)
        try:
            passes = [harness.run_pass(path,
                                       os.path.join(work, f"pass{i}"), cfg,
                                       spans=True)
                      for i in range(opts.passes)]
        finally:
            jax.profiler.stop_trace()
        profile = trace_reduce.load(trace_reduce.find_xplane(trace_dir))
        offset = trace_reduce.sync_offset_ns(profile, sync_t)
        if offset is None:
            print("the marker annotation is not in the profile")
            return 1
        spans = {}
        for p in passes:
            for start, _, name in p["spans"]:
                spans.setdefault(name, []).append(start)
        found = annotation_starts(profile, set(spans))
        report, worst_chunk = {}, None
        for name, starts in sorted(spans.items()):
            ann = found.get(name, [])
            if len(ann) != len(starts):
                report[name] = {"spans": len(starts),
                                "annotations": len(ann)}
                continue
            diffs = [abs((a - offset) / 1e9 - s) * 1e3
                     for a, s in zip(ann, sorted(starts))]
            report[name] = {"n": len(diffs), "max_diff_ms": max(diffs)}
            if name == "chunk":
                worst_chunk = max(diffs)
        for i, p in enumerate(passes):
            print(f"pass {i}: unnamed stretches of call (ms, after, before): "
                  + json.dumps(unnamed_stretches(p["spans"])), flush=True)
        print("span-vs-annotation start difference by span name: "
              + json.dumps(report), flush=True)
        print(json.dumps({"chunk_max_diff_ms": worst_chunk,
                          "max_diff_ms": max(
                              (r["max_diff_ms"] for r in report.values()
                               if "max_diff_ms" in r), default=None),
                          "unmatched": sorted(k for k, r in report.items()
                                              if "n" not in r)}),
              flush=True)
        return 0 if worst_chunk is not None \
            and worst_chunk < opts.limit_ms else 1
    finally:
        shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
