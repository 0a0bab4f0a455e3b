"""Kernel-autotuner CLI: tune / show / clear / verify the tune cache.

The measured kernel selector (ISSUE 7, ``pulsarutils_tpu/tuning/``)
normally tunes lazily — the first survey chunk at a new (backend,
geometry) key pays the micro-benchmark and every later run reads the
winner from the persistent cache.  This tool makes the cache a
first-class artifact:

* ``tune`` — measure one geometry NOW (pre-warming a production cache,
  or producing a committed artifact like ``TUNE_cpu.json``) and print
  the decision record;
* ``show`` — the per-key decision table of a cache file;
* ``clear`` — drop entries (all, or ``--match`` substring) after a
  kernel change that invalidates old measurements;
* ``verify`` — the artifact check (schema version + shape) plus a
  kernel-name sanity pass, exit 0/1 — the rule tier-1 holds the
  committed ``TUNE_cpu.json`` to.

Examples::

  JAX_PLATFORMS=cpu python tools/autotune.py tune \
      --nchan 256 --nsamples 262144 --ndm 256 --cache TUNE_cpu.json
  python tools/autotune.py show --cache TUNE_cpu.json
  python tools/autotune.py verify --cache TUNE_cpu.json
"""

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

#: the default geometry: start_freq MHz, bandwidth MHz, tsamp s —
#: overridable per invocation
GEOM = (1200.0, 200.0, 0.0005)


def _cache(opts):
    from pulsarutils_tpu.tuning.cache import TuneCache, default_cache_path

    return TuneCache(opts.cache or default_cache_path())


def cmd_tune(opts):
    from pulsarutils_tpu.ops.plan import dedispersion_plan, dmmax_for_trials
    from pulsarutils_tpu.tuning import autotune

    geom = (opts.start_freq, opts.bandwidth, opts.tsamp)
    dmmax = (opts.dmmax if opts.dmmax is not None
             else dmmax_for_trials(opts.dmmin, opts.ndm, *geom))
    trial_dms = dedispersion_plan(opts.nchan, opts.dmmin, dmmax, *geom)
    cache = _cache(opts)
    # a dedicated tuner: floor disabled (an explicit `tune` means
    # "measure this geometry", whatever its size), caller-chosen reps
    tuner = autotune.KernelTuner(cache=cache, mode="on", min_elements=0,
                                 reps=opts.reps,
                                 probe_trials=opts.probe_trials)
    if opts.force:
        import jax

        from pulsarutils_tpu.tuning.geometry import geometry_key

        cache.clear(match=geometry_key(jax.default_backend(), opts.nchan,
                                       opts.nsamples, len(trial_dms)))
    prev = autotune.set_tuner(tuner)
    try:
        mark = autotune.decision_seq()
        kernel = autotune.resolve_search_kernel(
            opts.nchan, opts.nsamples, len(trial_dms), None, False,
            *geom, trial_dms)
    finally:
        autotune.set_tuner(prev)
    decisions = autotune.decisions_since(mark)
    rec = decisions[-1] if decisions else {"kernel": kernel,
                                           "source": "cache (prior run)"}
    print(json.dumps(rec, indent=1))
    if cache.path:
        print(f"tune cache -> {cache.path}", file=sys.stderr)
    elements = opts.nchan * opts.nsamples
    if elements < autotune.MIN_TUNE_ELEMENTS:
        # the consuming resolve path floor-gates the DISK lookup too:
        # without a lowered floor this entry is dead weight — say so
        print(f"note: {opts.nchan}x{opts.nsamples} = {elements} elements "
              f"is below the default tune floor "
              f"({autotune.MIN_TUNE_ELEMENTS}); production kernel=\"auto\" "
              f"will only consult this entry with "
              f"PUTPU_AUTOTUNE_MIN={elements} (or lower) set",
              file=sys.stderr)
    return 0


def cmd_show(opts):
    cache = _cache(opts)
    entries = cache.entries()
    if not entries:
        print(f"(no tuned entries in {cache.path})")
        return 0
    wid = max(len(k) for k in entries)
    print(f"{'geometry key'.ljust(wid)}  kernel  source    measured_s")
    for key in sorted(entries):
        e = entries[key]
        meas = ", ".join(f"{k}={v:.4g}" for k, v in
                         sorted((e.get("measured_s") or {}).items(),
                                key=lambda kv: kv[1]))
        print(f"{key.ljust(wid)}  {e['kernel']:<6}  {e.get('source', '-'):<8}"
              f"  {meas or '-'}")
    print(f"{len(entries)} tuned key(s) in {cache.path}", file=sys.stderr)
    return 0


def cmd_clear(opts):
    cache = _cache(opts)
    removed = cache.clear(match=opts.match)
    print(f"removed {removed} entr{'y' if removed == 1 else 'ies'} "
          f"from {cache.path}")
    return 0


def cmd_verify(opts):
    from pulsarutils_tpu.tuning.cache import (
        TUNE_SCHEMA_VERSION,
        check_artifact,
    )

    path = opts.cache or os.path.join(REPO, "TUNE_cpu.json")
    ok, detail = check_artifact(path, expect_version=opts.expect_version
                                if opts.expect_version is not None
                                else TUNE_SCHEMA_VERSION)
    print(f"{path}: {'ok' if ok else 'FAIL'} — {detail}")
    if not ok:
        return 1
    # beyond the schema gate: every stored winner must name a kernel
    # the search layer can actually run
    known = {"gather", "roll", "pallas"}
    with open(path, encoding="utf-8") as f:
        entries = json.load(f)["entries"]
    bad = {k: e.get("kernel") for k, e in entries.items()
           if e.get("kernel") not in known}
    if bad:
        print(f"unknown kernel name(s) in entries: {bad}")
        return 1
    return 0


def main(argv=None):
    parser = argparse.ArgumentParser(
        description="measure, inspect and gate the kernel tune cache")
    sub = parser.add_subparsers(dest="cmd", required=True)

    p = sub.add_parser("tune", help="micro-benchmark one geometry and "
                                    "persist the winner")
    p.add_argument("--nchan", type=int, required=True)
    p.add_argument("--nsamples", type=int, required=True)
    p.add_argument("--ndm", type=int, default=256,
                   help="trial count (dmmax derived unless --dmmax)")
    p.add_argument("--dmmin", type=float, default=300.0)
    p.add_argument("--dmmax", type=float, default=None)
    p.add_argument("--start-freq", type=float, default=GEOM[0])
    p.add_argument("--bandwidth", type=float, default=GEOM[1])
    p.add_argument("--tsamp", type=float, default=GEOM[2])
    p.add_argument("--reps", type=int, default=3,
                   help="timed reps per candidate (median)")
    p.add_argument("--probe-trials", type=int, default=32)
    p.add_argument("--force", action="store_true",
                   help="re-measure even if the key is already tuned")
    p.add_argument("--cache", default=None,
                   help="cache file (default: the user cache, "
                        "$PUTPU_TUNE_CACHE)")
    p.set_defaults(fn=cmd_tune)

    p = sub.add_parser("show", help="print the per-key decision table")
    p.add_argument("--cache", default=None)
    p.set_defaults(fn=cmd_show)

    p = sub.add_parser("clear", help="drop tuned entries")
    p.add_argument("--cache", default=None)
    p.add_argument("--match", default=None,
                   help="only keys containing this substring")
    p.set_defaults(fn=cmd_clear)

    p = sub.add_parser("verify", help="schema/shape-check a cache "
                                      "artifact")
    p.add_argument("--cache", default=None,
                   help="artifact path (default: TUNE_cpu.json)")
    p.add_argument("--expect-version", type=int, default=None)
    p.set_defaults(fn=cmd_verify)

    opts = parser.parse_args(argv)
    return opts.fn(opts)


if __name__ == "__main__":
    sys.exit(main())
