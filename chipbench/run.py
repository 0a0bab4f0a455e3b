"""The benchmark's one command.

    python3 chipbench/run.py --workload <config>.<traffic> --seed N \
        --seconds S --trace 0|1

One process, no children: whoever imports JAX holds the chip.  A run

1. refuses anything but a TPU with the cell's chips (``--rehearsal`` runs
   every step on whatever backend there is, names it, and never exits 0);
2. writes the cell's SIGPROC file from the seed (``generate.py``);
3. cold pass: ``PUsearchfrb``'s own ``main()`` on the new file (no
   ``.badchans`` beside it, an empty output directory): set-up, and what
   ``correct`` is decided on; then everything set-up wrote is flushed;
4. timed window: the same ``main()`` on the same file again and again,
   each pass into a fresh output directory, no pass started after
   ``--seconds``; rates divide by the true length of the window;
5. with ``--trace 1`` the window is a short stretch inside
   ``jax.profiler`` and the program's span tracer instead, and the
   per-layer metrics are read from it;
6. after the window: the plain reference (``reference.py``, or the module
   of ``chipbench/`` the configuration names under ``"reference"``) over
   every chunk that holds a pulse, and the comparison that decides
   ``correct``;
7. prints every number compared beside its limit as the last lines of
   standard error, and one JSON object as the last line of standard
   output, the same numbers under its last key, ``compared``.

Everything of one configuration, traffic mix or per-layer metric lives in
a file of its own under ``configs/``, ``traffic/``, ``layer_metrics/``
(+ one reader per source kind under ``readers/``), found by name.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import generate, trace_reduce  # noqa: E402

#: no TPU with the cell's chips, no program in the checkout, or a rehearsal:
#: nothing was measured
EXIT_NOT_MEASURED = 2
TRACE_STRETCH_S = 6.0

#: counters of the program's registry that must not move in a run: a chunk
#: that left the device path, was retried, descended the OOM ladder, was
#: sanitized or quarantined makes the run a measurement of something else
#: (copied from chip_smoke.py, PR 22)
CLEAN_RUN_COUNTERS = (
    "putpu_host_fallbacks_total", "putpu_dispatch_retries_total",
    "putpu_oom_events_total", "putpu_oom_ladder_steps_total",
    "putpu_oom_splits_total", "putpu_oom_floor_total",
    "putpu_oom_preflight_splits_total", "putpu_chunks_quarantined_total",
    "putpu_chunks_sanitized_total", "putpu_read_retries_total",
    "putpu_persist_dead_letter_total")

def say(msg):
    print(msg, flush=True)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def resolve_cell(workload, rehearsal):
    """``<config>.<traffic>`` -> (config, traffic, chips).  A cell is an
    entry of BENCHMARK.json; the rehearsal may name any pair of files."""
    manifest = load_json(ROOT, "BENCHMARK.json")
    entry = next((w for w in manifest["workloads"]
                  if w["name"] == workload), None)
    if entry is None:
        if not rehearsal:
            raise SystemExit(f"{workload!r} is no cell of BENCHMARK.json")
        config, _, traffic = workload.partition(".")
        entry = {"config": config, "traffic": traffic, "chips": 1}
    cfg = load_json(HERE, "configs", entry["config"] + ".json")
    traffic = load_json(HERE, "traffic", entry["traffic"] + ".json")
    return manifest, entry, cfg, traffic


class _Capture(logging.Handler):
    """Keeps the ``BUDGET_JSON`` record a run logs."""

    def __init__(self):
        super().__init__(level=logging.INFO)
        self.budget = None
        self.notes = []

    def emit(self, record):
        msg = record.getMessage()
        if msg.startswith("BUDGET_JSON "):
            self.budget = json.loads(msg[len("BUDGET_JSON "):])
        elif msg.startswith(("snr_threshold resolved", "done: ")):
            self.notes.append(msg)


class CacheWatch:
    """Counts JAX's persistent-cache requests and hits; a request that is
    no hit compiled something."""

    def __init__(self):
        import jax

        self.requests = 0
        self.hits = 0
        jax.monitoring.register_event_listener(self._on)

    def _on(self, event, **kw):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def misses(self):
        return self.requests - self.hits


def registry_totals():
    """Every counter and gauge of the program's registry, summed over its
    label sets."""
    from pulsarutils_tpu.obs.metrics import REGISTRY

    totals = {}
    for sample in REGISTRY.snapshot():
        if "value" in sample:
            totals[sample["name"]] = (totals.get(sample["name"], 0)
                                      + sample["value"])
    return totals


def run_pass(path, outdir, cfg, spans=False):
    """One in-process ``PUsearchfrb`` run, as a user types it."""
    from pulsarutils_tpu.cli import search_main
    from pulsarutils_tpu.obs import trace as ptrace

    hop_s = cfg["chunk_samples"] // 2 * cfg["tsamp_s"]
    argv = [path, "--dmmin", str(cfg["dmmin"]), "--dmmax", str(cfg["dmmax"]),
            "--chunk-length", repr(hop_s), "--output-dir", outdir,
            "--plots", "none"] + list(cfg["cli_flags"])
    os.makedirs(outdir)
    cap = _Capture()
    logger = logging.getLogger("pulsarutils_tpu")
    logger.addHandler(cap)
    tracer = ptrace.start_tracing() if spans else None
    n0 = registry_totals()
    t0 = time.perf_counter()
    try:
        rc = search_main.main(argv)
    finally:
        t1 = time.perf_counter()
        logger.removeHandler(cap)
        if tracer is not None:
            ptrace.stop_tracing()
    n1 = registry_totals()
    span_list = []
    if tracer is not None:
        events, _ = tracer.events_since(0)
        span_list = [(tracer.epoch + ev["ts"] / 1e6,
                      tracer.epoch + (ev["ts"] + ev["dur"]) / 1e6, ev["name"])
                     for ev in events if ev.get("ph") == "X"]
    return {"rc": rc, "t0": t0, "t1": t1, "wall_s": t1 - t0,
            "budget": cap.budget, "outdir": outdir, "argv": argv,
            "notes": cap.notes,
            "registry_delta": {k: n1[k] - n0.get(k, 0) for k in n1},
            "spans": span_list}


def persisted(outdir):
    """What a pass left on disk, read back through the program's own
    store: ``{istart: (iend, best row, digest of the whole table)}``, the
    tables themselves, the chunks its ledger marks done, and any
    quarantine manifest."""
    import hashlib

    from pulsarutils_tpu.io.candidates import CandidateStore

    store = CandidateStore(outdir, None)
    rows, tables = {}, {}
    for root, lo, hi in store.candidates():
        _, table = store.load_candidate(root, lo, hi)
        best = table.best_row()
        digest = hashlib.sha256()
        for name in sorted(table.colnames):
            digest.update(name.encode())
            digest.update(table[name].tobytes())
        rows[int(lo)] = (int(hi), {
            "DM": float(best["DM"]), "snr": float(best["snr"]),
            "rebin": int(best["rebin"]), "peak": int(best["peak"]),
            "exact": bool(best["exact"]) if "exact" in best else None},
            digest.hexdigest())
        tables[int(lo)] = table
    done = set()
    for ledger in glob.glob(os.path.join(outdir, "progress_*.json")):
        done |= {int(s) for s in load_json(ledger).get("done", [])}
    manifests = glob.glob(os.path.join(outdir, "quarantine_*"))
    return rows, tables, done, manifests


def rms_gap(table, ref_rows):
    """Root mean square, over the reference's rows, of the relative gap
    between the S/N the program persisted at a trial and the reference's.
    A row counts where the program flags it exact and reports the
    reference's boxcar and peak sample.  One row's gap has a random sign
    and can be near zero by chance; the rms over the rows cannot."""
    import numpy as np

    gaps = []
    for r in ref_rows:
        i = r["row"]
        if (i < len(table["DM"]) and float(table["DM"][i]) == r["DM"]
                and ("exact" not in table or bool(table["exact"][i]))
                and int(table["rebin"][i]) == r["rebin"]
                and int(table["peak"][i]) == r["peak"]):
            gaps.append((float(table["snr"][i]) - r["snr"]) / r["snr"])
    rms = float(np.sqrt(np.mean(np.square(gaps)))) if gaps else float("inf")
    return rms, len(gaps)


def rows_as_table(rows):
    """The reference's (or its control's) rows as the columns of a
    persisted table, indexed by trial, for :func:`rms_gap`."""
    import numpy as np

    n = 1 + max(r["row"] for r in rows)
    table = {"DM": np.full(n, np.nan), "snr": np.zeros(n),
             "rebin": np.zeros(n, dtype=int), "peak": np.zeros(n, dtype=int)}
    for r in rows:
        for k, col in table.items():
            col[r["row"]] = r[k]
    return table


def percentile(values, q):
    """Nearest-rank percentile."""
    v = sorted(values)
    return v[max(math.ceil(q * len(v)) - 1, 0)]


def read_layer_metrics(manifest, cell, ctx):
    """Every per-layer metric the manifest lists for this cell, through the
    reader its file names."""
    out = {}
    for m in manifest["per_layer"]:
        if "workloads" in m and cell not in m["workloads"]:
            continue
        spec = load_json(HERE, "layer_metrics", m["name"] + ".json")
        reader = importlib.import_module(
            "chipbench.readers." + spec["source"]["kind"])
        value = reader.read(spec["source"], ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": spec["unit"]}
    return out


def compared_line(word, name, value, limit, ok):
    return (f"{word} {name}: value={value!r} limit={limit!r} "
            f"[{'ok' if ok else 'FAILED'}]")


def compare(name, value, limit, results, exact=True):
    ok = (value == limit) if exact else (value <= limit)
    results.append((name, value, limit, ok))
    say(compared_line("compare", name, value, limit, ok))
    return ok


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="run every step on whatever backend JAX has; "
                         "names it in the result and never exits 0")
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="also compare the control, which has to fail: the "
                         "reference with the cleaned chunk stored in "
                         "bfloat16, in the program's place")
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="copy the profiler's trace directory here")
    opts = ap.parse_args(argv)

    manifest, entry, cfg, traffic = resolve_cell(opts.workload,
                                                 opts.rehearsal)
    # a checkout that holds only the benchmark has no system to measure
    try:
        import pulsarutils_tpu  # noqa: F401
    except ImportError:
        say("the program (pulsarutils_tpu) is not in this checkout")
        return EXIT_NOT_MEASURED

    import jax

    devices = jax.devices()
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    say(f"device: {dev}")
    if dev["platform"] != "tpu" or dev["count"] < entry["chips"]:
        say(f"cell needs {entry['chips']} TPU chip(s); JAX reports "
            f"{dev['count']} x {dev['platform']!r}")
        if not opts.rehearsal:
            return EXIT_NOT_MEASURED
        say("rehearsal: every step runs anyway, nothing printed is a "
            "device number, the exit status stays non-zero")
    peaks = load_json(HERE, "peaks.json").get(dev["kind"])
    if peaks is None and not opts.rehearsal:
        raise SystemExit(f"no peaks for device kind {dev['kind']!r} in "
                         "chipbench/peaks.json")

    from pulsarutils_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    # every program goes to the cache, however quick its compile: the
    # window must find all of them there
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    cache_entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) \
        else 0
    say(f"compile cache: {cache_dir} ({cache_entries} entries at start: "
        f"{'cold' if not cache_entries else 'warm'})")
    watch = CacheWatch()

    work = tempfile.mkdtemp(prefix="chipbench_")
    try:
        return _run(opts, manifest, entry, cfg, traffic, dev, peaks, watch,
                    work)
    finally:
        shutil.rmtree(work, ignore_errors=True)


def _run(opts, manifest, entry, cfg, traffic, dev, peaks, watch, work):
    import jax

    results, control_results = [], []
    path = os.path.join(work, "obs.fil")
    info = generate.generate(path, cfg, traffic, opts.seed)
    say(f"generated {info['bytes'] / 2**20:.0f} MiB, {info['nsamples']} "
        f"samples ({info['duration_s']:.3f} s of sky) in "
        f"{info['seconds']:.2f} s; seed {opts.seed}, hit seed "
        f"{info['hit_seed']}; pulses "
        f"{json.dumps(info['pulses'])}")
    hop = info["hop"]
    chunk_starts = list(range(0, info["nsamples"] - hop, hop))

    # -- cold pass ---------------------------------------------------------
    traced = bool(opts.trace)
    cold = run_pass(path, os.path.join(work, "cold"), cfg, spans=traced)
    say(f"cold pass: exit {cold['rc']}, wall {cold['wall_s']:.3f} s, chunk "
        f"walls {[c['wall_s'] for c in (cold['budget'] or {}).get('per_chunk', [])]}"
        f", cache requests/hits so far {watch.requests}/{watch.hits}")
    say(f"cold pass: PUsearchfrb {' '.join(cold['argv'][1:])}; "
        f"{'; '.join(cold['notes'])}")
    compare("cold_exit_status", cold["rc"], 0, results)

    # -- the window --------------------------------------------------------
    # what set-up wrote (the file, the cold pass's products, compiled
    # programs) goes to disk now, inside setup_s: every pass that stood
    # still for 3-4 s did so some 30 s after set-up's last large write,
    # the kernel's age limit for dirty pages (PERF.md section 7)
    t_sync = time.perf_counter()
    os.sync()
    say(f"set-up's writes flushed in {time.perf_counter() - t_sync:.3f} s")
    passes = []
    miss0 = watch.misses()
    trace_dir = os.path.join(work, "trace")
    sync_t = None
    if traced:
        # device operations and the harness's marker; no Python call stacks
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        sync_t = time.perf_counter()
        with jax.profiler.TraceAnnotation(trace_reduce.SYNC_NAME):
            time.sleep(0.001)
    budget_s = min(opts.seconds, TRACE_STRETCH_S) if traced else opts.seconds
    t0 = time.perf_counter()
    setup_s = t0 - T_PROCESS
    try:
        while True:
            p = run_pass(path, os.path.join(work, f"pass{len(passes):04d}"),
                         cfg, spans=traced)
            passes.append(p)
            if p["rc"] != 0 or time.perf_counter() - t0 >= budget_s:
                break
    finally:
        t_end = time.perf_counter()
        if traced:
            jax.profiler.stop_trace()
    window_s = t_end - t0
    window_misses = watch.misses() - miss0
    chunk_walls = [c["wall_s"] for p in passes
                   for c in (p["budget"] or {}).get("per_chunk", [])]
    say(f"window: {len(passes)} passes in {window_s:.4f} s "
        f"(asked {budget_s:g}); pass walls s = "
        f"{[round(p['wall_s'], 4) for p in passes]}")
    say(f"window: {len(chunk_walls)} chunk walls, first pass' = "
        f"{chunk_walls[:len(chunk_starts)]}")
    for p in [cold] + passes[:1]:
        say(f"budget {os.path.basename(p['outdir'])}: "
            f"{json.dumps(p['budget'])}")
    stats = [d.memory_stats() or {} for d in jax.devices()[:entry["chips"]]]
    peak_bytes = max((s.get("peak_bytes_in_use", 0) for s in stats),
                     default=0)
    say(f"peak device bytes {peak_bytes} of "
        f"{stats[0].get('bytes_limit', 'n/a')}")

    # -- what the window produced, against the cold pass -----------------
    cold_rows, cold_tables, cold_done, cold_manifests = persisted(
        cold["outdir"])
    attempted = failed = 0
    missing_marks = len(set(chunk_starts) - cold_done)
    manifests = list(cold_manifests)
    for p in passes:
        rows, _, done, man = persisted(p["outdir"])
        manifests += man
        missing_marks += len(set(chunk_starts) - done)
        for s in chunk_starts:
            attempted += 1
            if rows.get(s) != cold_rows.get(s) or s not in done:
                failed += 1
                say(f"   pass {p['outdir'][-8:]} chunk {s}: "
                    f"{rows.get(s)} != cold {cold_rows.get(s)}")
        if p["rc"] != 0:
            failed = max(failed, 1)
    compare("window_chunks_differing_from_cold", failed, 0, results)
    compare("ledger_marks_missing", missing_marks, 0, results)
    compare("quarantine_manifests", len(manifests), 0, results)
    compare("cache_misses_in_window", window_misses, 0, results)
    moved = {k: sum(p["registry_delta"].get(k, 0) for p in [cold] + passes)
             for k in CLEAN_RUN_COUNTERS}
    compare("fallback_retry_oom_quarantine_counters_moved",
            sum(moved.values()), 0, results)

    # -- the plain reference, after the window, outside setup_s ------------
    limits = cfg["limits"]
    # a configuration may bring its own plain reference, a module of
    # chipbench/ with reference.py's best_row(); absent means reference.py
    ref_name = cfg.get("reference", "reference")
    reference = importlib.import_module("chipbench." + ref_name)
    # a pulse sits whole inside one hop, so the chunks that start at that
    # hop and at the one before hold it
    holding = [(pulse, s) for pulse in info["pulses"] for s in chunk_starts
               if s <= pulse["sample"] // hop * hop <= s + hop]
    for pulse, istart in holding:
        ref = reference.best_row(path, cfg, istart, pulse["dm"],
                                 control=bool(opts.control))
        say(f"reference chipbench.{ref_name} ({ref['seconds']:.1f} s, "
            f"{ref['ntrials']} trials, "
            f"rows {[r['row'] for r in ref['rows']]}): "
            f"{json.dumps({k: ref[k] for k in ('DM', 'row', 'snr', 'rebin', 'peak')})}")
        side = path + ".badchans"
        prog_bad = []
        if os.path.exists(side):
            with open(side) as f:
                prog_bad = [i for i, v in enumerate(f.read().split())
                            if int(float(v))]
        compare("badchans_differing",
                len(set(prog_bad) ^ set(ref["bad_channels_file_order"])), 0,
                results)
        got = cold_rows.get(istart)
        say(f"program, chunk {istart}: {got}")
        if got is None or got[0] != istart + cfg["chunk_samples"]:
            compare("pulse_chunk_candidate_missing", 1, 0, results)
            continue
        row = got[1]
        compare("trial_dm_rel_gap", abs(row["DM"] - ref["DM"]) / max(
            abs(ref["DM"]), 1e-12), limits["trial_dm_rel_gap"], results,
            exact=False)
        compare("peak_sample_gap", abs(row["peak"] - ref["peak"]), 0, results)
        compare("rebin_gap", abs(row["rebin"] - ref["rebin"]), 0, results)
        compare("best_row_not_exact", int(row["exact"] is False), 0, results)
        rms, nrows = rms_gap(cold_tables[istart], ref["rows"])
        say(f"best row's own snr_rel_gap "
            f"{abs(row['snr'] - ref['snr']) / ref['snr']!r} (one number of "
            f"random sign: no limit; the rms over the rows has one)")
        compare("reference_rows_short_of_3", max(3 - nrows, 0), 0, results)
        compare("snr_rel_gap_rms", rms, limits["snr_rel_gap_rms"], results,
                exact=False)
        if opts.control:
            # the control in the program's place: its rows through the
            # program's own comparison, which has to fail it
            ctl = ref["control"]
            ctl_rms, _ = rms_gap(rows_as_table(ctl["rows"]), ref["rows"])
            say(f"control (cleaned chunk in bfloat16): best "
                f"{json.dumps({k: ctl[k] for k in ('DM', 'snr', 'rebin', 'peak')})} "
                f"snr_rel_gap_rms={ctl_rms!r} "
                f"limit={limits['snr_rel_gap_rms']!r}")
            compare("snr_rel_gap_rms.control", ctl_rms,
                    limits["snr_rel_gap_rms"], control_results, exact=False)
    correct = all(ok for *_, ok in results)
    results += control_results
    compared, seen = {}, {}
    for name, value, limit, ok in results:
        # a comparison made once per pulse chunk: name, name.2, name.3
        seen[name] = seen.get(name, 0) + 1
        # json has no inf: a gap over no rows is printed as a string
        compared[name if seen[name] == 1 else f"{name}.{seen[name]}"] = {
            "value": value if math.isfinite(value) else repr(value),
            "limit": limit, "ok": ok}

    # -- metrics -----------------------------------------------------------
    device = dict(dev, memory_peak_bytes=int(peak_bytes))
    line = {"correct": correct, "attempted": attempted, "failed": failed,
            "device": device}
    done_passes = sum(1 for p in passes if p["rc"] == 0)
    if not traced:
        line["metrics"] = {
            "sky_s_per_s": {"value": done_passes * info["duration_s"]
                            / window_s, "unit": "s/s"},
            "chunk_wall_p90_ms": {"value": percentile(chunk_walls, 0.9) * 1e3
                                  if chunk_walls else float("nan"),
                                  "unit": "ms"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
        say(f"chunk_wall_p90_ms is over {len(chunk_walls)} chunk walls")
    else:
        host_spans = [s for p in passes for s in p["spans"]]
        reduced = None
        try:
            xplane = trace_reduce.find_xplane(trace_dir)
            reduced = trace_reduce.reduce_trace(
                trace_reduce.load(xplane), (t0, t_end), sync_t, host_spans)
            say(f"trace: {os.path.getsize(xplane)} bytes, planes "
                f"{reduced['planes']}, clock tied: {reduced['clock_tied']}")
        except FileNotFoundError as exc:
            say(f"trace: {exc}")
        if opts.keep_trace and os.path.isdir(trace_dir):
            shutil.copytree(trace_dir, opts.keep_trace, dirs_exist_ok=True)
        fbottom, bandwidth = generate.dispersion.band_edges(
            cfg["fch1_mhz"], cfg["foff_mhz"], cfg["nchans"])
        ctx = {"cold": cold, "passes": passes, "trace": reduced,
               "cfg": cfg, "traffic": traffic, "peaks": peaks, "notes": [],
               "shapes": {"nchan": cfg["nchans"],
                          "nsamples": cfg["chunk_samples"],
                          "dmmin": cfg["dmmin"], "dmmax": cfg["dmmax"],
                          "fbottom": fbottom, "bandwidth": bandwidth,
                          "tsamp": cfg["tsamp_s"]}}
        line["metrics"] = read_layer_metrics(manifest, opts.workload, ctx)
        for note in ctx["notes"]:
            say("note: " + note)
        device["busy_s"] = reduced["busy_s"] if reduced else 0.0
        device["window_s"] = window_s
        if reduced:
            line["breakdown"] = {"device_ops": reduced["device_ops"],
                                 "idle_gaps": reduced["idle_gaps"]}
            say(f"longest idle gaps s: {reduced['longest_gaps_s']}")
            ops = sorted(reduced["op_seconds"].items(), key=lambda kv: -kv[1])
            say(f"device operations by name ({len(ops)} names, s): "
                f"{json.dumps([[k, round(v, 5)] for k, v in ops[:60]])}")
    if opts.control:
        # a control that gave no reading has failed too
        line["control_correct"] = bool(control_results) and all(
            ok for *_, ok in control_results)
    line["compared"] = compared
    for name, c in compared.items():
        print(compared_line("compared", name, c["value"], c["limit"],
                            c["ok"]), file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    if dev["platform"] != "tpu":
        return EXIT_NOT_MEASURED
    return 0 if correct else 1


if __name__ == "__main__":
    sys.exit(main())
