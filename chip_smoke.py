"""The quickest proof that the file-survey path still starts on the chip.

Drives ``PUsearchfrb``'s own ``main()`` in THIS process, on one TPU, at
the width ``tools/survey_rehearsal.py`` defines: a seeded 2-bit SIGPROC
file of 1,024 channels x 2^21 samples (descending band 1,400 -> 1,200
MHz, 0.5 ms, an injected pulse, hot channels and a 60 Hz comb), searched
over DM 300-400 in three 50 %-overlapping device chunks of 1,024 x 2^20:

1. ``--kernel auto`` with the defaults a user gets;
2. ``--kernel hybrid --snr-threshold certifiable`` (the survey fast path);
3. ``--backend numpy`` — the plain reference — and both device kernels
   again, on ONE chunk of 1,024 x 2^18 that holds the pulse (the NumPy
   path at 2^20 samples does not fit a one-chip machine's host memory).

It fails unless, in both device runs, every chunk that holds the
injected pulse reports it at its (time, DM); no chunk was searched by
NumPy, cleaned on the host, retried, OOM-descended or quarantined (read
from the counters and the quarantine manifest the run already keeps);
and the reference chunk's best row (DM, time, rebin, peak) equals the
NumPy reference's.

    python chip_smoke.py              # one TPU; anything else exits 2
    python chip_smoke.py --rehearsal  # 64 ch x 2^14 chunks, any backend:
                                      # every phase runs, the exit stays
                                      # non-zero off a TPU
    python chip_smoke.py --chips 4    # builder-run: ONLY the 4-device
                                      # mesh hybrid against the one-chip
                                      # hybrid, per-chunk argbest equal

The last line of stdout is the verdict, one JSON object; everything
else is on earlier lines.  A phase that raises prints its traceback on
stderr, its name on stdout, and the exit is non-zero.  One process, no
children: whoever imports JAX holds the chip.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import logging
import os
import shutil
import sys
import tempfile
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))

EXIT_NO_TPU = 2

#: counters that must not move during a device run: a chunk that left
#: the device path, was retried, descended the OOM ladder or was
#: quarantined is a failed bring-up even though the run "succeeded"
CLEAN_RUN_COUNTERS = (
    "putpu_host_fallbacks_total", "putpu_dispatch_retries_total",
    "putpu_oom_events_total", "putpu_oom_ladder_steps_total",
    "putpu_oom_splits_total", "putpu_oom_floor_total",
    "putpu_oom_preflight_splits_total", "putpu_chunks_quarantined_total",
    "putpu_chunks_sanitized_total", "putpu_read_retries_total",
    "putpu_persist_dead_letter_total")


def say(msg):
    print(msg, flush=True)


class PhaseFailed(SystemExit):
    pass


@contextlib.contextmanager
def phase(name):
    """Run one phase; any exception -> traceback on stderr, the phase's
    name on stdout, exit 1.  Nothing is swallowed."""
    say(f"== phase {name}")
    t0 = time.perf_counter()
    try:
        yield
    except BaseException:
        traceback.print_exc(file=sys.stderr)
        say(f"FAILED phase: {name}")
        raise PhaseFailed(1)
    say(f"   phase {name}: ok in {time.perf_counter() - t0:.1f}s")


class _Capture(logging.Handler):
    """Keeps the one-line machine-readable records a run logs
    (``BUDGET_JSON``, ``done:``) — what the run already reports."""

    def __init__(self):
        super().__init__(level=logging.INFO)
        self.budget = None
        self.done = None

    def emit(self, record):
        msg = record.getMessage()
        if msg.startswith("BUDGET_JSON "):
            self.budget = json.loads(msg[len("BUDGET_JSON "):])
        elif msg.startswith("done: "):
            self.done = msg


def counter_totals():
    from pulsarutils_tpu.obs.metrics import REGISTRY

    return {name: REGISTRY.total(name) for name in CLEAN_RUN_COUNTERS}


def best_rows(outdir):
    """``{istart: (iend, best_row_dict)}`` from the candidate files a
    run persisted (the repo's own store reads them back)."""
    from pulsarutils_tpu.io.candidates import CandidateStore

    store = CandidateStore(outdir, None)
    out = {}
    for root, lo, hi in store.candidates():
        _, table = store.load_candidate(root, lo, hi)
        best = table.best_row()
        out[int(lo)] = (int(hi), {k: best[k].item()
                                  for k in ("DM", "snr", "rebin", "peak")})
    return out


def geometry(rehearsal):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import survey_rehearsal as sr

    if rehearsal:
        nchan, hop = 64, 1 << 13
    else:
        nchan, hop = sr.NCHAN, sr.HOP
    return sr, nchan, hop, 4 * hop


def expected_detections(pulses, nsamples, hop, max_delay):
    """``{chunk istart: pulse}`` for every chunk that holds a pulse's
    whole dispersion track (chunks are 2*hop long, hop apart)."""
    exp = {}
    for istart in range(0, nsamples - hop, hop):
        for pulse in pulses:
            pos = pulse[0]
            if istart + max_delay <= pos < istart + 2 * hop - max_delay:
                exp[istart] = pulse
    return exp


def check_recovery(name, rows, expected, tsamp):
    """Every expected (chunk, pulse) must be that chunk's best row at
    the pulse's (time, DM): within one pulse width + 8 samples, and
    within the 3 DM units ``tools/survey_rehearsal.py`` allows a 2-bit
    pulse on this grid."""
    ok = True
    for istart, (pos, dm, _amp, width) in sorted(expected.items()):
        got = rows.get(istart)
        if got is None:
            say(f"   {name}: chunk {istart}: pulse at sample {pos} DM "
                f"{dm:.2f} NOT reported (no candidate persisted)")
            ok = False
            continue
        row = got[1]
        t_got = istart + int(row["peak"])
        good = (abs(t_got - pos) <= width + 8
                and abs(float(row["DM"]) - dm) < 3.0)
        say(f"   {name}: chunk {istart}: injected t={pos * tsamp:.4f}s "
            f"DM={dm:.2f} w={width} -> best row t={t_got * tsamp:.4f}s "
            f"DM={float(row['DM']):.2f} rebin={int(row['rebin'])} "
            f"snr={float(row['snr']):.2f} "
            f"[{'recovered' if good else 'MISSED'}]")
        ok &= good
    return ok


def run_cli(name, path, outdir, extra, sr, chunk_len_s):
    """One in-process ``PUsearchfrb`` run; returns what it recorded."""
    import jax

    from pulsarutils_tpu.cli import search_main
    from pulsarutils_tpu.tuning import autotune
    from pulsarutils_tpu.utils.logging_utils import compile_snapshot

    os.makedirs(outdir)
    argv = [path, "--dmmin", str(sr.DMMIN), "--dmmax", str(sr.DMMAX),
            "--chunk-length", str(chunk_len_s), "--output-dir", outdir,
            "--plots", "none"] + list(extra)
    say(f"   PUsearchfrb {' '.join(argv[1:])}")
    cap = _Capture()
    logger = logging.getLogger("pulsarutils_tpu")
    logger.addHandler(cap)
    c0, n0 = compile_snapshot(), counter_totals()
    mark = autotune.decision_seq()
    t0 = time.perf_counter()
    try:
        rc = search_main.main(argv)
    finally:
        logger.removeHandler(cap)
    wall = time.perf_counter() - t0
    c1, n1 = compile_snapshot(), counter_totals()
    moved = {k: n1[k] - n0[k] for k in n1 if n1[k] != n0[k]}
    manifests = [f for f in os.listdir(outdir) if f.startswith("quarantine_")]
    budget = cap.budget or {}
    per_chunk = budget.get("per_chunk", [])
    stats = jax.devices()[0].memory_stats() or {}
    decisions = [f"{d['kernel']} ({d['source']})"
                 for d in autotune.decisions_since(mark)]
    kernel = extra[extra.index("--kernel") + 1] if "--kernel" in extra \
        else "auto"
    say(f"   {name}: exit {rc}, wall {wall:.1f}s, {cap.done}")
    say(f"   {name}: kernel={kernel}"
        + (f" resolved -> {', '.join(decisions)}" if decisions else
           " (as given, no tuner resolution)"))
    say(f"   {name}: chunk walls s = "
        f"{[round(c['wall_s'], 3) for c in per_chunk]} "
        "(first includes compiles)")
    say(f"   {name}: XLA backend compiles: {c1[0] - c0[0]} programs, "
        f"{c1[1] - c0[1]:.1f}s (persistent-cache hits compile nothing)")
    say(f"   {name}: per-chunk dispatches/readbacks = "
        f"{[(c['counters'].get('dispatches', 0), c['counters'].get('readbacks', 0)) for c in per_chunk]}")
    say(f"   {name}: budget buckets s = {budget.get('buckets_s')}")
    say(f"   {name}: peak device bytes (process so far) = "
        f"{stats.get('peak_bytes_in_use', 'not reported')} of "
        f"{stats.get('bytes_limit', 'n/a')}")
    say(f"   {name}: fall-back/retry/OOM/quarantine counters moved: "
        f"{moved or 'none'}; quarantine manifests: {manifests or 'none'}")
    return {"rc": rc, "moved": moved, "manifests": manifests,
            "chunks": len(per_chunk), "rows": best_rows(outdir)}


def same_row(a, b):
    """Exact-argbest equality: DM, time (peak sample), rebin, peak —
    the integer fields exactly, S/N to float32 reduction order."""
    return (float(a["DM"]) == float(b["DM"])
            and int(a["peak"]) == int(b["peak"])
            and int(a["rebin"]) == int(b["rebin"])
            and abs(float(a["snr"]) - float(b["snr"]))
            <= 1e-3 * abs(float(b["snr"])))


#: margin (samples) a pulse keeps from a chunk's edges to count as held
#: by it: above the 1,200-1,400 MHz band crossing at DM 400 (~612)
TRACK_MARGIN = 768

DEVICE_RUNS = (("auto", ["--kernel", "auto"]),
               ("hybrid", ["--kernel", "hybrid",
                           "--snr-threshold", "certifiable"]))


def require_clean(res):
    if res["rc"] != 0:
        raise RuntimeError(f"PUsearchfrb exit status {res['rc']}")
    if res["moved"] or res["manifests"]:
        raise RuntimeError(f"not a clean device run: {res['moved']} "
                           f"{res['manifests']}")


def one_chip(opts, sr, nchan, hop, nsamples, work, pulses, path):
    expected = expected_detections(pulses, nsamples, hop, TRACK_MARGIN)
    if not expected:
        raise RuntimeError("the seed placed no pulse inside a chunk")
    for name, extra in DEVICE_RUNS:
        with phase(f"search[{name}]"):
            res = run_cli(name, path, os.path.join(work, f"out_{name}"),
                          extra, sr, hop * sr.TSAMP)
            require_clean(res)
            if res["chunks"] != 3:
                raise RuntimeError(f"expected 3 chunks, ran {res['chunks']}")
            if not check_recovery(name, res["rows"], expected, sr.TSAMP):
                raise RuntimeError("an injected pulse was not recovered")

    # The plain reference, outside any timing.  At 2^20 samples the NumPy
    # path works on float64 copies of an 8 GiB chunk and does not fit the
    # one-chip machine's 40 GiB of host memory (first chip run of PR 22:
    # killed at the limit), so this ONE comparison keeps the width and
    # shortens the chunk to a quarter: the chunk of hop/4-spaced starts
    # that holds the pulse, searched by all three paths over the same
    # samples.
    ref_hop = hop // 4
    pos = pulses[0][0]
    k = pos // ref_hop
    istart = ((k - 1) * ref_hop
              if k and pos - k * ref_hop < ref_hop - TRACK_MARGIN
              else k * ref_hop)
    window = ["--tmin", repr((istart - 0.5) * sr.TSAMP), "--max-chunks", "1"]
    say(f"reference comparison on the {nchan} ch x {2 * ref_hop}-sample "
        f"chunk at sample {istart} (a quarter of the device chunk: the "
        "NumPy path at full chunk length needs more host memory than a "
        "one-chip machine has)")
    rows = {}
    for name, extra in (("numpy", ["--backend", "numpy"]),) + DEVICE_RUNS:
        with phase(f"reference[{name}]"):
            res = run_cli(f"ref-{name}", path,
                          os.path.join(work, f"ref_{name}"), extra + window,
                          sr, ref_hop * sr.TSAMP)
            require_clean(res)
            if istart not in res["rows"]:
                raise RuntimeError(f"no candidate in chunk {istart}")
            rows[name] = res["rows"][istart][1]
    with phase("compare[device vs numpy]"):
        ok = True
        for name, _ in DEVICE_RUNS:
            same = same_row(rows[name], rows["numpy"])
            say(f"   chunk {istart} best row {name}: {rows[name]} vs "
                f"numpy: {rows['numpy']} "
                f"[{'equal' if same else 'DIFFERENT'}]")
            ok &= same
        if not ok:
            raise RuntimeError("a device run's best row differs from the "
                               "NumPy reference's")
    return ok


def four_chips(opts, sr, nchan, hop, nsamples, work, pulses, path):
    """ONLY the 4-device mesh hybrid and the one-chip hybrid it is
    compared with: the exact-argbest contract of
    ``tests/test_sharded_fused.py`` on real devices."""
    import jax

    from pulsarutils_tpu.parallel.mesh import make_mesh
    from pulsarutils_tpu.pipeline.search_pipeline import search_by_chunks

    devices = jax.devices()
    if len(devices) < 4:
        raise SystemExit(f"--chips 4 needs four devices, JAX reports "
                         f"{len(devices)}")
    def peaks():
        return {d.id: (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                for d in devices[:4]}

    results, peak_after = {}, {}
    # threshold 0: every chunk comes back with its table, so each
    # chunk's argbest can be compared (the floorless hybrid: the fused
    # one-dispatch programs on both sides)
    for name, mesh in (("one-chip", None),
                       ("mesh-4", make_mesh((4, 1), ("dm", "chan"),
                                            devices=devices[:4]))):
        with phase(f"hybrid[{name}]"):
            t0 = time.perf_counter()
            n0 = counter_totals()
            hits, _ = search_by_chunks(
                path, chunk_length=hop * sr.TSAMP, dmmin=sr.DMMIN,
                dmmax=sr.DMMAX, backend="jax", kernel="hybrid",
                snr_threshold=0.0, mesh=mesh, make_plots=False,
                resume=False, output_dir=os.path.join(work, f"out_{name}"))
            n1 = counter_totals()
            moved = {k: n1[k] - n0[k] for k in n1 if n1[k] != n0[k]}
            say(f"   {name}: {len(hits)} chunks in "
                f"{time.perf_counter() - t0:.1f}s; fall-back/retry/OOM "
                f"counters moved: {moved or 'none'}")
            if moved or len(hits) != 3:
                raise RuntimeError(f"not a clean 3-chunk run: {moved}")
            peak_after[name] = peaks()
            results[name] = {
                h[0]: ({k: h[3].best_row()[k].item()
                        for k in ("DM", "snr", "rebin", "peak")},
                       bool(h[3].best_row()["exact"]))
                for h in hits}
    with phase("compare[mesh-4 vs one-chip]"):
        ok = True
        for istart, (row1, exact1) in sorted(results["one-chip"].items()):
            row4, exact4 = results["mesh-4"][istart]
            same = same_row(row4, row1) and exact1 == exact4
            say(f"   chunk {istart}: one-chip {row1} exact={exact1} | "
                f"mesh-4 {row4} exact={exact4} "
                f"[{'equal' if same else 'DIFFERENT'}]")
            ok &= same
        operand = nchan * 2 * hop * 4
        say(f"   peak bytes per device after the one-chip run: "
            f"{peak_after['one-chip']}; after the mesh run: "
            f"{peak_after['mesh-4']}; one float32 chunk = {operand}")
        if devices[0].platform != "tpu":
            say("   (no allocator statistics off the chip: residency "
                "not checked)")
        elif not all(v >= operand for v in peak_after["mesh-4"].values()):
            raise RuntimeError("the chunk was not resident on all four "
                               "devices")
        if not ok:
            raise RuntimeError("mesh candidates differ from the one-chip "
                               "hybrid's")
    return ok


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearsal", action="store_true",
                    help="64 ch x 2^14-sample chunks on whatever backend "
                         "JAX has; proves the control flow, never passes "
                         "off a TPU")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--workdir", default=None,
                    help="scratch directory (default: a fresh temp dir, "
                         "removed at exit)")
    opts = ap.parse_args(argv)

    # 1. the device, before anything else of the repo is imported
    import jax

    devices = jax.devices()
    dev = {"platform": devices[0].platform,
           "kind": devices[0].device_kind, "count": len(devices)}
    on_tpu = dev["platform"] == "tpu"
    say(f"device: {dev}")
    if not on_tpu:
        say("no TPU: JAX reports platform "
            f"{dev['platform']!r} — this smoke proves nothing off the chip")
        if not opts.rehearsal:
            return EXIT_NO_TPU
        say("rehearsal: running every phase at the tiny size anyway; the "
            "exit status stays non-zero")

    from pulsarutils_tpu.utils.compile_cache import enable_compile_cache

    say(f"compile cache: {enable_compile_cache()}")
    sr, nchan, hop, nsamples = geometry(opts.rehearsal)
    work = opts.workdir or tempfile.mkdtemp(prefix="chip_smoke_")
    os.makedirs(work, exist_ok=True)
    try:
        with phase("generate"):
            from pulsarutils_tpu.io import lowbit

            path = os.path.join(work, "smoke_2bit.fil")
            pulses, _, size = sr.generate(path, nsamples, say, nchan=nchan,
                                          hop=hop, seed=opts.seed)
            say(f"   {nchan} ch x {nsamples} samples, 2-bit, "
                f"{size / 2**20:.0f} MiB, seed {opts.seed}; pulses "
                f"(sample, DM, amp, width): {pulses}")
            say("   low-bit unpacker: "
                + ("native (built from native/unpack.cpp)"
                   if lowbit.native_available() else "NumPy decoder"))
        run = four_chips if opts.chips == 4 else one_chip
        ok = run(opts, sr, nchan, hop, nsamples, work, pulses, path)
    finally:
        if opts.workdir is None:
            shutil.rmtree(work, ignore_errors=True)
    if not on_tpu:
        say("rehearsal: every phase passed; no TPU, so no verdict")
        return EXIT_NO_TPU
    print(json.dumps({"ok": bool(ok), "device": dev}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
