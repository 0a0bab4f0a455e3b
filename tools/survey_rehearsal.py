"""End-to-end survey rehearsal from a multi-GB 2-bit SIGPROC file:
generate -> PUsearchfrb CLI -> verify -> artifact.

The one configuration the benchmarks bypass: the REAL on-disk file path
(native reader + low-bit unpack + threaded prefetch + device clean +
hybrid certificate) at survey scale, on hardware.  Reference bar:
``pulsarutils/clean.py:276-351`` run at scale.

Stages:
  1. generate a 2-bit descending-band filterbank with known injected
     pulses (exact integer dispersion tracks) + RFI (hot channels,
     broadband periodic interference);
  2. run the actual CLI (``python -m pulsarutils_tpu.cli.search_main``)
     twice: first capped at half the chunks (simulated interruption),
     then to completion — the second run must RESUME from the ledger
     (and must report the interrupted run's persisted candidates);
  3. verify every injected pulse is recovered (time + DM) from the
     resumed run's complete candidate report;
  4. optionally write a markdown report with per-stage wall-clock,
     chunks/s and the recovery table.

One process per chip: the CLI runs are children that each hold the
device for their lifetime, so THIS process never imports JAX.  The
geometry constants and the generator are shared with ``chip_smoke.py``
(the same path, three chunks, in one process).

Usage: python tools/survey_rehearsal.py [--gb 2.0] [--dir /tmp/survey]
       [--out report.md] [--keep]
"""

import argparse
import os
import re
import subprocess
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

NCHAN = 1024
TSAMP = 5e-4
FBOT, FTOP = 1200.0, 1400.0
DMMIN, DMMAX = 300.0, 400.0
HOP = 1 << 19
#: --chunk-length (seconds) -> step = 2**20 samples post-rounding (the
#: framework's device-resident chunk size; the CLI default would use the
#: reference's physics floor of ~2k samples and pay 8000 dispatches)
CHUNK_LEN_S = HOP * TSAMP
GEN_BLOCK = 1 << 17  # generation block (1024 x 131072 f32 = 512 MB)


def injected_pulses(nsamples, stride=2, nchan=NCHAN, hop=HOP, seed=7):
    """(sample, dm, amp_levels, width) — absolute positions, placed away
    from generation-block edges, in hops 1, 1+stride, 1+2*stride, ...

    NOTE on certification coverage: a 50%-overlap chunk spans TWO hops,
    so pulses in every odd hop of a long file leave NO pulse-free chunk
    — every chunk contains a pulse and the noise certificate never
    fires (correct behaviour).  Use ``stride=4`` (pulses in hops 1, 5,
    9, ...) when the artifact should also demonstrate certified
    signal-free chunks at scale.

    ``nchan``/``hop`` shrink the geometry for rehearsals of the control
    flow (``chip_smoke.py --rehearsal``): amplitudes scale by
    ``sqrt(NCHAN / nchan)`` so the matched-filter S/N stays where the
    survey width puts it."""
    margin = min(4096, hop // 4)
    picks = []
    rng = np.random.default_rng(seed)
    n_hops = nsamples // hop
    for hopi in range(1, n_hops - 1, stride):
        pos = hopi * hop + int(rng.integers(margin, hop - margin))
        dm = float(rng.uniform(DMMIN + 5, DMMAX - 5))
        width = int(rng.choice([1, 1, 2, 4]))
        # total amplitude scaled by sqrt(width) so every width lands at
        # exact S/N ~ 19-30, comfortably above the certifiable floor
        # (~13 at these chunks) but far from trivial at 2 bits
        amp = float(rng.uniform(0.45, 0.7)) * float(np.sqrt(width))
        picks.append((pos, dm, amp * float(np.sqrt(NCHAN / nchan)), width))
    return picks


def generate(path, nsamples, log, stride=2, nchan=NCHAN, hop=HOP, seed=7):
    """Write the rehearsal file; returns ``(pulses, seconds, bytes)``.
    ``seed`` draws the pulses; the noise stream is seeded from it too."""
    from pulsarutils_tpu.io.sigproc import FilterbankWriter
    from pulsarutils_tpu.ops.plan import dedispersion_shifts

    header = {"nchans": nchan, "nbits": 2, "nifs": 1, "tsamp": TSAMP,
              "fch1": FTOP, "foff": -(FTOP - FBOT) / nchan,
              "tstart": 60000.0, "source_name": "REHEARSAL"}
    pulses = injected_pulses(nsamples, stride=stride, nchan=nchan, hop=hop,
                             seed=seed)
    # exact integer track per pulse, ASCENDING-band channel order
    shifts = {dm: np.rint(np.asarray(dedispersion_shifts(
        nchan, dm, FBOT, FTOP - FBOT, TSAMP))).astype(np.int64)
        for _, dm, _, _ in pulses}

    rng = np.random.default_rng(seed + 35)
    t0 = time.time()
    with FilterbankWriter(path, header) as w:
        for lo in range(0, nsamples, GEN_BLOCK):
            n = min(GEN_BLOCK, nsamples - lo)
            # mean 1.6 levels, sd 0.65 -> quantized 0..3 keeps ~full
            # noise information at 2 bits
            block = rng.normal(1.6, 0.65, (nchan, n)).astype(np.float32)
            # RFI: two hot channels + one 60 Hz broadband comb
            block[(300 * nchan) // NCHAN] += 1.2
            block[(701 * nchan) // NCHAN] += 2.0
            tt = (lo + np.arange(n)) * TSAMP
            block += 0.25 * np.maximum(
                0, np.sign(np.sin(2 * np.pi * 60.0 * tt)))[None, :]
            for pos, dm, amp, width in pulses:
                sh = shifts[dm]
                # channel c (ascending) peaks at pos + sh[c]
                tc = pos + sh
                for k in range(width):
                    sel = (tc + k >= lo) & (tc + k < lo + n)
                    block[np.flatnonzero(sel),
                          tc[sel] + k - lo] += amp / width
            # file stores descending band: flip channel axis
            w.write_block(block[::-1])
            del block
    dt = time.time() - t0
    size = os.path.getsize(path)
    log(f"generated {size / 2**30:.2f} GiB ({nsamples} samples, "
        f"{len(pulses)} pulses) in {dt:.0f}s "
        f"({size / 2**20 / dt:.0f} MiB/s)")
    return pulses, dt, size


def run_cli(path, outdir, max_chunks=None, extra=()):
    cmd = [sys.executable, "-m", "pulsarutils_tpu.cli.search_main", path,
           "--dmmin", str(DMMIN), "--dmmax", str(DMMAX),
           "--kernel", "hybrid", "--snr-threshold", "certifiable",
           "--chunk-length", str(CHUNK_LEN_S),
           "--output-dir", outdir, "--plots", "none"]
    if max_chunks:
        cmd += ["--max-chunks", str(max_chunks)]
    cmd += list(extra)
    t0 = time.time()
    proc = subprocess.run(cmd, capture_output=True, text=True,
                          cwd=os.path.dirname(os.path.dirname(
                              os.path.abspath(__file__))))
    wall = time.time() - t0
    out = proc.stdout + proc.stderr
    if proc.returncode != 0:
        print(out[-4000:])
        raise SystemExit(f"CLI failed rc={proc.returncode}")
    return out, wall


def parse_report(out):
    stages = {}
    for m in re.finditer(r"stage (\S+)\s+([\d.]+)s total,\s+(\d+) calls,"
                         r"\s+([\d.]+)s/call", out):
        stages[m.group(1)] = (float(m.group(2)), int(m.group(3)),
                              float(m.group(4)))
    done = re.search(r"done: (\d+) chunks processed, (\d+) hits, "
                     r"(\d+) noise-certified", out)
    cands = [(float(m.group(1)), float(m.group(2)), float(m.group(3)))
             for m in re.finditer(
                 r"t=([\d.]+)s DM=([\d.]+) snr=([\d.]+)", out)]
    return stages, (tuple(int(g) for g in done.groups()) if done
                    else None), cands


def parse_budget(out):
    """The run's ``BUDGET_JSON`` line: the per-chunk wall-clock budget
    — buckets, counters, trips x RTT and the explicit ``unattributed``
    residual."""
    import json

    budget = None
    for m in re.finditer(r"BUDGET_JSON (\{.*\})", out):
        budget = json.loads(m.group(1))  # last one wins (run 2)
    return budget


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--gb", type=float, default=2.0)
    p.add_argument("--dir", default="/tmp/survey_rehearsal")
    p.add_argument("--out", default=None)
    p.add_argument("--keep", action="store_true")
    p.add_argument("--pulse-stride", type=int, default=2,
                   help="hop stride between injected pulses; 4 leaves "
                        "pulse-free chunks so the noise certificate "
                        "fires (see injected_pulses)")
    p.add_argument("--single-run", action="store_true",
                   help="skip the interrupt/resume split (supplementary "
                        "certification pass)")
    opts = p.parse_args(argv)

    os.makedirs(opts.dir, exist_ok=True)
    path = os.path.join(opts.dir, "rehearsal_2bit.fil")
    outdir = os.path.join(opts.dir, "out")
    os.makedirs(outdir, exist_ok=True)

    def log(msg):
        print(msg, flush=True)

    bytes_per_samp = NCHAN // 4
    hop = 1 << 19
    nsamples = int(opts.gb * 2**30 / bytes_per_samp) // hop * hop
    if not os.path.exists(path) or os.path.getsize(path) < nsamples // 4:
        pulses, gen_dt, size = generate(path, nsamples, log,
                                        stride=opts.pulse_stride)
    else:
        pulses, gen_dt, size = (injected_pulses(nsamples,
                                                stride=opts.pulse_stride),
                                0.0,
                                os.path.getsize(path))
        log("file already staged")

    n_chunks_est = nsamples // hop - 1
    half = max(2, n_chunks_est // 2)
    if opts.single_run:
        out1, wall1, done1 = "", 0.0, (0, 0, 0)
    else:
        log(f"run 1/2: interrupted at {half} chunks ...")
        out1, wall1 = run_cli(path, outdir, max_chunks=half)
        s1, done1, _ = parse_report(out1)
        log(f"  run1: {done1} wall={wall1:.0f}s")

    log("run 2/2: resume to completion ...")
    out2, wall2 = run_cli(path, outdir)
    stages, done2, cands = parse_report(out2)
    budget = parse_budget(out2)
    log(f"  run2: {done2} wall={wall2:.0f}s stages={stages}")
    if budget:
        log(f"  budget: {budget['attributed_pct']}% of {budget['wall_s']}s "
            f"chunk wall attributed ({budget.get('trips', 0)} device "
            f"trips x {budget.get('rtt_s', 0)}s RTT)")

    # recovery check: every injected pulse matched by a candidate at
    # (time within the 50%-overlap tolerance, DM within 2 trials)
    rows = []
    missed = 0
    for pos, dm, amp, width in pulses:
        t_pulse = pos * TSAMP
        best = None
        for (tc, dmc, snrc) in cands:
            if abs(tc - t_pulse) < 0.6 and abs(dmc - dm) < 3.0:
                if best is None or snrc > best[2]:
                    best = (tc, dmc, snrc)
        if best is None:
            missed += 1
            rows.append((t_pulse, dm, width, amp, None))
        else:
            rows.append((t_pulse, dm, width, amp, best))
    resumed = (opts.single_run
               or (done1 and done2
                   and done2[0] + done1[0] <= n_chunks_est + 2))

    log(f"recovered {len(pulses) - missed}/{len(pulses)} pulses; "
        f"resume={'OK' if resumed else 'SUSPECT'}")

    if opts.out:
        total = sum(v[0] for v in stages.values()) or 1.0
        lines = [
            "# Survey rehearsal — file -> hits on hardware",
            "",
            f"- file: {size / 2**30:.2f} GiB 2-bit SIGPROC, {NCHAN} chan x "
            f"{nsamples} samples ({nsamples * TSAMP:.0f} s of data), "
            f"descending band, 2 hot channels + 60 Hz broadband RFI, "
            f"{len(pulses)} injected pulses (generation: {gen_dt:.0f} s)",
            f"- CLI: `PUsearchfrb --dmmin 300 --dmmax 400 --kernel hybrid "
            f"--snr-threshold certifiable --chunk-length {CHUNK_LEN_S}`",
            f"- run 1 (interrupted at {half} chunks): {done1[0]} chunks, "
            f"{done1[2]} certified, wall {wall1:.0f} s",
            f"- run 2 (RESUMED from ledger): {done2[0]} further chunks, "
            f"{done2[1]} hits, {done2[2]} noise-certified, wall "
            f"{wall2:.0f} s -> "
            f"{done2[0] / wall2 * 60:.2f} chunks/min end-to-end "
            f"({done2[0] * (1 << 19) * TSAMP / wall2:.0f}x real time "
            "per chunk-hop)",
            "",
            "## Per-stage wall clock (run 2)",
            "",
            "| stage | total s | calls | s/call | share |",
            "|---|---|---|---|---|",
        ]
        for k, (tot, calls, per) in sorted(stages.items(),
                                           key=lambda kv: -kv[1][0]):
            lines.append(f"| {k} | {tot:.1f} | {calls} | {per:.3f} | "
                         f"{100 * tot / total:.0f}% |")
        if budget:
            wall_b = budget["wall_s"] or 1.0
            lines += [
                "",
                "## Per-chunk wall-clock budget (run 2)",
                "",
                f"**{budget['attributed_pct']}% of the "
                f"{budget['wall_s']:.1f} s summed chunk wall is "
                f"attributed** (unattributed residual "
                f"{budget['unattributed_s']:.2f} s); device trips: "
                f"{budget.get('trips', 0)} x "
                f"{budget.get('rtt_s', 0):.4f} s RTT = "
                f"{budget.get('trips_x_rtt_s', 0):.2f} s floor.",
                "",
                "| bucket | total s | share of wall |",
                "|---|---|---|",
            ]
            for k, v in budget["buckets_s"].items():
                lines.append(f"| {k} | {v:.2f} | "
                             f"{100 * v / wall_b:.1f}% |")
            lines.append(f"| unattributed | "
                         f"{budget['unattributed_s']:.2f} | "
                         f"{100 * budget['unattributed_s'] / wall_b:.1f}% |")
            lines += ["", f"counters: `{budget['counters']}`;  overlapped "
                          f"(off critical path): `{budget['async_s']}`"]
        lines += [
            "",
            "## Injected-pulse recovery",
            "",
            "| t (s) | DM | width | amp | recovered (t, DM, snr) |",
            "|---|---|---|---|---|",
        ]
        for t_pulse, dm, width, amp, best in rows:
            rec = (f"{best[0]:.2f}s, {best[1]:.1f}, {best[2]:.1f}"
                   if best else "**MISSED**")
            lines.append(f"| {t_pulse:.2f} | {dm:.1f} | {width} | "
                         f"{amp:.2f} | {rec} |")
        with open(opts.out, "w") as f:
            f.write("\n".join(lines) + "\n")
        log(f"report -> {opts.out}")

    if not opts.keep:
        os.unlink(path)
    return 1 if missed else 0


if __name__ == "__main__":
    sys.exit(main())
