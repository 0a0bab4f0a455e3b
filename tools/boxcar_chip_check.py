"""Do the long boxcar ladders hold against float64 at the chip's sizes?

The benchmark's cell for ``--boxcar-max 4096`` holds its pulse in the 16x
tier, so ``correct`` there compares a ladder of nine windows on 2^15
samples.  The ladders the flag is named for, 13 to 10 windows on 2^19 to
2^16 samples in tiers 0-3, run in every chunk but are held against nothing
on the device (``PERF.md`` section 7, PR 32).  This is that check, by hand:

    python tools/boxcar_chip_check.py [--boxcar-max 4096] [--seed N]
                                      [--skip-hybrid] [--rehearsal]

1. **The coarse sweep's scorer and its certificate captures, every tier.**
   For each tier of the HTRU plan (2^(19-k) samples, its own ladder) a
   plane of 64 rows of noise, each row holding one pulse: the ladder's own
   widths on a block of theirs (so every level wins a row), then widths
   between and beyond them at random phase.  ``score_plane_pallas`` (kernel
   ``score_rows``, as the sweep calls it, certificate row included) against
   ``chipbench/reference_boxcar.py:score_row`` in float64: window and peak
   equal in every row, S/N to ``--limit``; the certificate row against
   ``cert_profile_scores`` in float64 NumPy, and never under the block
   score it bounds.
2. **Tier 0 end to end through the hybrid.**  1,024 channels x 2^19
   samples of noise with one dispersed pulse a quarter of ``boxcar_max``
   wide (the widest level that clears the certifiable floor at any phase:
   a level of B blocks saturates at sqrt(B)) at DM 30, through the very call a tiered chunk makes for tier 0
   (``dedispersion_search(kernel="hybrid", trial_dms=, windows=,
   snr_floor=certifiable)``: the FDMT, the 13-level scorer, the
   certificate, the guarantee loop, the exact rescore with its rotation
   undone on the device).  A pulse that wide is flat in DM over most of
   the tier, so most rows are rescored; 17 of the exact ones, spread over the tier, are
   held against a float64 roll-and-sum with the reference's own shifts and
   ``score_row``: window, peak, S/N to ``--limit``.

One process (whoever imports JAX holds the chip).  ``--rehearsal`` runs
both parts at 64 channels x 2^15 samples with the kernel interpreted, for
the CPU.  Prints one JSON line per part and ``{"ok": ...}`` last; exit
status 0 when every comparison held.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

HTRU = dict(nchan=1024, fbottom=1182.0, bandwidth=400.0, tsamp=64e-6,
            foff=0.390625, dmmin=0.0, dmmax=1000.0, log2_t=19)
TINY = dict(nchan=64, fbottom=1182.0, bandwidth=400.0, tsamp=64e-6 * 16,
            foff=6.25, dmmin=0.0, dmmax=1000.0, log2_t=15)
ROWS = 64


def pulse_widths(ladder, rows, rng):
    """``[(width, aligned)]``: each window on a block of its own first,
    then widths between the windows and up to twice the widest, anywhere."""
    out = [(w, True) for w in ladder]
    while len(out) < rows:
        w = int(ladder[rng.integers(1, len(ladder))])
        out.append((int(rng.integers(w // 2 + 1, 2 * w + 1)), False))
    return out[:rows]


def scorer_part(geom, boxcar_max, seed, interpret, limit):
    """Part 1 of the module docstring; one result dict per tier."""
    import jax
    import jax.numpy as jnp

    from chipbench import reference_boxcar
    from pulsarutils_tpu.ops.plan import dm_tier_plan
    from pulsarutils_tpu.ops.score_pallas import score_plane_pallas
    from pulsarutils_tpu.ops.search import (cert_profile_scores,
                                            cert_wide_windows,
                                            scored_windows, unstack_scores)

    tiers = dm_tier_plan(geom["nchan"], geom["dmmin"], geom["dmmax"],
                         geom["fbottom"], geom["bandwidth"], geom["tsamp"],
                         geom["foff"], boxcar_max)
    results = []
    for tier in tiers:
        t = (1 << geom["log2_t"]) // tier.downsample
        ladder = scored_windows(tier.windows, t)
        rng = np.random.default_rng([seed, tier.downsample])
        plane = rng.standard_normal((ROWS, t)).astype(np.float32)
        widths = pulse_widths(ladder, ROWS, rng)
        for r, (w, aligned) in enumerate(widths):
            w = min(w, t // 4)
            at = int(rng.integers(0, t // w - 1)) * w if aligned \
                else int(rng.integers(0, t - w))
            plane[r, at:at + w] += np.float32(15.0 / np.sqrt(w))
        score = jax.jit(lambda p, lad=tier.windows: score_plane_pallas(
            p, with_cert=True, interpret=interpret, windows=lad))
        t0 = time.perf_counter()
        got = np.asarray(jax.block_until_ready(score(jnp.asarray(plane))))
        first_s = time.perf_counter() - t0
        _, _, snr, win, peak, cert = unstack_scores(got)
        plane64 = plane.astype(np.float64)
        want = [reference_boxcar.score_row(plane64[r], list(ladder))
                for r in range(ROWS)]
        want_snr = np.array([w[0] for w in want])
        window_gap = int(sum(int(win[r]) != want[r][1] for r in range(ROWS)))
        peak_gap = int(sum(int(peak[r]) != want[r][2] for r in range(ROWS)))
        snr_gap = float(np.max(np.abs(snr / want_snr - 1.0)))
        cert64 = cert_profile_scores(plane64, xp=np, windows=tier.windows)
        cert_gap = float(np.max(np.abs(cert / cert64 - 1.0)))
        # where the best block has a capture of its own width at half its
        # stride, the certificate holds it whole
        wide = np.isin([w[1] for w in want], cert_wide_windows(tier.windows,
                                                               t))
        cert_under = float(np.max((want_snr - cert64)[wide], initial=0.0))
        won = sorted({w[1] for w in want})
        ok = (window_gap == 0 and peak_gap == 0 and snr_gap <= limit
              and cert_gap <= limit and cert_under <= 1e-9
              and won == list(ladder))
        results.append({
            "part": "scorer", "downsample": tier.downsample, "samples": t,
            "windows": len(ladder), "rows": ROWS, "window_gap": window_gap,
            "peak_gap": peak_gap, "snr_rel_gap_max": snr_gap,
            "cert_rel_gap_max": cert_gap, "cert_under_block_max": cert_under,
            "windows_won": won, "first_call_s": round(first_s, 2), "ok": ok})
    return results


def hybrid_part(geom, boxcar_max, seed, limit, check_rows=17):
    """Part 2 of the module docstring."""
    from chipbench import dispersion, reference_boxcar
    from pulsarutils_tpu.ops.certify import (certifiable_snr_floor,
                                             retention_bound)
    from pulsarutils_tpu.ops.plan import dm_tier_plan
    from pulsarutils_tpu.ops.search import (dedispersion_search,
                                            scored_windows)

    nchan, t = geom["nchan"], 1 << geom["log2_t"]
    band = (geom["fbottom"], geom["bandwidth"], geom["tsamp"])
    tier = dm_tier_plan(nchan, geom["dmmin"], geom["dmmax"], *band,
                        geom["foff"], boxcar_max)[0]
    ladder = scored_windows(tier.windows, t)
    # a level of B blocks holds its own pulse in its std and saturates at
    # sqrt(B): the widest (128 blocks) stays under the certifiable floor
    # whatever the pulse, so the pulse is a quarter of it (512 blocks)
    width = int(ladder[-1]) // 4
    dms = np.asarray(tier.trial_dms)
    dm = float(dms[int(0.58 * len(dms))])  # DM 30 of 0-52.1
    rng = np.random.default_rng([seed, 7])
    data = rng.standard_normal((nchan, t), dtype=np.float32)
    shifts = dispersion.channel_shifts(np.float64(dm), nchan, *band)
    at = int(rng.integers(t // 4, t // 2))
    amp = np.float32(40.0 / np.sqrt(nchan * width))
    for c in range(nchan):
        data[c, (at + int(shifts[c]) + np.arange(width)) % t] += amp

    rho = retention_bound(nchan, dms, *band, t, cert=True,
                          windows=tier.windows)
    floor = certifiable_snr_floor(t, len(dms), rho)
    t0 = time.perf_counter()
    table = dedispersion_search(data, tier.dm_lo, tier.dm_hi, *band,
                                backend="jax", kernel="hybrid",
                                trial_dms=dms, windows=tier.windows,
                                snr_floor=floor)
    search_s = time.perf_counter() - t0
    exact = np.asarray(table["exact"])
    best = int(table.argbest())
    held = np.flatnonzero(exact)
    rows = sorted({best} | {int(held[int(i)]) for i in np.linspace(
        0, len(held) - 1, check_rows - 1).round()})
    offs = dispersion.channel_shifts(dms[rows], nchan, *band) % t

    def accumulate(chans):
        acc = np.zeros((len(rows), t))
        for c in chans:
            v = data[c].astype(np.float64)
            for i in range(len(rows)):
                o = int(offs[i, c])
                acc[i, : t - o] += v[o:]
                acc[i, t - o:] += v[:o]
        return acc

    threads = min(8, os.cpu_count() or 1)
    t0 = time.perf_counter()
    with ThreadPoolExecutor(threads) as pool:
        plane = sum(pool.map(accumulate, [list(range(nchan))[i::threads]
                                          for i in range(threads)]))
    want = [reference_boxcar.score_row(plane[i], list(ladder))
            for i in range(len(rows))]
    reference_s = time.perf_counter() - t0
    snr = np.asarray(table["snr"], dtype=np.float64)[rows]
    gaps = snr / np.array([w[0] for w in want]) - 1.0
    window_gap = int(sum(int(table["rebin"][r]) != w[1]
                         for r, w in zip(rows, want)))
    peak_gap = int(sum(int(table["peak"][r]) != w[2]
                       for r, w in zip(rows, want)))
    ibest = rows.index(best)
    ok = (not table.meta["certified"] and bool(exact[rows].all())
          and window_gap == 0 and peak_gap == 0
          and float(np.max(np.abs(gaps))) <= limit
          and want[ibest][0] >= floor and want[ibest][1] >= width // 2)
    return {
        "part": "hybrid_tier0", "samples": t, "trials": len(dms),
        "windows": len(ladder), "pulse_width": width, "pulse_dm": dm,
        "floor": floor, "rho": rho, "certified": bool(table.meta["certified"]),
        "rows_exact": int(exact.sum()), "rows_checked": len(rows),
        "all_checked_exact": bool(exact[rows].all()),
        "best_row": best, "best_snr": float(table["snr"][best]),
        "best_window": int(table["rebin"][best]),
        "reference_best_snr": want[ibest][0],
        "reference_best_window": want[ibest][1],
        "window_gap": window_gap, "peak_gap": peak_gap,
        "snr_rel_gap_max": float(np.max(np.abs(gaps))),
        "snr_rel_gap_rms": float(np.sqrt(np.mean(gaps ** 2))),
        "search_s": round(search_s, 2), "reference_s": round(reference_s, 2),
        "ok": ok}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--boxcar-max", type=int, default=None,
                    help="default: 4096, or 256 with --rehearsal")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--limit", type=float, default=5e-6,
                    help="largest relative S/N gap (the cell's own limit)")
    ap.add_argument("--skip-hybrid", action="store_true")
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--out", default=None, help="also write the lines here")
    args = ap.parse_args(argv)

    if args.rehearsal:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    from pulsarutils_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    geom = TINY if args.rehearsal else HTRU
    boxcar_max = args.boxcar_max or (256 if args.rehearsal else 4096)
    lines = [{"device": str(jax.devices()[0].device_kind),
              "backend": jax.default_backend(), "boxcar_max": boxcar_max,
              "seed": args.seed, "rehearsal": bool(args.rehearsal)}]
    lines += scorer_part(geom, boxcar_max, args.seed,
                         interpret=jax.default_backend() != "tpu",
                         limit=args.limit)
    if not args.skip_hybrid:
        lines.append(hybrid_part(geom, boxcar_max, args.seed, args.limit))
    ok = all(line.get("ok", True) for line in lines)
    lines.append({"ok": ok})
    text = "\n".join(json.dumps(line) for line in lines)
    print(text)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text + "\n")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
