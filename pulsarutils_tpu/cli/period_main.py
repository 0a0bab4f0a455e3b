"""``PUperiod`` — the survey-scale periodicity search front end.

Runs one filterbank through the full-observation periodicity job
(:func:`~pulsarutils_tpu.periodicity.driver.periodicity_search`):
stream + dedisperse + accumulate the whole observation into a
DM–time plane, sweep the (DM, acceleration) trial grid with harmonic
summing, sift (zap list / DM grouping / harmonic relations), fold the
survivors and print the candidate table.  The chunk ledger +
accumulator snapshot make the job exactly resumable — re-run the same
command after an interruption and only the remaining chunks stream.
"""

from __future__ import annotations

import argparse
import json

from ..utils.logging_utils import logger


def build_parser():
    parser = argparse.ArgumentParser(
        prog="PUperiod",
        description="Full-observation pulsar periodicity search: "
                    "DM-time accumulation, acceleration trials, "
                    "harmonic-aware sifting and candidate folding.")
    parser.add_argument("fname", help="filterbank file to search")
    parser.add_argument("--dmmin", type=float, default=200.0)
    parser.add_argument("--dmmax", type=float, default=800.0)
    parser.add_argument("--accel-max", type=float, default=0.0,
                        help="half-width of the trial acceleration "
                             "grid in m/s^2 (0 = unaccelerated search)")
    parser.add_argument("--n-accel", type=int, default=None,
                        help="override the physics-spaced trial count "
                             "(odd; the grid always includes 0)")
    parser.add_argument("--jerk-max", type=float, default=0.0,
                        help="half-width of the trial jerk grid in "
                             "m/s^3 (0 = no jerk axis)")
    parser.add_argument("--n-jerk", type=int, default=None,
                        help="override the physics-spaced jerk trial "
                             "count (odd; the grid always includes 0)")
    parser.add_argument("--accel-backend", default="auto",
                        choices=["auto", "time_stretch", "fdas"],
                        help="trial formulation: time_stretch (one FFT "
                             "per trial), fdas (one FFT per DM + "
                             "z/w-response correlation) or the "
                             "measured auto selection")
    parser.add_argument("--sigma-threshold", type=float, default=8.0,
                        help="candidate significance floor (Gaussian-"
                             "equivalent sigma)")
    parser.add_argument("--topk", type=int, default=64,
                        help="trial-search cells retained before the "
                             "sift")
    parser.add_argument("--max-harmonics", type=int, default=16)
    parser.add_argument("--fmin", type=float, default=None,
                        help="low frequency cut in Hz (default: 4 "
                             "cycles per observation)")
    parser.add_argument("--fmax", type=float, default=None)
    parser.add_argument("--nbin", type=int, default=32,
                        help="phase bins for candidate folding")
    parser.add_argument("--zap", default=None, metavar="PATH",
                        help="zap/birdie list of known RFI "
                             "periodicities (JSON, docs/periodicity.md)")
    parser.add_argument("--rebin", default="auto",
                        help="time-rebin factor of the accumulated "
                             "plane ('auto' sizes it by the memory "
                             "budget)")
    parser.add_argument("--snapshot-every", type=int, default=1,
                        help="accumulator snapshot cadence in chunks "
                             "(1 = after every chunk, the exact-resume "
                             "default)")
    parser.add_argument("--backend", default="jax",
                        choices=["jax", "numpy"])
    parser.add_argument("--snr-threshold", default="6.0",
                        help="single-pulse threshold of the streaming "
                             "leg (number, 'auto' or 'certifiable')")
    parser.add_argument("--output-dir", default=None)
    parser.add_argument("--no-resume", action="store_true")
    parser.add_argument("--canary", action="store_true",
                        help="inject the synthetic periodic canary "
                             "and report its recall")
    parser.add_argument("--chunk-length", type=float, default=None)
    parser.add_argument("--http-port", type=int, default=None,
                        help="live /metrics /healthz /progress surface")
    parser.add_argument("--report-out", default=None,
                        help="write the survey report (markdown + "
                             "HTML) with the Periodicity section")
    parser.add_argument("--json", action="store_true",
                        help="print the candidate table as JSON lines")
    return parser


def main(argv=None):
    from ..periodicity.driver import periodicity_search

    opts = build_parser().parse_args(argv)
    if opts.backend == "jax":
        from ..utils.compile_cache import enable_compile_cache

        enable_compile_cache()
    try:
        snr = float(opts.snr_threshold)
    except ValueError:
        snr = opts.snr_threshold
    rebin = opts.rebin if opts.rebin == "auto" else int(opts.rebin)
    kwargs = {}
    if opts.chunk_length is not None:
        kwargs["chunk_length"] = opts.chunk_length
    res = periodicity_search(
        opts.fname, opts.dmmin, opts.dmmax, accel_max=opts.accel_max,
        n_accel=opts.n_accel, jerk_max=opts.jerk_max,
        n_jerk=opts.n_jerk, accel_backend=opts.accel_backend,
        sigma_threshold=opts.sigma_threshold,
        topk=opts.topk, max_harmonics=opts.max_harmonics,
        fmin=opts.fmin, fmax=opts.fmax, nbin=opts.nbin,
        zap_path=opts.zap, rebin=rebin,
        snapshot_every=opts.snapshot_every, backend=opts.backend,
        snr_threshold=snr, output_dir=opts.output_dir,
        resume=not opts.no_resume, canary=opts.canary,
        http_port=opts.http_port, report_out=opts.report_out, **kwargs)
    if not res["complete"]:
        logger.warning("job incomplete — re-run the same command to "
                       "resume from the snapshot")
        return 1
    cands = res["candidates"]
    if opts.json:
        for c in cands:
            print(json.dumps({k: v for k, v in c.items()
                              if k != "profile"}, default=float))
    else:
        if not cands:
            print("no candidates above sigma "
                  f"{opts.sigma_threshold:g}")
        for i, c in enumerate(cands):
            print(f"#{i + 1}  P={1.0 / c['freq']:.6f}s  "
                  f"f={c['freq']:.6f}Hz  DM={c['dm']:.2f}  "
                  f"accel={c['accel']:+.1f} m/s^2  "
                  f"sigma={c['sigma']:.1f}  nharm={c['nharm']}  "
                  f"H={c.get('h', 0.0):.1f}")
        print(f"candidates -> {res['candidates_path']}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
