"""``PUmultibeam`` — the multi-beam / multi-tenant survey front end.

Two modes:

* **direct** (default): search the given filterbanks as the beams of
  one batched survey (``multibeam_search``), print the cross-beam
  coincidence verdicts, optionally write the survey report;
* **service** (``--serve``): start the job-submission service + HTTP
  surface and block — jobs arrive over ``POST /jobs`` (see
  ``docs/multibeam.md`` for curl examples), same-geometry tenants are
  batched into shared device dispatches, ``GET /jobs/<id>`` serves
  status/health, ``POST /jobs/<id>/cancel`` cancels.  Any filenames
  given on the command line are submitted as the first jobs.
"""

from __future__ import annotations

import argparse
import json
import os

from ..utils.logging_utils import logger


def build_parser():
    parser = argparse.ArgumentParser(
        prog="PUmultibeam",
        description="Batched multi-beam single-pulse survey with "
                    "cross-beam coincidence sifting (and an optional "
                    "job-submission service).")
    parser.add_argument("fnames", nargs="*",
                        help="same-geometry filterbank files (one per "
                             "beam / tenant job)")
    parser.add_argument("--dmmin", type=float, default=300.0)
    parser.add_argument("--dmmax", type=float, default=400.0)
    parser.add_argument("--snr-threshold", type=float, default=6.0)
    parser.add_argument("--output-dir", default=None)
    parser.add_argument("--max-chunks", type=int, default=None)
    parser.add_argument("--no-resume", action="store_true")
    parser.add_argument("--sequential", action="store_true",
                        help="dispatch beam-by-beam instead of batched "
                             "(the A/B baseline; results are "
                             "byte-identical either way)")
    parser.add_argument("--canary-rate", type=float, default=0.0,
                        help="per-beam canary injection rate (each beam "
                             "injects its own deterministic chunk "
                             "subset and owns its recall gauges)")
    parser.add_argument("--veto-frac", type=float, default=0.7,
                        help="fraction of beams that must see one "
                             "(DM, time) for the anti-coincidence RFI "
                             "veto (default 0.7)")
    parser.add_argument("--max-real-beams", type=int, default=2,
                        help="max adjacent beams a confirmed "
                             "astrophysical candidate may span")
    parser.add_argument("--serve", action="store_true",
                        help="start the job-submission service + HTTP "
                             "API and block (files become the first "
                             "submitted jobs)")
    parser.add_argument("--http-port", type=int, default=None,
                        help="HTTP surface port (required with --serve; "
                             "0 binds an ephemeral port)")
    parser.add_argument("--http-host", default="127.0.0.1")
    return parser


def _run_direct(opts):
    from ..beams.multibeam import multibeam_search

    result = multibeam_search(
        opts.fnames, opts.dmmin, opts.dmmax,
        snr_threshold=opts.snr_threshold, output_dir=opts.output_dir,
        resume=not opts.no_resume, max_chunks=opts.max_chunks,
        batched=not opts.sequential, canary_rate=opts.canary_rate,
        veto_frac=opts.veto_frac, max_real_beams=opts.max_real_beams)
    for beam in result["beams"]:
        logger.info("beam %s (%s): %d hit(s)%s", beam["beam"],
                    os.path.basename(beam["fname"]), len(beam["hits"]),
                    " [cancelled]" if beam["cancelled"] else "")
    coinc = result["coincidence"]
    if coinc is not None:
        from ..beams.coincidence import group_summary

        for row in group_summary(coinc["groups"]):
            logger.info("coincidence %-9s t=%.3fs DM=%.1f S/N=%.1f "
                        "beams=%s (%d member(s))", row["verdict"],
                        row["time_s"], row["dm"], row["snr"],
                        ",".join(row["beams"]), row["n_members"])
        print(json.dumps({"coincidence": coinc["stats"]}))
    return 0


def _run_service(opts):
    import time

    from ..beams.service import SurveyService
    from ..obs.server import start_obs_server

    if opts.http_port is None:
        logger.error("--serve needs --http-port (0 = ephemeral)")
        return 2
    out = opts.output_dir or os.getcwd()
    service = SurveyService(out, resume=not opts.no_resume)
    server = start_obs_server(opts.http_port, host=opts.http_host,
                              service=service)
    logger.info("job service on http://%s:%d — POST /jobs to submit",
                opts.http_host, server.port)
    for fname in opts.fnames:
        job_id = service.submit({"fname": fname, "dmmin": opts.dmmin,
                                 "dmmax": opts.dmmax,
                                 "snr_threshold": opts.snr_threshold,
                                 "max_chunks": opts.max_chunks})
        logger.info("submitted %s as %s", fname, job_id)
    try:
        while True:
            time.sleep(1.0)
    except KeyboardInterrupt:
        logger.info("shutting down job service")
    finally:
        server.close()
        service.close()
    return 0


def main(args=None):
    opts = build_parser().parse_args(args)
    if not opts.serve and not opts.fnames:
        build_parser().error("give at least one filterbank (or --serve)")
    from ..utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    if opts.serve:
        return _run_service(opts)
    return _run_direct(opts)


if __name__ == "__main__":
    raise SystemExit(main())
