"""``PUsearchfrb`` — chunked dispersed-pulse search over filterbank files.

Reference counterpart: ``pulsarutils/clean.py:360-373`` (which hardcoded
``dmmin=300, dmmax=400``; kept as defaults, now overridable).
"""

from __future__ import annotations

import argparse
import contextlib
import os

from ..obs import trace
from ..pipeline.search_pipeline import search_by_chunks
from ..utils.logging_utils import BUDGET_SCHEMA_VERSION, logger


def build_parser():
    parser = argparse.ArgumentParser(
        description="Clean filterbank data and search for FRBs/single pulses")
    parser.add_argument("fnames", nargs="+",
                        help="input SIGPROC filterbank files")
    def _snr_threshold(value):
        if value in ("auto", "certifiable"):
            return value
        try:
            return float(value)
        except ValueError:
            raise argparse.ArgumentTypeError(
                f"{value!r}: expected a number, 'auto' or 'certifiable'")

    parser.add_argument("--dmmin", type=float, default=300.0)
    parser.add_argument("--dmmax", type=float, default=400.0)
    parser.add_argument("--sample-time", type=float, default=None,
                        help="resample to this sample time (s); default "
                             "auto from DM smearing")
    parser.add_argument("--chunk-length", type=float, default=None,
                        help="chunk length in seconds; default = band "
                             "crossing delay at dmmax")
    parser.add_argument("--tmin", type=float, default=0.0,
                        help="skip data before this time (s)")
    parser.add_argument("--snr-threshold", type=_snr_threshold, default=6.0,
                        help="hit criterion: a number (reference default "
                             "6), 'auto' (noise-ceiling-matched floor for "
                             "the chunk geometry) or 'certifiable' (the "
                             "lowest floor whose hybrid noise certificate "
                             "fires on signal-free chunks — the survey "
                             "fast path with --kernel hybrid)")
    parser.add_argument("--surelybad", type=int, nargs="*", default=[])
    parser.add_argument("--backend", choices=("jax", "numpy"), default="jax")
    parser.add_argument("--kernel",
                        choices=("auto", "pallas", "gather", "fdmt",
                                 "hybrid", "fourier"),
                        default="auto",
                        help="jax-path kernel; fdmt = tree dedispersion "
                             "(fastest dense sweep, tree-rounded tracks); "
                             "hybrid = FDMT coarse + exact rescore of the "
                             "hit region (exact hits at near-FDMT speed); "
                             "fourier = exact fractional-sample delays "
                             "(precision option)")
    parser.add_argument("--fft-zap", action="store_true",
                        help="excise periodic RFI in the Fourier domain")
    parser.add_argument("--cut-outliers", action="store_true",
                        help="zero broadband outlier time bins")
    parser.add_argument("--zero-dm", action="store_true",
                        help="subtract the channel-averaged time series "
                             "(broadband un-dispersed RFI filter)")
    parser.add_argument("--dm-tiers", choices=("smearing",), default=None,
                        help="search the DM range in tiers: the sample time "
                             "doubles wherever the intra-channel smearing at "
                             "the band centre reaches one sample, and each "
                             "tier searches the band delays of its own "
                             "sample time (default off: one sample time for "
                             "the whole range)")
    parser.add_argument("--boxcar-max", type=int, default=None, metavar="N",
                        help="widest boxcar of the scorer's ladder, a power "
                             "of two in samples of the file (Heimdall's "
                             "boxcar_max): 1, 2, 4, ..., N, in a tier at "
                             "2^k samples 1 .. max(8, N / 2^k) of its own "
                             "(default off: 1, 2, 4, 8 of whatever sample "
                             "time a plan or tier works at)")
    parser.add_argument("--output-dir", default=None)
    parser.add_argument("--show-plots", action="store_true",
                        help="display each diagnostic figure interactively "
                             "as well as saving it (reference show=True "
                             "behaviour; needs an interactive matplotlib "
                             "backend — on a headless Agg session the "
                             "figures are only saved)")
    parser.add_argument("--plots", choices=("hits", "all", "none"),
                        default="hits")
    parser.add_argument("--no-resume", action="store_true",
                        help="reprocess chunks already in the ledger")
    parser.add_argument("--dispatch-timeout", type=float, default=None,
                        metavar="SECONDS",
                        help="deadline per device dispatch (watchdog "
                             "thread): a wedged device no longer stalls "
                             "the stream forever — the chunk proceeds to "
                             "retry/numpy fallback within timeout x "
                             "(retries+1).  Default off.  CAUTION: the "
                             "watchdog dispatches from a non-main "
                             "thread; device clients that require "
                             "main-thread dispatch must be tested "
                             "before enabling — see docs/robustness.md")
    parser.add_argument("--dispatch-retries", type=int, default=1,
                        help="same-backend retries before the numpy "
                             "fallback (default 1, the pre-hardening "
                             "behaviour)")
    parser.add_argument("--quarantine-policy", default="sanitize",
                        choices=("sanitize", "strict", "off"),
                        help="pre-search data-integrity gate: 'sanitize' "
                             "(default) imputes sub-threshold NaN/Inf and "
                             "quarantines unrecoverable chunks into "
                             "quarantine_<fingerprint>.jsonl; 'strict' "
                             "quarantines any non-finite chunk; 'off' "
                             "disables the gate")
    parser.add_argument("--max-chunks", type=int, default=None)
    parser.add_argument("--period-search", action="store_true",
                        help="also run the folded period search on each "
                             "chunk's dedispersed plane")
    parser.add_argument("--period-sigma", type=float, default=8.0,
                        help="significance threshold for periodic hits")
    parser.add_argument("--no-sift", action="store_true",
                        help="skip duplicate-candidate sifting (the 50%% "
                             "chunk overlap detects each pulse twice)")
    parser.add_argument("--trace", default=None, metavar="OUT.json",
                        help="write a Chrome/Perfetto trace of the run's "
                             "spans to this path AND a jax.profiler "
                             "device trace to '<OUT.json>_device/' (one "
                             "flag, both traces); the program's spans "
                             "are annotations in the device trace too")
    parser.add_argument("--metrics-out", default=None, metavar="PATH",
                        help="write the run's metrics-registry snapshot "
                             "(counters/gauges/histograms: candidates, "
                             "trips, bytes moved, memory "
                             "watermarks) to PATH — Prometheus textfile "
                             "format for a .prom suffix, JSONL otherwise")
    parser.add_argument("--http-port", type=int, default=None,
                        metavar="PORT",
                        help="serve the live survey surface while the "
                             "search runs: /metrics (Prometheus scrape), "
                             "/healthz (OK/DEGRADED/CRITICAL verdict, "
                             "HTTP 503 on CRITICAL), /progress (chunks "
                             "done/total, ETA, canary recall).  0 binds "
                             "an ephemeral port")
    parser.add_argument("--http-host", default="127.0.0.1",
                        metavar="ADDR",
                        help="bind address for --http-port (default "
                             "127.0.0.1: on-machine only; 0.0.0.0 "
                             "exposes the surface to remote Prometheus "
                             "scrapes / fleet healthz probes)")
    parser.add_argument("--canary-rate", type=float, default=0.0,
                        metavar="FRAC",
                        help="inject a synthetic dispersed canary pulse "
                             "into this fraction of chunks (reader "
                             "thread) and measure live recall / S/N "
                             "recovery / DM error; canary detections "
                             "are tagged and excluded from candidates, "
                             "ledger and sift.  0 (default) = off, "
                             "byte-identical data path")
    parser.add_argument("--canary-dm", type=float, default=None,
                        help="canary DM (default: middle of the search "
                             "range)")
    parser.add_argument("--canary-snr", type=float, default=12.0,
                        help="canary target S/N (default 12)")
    parser.add_argument("--lineage", action="store_true",
                        help="stamp every detection with a candidate "
                             "lineage record (trace id + monotonic "
                             "stage timestamps: read, dispatch, device "
                             "ready, sift, persist, alert), persisted "
                             "as <candidate>.lineage.json beside the "
                             "npz pair and driving the candidate-"
                             "latency SLO.  Default off, byte-inert")
    parser.add_argument("--push-webhook", action="append", default=None,
                        metavar="URL",
                        help="POST every detection to this webhook URL "
                             "(repeatable: one subscriber per flag).  "
                             "Delivery runs on a bounded background "
                             "queue — a slow or dead webhook never "
                             "stalls the search; undeliverable alerts "
                             "are journaled to push_dead_letter_"
                             "<fingerprint>.jsonl in the output dir.  "
                             "More subscribers (with min-S/N / DM-range "
                             "filters) can join a live run via POST "
                             "/subscribe on --http-port")
    parser.add_argument("--push-min-snr", type=float, default=None,
                        metavar="SNR",
                        help="only push detections at or above this "
                             "S/N (applies to every --push-webhook "
                             "subscriber)")
    parser.add_argument("--report-out", default=None, metavar="PATH",
                        help="write the end-of-run survey report "
                             "(PATH.md + self-contained PATH.html: "
                             "budget buckets, canary recall "
                             "curve, health incidents, sift counters, "
                             "quarantine manifest); with several input "
                             "files each gets PATH.<root>")
    return parser


#: exit status of a run that finished, persisted everything, and yet did
#: not search every chunk on the device it was asked to use
EXIT_DEGRADED = 3


def _degraded_counts():
    """Process-wide total of the ways a run leaves the device path:
    chunks searched by NumPy / cleaned on the host after a device
    failure, and chunks quarantined ``oom_floor``.  ``main`` compares it
    before and after (the registry is process-wide)."""
    from ..obs.metrics import REGISTRY

    return REGISTRY.total("putpu_host_fallbacks_total",
                          "putpu_oom_floor_total")


@contextlib.contextmanager
def _call_span(fname):
    """The root span of one file's search: ``call``.  Under a tracer the
    call's spans share one ``trace_id`` across the main, reader and
    persist threads — a fresh one unless the caller bound a context."""
    with contextlib.ExitStack() as stack:
        if trace.is_tracing() and trace.current_trace_context() is None:
            stack.enter_context(trace.trace_context(trace.new_trace_id()))
        stack.enter_context(
            trace.span("call", file=os.path.basename(str(fname))))
        yield


def main(args=None):
    opts = build_parser().parse_args(args)
    if opts.backend == "jax":
        from ..utils.compile_cache import enable_compile_cache

        enable_compile_cache()
    degraded_before = _degraded_counts()
    if opts.trace:
        session = trace.trace_session(
            path=opts.trace, device_trace_dir=opts.trace + "_device")
    else:
        session = contextlib.nullcontext()
    total_raw = 0
    total_cands = 0
    with session:
      for fname in opts.fnames:
        canary = None
        if opts.canary_rate > 0:
            from ..obs.canary import CanaryController

            # one controller per file: recall is a per-run statement
            canary = CanaryController(rate=opts.canary_rate,
                                      dm=opts.canary_dm,
                                      snr=opts.canary_snr)
        report_out = opts.report_out
        if report_out and len(opts.fnames) > 1:
            root = os.path.splitext(os.path.basename(str(fname)))[0]
            report_out = f"{report_out}.{root}"
        push = None
        if opts.push_webhook:
            push = [{"url": url,
                     **({"min_snr": opts.push_min_snr}
                        if opts.push_min_snr is not None else {})}
                    for url in opts.push_webhook]
        with _call_span(fname):
            hits, _ = search_by_chunks(
                fname,
                chunk_length=opts.chunk_length,
                new_sample_time=opts.sample_time,
                tmin=opts.tmin,
                dmmin=opts.dmmin,
                dmmax=opts.dmmax,
                surelybad=opts.surelybad,
                backend=opts.backend,
                kernel=opts.kernel,
                snr_threshold=opts.snr_threshold,
                output_dir=opts.output_dir,
                make_plots=False if opts.plots == "none" else opts.plots,
                show_plots=opts.show_plots,
                resume=not opts.no_resume,
                fft_zap=opts.fft_zap,
                cut_outliers=opts.cut_outliers,
                zero_dm=opts.zero_dm,
                dm_tiers=opts.dm_tiers,
                boxcar_max=opts.boxcar_max,
                max_chunks=opts.max_chunks,
                period_search=opts.period_search,
                period_sigma_threshold=opts.period_sigma,
                dispatch_timeout=opts.dispatch_timeout,
                dispatch_retries=opts.dispatch_retries,
                quarantine_policy=opts.quarantine_policy,
                http_port=opts.http_port,
                http_host=opts.http_host,
                canary=canary,
                report_out=report_out,
                lineage=opts.lineage,
                push=push,
            )
            total_raw += len(hits)
            if hits and not opts.no_sift:
                with trace.span("call/sift"):
                    from ..pipeline.sift import sift_hits

                    sift_stats = {}
                    sifted = sift_hits(hits, stats=sift_stats)
                    if report_out and sift_stats:
                        # the driver wrote the report before sift ran: fold
                        # the sift telemetry in now (observability must never
                        # fail the run, hence the containment)
                        from ..obs.report import amend_report

                        try:
                            amend_report(report_out, sift=sift_stats)
                        except Exception as exc:
                            logger.warning("could not amend the survey report "
                                           "with sift telemetry (%r)", exc)
                    total_cands += len(sifted)
                    logger.info("%s: %d raw detections -> %d sifted "
                                "candidates", fname, len(hits), len(sifted))
                    for c in sifted:
                        logger.info("  t=%.4fs DM=%.2f snr=%.2f width=%.4gs "
                                    "(%d detections)", c["time"], c["dm"],
                                    c["snr"], c["width"], c["n_members"])
            else:
                total_cands += len(hits)
    logger.info("total candidates: %d (%d raw detections)",
                total_cands, total_raw)
    if opts.metrics_out:
        from ..obs.metrics import REGISTRY

        if opts.metrics_out.endswith(".prom"):
            # the .prom route is parsed by Prometheus itself — no
            # JSON header line there
            n = REGISTRY.write_prometheus(opts.metrics_out)
        else:
            n = REGISTRY.write_jsonl(opts.metrics_out,
                                     schema_version=BUDGET_SCHEMA_VERSION)
        logger.info("metrics: %d lines -> %s", n, opts.metrics_out)
    if _degraded_counts() > degraded_before:
        # everything above was persisted as usual; the status says the
        # result did not come from the path the user asked for
        logger.error("run ended on a fall-back path (host search/clean "
                     "after a device failure, or oom_floor chunks): "
                     "exit status %d", EXIT_DEGRADED)
        return EXIT_DEGRADED
    return 0


if __name__ == "__main__":  # python -m pulsarutils_tpu.cli.search_main
    import sys

    sys.exit(main())
