"""Benchmark suite over the five BASELINE.json configurations.

Each config prints one JSON line (same schema as bench.py where a
baseline comparison exists). Select with --configs 1 2 3 4 5 (default:
all). Failures in one config don't stop the others.

  1  256-chan x 65k, 64 trials — single-core NumPy (reference semantics)
  2  1024-chan x 1M, 512 trials — jax kernel, one chip (== bench.py)
  3  RFI-contaminated 1024-chan stream -> FFT mask -> dedisperse
  4  4096 DM trials + folded period search (FFT over dedispersed plane)
  5  streaming 8 x 1M-sample chunks, on-device running stats + overlap
  6  Fourier-domain dedispersion (FDD, the precision option) trials/s
  7  instrumented streaming budget: on-disk 2-bit file -> hybrid
     search_by_chunks with the round-6 BudgetAccountant (wall/chunk,
     buckets, unattributed residual, device trips x RTT)
  8  mesh fused-vs-unfused hybrid A/B (tools/mesh_fused_ab.py): the
     per-route dispatch/readback
     counters — one fused shard_map program per hit chunk vs coarse +
     one dispatch per rescore bucket
  9  chaos drill (tools/chaos_drill.py): the full survey loop under the
     fault matrix — recoverable classes byte-identical to the
     fault-free run, unrecoverable classes quarantined + audited
 10  canary survey (ISSUE 5): short survey with canary pulses injected
     into EVERY chunk plus one injected RFI-storm chunk — emits live
     recall (the gated value), S/N recovery, DM error and the health
     engine's verdict transitions (must flip to DEGRADED on the storm
     and recover)

 11  putpu-lint static invariants (value 1.0 = zero new findings)
 12  tuned-vs-static kernel="auto" A/B (ISSUE 7): the measured
     autotuner from an empty cache against the PUTPU_AUTOTUNE=off
     static heuristic — same data, byte-identical tables required,
     zero steady-state tuning resolutions, and the CPU winner must
     reproduce PR 1's roll-scan choice by measurement
 13  N-beam batched vs sequential A/B (ISSUE 8): the same 3-beam
     survey dispatched as one batched program per chunk epoch vs
     beam-by-beam — device dispatches per beam-chunk must drop ~Nx,
     value = sequential/batched wall per beam-chunk ratio, forced to
     0.0 when any per-beam candidate table diverges byte-for-byte
 14  2-worker fleet vs single-process A/B (ISSUE 9): the same
     multi-file survey run single-process and then through a
     coordinator + two workers over the real /fleet/ wire protocol —
     value = single-process/fleet wall ratio, forced to 0.0 when any
     per-file ledger or candidate byte diverges (the fleet may change
     speed, never science)
 15  packed low-bit vs host-unpack A/B on the streaming driver
     (ISSUE 11): the same on-disk 2-bit file streamed twice — raw
     packed bytes with in-jit device unpack + integer accumulation vs
     host-unpacked float32 upload — value = host/packed wall ratio,
     forced to 0.0 when any per-chunk table byte diverges or the
     putpu_bytes_uploaded_total ratio falls below 8x (expect ~16x at
     2 bits)
 18  distributed-observability A/B (ISSUE 14): a 2-worker fleet run
     with tracing + metric time-series + SLO burn-rate alerting fully
     armed vs fully off — value = off/on wall (the layer's measured
     overhead), forced to 0.0 on any candidate/ledger byte divergence,
     a merged trace missing a completing worker's spans, or zero SLO
     evaluations
 19  killed-coordinator restart A/B (ISSUE 15): the same fleet survey
     uninterrupted vs coordinator killed mid-survey (one unit done,
     one lease stranded) and restarted via recover() — journal
     replay, ledger re-derive, epoch-fenced re-steal — value =
     uninterrupted/recovered wall, forced to 0.0 on any
     ledger/candidate byte divergence or a recovery that did not
     actually recover
 20  acceleration-backend A/B (ISSUE 16): a synthetic binary pulsar
     with nonzero jerk searched over the identical (accel, jerk)
     trial grid by the time_stretch (one FFT per trial) and fdas
     (one FFT per DM + z/w-response correlation) backends on the jit
     path — value = time_stretch/fdas wall at matched trial counts,
     forced to 0.0 when either backend's top candidate misses the
     injected (DM, P, accel, jerk) cell or the tables fail the
     cross-backend equivalence harness

Sizes scale down with BENCH_PRESET=quick for CPU smoke runs.
"""

import argparse
import json
import os
import sys
import time
import traceback

import numpy as np


def log(msg):
    print(msg, file=sys.stderr, flush=True)


#: every record emit() printed this run, in order — --metrics-out writes
#: them as the machine-readable snapshot tools/perf_gate.py compares
RECORDS = []


def emit(obj):
    obj = {**obj, **device_stamp()}
    RECORDS.append(obj)
    print(json.dumps(obj), flush=True)


# geometry/injected-DM single source of truth: bench.py's constants (the
# simulated dispersion and the suite's searches must share one geometry)
# — and its device stamp, so both harnesses name the device one way
from bench import GEOM, device_stamp  # noqa: E402


def _load_tool(name):
    """Import a tools/ module by path (the suite configs reuse the
    committed probe/generator tools rather than forking copies)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        name, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def simulate(nchan, nsamp, seed=0):
    import bench

    return bench.make_data(nchan, nsamp, seed=seed)


def timed(fn, n=2, warmup=True):
    if warmup:
        fn()
    t0 = time.time()
    for _ in range(n):
        out = fn()
    return out, (time.time() - t0) / n


def config1(quick):
    """Reference-semantics NumPy sweep (the PR1 baseline row)."""
    from pulsarutils_tpu.ops.search import dedispersion_search

    nchan, nsamp, ndm = (256, 1 << 16, 64) if not quick else (64, 1 << 13, 16)
    array = simulate(nchan, nsamp)
    dms = np.linspace(300., 400., ndm)

    def run():
        return dedispersion_search(array, None, None, *GEOM,
                                   backend="numpy", trial_dms=dms)

    table, dt = timed(run, n=1)
    emit({"config": 1, "metric": f"NumPy reference sweep {nchan}x{nsamp}, "
          f"{ndm} trials", "value": round(ndm / dt, 3),
          "unit": "DM-trials/sec",
          "best_dm": float(table["DM"][table.argbest()])})


def config2(quick):
    """Headline single-chip jax sweep — defer to bench.py's main()."""
    import bench

    bench.main()


def config3(quick):
    """RFI-contaminated stream -> FFT zap + renormalise -> sweep."""
    import jax
    import jax.numpy as jnp

    from pulsarutils_tpu.models.simulate import inject_rfi
    from pulsarutils_tpu.ops.clean_ops import fft_zap_time, renormalize_data
    from pulsarutils_tpu.ops.search import dedispersion_search

    nchan, nsamp, ndm = (1024, 1 << 18, 256) if not quick else (128, 1 << 14, 32)
    array = simulate(nchan, nsamp)
    array = inject_rfi(array, bad_channels=range(0, nchan, 97),
                       impulse_times=range(1000, nsamp, nsamp // 7),
                       rng=1).astype(np.float32)
    # upload once, outside the timed region (see config4); the timed work
    # is the on-device clean -> dedisperse pipeline step
    array = jnp.asarray(array)
    np.asarray(array[0, :1])  # force
    dms = np.linspace(300., 400., ndm)

    clean = jax.jit(lambda a: fft_zap_time(
        renormalize_data(a, xp=jnp), xp=jnp)[0])

    def run():
        cleaned = clean(array)
        return dedispersion_search(cleaned, None, None, *GEOM, backend="jax",
                                   trial_dms=dms)

    table, dt = timed(run)
    emit({"config": 3, "metric": f"clean(FFT zap + renorm) + sweep "
          f"{nchan}x{nsamp}, {ndm} trials", "value": round(ndm / dt, 2),
          "unit": "DM-trials/sec (incl. cleaning)",
          "best_dm": float(table["DM"][table.argbest()])})


def config4(quick):
    """4096-trial sweep + folded period search over the plane.

    The trial grid is the canonical one-sample-spaced plan (4096 trials
    from DM 300), computed by the FDMT tree transform on TPU so the
    ``(ndm, T)`` plane stays device-resident for the period search — no
    multi-GB host spill/re-upload.
    """
    import jax
    import jax.numpy as jnp

    from pulsarutils_tpu.models.simulate import simulate_pulsar_data
    from pulsarutils_tpu.ops.periodicity import period_search_plane
    from pulsarutils_tpu.ops.plan import dmmax_for_trials
    from pulsarutils_tpu.ops.search import dedispersion_search

    nchan, nsamp, ndm = (1024, 1 << 18, 4096) if not quick else (64, 1 << 14, 128)
    period = 0.0625
    array, header = simulate_pulsar_data(
        period=period, dm=350.0, tsamp=GEOM[2], nsamples=nsamp, nchan=nchan,
        start_freq=GEOM[0], bandwidth=GEOM[1], signal=0.5, noise=0.5, rng=2)
    # upload once, outside the timed region (the streaming driver
    # double-buffers uploads)
    array = jnp.asarray(array, dtype=jnp.float32)
    array.block_until_ready()
    dmmax = dmmax_for_trials(300.0, ndm, *GEOM)
    kernel = "fdmt" if jax.default_backend() == "tpu" else "gather"
    trial_dms = None if kernel == "fdmt" else np.linspace(300., dmmax, ndm)

    def run():
        table, plane = dedispersion_search(
            array, 300.0, dmmax, *GEOM, backend="jax", kernel=kernel,
            trial_dms=trial_dms, capture_plane=True)
        res = period_search_plane(jnp.asarray(plane), GEOM[2], fmin=2.0,
                                  refine_top=1, xp=jnp)
        return table, res

    (table, res), dt = timed(run, n=1)
    ratio = res["best_freq"] * period
    emit({"config": 4, "metric": f"{ndm}-trial sweep + folded period search, "
          f"{nchan}x{nsamp}", "value": round(ndm / dt, 2),
          "unit": "DM-trials/sec (incl. period search)",
          "best_freq": float(res["best_freq"]),
          "freq_harmonic_of_true": round(float(ratio), 3),
          "period_sigma": round(float(res["best_sigma"]), 1)})


def config5(quick):
    """Streaming chunks: on-device running bandpass stats + overlap search.

    Two numbers (VERDICT r1 asked for an honest split):

    * **compute-bound** (the headline ``value``): chunks live in HBM
      before the clock starts.  The working set of 8 x 1M-sample 50%%-
      overlap chunks (~19 GB unique samples) exceeds a v5e's HBM, so the
      chunks are *generated device-side* per hop half (seeded
      ``jax.random``, two halves live at a time) — zero host link in the
      timed region, exactly what a fast-ingest deployment would see.
    * **link-bound**: one real host chunk uploaded host->device and
      searched, timed end-to-end (one chunk characterises the rate).

    The REAL on-disk streaming measurement — native 2-bit file, packed
    upload, CLI, resume, certificate — is ``tools/survey_rehearsal.py``
    (and ``chip_smoke.py`` for three chunks); this config remains the
    compute-bound ceiling measurement.
    """
    import jax
    import jax.numpy as jnp

    from pulsarutils_tpu.ops.search import dedispersion_search
    from pulsarutils_tpu.pipeline.spectral_stats import (
        moment_accumulate,
        moments_to_spectra,
    )

    nchan = 1024 if not quick else 128
    chunk = (1 << 20) if not quick else (1 << 14)
    nchunks = 8 if not quick else 3
    ndm = 256 if not quick else 32
    dms = np.linspace(300., 400., ndm)
    hop = chunk // 2

    # -- compute-bound pass: device-generated halves, no host link -------
    @jax.jit
    def gen_half(seed):
        key = jax.random.PRNGKey(seed)
        return jnp.abs(
            jax.random.normal(key, (nchan, hop), jnp.float32)) * 0.5

    def run_device():
        s = jnp.zeros(nchan)
        sq = jnp.zeros(nchan)
        n = 0
        best = None
        prev = gen_half(0)
        for k in range(nchunks):
            nxt = gen_half(k + 1)
            block = jnp.concatenate([prev, nxt], axis=1)
            prev = nxt
            s, sq, n = moment_accumulate((s, sq, n), block)
            table = dedispersion_search(block, None, None, *GEOM,
                                        backend="jax", trial_dms=dms)
            row = table.best_row()
            if best is None or row["snr"] > best["snr"]:
                best = row
        mean, std = moments_to_spectra(s, sq, n, xp=jnp)
        mean.block_until_ready()
        return best, float(mean.mean())

    (_, _), dt = timed(run_device, n=1, warmup=True)
    samples_per_sec = nchunks * chunk / dt

    # -- survey-hybrid pass (round 3, VERDICT r2 #1): same chunks, ONE
    # carries an injected pulse; kernel="hybrid" with the certifiable
    # detection floor.  Signal-free chunks must take the noise-certified
    # fast path (one coarse sweep, zero exact rescores); the pulse chunk
    # must come back NOT certified with the exact kernel's argbest row.
    from pulsarutils_tpu.ops.certify import (
        cert_retention,
        certifiable_snr_floor,
    )
    from pulsarutils_tpu.ops.plan import dedispersion_shifts

    rho = float(cert_retention(nchan, dms, *GEOM, chunk).min())
    floor = round(certifiable_snr_floor(chunk, ndm, rho), 2)
    pulse_chunk = nchunks // 2
    shifts = jnp.asarray(np.rint(np.asarray(dedispersion_shifts(
        nchan, 350.0, *GEOM))).astype(np.int32) % chunk)

    # amplitude per bin for a width-2 boxcar pulse with exact S/N ~ 2x
    # the floor: snr = 2*amp*nchan / (0.301*sqrt(nchan)*sqrt(2)) with
    # 0.301 the per-sample std of the abs-normal*0.5 noise
    amp = 0.426 * 2.0 * floor / (2.0 * np.sqrt(nchan))

    @jax.jit
    def inject(block):
        # boxcar width-2 pulse along the exact integer track at DM 350
        pos = (chunk // 3 + shifts) % chunk
        chan_idx = jnp.arange(nchan)
        block = block.at[chan_idx, pos].add(amp)
        return block.at[chan_idx, (pos + 1) % chunk].add(amp)

    def run_hybrid():
        s = jnp.zeros(nchan)
        sq = jnp.zeros(nchan)
        n = 0
        certified = 0
        pulse_table = None
        prev = gen_half(100)
        for k in range(nchunks):
            nxt = gen_half(101 + k)
            block = jnp.concatenate([prev, nxt], axis=1)
            prev = nxt
            if k == pulse_chunk:
                block = inject(block)
            s, sq, n = moment_accumulate((s, sq, n), block)
            table = dedispersion_search(block, None, None, *GEOM,
                                        backend="jax", kernel="hybrid",
                                        trial_dms=dms, snr_floor=floor)
            if k == pulse_chunk:
                # counted separately: a wrongly-certified pulse chunk
                # must show up in the pulse_chunk block, not pad the
                # noise numerator
                pulse_table = table
            else:
                certified += bool(table.meta["certified"])
        mean, _ = moments_to_spectra(s, sq, n, xp=jnp)
        np.asarray(mean[:1])  # force
        return certified, pulse_table

    log(f"hybrid streaming pass: floor={floor} (rho_cert={rho:.3f})")
    (certified, pulse_table), dt_h = timed(run_hybrid, n=1, warmup=True)
    h_sps = nchunks * chunk / dt_h
    best = pulse_table.best_row()
    hybrid_section = {
        "dm_trials_per_sec": round(nchunks * ndm / dt_h, 1),
        "msamples_per_sec": round(h_sps / 1e6, 2),
        "snr_floor": floor,
        "rho_cert": round(rho, 3),
        "noise_chunks_certified": f"{certified}/{nchunks - 1}",
        "pulse_chunk": {
            "certified": bool(pulse_table.meta["certified"]),
            "best_dm": float(best["DM"]),
            "best_snr": round(float(best["snr"]), 2),
            "argbest_exact": bool(
                pulse_table["exact"][pulse_table.argbest()]),
            "above_floor": bool(best["snr"] > floor),
        },
        "note": "same device-generated stream, one injected DM-350 "
                "pulse; certified chunks pay one coarse sweep and zero "
                "exact rescores",
    }

    # -- link-bound pass: one real chunk host->device -------------------
    array = simulate(nchan, chunk)
    t0 = time.time()
    block = jnp.asarray(array)
    block.block_until_ready()
    t_up = time.time() - t0
    t0 = time.time()
    table = dedispersion_search(block, None, None, *GEOM, backend="jax",
                                trial_dms=dms)
    t_search = time.time() - t0
    link_sps = chunk / (t_up + t_search)

    emit({"config": 5, "metric": f"streaming {nchunks} x {chunk}-sample "
          f"chunks (50% overlap), {nchan} chan, {ndm} trials + running "
          "stats, chunks pre-staged in HBM (device-generated)",
          "value": round(samples_per_sec / 1e6, 2),
          "unit": "Msamples/sec (compute-bound)",
          "dm_trials_per_sec": round(nchunks * ndm / dt, 1),
          "hybrid_streaming": hybrid_section,
          "link_bound": {
              "msamples_per_sec": round(link_sps / 1e6, 3),
              "upload_s_per_chunk": round(t_up, 1),
              "search_s_per_chunk": round(t_search, 2),
              "note": "one real chunk host->device + search",
          },
          "best_dm": float(table["DM"][table.argbest()])})


def config6(quick):
    """Fourier-domain dedispersion (FDD): the precision option, measured.

    Exact fractional-sample delays via the uniform-grid incremental-
    rotation kernel (``ops/fourier.py``).  Reported so the "precision
    option" claim carries a number next to it (VERDICT r1 #4).
    """
    import jax.numpy as jnp

    from pulsarutils_tpu.ops.search import dedispersion_search

    nchan, nsamp, ndm = (1024, 1 << 20, 512) if not quick \
        else (64, 1 << 14, 64)
    array = simulate(nchan, nsamp)
    array = jnp.asarray(array, jnp.float32)
    np.asarray(array[0, :1])  # force upload outside the timed region
    from bench import DMMAX, DMMIN

    # full preset: the canonical plan grid (same trials as the headline);
    # quick: an explicit ndm-point uniform grid so the CPU smoke run
    # actually scales down
    trial_dms = None if not quick else np.linspace(DMMIN, DMMAX, ndm)

    def run():
        return dedispersion_search(array, DMMIN, DMMAX, *GEOM,
                                   backend="jax", kernel="fourier",
                                   trial_dms=trial_dms)

    table, dt = timed(run, n=1)
    emit({"config": 6, "metric": f"Fourier-domain dedispersion (exact "
          f"fractional delays), {nchan}x{nsamp}, {table.nrows} trials",
          "value": round(table.nrows / dt, 2), "unit": "DM-trials/sec",
          "best_dm": float(table["DM"][table.argbest()])})


def config7(quick):
    """Instrumented streaming budget (round 6): real on-disk 2-bit file
    -> packed upload -> device clean -> hybrid search at the certifiable
    floor, with every chunk's wall clock attributed by the
    BudgetAccountant.  The emitted record IS the deployment cost model:
    wall/chunk, per-bucket seconds, the explicit unattributed residual
    (must stay under ~5%), and dispatch+readback trips priced at the
    measured device RTT (the trips x RTT line is the floor no kernel
    work can remove).
    """
    import tempfile

    from pulsarutils_tpu.pipeline.search_pipeline import search_by_chunks
    from pulsarutils_tpu.utils.logging_utils import BudgetAccountant

    # one copy of the 2-bit pulse-file generator (exact-track injection,
    # descending band): tools/stream_budget_ab.py owns it
    ab = _load_tool("stream_budget_ab")

    nchan = 256 if not quick else 64
    hop = (1 << 15) if not quick else (1 << 12)
    nhops = 6 if not quick else 4
    nsamples = nhops * hop
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "budget.fil")
        ab.generate(path, nchan, nsamples, log, hop=hop,
                    margin=min(2048, hop // 4))

        acct = BudgetAccountant()
        t0 = time.time()
        hits, _ = search_by_chunks(
            path, chunk_length=hop * ab.TSAMP, dmmin=ab.DMMIN,
            dmmax=ab.DMMAX, backend="jax", kernel="hybrid",
            snr_threshold="certifiable",
            output_dir=os.path.join(tmp, "out"), make_plots=False,
            resume=False, progress=False, budget=acct)
        wall = time.time() - t0
    j = acct.to_json(max_per_chunk=0)
    emit({"config": 7, "metric": f"streaming budget: 2-bit {nchan}-chan "
          f"file, {j['chunks']} x {2 * hop}-sample hybrid chunks at the "
          "certifiable floor", "value": round(j["wall_s"] / j["chunks"], 3),
          "unit": "s/chunk (wall, budget-attributed)",
          "wall_s": round(wall, 2), "hits": len(hits),
          "attributed_pct": j["attributed_pct"],
          "unattributed_s": j["unattributed_s"],
          "buckets_s": j["buckets_s"], "counters": j["counters"],
          "async_s": j["async_s"], "rtt_s": j.get("rtt_s"),
          "trips": j.get("trips"),
          "trips_x_rtt_s": j.get("trips_x_rtt_s")})


def config8(quick):
    """Mesh fused-vs-unfused hybrid A/B (round 6, ISSUE 2).

    Runs ``tools/mesh_fused_ab.py``'s probe on whatever devices exist —
    a (1, 1) mesh everywhere (the overhead-floor configuration) plus
    the all-devices mesh when more are available — and emits the
    per-route record.  The dispatch counters are the
    platform-independent evidence: the fused route pays ONE program +
    ONE packed readback per typical hit chunk.
    """
    ab = _load_tool("mesh_fused_ab")

    result = ab.ab_cpu(quick=quick, log=log)
    fused = result["meshes"]["1x1"]["fused"]
    unfused = result["meshes"]["1x1"]["unfused"]
    emit({"config": 8, "metric": "mesh (1,1) hybrid fused-vs-unfused "
          f"A/B, {result['config']}",
          "value": unfused["trips"] - fused["trips"],
          "unit": "device round trips saved per hit chunk",
          "fused_wall_s": fused["wall_s"],
          "unfused_wall_s": unfused["wall_s"],
          "ab": result})


def config9(quick):
    """Chaos drill (ISSUE 4): the streaming survey under the fault
    matrix.  The emitted value is the number of fault classes survived
    (recoverable classes must reproduce the fault-free candidates +
    ledger byte-identically; unrecoverable classes must complete with
    the affected chunks quarantined and the integrity audit clean) —
    a drop is a robustness regression, gated like any perf number.
    """
    drill = _load_tool("chaos_drill")

    result = drill.run_drill(quick=quick, log=log)
    emit({"config": 9, "metric": "chaos drill: "
          f"{result['n_classes']} fault classes over a "
          f"{len(result['survey']['chunks'])}-chunk survey",
          "value": result["recovered_identical"] + result["contained"],
          "unit": "fault classes survived",
          "all_ok": result["all_ok"],
          "recovered_identical": result["recovered_identical"],
          "contained": result["contained"],
          "wall_s": result["wall_s"],
          "classes": {k: v["ok"] for k, v in result["classes"].items()}})


def config10(quick):
    """Canary-enabled rehearsal survey (ISSUE 5): detection efficiency
    as a gated number.  A short on-disk survey runs with a canary pulse
    injected into EVERY chunk (so recall is computed from >= 10
    injections) and ONE chunk hit by an injected broadband RFI storm
    (``faults.inject`` kind="impulse").  The emitted value is the
    canary recall — ``tools/perf_gate.py`` gates on it alongside the
    perf configs, so a change that silently degrades *detection* (not
    speed) fails the same gate.  The record also carries the health
    engine's verdict transitions: the storm must flip the verdict to
    DEGRADED (candidate-rate spike) and the clean chunks after it must
    bring it back to OK.
    """
    import tempfile
    import threading
    import urllib.request

    from pulsarutils_tpu.faults.inject import FaultPlan, FaultSpec
    from pulsarutils_tpu.io.sigproc import write_simulated_filterbank
    from pulsarutils_tpu.obs.canary import CanaryController
    from pulsarutils_tpu.obs.health import HealthEngine
    from pulsarutils_tpu.obs.server import start_obs_server
    from pulsarutils_tpu.pipeline.search_pipeline import search_by_chunks

    tsamp = 0.0005
    nchan = 64
    hop = 4096
    nhops = 14  # ~13 overlapped chunks — already tier-1 scale on CPU
    nsamples = nhops * hop
    rng = np.random.default_rng(10)
    array = np.abs(rng.normal(0, 0.5, (nchan, nsamples))) + 20.0
    header = {"bandwidth": 200., "fbottom": 1200., "nchans": nchan,
              "nsamples": nsamples, "tsamp": tsamp, "foff": 200. / nchan}
    storm_chunk = 5 * hop
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "canary.fil")
        write_simulated_filterbank(path, array, header, descending=True)
        # 8 impulses at 100 block-stds: bright enough that the wide
        # boxcar widths light up ~2/3 of the DM trials (a denser storm
        # self-suppresses — the row-std normalisation soaks it up)
        plan = FaultPlan([FaultSpec(site="corrupt", kind="impulse",
                                    chunks=(storm_chunk,), frac=0.001,
                                    times=1, amp=100.0)])
        canary = CanaryController(rate=1.0, snr=15.0, seed=10)
        engine = HealthEngine()
        # the live surface is part of what this config proves: a
        # scraper thread polls the REAL /metrics endpoint while the
        # survey runs and records the recall it saw on the wire once
        # >= 10 canaries had been injected
        srv = start_obs_server(0, health=engine,
                               progress_fn=lambda: canary.summary())
        scraped = {"recall": None, "injected": 0, "statuses": set()}
        stop = threading.Event()

        def scraper():
            base = f"http://127.0.0.1:{srv.port}"
            while not stop.is_set():
                try:
                    text = urllib.request.urlopen(
                        base + "/metrics", timeout=2.0).read().decode()
                    doc = json.loads(urllib.request.urlopen(
                        base + "/progress", timeout=2.0).read().decode())
                except Exception:
                    stop.wait(0.1)
                    continue
                scraped["statuses"].add(doc.get("status"))
                inj = doc.get("injected") or 0
                for line in text.splitlines():
                    if line.startswith("putpu_canary_recall "):
                        if inj >= 10:
                            scraped["recall"] = float(line.split()[1])
                            scraped["injected"] = inj
                stop.wait(0.1)

        poll = threading.Thread(target=scraper, daemon=True)
        poll.start()
        t0 = time.time()
        try:
            with plan.armed():
                hits, _ = search_by_chunks(
                    path, chunk_length=hop * tsamp, dmmin=100, dmmax=200,
                    backend="jax", snr_threshold=6.5,
                    output_dir=os.path.join(tmp, "out"),
                    make_plots=False, resume=False, progress=False,
                    canary=canary, health=engine)
        finally:
            stop.set()
            poll.join(timeout=5.0)
            srv.close()
        wall = time.time() - t0
    summary = canary.to_json()
    summary.pop("curve", None)  # the snapshot stays one bounded line
    reached = [t["to"] for t in engine.transitions]
    emit({"config": 10, "metric": "canary survey: "
          f"{summary['injected']} pulses injected (DM "
          f"{summary['dm']}, target S/N {summary['target_snr']}) + 1 "
          "RFI-storm chunk", "value": summary["recall"],
          "unit": "canary recall (fraction recovered)",
          "canary": summary,
          "health_final": engine.verdict,
          "health_reached_degraded": any(
              v in ("DEGRADED", "CRITICAL") for v in reached),
          "health_transitions": [
              {"chunk": t["chunk"], "from": t["from"], "to": t["to"],
               "reasons": t["reasons"]} for t in engine.transitions],
          "scraped_live": {
              "recall": scraped["recall"],
              "injected_at_scrape": scraped["injected"],
              "statuses_seen": sorted(s for s in scraped["statuses"]
                                      if s)},
          "hits": len(hits), "wall_s": round(wall, 2)})


def config11(quick):
    """putpu-lint static invariants as a bench config (ISSUE 6): the
    AST checkers (device-trip attribution, retrace hazards, lock
    discipline, metric-name sync, broad excepts, float64 leaks) run
    over the package — deterministic and sub-second, so it rides every
    gate run.  ``value`` is 1.0 only when the tree has ZERO new
    findings; any regression drops it to 0.0, far past any tolerance."""
    t0 = time.perf_counter()
    from pulsarutils_tpu.analysis.cli import run_lint

    project = run_lint()
    rep = project.report()
    emit({"config": 11,
          "metric": f"putpu-lint static invariants over {rep['files']} "
                    f"files ({len(rep['checkers'])} checkers)",
          "value": 1.0 if rep["clean"] else 0.0,
          "unit": "lint clean (1 = zero new findings)",
          "new": rep["new"], "waived": rep["waived"],
          "baselined": rep["baselined"],
          "wall_s": round(time.perf_counter() - t0, 3),
          "findings": sorted(f"{f.location()}: {f.checker}"
                             for f in project.new_findings())[:20]})


def config12(quick):
    """Tuned-vs-static ``kernel="auto"`` A/B (ISSUE 7): the measured
    autotuner against the static heuristic it replaced, on one
    geometry, same data.  The static arm runs with the tuner's
    ``off`` mode (the ``PUTPU_AUTOTUNE=off`` escape hatch, byte for
    byte); the tuned arm starts from an EMPTY cache, pays the
    measurement on first sight, then runs steady-state.  ``value`` is
    the static/tuned wall ratio (~1.0 on CPU, where both arms resolve
    to the PR 1 roll-scan) — forced to 0.0, far past any tolerance,
    when an invariant breaks: the tuned winner must reproduce the
    measured CPU roll-scan choice, the steady-state run must perform
    ZERO tuning resolutions, and the two arms' tables must be
    byte-identical (tuning may change speed, never hits)."""
    import tempfile

    import jax

    from pulsarutils_tpu.ops.search import dedispersion_search
    from pulsarutils_tpu.tuning import autotune
    from pulsarutils_tpu.tuning.cache import TuneCache

    nchan, nsamp, ndm = ((256, 1 << 16, 128) if not quick
                         else (64, 1 << 13, 64))
    array = simulate(nchan, nsamp, seed=12)
    dms = np.linspace(300., 360., ndm)

    def run():
        return dedispersion_search(array, None, None, *GEOM,
                                   backend="jax", trial_dms=dms)

    # static arm: the escape hatch — zero tuner side effects
    prev = autotune.set_tuner(autotune.KernelTuner(mode="off"))
    try:
        t_static, static_wall = timed(run, n=3)
    finally:
        autotune.set_tuner(prev)

    with tempfile.TemporaryDirectory() as tmp:
        tuner = autotune.KernelTuner(
            cache=TuneCache(os.path.join(tmp, "tune.json")),
            mode="on", min_elements=0)
        prev = autotune.set_tuner(tuner)
        try:
            t0 = time.perf_counter()
            run()  # first sight of the key: measure + cache + select
            first_wall = time.perf_counter() - t0
            mark = autotune.decision_seq()
            t_tuned, tuned_wall = timed(run, n=3, warmup=False)
            steady_resolutions = len(autotune.decisions_since(mark))
            decisions = tuner.decisions()
            key = next(iter(decisions))
            # None when measurement itself failed and the tuner fell
            # back to static (nothing cached) — that's an invariant
            # failure this config must REPORT as value 0.0, not a crash
            entry = tuner.cache.lookup(key) or {}
        finally:
            autotune.set_tuner(prev)

    static_kernel = autotune.static_search_kernel(jax.default_backend())
    winner = entry.get("kernel")
    identical = all(
        np.array_equal(np.asarray(t_static[c]), np.asarray(t_tuned[c]))
        for c in ("DM", "max", "std", "snr", "rebin", "peak"))
    # on CPU the tuner must rediscover PR 1's roll-scan win by
    # measurement; elsewhere the winner just has to be a cached one
    winner_ok = (winner == "roll"
                 if jax.default_backend() == "cpu" else winner is not None)
    ok = winner_ok and identical and steady_resolutions == 0
    measured = entry.get("measured_s") or {}
    vs_gather = (round(measured["gather"] / measured[winner], 2)
                 if "gather" in measured and winner in measured
                 and measured[winner] > 0 else None)
    emit({"config": 12, "metric": f"tuned-vs-static kernel=auto A/B, "
          f"{nchan}x{nsamp}, {ndm} trials ({jax.default_backend()})",
          "value": round(static_wall / tuned_wall, 4) if ok else 0.0,
          "unit": "x (static-auto wall / tuned wall; 0 = invariant "
                  "failure)",
          "key": key, "winner": winner,
          "static_kernel": static_kernel, "measured_s": measured,
          "winner_vs_gather": vs_gather,
          "static_wall_s": round(static_wall, 4),
          "tuned_wall_s": round(tuned_wall, 4),
          "first_sight_wall_s": round(first_wall, 4),
          "steady_resolutions": steady_resolutions,
          "tables_identical": identical})


def config13(quick):
    """N-beam batched vs sequential A/B (ISSUE 8): the multi-beam
    subsystem's amortisation claim, measured and identity-gated.

    Three same-geometry beam files (one carrying a dispersed pulse, one
    chunk epoch hit by an all-beam synthetic RFI impulse so the
    coincidence veto has something to veto) run twice through
    ``multibeam_search``: sequential (one dispatch per beam-chunk) and
    batched (ONE dispatch per chunk epoch).  The record carries
    dispatches per beam-chunk for both arms and the coincidence
    verdict counts; the headline ``value`` is the sequential/batched
    wall-per-beam-chunk ratio — forced to 0.0 (far past any gate
    tolerance) if any per-beam candidate table or ledger byte
    diverges, because batching may change speed, never science.
    """
    import tempfile

    from pulsarutils_tpu.beams.multibeam import multibeam_search
    from pulsarutils_tpu.io.sigproc import write_simulated_filterbank
    from pulsarutils_tpu.models.simulate import disperse_array
    from pulsarutils_tpu.utils.logging_utils import BudgetAccountant

    nbeams = 3
    nchan, nsamples = (256, 1 << 17) if not quick else (64, 1 << 13)
    tsamp, fbottom, bw = 0.0005, 1200.0, 200.0

    def dispersed(dm, t0, amp):
        base = np.zeros((nchan, nsamples))
        base[:, t0] = amp
        return disperse_array(base, dm, fbottom, bw, tsamp)

    with tempfile.TemporaryDirectory() as tmp:
        fnames = []
        # the SAME dispersed signal in every beam at one (DM, t): the
        # textbook anti-coincidence case (a pointlike sky signal cannot
        # be in all beams) — the sift must veto it as RFI
        rfi = dispersed(150.0, nsamples // 4, 8.0)
        # a genuinely astrophysical pulse, one beam only
        pulse = dispersed(150.0, (3 * nsamples) // 4, 8.0)
        for b in range(nbeams):
            rng = np.random.default_rng(130 + b)
            arr = np.abs(rng.normal(0, 0.5, (nchan, nsamples))) + 10.0
            arr = arr + rfi
            if b == 1:
                arr = arr + pulse
            header = {"bandwidth": bw, "fbottom": fbottom,
                      "nchans": nchan, "nsamples": nsamples,
                      "tsamp": tsamp, "foff": bw / nchan}
            path = os.path.join(tmp, f"beam{b}.fil")
            write_simulated_filterbank(path, arr, header, descending=True,
                                       nbeams=nbeams, ibeam=b + 1)
            fnames.append(path)

        def run(arm, batched):
            acc = BudgetAccountant()
            t0 = time.time()
            res = multibeam_search(
                fnames, 100, 200, snr_threshold=7.0,
                output_dir=os.path.join(tmp, arm), budget=acc,
                batched=batched, keep_tables=True, resume=True)
            return res, acc, time.time() - t0

        res_s, acc_s, wall_s = run("seq", batched=False)
        res_b, acc_b, wall_b = run("bat", batched=True)

        identical = True
        for bb, bs in zip(res_b["beams"], res_s["beams"]):
            if len(bb["tables"]) != len(bs["tables"]):
                identical = False
                break
            for (i1, t1), (i2, t2) in zip(bb["tables"], bs["tables"]):
                if i1 != i2 or any(
                        not np.array_equal(t1[c], t2[c])
                        for c in t1.colnames):
                    identical = False
        # union of BOTH arms' outputs: a candidate present in only one
        # directory (e.g. a dropped persist) is a divergence too
        names = set(os.listdir(os.path.join(tmp, "bat"))) \
            | set(os.listdir(os.path.join(tmp, "seq")))
        for name in sorted(names):
            bat_path = os.path.join(tmp, "bat", name)
            seq_path = os.path.join(tmp, "seq", name)
            if not (os.path.exists(bat_path) and os.path.exists(seq_path)):
                identical = False
                continue
            with open(bat_path, "rb") as fb, open(seq_path, "rb") as fs:
                if fb.read() != fs.read():
                    identical = False

        epochs = len(acc_b.chunks)
        beam_chunks = sum(b["chunks_done"] for b in res_b["beams"])
        disp_b = acc_b.counters_total.get("dispatches", 0)
        disp_s = acc_s.counters_total.get("dispatches", 0)
        ratio = (wall_s / beam_chunks) / (wall_b / beam_chunks) \
            if beam_chunks and wall_b else 0.0
        verdicts = (res_b["coincidence"]["stats"]["verdicts"]
                    if res_b["coincidence"] else {})
    emit({"config": 13, "metric": f"{nbeams}-beam batched vs sequential "
          f"A/B, {nchan}x{nsamples}, {epochs} chunk epochs",
          "value": round(ratio, 4) if identical else 0.0,
          "unit": "x (sequential/batched wall per beam-chunk; 0 = "
                  "identity failure)",
          "tables_identical": identical,
          "dispatches_per_beam_chunk": {
              "sequential": round(disp_s / beam_chunks, 3),
              "batched": round(disp_b / beam_chunks, 3)},
          "wall_per_beam_chunk_s": {
              "sequential": round(wall_s / beam_chunks, 4),
              "batched": round(wall_b / beam_chunks, 4)},
          "coincidence_verdicts": verdicts,
          "beam_hits": {str(b["beam"]): len(b["hits"])
                        for b in res_b["beams"]}})


def config14(quick):
    """2-worker fleet vs single-process A/B (ISSUE 9): the PR 4/8
    house rule applied to horizontal scale-out, measured and
    identity-gated over the REAL wire.

    A two-file survey (one file carrying a dispersed pulse) runs
    single-process (``search_by_chunks`` per file), then again through
    a :class:`~pulsarutils_tpu.fleet.coordinator.FleetCoordinator` +
    two :class:`~pulsarutils_tpu.fleet.worker.FleetWorker` threads
    speaking the HTTP ``/fleet/`` protocol — every lease, completion
    and ledger resolution is the production path, only the transport
    hop is loopback.  The headline ``value`` is the single-process /
    fleet wall ratio (~1 on a single-core CPU runner, where two
    workers just interleave; the number that must never silently
    regress is the dispatch math, and identity is the gate) — forced
    to 0.0, far past any tolerance, when any per-file ledger byte or
    candidate npz member diverges between the two runs, or the fleet
    fails to finish the survey.
    """
    import glob
    import tempfile
    import threading

    from pulsarutils_tpu.fleet.coordinator import FleetCoordinator
    from pulsarutils_tpu.fleet.worker import FleetWorker
    from pulsarutils_tpu.io.sigproc import write_simulated_filterbank
    from pulsarutils_tpu.models.simulate import disperse_array
    from pulsarutils_tpu.obs.server import start_obs_server
    from pulsarutils_tpu.pipeline.search_pipeline import search_by_chunks

    tsamp, nchan = 0.0005, 64
    hop = 4096 if quick else 8192
    nhops = 6
    nsamples = nhops * hop
    config = dict(dmmin=100, dmmax=200, chunk_length=hop * tsamp,
                  snr_threshold=6.5)
    with tempfile.TemporaryDirectory() as tmp:
        fnames = []
        for i in range(2):
            rng = np.random.default_rng(140 + i)
            arr = np.abs(rng.normal(0, 0.5, (nchan, nsamples))) + 20.0
            if i == 0:
                arr[:, (3 * nsamples) // 4] += 4.0
                arr = disperse_array(arr, 150.0, 1200., 200., tsamp)
            header = {"bandwidth": 200., "fbottom": 1200.,
                      "nchans": nchan, "nsamples": nsamples,
                      "tsamp": tsamp, "foff": 200. / nchan}
            path = os.path.join(tmp, f"survey{i}.fil")
            write_simulated_filterbank(path, arr, header,
                                       descending=True)
            fnames.append(path)

        single_dir = os.path.join(tmp, "single")
        t0 = time.time()
        for fname in fnames:
            search_by_chunks(fname, output_dir=single_dir,
                             make_plots=False, progress=False, **config)
        single_wall = time.time() - t0

        fleet_dir = os.path.join(tmp, "fleet")
        t0 = time.time()
        coordinator = FleetCoordinator(fleet_dir, lease_ttl_s=120.0,
                                       chunks_per_unit=1,
                                       probe_interval_s=0.5)
        server = start_obs_server(0, fleet=coordinator)
        url = f"http://127.0.0.1:{server.port}"
        coordinator.add_survey(fnames, **config)
        workers = [FleetWorker(url, http_port=None) for _ in range(2)]
        threads = [threading.Thread(target=w.run,
                                    kwargs={"max_idle_s": 120.0})
                   for w in workers]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600.0)
        fleet_wall = time.time() - t0
        progress = coordinator.progress_doc()
        server.close()
        coordinator.close()

        # identity: per-file ledger raw bytes + candidate npz member
        # bytes (the chaos-drill comparison rule — zip timestamps are
        # the only allowed whole-file difference)
        identical = progress["survey_done"]
        names = {os.path.basename(p) for d in (single_dir, fleet_dir)
                 for p in glob.glob(os.path.join(d, "progress_*.json"))
                 + glob.glob(os.path.join(d, "*.npz"))}
        for name in sorted(names):
            a_path = os.path.join(single_dir, name)
            b_path = os.path.join(fleet_dir, name)
            if not (os.path.exists(a_path) and os.path.exists(b_path)):
                identical = False
                log(f"config 14: {name} present in only one arm")
                continue
            if name.endswith(".json"):
                with open(a_path, "rb") as fa, open(b_path, "rb") as fb:
                    if fa.read() != fb.read():
                        identical = False
                        log(f"config 14: ledger bytes differ: {name}")
            else:
                with np.load(a_path, allow_pickle=False) as za, \
                        np.load(b_path, allow_pickle=False) as zb:
                    if set(za.files) != set(zb.files) or any(
                            za[k].tobytes() != zb[k].tobytes()
                            or za[k].dtype != zb[k].dtype
                            or za[k].shape != zb[k].shape
                            for k in za.files):
                        identical = False
                        log(f"config 14: candidate bytes differ: {name}")

    ratio = single_wall / fleet_wall if fleet_wall else 0.0
    emit({"config": 14, "metric": "2-worker fleet vs single-process "
          f"A/B, 2 files x {nchan}x{nsamples}, "
          f"{progress['chunks_total']} chunks over the /fleet/ wire "
          "protocol",
          "value": round(ratio, 4) if identical else 0.0,
          "unit": "x (single-process/fleet wall; 0 = identity or "
                  "completion failure)",
          "identical": identical,
          "survey_done": progress["survey_done"],
          "chunks_total": progress["chunks_total"],
          "chunks_done": progress["chunks_done"],
          "units": progress["units"],
          "lease_stats": progress["stats"],
          "units_per_worker": [w.units_done for w in workers],
          "single_wall_s": round(single_wall, 2),
          "fleet_wall_s": round(fleet_wall, 2)})


def config15(quick):
    """Packed low-bit vs host-unpack A/B on the streaming driver
    (ISSUE 11).  One on-disk 2-bit descending-band pulse file (the
    config-7 generator) streamed twice through ``stream_search``:

    * **host arm** — each chunk host-unpacked (the C++/numpy decoder)
      and shipped as float32, the pre-round-11 data path;
    * **packed arm** — each chunk shipped as the RAW packed bytes
      (:class:`~pulsarutils_tpu.io.lowbit.PackedFrames`): the bit
      unpack runs inside the search jit and the sweep accumulates in
      the exact integer dtype.

    ``value`` is the host/packed wall ratio — FORCED to 0.0, far past
    any tolerance, when any per-chunk table byte diverges between the
    arms or the measured ``putpu_bytes_uploaded_total`` ratio falls
    below 8x (a 2-bit file must upload 1/16th the float32 bytes; on a
    CPU runner with free "uploads" the wall ratio ~1 is expected — the
    bytes ratio is the production-link win this config gates).
    """
    import tempfile

    from pulsarutils_tpu.io.lowbit import PackedFrames
    from pulsarutils_tpu.io.sigproc import FilterbankReader
    from pulsarutils_tpu.obs import metrics as obs_metrics
    from pulsarutils_tpu.parallel.stream import stream_search

    ab = _load_tool("stream_budget_ab")
    nchan = 256 if not quick else 64
    hop = (1 << 15) if not quick else (1 << 12)
    nhops = 6 if not quick else 4
    nsamples = nhops * hop
    step = 2 * hop
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "lowbit.fil")
        ab.generate(path, nchan, nsamples, log, hop=hop,
                    margin=min(2048, hop // 4))
        reader = FilterbankReader(path)
        fb, bw = ab.FBOT, ab.FTOP - ab.FBOT
        starts = [s for s in range(0, nsamples, step)]
        host_chunks = [(s, reader.read_block(
            s, step, band_ascending=True).astype(np.float32))
            for s in starts]
        packed_chunks = [(s, PackedFrames.read(reader, s, step))
                         for s in starts]

        def arm(chunks):
            t0 = time.perf_counter()
            results, hits = stream_search(chunks, ab.DMMIN, ab.DMMAX,
                                          fb, bw, ab.TSAMP)
            return results, hits, time.perf_counter() - t0

        up = obs_metrics.counter("putpu_bytes_uploaded_total")
        arm(host_chunks)  # warm-up: compiles out of the timed region
        b0 = up.value
        res_h, hits_h, host_wall = arm(host_chunks)
        host_bytes = up.value - b0
        arm(packed_chunks)
        b0 = up.value
        res_p, hits_p, packed_wall = arm(packed_chunks)
        packed_bytes = up.value - b0

    identical = len(res_h) == len(res_p)
    if identical:
        for (i1, t1), (i2, t2) in zip(res_h, res_p):
            if i1 != i2 or t1.colnames != t2.colnames or any(
                    not np.array_equal(np.asarray(t1[c]),
                                       np.asarray(t2[c]))
                    for c in t1.colnames):
                identical = False
                log(f"config 15: chunk {i1} tables diverge")
                break
    bytes_ratio = host_bytes / packed_bytes if packed_bytes else 0.0
    ok = identical and bytes_ratio >= 8.0
    emit({"config": 15, "metric": "packed 2-bit vs host-unpack A/B on "
          f"the streaming driver, {nchan}x{nsamples}, "
          f"{len(starts)} chunks",
          "value": round(host_wall / packed_wall, 4) if ok else 0.0,
          "unit": "x (host-unpack/packed wall; 0 = identity or "
                  "bytes-ratio failure)",
          "tables_identical": identical,
          "bytes_uploaded": {"host": int(host_bytes),
                             "packed": int(packed_bytes),
                             "ratio": round(bytes_ratio, 2)},
          "host_wall_s": round(host_wall, 4),
          "packed_wall_s": round(packed_wall, 4),
          "hits": {"host": len(hits_h), "packed": len(hits_p)}})


def config16(quick):
    """Constrained-memory A/B (ISSUE 12): the chaos-drill survey
    searched twice through ``search_by_chunks`` —

    * **unconstrained arm** — the fault-free baseline;
    * **degraded arm** — a ``kind="oom"`` fault injected at the first
      chunk's dispatch (a real ``XlaRuntimeError``-shaped
      ``RESOURCE_EXHAUSTED``), forcing one degradation-ladder descent;
      every chunk from there on dispatches in split trial passes.

    ``value`` is the unconstrained/degraded wall ratio — FORCED to 0.0,
    far past any tolerance, when any candidate or ledger byte diverges
    between the arms, when no ladder descent actually fired, or when
    the degraded run's health verdict fails to recover to OK (the
    memory_pressure condition must decay on the clean chunks behind
    the injected one).
    """
    import shutil
    import tempfile

    drill = _load_tool("chaos_drill")
    from pulsarutils_tpu.faults.inject import FaultPlan, FaultSpec
    from pulsarutils_tpu.obs.health import HealthEngine

    base_dir = tempfile.mkdtemp(prefix="bench_oom_")
    try:
        path = os.path.join(base_dir, "survey.fil")
        drill.make_survey_file(path)
        from pulsarutils_tpu.pipeline.spectral_stats import get_bad_chans

        get_bad_chans(path)  # warm the pre-scan cache outside both arms
        # warm-up arm: compiles out of the timed region (both arms
        # reuse the same interior-chunk executable)
        drill.run_search(path, os.path.join(base_dir, "warm"))

        t0 = time.perf_counter()
        _, store = drill.run_search(path, os.path.join(base_dir, "clean"))
        clean_wall = time.perf_counter() - t0
        fingerprint = store.fingerprint
        baseline = drill.snapshot_outputs(os.path.join(base_dir, "clean"),
                                          fingerprint)

        plan = FaultPlan([FaultSpec(site="dispatch", kind="oom",
                                    chunks=(drill.NOISE_CHUNK,),
                                    times=1)])
        engine = HealthEngine()
        t0 = time.perf_counter()
        drill.run_search(path, os.path.join(base_dir, "degraded"),
                         plan=plan, health=engine)
        degraded_wall = time.perf_counter() - t0
        fresh = drill.snapshot_outputs(os.path.join(base_dir, "degraded"),
                                       fingerprint)
        diffs = drill.diff_outputs(baseline, fresh)
        descended = any(t["to"] in ("DEGRADED", "CRITICAL")
                        for t in engine.transitions)
        recovered = engine.verdict == "OK"
        ok = (not diffs and bool(plan.fired()) and descended
              and recovered)
        if diffs:
            log(f"config 16: degraded outputs diverge: {diffs}")
    finally:
        shutil.rmtree(base_dir, ignore_errors=True)
    emit({"config": 16, "metric": "constrained-memory A/B: injected "
          "RESOURCE_EXHAUSTED forces a degradation-ladder descent on a "
          f"{len(drill.CHUNKS)}-chunk survey",
          "value": round(clean_wall / degraded_wall, 4) if ok else 0.0,
          "unit": "x (unconstrained/degraded wall; 0 = byte divergence,"
                  " no descent, or health not recovered)",
          "byte_identical": not diffs,
          "oom_fired": plan.fired(),
          "ladder_descended": descended,
          "health_recovered": recovered,
          "clean_wall_s": round(clean_wall, 3),
          "degraded_wall_s": round(degraded_wall, 3)})


def config17(quick):
    """End-to-end periodicity A/B (ISSUE 13): a synthetic binary pulsar
    (known P, accel, DM) injected into a multi-chunk filterbank and
    searched by the FULL periodicity job — accumulate over the chunk
    stream, (DM, accel) trial sweep, harmonic sift, fold — once on the
    device path (``backend="jax"``: one batched jitted trial program)
    and once on the host reference (``backend="numpy"``).

    ``value`` is the host/device wall ratio — FORCED to 0.0, far past
    any tolerance, when the device arm's top candidate misses the
    injected (DM, P, accel) grid cell, or when the host and device
    candidate tables diverge (discrete fields cell-for-cell, scores to
    float tolerance — the repo's cross-path equivalence contract).
    """
    import shutil
    import tempfile

    from pulsarutils_tpu.io.sigproc import write_simulated_filterbank
    from pulsarutils_tpu.models.simulate import simulate_accel_pulsar_data
    from pulsarutils_tpu.periodicity.driver import periodicity_search
    from pulsarutils_tpu.pipeline.spectral_stats import get_bad_chans

    tsamp, nchan, nsamples = 0.0005, 32, 32768
    dm, f0, accel = 150.0, 60.0, 9.0e4
    arr, hdr = simulate_accel_pulsar_data(
        freq=f0, dm=dm, accel=accel, tsamp=tsamp, nsamples=nsamples,
        nchan=nchan, rng=17)

    base_dir = tempfile.mkdtemp(prefix="bench_period_")
    job = dict(dmmin=100, dmmax=200, accel_max=1.8e5, n_accel=9,
               sigma_threshold=8.0, chunk_length=8192 * tsamp,
               snr_threshold=8.0, progress=False)
    try:
        path = os.path.join(base_dir, "binary_psr.fil")
        write_simulated_filterbank(path, arr, hdr, descending=True)
        get_bad_chans(path)  # warm the pre-scan cache outside both arms
        # warm-up arm absorbs the device compiles out of the timed region
        periodicity_search(path, backend="jax",
                           output_dir=os.path.join(base_dir, "warm"),
                           **job)

        t0 = time.perf_counter()
        dev = periodicity_search(path, backend="jax",
                                 output_dir=os.path.join(base_dir, "dev"),
                                 **job)
        dev_wall = time.perf_counter() - t0
        t0 = time.perf_counter()
        host = periodicity_search(path, backend="numpy",
                                  output_dir=os.path.join(base_dir,
                                                          "host"),
                                  **job)
        host_wall = time.perf_counter() - t0

        acc = dev["accumulator"]
        true_bin = int(round(f0 * acc.nout * acc.tsamp))
        best = dev["candidates"][0] if dev["candidates"] else None
        cell_ok = (best is not None
                   and abs(best["dm"] - dm) < 5.0
                   and best["accel"] == accel
                   and abs(best["freq_bin"] - true_bin) <= 1)
        if not cell_ok:
            log(f"config 17: top candidate missed the injected cell: "
                f"{best}")
        tables_ok = len(dev["candidates"]) == len(host["candidates"])
        for cd, ch in zip(dev["candidates"], host["candidates"]):
            for k in ("dm_index", "accel_index", "freq_bin", "nharm"):
                if cd[k] != ch[k]:
                    tables_ok = False
                    log(f"config 17: host/device diverge on {k}: "
                        f"{cd[k]} != {ch[k]}")
            if abs(cd["sigma"] - ch["sigma"]) > 5e-3 * abs(ch["sigma"]):
                tables_ok = False
                log("config 17: host/device sigma diverge: "
                    f"{cd['sigma']} != {ch['sigma']}")
        ok = cell_ok and tables_ok
    finally:
        shutil.rmtree(base_dir, ignore_errors=True)
    emit({"config": 17, "metric": "periodicity E2E A/B: accelerated "
          f"binary pulsar (DM {dm}, f0 {f0} Hz, accel {accel:g} m/s^2) "
          "through the full accumulate+accel-search+sift+fold job",
          "value": round(host_wall / dev_wall, 4) if ok else 0.0,
          "unit": "x (host/device wall; 0 = missed injected cell or "
                  "host/device table divergence)",
          "recovered_cell": bool(cell_ok),
          "tables_identical": bool(tables_ok),
          "n_candidates": len(dev["candidates"] or []),
          "device_wall_s": round(dev_wall, 3),
          "host_wall_s": round(host_wall, 3)})


def config18(quick):
    """Distributed-observability A/B (ISSUE 14): the same 2-file survey
    run through a 2-worker fleet twice —

    * **off arm** — the plain fleet (no tracing, no time-series, no
      SLO engine), the pre-ISSUE-14 path;
    * **on arm** — the whole layer armed: coordinator span tracer +
      fleet trace collector, per-worker tracers draining spans over
      the ``complete`` wire, per-worker time-series samplers scraped
      by the coordinator sweep, and the default SLO set evaluating
      burn rates on every sample.

    ``value`` is the off/on wall ratio (the layer's measured overhead;
    ~1.0 expected) — FORCED to 0.0, far past any tolerance, when any
    candidate/ledger byte diverges between the arms, when the merged
    trace is missing spans from any worker that completed units (or
    the coordinator), or when zero SLO evaluations ran.
    """
    import glob
    import tempfile
    import threading

    from pulsarutils_tpu.fleet.coordinator import FleetCoordinator
    from pulsarutils_tpu.fleet.worker import FleetWorker
    from pulsarutils_tpu.io.sigproc import write_simulated_filterbank
    from pulsarutils_tpu.models.simulate import disperse_array
    from pulsarutils_tpu.obs import trace as obs_trace
    from pulsarutils_tpu.obs.collector import TraceCollector
    from pulsarutils_tpu.obs.server import start_obs_server
    from pulsarutils_tpu.obs.slo import SLOEngine
    from pulsarutils_tpu.obs.timeseries import TimeSeriesSampler

    tsamp, nchan = 0.0005, 64
    hop = 4096 if quick else 8192
    nhops = 6
    nsamples = nhops * hop
    config = dict(dmmin=100, dmmax=200, chunk_length=hop * tsamp,
                  snr_threshold=6.5)
    with tempfile.TemporaryDirectory() as tmp:
        fnames = []
        for i in range(2):
            rng = np.random.default_rng(180 + i)
            arr = np.abs(rng.normal(0, 0.5, (nchan, nsamples))) + 20.0
            if i == 0:
                arr[:, (3 * nsamples) // 4] += 4.0
                arr = disperse_array(arr, 150.0, 1200., 200., tsamp)
            header = {"bandwidth": 200., "fbottom": 1200.,
                      "nchans": nchan, "nsamples": nsamples,
                      "tsamp": tsamp, "foff": 200. / nchan}
            path = os.path.join(tmp, f"survey{i}.fil")
            write_simulated_filterbank(path, arr, header,
                                       descending=True)
            fnames.append(path)

        def fleet_run(outdir, *, armed):
            collector = tracer = sampler = engine = None
            if armed:
                collector = TraceCollector()
                tracer = obs_trace.start_tracing()
                engine = SLOEngine()
                sampler = TimeSeriesSampler(
                    interval_s=0.2,
                    on_sample=lambda _p: engine.evaluate(sampler))
                sampler.start()
            t0 = time.time()
            coordinator = FleetCoordinator(
                outdir, lease_ttl_s=120.0, chunks_per_unit=1,
                probe_interval_s=0.3, collector=collector)
            server = start_obs_server(0, fleet=coordinator,
                                      timeseries=sampler, slo=engine)
            url = f"http://127.0.0.1:{server.port}"
            coordinator.add_survey(fnames, **config)
            workers = [FleetWorker(url, http_port=0 if armed else None,
                                   trace=armed,
                                   history_interval_s=0.2 if armed
                                   else None)
                       for _ in range(2)]
            threads = [threading.Thread(target=w.run,
                                        kwargs={"max_idle_s": 120.0})
                       for w in workers]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=600.0)
            wall = time.time() - t0
            progress = coordinator.progress_doc()
            summary = coordinator.summary()
            server.close()
            coordinator.close()
            merged = None
            if armed:
                sampler.stop()
                engine.evaluate(sampler)
                engine.footer(log=__import__("logging").getLogger(
                    "pulsarutils_tpu"))
                obs_trace.stop_tracing()
                collector.ingest_tracer("coordinator", tracer)
                merged = collector.to_chrome()
            return dict(wall=wall, progress=progress, summary=summary,
                        workers=workers, merged=merged, engine=engine)

        off = fleet_run(os.path.join(tmp, "off"), armed=False)
        on = fleet_run(os.path.join(tmp, "on"), armed=True)

        # identity: per-file ledger + candidate npz bytes between arms
        # (the config-14 comparison rule)
        identical = off["progress"]["survey_done"] \
            and on["progress"]["survey_done"]
        names = {os.path.basename(p)
                 for d in ("off", "on")
                 for p in glob.glob(os.path.join(tmp, d,
                                                 "progress_*.json"))
                 + glob.glob(os.path.join(tmp, d, "*.npz"))}
        for name in sorted(names):
            a_path = os.path.join(tmp, "off", name)
            b_path = os.path.join(tmp, "on", name)
            if not (os.path.exists(a_path) and os.path.exists(b_path)):
                identical = False
                log(f"config 18: {name} present in only one arm")
                continue
            if name.endswith(".json"):
                with open(a_path, "rb") as fa, open(b_path, "rb") as fb:
                    if fa.read() != fb.read():
                        identical = False
                        log(f"config 18: ledger bytes differ: {name}")
            else:
                with np.load(a_path, allow_pickle=False) as za, \
                        np.load(b_path, allow_pickle=False) as zb:
                    if set(za.files) != set(zb.files) or any(
                            za[k].tobytes() != zb[k].tobytes()
                            for k in za.files):
                        identical = False
                        log(f"config 18: candidate bytes differ: {name}")

        # the merged trace must hold spans from the coordinator AND
        # every worker that completed units, sharing trace ids
        merged = on["merged"]
        span_pids = {}
        pid_names = {}
        for ev in merged["traceEvents"]:
            if ev.get("ph") == "M" and ev.get("name") == "process_name":
                pid_names[ev["pid"]] = ev["args"]["name"]
            if ev.get("ph") in ("X", "b") \
                    and ev.get("name") != "clock_sync":
                span_pids.setdefault(ev["pid"], 0)
                span_pids[ev["pid"]] += 1
        traced = {pid_names.get(pid) for pid in span_pids}
        needed = {"coordinator"} | {
            f"worker {w.worker_id}" for w in on["workers"]
            if w.units_done > 0}
        trace_ok = needed <= traced
        if not trace_ok:
            log(f"config 18: merged trace missing spans: needed "
                f"{sorted(needed)}, traced {sorted(t for t in traced if t)}")
        evaluations = on["engine"].alerts_doc()["evaluations"]
        slo_ok = evaluations > 0
        history = on["summary"].get("history") or {}
        ok = identical and trace_ok and slo_ok
    emit({"config": 18, "metric": "distributed observability A/B: "
          "2-worker fleet with tracing+timeseries+SLO armed vs off, "
          f"2 files x {nchan}x{nsamples}",
          "value": round(off["wall"] / on["wall"], 4) if ok else 0.0,
          "unit": "x (off/on wall; 0 = byte divergence, missing "
                  "worker spans, or zero SLO evaluations)",
          "identical": identical,
          "trace_ok": trace_ok,
          "traced_processes": sorted(t for t in traced if t),
          "slo_evaluations": evaluations,
          "alerts_fired": on["engine"].alerts_doc()
          ["alerts_fired_total"],
          "workers_with_history": sorted(history),
          "units_per_worker": [w.units_done for w in on["workers"]],
          "off_wall_s": round(off["wall"], 2),
          "on_wall_s": round(on["wall"], 2)})


def config19(quick):
    """Killed-coordinator restart A/B (ISSUE 15): the same one-file
    survey run through a 1-worker fleet twice —

    * **uninterrupted arm** — coordinator up for the whole survey;
    * **killed arm** — the worker completes ONE unit, a second lease
      is left stranded in flight, and the coordinator is killed (its
      in-memory state dropped; only the per-event-flushed
      ``fleet_journal.jsonl`` and the ledgers survive — exactly what a
      SIGKILL leaves).  ``FleetCoordinator.recover()`` replays the
      journal, re-derives outstanding units from the ledgers, re-steals
      the stranded lease under a bumped fencing epoch, and a fresh
      worker finishes.

    ``value`` is the uninterrupted/killed-and-recovered wall ratio
    (restart overhead; ~1.0 expected) — FORCED to 0.0, far past any
    tolerance, when any per-file ledger or candidate byte diverges
    between the arms, when either survey fails to finish, or when the
    recovery did not actually recover (no stranded lease re-stolen, no
    epoch bump): "the coordinator died" must be a restart, never a
    different answer.
    """
    import glob
    import tempfile

    from pulsarutils_tpu.fleet.coordinator import FleetCoordinator
    from pulsarutils_tpu.fleet.worker import FleetWorker
    from pulsarutils_tpu.io.sigproc import write_simulated_filterbank
    from pulsarutils_tpu.models.simulate import disperse_array
    from pulsarutils_tpu.obs.server import start_obs_server

    tsamp, nchan = 0.0005, 64
    hop = 4096 if quick else 8192
    nhops = 4
    nsamples = nhops * hop
    config = dict(dmmin=100, dmmax=200, chunk_length=hop * tsamp,
                  snr_threshold=6.5)
    with tempfile.TemporaryDirectory() as tmp:
        rng = np.random.default_rng(190)
        arr = np.abs(rng.normal(0, 0.5, (nchan, nsamples))) + 20.0
        arr[:, (3 * nsamples) // 4] += 4.0
        arr = disperse_array(arr, 150.0, 1200., 200., tsamp)
        header = {"bandwidth": 200., "fbottom": 1200., "nchans": nchan,
                  "nsamples": nsamples, "tsamp": tsamp,
                  "foff": 200. / nchan}
        fname = os.path.join(tmp, "survey.fil")
        write_simulated_filterbank(fname, arr, header, descending=True)

        def run_fleet(outdir, kill_mid_survey):
            t0 = time.time()
            coordinator = FleetCoordinator(outdir, lease_ttl_s=120.0,
                                           chunks_per_unit=1,
                                           auto_sweep=False)
            server = start_obs_server(0, fleet=coordinator)
            url = f"http://127.0.0.1:{server.port}"
            coordinator.add_survey([fname], **config)
            recovery = {"stranded": 0, "epoch_bumped": False,
                        "units_before_kill": None}
            if kill_mid_survey:
                worker = FleetWorker(url, http_port=None)
                orig = worker._run_unit

                def drain_after_first(lease):
                    result = orig(lease)
                    worker.drain()
                    return result

                worker._run_unit = drain_after_first
                worker.run()
                recovery["units_before_kill"] = worker.units_done
                ghost = coordinator.register({})["worker"]
                stranded = coordinator.lease(
                    {"worker": ghost, "max_units": 1})["leases"]
                recovery["stranded"] = len(stranded)
                server.close()
                coordinator.close()
                del coordinator          # the kill
                coordinator = FleetCoordinator.recover(
                    outdir, lease_ttl_s=120.0, chunks_per_unit=1,
                    auto_sweep=False)
                if stranded:
                    unit = coordinator._units.get(stranded[0]["unit"])
                    recovery["epoch_bumped"] = (
                        unit is not None
                        and unit.epoch > stranded[0]["epoch"])
                server = start_obs_server(0, fleet=coordinator)
                url = f"http://127.0.0.1:{server.port}"
            finisher = FleetWorker(url, http_port=None)
            finisher.run(max_idle_s=120.0)
            done = coordinator.survey_done
            stats = coordinator.progress_doc()["stats"]
            server.close()
            coordinator.close()
            return {"wall": time.time() - t0, "done": done,
                    "stats": stats, **recovery}

        base = run_fleet(os.path.join(tmp, "uninterrupted"),
                         kill_mid_survey=False)
        killed = run_fleet(os.path.join(tmp, "killed"),
                           kill_mid_survey=True)

        # identity: ledger raw bytes + candidate npz member bytes (the
        # chaos-drill rule; fence/journal sidecars are control-plane
        # state, not science output)
        identical = base["done"] and killed["done"]
        names = {os.path.basename(p)
                 for d in ("uninterrupted", "killed")
                 for p in glob.glob(os.path.join(tmp, d,
                                                 "progress_*.json"))
                 + glob.glob(os.path.join(tmp, d, "*.npz"))}
        for name in sorted(names):
            a_path = os.path.join(tmp, "uninterrupted", name)
            b_path = os.path.join(tmp, "killed", name)
            if not (os.path.exists(a_path) and os.path.exists(b_path)):
                identical = False
                log(f"config 19: {name} present in only one arm")
                continue
            if name.endswith(".json"):
                with open(a_path, "rb") as fa, open(b_path, "rb") as fb:
                    if fa.read() != fb.read():
                        identical = False
                        log(f"config 19: ledger bytes differ: {name}")
            else:
                with np.load(a_path, allow_pickle=False) as za, \
                        np.load(b_path, allow_pickle=False) as zb:
                    if set(za.files) != set(zb.files) or any(
                            za[k].tobytes() != zb[k].tobytes()
                            or za[k].dtype != zb[k].dtype
                            or za[k].shape != zb[k].shape
                            for k in za.files):
                        identical = False
                        log(f"config 19: candidate bytes differ: {name}")

    recovered = bool(killed["stranded"]) and killed["epoch_bumped"] \
        and killed["units_before_kill"] == 1
    ok = identical and recovered
    ratio = base["wall"] / killed["wall"] if killed["wall"] else 0.0
    emit({"config": 19, "metric": "killed-coordinator restart A/B, "
          f"{nchan}x{nsamples}, journal replay + ledger re-derive + "
          "epoch-fenced re-steal over the /fleet/ wire",
          "value": round(ratio, 4) if ok else 0.0,
          "unit": "x (uninterrupted/recovered wall; 0 = identity or "
                  "recovery failure)",
          "identical": identical,
          "surveys_done": [base["done"], killed["done"]],
          "units_before_kill": killed["units_before_kill"],
          "stranded_leases": killed["stranded"],
          "epoch_bumped": killed["epoch_bumped"],
          "killed_stats": killed["stats"],
          "uninterrupted_wall_s": round(base["wall"], 2),
          "recovered_wall_s": round(killed["wall"], 2)})


def config20(quick):
    """Acceleration-backend A/B (ISSUE 16): the same synthetic binary
    pulsar — nonzero jerk, injected at a known (DM row, Fourier bin,
    accel trial, jerk trial) cell — searched over the IDENTICAL
    (accel, jerk) trial grid by both trial formulations on the jit
    path:

    * ``time_stretch`` — PR 12's stretch-resample + one rfft per trial;
    * ``fdas`` — one rfft per DM + batched z/w-response correlation
      (ISSUE 16's tentpole).

    ``value`` is the time_stretch/fdas steady-state wall ratio at
    matched trial counts (> 1.0 means the correlation formulation
    wins) — FORCED to 0.0, far past any tolerance, when either
    backend's top candidate misses the injected cell or the two
    tables fail the cross-backend equivalence harness
    (:func:`~pulsarutils_tpu.tuning.autotune.accel_tables_match`:
    discrete fields exact, sigma within the documented scalloping
    tolerance).  The injection sits at ~0.35x Nyquist with the search
    band cut at ``1.25 f0``: high enough that the 45-trial grid is
    non-degenerate at ``f0``, low enough that stretch scalloping stays
    a few percent.
    """
    import jax.numpy as jnp

    from pulsarutils_tpu.periodicity.accel import accel_search
    from pulsarutils_tpu.periodicity.fdas import fdas_search
    from pulsarutils_tpu.tuning.autotune import (accel_tables_match,
                                                 synthetic_accel_plane)

    tsamp, nsamples, ndm = 5e-4, 16384, 8
    accels = np.linspace(-2e5, 2e5, 9)
    jerks = np.linspace(-5e4, 5e4, 5)
    inj_accel, inj_jerk = 6, 3  # grid indices of the injected trial
    inj_dm = ndm // 3
    k0 = int(round(0.175 * nsamples))  # the injection Fourier bin
    f0 = k0 / (nsamples * tsamp)
    plane = synthetic_accel_plane(ndm, nsamples, tsamp,
                                  float(accels[inj_accel]),
                                  jerk=float(jerks[inj_jerk]), seed=20)
    kw = dict(jerks=jerks, max_harmonics=1, fmax=1.25 * f0, topk=8,
              xp=jnp)

    # warm-up arm per backend absorbs the compiles out of the timed
    # region; each call's host-side result table is the dispatch fence
    t_stretch = accel_search(plane, tsamp, accels, **kw)
    t_fdas = fdas_search(plane, tsamp, accels, **kw)

    reps = 3 if quick else 5

    def steady_wall(fn):
        walls = []
        for _ in range(reps):
            t0 = time.perf_counter()
            fn(plane, tsamp, accels, **kw)
            walls.append(time.perf_counter() - t0)
        walls.sort()
        return walls[len(walls) // 2]

    stretch_wall = steady_wall(accel_search)
    fdas_wall = steady_wall(fdas_search)

    def top_ok(tbl, name):
        got = (int(tbl["dm_index"][0]), int(tbl["accel_index"][0]),
               int(tbl["jerk_index"][0]), int(tbl["freq_bin"][0]))
        want = (inj_dm, inj_accel, inj_jerk, k0)
        if got[:3] != want[:3] or abs(got[3] - want[3]) > 1:
            log(f"config 20: {name} top candidate {got} missed the "
                f"injected cell {want}")
            return False
        return True

    cell_ok = (top_ok(t_stretch, "time_stretch")
               and top_ok(t_fdas, "fdas"))
    tables_ok = accel_tables_match(t_stretch, t_fdas)
    if not tables_ok:
        log("config 20: backends fail the cross-backend table harness")
    ok = cell_ok and tables_ok
    emit({"config": 20, "metric": "accel-backend A/B: jerked binary "
          f"pulsar (f0 {f0:.1f} Hz, accel {accels[inj_accel]:g} m/s^2, "
          f"jerk {jerks[inj_jerk]:g} m/s^3) over {len(accels)} accel x "
          f"{len(jerks)} jerk trials, time_stretch vs fdas",
          "value": round(stretch_wall / fdas_wall, 4) if ok else 0.0,
          "unit": "x (time_stretch/fdas wall; 0 = missed injected cell "
                  "or cross-backend table divergence)",
          "recovered_cell": bool(cell_ok),
          "tables_match": bool(tables_ok),
          "time_stretch_wall_s": round(stretch_wall, 3),
          "fdas_wall_s": round(fdas_wall, 3)})


def config21(quick):
    """Precision-policy A/B (ISSUE 17): ``bf16_operand_f32_accum`` —
    bfloat16 operands feeding a float32 accumulator, the
    bandwidth-bound-sweep strategy — against the plain-f32 default on
    the SAME jit gather sweep, at a geometry past the float32
    exact-integer domain (quick: > 2^24 summed plane elements; full:
    the SERIES itself beyond 2^24 samples, where
    ``precision.exactness_domain`` reports peak-index exactness lost —
    the regime the policy engine exists for).

    ``value`` is the f32/bf16 steady-state wall ratio (> 1.0 means the
    half-width operands pay for themselves) — FORCED to 0.0, far past
    any tolerance, when either

    * the two arms' best candidates diverge in any discrete field
      (DM row, rebin window, peak sample) or miss the injected trial, or
    * the bf16 arm's dedispersed profile at the injected trial violates
      the strategy's documented error bound
      (``Strategy.error_bound(nchan)`` relative to the per-sample
      absolute operand sum) against a float64 oracle.

    Same contract the autotuner's exact-hit-match harness enforces
    before ever caching a (kernel, policy) winner — here re-checked
    end-to-end through ``dedispersion_search`` with an explicit policy.
    """
    from pulsarutils_tpu.ops.search import (_offsets_for,
                                            dedispersion_search)
    from pulsarutils_tpu.precision import STRATEGIES, exactness_domain
    from pulsarutils_tpu.tuning.autotune import synthetic_chunk

    if quick:
        nchan, nsamples, ndm = 16, (1 << 20) + 4096, 8
    else:
        nchan, nsamples, ndm = 8, (1 << 24) + (1 << 16), 4
    geom = (1400.0, 400.0, 5e-4)  # start_freq, bandwidth, sample_time
    dms = np.linspace(40.0, 80.0, ndm)
    offsets = _offsets_for(dms, nchan, *geom, nsamples)
    inj = ndm // 2
    data = synthetic_chunk(nchan, nsamples, offsets[inj], seed=21)
    dom = exactness_domain(nchan, nsamples)
    kw = dict(backend="jax", trial_dms=dms, kernel="gather")

    def run(policy, capture=False):
        return dedispersion_search(data, None, None, *geom,
                                   capture_plane=capture,
                                   precision=policy, **kw)

    # warm-up arm per policy absorbs the compiles; the bf16 arm's plane
    # is captured ONCE here for the oracle bound check (the timed calls
    # never capture — plane readback is not part of the A/B)
    t_f32 = run("f32")
    t_bf16, plane_bf16 = run("bf16_operand_f32_accum", capture=True)

    reps = 3 if quick else 5

    def steady_wall(policy):
        walls = []
        for _ in range(reps):
            t0 = time.perf_counter()
            run(policy)
            walls.append(time.perf_counter() - t0)
        walls.sort()
        return walls[len(walls) // 2]

    f32_wall = steady_wall("f32")
    bf16_wall = steady_wall("bf16_operand_f32_accum")

    def best(tbl):
        i = int(np.argmax(np.asarray(tbl["snr"])))
        return (i, int(np.asarray(tbl["rebin"])[i]),
                int(np.asarray(tbl["peak"])[i]))

    b32, b16 = best(t_f32), best(t_bf16)
    cell_ok = b32 == b16 and b32[0] == inj
    if not cell_ok:
        log(f"config 21: best candidates diverged or missed the "
            f"injected trial {inj}: f32={b32} bf16={b16}")

    # float64 oracle for the injected trial's dedispersed profile,
    # channel-at-a-time (the full-preset plane is ~0.5 GB in f64 —
    # never materialise more than one channel row):
    # out[t] = sum_c data[c, (t + off[c]) mod T]  ==  sum_c roll(row, -off)
    prof64 = np.zeros(nsamples, dtype=np.float64)
    abs64 = np.zeros(nsamples, dtype=np.float64)
    for c in range(nchan):
        rolled = np.roll(data[c].astype(np.float64),
                         -int(offsets[inj, c]))
        prof64 += rolled
        abs64 += np.abs(rolled)
    bound = STRATEGIES["bf16_operand_f32_accum"].error_bound(nchan)
    got = np.asarray(plane_bf16[inj], dtype=np.float64)
    excess = np.abs(got - prof64) - (bound * abs64 + 1e-6)
    bound_ok = bool((excess <= 0.0).all())
    if not bound_ok:
        log(f"config 21: bf16 plane violates the documented error bound "
            f"({bound:.3e} rel) by up to {float(excess.max()):.3e}")

    ok = cell_ok and bound_ok
    emit({"config": 21, "metric": "precision-policy A/B: bf16 operands "
          f"+ f32 accumulation vs plain f32, {nchan}x{nsamples} gather "
          f"sweep over {ndm} trials (> 2^24 summed elements"
          + ("" if dom.peak_index_exact
             else ", peak-index exactness lost") + ")",
          "value": round(f32_wall / bf16_wall, 4) if ok else 0.0,
          "unit": "x (f32/bf16 wall; 0 = discrete divergence or "
                  "error-bound violation)",
          "best_match": bool(cell_ok),
          "bound_ok": bool(bound_ok),
          "error_bound_rel": bound,
          "max_bound_excess": float(excess.max()),
          "peak_index_exact": bool(dom.peak_index_exact),
          "f32_wall_s": round(f32_wall, 3),
          "bf16_wall_s": round(bf16_wall, 3)})


def config22(quick):
    """Candidate-lifecycle A/B (ISSUE 18): the same multi-hit survey
    run through ``search_by_chunks`` twice —

    * **off arm** — the plain driver (no lineage, no push), the
      pre-ISSUE-18 path;
    * **on arm** — lineage recording armed (per-candidate docs + the
      stage/latency histograms) and alert push fanning every detection
      out to a local in-process webhook sink, plus one subscriber whose
      ``min_snr`` filter excludes everything (the negative control).

    ``value`` is the off/on wall ratio (the layer's measured overhead;
    ~1.0 expected) — FORCED to 0.0, far past any tolerance, when any
    candidate/ledger byte diverges between the arms, when any persisted
    hit is missing its lineage doc (or its stage offsets are not
    monotone), when the sink did not receive every detection, or when
    the filtered-out subscriber received anything at all.
    """
    import glob
    import http.server
    import tempfile
    import threading

    from pulsarutils_tpu.io.sigproc import write_simulated_filterbank
    from pulsarutils_tpu.models.simulate import disperse_array
    from pulsarutils_tpu.pipeline.search_pipeline import search_by_chunks

    tsamp, nchan = 0.0005, 64
    hop = 4096 if quick else 8192
    nhops = 6
    nsamples = nhops * hop
    config = dict(dmmin=100, dmmax=200, backend="jax",
                  chunk_length=hop * tsamp, snr_threshold=6.5,
                  make_plots=False, progress=False, resume=True)

    class Sink:
        def __init__(self):
            received = self.received = []

            class Handler(http.server.BaseHTTPRequestHandler):
                def do_POST(self):
                    n = int(self.headers.get("Content-Length") or 0)
                    received.append(json.loads(self.rfile.read(n)))
                    self.send_response(200)
                    self.send_header("Content-Length", "2")
                    self.end_headers()
                    self.wfile.write(b"{}")

                def log_message(self, *a):
                    pass

            self.httpd = http.server.ThreadingHTTPServer(
                ("127.0.0.1", 0), Handler)
            self.httpd.daemon_threads = True
            threading.Thread(target=self.httpd.serve_forever,
                             daemon=True).start()
            self.url = (f"http://127.0.0.1:"
                        f"{self.httpd.server_address[1]}/hook")

        def close(self):
            self.httpd.shutdown()
            self.httpd.server_close()

    with tempfile.TemporaryDirectory() as tmp:
        rng = np.random.default_rng(220)
        arr = np.abs(rng.normal(0, 0.5, (nchan, nsamples))) + 20.0
        # one pulse per interior hop: a MULTI-hit survey, so the sink
        # count and per-hit doc checks exercise more than one candidate
        for h in range(1, nhops - 1):
            arr[:, h * hop + hop // 2] += 4.0
        arr = disperse_array(arr, 150.0, 1200., 200., tsamp)
        header = {"bandwidth": 200., "fbottom": 1200., "nchans": nchan,
                  "nsamples": nsamples, "tsamp": tsamp,
                  "foff": 200. / nchan}
        fname = os.path.join(tmp, "survey.fil")
        write_simulated_filterbank(fname, arr, header, descending=True)

        sink, control = Sink(), Sink()
        try:
            t0 = time.time()
            hits_off, _store = search_by_chunks(
                fname, output_dir=os.path.join(tmp, "off"), **config)
            off_wall = time.time() - t0

            t0 = time.time()
            hits_on, _store = search_by_chunks(
                fname, output_dir=os.path.join(tmp, "on"),
                lineage=True,
                push=[sink.url,
                      {"url": control.url, "name": "control",
                       "min_snr": 1e9}],
                **config)
            on_wall = time.time() - t0
            # the driver-owned broker is closed (drained) at the
            # driver's tail, so both sinks' lists are settled here
        finally:
            sink.close()
            control.close()

        # identity: ledger + candidate npz bytes between arms
        # (lineage docs are EXTRA files beside the pair, excluded by
        # these globs on purpose — the pre-PR artifact set must match)
        identical = True
        names = {os.path.basename(p)
                 for d in ("off", "on")
                 for p in glob.glob(os.path.join(tmp, d,
                                                 "progress_*.json"))
                 + glob.glob(os.path.join(tmp, d, "*.npz"))}
        for name in sorted(names):
            a_path = os.path.join(tmp, "off", name)
            b_path = os.path.join(tmp, "on", name)
            if not (os.path.exists(a_path) and os.path.exists(b_path)):
                identical = False
                log(f"config 22: {name} present in only one arm")
                continue
            if name.endswith(".json"):
                with open(a_path, "rb") as fa, open(b_path, "rb") as fb:
                    if fa.read() != fb.read():
                        identical = False
                        log(f"config 22: ledger bytes differ: {name}")
            else:
                with np.load(a_path, allow_pickle=False) as za, \
                        np.load(b_path, allow_pickle=False) as zb:
                    if set(za.files) != set(zb.files) or any(
                            za[k].tobytes() != zb[k].tobytes()
                            for k in za.files):
                        identical = False
                        log(f"config 22: candidate bytes differ: {name}")

        # every persisted hit carries a lineage doc with monotone stages
        docs_ok = len(hits_on) >= 2
        if not docs_ok:
            log(f"config 22: expected a multi-hit survey, got "
                f"{len(hits_on)} hit(s)")
        for istart, iend, _info, _tab in hits_on:
            matches = glob.glob(os.path.join(
                tmp, "on", f"*_{istart}-{iend}.lineage.json"))
            if len(matches) != 1:
                docs_ok = False
                log(f"config 22: hit {istart}-{iend} has no lineage doc")
                continue
            with open(matches[0]) as f:
                doc = json.load(f)
            order = [doc["stages"].get(s) for s in
                     ("read", "dispatch", "ready", "sift", "persist")]
            if None in order or order != sorted(order):
                docs_ok = False
                log(f"config 22: non-monotone stages for hit "
                    f"{istart}-{iend}: {doc['stages']}")

        delivered_ok = (sorted(a["chunk"] for a in sink.received)
                        == sorted(h[0] for h in hits_on))
        if not delivered_ok:
            log(f"config 22: sink received chunks "
                f"{sorted(a.get('chunk') for a in sink.received)} vs "
                f"hits {sorted(h[0] for h in hits_on)}")
        control_ok = not control.received
        if not control_ok:
            log(f"config 22: the filtered-out subscriber received "
                f"{len(control.received)} alert(s) — filter violated")

        ok = identical and docs_ok and delivered_ok and control_ok
    emit({"config": 22, "metric": "candidate-lifecycle A/B: lineage + "
          "alert push armed vs off over a multi-hit survey "
          f"({nchan}x{nsamples}, in-process webhook sink + filtered "
          "control subscriber)",
          "value": round(off_wall / on_wall, 4) if ok else 0.0,
          "unit": "x (off/on wall; 0 = byte divergence, missing "
                  "lineage docs, or a filter violation)",
          "identical": identical,
          "lineage_docs_ok": bool(docs_ok),
          "delivered_ok": bool(delivered_ok),
          "control_clean": bool(control_ok),
          "hits": len(hits_on),
          "alerts_delivered": len(sink.received),
          "off_wall_s": round(off_wall, 2),
          "on_wall_s": round(on_wall, 2)})


def config23(quick):
    """Live-ingest A/B (ISSUE 19): the same survey searched twice —

    * **file arm** — ``stream_search`` over chunks sliced straight off
      the disk block (the classic path);
    * **feed arm** — the block packetized into the PUTP wire format,
      streamed over a localhost TCP socket into
      :class:`~pulsarutils_tpu.ingest.ChunkAssembler`, and searched
      from the assembler's live chunk iterator while the feeder is
      still sending.

    ``value`` is the file/feed wall ratio (the frontend's measured
    overhead; ~1.0 expected — socket transfer and assembly overlap the
    search) — FORCED to 0.0, far past any tolerance, when any
    per-chunk result table byte-diverges between the arms, the hit
    lists differ, any packet arrives damaged, or the ingest ledger
    ends with gap-filled/journaled/unaccounted samples: a lossless
    local feed must be byte-identical to the disk search.
    """
    import tempfile
    import threading

    from pulsarutils_tpu.ingest import (ChunkAssembler, TCPSource,
                                        feed_tcp)
    from pulsarutils_tpu.io.packets import packetize_array
    from pulsarutils_tpu.io.sigproc import (FilterbankReader,
                                            write_simulated_filterbank)
    from pulsarutils_tpu.models.simulate import disperse_array
    from pulsarutils_tpu.parallel.stream import stream_search

    tsamp, nchan = 0.0005, 64
    step = 4096 if quick else 8192
    nchunks = 4
    nsamples = nchunks * step
    search_args = (100.0, 200.0, 1200.0, 200.0, tsamp)
    search_kw = dict(backend="jax", kernel="auto", snr_threshold=6.5)

    with tempfile.TemporaryDirectory() as tmp:
        rng = np.random.default_rng(230)
        arr = np.abs(rng.normal(0, 0.5, (nchan, nsamples))) + 20.0
        # one pulse per interior chunk: both arms must agree on a
        # multi-hit list, not just on noise tables
        for h in range(1, nchunks - 1):
            arr[:, h * step + step // 2] += 4.0
        arr = disperse_array(arr, 150.0, 1200., 200., tsamp)
        header = {"bandwidth": 200., "fbottom": 1200., "nchans": nchan,
                  "nsamples": nsamples, "tsamp": tsamp,
                  "foff": 200. / nchan}
        fname = os.path.join(tmp, "survey.fil")
        write_simulated_filterbank(fname, arr, header, descending=True)

        reader = FilterbankReader(fname)
        # the disk arm reads search-ready ascending chunks; the feed
        # arm ships raw file-order frames and relies on the assembler
        # to deliver the same ascending orientation
        wire = reader.read_block(0, nsamples).astype(np.float32)
        block = reader.read_block(
            0, nsamples, band_ascending=True).astype(np.float32)
        file_chunks = [(s, np.ascontiguousarray(block[:, s:s + step]))
                       for s in range(0, nsamples, step)]

        # warm the jit cache off the clock: both timed arms then run
        # against the same compiled executable
        stream_search(file_chunks, *search_args, **search_kw)

        t0 = time.time()
        res_file, hits_file = stream_search(file_chunks, *search_args,
                                            **search_kw)
        file_wall = time.time() - t0

        encoded = packetize_array(
            wire, samples_per_packet=256,
            band_descending=reader.band_descending)
        asm = ChunkAssembler(nchan=nchan, step=step,
                             band_descending=reader.band_descending,
                             policy="sanitize", shed=nchunks + 1,
                             wait_poll_s=0.05)
        t0 = time.time()
        # max_reconnects=0: the reader drains the single feed
        # connection, then exits + flushes the moment it closes — a
        # deterministic end-of-feed, no idle-timeout wait on the clock
        with TCPSource(asm, port=0, max_reconnects=0) as src:
            feeder = threading.Thread(
                target=feed_tcp, args=(src.host, src.port, encoded),
                daemon=True)
            feeder.start()
            res_feed, hits_feed = stream_search(asm.chunks(),
                                                *search_args,
                                                **search_kw)
            feeder.join(timeout=60)
            src.wait(timeout_s=60)
        feed_wall = time.time() - t0

    identical = len(res_file) == len(res_feed)
    if not identical:
        log(f"config 23: chunk counts differ: {len(res_file)} file "
            f"vs {len(res_feed)} feed")
    for (sa, ta), (sb, tb) in zip(res_file, res_feed):
        if sa != sb:
            identical = False
            log(f"config 23: chunk starts differ: {sa} vs {sb}")
            continue
        for col in ta.colnames:
            if np.asarray(ta[col]).tobytes() \
                    != np.asarray(tb[col]).tobytes():
                identical = False
                log(f"config 23: chunk {sa} column {col!r} bytes "
                    "differ between arms")
    hits_ok = ([h[0] for h in hits_file] == [h[0] for h in hits_feed]
               and len(hits_file) >= nchunks - 2)
    if not hits_ok:
        log(f"config 23: hits differ or too few: "
            f"{[h[0] for h in hits_file]} file vs "
            f"{[h[0] for h in hits_feed]} feed")
    led = asm.ledger
    ledger_ok = (led.unaccounted() == 0 and not led.journal
                 and led.gap_filled == 0 and led.observed == nsamples
                 and asm.invalid == 0 and asm.duplicates == 0)
    if not ledger_ok:
        log(f"config 23: ingest ledger not clean: "
            f"{asm.summary()['ledger']}")

    ok = identical and hits_ok and ledger_ok
    emit({"config": 23, "metric": "live-ingest A/B: localhost TCP "
          f"packet feed vs disk chunks, {nchan}x{nsamples} survey "
          f"({nchunks} chunks, {len(hits_file)} hits)",
          "value": round(file_wall / feed_wall, 4) if ok else 0.0,
          "unit": "x (file/feed wall; 0 = byte divergence, damaged "
                  "packets, or unaccounted samples)",
          "identical": bool(identical),
          "hits_ok": bool(hits_ok),
          "ledger_clean": bool(ledger_ok),
          "packets": asm.packets,
          "file_wall_s": round(file_wall, 3),
          "feed_wall_s": round(feed_wall, 3)})


def config24(quick):
    """Capacity-observability A/B (ISSUE 20): the same 2-file survey
    run through a 2-worker fleet twice —

    * **off arm** — the plain fleet (capacity off, the pre-ISSUE-20
      path): ``/fleet/capacity`` must serve an explicit
      ``enabled: false`` refusal, never a guessed advice;
    * **on arm** — capacity armed: worker utilization clocks +
      busy-fraction gauges riding each ``complete``, the coordinator
      deriving lease waits and folding per-worker EWMA throughput,
      the saturation detector classifying every sweep, and the
      scaling-advice engine served at ``/fleet/capacity``.

    ``value`` is the off/on wall ratio (the layer's measured overhead;
    ~1.0 expected) — FORCED to 0.0, far past any tolerance, when any
    candidate/ledger byte diverges between the arms, when the armed
    ``/fleet/capacity`` document is missing/disabled/evidence-free,
    or when the advice points **up** on a drained fleet (the one
    unambiguously wrong direction once the backlog is gone).
    """
    import glob
    import json as _json
    import tempfile
    import threading
    from urllib.request import urlopen

    from pulsarutils_tpu.fleet.coordinator import FleetCoordinator
    from pulsarutils_tpu.fleet.worker import FleetWorker
    from pulsarutils_tpu.io.sigproc import write_simulated_filterbank
    from pulsarutils_tpu.models.simulate import disperse_array
    from pulsarutils_tpu.obs.health import HealthEngine
    from pulsarutils_tpu.obs.server import start_obs_server

    tsamp, nchan = 0.0005, 64
    hop = 4096 if quick else 8192
    nhops = 6
    nsamples = nhops * hop
    config = dict(dmmin=100, dmmax=200, chunk_length=hop * tsamp,
                  snr_threshold=6.5)
    with tempfile.TemporaryDirectory() as tmp:
        fnames = []
        for i in range(2):
            rng = np.random.default_rng(240 + i)
            arr = np.abs(rng.normal(0, 0.5, (nchan, nsamples))) + 20.0
            if i == 0:
                arr[:, (3 * nsamples) // 4] += 4.0
                arr = disperse_array(arr, 150.0, 1200., 200., tsamp)
            header = {"bandwidth": 200., "fbottom": 1200.,
                      "nchans": nchan, "nsamples": nsamples,
                      "tsamp": tsamp, "foff": 200. / nchan}
            path = os.path.join(tmp, f"survey{i}.fil")
            write_simulated_filterbank(path, arr, header,
                                       descending=True)
            fnames.append(path)

        def fleet_run(outdir, *, armed):
            t0 = time.time()
            coordinator = FleetCoordinator(
                outdir, lease_ttl_s=120.0, chunks_per_unit=1,
                probe_interval_s=0.2, capacity=armed,
                health=HealthEngine() if armed else None)
            server = start_obs_server(0, fleet=coordinator)
            url = f"http://127.0.0.1:{server.port}"
            coordinator.add_survey(fnames, **config)
            workers = [FleetWorker(url, http_port=None)
                       for _ in range(2)]
            threads = [threading.Thread(target=w.run,
                                        kwargs={"max_idle_s": 120.0})
                       for w in workers]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=600.0)
            wall = time.time() - t0
            # one post-drain sweep so the armed detector sees the
            # terminal state before the document is read
            coordinator.sweep()
            with urlopen(url + "/fleet/capacity", timeout=10.0) as resp:
                doc = _json.loads(resp.read().decode())
            progress = coordinator.progress_doc()
            server.close()
            coordinator.close()
            return dict(wall=wall, progress=progress, doc=doc,
                        workers=workers)

        off = fleet_run(os.path.join(tmp, "off"), armed=False)
        on = fleet_run(os.path.join(tmp, "on"), armed=True)

        # identity: per-file ledger + candidate npz bytes between arms
        # (the config-14/18 comparison rule)
        identical = off["progress"]["survey_done"] \
            and on["progress"]["survey_done"]
        names = {os.path.basename(p)
                 for d in ("off", "on")
                 for p in glob.glob(os.path.join(tmp, d,
                                                 "progress_*.json"))
                 + glob.glob(os.path.join(tmp, d, "*.npz"))}
        for name in sorted(names):
            a_path = os.path.join(tmp, "off", name)
            b_path = os.path.join(tmp, "on", name)
            if not (os.path.exists(a_path) and os.path.exists(b_path)):
                identical = False
                log(f"config 24: {name} present in only one arm")
                continue
            if name.endswith(".json"):
                with open(a_path, "rb") as fa, open(b_path, "rb") as fb:
                    if fa.read() != fb.read():
                        identical = False
                        log(f"config 24: ledger bytes differ: {name}")
            else:
                with np.load(a_path, allow_pickle=False) as za, \
                        np.load(b_path, allow_pickle=False) as zb:
                    if set(za.files) != set(zb.files) or any(
                            za[k].tobytes() != zb[k].tobytes()
                            for k in za.files):
                        identical = False
                        log(f"config 24: candidate bytes differ: {name}")

        # the armed document must be present AND evidenced: detector
        # state, per-worker throughput behind the advice, an ETA seam
        doc = on["doc"]
        advice = doc.get("advice") or {}
        observations = (doc.get("throughput") or {}).get(
            "observations", 0)
        doc_ok = (doc.get("enabled") is True
                  and doc.get("state") in ("healthy", "worker-bound",
                                           "starved", "draining")
                  and observations > 0
                  and advice.get("direction") in ("up", "down", "hold"))
        if not doc_ok:
            log(f"config 24: armed /fleet/capacity doc not evidenced: "
                f"{doc}")
        # the drained fleet has nothing left to scale for: "up" here is
        # the wrong-direction advice the gate forces to 0.0
        direction_ok = advice.get("direction") != "up"
        if not direction_ok:
            log(f"config 24: advice scales UP a drained fleet: {advice}")
        off_refused = off["doc"].get("enabled") is False \
            and bool(off["doc"].get("reason"))
        if not off_refused:
            log(f"config 24: capacity-off doc not an explicit refusal: "
                f"{off['doc']}")
        ok = identical and doc_ok and direction_ok and off_refused
    emit({"config": 24, "metric": "capacity observability A/B: "
          "2-worker fleet with utilization/saturation/scaling-advice "
          f"armed vs off, 2 files x {nchan}x{nsamples}",
          "value": round(off["wall"] / on["wall"], 4) if ok else 0.0,
          "unit": "x (off/on wall; 0 = byte divergence, missing "
                  "capacity doc, or wrong-direction advice)",
          "identical": identical,
          "doc_ok": bool(doc_ok),
          "direction_ok": bool(direction_ok),
          "off_refused": bool(off_refused),
          "state": doc.get("state"),
          "advice": advice,
          "throughput_observations": observations,
          "units_per_worker": [w.units_done for w in on["workers"]],
          "off_wall_s": round(off["wall"], 2),
          "on_wall_s": round(on["wall"], 2)})


def main(argv=None):
    parser = argparse.ArgumentParser()
    parser.add_argument("--configs", type=int, nargs="*",
                        default=[1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12,
                                 13, 14, 15, 16, 17, 18, 19, 20, 21,
                                 22, 23, 24])
    parser.add_argument("--metrics-out", default=None, metavar="PATH",
                        help="write every config's JSON record plus a "
                             "final metrics-registry line to PATH (JSON "
                             "lines) — the snapshot tools/perf_gate.py "
                             "compares against a committed baseline")
    parser.add_argument("--backend", default=None, metavar="NAME",
                        help="backend lane stamped into the snapshot "
                             "header (default: jax.default_backend()); "
                             "tools/perf_gate.py refuses to compare "
                             "snapshots across backend lanes")
    opts = parser.parse_args(argv)
    quick = os.environ.get("BENCH_PRESET") == "quick"
    # the tune cache and the compile cache both live at fixed paths
    # inside the checkout (utils/compile_cache.py): nothing under $HOME
    # steers or survives a run
    from pulsarutils_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    fns = {1: config1, 2: config2, 3: config3, 4: config4, 5: config5,
           6: config6, 7: config7, 8: config8, 9: config9, 10: config10,
           11: config11, 12: config12, 13: config13, 14: config14,
           15: config15, 16: config16, 17: config17, 18: config18,
           19: config19, 20: config20, 21: config21, 22: config22,
           23: config23, 24: config24}
    failed = []
    for c in opts.configs:
        log(f"=== config {c} ===")
        try:
            fns[c](quick)
        except Exception as exc:
            # the remaining configs still run (one call, many records),
            # but the failure is on the line AND in the exit status
            traceback.print_exc()
            emit({"config": c, "error": f"{type(exc).__name__}: {exc}"})
            failed.append(c)
    if opts.metrics_out:
        from pulsarutils_tpu.obs.gate import SCHEMA_VERSION
        from pulsarutils_tpu.obs.metrics import REGISTRY
        from pulsarutils_tpu.precision import policy_name

        backend = opts.backend or device_stamp()["platform"]
        with open(opts.metrics_out, "w") as f:
            # versioned header first: the gate REFUSES snapshots whose
            # schema drifted instead of silently comparing them — and
            # (v3) stamps the bench LANE: walls only compare within one
            # (JAX backend, precision policy) pair, so the gate can
            # refuse a cross-backend or cross-policy comparison
            f.write(json.dumps({
                "schema_version": SCHEMA_VERSION,
                "backend": backend,
                "precision_policy": policy_name(
                    os.environ.get("PUTPU_PRECISION")),
            }) + "\n")
            for rec in RECORDS:
                f.write(json.dumps(rec) + "\n")
            # registry tail: counters/gauges/histograms the configs'
            # pipeline runs accumulated (ignored by the gate's loader)
            f.write(json.dumps({"metrics": REGISTRY.snapshot()}) + "\n")
        log(f"metrics snapshot -> {opts.metrics_out}")
    if failed:
        raise SystemExit(f"bench_suite: config(s) {failed} raised")


if __name__ == "__main__":
    main()
