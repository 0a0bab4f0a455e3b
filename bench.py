"""Benchmark: DM-trials/sec of the TPU dedispersion sweep vs single-core NumPy.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "DM-trials/sec", "vs_baseline": N, ...}

Headline configuration (BASELINE.json config 2): 1024 channels x 1M samples,
512 DM trials (the canonical plan: one trial per integer sample of
band-crossing delay, starting at DM 300), single chip.  The headline
kernel is the HYBRID sweep (``ops/search.py:_search_jax_hybrid``): an
FDMT coarse pass over every trial plus an exact Pallas rescore of the hit
region — exact (bit-identical-vs-NumPy) hit detection at near-FDMT
throughput.  The run verifies the claim in-place under
``exact_hit_match``: the hybrid's best row must be byte-equal to a full
exact Pallas sweep on argbest plan index, DM, rebin and peak, and its
f32 snr must agree to reduction-order tolerance (``snr_close``,
rel < 1e-5 — the two paths add the same floats in the same order but
reduce through different plane shapes).  Pure-FDMT and pure-Pallas
sweeps are reported as secondary metrics.

The NumPy baseline is the reference algorithm (per-channel circular
roll-and-accumulate + 4-window boxcar scoring, semantics of reference
``pulsarutils/dedispersion.py:174-202``) in its efficient single-core
form: allocation-free slice-adds, no gather temporaries.  It is measured
AT the full benchmark size (no extrapolation in ``nsamples``) over a
handful of trials — per-trial cost is trial-count-independent by
construction (an outer Python loop over trials), and the reported
``linearity_check`` (per-trial cost ratio between a 4-trial and an
8-trial run at full size) confirms it.

No fall-back: the run measures the kernel it was asked for, at the size
it was asked for, on the device JAX reports — stamped on the line as
``platform``/``device_kind``/``device_count`` — or it fails with a
non-zero exit.  A device failure, a failed secondary sweep or a failed
``exact_hit_match`` is an error, never a "degraded" number from a
smaller shape, another kernel or the CPU.  The XLA gather kernel is
refused on a TPU: at benchmark sizes the chip's compiler rejects it
(a 128 GiB index temporary at 1,024 x 2^20).

Environment knobs:
  BENCH_PRESET=full|quick   (default full; quick = small shapes)
  BENCH_NCHAN, BENCH_NSAMP  (override individual sizes)
  BENCH_KERNEL=hybrid|fdmt|pallas|gather  (default hybrid)
  BENCH_TRACE=<dir>         (write a jax.profiler trace of the timed run)
"""

import json
import os
import sys
import time


GEOM = (1200.0, 200.0, 0.0005)  # start_freq MHz, bandwidth MHz, tsamp s
NTRIALS = 512  # BASELINE.json config 2
DMMIN = 300.0
INJECT_DM = 350.0


def _dmmax_for_trials(n_trials):
    from pulsarutils_tpu.ops.plan import dmmax_for_trials

    return dmmax_for_trials(DMMIN, n_trials, *GEOM)


DMMAX = _dmmax_for_trials(NTRIALS)


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def device_stamp():
    """``platform``/``device_kind``/``device_count`` as JAX reports them
    — on every line ``bench.py`` and ``bench_suite.py`` print, so no
    number can be read without the device it was measured on."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices)}


def make_data(nchan, nsamp, seed=0):
    import numpy as np

    from pulsarutils_tpu.ops.plan import dedispersion_shifts

    start_freq, bandwidth, tsamp = GEOM
    rng = np.random.default_rng(seed)
    log(f"simulating {nchan} x {nsamp} filterbank ...")
    # in place: the full config is a 4 GB array on a 1-core host —
    # np.abs(...) * 0.5 would allocate two extra copies
    array = rng.standard_normal((nchan, nsamp), dtype=np.float32)
    np.abs(array, out=array)
    array *= 0.5
    array[:, nsamp // 2] += 1.0
    # disperse: per-channel circular roll (fast host path)
    shifts = np.rint(np.asarray(dedispersion_shifts(
        nchan, INJECT_DM, start_freq, bandwidth, tsamp))).astype(int) % nsamp
    for c in range(nchan):
        array[c] = np.roll(array[c], shifts[c])
    return array


def upload(array):
    import jax.numpy as jnp
    import numpy as np

    # upload once, outside any timed region (the streaming pipeline
    # double-buffers uploads anyway); the measured upload seconds are
    # reported in the JSON beside the headline
    t0 = time.time()
    device_array = jnp.asarray(array, dtype=jnp.float32)
    device_array.block_until_ready()
    dt = time.time() - t0
    log(f"host->device upload: {dt:.1f}s")
    return device_array, dt


#: headline timing protocol: at least MIN_REPEATS steady-state sweeps,
#: extended up to MAX_REPEATS until the spread of the rank-2..5 cluster
#: falls under SPREAD_BOUND — a noisy session then flags the artifact
#: (``timing.stable: false``) instead of silently shipping it
MIN_REPEATS = 5
MAX_REPEATS = 9
SPREAD_BOUND = 0.06


def measure_kernel(device_array, kernel, repeats=2, stabilize=False):
    """Warm + time steady-state sweeps (best of ``repeats``).

    Host-clock times vary run to run (a one-chip machine shares its
    host's cores); all raw times and their median are logged beside
    the min-of-N headline.  With ``stabilize`` (the
    headline protocol) repeats extend up to :data:`MAX_REPEATS` until
    the relative spread of the best three times is under
    :data:`SPREAD_BOUND`.
    Returns ``(table, trials/s, secs, timing_dict)``.
    """
    from pulsarutils_tpu.ops.search import dedispersion_search
    from pulsarutils_tpu.utils.logging_utils import device_trace

    def run():
        return dedispersion_search(
            device_array, DMMIN, DMMAX, *GEOM, backend="jax", kernel=kernel)

    log(f"compiling + warming up JAX kernel ({kernel}) ...")
    t0 = time.time()
    table = run()
    log(f"first run (incl. compile): {time.time() - t0:.2f}s")

    if stabilize:
        repeats = max(repeats, MIN_REPEATS)

    trace_dir = os.environ.get("BENCH_TRACE")
    times = []
    with device_trace(trace_dir):  # no-op when BENCH_TRACE unset
        t0 = time.time()
        table = run()
        times.append(time.time() - t0)
    if trace_dir:
        log(f"profiler trace written to {trace_dir}")

    def cluster_spread():
        """Relative spread of sweeps ranked 2-5 (0-indexed 1..4).

        Robust to one fast outlier and to slow stragglers; a genuinely
        noisy session still spreads the cluster itself and flags.
        """
        if len(times) < 5:
            return float("inf")
        s = sorted(times)
        return (s[4] - s[1]) / s[1]

    while len(times) < repeats or (
            stabilize and cluster_spread() > SPREAD_BOUND
            and len(times) < MAX_REPEATS):
        t0 = time.time()
        table = run()
        times.append(time.time() - t0)
    dt = min(times)
    timing = {"times_s": [round(x, 3) for x in times],
              "median_s": round(sorted(times)[len(times) // 2], 3),
              "cluster_spread": round(cluster_spread(), 4)}
    if stabilize:
        timing["stable"] = cluster_spread() <= SPREAD_BOUND
        timing["spread_bound"] = SPREAD_BOUND
    log(f"kernel={kernel}: {dt:.3f}s steady-state "
        f"(best of {timing['times_s']}, cluster spread "
        f"{timing['cluster_spread']:.1%}), {table.nrows} trials "
        f"-> {table.nrows / dt:.1f} DM-trials/s")
    return table, table.nrows / dt, dt, timing


def measure_numpy_baseline(array, nsamp):
    """Single-core reference-semantics sweep, measured AT full size.

    Runs 4 and 8 trials directly on the full ``(nchan, nsamp)`` array (the
    trials/s figure divides out the trial count, which is exact: the sweep
    is an outer Python loop over trials).  No extrapolation across
    ``nsamples``; the 4-vs-8-trial per-trial cost ratio is reported as
    ``linearity_check`` (VERDICT r1: the old two-size nsamples
    extrapolation drifted 44%).
    """
    import numpy as np

    from pulsarutils_tpu.ops.search import _search_numpy

    log("measuring NumPy single-core baseline at full size ...")
    data64 = np.asarray(array, dtype=np.float64)

    def numpy_time(ndm, repeats):
        dms = np.linspace(DMMIN, DMMAX, ndm)
        best = float("inf")
        for _ in range(repeats):  # min-of: host timing noise is +-30%
            t0 = time.time()
            _search_numpy(data64, dms, *GEOM, capture_plane=False)
            best = min(best, time.time() - t0)
        return best

    numpy_time(1, 1)  # warm up allocator/page cache
    t_4 = numpy_time(4, 2)
    t_8 = numpy_time(8, 2)
    linearity = (t_8 / 8) / (t_4 / 4)
    del data64
    numpy_tps = 8 / t_8
    log(f"NumPy @ full size: {t_4:.2f}s/4 trials, {t_8:.2f}s/8 trials "
        f"(per-trial linearity {linearity:.2f}) -> {numpy_tps:.4f} "
        f"DM-trials/s measured at {nsamp} samples")
    return numpy_tps, linearity


def main():
    preset = os.environ.get("BENCH_PRESET", "full")
    nchan = int(os.environ.get("BENCH_NCHAN", 1024 if preset == "full" else 128))
    nsamp = int(os.environ.get("BENCH_NSAMP",
                               1 << 20 if preset == "full" else 1 << 14))
    kernel = os.environ.get("BENCH_KERNEL", "hybrid")

    import numpy as np

    from pulsarutils_tpu.utils.compile_cache import enable_compile_cache

    device = device_stamp()
    platform = device["platform"]
    log(f"device: {device}")
    enable_compile_cache()
    if platform == "tpu" and kernel == "gather":
        raise SystemExit("BENCH_KERNEL=gather is refused on a TPU: the "
                         "chip's compiler rejects the XLA gather at "
                         "benchmark sizes (see the module docstring)")

    array = make_data(nchan, nsamp)
    device_array, upload_s = upload(array)
    table, jax_tps, _, headline_timing = measure_kernel(
        device_array, kernel, stabilize=True)
    # secondary metrics + in-place verification of the hybrid's claim:
    # its best row must be byte-equal to a full exact Pallas sweep
    # (bit-identical-vs-NumPy hit detection).  A sweep that raises
    # fails the run: an unverified headline is not a result.
    secondary = []
    exact_hit_match = None
    failed = []

    def secondary_row(kern, label):
        t2, tps2, dt2, _ = measure_kernel(device_array, kern)
        secondary.append({
            "kernel": label,
            "trials_per_sec": round(tps2, 1),
            "full_sweep_s": round(dt2, 3),
            "best_dm": float(t2["DM"][t2.argbest()]),
        })
        return t2

    if kernel == "hybrid" and platform == "tpu":
        t2 = secondary_row("pallas", "pallas (full exact sweep)")
        best_h, best_p = table.argbest("snr"), t2.argbest("snr")
        exact_hit_match = {
            "argbest_equal": best_h == best_p,
            "dm_byte_equal": bool(table["DM"][best_h] == t2["DM"][best_p]),
            "rebin_equal": int(table["rebin"][best_h])
                           == int(t2["rebin"][best_p]),
            "peak_equal": int(table["peak"][best_h])
                          == int(t2["peak"][best_p]),
            # the two paths add the same floats in the same order but
            # score through different-shaped reductions (16-row vs
            # 512-row planes), so snr agrees to f32 reduction order,
            # not byte-for-byte; assert the tolerance and report the
            # actual relative gap
            "snr_close": bool(abs(table["snr"][best_h] - t2["snr"][best_p])
                              <= 1e-5 * abs(t2["snr"][best_p])),
            "snr_rel_diff": float(abs(table["snr"][best_h]
                                      - t2["snr"][best_p])
                                  / abs(t2["snr"][best_p])),
            "rescored_rows": int(np.count_nonzero(table["exact"])),
        }
        log(f"exact_hit_match: {exact_hit_match}")
        failed = [k for k, v in exact_hit_match.items()
                  if isinstance(v, bool) and not v]
        secondary_row("fdmt", "fdmt (coarse sweep alone)")
    elif kernel == "fdmt" and platform == "tpu":
        secondary_row("pallas", "pallas (bit-exact hit detection)")

    numpy_tps, linearity = measure_numpy_baseline(array, nsamp)

    result = {
        "metric": f"DM-trials/sec, {nchan}-chan x {nsamp}-sample filterbank, "
                  f"DM {DMMIN:.0f}-{DMMAX:.0f} ({table.nrows} trials), "
                  f"backend=jax ({platform})",
        "value": round(jax_tps, 2),
        "unit": "DM-trials/sec",
        "vs_baseline": round(jax_tps / numpy_tps, 2),
        "baseline": {
            "what": "single-core NumPy (reference semantics, efficient "
                    "roll-and-accumulate form), measured directly at the "
                    "full benchmark size (no nsamples extrapolation)",
            "dm_trials_per_sec": round(numpy_tps, 4),
            "linearity_check": round(linearity, 3),
        },
        **device,
        "kernel": kernel,
        "best_dm": float(table["DM"][table.argbest()]),
        "injected_dm": INJECT_DM,
        # timing.stable=false: the stated variance bound was not reached
        # within MAX_REPEATS — flagged on the line, not hidden
        "timing": headline_timing,
        "upload_s": round(upload_s, 1),
    }
    if exact_hit_match is not None:
        result["exact_hit_match"] = exact_hit_match
    if secondary:
        result["secondary"] = secondary
    print(json.dumps(result), flush=True)
    if failed:
        raise SystemExit(f"exact_hit_match FAILED on {failed}: the "
                         "hybrid's best row does not match the exact sweep")


if __name__ == "__main__":
    main()
