"""Folded period search: FFT power spectra, harmonic summing, phase folding.

The reference reserves Z^2/H statistic slots on its candidate record
(``pulsarutils/clean.py:43-55``) and scores the dedispersed plane with an
H-test borrowed from ``hendrics`` (``clean.py:252-255``), but never builds
the periodicity *search* those statistics exist for.  This module is that
search, designed TPU-first:

* the power spectrum of a whole dedispersed plane ``(ndm, T)`` is ONE
  batched real FFT — XLA maps it onto the MXU/VPU and it stays in HBM;
* harmonic summing is a batched gather at stride-``j`` indices (the
  "stretch" method), fused by XLA with the spectrum normalisation;
* phase folding over a grid of trial frequencies is a scatter-add under
  ``vmap`` (one-hot-free, O(T) per trial), refined by the native
  Z^2_n / H statistics in :mod:`.robust`;
* everything takes ``xp`` (numpy | jax.numpy) like the rest of the ops
  layer, and the jax path is jit-compatible with static shapes.

White-noise calibration: spectra are median-normalised (median of an
Exp(1) variable is ``ln 2``) so a sum of ``h`` harmonics is Erlang(h)
under the null, giving closed-form false-alarm probabilities
(:func:`power_sf_log`) without any scipy dependency.
"""

from __future__ import annotations

import functools

import numpy as np

from .robust import h_test_batch, ref_mad

#: harmonic-sum depths tried by the search (PRESTO-style powers of two)
HARMONIC_SUMS = (1, 2, 4, 8, 16)

_LN2 = float(np.log(2.0))


# ---------------------------------------------------------------------------
# Power spectra
# ---------------------------------------------------------------------------

def power_spectrum(series, xp=np):
    """Raw rFFT power of ``series`` (..., T) -> (..., T//2 + 1).

    The DC bin is zeroed (the search never uses it and the mean level would
    otherwise dominate every normalisation).
    """
    series = xp.asarray(series)
    spec = xp.fft.rfft(series, axis=-1)
    power = xp.abs(spec) ** 2
    return power * _dc_mask(power.shape[-1], xp)


def _dc_mask(nbins, xp):
    mask = xp.ones(nbins)
    return mask.at[0].set(0.0) if xp is not np else _np_dc_mask(nbins)


def _np_dc_mask(nbins):
    mask = np.ones(nbins)
    mask[0] = 0.0
    return mask


def normalize_power(power, xp=np):
    """Median-normalise so white-noise bins are ~ Exp(1).

    For exponentially distributed raw powers the median is ``ln 2`` times
    the mean, so dividing by ``median / ln 2`` is a robust unit-mean
    normalisation that a strong periodic signal cannot bias the way the
    mean can.  Normalises each spectrum (last axis) independently.
    """
    power = xp.asarray(power)
    med = xp.median(power[..., 1:], axis=-1, keepdims=True)
    return power / xp.where(med > 0, med / _LN2, 1.0)


# ---------------------------------------------------------------------------
# Harmonic summing
# ---------------------------------------------------------------------------

def _add_harmonic(acc, power, j, xp):
    """Add harmonic ``j`` of every fundamental bin into ``acc`` (one gather)."""
    n = power.shape[-1]
    idx = xp.arange(n) * j
    valid = idx < n
    gathered = xp.take(power, xp.where(valid, idx, 0), axis=-1)
    return acc + xp.where(valid, gathered, 0.0)


def _add_harmonic_comp(acc, comp, power, j, xp):
    """Compensated (TwoSum) variant of :func:`_add_harmonic`.

    Carries the rounding error of each harmonic add in ``comp`` — the
    ``f32_compensated``/``split_f32`` policy's path through the stack
    (the harmonic count is small, so the two strategies share the
    sequential compensated form here).
    """
    n = power.shape[-1]
    idx = xp.arange(n) * j
    valid = idx < n
    gathered = xp.take(power, xp.where(valid, idx, 0), axis=-1)
    v = xp.where(valid, gathered, 0.0)
    s = acc + v
    bp = s - acc
    comp = comp + ((acc - (s - bp)) + (v - bp))
    return s, comp


def harmonic_sum(power, nharm, xp=np, policy=None):
    """Stretch-sum the first ``nharm`` harmonics of every fundamental bin.

    ``out[..., i] = sum_{j=1..nharm} power[..., i * j]`` with out-of-range
    harmonics contributing zero.  A bin whose fundamental is ``i`` collects
    the power a narrow pulse spreads over its harmonics; under the null the
    result is Erlang(``nharm``) when ``power`` is Exp(1)-normalised.

    ``policy`` selects a :mod:`..precision` accumulation strategy for
    the harmonic adds (``None``/``"f32"`` = the unchanged plain path).
    """
    power = xp.asarray(power)
    out = xp.zeros_like(power)
    if policy not in (None, "f32"):
        from ..precision import STRATEGIES, policy_name

        strat = STRATEGIES[policy_name(policy)]
        if strat.accumulator in ("compensated", "split"):
            comp = xp.zeros_like(power)
            for j in range(1, int(nharm) + 1):
                out, comp = _add_harmonic_comp(out, comp, power, j, xp)
            return out + comp
    for j in range(1, int(nharm) + 1):
        out = _add_harmonic(out, power, j, xp)
    return out


def power_sf_log(power, nsum=1, xp=np):
    """``log`` survival function of an Erlang(``nsum``) harmonic sum.

    ``P(S > p) = exp(-p) * sum_{k<nsum} p^k / k!`` — the false-alarm
    probability of a single bin of an ``nsum``-harmonic sum of Exp(1)
    powers.  Returned in log space to stay finite for strong detections.
    """
    power = xp.asarray(power, dtype=float)
    # log-sum-exp over k of (k*log p - log k!)
    logp = xp.log(xp.where(power > 0, power, 1e-300))
    terms = [k * logp - _log_factorial(k) for k in range(int(nsum))]
    stacked = xp.stack(terms)
    m = xp.max(stacked, axis=0)
    lse = m + xp.log(xp.sum(xp.exp(stacked - m), axis=0))
    return -power + lse


def _log_factorial(k):
    return float(np.sum(np.log(np.arange(1, k + 1)))) if k > 1 else 0.0


def sf_log_to_sigma(log_sf, xp=np):
    """Gaussian-equivalent significance of a log false-alarm probability.

    Uses the asymptotic expansion of the normal quantile for small tail
    probabilities, ``sigma ~ sqrt(u - log u)`` with ``u = -2 log(sf) -
    log(2 pi)`` — accurate to ~1% for sigma > 2, exact enough for ranking
    candidates (the number the reference never computed at all).
    """
    log_sf = xp.asarray(log_sf, dtype=float)
    u = -2.0 * log_sf - float(np.log(2.0 * np.pi))
    u = xp.where(u > 1.0, u, 1.0)
    return xp.sqrt(u - xp.log(u))


# ---------------------------------------------------------------------------
# Spectral search over a dedispersed plane
# ---------------------------------------------------------------------------

def score_normalized_power(power, nsamples, tsamp, max_harmonics=16,
                           fmin=None, fmax=None, xp=np, policy=None):
    """Harmonic-sum scoring of an already Exp(1)-normalised power
    spectrum ``power`` (..., nbins) of a length-``nsamples`` series.

    The scoring half of :func:`spectral_search`, split out so the
    Fourier-domain acceleration backend
    (:mod:`pulsarutils_tpu.periodicity.fdas`) can feed its correlated
    trial spectra through the IDENTICAL harmonic-sum / false-alarm /
    sigma chain — the cell-for-cell agreement contract between the
    backends rides on this being one implementation, not two.

    ``policy`` selects the :mod:`..precision` accumulation strategy for
    the incremental harmonic stack: compensated strategies thread a
    TwoSum carry through the adds; ``bf16_operand_f32_accum`` gathers
    bfloat16 bins and accumulates float32 (jax only).
    ``None``/``"f32"`` is the byte-identical default.
    """
    strat = None
    if policy not in (None, "f32"):
        from ..precision import STRATEGIES, policy_name

        strat = STRATEGIES[policy_name(policy)]
        if strat.operand_dtype == "bfloat16" and xp is np:
            raise ValueError("bf16_operand_f32_accum needs the jax path "
                             "(numpy has no bfloat16)")
    t = int(nsamples)
    nbins = power.shape[-1]
    freqs = xp.arange(nbins) / (t * tsamp)

    lo = 1 if fmin is None else max(1, int(np.ceil(fmin * t * tsamp)))
    hi = nbins if fmax is None else min(nbins, int(fmax * t * tsamp) + 1)
    band = xp.zeros(nbins)
    if xp is np:
        band[lo:hi] = 1.0
    else:
        band = band.at[lo:hi].set(1.0)

    best_logsf = xp.full(power.shape[:-1], xp.inf)
    best_freq = xp.zeros(power.shape[:-1])
    best_power = xp.zeros(power.shape[:-1])
    best_nharm = xp.zeros(power.shape[:-1], dtype=xp.int32)

    # incremental harmonic accumulation: one gather per harmonic (16 total),
    # scored whenever the depth hits one of HARMONIC_SUMS
    gath = power
    if strat is not None and strat.operand_dtype == "bfloat16":
        # narrow the gathered operand (the bandwidth-bound read); the
        # accumulator stays float32 below
        from ..precision import cast_operand

        gath = cast_operand(power, strat.name, xp)
    compensated = (strat is not None
                   and strat.accumulator in ("compensated", "split"))
    acc = xp.zeros_like(power)
    comp = xp.zeros_like(power) if compensated else None
    depth = 0
    for h in HARMONIC_SUMS:
        if h > max_harmonics:
            break
        for j in range(depth + 1, h + 1):
            if compensated:
                acc, comp = _add_harmonic_comp(acc, comp, power, j, xp)
            elif gath is not power:
                n = power.shape[-1]
                idx = xp.arange(n) * j
                valid = idx < n
                g = xp.take(gath, xp.where(valid, idx, 0), axis=-1)
                acc = acc + xp.where(valid, g.astype(power.dtype), 0.0)
            else:
                acc = _add_harmonic(acc, power, j, xp)
        depth = h
        hsum = (acc + comp if compensated else acc) * band
        peak = xp.argmax(hsum, axis=-1)
        pval = xp.take_along_axis(hsum, peak[..., None], axis=-1)[..., 0]
        log_sf = power_sf_log(pval, nsum=h, xp=xp)
        better = log_sf < best_logsf
        best_logsf = xp.where(better, log_sf, best_logsf)
        best_freq = xp.where(better, xp.take(freqs, peak), best_freq)
        best_power = xp.where(better, pval, best_power)
        best_nharm = xp.where(better, h, best_nharm)

    return {
        "freq": best_freq,
        "power": best_power,
        "nharm": best_nharm,
        "log_sf": best_logsf,
        "sigma": sf_log_to_sigma(best_logsf, xp=xp),
    }


def spectral_search(series, tsamp, max_harmonics=16, fmin=None, fmax=None,
                    xp=np, policy=None):
    """FFT periodicity search of ``series`` (..., T).

    For every harmonic-sum depth ``h`` in :data:`HARMONIC_SUMS` up to
    ``max_harmonics``, find the most significant fundamental bin; return the
    overall best per series.

    Returns a dict of arrays (leading axes = ``series``'s batch axes):
    ``freq`` (Hz), ``power`` (summed normalised power), ``nharm``,
    ``log_sf`` (single-bin log false-alarm probability) and ``sigma``.
    ``policy`` threads a :mod:`..precision` accumulation strategy into
    the harmonic stack (see :func:`score_normalized_power`).
    """
    series = xp.asarray(series)
    t = series.shape[-1]
    power = normalize_power(power_spectrum(series, xp=xp), xp=xp)
    return score_normalized_power(power, t, tsamp,
                                  max_harmonics=max_harmonics,
                                  fmin=fmin, fmax=fmax, xp=xp,
                                  policy=policy)


_SPEC_KEYS = ("freq", "power", "nharm", "log_sf", "sigma")


@functools.lru_cache(maxsize=32)
def _jitted_spectral_stacked(tsamp, max_harmonics, fmin, fmax, policy=None):
    """One jitted program per (tsamp, depth, band) running the whole
    spectral search and returning the five per-row results as ONE
    ``(5, rows)`` array — eager dispatch costs ~50 op dispatches per
    chunk, plus five readbacks."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def run(chunk):
        spec = spectral_search(chunk, tsamp, max_harmonics=max_harmonics,
                               fmin=fmin, fmax=fmax, xp=jnp, policy=policy)
        return jnp.stack([spec[k].astype(jnp.float32) if k == "nharm"
                          else spec[k] for k in _SPEC_KEYS])

    return run


def _spectral_chunk(plane_chunk, tsamp, max_harmonics, fmin, fmax, xp,
                    kernel="auto", policy=None):
    """Spectral-search one row chunk; host dict out (one readback on jax).

    ``kernel`` picks the jax scoring program: ``"xla"`` (the jitted
    :func:`spectral_search` chain), ``"pallas"`` (the one-pass
    :mod:`.harmonic_pallas` normalize+stack kernel) or ``"auto"`` — the
    measured selection via
    :func:`~pulsarutils_tpu.tuning.autotune.resolve_harmonic_kernel`
    (static fallback ``"xla"``; a Pallas win is only ever cached after
    the identity harness passes).  The numpy path ignores ``kernel``.
    """
    if xp is np:
        c = spectral_search(np.asarray(plane_chunk), tsamp,
                            max_harmonics=max_harmonics, fmin=fmin,
                            fmax=fmax, xp=np, policy=policy)
        return {k: np.asarray(v) for k, v in c.items()}
    rows, t = plane_chunk.shape[-2], plane_chunk.shape[-1]
    if kernel == "auto":
        from ..tuning.autotune import resolve_harmonic_kernel

        kernel = resolve_harmonic_kernel(rows, t, float(tsamp),
                                         max_harmonics=int(max_harmonics),
                                         fmin=fmin, fmax=fmax,
                                         policy=policy)
    if kernel == "pallas":
        from .harmonic_pallas import spectral_search_pallas

        spec = spectral_search_pallas(plane_chunk, tsamp,
                                      max_harmonics=max_harmonics,
                                      fmin=fmin, fmax=fmax, policy=policy)
        out = {k: np.asarray(v) for k, v in spec.items()}
        out["nharm"] = np.rint(out["nharm"]).astype(np.int32)
        return out
    run = _jitted_spectral_stacked(
        float(tsamp), int(max_harmonics),
        None if fmin is None else float(fmin),
        None if fmax is None else float(fmax), policy)
    stacked = np.asarray(run(xp.asarray(plane_chunk)))
    out = dict(zip(_SPEC_KEYS, stacked))
    out["nharm"] = np.rint(out["nharm"]).astype(np.int32)
    return out


# ---------------------------------------------------------------------------
# Phase folding
# ---------------------------------------------------------------------------

#: samples per phase-anchor block in the device fold kernel.  Anchored
#: folding bounds the float32 phase error to ~``_FOLD_BLOCK * eps`` cycles
#: regardless of series length (see :func:`_phase_anchors`).
_FOLD_BLOCK = 4096


def _phase_anchors(nsamples, freqs, tsamp, t0):
    """Host-side float64 phase at the start of every anchor block.

    Device arithmetic is float32; computing ``(i * tsamp * freq) mod 1``
    directly in float32 accumulates phase error linearly in ``i`` (0.05
    cycles by ``i ~ 2^24`` at 100 Hz — enough to smear a profile).  Instead
    the exact (float64) phase is evaluated every ``_FOLD_BLOCK`` samples and
    the device only extrapolates within a block, where the float32 error is
    a few 1e-4 cycles.  Returns ``(anchors, step_frac)``: ``(nfreq,
    nblocks)`` block-start phases in [0, 1) and the per-freq fractional
    phase step per sample.
    """
    freqs = np.atleast_1d(np.asarray(freqs, dtype=np.float64))
    nblocks = -(-int(nsamples) // _FOLD_BLOCK)
    starts = np.arange(nblocks, dtype=np.float64) * _FOLD_BLOCK
    step = freqs * float(tsamp)
    anchors = ((starts[None, :] * step[:, None])
               + float(t0) * freqs[:, None]) % 1.0
    return anchors, step % 1.0


def _fold_jax_anchored(series, anchors, step_frac, nbin):
    """Device fold from precomputed anchors: one trial frequency."""
    import jax.numpy as jnp

    t = series.shape[0]
    nblocks = anchors.shape[0]
    i = jnp.arange(_FOLD_BLOCK, dtype=series.dtype)
    # (nblocks, B): i * step mod 1 == i * frac(step) mod 1 for integer i
    phase = (anchors[:, None] + i[None, :] * step_frac) % 1.0
    bins = (phase * nbin).astype(jnp.int32) % nbin
    bins = bins.reshape(-1)[:t]
    profile = jnp.zeros(nbin, dtype=series.dtype).at[bins].add(series)
    hits = jnp.zeros(nbin, dtype=series.dtype).at[bins].add(1.0)
    return profile, hits


def fold(series, freq, tsamp, nbin=32, t0=0.0, xp=np):
    """Fold ``series`` (T,) at frequency ``freq`` into ``nbin`` phase bins.

    Returns ``(profile, hits)``: the per-bin sum of samples and the per-bin
    sample counts (callers divide for a mean profile; the raw sums are what
    the Z^2/H statistics want).  ``freq`` must be a concrete (host) scalar:
    phase anchors are precomputed in float64 so device folding stays
    accurate for arbitrarily long series (see :func:`_phase_anchors`).
    """
    series = xp.asarray(series)
    t = series.shape[0]
    if xp is np:
        phases = ((np.arange(t) * float(tsamp) + t0) * float(freq)) % 1.0
        bins = np.floor(phases * nbin).astype(np.int64) % nbin
        profile = np.bincount(bins, weights=series, minlength=nbin)
        hits = np.bincount(bins, minlength=nbin).astype(float)
        return profile, hits
    anchors, step_frac = _phase_anchors(t, float(freq), tsamp, t0)
    return _fold_jax_anchored(series, xp.asarray(anchors[0], dtype=series.dtype),
                              xp.asarray(step_frac[0], dtype=series.dtype), nbin)


def fold_batch(series, freqs, tsamp, nbin=32, t0=0.0, xp=np):
    """Fold one series at many trial frequencies -> ``(nfreq, nbin)`` sums.

    On the jax path the frequency axis is ``vmap``-ed over the precomputed
    phase anchors so all trials fold in one compiled program.  ``freqs``
    must be concrete host values (they parameterise the float64 anchor
    table, not the traced computation).
    """
    freqs = np.asarray(  # putpu-lint: disable=device-trip — concrete host anchors by contract
        freqs, dtype=np.float64)
    if xp is np:
        folded = [fold(series, f, tsamp, nbin, t0) for f in freqs]
        return (np.stack([p for p, _ in folded]),
                np.stack([h for _, h in folded]))
    import jax

    anchors, step_frac = _phase_anchors(series.shape[0], freqs, tsamp, t0)
    f = jax.vmap(lambda a, s: _fold_jax_anchored(series, a, s, nbin))
    return f(xp.asarray(anchors, dtype=series.dtype),
             xp.asarray(step_frac, dtype=series.dtype))


def _epoch_fold_score(series, profiles, hits, nmax, xp):
    """Exposure-correct folded profiles and H-test them (pure, jittable)."""
    mean_rate = profiles.sum(axis=-1, keepdims=True) / xp.maximum(
        hits.sum(axis=-1, keepdims=True), 1.0)
    corrected = profiles - hits * mean_rate
    sigma = ref_mad(series, xp=xp)
    total = series.shape[0] * xp.maximum(sigma * sigma, 1e-30)
    return h_test_batch(corrected, nmax=nmax, xp=xp, total=total)


@functools.lru_cache(maxsize=16)
def _jitted_epoch_fold(nbin, nmax):
    """Fold + exposure-correct + H-test as ONE compiled program (eager
    dispatch costs ~30 op dispatches)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def run(series, anchors, step_frac):
        profiles, hits = jax.vmap(
            lambda a, s: _fold_jax_anchored(series, a, s, nbin))(
                anchors, step_frac)
        h, m = _epoch_fold_score(series, profiles, hits, nmax, jnp)
        return h, m, profiles

    return run


def epoch_folding_search(series, tsamp, freqs, nbin=32, nmax=8, xp=np):
    """Refine candidate frequencies by folding + H-test.

    Folds ``series`` at every trial frequency, exposure-corrects the
    profiles (uneven per-bin hit counts tilt them) and scores with the
    de Jager H-test under the *Gaussian* normalisation ``total = T sigma^2``
    (robust sigma from :func:`~.robust.ref_mad`), so H stays chi-square
    calibrated instead of scaling with the input noise amplitude.  Returns
    ``(h_stats, m_best, profiles)``.  Capability-equivalent of the efsearch
    step the reference outsourced to hendrics (``clean.py:252-255``), run
    over frequency instead of plane rows.
    """
    series = xp.asarray(series)
    if xp is not np:
        freqs64 = np.asarray(freqs, dtype=np.float64)
        anchors, step_frac = _phase_anchors(series.shape[0], freqs64, tsamp,
                                            0.0)
        run = _jitted_epoch_fold(int(nbin), int(nmax))
        return run(series, xp.asarray(anchors, dtype=series.dtype),
                   xp.asarray(step_frac, dtype=series.dtype))
    profiles, hits = fold_batch(series, freqs, tsamp, nbin=nbin, xp=xp)
    h, m = _epoch_fold_score(series, profiles, hits, nmax, xp)
    return h, m, profiles


def refine_grid(freq, tsamp, nsamples, oversample=8, half_width_bins=2):
    """Trial-frequency grid around ``freq`` spanning ±``half_width_bins``
    Fourier bins at ``oversample`` trials per bin (the Fourier resolution of
    an ``nsamples``-long series is ``1 / (T tsamp)``)."""
    df = 1.0 / (nsamples * tsamp)
    n = 2 * half_width_bins * oversample + 1
    return freq + np.linspace(-half_width_bins * df, half_width_bins * df, n)


# ---------------------------------------------------------------------------
# Full folded period search (the BASELINE config-4 pipeline step)
# ---------------------------------------------------------------------------

def period_search_plane(plane, tsamp, max_harmonics=16, fmin=None, fmax=None,
                        nbin=32, oversample=8, refine_top=1, row_chunk=None,
                        xp=np):
    """Folded period search over a dedispersed plane ``(ndm, T)``.

    Stage 1 (device): batched FFT + harmonic-sum search per DM trial,
    processed ``row_chunk`` rows at a time — XLA's batched rFFT allocates
    several (rows x T) temporaries, so an unchunked 4096-trial x 256k
    plane overruns HBM.  Default keeps each chunk's FFT workspace near
    0.5 GB.  Per-row results concatenate exactly, so chunking changes
    nothing numerically.
    Stage 2 (device): for the ``refine_top`` most significant DM rows, fold
    on a fine frequency grid around the spectral candidate and H-test.

    Returns a dict: per-DM spectral results (``freq, power, nharm, log_sf,
    sigma``) plus ``best_dm_index``, ``best_freq``, ``best_h``, ``best_m``,
    ``best_sigma`` (Gaussian-equivalent significance of the refined H via
    the de Jager & Büsching 2010 tail ``P(>H) ~ exp(-0.4 H)``) and
    ``best_profile``.
    """
    # NOTE: do not blanket-convert ``plane`` with xp.asarray — a plane the
    # search spilled to host (ndm beyond one superblock) would be shipped
    # back to HBM whole, defeating the chunked memory bound below; chunks
    # are converted as they are processed
    ndm, t = plane.shape
    if row_chunk is None:
        row_chunk = max(16, (1 << 27) // max(1, t))
    if hasattr(plane, "spectral_scores"):
        # mesh path: the plane is a DM-sharded device-resident handle
        # (:class:`~pulsarutils_tpu.parallel.sharded_plane.ShardedPlane`);
        # stage 1 runs shard-locally on each device's rows and only the
        # per-row score vectors come to host.  Stage 2 below fetches the
        # refine rows individually (``plane[d]`` -> one host row).
        spec = plane.spectral_scores(tsamp, max_harmonics=max_harmonics,
                                     fmin=fmin, fmax=fmax)
    elif ndm <= row_chunk:
        spec = _spectral_chunk(plane, tsamp, max_harmonics, fmin, fmax, xp)
    else:
        chunks = []
        for lo in range(0, ndm, row_chunk):
            # each chunk runs as one jitted program with one host readback
            # (_spectral_chunk); pulling to host INSIDE the loop keeps a
            # single chunk's FFT workspace live in HBM at a time — async
            # dispatch would otherwise run several concurrently, the very
            # blow-up the chunking exists to prevent
            chunks.append(_spectral_chunk(plane[lo:lo + row_chunk], tsamp,
                                          max_harmonics, fmin, fmax, xp))
        spec = {k: np.concatenate([c[k] for c in chunks])
                for k in chunks[0]}

    order = np.argsort(np.asarray(spec["log_sf"]))
    best = {}
    for rank in range(min(int(refine_top), ndm)):
        d = int(order[rank])
        f0 = float(np.asarray(spec["freq"])[d])
        if f0 <= 0:
            continue
        grid = refine_grid(f0, tsamp, t, oversample=oversample)
        h, m, profiles = epoch_folding_search(plane[d], tsamp,
                                              xp.asarray(grid), nbin=nbin,
                                              xp=xp)
        k = int(np.argmax(np.asarray(h)))
        cand = {
            "dm_index": d,
            "freq": float(grid[k]),
            "h": float(np.asarray(h)[k]),
            "m": int(np.asarray(m)[k]),
            "profile": np.asarray(profiles[k]),
        }
        if not best or cand["h"] > best["h"]:
            best = cand

    best_h = best.get("h", 0.0)
    best_sigma = float(sf_log_to_sigma(np.asarray(-0.4 * best_h), xp=np)) \
        if best_h > 0 else float(np.asarray(spec["sigma"])[order[0]])
    return {
        **{k: np.asarray(v) for k, v in spec.items()},
        "best_dm_index": best.get("dm_index", int(order[0])),
        "best_freq": best.get("freq", float(np.asarray(spec["freq"])[order[0]])),
        "best_h": best_h,
        "best_m": best.get("m", 0),
        "best_sigma": best_sigma,
        "best_profile": best.get("profile"),
    }
