"""Data conditioning and RFI excision ops.

Capability-equivalents of the reference's array-level cleaning layer
(``pulsarutils/clean.py:58-133,183-189``), written as pure functions that
run identically under NumPy and ``jax.numpy`` (all jit-compatible: static
shapes, ``where`` instead of boolean fancy-indexing).

Components and their reference counterparts:

* :func:`get_noisier_channels`  <- ``clean.py:58-67``
* :func:`renormalize_data`      <- ``clean.py:70-111`` (with the
  ``cut_outliers`` accumulation bug fixed: the reference computed
  ``bad_bins`` per window but only applied the last window's mask,
  ``clean.py:93-105``; here every window's outliers are cut)
* :func:`measure_channel_variability` <- ``clean.py:114-133`` (with the
  quartile indices taken over the *good*-channel count — the reference
  indexed the filtered array with full-size indices, an out-of-bounds
  hazard when many channels are masked)
* :func:`fft_zap_time` — FFT-domain periodic-RFI mask (the "FFT mask"
  stage of benchmark config 3); no direct reference counterpart, the
  reference's excision is purely spectral-statistics based.

The smoothing primitives (:func:`gaussian_filter_1d`,
:func:`uniform_filter_1d`) reproduce ``scipy.ndimage`` semantics
(reflect/"symmetric" boundary, ``truncate=4`` Gaussian radius) so the NumPy
path matches the reference's scipy calls while the same code jits on TPU.
"""

from __future__ import annotations

import numpy as np

from .robust import mad, median_filter_1d, ref_mad


# ---------------------------------------------------------------------------
# scipy.ndimage-equivalent smoothing primitives (backend-generic)
# ---------------------------------------------------------------------------

def _symmetric_pad_1d(x, left, right, xp):
    """'reflect' boundary of scipy.ndimage (edge value repeated)."""
    if left == 0 and right == 0:
        return x
    n = x.shape[0]
    left = min(left, n)
    right = min(right, n)
    return xp.concatenate([x[:left][::-1], x, x[n - right:][::-1]])


def _convolve_valid(padded, kernel, xp):
    """``convolve(padded, kernel, mode='valid')`` with a TPU-safe jax path.

    ``xp.convolve`` lowers to ``conv_general_dilated``; at awkward
    lengths (e.g. 120000-sample chunks) XLA:TPU's convolution tiling
    compiles pathologically (observed: minutes to never).  The jax path
    therefore runs the convolution in the Fourier domain at a
    power-of-two size — deterministic compile, exact same 'valid' slice.
    """
    kernel = xp.asarray(kernel, dtype=float)
    if xp is np:
        return np.convolve(padded, kernel, mode="valid")
    n = int(padded.shape[0])
    k = int(kernel.shape[0])
    m = n + k - 1
    size = 1 << int(np.ceil(np.log2(max(m, 2))))
    full = xp.fft.irfft(xp.fft.rfft(padded, n=size)
                        * xp.fft.rfft(kernel, n=size), n=size)
    return full[k - 1:n]


def gaussian_filter_1d(x, sigma, truncate=4.0, xp=np):
    """Gaussian smoothing matching ``scipy.ndimage.gaussian_filter1d``
    (mode='reflect', radius ``int(truncate * sigma + 0.5)``)."""
    x = xp.asarray(x, dtype=float)
    radius = int(truncate * float(sigma) + 0.5)
    if radius == 0:
        return x
    # kernel built host-side: sigma is a static configuration value
    kx = np.arange(-radius, radius + 1)
    kernel = np.exp(-0.5 * (kx / float(sigma)) ** 2)
    kernel = kernel / kernel.sum()
    # scipy clips the requested radius to the array length via reflection;
    # for radius >= n repeat the symmetric extension until long enough
    padded = x
    left = right = radius
    while left > 0 or right > 0:
        n = padded.shape[0]
        take_l, take_r = min(left, n), min(right, n)
        padded = _symmetric_pad_1d(padded, take_l, take_r, xp)
        left, right = left - take_l, right - take_r
    return _convolve_valid(padded, kernel, xp)


def uniform_filter_1d(x, size, xp=np):
    """Boxcar mean matching ``scipy.ndimage.uniform_filter1d``
    (mode='reflect', window centred with left-bias for even sizes)."""
    x = xp.asarray(x, dtype=float)
    size = int(size)
    if size <= 1:
        return x
    left = size // 2
    right = size - 1 - left
    padded = _symmetric_pad_1d(x, left, right, xp)
    kernel = np.full(size, 1.0 / size)
    return _convolve_valid(padded, kernel, xp)


# ---------------------------------------------------------------------------
# Channel flagging
# ---------------------------------------------------------------------------

def _masked_channel_mean(array, good, xp):
    """Per-sample mean over the good channels (shared by the cleaners)."""
    ngood = xp.maximum(good.sum(), 1)
    return xp.where(good[:, None], array, 0.0).sum(axis=0) / ngood


def zero_dm_filter(array, badchans_mask=None, xp=np):
    """Subtract the per-sample mean over (good) channels — the classic
    "zero-DM" broadband-RFI filter (Eatough, Keane & Lyne 2009).

    Terrestrial interference arrives un-dispersed, so it sits at DM 0:
    removing the channel-averaged time series cancels it while a
    dispersed pulse (spread across samples per channel) loses only
    ``~nchan_occupied/nchan`` of its power.  No reference counterpart —
    the reference's excision is purely spectral-statistics based
    (``stats.py``/``clean.py``); this complements it for impulsive
    broadband RFI.  Pure / jit-compatible.
    """
    array = xp.asarray(array)
    nchan = array.shape[0]
    if badchans_mask is None:
        badchans_mask = xp.zeros(nchan, dtype=bool)
    good = ~xp.asarray(badchans_mask)
    mean_t = _masked_channel_mean(array, good, xp)
    return xp.where(good[:, None], array - mean_t[None, :], array)


def get_noisier_channels(array, medfilt_size=7, nsigma=5.0, xp=np):
    """Flag channels whose mean lies above a median-filtered bandpass by
    ``nsigma`` reference-MADs (reference ``clean.py:58-67``)."""
    array = xp.asarray(array)
    spec = array.mean(axis=1)
    smooth = median_filter_1d(spec, medfilt_size, xp=xp)
    sigma = ref_mad(spec, xp=xp)
    return spec > smooth + nsigma * sigma


def measure_channel_variability(array, badchans_mask=None, xp=np):
    """Flag channels whose time-std falls outside robust quartile fences:
    ``[q2 - 2(q2 - q1), q2 + 2(q3 - q2)]`` (reference ``clean.py:114-133``).

    jit-friendly: already-bad channels are pushed to +inf before sorting and
    the quartile indices are computed from the good-channel count.
    """
    array = xp.asarray(array)
    nchan = array.shape[0]
    if badchans_mask is None:
        badchans_mask = xp.zeros(nchan, dtype=bool)
    spec = xp.std(array, axis=1)
    spec_for_sort = xp.where(badchans_mask, xp.inf, spec)
    ordered = xp.sort(spec_for_sort)
    ngood = (~badchans_mask).sum()
    q1 = ordered[ngood // 4]
    q2 = ordered[ngood // 2]
    q3 = ordered[ngood // 4 * 3]
    lowlim = q2 - 2 * (q2 - q1)
    hilim = q2 + 2 * (q3 - q2)
    return (spec < lowlim) | (spec > hilim) | badchans_mask


# ---------------------------------------------------------------------------
# Renormalisation / conditioning
# ---------------------------------------------------------------------------

def renormalize_data(array, badchans_mask=None, baseline_window=101,
                     cut_outliers=False, xp=np):
    """Condition a filterbank chunk for searching.

    Reference semantics (``clean.py:70-111``):

    1. flatten the time baseline: divide out the Gaussian-smoothed mean
       lightcurve of the good channels (window clipped to
       ``nsamples // 100 * 2 + 1``);
    2. per-channel bandpass normalisation to fractional deviation
       ``(x - mean_c) / mean_c``;
    3. zero the bad channels;
    4. optionally zero time bins where the boxcar-smoothed mean lightcurve
       exceeds +5 sigma or dips below -3 sigma at *any* boxcar width
       1,2,4,8,16 (the reference only applied the width-16 mask —
       fixed here, see module docstring).

    Pure function; jit-compatible for fixed shapes and flags.
    """
    array = xp.asarray(array).astype(float)
    nchan, nsamples = array.shape
    if badchans_mask is None:
        badchans_mask = xp.zeros(nchan, dtype=bool)
    badchans_mask = xp.asarray(badchans_mask)
    good = ~badchans_mask

    lc = _masked_channel_mean(array, good, xp)
    window = min(int(baseline_window), nsamples // 100 * 2 + 1)
    lc_smooth = gaussian_filter_1d(lc, window, xp=xp)
    lc_smooth = xp.where(lc_smooth == 0, 1.0, lc_smooth)
    factor = xp.median(lc_smooth) / lc_smooth
    renorm = array * factor[None, :]

    spec = renorm.mean(axis=1)
    denom = xp.where(spec == 0, 1.0, spec)
    renorm = (renorm - spec[:, None]) / denom[:, None]

    renorm = xp.where(badchans_mask[:, None], 0.0, renorm)

    if cut_outliers:
        lc = renorm.mean(axis=0)
        bad_bins = xp.zeros(nsamples, dtype=bool)
        for wpow in range(5):
            window = 1 << wpow
            lc_reb = uniform_filter_1d(lc, window, xp=xp)
            sigma = xp.std(lc_reb[::window])
            bad_bins = bad_bins | (lc_reb > 5 * sigma) | (lc_reb < -3 * sigma)
        renorm = xp.where(bad_bins[None, :], 0.0, renorm)

    return renorm


# ---------------------------------------------------------------------------
# FFT-domain RFI mask
# ---------------------------------------------------------------------------

def fft_zap_time(array, nsigma=5.0, protect_dc=1, xp=np):
    """Excise *periodic* broadband RFI in the Fourier domain.

    rFFT each channel over time, form the channel-averaged power spectrum,
    flag Fourier bins whose log-power exceeds a running-median + MAD
    threshold, null those bins in every channel, inverse transform.

    Returns ``(cleaned_array, zapped_bins_mask)``.  The reference package
    has no Fourier-domain excision — its cleaning is purely spectral-stats
    based — so this op is an extension, not a parity item.

    jit-compatible (fixed shapes; threshold via ``where``).
    """
    array = xp.asarray(array, dtype=float)
    spec = xp.fft.rfft(array, axis=1)
    power = (xp.abs(spec) ** 2).mean(axis=0)
    logp = xp.log(power + 1e-30)
    baseline = median_filter_1d(logp, 11, xp=xp)
    sigma = mad(logp - baseline, xp=xp)
    zap = logp > baseline + nsigma * sigma
    if protect_dc:
        keep = xp.arange(zap.shape[0]) < protect_dc
        zap = zap & ~keep
    cleaned = xp.fft.irfft(xp.where(zap[None, :], 0.0, spec), n=array.shape[1],
                           axis=1)
    return cleaned, zap
