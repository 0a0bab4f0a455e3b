"""Robust statistics and periodicity scores, self-contained.

The reference borrowed two scientific functions from third-party packages;
this framework implements them natively (SURVEY §2 note):

* ``mad`` — normalised median absolute deviation
  (capability-equivalent of ``statsmodels.robust.mad``, used at reference
  ``pulsarutils/stats.py:4,32`` and ``clean.py:24,186``);
* ``h_test`` / ``z_n_test`` — de Jager H-test and Z^2_n periodicity
  statistics over binned profiles (capability-equivalent of
  ``hendrics.efsearch.h_test``, used at reference ``clean.py:20,252-255``).

Plus the derived estimators the reference defines itself:

* ``ref_mad`` — MAD of the first difference / sqrt(2), a noise estimate
  robust to smooth baselines (reference ``stats.py:11-32``).  The
  reference's docstring promises a rolling-window minimum that the body
  never implemented; here ``window > 1`` actually does it.
* ``median_filter_1d`` — zero-padded running median matching
  ``scipy.signal.medfilt`` semantics (used for bandpass smoothing at
  reference ``stats.py:74``, ``clean.py:61``), with a jit-friendly
  stacked-sort implementation for the JAX path.
* ``digitize`` — scale data to integer counts for the H-test (reference
  ``clean.py:183-189``).

Everything takes ``xp`` (numpy or jax.numpy) and is jit-compatible under
``xp=jax.numpy``.
"""

from __future__ import annotations

import numpy as np

#: Phi^-1(3/4): scipy.stats.norm.ppf(0.75), the consistency constant that
#: makes MAD estimate sigma for Gaussian data (statsmodels' default).
MAD_SCALE = 0.6744897501960817


def mad(array, axis=None, xp=np):
    """Normalised median absolute deviation: ``median(|x - med|) / 0.6745``.

    ``axis=None`` reduces over the whole array (scalar); an integer axis
    reduces along it.  Capability-equivalent of ``statsmodels.robust.mad``
    (whose default is ``axis=0``; pass ``axis=0`` for bug-compatible
    behaviour on 2-D input).
    """
    array = xp.asarray(array)
    med = xp.median(array, axis=axis, keepdims=axis is not None)
    return xp.median(xp.abs(array - med), axis=axis) / MAD_SCALE


def ref_mad(array, window=1, xp=np):
    """Reference MAD: ``mad(diff(x)) / sqrt(2)`` — noise of the underlying
    series, insensitive to smooth trends (reference ``stats.py:11-32``).

    ``window > 1`` implements the rolling-window-minimum the reference
    documented but never wrote: the MAD is computed in non-overlapping
    windows of ``window`` samples and the minimum is returned (the quietest
    stretch estimates the true noise floor).
    """
    array = xp.asarray(array)
    d = xp.diff(array)
    if window and window > 1:
        n = d.shape[0] // int(window)
        if n >= 1:
            blocks = d[: n * int(window)].reshape(n, int(window))
            return xp.min(mad(blocks, axis=1, xp=xp)) / np.sqrt(2)
    return mad(d, xp=xp) / np.sqrt(2)


def median_filter_1d(x, size, xp=np):
    """Running median with zero padding, matching ``scipy.signal.medfilt``.

    ``size`` must be odd.  Implemented as a stacked-window sort so the same
    code jits on TPU (the windows tensor is ``(size, n)`` — tiny for the
    bandpass spectra this is applied to).
    """
    if size % 2 != 1:
        raise ValueError("median filter size must be odd")
    x = xp.asarray(x)
    n = x.shape[0]
    half = size // 2
    pad = xp.zeros(half, dtype=x.dtype)
    xpadded = xp.concatenate([pad, x, pad])
    windows = xp.stack([xpadded[i:i + n] for i in range(size)])
    return xp.median(windows, axis=0)


def z_n_test(profile, n_harmonics, xp=np):
    """Z^2_n periodicity statistic of a binned phase profile.

    ``Z^2_n = (2/N) * sum_{k=1..n} |FFT(profile)_k|^2`` with ``N`` the total
    number of counts.  Buccheri et al. 1983; the statistic the reference
    reserves slots for on its candidate record (``clean.py:43-55``).
    """
    profile = xp.asarray(profile, dtype=float)
    nbin = profile.shape[0]
    n_harmonics = int(n_harmonics)
    if n_harmonics > nbin // 2:
        # rfft only resolves nbin//2 harmonics; silently summing fewer
        # would understate the statistic the caller asked for
        raise ValueError(
            f"n_harmonics={n_harmonics} exceeds the {nbin // 2} harmonics "
            f"resolvable in a {nbin}-bin profile")
    total = profile.sum()
    spec = xp.fft.rfft(profile)
    powers = xp.abs(spec[1:n_harmonics + 1]) ** 2
    return 2.0 / total * powers.sum()


def h_test(profile, nmax=20, xp=np):
    """de Jager H-test over a binned phase profile.

    ``H = max_m (Z^2_m - 4m + 4)`` for ``1 <= m <= nmax``.  Returns
    ``(H, m_best)``.  Capability-equivalent of ``hendrics.efsearch.h_test``
    as called by the reference's diagnostic plot (``clean.py:252-255``).
    Works under jit for fixed ``nmax``.
    """
    profile = xp.asarray(profile, dtype=float)
    nmax = int(max(1, min(nmax, profile.shape[0] // 2 if profile.shape[0] >= 4 else 1)))
    total = profile.sum()
    spec = xp.fft.rfft(profile)
    powers = xp.abs(spec[1:nmax + 1]) ** 2
    z2 = 2.0 / total * xp.cumsum(powers)
    m = xp.arange(1, nmax + 1)
    h_candidates = z2 - 4.0 * m + 4.0
    best = xp.argmax(h_candidates)
    return h_candidates[best], best + 1


def z_n_and_h(profile, harmonics, nmax=20):
    """``Z^2_n`` for every ``n`` of ``harmonics`` and the H-test up to
    ``nmax`` of one binned profile, from ONE ``rfft``: the floats
    :func:`z_n_test` and :func:`h_test` return, bit for bit (the same
    powers summed in the same order).  A candidate's record asks for four
    ``Z^2_n`` and ``H`` of a profile as long as its chunk; five
    transforms of 2^20 points, each building its own plan and buffers,
    were 44 to 178 ms of a hit chunk's host time (``PERF.md`` section 6,
    PR 37).

    Returns ``({n: Z^2_n}, H, m_best)``.
    """
    profile = np.asarray(profile, dtype=float)
    nbin = profile.shape[0]
    harmonics = [int(n) for n in harmonics]
    if harmonics and max(harmonics) > nbin // 2:
        raise ValueError(
            f"n_harmonics={max(harmonics)} exceeds the {nbin // 2} "
            f"harmonics resolvable in a {nbin}-bin profile")
    nmax = int(max(1, min(nmax, nbin // 2 if nbin >= 4 else 1)))
    total = profile.sum()
    spec = np.fft.rfft(profile)
    z = {n: 2.0 / total * (np.abs(spec[1:n + 1]) ** 2).sum()
         for n in harmonics}
    z2 = 2.0 / total * np.cumsum(np.abs(spec[1:nmax + 1]) ** 2)
    h_candidates = z2 - 4.0 * np.arange(1, nmax + 1) + 4.0
    best = np.argmax(h_candidates)
    return z, h_candidates[best], best + 1


def h_test_batch(profiles, nmax=20, xp=np, total=None):
    """Vectorised H-test over a batch of profiles ``(nprof, nbin)``.

    Returns ``(H, m_best)`` arrays of shape ``(nprof,)``.  This is what the
    diagnostics use to score the whole dedispersed plane in one shot instead
    of the reference's per-row Python loop (``clean.py:253``).

    ``total`` overrides the ``2 / total`` normalising denominator.  The
    default (per-profile sum) is the Poisson/event-count convention; for
    profiles folded from *Gaussian* data pass ``total = T * sigma**2``
    (samples times per-sample variance) — then the Fourier powers have
    variance ``T sigma^2 / 2`` per component and ``Z^2_m ~ chi^2_{2m}``
    under the null, keeping H chi-square calibrated instead of scaling
    with the noise amplitude.
    """
    profiles = xp.asarray(profiles, dtype=float)
    nbin = profiles.shape[1]
    nmax = int(max(1, min(nmax, nbin // 2 if nbin >= 4 else 1)))
    if total is None:
        total = profiles.sum(axis=1, keepdims=True)
    else:
        total = xp.reshape(xp.asarray(total, dtype=float), (-1, 1))
    spec = xp.fft.rfft(profiles, axis=1)
    powers = xp.abs(spec[:, 1:nmax + 1]) ** 2
    z2 = 2.0 / total * xp.cumsum(powers, axis=1)
    m = xp.arange(1, nmax + 1)[None, :]
    h_candidates = z2 - 4.0 * m + 4.0
    best = xp.argmax(h_candidates, axis=1)
    h = xp.take_along_axis(h_candidates, best[:, None], axis=1)[:, 0]
    return h, best + 1


def digitize(data, xp=np, center=None, scale=None):
    """Scale data to non-negative integer counts for event statistics.

    ``rint(clip((x - median) / MAD * 3, 0, inf))`` — reference
    ``clean.py:183-189``.  Deviations from the reference, on purpose:
    integer input passes through (the reference's ``isinstance(data,
    np.int)`` check could never fire for arrays), and the MAD is a *global*
    scalar rather than statsmodels' silent per-column axis-0 reduction.

    ``center``/``scale`` override the internally computed median/MAD —
    for callers whose array carries rows that must not contaminate the
    stats (the DM-sharded plane's SPMD pad rows,
    :meth:`~pulsarutils_tpu.parallel.sharded_plane.ShardedPlane.h_curve`).
    """
    data = xp.asarray(data)
    if np.issubdtype(np.dtype(str(data.dtype)), np.integer):
        return data
    std = mad(data, xp=xp) if scale is None else scale
    med = xp.median(data) if center is None else center
    scaled = (data - med) / std * 3.0
    scaled = xp.where(scaled < 0, 0.0, scaled)
    return xp.rint(scaled).astype(xp.int32)
