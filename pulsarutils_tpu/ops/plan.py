"""Dedispersion plan math: per-channel delays, trial-DM grids, smearing.

These are the scientific correctness anchors of the whole framework.  They
reproduce — exactly, including the rounding conventions — the behaviour of
the reference implementation:

* per-channel shifts: reference ``pulsarutils/dedispersion.py:125-139``
* differential band delay: reference ``pulsarutils/dedispersion.py:142-146``
* trial-DM plan (one trial per integer sample of differential band delay):
  reference ``pulsarutils/dedispersion.py:149-171``
* shift normalisation into ``[0, N)``: reference
  ``pulsarutils/dedispersion.py:101-122``
* intra-channel DM smearing: reference ``pulsarutils/clean.py:272-274``

Every function is written against a pluggable array namespace (``xp``) so the
identical formula runs under NumPy on the host (static plan construction) and
under ``jax.numpy`` inside jitted/sharded kernels (on-device shift
computation, which keeps the (ndm, nchan) shift table out of host->device
transfers).

Sign/rounding conventions that the S/N recovery depends on (pinned by tests):

* delays are measured **relative to the band-centre frequency**, so shifts are
  positive below centre and negative above;
* a shift is ``rint(delay // sample_time)`` — float floor-division first,
  then round-to-nearest-even (reference ``dedispersion.py:137``);
* ``normalize_shifts`` rounds with ``rint`` then wraps into ``[0, N)``
  (reference ``dedispersion.py:101-122``).
"""

from __future__ import annotations

import dataclasses

import numpy as np

#: Dispersion constant in s MHz^2 cm^3 pc^-1 (reference uses the rounded
#: value 4149; ``pulsarutils/dedispersion.py:130,136,144-145``).
DM_DELAY_CONST = 4149.0

#: Intra-channel smearing constant (seconds, MHz): ``8300 * DM * df / f^3``
#: (reference ``pulsarutils/clean.py:272-274``).
DM_SMEARING_CONST = 8300.0


def dm_delay(dm, freq, xp=np):
    """Cold-plasma dispersion delay (seconds) at ``freq`` MHz for ``dm``."""
    return DM_DELAY_CONST * dm * freq ** (-2.0)


def delta_delay(dm, start_freq, stop_freq, xp=np):
    """Differential dispersion delay (s) between two frequencies (MHz).

    Reference: ``pulsarutils/dedispersion.py:142-146``.
    """
    return dm_delay(dm, start_freq, xp=xp) - dm_delay(dm, stop_freq, xp=xp)


def dm_broadening(dm, freq, df, xp=np):
    """Intra-channel DM smearing time (s) in a channel of width ``df`` MHz.

    Reference: ``pulsarutils/clean.py:272-274``.  Used by the streaming
    driver to pick the automatic resampling factor.
    """
    return DM_SMEARING_CONST * dm * df / freq ** 3


def channel_frequencies(nchan, start_freq, bandwidth, xp=np):
    """Lower-edge frequency of each channel (MHz).

    The reference indexes channels from the *bottom* of the band with the
    channel's lower edge as its frequency (``dedispersion.py:127,135``).
    """
    dfreq = bandwidth / nchan
    return start_freq + xp.arange(nchan) * dfreq


def dedispersion_shifts(nchan, dm, start_freq, bandwidth, sample_time, xp=np):
    """Integer per-channel sample delays (as a float array) for one DM.

    ``shift[i] = rint((delay_i - delay_center) // sample_time)`` where
    ``delay_f = 4149 * dm / f^2`` and the reference point is the band-centre
    frequency.  Reference: ``pulsarutils/dedispersion.py:125-139`` (note the
    float floor-division *before* ``rint`` — kept bit-identical here).

    Returns a float array of shape ``(nchan,)`` holding integer values,
    matching the reference's return type.
    """
    center_freq = start_freq + bandwidth / 2.0
    ref_delay = dm_delay(dm, center_freq, xp=xp)
    chan_freq = channel_frequencies(nchan, start_freq, bandwidth, xp=xp)
    delay = DM_DELAY_CONST * dm * chan_freq ** (-2.0) - ref_delay
    return xp.rint(delay // sample_time)


def dedispersion_shifts_batch(trial_dms, nchan, start_freq, bandwidth,
                              sample_time, xp=np):
    """Per-channel shifts for a whole trial-DM grid at once.

    Vectorised form of :func:`dedispersion_shifts` over the trial axis —
    the batched equivalent of the per-trial call inside the reference sweep
    (``pulsarutils/dedispersion.py:183``).  Returns ``(ndm, nchan)`` floats
    holding integer values; bit-identical per row to the scalar function.
    """
    trial_dms = xp.asarray(trial_dms)
    center_freq = start_freq + bandwidth / 2.0
    chan_freq = channel_frequencies(nchan, start_freq, bandwidth, xp=xp)
    # delay[d, c] relative to band centre
    delay = (DM_DELAY_CONST * trial_dms[:, None]
             * (chan_freq[None, :] ** (-2.0) - center_freq ** (-2.0)))
    return xp.rint(delay // sample_time)


def normalize_shifts(shifts, n, xp=np):
    """Round shifts and wrap them into ``[0, n)`` as ``int32``.

    Vectorised re-statement of the reference's rint + while-loop wrap
    (``pulsarutils/dedispersion.py:101-122``): for any finite shift,
    repeatedly adding/subtracting ``n`` is exactly the mathematical modulo,
    which both NumPy's and JAX's ``%`` implement for the int32 values
    produced by ``rint``.

    >>> normalize_shifts(np.array([-1.2, 0.0, 3.6, 10.0]), 8)
    array([7, 0, 4, 2], dtype=int32)
    """
    shifts = xp.asarray(shifts)
    # float modulo is exact for the integer-valued magnitudes produced here
    # (|shift| < 2**24 even in float32), and avoids int64 on accelerators
    wrapped = xp.rint(shifts) % n
    return wrapped.astype(xp.int32)


def dedispersion_plan(nchan, dmmin, dmmax, start_freq, bandwidth, sample_time,
                      xp=np):
    """Trial-DM grid: one trial per integer sample of band-crossing delay.

    The spacing criterion of the reference (``dedispersion.py:149-171``):
    the differential delay across the full band, in samples, steps by one
    between consecutive trials.  ``trial_N = arange(min_N, max_N + 1)`` is
    then inverted to DM.  (The reference's ``np.float`` calls — removed from
    NumPy >= 1.24 — are simply dropped; values are already floats.)

    The endpoints bracket the requested range and consecutive trials differ
    by one sample of band delay:

    >>> dms = dedispersion_plan(64, 100, 200.0, 1200.0, 200.0, 0.0005)
    >>> bool(dms[0] <= 100.5) and bool(dms[-1] >= 199.0)
    True
    >>> d = (delta_delay(dms[1], 1200.0, 1400.0)
    ...      - delta_delay(dms[0], 1200.0, 1400.0)) / 0.0005
    >>> round(float(d), 6)
    1.0
    """
    stop_freq = start_freq + bandwidth
    f0 = float(start_freq)
    f1 = float(stop_freq)

    max_n = delta_delay(float(dmmax), f0, f1) / sample_time
    min_n = delta_delay(float(dmmin), f0, f1) / sample_time

    trial_n = xp.arange(min_n, max_n + 1)
    trial_dm = trial_n * sample_time / DM_DELAY_CONST / (f0 ** -2.0 - f1 ** -2.0)
    return trial_dm


@dataclasses.dataclass(frozen=True)
class DMTier:
    """One tier of a smearing-tiered search (:func:`dm_tier_plan`)."""
    downsample: int        # time-rebin factor 2^k applied before the search
    sample_time: float     # downsample x the plan's sample time
    dm_lo: float           # DM interval this tier answers for
    dm_hi: float
    trial_dms: np.ndarray  # its trial grid (float64)
    # its boxcar ladder, in its own samples (ops/search.py:boxcar_ladder)
    windows: tuple = (1, 2, 4, 8)


def dm_tier_plan(nchan, dmmin, dmmax, start_freq, bandwidth, sample_time,
                 foff, boxcar_max=None):
    """The tiers of a smearing-tiered search of ``dmmin..dmmax``.

    Tier ``k`` works at ``2^k * sample_time`` and **ends** at the DM
    where the intra-channel smearing at the band centre,
    ``dm_broadening(dm, start_freq + bandwidth / 2, |foff|)``, reaches
    that sample time: the sample time is doubled where the smearing
    reaches one sample.  (The reference's ``plan_chunks`` rule takes the
    bottom of the band and a tenth of the smearing, for its single
    resampling factor; this is a departure, docs/reference_parity.md.)

    The first tier is :func:`dedispersion_plan`'s grid from ``dmmin``;
    every later tier searches the integer band delays ``n`` of its own
    sample time above ``N_k(lower edge)``, ``DM_n`` by the plan's own
    expression.  Every tier but the last keeps the trials whose band
    delay is at most ``N_k(upper edge)``; the last runs to the first
    trial at or past ``dmmax`` as :func:`dedispersion_plan` does, so a
    plan of one tier is that function's grid to the bit.  Since
    ``n_(k+1) = n_k / 2`` names the same DM, no DM is searched twice.
    Tiers below ``dmmin`` are left out, so the first tier's
    ``downsample`` need not be 1.

    Every tier carries its boxcar ladder, in its own samples
    (:func:`~pulsarutils_tpu.ops.search.boxcar_ladder`): the default
    four, or with ``boxcar_max`` (samples of the plan's sample time)
    ``1 .. max(8, boxcar_max / 2^k)``, so that every tier reaches the
    same width.

    >>> tiers = dm_tier_plan(1024, 0.0, 1000.0, 1182.0, 400.0, 64e-6, 0.390625)
    >>> [(t.downsample, len(t.trial_dms)) for t in tiers]
    [(1, 1069), (2, 534), (4, 534), (8, 534), (16, 534), (32, 107)]
    >>> round(tiers[0].dm_hi, 2)
    52.1
    >>> [len(t.windows) for t in dm_tier_plan(
    ...     1024, 0.0, 1000.0, 1182.0, 400.0, 64e-6, 0.390625, 4096)]
    [13, 12, 11, 10, 9, 8]
    """
    from .search import boxcar_ladder

    f0 = float(start_freq)
    f1 = f0 + float(bandwidth)
    dmmin, dmmax = float(dmmin), float(dmmax)
    smear = dm_broadening(1.0, f0 + float(bandwidth) / 2.0, abs(float(foff)))
    unit = f0 ** -2.0 - f1 ** -2.0

    def edge(k):  # DM at which the smearing reaches 2^k samples
        return (2 ** k) * sample_time / smear

    k = 0
    while edge(k) <= dmmin:
        k += 1
    tiers = []
    lo = dmmin
    while True:
        tsamp_k = (2 ** k) * sample_time
        last = edge(k) >= dmmax
        hi = dmmax if last else edge(k)
        min_n = delta_delay(lo, f0, f1) / tsamp_k
        max_n = delta_delay(hi, f0, f1) / tsamp_k
        if tiers:
            min_n = np.floor(min_n) + 1.0
        # the last tier's is dedispersion_plan's own arange
        trial_n = (np.arange(min_n, max_n + 1) if last
                   else min_n + np.arange(np.floor(max_n - min_n) + 1))
        trial_dm = trial_n * tsamp_k / DM_DELAY_CONST / unit
        tiers.append(DMTier(downsample=2 ** k, sample_time=tsamp_k,
                            dm_lo=lo, dm_hi=hi,
                            trial_dms=np.asarray(trial_dm, np.float64),
                            windows=boxcar_ladder(boxcar_max, 2 ** k)))
        if last:
            return tiers
        lo = hi
        k += 1


def dmmax_for_trials(dmmin, n_trials, start_freq, bandwidth, sample_time):
    """DM upper bound whose canonical integer-band-delay grid spans exactly
    ``n_trials`` starting at ``dmmin``.

    The inverse of :func:`pulsarutils_tpu.ops.fdmt.fdmt_trial_dms`'s grid
    sizing: trials sit at integer samples of band-crossing delay, the first
    at ``ceil(delta_delay(dmmin) / sample_time)``.  A half-sample margin is
    added so float rounding cannot drop the last trial.

    >>> dmmax = dmmax_for_trials(300.0, 512, 1200.0, 200.0, 0.0005)
    >>> from pulsarutils_tpu.ops.fdmt import fdmt_trial_dms
    >>> len(fdmt_trial_dms(1024, 300.0, dmmax, 1200.0, 200.0, 0.0005)[0])
    512
    """
    f0 = float(start_freq)
    f1 = f0 + float(bandwidth)
    unit = delta_delay(1.0, f0, f1)  # band-delay seconds per DM unit
    n_lo = int(np.ceil(delta_delay(float(dmmin), f0, f1) / sample_time))
    return (n_lo + n_trials - 0.5) * sample_time / unit


def plan_size(nchan, dmmin, dmmax, start_freq, bandwidth, sample_time):
    """Number of trials the plan will contain, computed without allocating.

    Useful for static-shape padding decisions before jit tracing.
    """
    stop_freq = start_freq + bandwidth
    max_n = delta_delay(float(dmmax), start_freq, stop_freq) / sample_time
    min_n = delta_delay(float(dmmin), start_freq, stop_freq) / sample_time
    # len(np.arange(a, b)) == ceil(b - a) for b > a
    return int(np.ceil(max_n + 1 - min_n))
