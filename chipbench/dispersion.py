"""The benchmark's own copy of the dispersion arithmetic.

Kept under ``chipbench/`` so that no later change to the program can move
the yardstick: the generator injects along these tracks and the reference
dedisperses along them.  The conventions are the ones the upstream
``pulsarutils`` fixed (SURVEY.md): delay ``4149 * DM / f^2`` seconds (f in
MHz), a channel's frequency is its LOWER edge counted from the bottom of
the band, delays are taken relative to the band centre, a shift is
``rint(delay // tsamp)``, and the trial grid has one trial per sample of
band-crossing delay.
"""

from __future__ import annotations

import numpy as np

DM_DELAY_CONST = 4149.0


def band_edges(fch1, foff, nchan):
    """``(fbottom, bandwidth)`` in MHz from SIGPROC's first-channel centre
    and channel offset (either sign)."""
    centres = fch1 + np.arange(nchan) * foff
    return float(centres.min() - abs(foff) / 2), abs(foff) * nchan


def channel_shifts(dm, nchan, fbottom, bandwidth, tsamp):
    """Integer sample delay of every channel (ascending frequency order)
    at ``dm``, relative to the band centre; ``dm`` may be an array of
    trials, giving ``(ndm, nchan)``."""
    dm = np.asarray(dm, dtype=np.float64)
    freq = fbottom + np.arange(nchan) * (bandwidth / nchan)
    centre = fbottom + bandwidth / 2.0
    delay = (DM_DELAY_CONST * dm[..., None]
             * (freq ** (-2.0) - centre ** (-2.0)))
    return np.rint(delay // tsamp).astype(np.int64)


def band_delay_samples(dm, fbottom, bandwidth, tsamp):
    """Delay across the whole band at ``dm``, in samples (a float)."""
    f0, f1 = float(fbottom), float(fbottom) + float(bandwidth)
    return (DM_DELAY_CONST * dm * f0 ** (-2.0)
            - DM_DELAY_CONST * dm * f1 ** (-2.0)) / tsamp


def trial_dms(dmmin, dmmax, fbottom, bandwidth, tsamp):
    """The trial grid: band delays ``arange(n(dmmin), n(dmmax) + 1)``
    samples, turned back into DM."""
    f0, f1 = float(fbottom), float(fbottom) + float(bandwidth)
    n = np.arange(band_delay_samples(float(dmmin), f0, bandwidth, tsamp),
                  band_delay_samples(float(dmmax), f0, bandwidth, tsamp) + 1)
    return n * tsamp / DM_DELAY_CONST / (f0 ** -2.0 - f1 ** -2.0)
