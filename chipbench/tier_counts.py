"""Operations and bytes of a smearing-tiered search's kernels, from the
cell's shapes alone (``kernel_counts.py``'s conventions: what the
algorithm needs for one chunk, never what an implementation moves).

The tier rule is restated here in band delays: tier ``k`` works at ``2^k *
tsamp`` and ends where the intra-channel smearing at the band centre,
``8300 * (bandwidth / nchan) / centre^3`` seconds per DM unit, reaches
that sample time; the first tier starts at ``dmmin``, every later tier at
the first integer band delay of its own sample time above its lower edge;
every tier but the last stops at the last integer band delay up to its
upper edge, the last at the first at or past ``dmmax``.
"""

from __future__ import annotations

import math

from . import dispersion, kernel_counts


def tier_delay_rows(nchan, dmmin, dmmax, fbottom, bandwidth, tsamp):
    """``[(factor, n_first, n_last)]``: each tier's downsampling factor and
    its lowest and highest band delay, in samples of its own sample time
    (the first may be fractional, as the flat grid's is)."""
    f0 = float(fbottom)
    smear = 8300.0 * (bandwidth / nchan) / (f0 + bandwidth / 2.0) ** 3
    k = 0
    while (2 ** k) * tsamp / smear <= dmmin:
        k += 1
    tiers, lo = [], float(dmmin)
    while True:
        ts = (2 ** k) * tsamp
        last = ts / smear >= dmmax
        hi = float(dmmax) if last else ts / smear
        n_lo = dispersion.band_delay_samples(lo, f0, bandwidth, ts)
        n_hi = dispersion.band_delay_samples(hi, f0, bandwidth, ts)
        if tiers:
            n_lo = math.floor(n_lo) + 1.0
        steps = (math.ceil(n_hi - n_lo) if last
                 else math.floor(n_hi - n_lo))
        tiers.append((2 ** k, n_lo, n_lo + steps))
        if last:
            return tiers
        lo, k = hi, k + 1


def tiered_fdmt_counts(nchan, nsamples, dmmin, dmmax, fbottom, bandwidth,
                       tsamp, itemsize=4):
    """The tree dedispersions of one ``nchan x nsamples`` float32 chunk, one
    per tier.  bytes: the native chunk read once.  adds:
    ``kernel_counts.fdmt_counts``' adds summed over the tiers, each at its
    own sample count and over its own delay rows."""
    unit = dispersion.band_delay_samples(1.0, fbottom, bandwidth, tsamp)
    adds = rows = 0
    for factor, n_first, n_last in tier_delay_rows(
            nchan, dmmin, dmmax, fbottom, bandwidth, tsamp):
        # a quarter of a row inside the two ends: fdmt_counts floors the
        # lower and ceils the upper band delay, and a DM made from an
        # integer delay comes back a rounding error to either side of it
        per_dm = unit / factor
        c = kernel_counts.fdmt_counts(
            nchan, nsamples // factor, (n_first + 0.25) / per_dm,
            (n_last - 0.25) / per_dm, fbottom, bandwidth, tsamp * factor,
            itemsize=itemsize)
        adds += c["flops"]
        rows += c["rows_out"]
    return {"bytes": nchan * nsamples * itemsize, "flops": adds,
            "rows_out": rows}


def tier_downsample_counts(nchan, nsamples, dmmin, dmmax, fbottom, bandwidth,
                           tsamp, itemsize=4):
    """The downsample chain of one cleaned chunk.  bytes: the native chunk
    read once and every downsampled copy written once (a later copy can be
    made from the one before while that is still in fast memory).  adds:
    one per sample written times the doubling it took."""
    written = adds = 0
    have = 1
    for factor, _, _ in tier_delay_rows(nchan, dmmin, dmmax, fbottom,
                                        bandwidth, tsamp):
        if factor > 1:
            written += nchan * (nsamples // factor)
            adds += nchan * (nsamples // factor) * (factor // have - 1)
            have = factor
    return {"bytes": (nchan * nsamples + written) * itemsize, "flops": adds}
