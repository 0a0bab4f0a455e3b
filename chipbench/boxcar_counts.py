"""Bytes of the coarse scorer of a smearing-tiered search, from the cell's
shapes alone (``kernel_counts.py``'s conventions: what the algorithm needs
for one chunk, never what an implementation moves).

Whatever the boxcar ladder is, the scorer has to read each tier's coarse
plane once: one row per trial of the tier (``tier_counts.tier_delay_rows``)
times the tier's samples, float32.  Block sums, their maxima and the
certificate's captures can all be accumulated while a row passes through
fast memory, so a longer ladder adds operations (counted: one add per
sample for each doubling) and no bytes.
"""

from __future__ import annotations

from . import tier_counts

DEFAULT_WIDEST = 8


def score_counts(nchan, nsamples, dmmin, dmmax, fbottom, bandwidth, tsamp,
                 itemsize=4, boxcar_max=4096):
    """The scoring of one chunk's coarse planes, all tiers.  bytes: every
    tier's plane (trial rows x the tier's samples) read once.  adds: per
    level above the first, one add for each two samples of the level
    below, so under ``rows * samples`` a tier however long its ladder."""
    nbytes = adds = rows_out = 0
    for factor, n_first, n_last in tier_counts.tier_delay_rows(
            nchan, dmmin, dmmax, fbottom, bandwidth, tsamp):
        rows = int(round(n_last - n_first)) + 1
        samples = nsamples // factor
        widest = max(DEFAULT_WIDEST, int(boxcar_max) // factor)
        nbytes += rows * samples * itemsize
        adds += sum(rows * (samples >> j)
                    for j in range(1, widest.bit_length()))
        rows_out += rows
    return {"bytes": nbytes, "flops": adds, "rows_out": rows_out}
