"""Bytes of the kernels of a search in time tiles, from the cell's shapes
alone (``kernel_counts.py``'s conventions: what the algorithm needs for
one chunk, never what an implementation moves).

A chunk too long for the device is searched from its resident packed
bytes a time tile at a time (``pulsarutils_tpu/pipeline/time_tiles.py``).
The **work** is the untiled search's: the chunk's moments from its bytes,
one read of the cleaned chunk by the sweeps, one read of every tier's
coarse plane by the scorer.  Halos swept twice, tiles cleaned again for
each tier and deep tiers' arrays laid from the bytes are the
implementation's, and count as time, not as work.
"""

from __future__ import annotations

from . import boxcar_counts


def chunk_stats_counts(nchan, nsamples, dmmin, dmmax, fbottom, bandwidth,
                       tsamp, nbits=8):
    """The chunk-wide moments of the clean from the packed chunk.  bytes:
    the chunk as the file stores it, read twice: the light curve takes
    every channel of a sample, and the per-channel means take the light
    curve smoothed over the whole chunk, so one pass cannot give both.
    adds: one per sample for each pass, and a scaling in the second."""
    samples = nchan * nsamples
    return {"bytes": 2 * samples * nbits // 8, "flops": 3 * samples}


def tiled_sweep_counts(nchan, nsamples, dmmin, dmmax, fbottom, bandwidth,
                       tsamp, itemsize=4):
    """Every sweep of one chunk, all tiers and tiles.  bytes: the cleaned
    ``nchan x nsamples`` float32 chunk read once (``tier_counts.
    tiered_fdmt_counts``' bytes).  No operations are counted: the share is
    of the memory roof alone, so it reads the same whatever tree the adds
    are arranged in."""
    return {"bytes": nchan * nsamples * itemsize, "flops": 0}


def tiled_score_counts(nchan, nsamples, dmmin, dmmax, fbottom, bandwidth,
                       tsamp, itemsize=4, boxcar_max=2048):
    """The scoring of one chunk's coarse planes: ``boxcar_counts.
    score_counts`` at MeerTRAP's ladder (``--boxcar-max 2048``): every
    tier's plane, trial rows x the tier's samples, read once."""
    return boxcar_counts.score_counts(
        nchan, nsamples, dmmin, dmmax, fbottom, bandwidth, tsamp,
        itemsize=itemsize, boxcar_max=boxcar_max)
