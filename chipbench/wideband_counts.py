"""Bytes of the kernels of a wide-band search whose sweeps run no fused
head, from the cell's shapes alone (``kernel_counts.py``'s conventions:
what the algorithm needs for one chunk, never what an implementation
moves).

Parkes' ultra-wideband receiver (3,328 channels, 704-4,032 MHz, 2 bits)
is searched from its resident packed bytes: the FDMT pads the band to
4,096 channels and walks twelve merge levels, the native tier in time
tiles with a halo, and a hit's exact rescore dedisperses thousands of
rows on every cleaned tile.  The **work** is none of that: one read of
the cleaned chunk of the real channels by the sweeps, one read of the
packed chunk and one write of its float32 form by the clean, one read of
the cleaned tier and one write of its rows by the rescore.  Padded
channels, halos swept twice, tiles cleaned again for a rescore and a tile
read once a row bucket are the implementation's, and count as time.  No
operations are counted: each share is of the memory roof alone, so it
reads the same whatever arranges the adds, and none can pass 100 %.
"""

from __future__ import annotations

from . import tile_counts_fulldm


def sweep_counts(nchan, nsamples, dmmin, dmmax, fbottom, bandwidth, tsamp,
                 itemsize=4):
    """Every sweep of one chunk, all tiers and tiles.  bytes: the cleaned
    ``nchan x nsamples`` float32 chunk of the **real** channels read once
    (``tile_counts_fulldm.tiled_sweep_counts``, whose ``nchan`` is the
    band's, never the tree's padded width); the deeper tiers read sums of
    the same samples."""
    return tile_counts_fulldm.tiled_sweep_counts(
        nchan, nsamples, dmmin, dmmax, fbottom, bandwidth, tsamp,
        itemsize=itemsize)


def tile_clean_counts(nchan, nsamples, dmmin, dmmax, fbottom, bandwidth,
                      tsamp, nbits=2, itemsize=4):
    """The clean of one chunk from its packed bytes.  bytes: the chunk as
    the file stores it read once (``nbits / 8`` of a byte a sample) and
    its cleaned float32 form written once; the chunk-wide moments
    (``tile_counts_fulldm.chunk_stats_counts``) are another kernel's."""
    samples = nchan * nsamples
    return {"bytes": samples * nbits // 8 + samples * itemsize, "flops": 0}


def rescore_counts(nchan, nsamples, dmmin, dmmax, fbottom, bandwidth, tsamp,
                   rows=0, itemsize=4):
    """The exact rescore of ``rows`` trial rows of the native tier.  bytes:
    the tier's cleaned tiles, which together hold every sample of every
    row's track, read once (``nchan x nsamples`` float32), and the
    dedispersed rows written once (``rows x nsamples``); scoring them in
    fast memory would save the write, so this is the generous count."""
    return {"bytes": (nchan + rows) * nsamples * itemsize, "flops": 0}
